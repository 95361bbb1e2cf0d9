"""Proxima core: the paper's algorithmic contribution (Algorithm 1 + §III/§IV-E
data-layout optimizations) as composable JAX modules."""
from repro.core.dataset import (
    ArraySegmentSource, Dataset, SyntheticSegmentSource, exact_knn,
    exact_knn_stream, make_dataset, recall_at_k, recall_hits,
    recall_hits_per_query,
)
from repro.core.index import ProximaIndex, build_index, build_index_monolithic
from repro.core.segmented import (
    IndexSegment, SegmentedIndex, build_segmented,
)
from repro.core.search import (
    Corpus, SearchResult, SearchState, finalize_search, graph_search,
    graph_search_advance, graph_search_step, graph_search_stepped,
    init_search_state, search, search_reference, search_state_active,
)

__all__ = [
    "graph_search",
    "Dataset",
    "exact_knn",
    "make_dataset",
    "recall_at_k",
    "recall_hits",
    "recall_hits_per_query",
    "ProximaIndex",
    "build_index",
    "build_index_monolithic",
    "build_segmented",
    "SegmentedIndex",
    "IndexSegment",
    "ArraySegmentSource",
    "SyntheticSegmentSource",
    "exact_knn_stream",
    "Corpus",
    "SearchResult",
    "SearchState",
    "init_search_state",
    "graph_search_step",
    "graph_search_advance",
    "graph_search_stepped",
    "finalize_search",
    "search_state_active",
    "search",
    "search_reference",
]
