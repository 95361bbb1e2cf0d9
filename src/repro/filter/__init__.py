"""Filtered ANN subsystem: attribute store (scalar columns and tag-set
fields with posting lists), FilterSpec predicates (Eq / Range / In over
columns, Contains over tag sets), selectivity-adaptive filtered traversal,
and the per-tile bitmap plumbing for near-storage predicate pushdown
(billed by ``nand.simulator``)."""
from repro.filter.attributes import (
    AttributeStore,
    TagField,
    bitmap_popcount,
    encode_categorical,
    pack_bitmap,
    random_attributes,
    unpack_bitmap,
)
from repro.filter.spec import ALL, Contains, Eq, FilterSpec, In, Range
from repro.filter.traversal import (
    FilteredSearchResult,
    adapt_search_cfg,
    filtered_search,
    scan_search,
    tile_node_masks,
)


def attach_attributes(index, store: AttributeStore) -> AttributeStore:
    """Attach a per-node attribute store to a built ``ProximaIndex``. Rows
    must be keyed by the index's CURRENT (reordered) internal ids — permute
    a corpus-order store through ``index.reordering`` first::

        store = store.permuted(index.reordering.inv)    # if reordered

    Returns the store for chaining."""
    if len(store) != index.dataset.num_base:
        raise ValueError(
            f"attribute store has {len(store)} rows, index has "
            f"{index.dataset.num_base} vertices"
        )
    index.attributes = store
    return store


__all__ = [
    "ALL",
    "AttributeStore",
    "Contains",
    "Eq",
    "FilterSpec",
    "FilteredSearchResult",
    "In",
    "Range",
    "TagField",
    "adapt_search_cfg",
    "attach_attributes",
    "bitmap_popcount",
    "encode_categorical",
    "filtered_search",
    "pack_bitmap",
    "random_attributes",
    "scan_search",
    "tile_node_masks",
    "unpack_bitmap",
]
