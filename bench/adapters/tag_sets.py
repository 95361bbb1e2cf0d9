"""The adapter of configurations with ``labels`` (``bench/catalog.py``):
the program's attribute store holds the base rows' tags as one tag-set
field, ``tags`` (CSR with posting lists, memory in proportion to the tags
stored whatever the vocabulary), and a request's predicate is the
program's containment filter over it: the row's tags hold every tag of
the predicate."""
from __future__ import annotations

import numpy as np

FIELD = "tags"


def attach(index, offsets: np.ndarray, tags: np.ndarray,
           to_corpus: np.ndarray) -> None:
    """Hand the built index the tag sets, re-keyed to its row order (row i
    of the index is corpus row ``to_corpus[i]``)."""
    from repro.filter import AttributeStore, attach_attributes

    store = AttributeStore.from_columns({}, {FIELD: (offsets, tags)})
    attach_attributes(index, store.permuted(to_corpus))


def request_filter(tags: tuple):
    from repro.filter import FilterSpec

    return FilterSpec.contains(FIELD, tags)
