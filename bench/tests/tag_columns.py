"""An adapter for configurations with ``labels`` and a small vocabulary:
the program's attribute store holds one int32 column per tag (1 where the
row has it), and a predicate is ``FilterSpec.eq(tag, 1)`` of each of its
tags, joined with ``&``. A store of N x vocabulary int32 values: for
vocabularies of about a thousand tags at most."""
from __future__ import annotations

import functools

import numpy as np


def column(tag: int) -> str:
    return f"tag{tag}"


def attach(index, offsets: np.ndarray, tags: np.ndarray,
           to_corpus: np.ndarray) -> None:
    from repro.filter import AttributeStore, attach_attributes

    n = len(offsets) - 1
    vocabulary = int(tags.max(initial=-1)) + 1
    values = np.zeros((n, vocabulary), np.int32)
    values[np.repeat(np.arange(n), np.diff(offsets)), tags] = 1
    attach_attributes(index, AttributeStore(
        [column(t) for t in range(vocabulary)], values[to_corpus]))


def request_filter(tags: tuple):
    from repro.filter import FilterSpec

    return functools.reduce(lambda a, b: a & b,
                            (FilterSpec.eq(column(t), 1) for t in tags))
