"""FilterSpec — AND-composed attribute predicates over the corpus.

A spec is a tuple of predicates: equality / inclusive range / IN-set over
named integer attribute columns (categorical fields are integer-coded by the
caller, ``attributes.encode_categorical``), and containment over a tag-set
field (the row's set of tags holds every tag of the predicate). Specs are
frozen and hashable so the serving engine can batch requests by filter
hash. ``evaluate`` handles the column predicates with operator-only
arithmetic that works identically on numpy (host-side mask compilation) and
jnp (device-side evaluation) column matrices; containment is answered by
the store's inverted index (``AttributeStore.mask``).

Compose with ``&``::

    spec = FilterSpec.eq("category", 3) & FilterSpec.range("price", 0, 49)
    spec = FilterSpec.contains("tags", (17, 4096)) & FilterSpec.eq("year", 9)
"""
from __future__ import annotations

from dataclasses import dataclass
from typing import Optional, Tuple, Union

import numpy as np


@dataclass(frozen=True)
class Eq:
    """``field == value``."""
    field: str
    value: int


@dataclass(frozen=True)
class Range:
    """``lo <= field <= hi`` (inclusive; ``None`` leaves a side open)."""
    field: str
    lo: Optional[int] = None
    hi: Optional[int] = None


@dataclass(frozen=True)
class In:
    """``field in values``."""
    field: str
    values: Tuple[int, ...]

    def __post_init__(self):
        object.__setattr__(self, "values",
                           tuple(int(v) for v in self.values))


@dataclass(frozen=True)
class Contains:
    """The row's tag set in ``field`` holds every tag of ``tags``. Tags are
    normalised to a sorted tuple of distinct ints, so ``{3, 7}`` and
    ``(7, 3, 3)`` are one predicate (one plan-cache key)."""
    field: str
    tags: Tuple[int, ...]

    def __post_init__(self):
        object.__setattr__(self, "tags",
                           tuple(sorted({int(t) for t in self.tags})))


Predicate = Union[Eq, Range, In, Contains]


def _eval_predicate(p: Predicate, col, xp):
    if isinstance(p, Eq):
        return col == p.value
    if isinstance(p, Range):
        m = xp.ones(col.shape, bool)
        if p.lo is not None:
            m = m & (col >= p.lo)
        if p.hi is not None:
            m = m & (col <= p.hi)
        return m
    if isinstance(p, In):
        if not p.values:
            return xp.zeros(col.shape, bool)
        vals = xp.asarray(p.values)
        return (col[:, None] == vals[None, :]).any(axis=1)
    raise TypeError(f"unknown predicate {type(p).__name__}")


@dataclass(frozen=True)
class FilterSpec:
    """AND-composition of predicates. The empty spec passes every node."""
    predicates: Tuple[Predicate, ...] = ()

    def __post_init__(self):
        object.__setattr__(self, "predicates", tuple(self.predicates))

    # --------------------------------------------------------- constructors
    @staticmethod
    def eq(field: str, value: int) -> "FilterSpec":
        return FilterSpec((Eq(field, int(value)),))

    @staticmethod
    def range(field: str, lo: Optional[int] = None,
              hi: Optional[int] = None) -> "FilterSpec":
        return FilterSpec((Range(field, lo, hi),))

    @staticmethod
    def isin(field: str, values) -> "FilterSpec":
        return FilterSpec((In(field, tuple(values)),))

    @staticmethod
    def contains(field: str, tags) -> "FilterSpec":
        """Rows whose tag set in ``field`` holds every tag of ``tags``."""
        return FilterSpec((Contains(field, tuple(tags)),))

    def __and__(self, other: "FilterSpec") -> "FilterSpec":
        return FilterSpec(self.predicates + other.predicates)

    # ----------------------------------------------------------- evaluation
    @property
    def is_all(self) -> bool:
        return not self.predicates

    def fields(self) -> Tuple[str, ...]:
        return tuple(p.field for p in self.predicates)

    def evaluate(self, values, fields: Tuple[str, ...], xp=np):
        """(N, F) column matrix -> (N,) boolean pass mask."""
        mask = xp.ones(values.shape[0], bool)
        for p in self.predicates:
            if isinstance(p, Contains):
                raise TypeError(
                    f"containment on {p.field!r} is answered by the "
                    "attribute store's tag index (AttributeStore.mask), "
                    "not by a column")
            try:
                col = values[:, fields.index(p.field)]
            except ValueError:
                raise KeyError(
                    f"filter references unknown attribute {p.field!r}; "
                    f"store has {fields}"
                ) from None
            mask = mask & _eval_predicate(p, col, xp)
        return mask


ALL = FilterSpec()
