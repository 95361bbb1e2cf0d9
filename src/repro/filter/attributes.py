"""AttributeStore — per-node attribute fields + bitmap mask compilation.

A field is either a scalar column or a tag set. Scalar columns live as a
fixed-shape ``(N, F)`` int32 matrix (categorical fields are integer-coded),
the host-side twin of the attribute words the NAND layout keeps in each
node's page spare area (``FilterConfig.attr_bits`` per word, billed by
``nand.simulator``). A tag-set field (``TagField``) holds each row's set of
tags in CSR form with an inverted index beside it (tag -> ascending rows),
so a vocabulary of any size costs memory in proportion to the tags stored,
never rows x vocabulary. A ``FilterSpec`` compiles to a per-node boolean
mask: column predicates in one vectorized pass, containment by intersecting
posting lists. Masks pack into uint32 bitmaps — the wire/storage form the
tile-level zero-pass skip and the pushdown accounting use (32 nodes per
word, fixed shapes, jit-friendly).

The store is row-indexed; what the rows key (a frozen index's reordered
internal ids, or a ``MutableIndex``'s stable external ids) is the owner's
contract. ``append`` supports the streaming insert path with amortized
doubling growth, for stores of scalar columns only.
"""
from __future__ import annotations

from typing import Dict, Optional, Sequence, Tuple

import numpy as np

from repro.filter.spec import Contains, FilterSpec


def encode_categorical(values: Sequence) -> Tuple[np.ndarray, Dict]:
    """String/object categories -> (int32 codes, {category: code} vocab).
    Codes are assigned in first-appearance order (deterministic)."""
    vocab: Dict = {}
    codes = np.empty(len(values), np.int32)
    for i, v in enumerate(values):
        if v not in vocab:
            vocab[v] = len(vocab)
        codes[i] = vocab[v]
    return codes, vocab


def pack_bitmap(mask: np.ndarray) -> np.ndarray:
    """(N,) bool -> (ceil(N/32),) uint32, little-endian bit order."""
    bits = np.packbits(np.asarray(mask, bool), bitorder="little")
    pad = (-len(bits)) % 4
    if pad:
        bits = np.concatenate([bits, np.zeros(pad, np.uint8)])
    return bits.view("<u4")


def unpack_bitmap(bitmap: np.ndarray, n: int) -> np.ndarray:
    """(W,) uint32 -> (n,) bool."""
    bits = np.unpackbits(np.ascontiguousarray(bitmap).view(np.uint8),
                         bitorder="little")
    return bits[:n].astype(bool)


def bitmap_popcount(bitmap: np.ndarray) -> int:
    return int(np.unpackbits(
        np.ascontiguousarray(bitmap).view(np.uint8)).sum())


class TagField:
    """One tag-set field: row i holds ``tags[offsets[i]:offsets[i + 1]]``
    (CSR), and the inverted index keeps each tag's posting list (its rows,
    ascending). Built with one stable sort of the stored tags; memory is
    O(rows + stored tags), whatever the vocabulary."""

    def __init__(self, offsets, tags):
        offsets = np.asarray(offsets, np.int64)
        tags = np.asarray(tags, np.int32)
        if (offsets.ndim != 1 or len(offsets) < 1 or offsets[0] != 0
                or (np.diff(offsets) < 0).any()
                or offsets[-1] != len(tags)):
            raise ValueError("tag offsets must start at 0, never decrease "
                             "and end at the number of tags")
        if (tags < 0).any():
            raise ValueError("tags must be non-negative ints")
        self.offsets, self.tags = offsets, tags
        rows = np.repeat(np.arange(len(offsets) - 1, dtype=np.int32),
                         np.diff(offsets))
        order = np.argsort(tags, kind="stable")    # rows stay ascending
        by_tag, self._rows = tags[order], rows[order]
        same = by_tag[1:] == by_tag[:-1]
        if (same & (self._rows[1:] == self._rows[:-1])).any():
            raise ValueError("a row lists one tag twice")
        starts = np.flatnonzero(np.concatenate([[True], ~same])) \
            if len(tags) else np.zeros(0, np.int64)
        self._keys = by_tag[starts]                 # distinct tags, sorted
        self._starts = np.append(starts, len(tags)).astype(np.int64)

    def __len__(self) -> int:
        return len(self.offsets) - 1

    @property
    def nbytes(self) -> int:
        return sum(a.nbytes for a in (self.offsets, self.tags, self._rows,
                                      self._keys, self._starts))

    def postings(self, tag: int) -> np.ndarray:
        """The rows that hold ``tag``, ascending (empty if none does)."""
        if not 0 <= tag <= np.iinfo(np.int32).max:   # stored tags are int32
            return self._rows[:0]
        i = int(np.searchsorted(self._keys, tag))
        if i == len(self._keys) or self._keys[i] != tag:
            return self._rows[:0]
        return self._rows[self._starts[i]:self._starts[i + 1]]

    def mask(self, tags: Tuple[int, ...]) -> Tuple[np.ndarray, int]:
        """(pass mask over the rows, posting entries read) of "the row's set
        holds every tag of ``tags``": the posting lists are intersected
        shortest first, stopping once nothing is left; a list counts as
        read in full when the intersection takes it up."""
        lists = sorted((self.postings(t) for t in tags), key=len)
        mask = np.zeros(len(self), bool)
        if not lists:
            mask[:] = True
            return mask, 0
        rows, read = lists[0], len(lists[0])
        for other in lists[1:]:
            if not len(rows):
                break
            read += len(other)
            pos = np.minimum(np.searchsorted(other, rows), len(other) - 1)
            rows = rows[other[pos] == rows] if len(other) else rows[:0]
        mask[rows] = True
        return mask, read

    def permuted(self, perm: np.ndarray) -> "TagField":
        """Rows re-keyed through ``perm``: row i of the result is old row
        ``perm[i]``."""
        perm = np.asarray(perm)
        counts = np.diff(self.offsets)[perm]
        offsets = np.concatenate([[0], np.cumsum(counts)])
        first = np.repeat(self.offsets[perm] - offsets[:-1], counts)
        return TagField(offsets, self.tags[first + np.arange(offsets[-1])])


class AttributeStore:
    """Attribute table over corpus rows: int32 scalar columns (``fields``)
    and tag-set fields (``tag_fields``, name -> ``TagField``)."""

    def __init__(self, fields: Sequence[str], values: np.ndarray,
                 tag_fields: Optional[Dict[str, TagField]] = None):
        values = np.asarray(values, np.int32)
        if values.ndim != 2 or values.shape[1] != len(tuple(fields)):
            raise ValueError(
                f"values must be (N, {len(tuple(fields))}), got {values.shape}"
            )
        self.fields: Tuple[str, ...] = tuple(fields)
        self.tag_fields: Dict[str, TagField] = dict(tag_fields or {})
        clash = set(self.fields) & set(self.tag_fields)
        if clash:
            raise ValueError(f"fields {sorted(clash)} are both a column and "
                             "a tag set")
        for name, tf in self.tag_fields.items():
            if len(tf) != values.shape[0]:
                raise ValueError(f"tag field {name!r} has {len(tf)} rows, "
                                 f"the columns have {values.shape[0]}")
        self._values = np.ascontiguousarray(values)
        self._len = values.shape[0]

    # -------------------------------------------------------- constructors
    @classmethod
    def from_columns(cls, columns: Dict[str, np.ndarray],
                     tag_sets: Optional[Dict[str, tuple]] = None,
                     ) -> "AttributeStore":
        """Scalar ``columns`` (name -> (N,) ints) and ``tag_sets`` (name ->
        CSR ``(offsets, tags)``, ``offsets`` of length N + 1)."""
        tag_fields = {name: TagField(*csr)
                      for name, csr in (tag_sets or {}).items()}
        fields = tuple(columns)
        if fields:
            vals = np.stack(
                [np.asarray(columns[f], np.int32) for f in fields], axis=1)
        else:
            n = len(next(iter(tag_fields.values()))) if tag_fields else 0
            vals = np.zeros((n, 0), np.int32)
        return cls(fields, vals, tag_fields)

    def __len__(self) -> int:
        return self._len

    @property
    def values(self) -> np.ndarray:
        """(N, F) int32 view of the live rows."""
        return self._values[: self._len]

    @property
    def num_fields(self) -> int:
        return len(self.fields)

    @property
    def attr_bits(self) -> int:
        """Bits of one node's packed attribute word (spare-area footprint):
        32 a scalar column, and 32 a stored tag of each tag-set field,
        averaged over the rows and rounded up."""
        bits = 32 * self.num_fields
        for tf in self.tag_fields.values():
            bits += -(-32 * len(tf.tags) // max(len(tf), 1))
        return bits

    @property
    def nbytes(self) -> int:
        """Host bytes of every array the store holds."""
        return self._values.nbytes + sum(
            tf.nbytes for tf in self.tag_fields.values())

    def column(self, field: str) -> np.ndarray:
        return self.values[:, self.fields.index(field)]

    # ------------------------------------------------------------ mutation
    def coerce_row(self, row) -> list:
        """Validate one node's attributes (dict by field name, or a value
        sequence in column order) into the int column order — raises
        without touching the store, so callers can validate BEFORE other
        state mutates (e.g. MutableIndex.insert). A store with tag-set
        fields takes no new rows."""
        if self.tag_fields:
            raise NotImplementedError(
                f"the store holds tag-set fields {sorted(self.tag_fields)}; "
                "appending rows (inserting vectors) is not supported there")
        if isinstance(row, dict):
            unknown = set(row) - set(self.fields)
            if unknown:
                raise KeyError(f"unknown attribute fields {sorted(unknown)}")
            return [int(row.get(f, 0)) for f in self.fields]
        vals = [int(v) for v in row]
        if len(vals) != self.num_fields:
            raise ValueError(
                f"row has {len(vals)} values, store has "
                f"{self.num_fields} fields"
            )
        return vals

    def append(self, row) -> int:
        """Append one node's attributes; returns the new row id."""
        vals = self.coerce_row(row)
        if self._len == self._values.shape[0]:
            grown = np.zeros(
                (max(2 * self._len, 64), self.num_fields), np.int32
            )
            grown[: self._len] = self._values[: self._len]
            self._values = grown
        self._values[self._len] = vals
        self._len += 1
        return self._len - 1

    # ----------------------------------------------------- mask compilation
    def mask(self, spec: FilterSpec) -> np.ndarray:
        """Compile ``spec`` to a (N,) boolean pass mask."""
        return self.mask_and_reads(spec)[0]

    def mask_and_reads(self, spec: FilterSpec) -> Tuple[np.ndarray, int]:
        """(pass mask, posting entries read): column predicates in one
        vectorized pass over the columns, each containment predicate by
        intersecting its tags' posting lists."""
        columns = FilterSpec(tuple(p for p in spec.predicates
                                   if not isinstance(p, Contains)))
        mask = np.asarray(columns.evaluate(self.values, self.fields, np))
        read = 0
        for p in spec.predicates:
            if isinstance(p, Contains):
                tf = self.tag_fields.get(p.field)
                if tf is None:
                    raise KeyError(
                        f"filter references unknown tag field {p.field!r}; "
                        f"store has {tuple(self.tag_fields)}")
                m, r = tf.mask(p.tags)
                mask = mask & m
                read += r
        return mask, read

    def bitmap(self, spec: FilterSpec) -> np.ndarray:
        """Compile ``spec`` to the packed uint32 form (32 nodes per word)."""
        return pack_bitmap(self.mask(spec))

    def selectivity(self, spec: FilterSpec) -> float:
        """Exact passing fraction — the estimator is exact because the mask
        is one vectorized pass over a host-resident column matrix."""
        if self._len == 0:
            return 0.0
        return float(self.mask(spec).mean())

    # ------------------------------------------------------------- reindex
    def permuted(self, perm: np.ndarray) -> "AttributeStore":
        """Rows re-keyed through ``perm`` (e.g. the index's visit-frequency
        reordering: row i of the result is old row perm[i])."""
        return AttributeStore(
            self.fields, self.values[np.asarray(perm)],
            {name: tf.permuted(perm) for name, tf in self.tag_fields.items()})

    def take(self, ids: np.ndarray) -> np.ndarray:
        """Gather column rows (e.g. one tile's slice); negative ids -> zero
        rows. Tag-set fields have no column form and are not gathered."""
        ids = np.asarray(ids)
        out = self.values[np.clip(ids, 0, None)].copy()
        out[ids < 0] = 0
        return out


def random_attributes(
    n: int,
    spec: Dict[str, int] | None = None,
    seed: int = 0,
) -> AttributeStore:
    """Synthetic workload attributes: ``spec`` maps field name -> cardinality
    (values uniform in [0, cardinality)). Default schema gives a coarse
    categorical plus a fine-grained int, enough to dial any selectivity."""
    spec = spec or {"category": 16, "price": 1000}
    rng = np.random.default_rng(seed)
    cols = {
        f: rng.integers(0, card, size=n, dtype=np.int32)
        for f, card in spec.items()
    }
    return AttributeStore.from_columns(cols)
