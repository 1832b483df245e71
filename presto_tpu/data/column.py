"""Columnar data plane: Column / Page as JAX pytrees.

Re-design of the reference's Page/Block hierarchy
(presto-common/src/main/java/com/facebook/presto/common/Page.java:45,
presto-common/.../block/Block.java:40) for XLA's static-shape compilation
model:

- A Page has a *static capacity* (its array length) and a *traced row count*
  `num_rows` — rows [num_rows, capacity) are padding. Capacities come from a
  small set of power-of-two buckets so each operator compiles a handful of
  times, not once per batch (SURVEY.md §7.3 hard part #1).
- A Column is `values` (fixed-width, see types.py) + `nulls` (bool mask,
  True = NULL). Null slots hold the type's sort sentinel so padding/nulls
  sort last without branching.
- Strings are int32 codes into a host-side *sorted* StringDict: code order ==
  lexicographic order, so comparisons, grouping and sorting run on-device on
  codes alone; only LIKE/substring-style ops touch the host dictionary (they
  evaluate over the (small) dictionary once, then gather by code).
- Pages are pytrees, so whole fragments jit/vmap/shard_map over them.

The invariant everywhere: *valid rows are the first num_rows rows*. Filters
therefore compact (stable partition of survivors to the front) — a gather,
which is cheap on TPU — instead of carrying per-row masks through every
downstream operator.
"""

from __future__ import annotations

import dataclasses
import decimal as _decimal
import hashlib
import itertools
import struct
import threading
import weakref
from typing import Iterable, List, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from presto_tpu.types import Type, DecimalType, VARCHAR


#: explicit wide context for every engine-side Decimal op: python's
#: DEFAULT context is per-THREAD with prec=28, so scaleb on a 38-digit
#: value silently rounds when it happens to run on a worker thread (the
#: round-5 distributed-DECIMAL truncation bug). 80 digits covers
#: DECIMAL(38) sums with huge counts.
DEC_CTX = _decimal.Context(prec=80)


def scale_down_decimal(unscaled: int, scale: int) -> _decimal.Decimal:
    """Unscaled int -> exact python Decimal at `scale`. THE conversion
    for every decimal read path (never a float64 image; the reference
    client protocol carries decimals as exact strings)."""
    return DEC_CTX.scaleb(_decimal.Decimal(unscaled), -scale)


def unscale_decimal(v, scale: int) -> int:
    """Python value -> exact unscaled int at `scale`, HALF_UP (the
    reference's decimal rounding, UnscaledDecimal128Arithmetic). One
    shared definition so every write path rounds identically; floats go
    through Decimal(str(v)) — their shortest decimal reading — never a
    binary-scaled round()."""
    if not isinstance(v, _decimal.Decimal):
        v = _decimal.Decimal(str(v))
    return int(DEC_CTX.scaleb(v, scale).to_integral_value(
        rounding=_decimal.ROUND_HALF_UP))


# Capacity buckets: pages are padded up to the next bucket so XLA compiles a
# bounded set of shapes. Min bucket keeps tiny test pages cheap.
_BUCKETS = [256, 1024, 4096, 16384, 65536, 262144, 1048576, 2097152,
            4194304, 8388608, 16777216]


def bucket_capacity(n: int) -> int:
    for b in _BUCKETS:
        if n <= b:
            return b
    # Beyond the largest bucket, round up to a multiple of the largest.
    b = _BUCKETS[-1]
    return ((n + b - 1) // b) * b


class StringDict:
    """Host-side sorted string dictionary. Identity-hashed so it can live in
    pytree aux data without hashing millions of strings per jit-cache lookup;
    keep one instance per table column and reuse it."""

    __slots__ = ("words", "sparse", "_wire", "__weakref__")

    def __init__(self, words: Sequence[str], sparse: bool = False):
        self.words: Tuple[str, ...] = tuple(words)
        #: the pages that carry this dictionary may use only some of its
        #: words: it is a dictionary as it crossed the wire, shared by
        #: every page that named it (protocol/serde). Whoever fuses such
        #: pages, or hands one on as a page of its own, compacts
        #: (`compact_string_dict`)
        self.sparse = sparse
        self._wire = None

    @property
    def has_wire_form(self) -> bool:
        return self._wire is not None

    def wire_form(self) -> Tuple[np.ndarray, np.ndarray, Tuple[int, int]]:
        """The dictionary as a VARIABLE_WIDTH block holds it: the int32
        end offset of every word, the words' UTF-8 bytes joined, and a
        128-bit digest of both as two int64 — what names this dictionary
        to a receiver. Built on first use and kept: a dictionary lives as
        long as its column, and every page sent with it needs the same
        bytes. An empty dictionary goes as the one word ""."""
        if self._wire is None:
            words = self.words or ("",)
            text = "".join(words)
            payload = text.encode()
            if len(payload) == len(text):      # ASCII: bytes == characters
                lens = map(len, words)
            else:
                lens = (len(w.encode()) for w in words)
            ends = np.cumsum(np.fromiter(lens, np.int64, len(words))
                             ).astype(np.int32)
            payload = np.frombuffer(payload, dtype=np.uint8)
            digest = hashlib.blake2b(digest_size=16)
            digest.update(ends)
            digest.update(payload)
            self._wire = (ends, payload,
                          struct.unpack("<qq", digest.digest()))
        return self._wire

    def __len__(self) -> int:
        return len(self.words)

    def __getitem__(self, i: int) -> str:
        return self.words[i]

    def __hash__(self) -> int:
        return id(self)

    def __eq__(self, other) -> bool:
        return self is other

    def __repr__(self) -> str:
        return f"StringDict(n={len(self.words)})"

    def code_of(self, s: str) -> int:
        """Exact code of s, or -1 if absent (never matches a real code)."""
        import bisect
        i = bisect.bisect_left(self.words, s)
        if i < len(self.words) and self.words[i] == s:
            return i
        return -1

    def lower_bound(self, s: str) -> int:
        """First code whose word >= s (for range comparisons on codes)."""
        import bisect
        return bisect.bisect_left(self.words, s)

    @staticmethod
    def build(strings: Iterable[str]) -> Tuple["StringDict", np.ndarray]:
        arr = np.asarray(list(strings), dtype=object)
        uniq, codes = np.unique(arr.astype(str), return_inverse=True)
        return StringDict([str(u) for u in uniq]), codes.astype(np.int32)


@jax.tree_util.register_pytree_node_class
@dataclasses.dataclass
class Column:
    values: jnp.ndarray          # [capacity], dtype per type
    nulls: jnp.ndarray           # [capacity] bool, True = NULL
    type: Type                   # aux (static)
    dictionary: Optional[StringDict] = None  # aux (static), strings only

    # -- pytree protocol --------------------------------------------------
    def tree_flatten(self):
        return (self.values, self.nulls), (self.type, self.dictionary)

    @classmethod
    def tree_unflatten(cls, aux, children):
        values, nulls = children
        return cls(values, nulls, aux[0], aux[1])

    @property
    def capacity(self) -> int:
        return self.values.shape[0]

    # -- construction -----------------------------------------------------
    @staticmethod
    def host_from_numpy(values: np.ndarray, type: Type,
                        nulls: Optional[np.ndarray] = None,
                        dictionary: Optional[StringDict] = None,
                        capacity: Optional[int] = None) -> "Column":
        """A column over numpy arrays: `from_numpy`'s padding and
        sentinels, and nothing on the device. The form of a page that
        only crosses the exchange (`select_page_host`, `decode_pages`);
        a jitted island takes none (`page_to_device`)."""
        n = len(values)
        cap = capacity if capacity is not None else bucket_capacity(n)
        dt = type.dtype
        vals = np.asarray(values, dtype=dt)
        nl = (np.zeros(n, dtype=bool) if nulls is None
              else np.asarray(nulls, dtype=bool))
        return Column(
            fuse_lanes([null_sentinels(vals, nl, type)], cap, dt,
                       dt.type(type.null_sentinel())),
            fuse_lanes([nl], cap, np.bool_, True), type, dictionary)

    @staticmethod
    def from_numpy(values: np.ndarray, type: Type,
                   nulls: Optional[np.ndarray] = None,
                   dictionary: Optional[StringDict] = None,
                   capacity: Optional[int] = None) -> "Column":
        c = Column.host_from_numpy(values, type, nulls, dictionary, capacity)
        return Column(jnp.asarray(c.values), jnp.asarray(c.nulls), type,
                      dictionary)

    @staticmethod
    def host_from_strings(strings: Sequence[Optional[str]],
                          capacity: Optional[int] = None) -> "Column":
        nulls = np.array([s is None for s in strings], dtype=bool)
        filled = ["" if s is None else s for s in strings]
        d, codes = StringDict.build(filled)
        return Column.host_from_numpy(codes, VARCHAR, nulls=nulls,
                                      dictionary=d, capacity=capacity)

    @staticmethod
    def from_strings(strings: Sequence[Optional[str]],
                     capacity: Optional[int] = None) -> "Column":
        return page_to_device(Column.host_from_strings(strings, capacity))

    # -- host access ------------------------------------------------------
    def to_numpy(self, num_rows: Optional[int] = None):
        v = np.asarray(self.values)
        n = np.asarray(self.nulls)
        if num_rows is not None:
            v, n = v[:num_rows], n[:num_rows]
        return v, n

    def gather(self, idx: jnp.ndarray, valid: Optional[jnp.ndarray] = None
               ) -> "Column":
        """Gather rows; rows where valid is False become padding/null."""
        vals = jnp.take(self.values, idx, mode="clip")
        nulls = jnp.take(self.nulls, idx, mode="clip")
        if valid is not None:
            sent = jnp.asarray(self.type.null_sentinel(),
                               dtype=self.values.dtype)
            vals = jnp.where(valid, vals, sent)
            nulls = jnp.where(valid, nulls, True)
        return Column(vals, nulls, self.type, self.dictionary)

    def with_null_sentinels(self) -> "Column":
        """Ensure null slots hold the sort sentinel (after arithmetic the
        value lanes of null rows may hold garbage)."""
        sent = jnp.asarray(self.type.null_sentinel(), dtype=self.values.dtype)
        return Column(jnp.where(self.nulls, sent, self.values), self.nulls,
                      self.type, self.dictionary)


@jax.tree_util.register_pytree_node_class
@dataclasses.dataclass
class Decimal128Column:
    """DECIMAL(p>18) values — table storage, partial states AND final
    aggregates — as FOUR 32-bit limb lanes in int64 arrays:

        exact value = (l3 << 96) + (l2 << 64) + (l1 << 32) + l0

    with l3 signed (carries the sign via arithmetic-shift decomposition)
    and l2/l1/l0 unsigned 32-bit limbs. Reference:
    presto-common/.../type/UnscaledDecimal128Arithmetic.java, re-expressed
    as limb LANES because the TPU X64 pass lowers no 128-bit ops. The
    four-lane form covers the full +-(10^38-1) < 2^127 range at rest
    (round 4's two-lane hi/lo capped exactness at 2^95 — the 'input
    storage int64-bounded' gap), and each int64 lane can accumulate 2^31
    row-limbs carry-free, so SUM partials are plain per-lane segment
    sums; carries are resolved host-side with python big ints at
    value_at. With `count` set the logical value is the AVERAGE:
    exact_sum / count rounded HALF_UP at the type's scale."""
    l3: jnp.ndarray              # [capacity] int64 (signed top limbs)
    l2: jnp.ndarray              # [capacity] int64 (unsigned 32-bit limbs)
    l1: jnp.ndarray              # [capacity] int64
    l0: jnp.ndarray              # [capacity] int64
    nulls: jnp.ndarray           # [capacity] bool
    type: Type                   # aux: DecimalType(p>18, s)
    count: Optional[jnp.ndarray] = None   # avg denominator

    def tree_flatten(self):
        lanes = (self.l3, self.l2, self.l1, self.l0, self.nulls)
        if self.count is None:
            return lanes, (self.type, False)
        return lanes + (self.count,), (self.type, True)

    @classmethod
    def tree_unflatten(cls, aux, leaves):
        t, has_count = aux
        if has_count:
            l3, l2, l1, l0, nulls, count = leaves
            return cls(l3, l2, l1, l0, nulls, t, count)
        l3, l2, l1, l0, nulls = leaves
        return cls(l3, l2, l1, l0, nulls, t, None)

    @property
    def capacity(self) -> int:
        return self.l3.shape[0]

    @property
    def dictionary(self):
        return None

    @property
    def value_lanes(self):
        return (self.l3, self.l2, self.l1, self.l0)

    # -- construction -----------------------------------------------------
    @staticmethod
    def decompose_int64(v: jnp.ndarray):
        """Device-side limb decomposition of int64 unscaled values (the
        DECIMAL(<=18) storage feeding a 128-bit accumulator); delegates
        to the one shared definition in data/int128.py."""
        from presto_tpu.data import int128
        return int128.from_int64(v)

    @staticmethod
    def host_from_unscaled_ints(ints, type: Type, nulls=None,
                                capacity: Optional[int] = None,
                                ) -> "Decimal128Column":
        """Host build from python-int unscaled values (exact for the
        full 38-digit range): numpy lanes, nothing on the device."""
        n = len(ints)
        cap = capacity if capacity is not None else bucket_capacity(n)
        lanes = [np.zeros(cap, np.int64) for _ in range(4)]
        nl = np.ones(cap, dtype=bool)
        for i, v in enumerate(ints):
            if v is None or (nulls is not None and nulls[i]):
                continue
            nl[i] = False
            v = int(v)
            lanes[0][i] = v >> 96
            lanes[1][i] = (v >> 64) & 0xFFFFFFFF
            lanes[2][i] = (v >> 32) & 0xFFFFFFFF
            lanes[3][i] = v & 0xFFFFFFFF
        return Decimal128Column(lanes[0], lanes[1], lanes[2], lanes[3],
                                nl, type)

    @staticmethod
    def from_unscaled_ints(ints, type: Type, nulls=None,
                           capacity: Optional[int] = None,
                           ) -> "Decimal128Column":
        return page_to_device(Decimal128Column.host_from_unscaled_ints(
            ints, type, nulls, capacity))

    # -- generic row-lane protocol (compact/sort payload) -----------------
    def row_lanes(self):
        lanes = [self.l3, self.l2, self.l1, self.l0, self.nulls]
        if self.count is not None:
            lanes.append(self.count)
        return lanes

    def from_lanes(self, lanes):
        if self.count is not None:
            return Decimal128Column(lanes[0], lanes[1], lanes[2],
                                    lanes[3], lanes[4], self.type,
                                    lanes[5])
        return Decimal128Column(lanes[0], lanes[1], lanes[2], lanes[3],
                                lanes[4], self.type)

    @staticmethod
    def mask_lanes(lanes, valid):
        """Zero value/count lanes and null out rows where ~valid; lane
        order matches row_lanes() (nulls at index 4)."""
        out = list(lanes)
        for j in (0, 1, 2, 3):
            out[j] = jnp.where(valid, out[j], 0)
        out[4] = jnp.where(valid, out[4], True)
        if len(out) > 5:
            out[5] = jnp.where(valid, out[5], 0)
        return out

    def gather(self, idx: jnp.ndarray, valid=None) -> "Decimal128Column":
        lanes = [jnp.take(x, idx, mode="clip") for x in self.row_lanes()]
        if valid is not None:
            lanes = Decimal128Column.mask_lanes(lanes, valid)
        return self.from_lanes(lanes)

    def to_numpy(self, num_rows: Optional[int] = None):
        """(approximate float values, nulls) — ordering/debug only; exact
        values come from value_at."""
        v = (np.asarray(self.l3, dtype=np.float64) * float(2 ** 96)
             + np.asarray(self.l2, dtype=np.float64) * float(2 ** 64)
             + np.asarray(self.l1, dtype=np.float64) * float(2 ** 32)
             + np.asarray(self.l0, dtype=np.float64))
        n = np.asarray(self.nulls)
        if num_rows is not None:
            v, n = v[:num_rows], n[:num_rows]
        return v, n

    def _host(self):
        """One host transfer per lane, memoized (value_at is called per
        row by to_pylist / wire encode loops). Returns
        (lanes_tuple, nulls, count|None)."""
        cached = getattr(self, "_host_cache", None)
        if cached is None:
            cached = (tuple(np.asarray(x) for x in self.value_lanes),
                      np.asarray(self.nulls),
                      None if self.count is None
                      else np.asarray(self.count))
            object.__setattr__(self, "_host_cache", cached)
        return cached

    def unscaled_at(self, i: int) -> int:
        lanes, _nulls, _count = self._host()
        return ((int(lanes[0][i]) << 96) + (int(lanes[1][i]) << 64)
                + (int(lanes[2][i]) << 32) + int(lanes[3][i]))

    def value_at(self, i: int):
        """Exact python value of row i (scaled down per the type)."""
        _lanes, nulls, count = self._host()
        if bool(nulls[i]):
            return None
        unscaled = self.unscaled_at(i)
        scale = self.type.scale
        if self.count is not None:
            n = int(count[i])
            if n == 0:
                return None
            # avg = sum/n rounded HALF_UP at the result scale
            num = unscaled
            sign = -1 if (num < 0) != (n < 0) else 1
            num, n = abs(num), abs(n)
            q, r = divmod(num, n)
            if 2 * r >= n:
                q += 1
            unscaled = sign * q
        if scale == 0:
            return unscaled
        return scale_down_decimal(unscaled, scale)   # exact, not float


@jax.tree_util.register_pytree_node_class
@dataclasses.dataclass
class NestedColumn:
    """ARRAY/MAP/ROW column: per-row (start, length) slices into flat
    child columns (reference: presto-common ArrayBlock/MapBlock/RowBlock
    offset encoding — here start+length instead of a prefix array so
    row-wise gather/filter never rewrites the child buffers).

    ARRAY: children = (elements,);  MAP: children = (keys, values) —
    parallel, one entry pair per map entry;  ROW: children = one column
    per field, aligned 1:1 with parent rows (starts/lengths are identity
    and unused). The jit engine consumes these only through UNNEST (which
    flattens to ordinary columns); every other operator rejects nested
    input up front."""
    starts: jnp.ndarray          # [capacity] int32 into children
    lengths: jnp.ndarray         # [capacity] int32 (entries per row)
    nulls: jnp.ndarray           # [capacity] bool, True = NULL row
    children: Tuple["Column", ...]
    type: Type                   # aux: ArrayType | MapType | RowType

    def tree_flatten(self):
        return ((self.starts, self.lengths, self.nulls, self.children),
                (self.type,))

    @classmethod
    def tree_unflatten(cls, aux, leaves):
        starts, lengths, nulls, children = leaves
        return cls(starts, lengths, nulls, tuple(children), aux[0])

    @property
    def capacity(self) -> int:
        return self.starts.shape[0]

    @property
    def dictionary(self):
        return None

    def gather(self, idx: jnp.ndarray, valid=None) -> "NestedColumn":
        # starts are absolute child positions, so children never move on
        # row-wise gather — ROW columns too (their starts index fields).
        starts = jnp.take(self.starts, idx, mode="clip")
        lengths = jnp.take(self.lengths, idx, mode="clip")
        nulls = jnp.take(self.nulls, idx, mode="clip")
        if valid is not None:
            starts = jnp.where(valid, starts, 0)
            lengths = jnp.where(valid, lengths, 0)
            nulls = jnp.where(valid, nulls, True)
        return NestedColumn(starts, lengths, nulls, self.children,
                            self.type)

    def to_numpy(self, num_rows: Optional[int] = None):
        """Match Column.to_numpy's (values, nulls) shape contract for
        callers that only need validity; values are the lengths lane."""
        v = np.asarray(self.lengths)
        n = np.asarray(self.nulls)
        if num_rows is not None:
            v, n = v[:num_rows], n[:num_rows]
        return v, n

    # -- host construction/access ----------------------------------------
    @staticmethod
    def from_pylist(vals, type: Type,
                    capacity: Optional[int] = None) -> "NestedColumn":
        """Build from python values: lists (array), dicts (map), tuples
        (row), or None."""
        return page_to_device(
            NestedColumn.host_from_pylist(vals, type, capacity))

    @staticmethod
    def host_from_pylist(vals, type: Type,
                         capacity: Optional[int] = None) -> "NestedColumn":
        """`from_pylist` over numpy arrays, children included."""
        n = len(vals)
        cap = capacity if capacity is not None else bucket_capacity(n)
        nulls = np.array([v is None for v in vals] + [True] * (cap - n),
                         dtype=bool)
        if type.name == "row":
            fields = []
            for i, ft in enumerate(type.field_types):
                fvals = [None if v is None else v[i] for v in vals]
                fields.append(_column_from_pylist(fvals, ft, cap))
            ident = np.arange(cap, dtype=np.int32)
            return NestedColumn(ident, np.ones(cap, np.int32), nulls,
                                tuple(fields), type)
        lengths = np.zeros(cap, np.int32)
        flat_items: list = []
        starts = np.zeros(cap, np.int32)
        for i, v in enumerate(vals):
            starts[i] = len(flat_items)
            if v is None:
                continue
            items = list(v.items()) if type.name == "map" else list(v)
            lengths[i] = len(items)
            flat_items.extend(items)
        ecap = bucket_capacity(max(len(flat_items), 1))
        if type.name == "map":
            keys = _column_from_pylist(
                [k for k, _v in flat_items], type.key, ecap)
            values = _column_from_pylist(
                [v for _k, v in flat_items], type.value, ecap)
            children = (keys, values)
        else:
            children = (_column_from_pylist(
                flat_items, type.element, ecap),)
        return NestedColumn(starts, lengths, nulls, children, type)

    def value_at(self, i: int):
        """Python value of row i (host; to_pylist support)."""
        if bool(np.asarray(self.nulls)[i]):
            return None
        if self.type.name == "row":
            return tuple(_pyvalue(c, int(np.asarray(self.starts)[i]))
                         for c in self.children)
        s = int(np.asarray(self.starts)[i])
        ln = int(np.asarray(self.lengths)[i])
        if self.type.name == "map":
            return {_pyvalue(self.children[0], j):
                    _pyvalue(self.children[1], j)
                    for j in range(s, s + ln)}
        return [_pyvalue(self.children[0], j) for j in range(s, s + ln)]


def _column_from_pylist(vals, t: Type, capacity: int):
    """list of python values -> host Column/NestedColumn of type t."""
    if isinstance(t, Type) and t.name in ("array", "map", "row"):
        return NestedColumn.host_from_pylist(vals, t, capacity)
    if t.is_string:
        return Column.host_from_strings(vals, capacity=capacity)
    nulls = np.array([v is None for v in vals], dtype=bool)
    if t.is_decimal:
        # exact unscaling: Decimal values never round-trip through
        # float64 (38-digit literals keep every digit)
        filled = np.array([0 if v is None else unscale_decimal(v, t.scale)
                           for v in vals], dtype=np.int64)
    else:
        filled = np.array([0 if v is None else v for v in vals])
    return Column.host_from_numpy(filled, t, nulls=nulls, capacity=capacity)


def _pyvalue(col, i: int):
    """One position of a Column/NestedColumn as a python value."""
    if isinstance(col, NestedColumn):
        return col.value_at(i)
    v, nl = col.to_numpy()
    if nl[i]:
        return None
    if col.type.is_string:
        return (col.dictionary[int(v[i])]
                if col.dictionary is not None else int(v[i]))
    if isinstance(col.type, DecimalType):
        return scale_down_decimal(int(v[i]), col.type.scale)
    if col.type.name == "boolean":
        return bool(v[i])
    if col.type.is_floating:
        return float(v[i])
    return int(v[i])


@jax.tree_util.register_pytree_node_class
@dataclasses.dataclass
class Page:
    columns: Tuple[Column, ...]
    num_rows: jnp.ndarray        # scalar int32 (traced)
    names: Tuple[str, ...] = ()  # aux: output column names (may be empty)

    def tree_flatten(self):
        return (self.columns, self.num_rows), (self.names,)

    @classmethod
    def tree_unflatten(cls, aux, children):
        columns, num_rows = children
        return cls(tuple(columns), num_rows, aux[0])

    @property
    def capacity(self) -> int:
        return self.columns[0].capacity if self.columns else 0

    @property
    def num_columns(self) -> int:
        return len(self.columns)

    def row_valid(self) -> jnp.ndarray:
        return jnp.arange(self.capacity, dtype=jnp.int32) < self.num_rows

    def column(self, i: int) -> Column:
        return self.columns[i]

    # -- construction -----------------------------------------------------
    @staticmethod
    def from_columns(columns: Sequence[Column], num_rows,
                     names: Sequence[str] = ()) -> "Page":
        return Page(tuple(columns), jnp.asarray(num_rows, dtype=jnp.int32),
                    tuple(names))

    @staticmethod
    def host_from_columns(columns: Sequence[Column], num_rows,
                          names: Sequence[str] = ()) -> "Page":
        """A page whose row count stays on the host too (`np.int32`):
        with host columns, a page no part of which is on the device."""
        return Page(tuple(columns), np.int32(num_rows), tuple(names))

    @staticmethod
    def from_pydict(data: dict, types: dict, capacity: Optional[int] = None
                    ) -> "Page":
        """Build a Page from {name: list-of-python-values} (tests/tools)."""
        cols, names = [], []
        n = 0
        for name, vals in data.items():
            n = len(vals)
            t = types[name]
            cap = capacity if capacity is not None else bucket_capacity(n)
            cols.append(_column_from_pylist(list(vals), t, cap))
            names.append(name)
        return page_to_device(Page.host_from_columns(cols, n, names))

    # -- host access ------------------------------------------------------
    def to_pylist(self) -> List[tuple]:
        """Materialize valid rows as python tuples (decoded strings,
        decimals as floats scaled down). For tests and result delivery."""
        n = int(self.num_rows)
        rows: List[tuple] = []
        cols = []
        for c in self.columns:
            v, nl = c.to_numpy(n)
            cols.append((c, v, nl))
        for i in range(n):
            row = []
            for c, v, nl in cols:
                if isinstance(c, (NestedColumn, Decimal128Column)):
                    row.append(c.value_at(i))
                elif nl[i]:
                    row.append(None)
                elif c.type.is_string:
                    row.append(c.dictionary[int(v[i])]
                               if c.dictionary is not None else int(v[i]))
                elif isinstance(c.type, DecimalType):
                    row.append(scale_down_decimal(int(v[i]),
                                                  c.type.scale))
                elif c.type.name == "boolean":
                    row.append(bool(v[i]))
                elif c.type.is_floating:
                    row.append(float(v[i]))
                else:
                    row.append(int(v[i]))
            rows.append(tuple(row))
        return rows


# ---------------------------------------------------------------------------
# Host-side page assembly (exchange data plane, outside jit)
# ---------------------------------------------------------------------------

# A dictionary made by a fuse is the same object when its words are the
# same. StringDict hashes by identity and sits in Column's pytree aux, so
# a new object for the words of the last statement's fuse is a new jit
# cache key: every program above the fuse would be traced and compiled
# again, and each trace would pin its dictionary in that cache. Keyed by
# (number of words, the 128-bit digest `wire_form` computes and keeps
# for the codec anyway); weak, so a dictionary lives as long as a page
# or a cached program names it.
_INTERNED: "weakref.WeakValueDictionary" = weakref.WeakValueDictionary()
_INTERNED_LOCK = threading.Lock()


def intern_string_dict(d: StringDict) -> StringDict:
    """The one StringDict of `d`'s words: `d` itself where they are new.
    For dictionaries a fuse has just made (`compact_string_dict`,
    `merge_string_dicts`), never for a sparse one."""
    key = (len(d), d.wire_form()[2])
    with _INTERNED_LOCK:
        return _INTERNED.setdefault(key, d)


def merge_string_dicts(dicts: Sequence[Optional[StringDict]]
                       ) -> Tuple[StringDict, List[np.ndarray]]:
    """Union N sorted dictionaries into one sorted dictionary; returns the
    union and, per input dict, the code remap array (old code -> new code).
    This is how independently produced pages (different workers, different
    scans) become comparable on codes again — the cross-page dictionary
    story the round-1 review flagged (reference role: the Block layer's
    DictionaryBlock id spaces are also per-block and re-resolved on use)."""
    first = dicts[0] if dicts else None
    if first is not None and all(d is first for d in dicts):
        # one object on every page (a table's dictionary, or one the
        # wire decode handed to all of them): nothing to union
        same = np.arange(len(first), dtype=np.int32)
        return first, [same] * len(dicts)
    word_lists = [list(d.words) if d is not None else [] for d in dicts]
    union = sorted(set().union(*[set(w) for w in word_lists]))
    union_arr = np.asarray(union, dtype=object).astype(str)
    out = intern_string_dict(StringDict(union))
    remaps = []
    for words in word_lists:
        if not words:
            remaps.append(np.zeros(0, np.int32))
            continue
        remaps.append(np.searchsorted(
            union_arr, np.asarray(words, dtype=object).astype(str)
        ).astype(np.int32))
    return out, remaps


def compact_string_dict(dictionary: StringDict, codes: np.ndarray,
                        nulls: np.ndarray, interned: bool = True
                        ) -> Tuple[StringDict, np.ndarray]:
    """The dictionary of exactly the words that the rows use, and the
    rows' codes into it: one pass of arrays over the rows and one over
    the words. What a page decoded from the wire has always carried (a
    sorted dictionary of the words present), and what
    `ops/aggregate._direct_domains` and every lowering that reads
    `len(dictionary)` therefore see. A null string crosses the wire as a
    null slot and decodes as "", so "" stays where a row is null. The
    same StringDict for the same words (`intern_string_dict`), unless the
    caller unions the result straight away (`interned` false)."""
    k = len(dictionary)
    if not k:
        return intern_string_dict(StringDict(())), codes
    some_null = bool(nulls.any())
    used = np.zeros(k, dtype=bool)
    used[codes[~nulls] if some_null else codes] = True
    if some_null and dictionary.words[0] == "":
        used[0] = True
    remap = np.cumsum(used, dtype=np.int32) - 1
    words = itertools.compress(dictionary.words, used.tolist())
    # null rows hold the sort sentinel once they have been a Column
    codes = remap[np.where(nulls, 0, codes) if some_null else codes]
    out = StringDict(words)
    return (intern_string_dict(out) if interned else out), codes


def page_to_device(tree):
    """A page (or a column) with every numpy leaf put on the device, in
    one `jax.device_put` of the whole tree; a leaf that is there already
    stays. The one way a host page becomes an island's input."""
    return jax.device_put(tree)


def device_leaves(tree) -> int:
    """How many arrays of a page (or of pages) live on the device. A
    page's form is the type of its arrays: 0 says a host page."""
    return sum(isinstance(a, jax.Array)
               for a in jax.tree_util.tree_leaves(tree))


def null_sentinels(vals: np.ndarray, nulls: np.ndarray, t: Type
                   ) -> np.ndarray:
    """`vals` with the type's sort sentinel in every null slot (what a
    Column holds there): `vals` itself where no slot is null."""
    if not nulls.any():
        return vals
    return np.where(nulls, t.dtype.type(t.null_sentinel()), vals)


def fuse_lanes(parts: Sequence[np.ndarray], cap: int, dtype, fill
               ) -> np.ndarray:
    """The parts end to end in one new array of `cap` slots, `fill`
    after the last: each part is copied once, into its place."""
    out = np.empty(cap, dtype=dtype)
    at = 0
    for a in parts:
        out[at:at + len(a)] = a
        at += len(a)
    out[at:] = fill
    return out


def concat_pages_host(pages: Sequence[Page],
                      capacity: Optional[int] = None) -> Page:
    """Concatenate pages row-wise in numpy, merging per-column string
    dictionaries, and put the fused page on the device: the one upload
    of an exchange's consumer. Used by the worker to fuse pulled exchange
    streams into one scan-like input page (the consumer side of
    ExchangeClient.java:255, materialized batch-wise for the jit engine).
    It reads every page through `np.asarray`: a host page (the
    exchange's `decode_pages`) costs nothing to read, a device page
    (`DistSplitExecutor`, the mesh tier, a test) is fetched first. The
    result is a device page whatever came in."""
    assert pages, "concat of zero pages"
    first = pages[0]
    rows = [int(p.num_rows) for p in pages]
    total = sum(rows)
    cap = capacity if capacity is not None else bucket_capacity(max(total, 1))
    cols: List[Column] = []
    for ci, c0 in enumerate(first.columns):
        if isinstance(c0, Decimal128Column):
            lanes = []
            for li in range(len(c0.row_lanes())):
                parts = [np.asarray(p.columns[ci].row_lanes()[li])[:n_p]
                         for p, n_p in zip(pages, rows)]
                # row_lanes: l3..l0, nulls, then the count of an average
                lanes.append(fuse_lanes(parts, cap, parts[0].dtype,
                                        True if li == 4 else 0))
            cols.append(c0.from_lanes(lanes))
            continue
        if isinstance(c0, NestedColumn):
            # host re-materialization through python values (exchange
            # volumes of nested data are modest until nested compute
            # exists; correctness first)
            pyvals: List = []
            for p, n_p in zip(pages, rows):
                col = p.columns[ci]
                pyvals.extend(col.value_at(i) for i in range(n_p))
            cols.append(NestedColumn.host_from_pylist(pyvals, c0.type, cap))
            continue
        parts = [(p.columns[ci].dictionary, *p.columns[ci].to_numpy(n_p))
                 for p, n_p in zip(pages, rows)]
        union = None
        if c0.type.is_string:
            d0 = parts[0][0]
            if any(d is not d0 for d, _v, _nl in parts):
                # a dictionary from the wire holds words its page does
                # not use: union the words in use, as before
                # (the per-page dictionaries go no further than the
                # union below, so they stay out of the intern table)
                parts = [
                    (*compact_string_dict(d, v, nl, interned=False), nl)
                    if d is not None and d.sparse else (d, v, nl)
                    for d, v, nl in parts]
            union, remaps = merge_string_dicts([d for d, _v, _nl in parts])
            parts = [
                (d, remap[np.clip(v, 0, len(remap) - 1)]
                 if d is not union and len(remap) else v, nl)
                for (d, v, nl), remap in zip(parts, remaps)]
        dt = c0.type.dtype
        vals = fuse_lanes([v for _d, v, _nl in parts], cap, dt,
                          dt.type(c0.type.null_sentinel()))
        nulls = fuse_lanes([nl for _d, _v, nl in parts], cap, np.bool_,
                           True)
        live, live_nulls = vals[:total], nulls[:total]
        if union is not None and union.sparse:
            # every page brought the one wire dictionary: compact
            # once, for the fused page
            union, live[:] = compact_string_dict(union, live, live_nulls)
        live[:] = null_sentinels(live, live_nulls, c0.type)
        cols.append(Column(vals, nulls, c0.type, union))
    return page_to_device(Page.host_from_columns(cols, total, first.names))


def page_nbytes(page: Page) -> int:
    """Bytes of a page's arrays at their capacity: what a transfer of
    the page moves."""
    return sum(int(getattr(a, "nbytes", 0))
               for a in jax.tree_util.tree_leaves(page))


def page_to_host(page: Page) -> None:
    """Fetch every array of a device page. jax keeps the host copy with
    the array, so the `np.asarray` of each leaf that follows is free:
    the device->host step of an output page is paid here, once, apart
    from partitioning and serialization. Who relies on that: the
    partitioner (`_hash_partition_ids`, `select_page_host`) and the
    single-buffer `_serialize(page)`, which all read the device page's
    own arrays; a partition is a host page from then on."""
    for a in jax.tree_util.tree_leaves(page):
        np.asarray(a)


def select_page_host(page: Page, idx: np.ndarray) -> Page:
    """Host-side row selection (numpy take) keeping dictionaries — the
    producer side of partitioned output (PartitionedOutputOperator.java:57
    splitting rows into per-destination pages). The result is a host
    page of exactly the selected rows, with no padding: it exists to
    become wire blocks (`page_to_wire_blocks` reads `[:num_rows]`), and
    nothing of it touches the device."""
    cols = []
    for c in page.columns:
        if isinstance(c, Decimal128Column):
            cols.append(c.from_lanes(
                [np.asarray(lane)[idx] for lane in c.row_lanes()]))
            continue
        if isinstance(c, NestedColumn):
            # starts are absolute child positions: the children stay
            # whole, as the host copies `page_to_host` left with them
            cols.append(NestedColumn(
                np.asarray(c.starts)[idx], np.asarray(c.lengths)[idx],
                np.asarray(c.nulls)[idx],
                jax.tree_util.tree_map(np.asarray, c.children), c.type))
            continue
        v, nl = c.to_numpy()
        v, nl = v[idx], nl[idx]
        cols.append(Column(null_sentinels(v, nl, c.type), nl, c.type,
                           c.dictionary))
    return Page.host_from_columns(cols, len(idx), page.names)


# ---------------------------------------------------------------------------
# Core page transforms (shared by operators)
# ---------------------------------------------------------------------------

def gather_page(page: Page, idx: jnp.ndarray,
                valid: Optional[jnp.ndarray] = None,
                num_rows=None, names: Optional[tuple] = None) -> Page:
    """Row-wise gather of every column (rows where `valid` is False
    become padding/null). THE payload-movement primitive: operators sort
    only key lanes (ops/keys.lex_perm) and move data with this."""
    cols = tuple(c.gather(idx, valid) for c in page.columns)
    return Page(cols,
                page.num_rows if num_rows is None else num_rows,
                page.names if names is None else names)


def compact(page: Page, keep: jnp.ndarray,
            capacity: Optional[int] = None) -> Page:
    """Stable-partition rows where `keep` is True to the front; the result's
    num_rows is the survivor count. This is the engine's filter primitive.
    With a smaller `capacity` the result holds only that many slots (the
    first survivors; the caller watches the count for overflow), and the
    gathers fetch only that many elements.

    Implemented as ONE 2-operand argsort on the order key + per-column
    gathers: on this stack gathers compile in under a second, while a
    lax.sort carrying every column as a payload operand multiplies
    compile cost with column count (~25 s an operand; PR 27). The
    gathers are what it costs to RUN: 7-9 ns an element for every 32-bit
    lane on the chip, values and null flags alike (15-19 ms a lane at
    2 M rows, ~20 ns an element where the table is out of near memory;
    ledger, PR 26), where one stacked [k, n] gather moves up to 8 lanes
    for the price of one (ops/join._gather_columns; PERF.md, PR 27).

    Reference semantics: PageProcessor's filter
    (presto-main-base/.../operator/project/PageProcessor.java:56), re-expressed
    as a compaction so downstream ops see dense pages.
    """
    keep = keep & page.row_valid()
    cap = page.capacity
    # Stable order: non-survivors get index offset + capacity.
    order_key = (jnp.where(keep, 0, cap).astype(jnp.int32)
                 + jnp.arange(cap, dtype=jnp.int32))
    perm = jnp.argsort(order_key)        # distinct keys: stability free
    if capacity is not None and capacity < cap:
        perm, cap = perm[:capacity], capacity
    n = jnp.minimum(jnp.sum(keep), cap).astype(jnp.int32)
    valid = jnp.arange(cap, dtype=jnp.int32) < n
    cols = [c.gather(perm, valid) for c in page.columns]
    return Page(tuple(cols), n, page.names)
