"""TPC-H connector: deterministic in-memory data generation.

Reference role: presto-tpch (presto-tpch/src/main/java/com/facebook/presto/
tpch/TpchConnectorFactory.java, TpchRecordSetProvider) — data generated on
the fly from split info, no external files; the standard deterministic
fixture for every engine test (SURVEY.md §4).

This generator is *spec-shaped*, not bit-identical to dbgen: row counts,
key relationships (lineitem->orders, partsupp's 4-suppliers-per-part
formula, customers without orders), value distributions and date ranges
follow the TPC-H spec so query selectivities and join fan-outs are
realistic; exact values differ from airlift's dbgen port. Correctness
testing compares against a pandas oracle over the *same* data
(tests/oracle.py), mirroring the reference's H2QueryRunner strategy
(presto-tests/.../H2QueryRunner.java).

Tables partition by primary-key row ranges (part k of n), matching the
reference's split model where tpch splits are self-describing
(TpchSplitManager). Splits are row-range slices of the cached full table
so string codes share one table-wide StringDict — the invariant every
cross-device exchange and dictionary-aligned operator relies on.

A string column over a fixed list of words (flags, modes, segments,
priorities, part types, clerks) is drawn as codes and never holds a
string a row. A column of text (comments, addresses, phones, names) is
made when something first reads it, from the random stream as it stood
where the table drew it, so a scan of other columns never pays for it
and every column is the same whichever are made.
"""

from __future__ import annotations

import copy
import dataclasses
import functools
import threading
import time
from collections.abc import MutableMapping
from typing import Callable, Dict, List, Optional, Sequence, Tuple

import numpy as np

from presto_tpu.connectors.scan_cache import SCAN_CACHE, KeptColumns
from presto_tpu.data.column import (Column, Page, StringDict,
                                    bucket_capacity, page_nbytes)
from presto_tpu.expr.compile import days_from_civil
from presto_tpu.obs.metrics import counter as _counter
from presto_tpu.types import BIGINT, DATE, DOUBLE, INTEGER, VARCHAR, Type
from presto_tpu.utils.tracing import TRACER

_GENERATED_BYTES = _counter(
    "presto_tpu_connector_generated_bytes_total",
    "Bytes of generated columns made on first read, after their table "
    "(codes and dictionary words)", labelnames=("table",))
_GENERATE_SECONDS = _counter(
    "presto_tpu_connector_generate_seconds_total",
    "Seconds spent making generated columns on first read, after their "
    "table", labelnames=("table",))

# ---------------------------------------------------------------------------
# schema
# ---------------------------------------------------------------------------

TPCH_SCHEMA: Dict[str, List[Tuple[str, Type]]] = {
    "region": [("r_regionkey", BIGINT), ("r_name", VARCHAR),
               ("r_comment", VARCHAR)],
    "nation": [("n_nationkey", BIGINT), ("n_name", VARCHAR),
               ("n_regionkey", BIGINT), ("n_comment", VARCHAR)],
    "supplier": [("s_suppkey", BIGINT), ("s_name", VARCHAR),
                 ("s_address", VARCHAR), ("s_nationkey", BIGINT),
                 ("s_phone", VARCHAR), ("s_acctbal", DOUBLE),
                 ("s_comment", VARCHAR)],
    "customer": [("c_custkey", BIGINT), ("c_name", VARCHAR),
                 ("c_address", VARCHAR), ("c_nationkey", BIGINT),
                 ("c_phone", VARCHAR), ("c_acctbal", DOUBLE),
                 ("c_mktsegment", VARCHAR), ("c_comment", VARCHAR)],
    "part": [("p_partkey", BIGINT), ("p_name", VARCHAR), ("p_mfgr", VARCHAR),
             ("p_brand", VARCHAR), ("p_type", VARCHAR), ("p_size", INTEGER),
             ("p_container", VARCHAR), ("p_retailprice", DOUBLE),
             ("p_comment", VARCHAR)],
    "partsupp": [("ps_partkey", BIGINT), ("ps_suppkey", BIGINT),
                 ("ps_availqty", INTEGER), ("ps_supplycost", DOUBLE),
                 ("ps_comment", VARCHAR)],
    "orders": [("o_orderkey", BIGINT), ("o_custkey", BIGINT),
               ("o_orderstatus", VARCHAR), ("o_totalprice", DOUBLE),
               ("o_orderdate", DATE), ("o_orderpriority", VARCHAR),
               ("o_clerk", VARCHAR), ("o_shippriority", INTEGER),
               ("o_comment", VARCHAR)],
    "lineitem": [("l_orderkey", BIGINT), ("l_partkey", BIGINT),
                 ("l_suppkey", BIGINT), ("l_linenumber", INTEGER),
                 ("l_quantity", DOUBLE), ("l_extendedprice", DOUBLE),
                 ("l_discount", DOUBLE), ("l_tax", DOUBLE),
                 ("l_returnflag", VARCHAR), ("l_linestatus", VARCHAR),
                 ("l_shipdate", DATE), ("l_commitdate", DATE),
                 ("l_receiptdate", DATE), ("l_shipinstruct", VARCHAR),
                 ("l_shipmode", VARCHAR), ("l_comment", VARCHAR)],
}

_NATIONS = [
    ("ALGERIA", 0), ("ARGENTINA", 1), ("BRAZIL", 1), ("CANADA", 1),
    ("EGYPT", 4), ("ETHIOPIA", 0), ("FRANCE", 3), ("GERMANY", 3),
    ("INDIA", 2), ("INDONESIA", 2), ("IRAN", 4), ("IRAQ", 4), ("JAPAN", 2),
    ("JORDAN", 4), ("KENYA", 0), ("MOROCCO", 0), ("MOZAMBIQUE", 0),
    ("PERU", 1), ("CHINA", 2), ("ROMANIA", 3), ("SAUDI ARABIA", 4),
    ("VIETNAM", 2), ("RUSSIA", 3), ("UNITED KINGDOM", 3),
    ("UNITED STATES", 1),
]
_REGIONS = ["AFRICA", "AMERICA", "ASIA", "EUROPE", "MIDDLE EAST"]
_SEGMENTS = ["AUTOMOBILE", "BUILDING", "FURNITURE", "HOUSEHOLD", "MACHINERY"]
_PRIORITIES = ["1-URGENT", "2-HIGH", "3-MEDIUM", "4-NOT SPECIFIED", "5-LOW"]
_SHIPMODES = ["AIR", "FOB", "MAIL", "RAIL", "REG AIR", "SHIP", "TRUCK"]
_INSTRUCTS = ["COLLECT COD", "DELIVER IN PERSON", "NONE",
              "TAKE BACK RETURN"]
_CONTAINERS = [f"{a} {b}" for a in
               ["SM", "LG", "MED", "JUMBO", "WRAP"] for b in
               ["CASE", "BOX", "BAG", "JAR", "PKG", "PACK", "CAN", "DRUM"]]
_TYPE_S1 = ["STANDARD", "SMALL", "MEDIUM", "LARGE", "ECONOMY", "PROMO"]
_TYPE_S2 = ["ANODIZED", "BURNISHED", "PLATED", "POLISHED", "BRUSHED"]
_TYPE_S3 = ["TIN", "NICKEL", "BRASS", "STEEL", "COPPER"]
_PTYPES = [f"{a} {b} {c}" for a in _TYPE_S1 for b in _TYPE_S2
           for c in _TYPE_S3]
_PNAME_WORDS = [
    "almond", "antique", "aquamarine", "azure", "beige", "bisque", "black",
    "blanched", "blue", "blush", "brown", "burlywood", "burnished",
    "chartreuse", "chiffon", "chocolate", "coral", "cornflower", "cornsilk",
    "cream", "cyan", "dark", "deep", "dim", "dodger", "drab", "firebrick",
    "floral", "forest", "frosted", "gainsboro", "ghost", "goldenrod",
    "green", "grey", "honeydew", "hot", "hotpink", "indian", "ivory",
    "khaki", "lace", "lavender", "lawn", "lemon", "light", "lime", "linen",
    "magenta", "maroon", "medium", "metallic", "midnight", "mint", "misty",
    "moccasin", "navajo", "navy", "olive", "orange", "orchid", "pale",
    "papaya", "peach", "peru", "pink", "plum", "powder", "puff", "purple",
    "red", "rose", "rosy", "royal", "saddle", "salmon", "sandy", "seashell",
    "sienna", "sky", "slate", "smoke", "snow", "spring", "steel", "tan",
    "thistle", "tomato", "turquoise", "violet", "wheat", "white", "yellow",
]
_COMMENT_WORDS = [
    "carefully", "quickly", "furiously", "slyly", "blithely", "ironic",
    "final", "pending", "regular", "express", "special", "bold", "even",
    "silent", "unusual", "requests", "deposits", "packages", "accounts",
    "instructions", "theodolites", "platelets", "foxes", "ideas", "courts",
    "sleep", "wake", "nag", "haggle", "cajole", "detect", "integrate",
    "among", "across", "above", "against", "along",
]

_MIN_DATE = days_from_civil(1992, 1, 1)
_MAX_ORDER_DATE = days_from_civil(1998, 8, 2)
_CURRENT = days_from_civil(1995, 6, 17)  # dbgen CURRENTDATE analogue

_SF_BASE = {"supplier": 10_000, "customer": 150_000, "part": 200_000,
            "orders": 1_500_000}
_SUPP_PER_PART = 4
_SCHEMA_SCALES = {"tiny": 0.001, "sf0.01": 0.01, "sf0.1": 0.1, "sf1": 1.0,
                  "sf10": 10.0, "sf100": 100.0}


def _counts(sf: float) -> Dict[str, int]:
    return {
        "region": 5,
        "nation": 25,
        "supplier": max(10, int(_SF_BASE["supplier"] * sf)),
        "customer": max(15, int(_SF_BASE["customer"] * sf)),
        "part": max(20, int(_SF_BASE["part"] * sf)),
        "orders": max(150, int(_SF_BASE["orders"] * sf)),
    }


def _comment_draws(rng: np.random.Generator, n: int, words: int
                   ) -> np.ndarray:
    return rng.integers(0, len(_COMMENT_WORDS), size=(n, words))


def _comment(rng: np.random.Generator, n: int, words: int = 4) -> np.ndarray:
    w = np.asarray(_COMMENT_WORDS, dtype=object)
    idx = _comment_draws(rng, n, words)
    out = w[idx[:, 0]]
    for k in range(1, words):
        out = out + " " + w[idx[:, k]]
    return out


def _phone_draws(rng: np.random.Generator, n: int):
    return (rng.integers(100, 1000, size=n), rng.integers(100, 1000, size=n),
            rng.integers(1000, 10000, size=n))


def _phone(rng: np.random.Generator, nation: np.ndarray) -> np.ndarray:
    a = nation + 10
    b, c, d = _phone_draws(rng, len(nation))
    return np.char.add(np.char.add(np.char.add(np.char.add(
        a.astype(str), "-"), b.astype(str)), "-"),
        np.char.add(np.char.add(c.astype(str), "-"), d.astype(str))
    ).astype(object)


def _pname_draws(rng: np.random.Generator, n: int) -> np.ndarray:
    return rng.integers(0, len(_PNAME_WORDS), size=(n, 5))


def _pname(rng: np.random.Generator, n: int) -> np.ndarray:
    w = np.asarray(_PNAME_WORDS, dtype=object)
    idx = _pname_draws(rng, n)
    nm = w[idx[:, 0]]
    for k in range(1, 5):
        nm = nm + " " + w[idx[:, k]]
    return nm


def _supplier_comment(rng: np.random.Generator, n: int) -> np.ndarray:
    # ~5 of every 1000 suppliers complain, ~5 recommend (Q16/Q21)
    comm = _comment(rng, n)
    tag = rng.integers(0, 1000, size=n)
    comm = np.where(tag < 5, "Customer Complaints " + comm, comm)
    comm = np.where(tag >= 995, "Customer Recommends " + comm, comm)
    return comm.astype(object)


def _keyed_names(prefix: str, key: np.ndarray) -> np.ndarray:
    return np.char.add(prefix, np.char.zfill(key.astype(str), 9)
                       ).astype(object)


def _coded(words: Sequence[str], idx: np.ndarray
           ) -> Tuple[np.ndarray, StringDict]:
    """The column `words[idx]` coded as `StringDict.build` codes it, with
    no string made a row: the dictionary holds the words drawn, in
    order, and a row's code is its word's rank among them."""
    drawn = np.flatnonzero(np.bincount(idx, minlength=len(words)))
    order = sorted(drawn, key=words.__getitem__)
    rank = np.zeros(len(words), np.int32)
    rank[order] = np.arange(len(order), dtype=np.int32)
    return rank[idx], StringDict([words[i] for i in order])


def _later(rng: np.random.Generator, make, *skips
           ) -> Callable[[], Tuple[np.ndarray, StringDict]]:
    """A text column made on first read: `make` draws from a copy of the
    stream as it stands now. `skips` are the same draws, made on `rng`
    itself where the table draws more after this column, so every later
    column draws what it drew when all were made at once."""
    saved = copy.deepcopy(rng)
    for skip in skips:
        skip(rng)
    return lambda: _dictify(make(copy.deepcopy(saved)))


def _retailprice(partkey: np.ndarray) -> np.ndarray:
    return (90000 + ((partkey // 10) % 20001) + 100 * (partkey % 1000)) / 100.0


def _part_suppliers(partkey: np.ndarray, j: np.ndarray, num_supp: int
                    ) -> np.ndarray:
    """dbgen-style formula: the j-th supplier of part p (j in [0,4))."""
    return ((partkey - 1 + j * (num_supp // _SUPP_PER_PART + 1)) % num_supp
            ) + 1


class LazyColumns(MutableMapping):
    """Column name -> array, made by `make(name)` on first read and kept;
    two threads that ask at once get one `make`. With `names` the mapping
    holds those columns, made or not: `in`, `len` and iteration count
    them, and reading one makes it. Without, it holds what was made (a
    file's columns) and any other name is asked of `make`. `ready`
    holds what is there from the start."""

    def __init__(self, make: Callable[[str], np.ndarray],
                 names: Optional[Sequence[str]] = None,
                 ready: Optional[Dict[str, np.ndarray]] = None):
        self._make = make
        self._names = None if names is None else tuple(names)
        self._made: Dict[str, np.ndarray] = dict(ready or {})
        self._lock = threading.Lock()

    def __getitem__(self, key: str) -> np.ndarray:
        try:
            return self._made[key]
        except KeyError:
            if self._names is not None and key not in self._names:
                raise
        with self._lock:
            if key not in self._made:
                self._made[key] = self._make(key)
            return self._made[key]

    def __setitem__(self, key: str, value: np.ndarray) -> None:
        self._made[key] = value

    def __delitem__(self, key: str) -> None:
        del self._made[key]

    def __contains__(self, key) -> bool:
        return key in self._made or (self._names is not None
                                     and key in self._names)

    def __iter__(self):
        return iter(self._names if self._names is not None
                    else list(self._made))

    def __len__(self) -> int:
        return len(self._names if self._names is not None else self._made)

    def made(self) -> List[str]:
        """The columns made so far."""
        return list(self._made)


@dataclasses.dataclass
class HostTable:
    """Host-side generated table: numeric numpy arrays (string columns
    stored as int32 codes) + shared StringDicts. `page()` uploads a
    column-pruned, bucket-padded device Page. `nulls` is optional (the
    TPC fixtures are null-free; written tables — connectors/memory.py —
    carry real null masks)."""
    name: str
    num_rows: int
    arrays: Dict[str, np.ndarray]
    types: Dict[str, Type]
    dicts: Dict[str, StringDict]
    nulls: Optional[Dict[str, np.ndarray]] = None

    #: whether `page` keeps the device columns it builds (no dataclass
    #: field: a `row_slice` window turns it off on itself)
    keeps_device_columns = True

    def column_names(self) -> List[str]:
        return list(self.types)      # schema insertion order

    def null_mask(self, c: str) -> Optional[np.ndarray]:
        if self.nulls is None:
            return None
        m = self.nulls.get(c)
        return m[:self.num_rows] if m is not None else None

    def row_slice(self, lo: int, hi: int) -> "HostTable":
        """A [lo, hi) row window as a VIEW table: numpy slices share the
        parent's buffers and StringDicts — no copy. The window is
        throwaway by design (streaming scan runs exist because the split
        does not fit: each run is put up once), so its `page` keeps no
        device column; `split` is the window that is kept. A column is
        sliced when the window's column is first read, so a lazy table
        (parquet, a generated column of text) makes only what is
        scanned."""
        # the closures hold the parent's mappings and not the parent: a
        # split view is kept on it, and a cycle would leave both, with
        # their device columns, to the cyclic collector
        parent, parent_nulls = self.arrays, self.nulls
        arrays = LazyColumns(lambda c: parent[c][lo:hi], self.column_names())
        nulls = None
        if parent_nulls is not None:
            def mask(c: str) -> np.ndarray:
                parent[c]          # a file's mask comes with its column
                m = parent_nulls.get(c)
                if m is None:
                    raise KeyError(c)
                return m[lo:hi]
            nulls = LazyColumns(mask)
        view = HostTable(self.name, hi - lo, arrays, self.types,
                         self.dicts, nulls)
        view.keeps_device_columns = False
        return view

    def split(self, part: int, num_parts: int) -> "HostTable":
        """Row range `part` of `num_parts` as a view, made once and kept
        on THIS instance: every task that scans the split gets the same
        view, so the device columns its `page` keeps outlive the task
        and die with this table. A connector that replaces a written
        table's instance (connectors/memory.py) thereby drops every
        split of the old version with it — no version check to race."""
        if num_parts == 1:
            return self
        views = self.__dict__.setdefault("_split_views", {})
        view = views.get((part, num_parts))
        if view is None:
            lo, hi = _slice_rows(self.num_rows, part, num_parts)
            view = self.row_slice(lo, hi)
            view.keeps_device_columns = True
            # two tasks may get here at once: one view wins, for both
            view = views.setdefault((part, num_parts), view)
        return view

    def _device_column(self, c: str, cap: int):
        t = self.types[c]
        if t.name in ("array", "map", "row"):
            from presto_tpu.data.column import NestedColumn
            return NestedColumn.from_pylist(
                list(self.arrays[c][:self.num_rows]), t, cap)
        if getattr(t, "uses_int128", False):
            # DECIMAL(p>18) at rest: python-int unscaled values
            # -> four 32-bit limb lanes (exact 38-digit range)
            from presto_tpu.data.column import Decimal128Column
            return Decimal128Column.from_unscaled_ints(
                list(self.arrays[c][:self.num_rows]), t,
                nulls=self.null_mask(c), capacity=cap)
        return Column.from_numpy(
            self.arrays[c][:self.num_rows], t, nulls=self.null_mask(c),
            dictionary=self.dicts.get(c), capacity=cap)

    def page(self, columns: Optional[Sequence[str]] = None,
             capacity: Optional[int] = None) -> Page:
        cols = list(columns) if columns is not None else self.column_names()
        cap = capacity or bucket_capacity(self.num_rows)
        # per-(column, capacity) DEVICE columns, kept on this instance:
        # re-executions, sibling islands and the next statement's tasks
        # find them resident instead of moving hundreds of MB from host
        # to device again. Different column subsets share entries
        # because keeping is per column. Whole tables (lru-cached
        # _gen_table / MemoryConnector.tables entries) and the split
        # views a connector's table(part=...) hands out (`split`) are
        # kept alike, under one budget (connectors/scan_cache.py); a
        # `row_slice` window keeps nothing.
        kept = self.__dict__.setdefault("_dev_page_cache", KeptColumns())
        out = []
        resident = evicted = 0
        for c in cols:
            key = (c, cap)
            col = kept.get(key)
            if col is not None:
                SCAN_CACHE.hit(kept, key)
                resident += page_nbytes(col)
            else:
                SCAN_CACHE.miss()
                col = self._device_column(c, cap)
                if self.keeps_device_columns:
                    evicted += SCAN_CACHE.keep(kept, key, col,
                                               page_nbytes(col))
            out.append(col)
        # the page's row count is four bytes on the device too, kept
        # beside the columns and outside the budget
        rows = kept.num_rows
        if rows is not None:
            resident += rows.nbytes
        page = Page.from_columns(
            out, self.num_rows if rows is None else rows, cols)
        if self.keeps_device_columns:
            kept.num_rows = page.num_rows
        # what the device already holds is not moved: the `upload` span
        # around this call counts the rest
        if resident:
            TRACER.add("upload", resident=resident)
        if evicted:
            TRACER.add("upload", evicted=evicted)
        return page


def _dictify(values: np.ndarray) -> Tuple[np.ndarray, StringDict]:
    d, codes = StringDict.build(values)
    return codes, d


def _slice_rows(total: int, part: int, num_parts: int) -> Tuple[int, int]:
    per = (total + num_parts - 1) // num_parts
    lo = min(part * per, total)
    hi = min(lo + per, total)
    return lo, hi


def _seed(name: str, sf: float, part: int) -> int:
    """Stable across processes (python hash() is per-process randomized —
    workers on different hosts must regenerate identical splits)."""
    import zlib
    return zlib.crc32(f"{name}|{sf}|{part}".encode())


def _generated(name: str, n: int, arrays: Dict[str, np.ndarray],
               dicts: Dict[str, StringDict],
               later: Dict[str, Callable[[], Tuple[np.ndarray, StringDict]]]
               ) -> HostTable:
    """The table `name` of `n` rows: `arrays` and `dicts` as drawn, and
    the columns of `later` made on first read — once, whichever of its
    codes or its dictionary is asked for first, under a `generate` span
    and the connector's two counters."""
    lock = threading.Lock()

    def make(c: str) -> Tuple[np.ndarray, StringDict]:
        with lock:
            if c not in made:
                with TRACER.span(None, "generate", table=name, column=c,
                                 rows=n) as s:
                    t0 = time.perf_counter()
                    codes, d = later[c]()
                    seconds = time.perf_counter() - t0
                    s.attributes["bytes"] = nbytes = (
                        codes.nbytes + sum(map(len, d.words)))
                _GENERATED_BYTES.inc(nbytes, table=name)
                _GENERATE_SECONDS.inc(seconds, table=name)
                made[c] = codes, d
            return made[c]

    made: Dict[str, Tuple[np.ndarray, StringDict]] = {}
    types = dict(TPCH_SCHEMA[name])
    return HostTable(
        name, n,
        LazyColumns(lambda c: make(c)[0], types, arrays),
        types,
        LazyColumns(lambda c: make(c)[1], [*dicts, *later], dicts))


@functools.lru_cache(maxsize=64)
def _gen_table(name: str, sf: float) -> HostTable:
    # Whole-table generation only: splits are row-range slices served by
    # TpchConnector.table() so codes share one table-wide StringDict.
    part, num_parts = 0, 1
    c = _counts(sf)
    rng = np.random.default_rng(
        _seed(name if name != "lineitem" else "orders", sf, part))
    arrays: Dict[str, np.ndarray] = {}
    dicts: Dict[str, StringDict] = {}
    later: Dict[str, Callable[[], Tuple[np.ndarray, StringDict]]] = {}

    def put_coded(col: str, words: Sequence[str], idx: np.ndarray):
        arrays[col], dicts[col] = _coded(words, idx)

    if name == "region":
        lo, hi = _slice_rows(5, part, num_parts)
        arrays["r_regionkey"] = np.arange(lo, hi, dtype=np.int64)
        put_coded("r_name", _REGIONS, np.arange(lo, hi))
        later["r_comment"] = _later(rng, lambda g: _comment(g, hi - lo))
        n = hi - lo
    elif name == "nation":
        lo, hi = _slice_rows(25, part, num_parts)
        arrays["n_nationkey"] = np.arange(lo, hi, dtype=np.int64)
        put_coded("n_name", [x[0] for x in _NATIONS], np.arange(lo, hi))
        arrays["n_regionkey"] = np.asarray(
            [x[1] for x in _NATIONS], dtype=np.int64)[lo:hi]
        later["n_comment"] = _later(rng, lambda g: _comment(g, hi - lo))
        n = hi - lo
    elif name == "supplier":
        lo, hi = _slice_rows(c["supplier"], part, num_parts)
        n = hi - lo
        key = np.arange(lo + 1, hi + 1, dtype=np.int64)
        arrays["s_suppkey"] = key
        later["s_name"] = lambda: _dictify(_keyed_names("Supplier#", key))
        later["s_address"] = _later(rng, lambda g: _comment(g, n, 2),
                                    lambda g: _comment_draws(g, n, 2))
        nat = rng.integers(0, 25, size=n)
        arrays["s_nationkey"] = nat.astype(np.int64)
        later["s_phone"] = _later(rng, lambda g: _phone(g, nat),
                                  lambda g: _phone_draws(g, n))
        arrays["s_acctbal"] = np.round(
            rng.uniform(-999.99, 9999.99, size=n), 2)
        later["s_comment"] = _later(rng, lambda g: _supplier_comment(g, n))
    elif name == "customer":
        lo, hi = _slice_rows(c["customer"], part, num_parts)
        n = hi - lo
        key = np.arange(lo + 1, hi + 1, dtype=np.int64)
        arrays["c_custkey"] = key
        later["c_name"] = lambda: _dictify(_keyed_names("Customer#", key))
        later["c_address"] = _later(rng, lambda g: _comment(g, n, 2),
                                    lambda g: _comment_draws(g, n, 2))
        nat = rng.integers(0, 25, size=n)
        arrays["c_nationkey"] = nat.astype(np.int64)
        later["c_phone"] = _later(rng, lambda g: _phone(g, nat),
                                  lambda g: _phone_draws(g, n))
        arrays["c_acctbal"] = np.round(
            rng.uniform(-999.99, 9999.99, size=n), 2)
        put_coded("c_mktsegment", _SEGMENTS, rng.integers(0, 5, size=n))
        later["c_comment"] = _later(rng, lambda g: _comment(g, n, 6))
    elif name == "part":
        lo, hi = _slice_rows(c["part"], part, num_parts)
        n = hi - lo
        key = np.arange(lo + 1, hi + 1, dtype=np.int64)
        arrays["p_partkey"] = key
        later["p_name"] = _later(rng, lambda g: _pname(g, n),
                                 lambda g: _pname_draws(g, n))
        mfgr = rng.integers(1, 6, size=n)
        put_coded("p_mfgr", [f"Manufacturer#{i}" for i in range(6)], mfgr)
        brand = mfgr * 10 + rng.integers(1, 6, size=n)
        put_coded("p_brand", [f"Brand#{i}" for i in range(56)], brand)
        put_coded("p_type", _PTYPES, rng.integers(0, len(_PTYPES), size=n))
        arrays["p_size"] = rng.integers(1, 51, size=n).astype(np.int32)
        put_coded("p_container", _CONTAINERS,
                  rng.integers(0, len(_CONTAINERS), size=n))
        arrays["p_retailprice"] = _retailprice(key)
        later["p_comment"] = _later(rng, lambda g: _comment(g, n, 2))
    elif name == "partsupp":
        lo, hi = _slice_rows(c["part"], part, num_parts)
        n = (hi - lo) * _SUPP_PER_PART
        pk = np.repeat(np.arange(lo + 1, hi + 1, dtype=np.int64),
                       _SUPP_PER_PART)
        j = np.tile(np.arange(_SUPP_PER_PART, dtype=np.int64), hi - lo)
        arrays["ps_partkey"] = pk
        arrays["ps_suppkey"] = _part_suppliers(pk, j, c["supplier"])
        arrays["ps_availqty"] = rng.integers(
            1, 10000, size=n).astype(np.int32)
        arrays["ps_supplycost"] = np.round(
            rng.uniform(1.0, 1000.0, size=n), 2)
        later["ps_comment"] = _later(rng, lambda g: _comment(g, n, 6))
    elif name in ("orders", "lineitem"):
        return _gen_orders_lineitem(name, sf)
    else:
        raise KeyError(name)

    return _generated(name, n, arrays, dicts, later)


@functools.lru_cache(maxsize=32)
def _gen_orders_lineitem(which: str, sf: float) -> HostTable:
    """Orders and their lineitems generate together (totalprice is the sum
    of its lines). Whole-table only — splits are slices, see table()."""
    part, num_parts = 0, 1
    c = _counts(sf)
    rng = np.random.default_rng(_seed("orders", sf, part))
    lo, hi = _slice_rows(c["orders"], part, num_parts)
    n = hi - lo
    okey = np.arange(lo + 1, hi + 1, dtype=np.int64)
    # Customers with c%3==0 never order (dbgen leaves 1/3 of customers
    # orderless — exercised by Q13/Q22).
    ck = rng.integers(1, c["customer"] + 1, size=n).astype(np.int64)
    ck = np.where(ck % 3 == 0, (ck % (c["customer"] - 1)) + 1, ck)
    ck = np.where(ck % 3 == 0, ck + 1, ck)
    # Clause 4.2.3: O_ORDERDATE uniform in [STARTDATE, ENDDATE - 151 days],
    # both ends drawn; the 1..121 days a line takes to ship come on top.
    # The dates have a stream of their own, and `rng` still makes the draw
    # that stopped 121 days short: a bounded draw rejects now and then, so
    # another bound moves every later column and lineitem's row count.
    odate = np.random.default_rng(_seed("orders.o_orderdate", sf, part)
                                  ).integers(_MIN_DATE, _MAX_ORDER_DATE + 1,
                                             size=n).astype(np.int32)
    rng.integers(_MIN_DATE, _MAX_ORDER_DATE - 121, size=n)

    nlines = rng.integers(1, 8, size=n)
    total_lines = int(nlines.sum())
    l_okey = np.repeat(okey, nlines)
    l_odate = np.repeat(odate, nlines)
    starts = np.concatenate([[0], np.cumsum(nlines)[:-1]])
    l_lineno = (np.arange(total_lines) -
                np.repeat(starts, nlines) + 1).astype(np.int32)

    pk = rng.integers(1, c["part"] + 1, size=total_lines).astype(np.int64)
    j = rng.integers(0, _SUPP_PER_PART, size=total_lines).astype(np.int64)
    sk = _part_suppliers(pk, j, c["supplier"])
    qty = rng.integers(1, 51, size=total_lines).astype(np.float64)
    eprice = qty * _retailprice(pk)
    disc = rng.integers(0, 11, size=total_lines) / 100.0
    tax = rng.integers(0, 9, size=total_lines) / 100.0
    sdate = (l_odate + rng.integers(1, 122, size=total_lines)).astype(np.int32)
    cdate = (l_odate + rng.integers(30, 91, size=total_lines)).astype(np.int32)
    rdate = (sdate + rng.integers(1, 31, size=total_lines)).astype(np.int32)
    # a line received by CURRENTDATE is R or A, one chance in two; N else
    half = rng.random(total_lines) < 0.5
    is_open = sdate > _CURRENT          # l_linestatus O, else F
    arrays: Dict[str, np.ndarray] = {}
    dicts: Dict[str, StringDict] = {}

    def put_coded(col: str, words: Sequence[str], idx: np.ndarray):
        arrays[col], dicts[col] = _coded(words, idx)

    if which == "lineitem":
        arrays.update({
            "l_orderkey": l_okey, "l_partkey": pk, "l_suppkey": sk,
            "l_linenumber": l_lineno, "l_quantity": qty,
            "l_extendedprice": eprice, "l_discount": disc, "l_tax": tax,
            "l_shipdate": sdate, "l_commitdate": cdate,
            "l_receiptdate": rdate,
        })
        put_coded("l_returnflag", ("R", "A", "N"),
                  np.where(rdate <= _CURRENT, np.where(half, 0, 1), 2))
        put_coded("l_linestatus", ("O", "F"), np.where(is_open, 0, 1))
        put_coded("l_shipinstruct", _INSTRUCTS,
                  rng.integers(0, 4, size=total_lines))
        put_coded("l_shipmode", _SHIPMODES,
                  rng.integers(0, 7, size=total_lines))
        later = {"l_comment": _later(
            rng, lambda g: _comment(g, total_lines, 3))}
        return _generated("lineitem", total_lines, arrays, dicts, later)

    # orders
    line_total = eprice * (1.0 + tax) * (1.0 - disc)
    totalprice = np.add.reduceat(line_total, starts)
    any_open = np.add.reduceat(is_open.astype(np.int64), starts)
    arrays.update({"o_orderkey": okey, "o_custkey": ck,
                   "o_totalprice": np.round(totalprice, 2),
                   "o_orderdate": odate,
                   "o_shippriority": np.zeros(n, dtype=np.int32)})
    put_coded("o_orderstatus", ("F", "O", "P"),
              np.where(any_open == 0, 0, np.where(any_open == nlines, 1, 2)))
    put_coded("o_orderpriority", _PRIORITIES, rng.integers(0, 5, size=n))
    clerks = max(2, int(1000 * sf))
    put_coded("o_clerk", [f"Clerk#{i:09d}" for i in range(clerks + 1)],
              rng.integers(1, clerks + 1, size=n))
    later = {"o_comment": _later(rng, lambda g: _comment(g, n, 5))}
    return _generated("orders", n, arrays, dicts, later)


from presto_tpu.connectors.base import SplitSource


class TpchConnector(SplitSource):
    NAME = "tpch"
    """Connector facade: schema + partitioned table generation.

    Reference surface: ConnectorMetadata + ConnectorSplitManager +
    ConnectorPageSource (presto-spi/.../ConnectorPageSource.java), collapsed
    into the two calls an in-memory generated source actually needs."""

    def __init__(self, scale_factor: float = 0.01):
        self.scale_factor = scale_factor

    def schema(self, table: str) -> List[Tuple[str, Type]]:
        return TPCH_SCHEMA[table]

    def row_count(self, table: str) -> int:
        """Planner statistics (reference role: connector-provided
        TableStatistics feeding the CBO, cost/ package)."""
        c = _counts(self.scale_factor)
        if table in c:
            return c[table]
        if table == "partsupp":
            return c["part"] * _SUPP_PER_PART
        if table == "lineitem":
            return c["orders"] * 4
        raise KeyError(table)

    def table(self, name: str, part: int = 0, num_parts: int = 1
              ) -> HostTable:
        """Full table (cached), or split `part` of `num_parts` as a
        row-range slice of it (`HostTable.split`: one view a split, kept
        on the full table). Slices share the full table's StringDicts,
        so codes are globally consistent — the property every cross-device
        exchange and dictionary-aligned operator relies on (reference
        analogue: TpchSplitManager handing row ranges of one logical
        table, presto-tpch/.../TpchSplitManager.java)."""
        if name not in TPCH_SCHEMA:
            raise KeyError(f"unknown tpch table {name}")
        full = _gen_table(name, self.scale_factor)  # lru_cached
        return full.split(part, num_parts)


class TpchTextOnReadConnector(TpchConnector):
    """TPC-H under the name a configuration gives when its tables fit the
    host only because text columns wait for their first read: SF10's
    lineitem made whole passes 37 GB of host memory, its columns but
    `l_comment` take 6.8 GB. Tables, splits and connector id are
    TpchConnector's. A generator that builds every column at once has no
    connector by this name, so such a configuration stops there at once
    instead of filling the host."""

    NAME = "tpch_text_on_read"

    def connector_id(self, table: Optional[str] = None) -> str:
        return TpchConnector.NAME
