"""Parquet connector — columnar files -> engine Pages.

Reference roles: presto-parquet's reader
(presto-parquet/.../reader/ParquetReader.java — predicate/projection
pushdown into row groups, dictionary pages, nested columns) +
presto-hive's directory/split model
(BackgroundHiveSplitLoader.java: a table is a directory of files, a
split is a file byte-range — here a row-group range, parquet's natural
split unit via ParquetPageSourceFactory).

TPU-first realization:
- **Projection pushdown**: columns load LAZILY — `page(columns=...)`
  touches only the requested columns, and each loads straight from the
  column chunk (never the whole file).
- **Dictionary pages**: string columns read as Arrow dictionary arrays
  (the parquet dictionary page survives decode), then remap into the
  engine's *sorted* StringDict codes — one vectorized indirection, no
  per-value python.
- **Row-group statistics**: `column_minmax()` serves min/max from file
  metadata without reading data; the lifespan dynamic filter and split
  pruning consult it.
- **Multi-file tables**: `<dir>/<table>/` holds N parquet files
  (Hive-style layout); `<dir>/<table>.parquet` stays the single-file
  form. Splits are (file, row-group) pairs.
- **Nested columns**: arrow list/map/struct map to the engine's
  ARRAY/MAP/ROW with offset-encoded NestedColumns.

The decode layer is pyarrow (in-image), playing the role the reference
delegates to its parquet-mr-derived decoder; everything above it —
lazy projection, split construction, statistics pruning, the
dictionary-code remap, type mapping — is this connector.
"""

from __future__ import annotations

import os
from typing import Dict, List, Optional, Sequence, Tuple

import numpy as np

from presto_tpu.connectors.base import SplitSource
from presto_tpu.connectors.tpch import HostTable, LazyColumns, _slice_rows
from presto_tpu.data.column import StringDict
from presto_tpu.types import (
    BIGINT, BOOLEAN, DATE, DOUBLE, INTEGER, REAL, SMALLINT, TIMESTAMP,
    TINYINT, VARCHAR, ArrayType, DecimalType, MapType, RowType, Type,
)


def _arrow_to_type(t) -> Type:
    import pyarrow as pa

    if pa.types.is_boolean(t):
        return BOOLEAN
    if pa.types.is_int8(t):
        return TINYINT
    if pa.types.is_int16(t):
        return SMALLINT
    if pa.types.is_int32(t):
        return INTEGER
    if pa.types.is_int64(t):
        return BIGINT
    if pa.types.is_float32(t):
        return REAL
    if pa.types.is_float64(t):
        return DOUBLE
    if pa.types.is_date32(t) or pa.types.is_date64(t):
        return DATE
    if pa.types.is_timestamp(t):
        return TIMESTAMP
    if pa.types.is_decimal(t):
        return DecimalType(t.precision, t.scale)
    if pa.types.is_string(t) or pa.types.is_large_string(t):
        return VARCHAR
    if pa.types.is_dictionary(t):
        return _arrow_to_type(t.value_type)
    if pa.types.is_list(t) or pa.types.is_large_list(t):
        return ArrayType(_arrow_to_type(t.value_type))
    if pa.types.is_map(t):
        return MapType(_arrow_to_type(t.key_type),
                       _arrow_to_type(t.item_type))
    if pa.types.is_struct(t):
        return RowType(tuple(f.name for f in t),
                       tuple(_arrow_to_type(f.type) for f in t))
    raise NotImplementedError(f"arrow type {t}")


def _type_to_arrow(t: Type):
    import pyarrow as pa

    if isinstance(t, DecimalType):
        return pa.decimal128(t.precision, t.scale)
    if isinstance(t, ArrayType):
        return pa.list_(_type_to_arrow(t.element))
    if isinstance(t, MapType):
        return pa.map_(_type_to_arrow(t.key), _type_to_arrow(t.value))
    if isinstance(t, RowType):
        return pa.struct([pa.field(n, _type_to_arrow(ft))
                          for n, ft in zip(t.field_names, t.field_types)])
    return {
        "boolean": pa.bool_(), "tinyint": pa.int8(),
        "smallint": pa.int16(), "integer": pa.int32(),
        "bigint": pa.int64(), "real": pa.float32(),
        "double": pa.float64(), "date": pa.date32(),
        "timestamp": pa.timestamp("us"), "varchar": pa.string(),
        "char": pa.string(),
    }[t.name]


def _decode_column(col, t: Type):
    """One arrow ChunkedArray -> (values ndarray, nulls ndarray,
    StringDict|None). The engine's storage forms (codes into a sorted
    dictionary, unscaled decimal ints, epoch integers)."""
    import pyarrow as pa
    import pyarrow.compute as pc

    col = col.combine_chunks()
    mask = np.asarray(col.is_null())
    if t.is_string:
        # dictionary-page path: decode keeps (indices, dictionary);
        # remap the file dictionary onto the engine's sorted dictionary
        # with one vectorized take
        if not pa.types.is_dictionary(col.type):
            col = pc.dictionary_encode(col)
        dict_words = col.dictionary.to_pylist()
        indices = np.asarray(col.indices.fill_null(0),
                             dtype=np.int32)
        d, remap = StringDict.build(
            ["" if w is None else w for w in dict_words] or [""])
        codes = np.asarray(remap, dtype=np.int32)[
            np.clip(indices, 0, max(len(dict_words) - 1, 0))]
        return codes, mask, d
    if t.is_decimal:
        vals = col.to_pylist()
        if t.uses_int128:
            arr = np.empty(len(vals), object)
            arr[:] = [0 if v is None else int(v.scaleb(t.scale))
                      for v in vals]
            return arr, mask, None
        return np.asarray(
            [0 if v is None else int(v.scaleb(t.scale)) for v in vals],
            dtype=np.int64), mask, None
    if t.name == "timestamp":
        us = col.cast(pa.timestamp("us")).cast(pa.int64())
        return np.asarray(us.fill_null(0), dtype=np.int64), mask, None
    if t.name == "date":
        return np.asarray(col.cast(pa.date32()).cast(pa.int32())
                          .fill_null(0), dtype=np.int32), mask, None
    if t.name in ("array", "map", "row"):
        arr = np.empty(len(col), object)
        arr[:] = col.to_pylist()
        return arr, mask, None
    if t.name == "boolean":
        return np.asarray(col.fill_null(False), dtype=bool), mask, None
    return (np.asarray(col.fill_null(0)).astype(t.dtype), mask, None)


class LazyFileTable(HostTable):
    """Base for lazily-loading file-format tables: the null-mask cache
    rides the same lazy column load."""

    def null_mask(self, c: str):
        if c not in self._nulls:
            _ = self.arrays[c]          # triggers the lazy load
        m = self._nulls.get(c)
        return m[:self.num_rows] if m is not None else None


class ParquetTable(LazyFileTable):
    """Lazily-loading HostTable over one or more parquet files.
    `files` shares already-open ParquetFile handles (split/prune
    derivatives must not re-open and re-parse every file's metadata)."""

    def __init__(self, name: str, paths: List[str],
                 row_groups: Optional[List[Tuple[int, int]]] = None,
                 files=None):
        import pyarrow.parquet as pq

        self.paths = paths
        self._files = (files if files is not None
                       else [pq.ParquetFile(p) for p in paths])
        # (file index, row group index) units — the split currency
        self.units = (row_groups if row_groups is not None
                      else [(fi, g) for fi, f in enumerate(self._files)
                            for g in range(f.metadata.num_row_groups)])
        schema = self._files[0].schema_arrow
        types = {f.name: _arrow_to_type(f.type) for f in schema}
        n = sum(self._files[fi].metadata.row_group(g).num_rows
                for fi, g in self.units)
        self._dicts: Dict[str, StringDict] = {}
        self._nulls: Dict[str, np.ndarray] = {}
        super().__init__(name, n, LazyColumns(self._load_column), types,
                         self._dicts, self._nulls)

    def unit_rows(self, unit: Tuple[int, int]) -> int:
        fi, g = unit
        return self._files[fi].metadata.row_group(g).num_rows

    # -- lazy column load (projection pushdown) -------------------------
    def _load_column(self, col: str):
        import pyarrow as pa

        t = self.types[col]
        chunks = []
        for fi, g in self.units:
            chunks.append(self._files[fi].read_row_group(
                g, columns=[col]).column(0))
        merged = pa.chunked_array([c for ch in chunks
                                   for c in ch.chunks]) \
            if chunks else pa.chunked_array([], type=pa.int64())
        vals, nulls, d = _decode_column(merged, t)
        if d is not None:
            self._dicts[col] = d
        self._nulls[col] = nulls
        return vals

    # -- row-group statistics (predicate pushdown support) --------------
    def _leaf_index(self, col: str) -> Optional[int]:
        """Row-group metadata enumerates FLATTENED LEAF columns (a
        nested column contributes one entry per leaf, e.g.
        'a.list.element'), so arrow top-level schema positions misalign
        the moment any earlier column is nested. Map by exact leaf
        path instead; a name that is not itself a leaf (nested column)
        has no usable scalar stats -> None."""
        md = self._files[0].metadata
        for i in range(md.num_columns):
            if md.schema.column(i).path == col:
                return i
        return None

    def _stat_value(self, v, col: str):
        """One parquet stat value -> the engine's storage
        representation for the column's type (epoch days / epoch
        microseconds / python str / unscaled decimal int), so callers
        can compare stats against engine values directly."""
        import datetime
        import decimal as _dec

        if v is None:
            return None
        t = self.types.get(col)
        if t is None:
            return v
        if t.name == "date":
            if isinstance(v, datetime.date) \
                    and not isinstance(v, datetime.datetime):
                return (v - datetime.date(1970, 1, 1)).days
            return int(v)
        if t.name == "timestamp":
            if isinstance(v, datetime.datetime):
                epoch = datetime.datetime(1970, 1, 1,
                                          tzinfo=v.tzinfo)
                return int((v - epoch) / datetime.timedelta(
                    microseconds=1))
            return int(v)
        if t.is_string:
            return v.decode("utf-8", "replace") \
                if isinstance(v, bytes) else str(v)
        if t.is_decimal:
            if isinstance(v, _dec.Decimal):
                return int(v.scaleb(t.scale))
            return v
        return v

    def column_minmax(self, col: str):
        """(min, max) from row-group metadata WITHOUT reading data, in
        engine representation; None when the column is nested or any
        unit lacks statistics. Reference: TupleDomainParquetPredicate
        over ColumnChunkMetaData stats."""
        idx = self._leaf_index(col)
        if idx is None:
            return None
        los, his = [], []
        for fi, g in self.units:
            meta = self._files[fi].metadata.row_group(g)
            st = meta.column(idx).statistics
            if st is None or not st.has_min_max:
                return None
            los.append(self._stat_value(st.min, col))
            his.append(self._stat_value(st.max, col))
        if not los:
            return None
        return min(los), max(his)

    def prune_units(self, col: str, lo, hi) -> "ParquetTable":
        """Row groups whose [min, max] cannot intersect [lo, hi] drop
        out of the split list (the reader's row-group skip). `lo`/`hi`
        are engine-representation values; stats normalize to match.
        Unknown/nested columns and incomparable stats keep every unit
        (pruning is an optimization, never a correctness gate)."""
        idx = self._leaf_index(col)
        if idx is None:
            return self
        kept = []
        for fi, g in self.units:
            st = self._files[fi].metadata.row_group(g).column(
                idx).statistics
            if st is None or not st.has_min_max:
                kept.append((fi, g))
                continue
            try:
                if self._stat_value(st.max, col) < lo \
                        or self._stat_value(st.min, col) > hi:
                    continue
            except TypeError:
                kept.append((fi, g))
                continue
            kept.append((fi, g))
        if len(kept) == len(self.units):
            return self
        return ParquetTable(self.name, self.paths, kept,
                            files=self._files)


def read_parquet_table(path: str, name: str) -> ParquetTable:
    """One parquet file (or a directory of them) -> lazy table."""
    if os.path.isdir(path):
        paths = sorted(os.path.join(path, f) for f in os.listdir(path)
                       if f.endswith(".parquet"))
        if not paths:
            raise FileNotFoundError(f"no parquet files under {path}")
        return ParquetTable(name, paths)
    return ParquetTable(name, [path])


def rows_to_arrow_table(rows: List[tuple],
                        schema: Sequence[Tuple[str, Type]]):
    """Engine result rows (to_pylist shape) -> pa.Table with engine
    value coercion (python Decimals, epoch-day dates) — THE shared
    write-side conversion for every file format (parquet, orc)."""
    import pyarrow as pa

    cols = []
    fields = []
    for i, (name, t) in enumerate(schema):
        vals = [r[i] for r in rows]
        if isinstance(t, DecimalType):
            from decimal import Decimal
            vals = [None if v is None else
                    (v if isinstance(v, Decimal)
                     else Decimal(str(round(v, t.scale)))) for v in vals]
        if t.name == "date":
            import datetime
            epoch = datetime.date(1970, 1, 1)
            vals = [None if v is None else
                    (v if isinstance(v, datetime.date)
                     else epoch + datetime.timedelta(days=int(v)))
                    for v in vals]
        fields.append(pa.field(name, _type_to_arrow(t)))
        cols.append(pa.array(vals, type=_type_to_arrow(t)))
    return pa.Table.from_arrays(cols, schema=pa.schema(fields))


def write_parquet_table(path: str, rows: List[tuple],
                        schema: Sequence[Tuple[str, Type]],
                        row_group_size: Optional[int] = None):
    """Engine result rows (to_pylist shape) -> one parquet file."""
    import pyarrow.parquet as pq

    pq.write_table(rows_to_arrow_table(rows, schema), path,
                   row_group_size=row_group_size)


def write_host_table(table: HostTable, path: str,
                     row_group_size: Optional[int] = None) -> None:
    """Vectorized HostTable -> parquet (no per-row python): numeric
    arrays pass straight through; string columns become arrow
    DictionaryArrays from their codes (dictionary pages on disk)."""
    import pyarrow as pa
    import pyarrow.parquet as pq

    n = table.num_rows
    fields, cols = [], []
    for c in table.column_names():
        t = table.types[c]
        mask = table.null_mask(c)
        if t.is_string:
            codes = np.asarray(table.arrays[c][:n], dtype=np.int32)
            words = list(table.dicts[c].words)
            arr = pa.DictionaryArray.from_arrays(
                pa.array(codes, type=pa.int32(),
                         mask=None if mask is None else mask),
                pa.array(words or [""], type=pa.string()))
            fields.append(pa.field(c, arr.type))
        elif t.is_decimal and not t.uses_int128:
            from decimal import Decimal
            vals = [Decimal(int(v)).scaleb(-t.scale)
                    for v in np.asarray(table.arrays[c][:n])]
            arr = pa.array(vals, type=pa.decimal128(t.precision, t.scale),
                           mask=None if mask is None else mask)
            fields.append(pa.field(c, arr.type))
        elif t.name == "date":
            arr = pa.array(np.asarray(table.arrays[c][:n],
                                      dtype=np.int32),
                           type=pa.date32(),
                           mask=None if mask is None else mask)
            fields.append(pa.field(c, arr.type))
        else:
            arr = pa.array(np.asarray(table.arrays[c][:n]),
                           mask=None if mask is None else mask)
            fields.append(pa.field(c, arr.type))
        cols.append(arr)
    pq.write_table(
        pa.Table.from_arrays(cols, schema=pa.schema(fields)), path,
        row_group_size=row_group_size)


def materialize_connector(conn, directory: str, tables: List[str],
                          row_group_size: Optional[int] = None) -> None:
    """Serialize a connector's tables into a parquet directory catalog
    (the fixture -> lakehouse bridge the scan bench uses)."""
    os.makedirs(directory, exist_ok=True)
    for t in tables:
        out = os.path.join(directory, f"{t}.parquet")
        if not os.path.exists(out):
            write_host_table(conn.table(t), out,
                             row_group_size=row_group_size)


class FileCatalogConnector(SplitSource):
    """Shared directory-catalog mechanics for file formats:
    `<dir>/<table>.<ext>` (single file) or `<dir>/<table>/`
    (multi-file, Hive-style); splits are unit ranges; an optional
    fallback serves other names (multi-catalog facade). Subclasses
    supply EXT, `_open(path, name)` and `_slice(full, name, units)`."""

    EXT = ""

    def __init__(self, directory: str, fallback=None):
        self.directory = directory
        self.fallback = fallback
        self._cache: Dict[str, HostTable] = {}

    def _open(self, path: str, name: str) -> HostTable:
        raise NotImplementedError

    def _slice(self, full, name: str, units) -> HostTable:
        raise NotImplementedError

    def _path(self, table: str) -> Optional[str]:
        p = os.path.join(self.directory, f"{table}.{self.EXT}")
        if os.path.exists(p):
            return p
        d = os.path.join(self.directory, table)
        if os.path.isdir(d):
            return d
        return None

    def _load(self, table: str):
        if table in self._cache:
            return self._cache[table]
        p = self._path(table)
        if p is None:
            return None
        t = self._open(p, table)
        self._cache[table] = t
        return t

    def schema(self, table: str) -> List[Tuple[str, Type]]:
        t = self._load(table)
        if t is None:
            if self.fallback is not None:
                return self.fallback.schema(table)
            raise KeyError(f"unknown table {table}")
        return [(c, t.types[c]) for c in t.column_names()]

    def row_count(self, table: str) -> int:
        t = self._load(table)
        if t is None:
            if self.fallback is not None:
                return self.fallback.row_count(table)
            raise KeyError(f"unknown table {table}")
        return t.num_rows

    def table(self, name: str, part: int = 0, num_parts: int = 1
              ) -> HostTable:
        full = self._load(name)
        if full is None:
            if self.fallback is not None:
                return self.fallback.table(name, part, num_parts)
            raise KeyError(f"unknown table {name}")
        if num_parts == 1:
            return full
        # split by UNIT ranges (row groups / stripes) when the layout
        # allows it — a split then reads only its own column chunks —
        # falling back to row slices when there are fewer units
        if len(full.units) >= num_parts:
            lo, hi = _slice_rows(len(full.units), part, num_parts)
            return self._slice(full, name, full.units[lo:hi])
        lo, hi = _slice_rows(full.num_rows, part, num_parts)
        arrays = {c: full.arrays[c][lo:hi] for c in full.column_names()}
        nulls = {c: full.null_mask(c)[lo:hi]
                 for c in full.column_names()
                 if full.null_mask(c) is not None}
        return HostTable(name, hi - lo, arrays, full.types, full.dicts,
                         nulls or None)

    def scan_runs(self, table: str, max_rows: int, part: int = 0,
                  num_parts: int = 1):
        """Streaming scans with bounded PHYSICAL IO: chunk the split's
        units (row groups / stripes) greedily so each run decodes only
        its own column chunks and holds ~max_rows rows (a single unit
        larger than max_rows still ships whole — the unit is the IO
        granularity). Splits that fell back to row slicing (fewer units
        than parts) stream by row windows instead."""
        if self._load(table) is None and self.fallback is not None:
            yield from self.fallback.scan_runs(
                table, max_rows, part=part, num_parts=num_parts)
            return
        t = self.table(table, part=part, num_parts=num_parts)
        units = getattr(t, "units", None)
        if units is not None and not units:   # empty split: one empty run
            yield t
            return
        if max_rows <= 0 or units is None:
            if max_rows > 0 and t.num_rows > max_rows:
                for lo in range(0, int(t.num_rows), max_rows):
                    yield t.row_slice(lo, min(lo + max_rows,
                                              int(t.num_rows)))
            else:
                yield t
            return
        chunk, rows = [], 0
        for u in units:
            r = t.unit_rows(u)
            if chunk and rows + r > max_rows:
                yield self._slice(t, table, chunk)
                chunk, rows = [], 0
            chunk.append(u)
            rows += r
        if chunk:
            yield self._slice(t, table, chunk)

    def invalidate(self, table: Optional[str] = None):
        """Drop cached handles after files changed on disk — the
        catalog's write signal, so it also bumps the data versions the
        fragment result cache keys on."""
        if table is None:
            for t in list(self._cache):
                self.bump_table_version(t)
            self._cache.clear()
        else:
            self._cache.pop(table, None)
            self.bump_table_version(table)

    def table_version(self, table: str) -> int:
        if self._path(table) is None and self.fallback is not None:
            return self.fallback.table_version(table)
        return super().table_version(table)


class ParquetConnector(FileCatalogConnector):
    NAME = "parquet"
    EXT = "parquet"

    def _open(self, path: str, name: str) -> "ParquetTable":
        return read_parquet_table(path, name)

    def _slice(self, full, name: str, units) -> "ParquetTable":
        return ParquetTable(name, full.paths, units,
                            files=full._files)
