"""Memory connector — writable in-memory tables.

Reference role: presto-memory (presto-memory/src/main/java/com/facebook/
presto/plugin/memory/ — MemoryMetadata/MemoryPagesStore), the standard
writable test backend. Tables live as host numpy arrays in the same
HostTable shape scans use, so written tables are immediately scannable
with the table-wide-StringDict invariant preserved.

An optional `fallback` connector provides read-through for names not
written here (the multi-catalog surface collapsed into one facade: CTAS
from tpch into memory works through a single engine connector)."""

from __future__ import annotations

from typing import Dict, List, Optional, Sequence, Tuple

import numpy as np

from presto_tpu.connectors.base import SplitSource
from presto_tpu.connectors.tpch import HostTable
from presto_tpu.data.column import StringDict
from presto_tpu.types import Type


class MemoryConnector(SplitSource):
    NAME = "memory"

    def __init__(self, fallback=None):
        import threading
        self.fallback = fallback
        self.tables: Dict[str, HostTable] = {}
        # concurrent TableWriter tasks append in parallel (reference:
        # MemoryPagesStore synchronization)
        self._write_lock = threading.Lock()

    def _record_watermark(self, name: str, version: int) -> None:
        """Pair the just-bumped version with the table's cumulative row
        count (stream/watermarks.py) so delta consumers can read "rows
        since version V". A vanished table (drop / staged-move source)
        resets its history — its row count is no longer append-only."""
        from presto_tpu.stream.watermarks import watermark_store
        store = watermark_store(self)
        t = self.tables.get(name)
        if t is None:
            store.forget(name)
        else:
            store.record(name, version, t.num_rows)

    def connector_id(self, table: str = None) -> str:
        if table is not None and table not in self.tables \
                and self.fallback is not None:
            return self.fallback.connector_id(table)
        return self.NAME

    def table_version(self, table: str) -> int:
        # locally-written tables version here; read-through names keep
        # the fallback's version stream (one facade, one version truth)
        if table not in self.tables and self.fallback is not None:
            return self.fallback.table_version(table)
        return super().table_version(table)

    # ------------------------------------------------------------- reads
    def schema(self, table: str) -> List[Tuple[str, Type]]:
        t = self.tables.get(table)
        if t is not None:
            return [(c, t.types[c]) for c in t.column_names()]
        if self.fallback is not None:
            return self.fallback.schema(table)
        raise KeyError(f"unknown table {table}")

    def row_count(self, table: str) -> int:
        t = self.tables.get(table)
        if t is not None:
            return t.num_rows
        if self.fallback is not None:
            return self.fallback.row_count(table)
        raise KeyError(f"unknown table {table}")

    def table(self, name: str, part: int = 0, num_parts: int = 1
              ) -> HostTable:
        full = self.tables.get(name)
        if full is None:
            if self.fallback is not None:
                return self.fallback.table(name, part, num_parts)
            raise KeyError(f"unknown table {name}")
        # the split view is kept on `full`, and every write replaces
        # `self.tables[name]`: a new version starts without views
        return full.split(part, num_parts)

    # ------------------------------------------------------------ writes
    def exists(self, name: str) -> bool:
        return name in self.tables

    def create(self, name: str, schema: Sequence[Tuple[str, Type]]):
        if name in self.tables:
            raise ValueError(f"table {name} already exists")
        arrays: Dict[str, np.ndarray] = {}
        dicts: Dict[str, StringDict] = {}
        types = {}
        for c, t in schema:
            types[c] = t
            if t.name in ("array", "map", "row"):
                # nested values stored as python objects host-side;
                # page() builds offset-encoded NestedColumns
                arrays[c] = np.zeros(0, object)
            elif t.is_decimal and t.uses_int128:
                # python-int unscaled values (exact 38-digit range);
                # page() builds Decimal128Column limb lanes
                arrays[c] = np.zeros(0, object)
            elif t.is_string:
                arrays[c] = np.zeros(0, np.int32)
                dicts[c] = StringDict([])
            else:
                arrays[c] = np.zeros(0, t.dtype)
        self.tables[name] = HostTable(name, 0, arrays, types, dicts)
        self._record_watermark(name, self.bump_table_version(name))

    def drop(self, name: str, if_exists: bool = False):
        if name not in self.tables and not if_exists:
            raise KeyError(f"unknown table {name}")
        if self.tables.pop(name, None) is not None:
            self._record_watermark(name, self.bump_table_version(name))

    def append_rows(self, name: str, rows: List[tuple]) -> int:
        """Append python rows (strings decoded, decimals as python
        floats — the engine's to_pylist() shape). Reference role:
        ConnectorPageSink.appendPage (MemoryPagesStore.add)."""
        with self._write_lock:
            n = self._append_rows_locked(name, rows)
            if n:
                self._record_watermark(name, self.bump_table_version(name))
            return n

    def move_table_rows(self, src: str, dst: str) -> int:
        """Move every row of `src` into `dst` (identical schemas) by raw
        array concatenation — no python-value round trip, so DECIMAL
        limbs and dictionary codes stay exact. The staged-INSERT commit
        step (reference: TableFinishOperator making sink writes visible
        atomically). Drops `src`. Returns rows moved."""
        from presto_tpu.data.column import merge_string_dicts
        with self._write_lock:
            s, t = self.tables[src], self.tables[dst]
            n_new = s.num_rows
            if n_new:
                new_arrays: Dict[str, np.ndarray] = {}
                new_dicts: Dict[str, StringDict] = dict(t.dicts)
                new_nulls: Dict[str, np.ndarray] = {}
                for c in t.column_names():
                    typ = t.types[c]
                    old_null = (t.nulls or {}).get(
                        c, np.zeros(t.num_rows, dtype=bool))[:t.num_rows]
                    src_null = (s.nulls or {}).get(
                        c, np.zeros(n_new, dtype=bool))[:n_new]
                    new_nulls[c] = np.concatenate([old_null, src_null])
                    sa = s.arrays[c][:n_new]
                    if typ.is_string:
                        union, (remap_old, remap_new) = merge_string_dicts(
                            [t.dicts[c], s.dicts[c]])
                        old_codes = t.arrays[c][:t.num_rows]
                        new_arrays[c] = np.concatenate([
                            remap_old[old_codes] if len(remap_old)
                            else old_codes,
                            remap_new[sa] if len(remap_new) else sa])
                        new_dicts[c] = union
                    else:
                        new_arrays[c] = np.concatenate(
                            [t.arrays[c][:t.num_rows], sa])
                self.tables[dst] = HostTable(
                    dst, t.num_rows + n_new, new_arrays, t.types,
                    new_dicts, new_nulls)
            self.tables.pop(src, None)
            self._record_watermark(src, self.bump_table_version(src))
            self._record_watermark(dst, self.bump_table_version(dst))
            return n_new

    def register_row_slice(self, src: str, dst: str, lo: int,
                           hi: int) -> int:
        """Register rows [lo, hi) of `src` as a temp table `dst` — a
        zero-copy array view (dicts shared, arrays sliced) backing the
        incremental-MV delta scan: the maintenance query runs against
        `dst` through the ordinary scan path and sees exactly the rows
        one watermark interval appended. Returns the view's row count;
        drop `dst` normally when done."""
        with self._write_lock:
            if dst in self.tables:
                raise ValueError(f"table {dst} already exists")
            s = self.tables[src]
            lo = max(0, min(int(lo), s.num_rows))
            hi = max(lo, min(int(hi), s.num_rows))
            arrays = {c: a[lo:hi] for c, a in s.arrays.items()}
            nulls = ({c: m[lo:hi] for c, m in s.nulls.items()}
                     if s.nulls is not None else None)
            self.tables[dst] = HostTable(dst, hi - lo, arrays, s.types,
                                         s.dicts, nulls)
            self._record_watermark(dst, self.bump_table_version(dst))
            return hi - lo

    def _append_rows_locked(self, name: str, rows: List[tuple]) -> int:
        t = self.tables[name]
        cols = t.column_names()
        n_new = len(rows)
        if n_new == 0:
            return 0
        new_arrays: Dict[str, np.ndarray] = {}
        new_dicts: Dict[str, StringDict] = dict(t.dicts)
        new_nulls: Dict[str, np.ndarray] = {}
        for i, c in enumerate(cols):
            typ = t.types[c]
            vals = [r[i] for r in rows]
            old_null = (t.nulls or {}).get(
                c, np.zeros(t.num_rows, dtype=bool))[:t.num_rows]
            new_nulls[c] = np.concatenate(
                [old_null, np.asarray([v is None for v in vals], bool)])
            if typ.name in ("array", "map", "row"):
                arr = np.empty(n_new, object)
                arr[:] = vals
                new_arrays[c] = np.concatenate(
                    [t.arrays[c][:t.num_rows], arr])
            elif typ.is_string:
                # merge into one table-wide sorted dictionary, remapping
                # existing codes (the shared cross-page dictionary
                # machinery, data/column.merge_string_dicts)
                from presto_tpu.data.column import merge_string_dicts
                new_words, new_codes = StringDict.build(
                    ["" if v is None else v for v in vals])
                union, (remap_old, remap_new) = merge_string_dicts(
                    [t.dicts[c], new_words])
                if union.words == t.dicts[c].words:
                    # no new words: keep the OLD dict object — it is
                    # identity-hashed jit aux data, so swapping in an
                    # equal copy would invalidate every compiled
                    # program scanning this table (steady-state ingest
                    # would recompile per batch)
                    union = t.dicts[c]
                old_codes = t.arrays[c][:t.num_rows]
                old_new = (remap_old[old_codes] if len(remap_old)
                           else old_codes)
                new_arrays[c] = np.concatenate(
                    [old_new, remap_new[new_codes]])
                new_dicts[c] = union
            else:
                filled = [0 if v is None else v for v in vals]
                if typ.is_decimal and typ.uses_int128:
                    # DECIMAL(p>18): python-int unscaled values in an
                    # object array — exact for the full 38-digit range
                    # (int64 storage capped exactness at 2^63; the page
                    # builds four 32-bit limb lanes from these)
                    from presto_tpu.data.column import unscale_decimal
                    arr = np.empty(n_new, object)
                    arr[:] = [int(unscale_decimal(v, typ.scale))
                              for v in filled]
                elif typ.is_decimal:
                    # exact unscale, one shared rounding rule
                    from presto_tpu.data.column import unscale_decimal
                    arr = np.asarray(
                        [unscale_decimal(v, typ.scale) for v in filled],
                        np.int64)
                else:
                    arr = np.asarray(filled, dtype=typ.dtype)
                new_arrays[c] = np.concatenate(
                    [t.arrays[c][:t.num_rows], arr])
        self.tables[name] = HostTable(name, t.num_rows + n_new,
                                      new_arrays, t.types, new_dicts,
                                      new_nulls)
        return n_new
