"""ORC connector — the second lakehouse file format.

Reference role: presto-orc (the ORC->Page reader feeding Hive scans,
presto-orc/.../OrcReader.java) + presto-hive's directory layout. Same
TPU-first shape as the parquet connector (connectors/parquet.py):
columns decode lazily per stripe (projection pushdown), a table is one
file or a directory of files, and the split unit is (file, stripe) —
ORC's natural row-group analog. Decode is pyarrow.orc (the role the
reference delegates to its own ORC decoder); the lazy projection,
split construction, dictionary remap and type mapping are this
connector. pyarrow's ORC API exposes no per-stripe column statistics,
so there is no metadata min/max pruning here (the parquet path has it).
"""

from __future__ import annotations

import os
from typing import Dict, List, Optional, Tuple

import numpy as np

from presto_tpu.connectors.parquet import (
    FileCatalogConnector, LazyFileTable, _arrow_to_type,
    _decode_column, rows_to_arrow_table,
)
from presto_tpu.connectors.tpch import LazyColumns
from presto_tpu.data.column import StringDict


class OrcTable(LazyFileTable):
    """Lazily-loading HostTable over one or more ORC files; units are
    (file index, stripe index). Row counts come from file metadata
    (ORCFile.nrows) for whole files; slices get per-stripe lengths
    computed ONCE on the parent and passed down — never re-read."""

    def __init__(self, name: str, paths: List[str],
                 stripes: Optional[List[Tuple[int, int]]] = None,
                 files=None, stripe_rows=None):
        import pyarrow.orc as orc

        self.paths = paths
        self._files = (files if files is not None
                       else [orc.ORCFile(p) for p in paths])
        self.units = (stripes if stripes is not None
                      else [(fi, s) for fi, f in enumerate(self._files)
                            for s in range(f.nstripes)])
        self._stripe_rows = stripe_rows
        schema = self._files[0].schema
        types = {f.name: _arrow_to_type(f.type) for f in schema}
        if stripes is None:
            n = sum(f.nrows for f in self._files)
        else:
            n = sum(self.stripe_lengths()[u] for u in self.units)
        self._dicts: Dict[str, StringDict] = {}
        self._nulls: Dict[str, np.ndarray] = {}
        super().__init__(name, n, LazyColumns(self._load_column),
                         types, self._dicts, self._nulls)

    def stripe_lengths(self) -> Dict[Tuple[int, int], int]:
        """(file, stripe) -> row count, computed once per table family
        (pyarrow exposes no per-stripe metadata; reading one narrow
        column per stripe is the cheapest measure and is shared with
        every slice via the `stripe_rows=` handoff)."""
        if self._stripe_rows is None:
            first_col = self._files[0].schema[0].name
            self._stripe_rows = {
                (fi, s): len(self._files[fi].read_stripe(
                    s, columns=[first_col]))
                for fi, f in enumerate(self._files)
                for s in range(f.nstripes)}
        return self._stripe_rows

    def _load_column(self, col: str):
        import pyarrow as pa

        t = self.types[col]
        chunks = []
        for fi, s in self.units:
            batch = self._files[fi].read_stripe(s, columns=[col])
            chunks.append(batch.column(0))
        merged = pa.chunked_array(chunks) if chunks \
            else pa.chunked_array([], type=pa.int64())
        vals, nulls, d = _decode_column(merged, t)
        if d is not None:
            self._dicts[col] = d
        self._nulls[col] = nulls
        return vals


def read_orc_table(path: str, name: str) -> OrcTable:
    if os.path.isdir(path):
        paths = sorted(os.path.join(path, f) for f in os.listdir(path)
                       if f.endswith(".orc"))
        if not paths:
            raise FileNotFoundError(f"no orc files under {path}")
        return OrcTable(name, paths)
    return OrcTable(name, [path])


def write_orc_table(path: str, rows: List[tuple], schema,
                    stripe_size: Optional[int] = None) -> None:
    """Engine result rows -> one ORC file (write side for round trips;
    reference role: OrcWriter). Value coercion is the shared
    rows_to_arrow_table."""
    import pyarrow.orc as orc

    kw = {}
    if stripe_size:
        kw["stripe_size"] = stripe_size
    orc.write_table(rows_to_arrow_table(rows, schema), path, **kw)


class OrcConnector(FileCatalogConnector):
    NAME = "orc"
    EXT = "orc"

    def _open(self, path: str, name: str) -> OrcTable:
        return read_orc_table(path, name)

    def _slice(self, full, name: str, units) -> OrcTable:
        return OrcTable(name, full.paths, units, files=full._files,
                        stripe_rows=full.stripe_lengths())
