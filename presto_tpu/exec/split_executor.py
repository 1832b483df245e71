"""SplitExecutor — scans read ASSIGNED splits (row ranges), not whole
tables: the worker-side contract (splits arrive in
TaskUpdateRequest.sources; reference ScheduledSplit / ConnectorSplit) and
the building block of lifespan-batched execution (exec/lifespan.py).

A scan of ONE split is that split's `HostTable.page`: the connector hands
every task the same split view, so the columns a first task put on the
device are resident for the next (connectors/scan_cache.py holds the
bytes under its budget). Several splits in one task, and streaming scan
runs (`set_split_tables`), are put up for the scan alone."""

from __future__ import annotations

import dataclasses
from typing import Dict, List, Tuple

import numpy as np

from presto_tpu.data.column import Column, Page
from presto_tpu.exec.executor import Executor, ScanSpec


@dataclasses.dataclass(frozen=True)
class RemotePageSpec:
    """Scan-slot placeholder for an input pulled from upstream tasks
    (bound by node id; reference: RemoteSourceNode -> ExchangeOperator)."""
    node_id: str
    capacity: int


class SplitExecutor(Executor):
    def __init__(self, connector, session=None, programs=None):
        super().__init__(connector, session=session, programs=programs)
        self.splits: Dict[str, List[Tuple[int, int]]] = {}
        # table -> pre-materialized host table (one streaming scan run);
        # consulted BEFORE split (part, numParts) resolution so lifespan
        # streaming can feed bounded page runs through an unchanged plan.
        self.split_tables: Dict[str, object] = {}
        # node_id -> concatenated engine Page pulled over the HTTP
        # exchange before execution (data/column.concat_pages_host).
        self.remote_pages: Dict[str, "Page"] = {}

    def set_splits(self, by_table: Dict[str, List[Tuple[int, int]]]):
        self.splits = by_table

    def set_split_tables(self, by_table: Dict[str, object]):
        """Bind host tables (streaming scan runs) directly to leaf
        scans; pass {} to fall back to split-range resolution."""
        self.split_tables = by_table

    def set_remote_pages(self, by_node: Dict[str, Page]):
        self.remote_pages = by_node

    def _remote_source(self, node, scans):
        page = self.remote_pages.get(node.node_id)
        if page is None:
            raise RuntimeError(
                f"no remote pages bound for plan node {node.node_id!r}")
        idx = len(scans)
        scans.append(RemotePageSpec(node.node_id, page.capacity))
        return (lambda pages: pages[idx]), page.capacity

    def _scan_rows(self, node) -> int:
        t = self.split_tables.get(node.table)
        if t is not None:
            return max(1, int(t.num_rows))
        parts = self.splits.get(node.table)
        if parts is None:
            return self.connector.table(node.table).num_rows
        return max(1, sum(
            self.connector.table(node.table, part=p, num_parts=n).num_rows
            for p, n in parts))

    def _fetch(self, s) -> Page:
        if isinstance(s, RemotePageSpec):
            return self.remote_pages[s.node_id]
        return super()._fetch(s)

    def _scan_page(self, s: ScanSpec) -> Page:
        t = self.split_tables.get(s.table)
        if t is not None:
            return t.page(columns=list(s.columns), capacity=s.capacity)
        parts = self.splits.get(s.table)
        if parts is None:
            return super()._scan_page(s)
        tables = [self.connector.table(s.table, part=p, num_parts=n)
                  for p, n in parts]
        if len(tables) == 1:
            # one split a table is what a task of the served path holds:
            # the split's own page, whose columns stay on the device
            # with the table the connector keeps (HostTable.split)
            return tables[0].page(columns=list(s.columns),
                                  capacity=s.capacity)
        # several splits in one task (lifespans, pruned split sets):
        # concatenated on the host and put up for this scan alone
        n_rows = sum(t.num_rows for t in tables)
        cols = []
        for c in s.columns:
            t0 = tables[0]
            if t0.types[c].name in ("array", "map", "row"):
                from presto_tpu.data.column import NestedColumn
                vals = [v for t in tables
                        for v in t.arrays[c][:t.num_rows]]
                cols.append(NestedColumn.from_pylist(
                    vals, t0.types[c], s.capacity))
                continue
            if t0.types[c].is_string and len(tables) > 1:
                # materialize FIRST: lazy tables (parquet) only build
                # their dictionary on column access, so comparing dicts
                # before the load sees None==None and would skip the
                # remap
                for t in tables:
                    _ = t.arrays[c]
            if t0.types[c].is_string and len(tables) > 1 and any(
                    t.dicts.get(c) is not tables[0].dicts.get(c)
                    for t in tables[1:]):
                # splits with PER-SPLIT dictionaries (parquet row-group
                # units decode their own dictionary pages): remap all
                # code spaces into one union dictionary
                from presto_tpu.data.column import merge_string_dicts
                union, remaps = merge_string_dicts(
                    [t.dicts.get(c) for t in tables])
                parts = []
                for t, remap in zip(tables, remaps):
                    codes = np.asarray(t.arrays[c][:t.num_rows])
                    parts.append(remap[codes] if len(remap) else codes)
                arr = np.concatenate(parts)
                masks = [t.null_mask(c) for t in tables]
                nulls = (np.concatenate(
                    [m if m is not None else np.zeros(t.num_rows, bool)
                     for m, t in zip(masks, tables)])
                    if any(m is not None for m in masks) else None)
                cols.append(Column.from_numpy(
                    arr, t0.types[c], nulls=nulls, dictionary=union,
                    capacity=s.capacity))
                continue
            arr = np.concatenate([t.arrays[c][:t.num_rows] for t in tables])
            masks = [t.null_mask(c) for t in tables]
            nulls = (np.concatenate(
                [m if m is not None else np.zeros(t.num_rows, bool)
                 for m, t in zip(masks, tables)])
                if any(m is not None for m in masks) else None)
            if getattr(t0.types[c], "uses_int128", False):
                # DECIMAL(p>18) at rest: python-int unscaled values ->
                # limb lanes (see HostTable.page)
                from presto_tpu.data.column import Decimal128Column
                cols.append(Decimal128Column.from_unscaled_ints(
                    list(arr), t0.types[c], nulls=nulls,
                    capacity=s.capacity))
                continue
            cols.append(Column.from_numpy(
                arr, t0.types[c], nulls=nulls, dictionary=t0.dicts.get(c),
                capacity=s.capacity))
        return Page.from_columns(cols, n_rows, s.columns)
