"""The comparison that decides `correct`.

Every statement of the timed window is held against its template's plain
reference (`queries/<template>.py`, numpy/pandas over the generated
arrays, nothing of the engine). Two numbers a template:

- `wrong_cells`: rows missing or extra, and cells that are not floats
  (keys, dates, counts, strings) and differ. Exact: the limit is 0.
- `max_rel_err`: the widest gap of a float cell, |got - want| over
  max(|want|, 1). Its limit stands in the template's file, set between
  what the program reads and what the float32 control reads (PERF.md).
"""

from __future__ import annotations

from typing import Dict, List

from qgen import load_py


def load_reference(query: dict):
    """The `reference(tables, params, dtype)` function of a template: in
    `queries/<name>.py`, or in the file the query names under `reference`
    (two templates of one query share one)."""
    template = query.get("reference", query["name"])
    return load_py("queries", template + ".py").reference


def row_gaps(got: list, want: list) -> Dict[str, float]:
    """wrong_cells and max_rel_err of one statement's rows, in order."""
    wrong = abs(len(got) - len(want))
    worst = 0.0
    for g, w in zip(got, want):
        if len(g) != len(w):
            wrong += max(len(g), len(w))
            continue
        for x, y in zip(g, w):
            if isinstance(y, float) and isinstance(x, (int, float)) \
                    and not isinstance(x, bool):
                err = abs(float(x) - y) / max(abs(y), 1.0)
                if err != err:  # NaN never compares: count it as wrong
                    wrong += 1
                else:
                    worst = max(worst, err)
            elif x != y:
                wrong += 1
    return {"wrong_cells": wrong, "max_rel_err": worst}


def judge(records: List[dict], wanted: List[list],
          limits: Dict[str, Dict[str, float]]) -> dict:
    """`records[i]` (a finished statement) against `wanted[i]`.
    Returns {"correct", "compared": {name: {"value", "limit"}},
    "wrong_statements": indices}. A statement is wrong when either of its
    numbers passes its template's limit."""
    compared: Dict[str, Dict[str, float]] = {}
    wrong_statements = []
    for i, (rec, want) in enumerate(zip(records, wanted)):
        t = rec["template"]
        gaps = row_gaps(rec["rows"], want)
        bad = False
        for key, value in gaps.items():
            limit = limits[t][key]
            slot = compared.setdefault(f"{t}.{key}",
                                       {"value": 0.0, "limit": limit})
            slot["value"] = max(slot["value"], value)
            bad |= value > limit
        if bad:
            wrong_statements.append(i)
    correct = bool(records) and not wrong_statements
    return {"correct": correct, "compared": compared,
            "wrong_statements": wrong_statements}
