#!/usr/bin/env python3
"""An independent look at the generated tables: `correct` holds the
program against a reference that reads the same arrays, so a fault of the
generator or of a dictionary's coding is invisible to it. This holds the
arrays themselves against what the configuration's source says of them
(`configs/<config>.json`, "rows" and "tables"): row counts, value domains,
the only words a dictionary may hold, keys unique, children per parent,
and a column's distance from the parent's.

    python3 benchmarks/datacheck.py --config tpch_sf1_http [--sf 0.01]

Prints one line a rule broken and exits 1, or exits 0. Numpy on the host:
no cluster, no chip; the data are the same on every machine.
`tests/test_data.py` keeps it at SF0.01, with two generators' faults
planted. The benchmark's runs do not call it (PERF.md, Open questions).
"""

from __future__ import annotations

import argparse
import json
import os
import sys
from typing import List

import numpy as np

HERE = os.path.dirname(os.path.abspath(__file__))
REPO = os.path.dirname(HERE)
for p in (HERE, REPO):
    if p not in sys.path:
        sys.path.insert(0, p)

import qgen  # noqa: E402


def _column_faults(name: str, values, words, rule: dict, rows: dict
                   ) -> List[str]:
    out = []
    if "words" in rule:
        seen = {words[int(code)] for code in np.unique(values)}
        if seen != set(rule["words"]):
            out.append(f"{name}: words {sorted(seen)}, the source has "
                       f"{sorted(rule['words'])}")
        return out
    lo = rule.get("lo", qgen.days(rule["lo_date"])
                  if "lo_date" in rule else None)
    hi = rule.get("hi", qgen.days(rule["hi_date"])
                  if "hi_date" in rule else None)
    if "hi_rows" in rule:
        hi = rows[rule["hi_rows"]]
    if lo is not None and values.min() < lo:
        out.append(f"{name}: least {values.min()} under {lo}")
    if hi is not None and values.max() > hi:
        out.append(f"{name}: largest {values.max()} over {hi}")
    if rule.get("integral") and (values != np.round(values)).any():
        out.append(f"{name}: not whole numbers")
    if rule.get("cents") and (np.abs(values * 100 - np.round(values * 100))
                              > 1e-6).any():
        out.append(f"{name}: not whole hundredths")
    if rule.get("unique") and len(np.unique(values)) != len(values):
        out.append(f"{name}: not unique")
    if "not_multiple_of" in rule \
            and (values % rule["not_multiple_of"] == 0).any():
        out.append(f"{name}: holds multiples of {rule['not_multiple_of']}")
    return out


def faults(tables, config: dict, scale: float = 1.0) -> List[str]:
    """Every rule of the configuration that the tables break. `scale` is
    the share of the configuration's scale factor that was generated (the
    rows then scale with it; lineitem's are drawn, so within 1%)."""
    out = []
    spec = {t: r for t, r in config["tables"].items() if t != "comment"}
    rows = {t: int(round(n * scale)) for t, n in config["rows"].items()}
    for table, rules in spec.items():
        some = next(iter(rules["columns"]))
        n = len(tables.column(table, some))
        drawn = "per_parent" in rules
        if abs(n - rows[table]) > (0.01 * rows[table] if drawn and scale != 1
                                   else 0):
            out.append(f"{table}: {n} rows, the configuration says "
                       f"{rows[table]}")
        for col, rule in rules["columns"].items():
            words = tables.words(table, col) if "words" in rule else None
            out += _column_faults(f"{table}.{col}",
                                  tables.column(table, col), words, rule,
                                  rows)
        if drawn:
            pp = rules["per_parent"]
            keys, counts = np.unique(tables.column(table, pp["column"]),
                                     return_counts=True)
            parents = tables.column(pp["parent"], pp["parent_key"])
            if not np.array_equal(keys, np.sort(parents)):
                out.append(f"{table}.{pp['column']}: not the keys of "
                           f"{pp['parent']}.{pp['parent_key']}")
            if counts.min() < pp["lo"] or counts.max() > pp["hi"]:
                out.append(f"{table}: {counts.min()}..{counts.max()} rows "
                           f"a parent, the source has {pp['lo']}..{pp['hi']}")
        for col, other, other_col, lo, hi in rules.get("links", []):
            pp = rules["per_parent"]
            parents = tables.column(other, pp["parent_key"])
            order = np.argsort(parents, kind="stable")
            child = tables.column(table, pp["column"])
            at = np.minimum(np.searchsorted(parents[order], child),
                            len(parents) - 1)
            has = parents[order][at] == child  # an orphan is per_parent's
            gap = (tables.column(table, col).astype(np.int64)[has]
                   - tables.column(other, other_col)[order][at][has])
            if gap.min() < lo or gap.max() > hi:
                out.append(f"{table}.{col} - {other}.{other_col}: "
                           f"{gap.min()}..{gap.max()}, the source has "
                           f"{lo}..{hi}")
    return out


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--config", required=True)
    ap.add_argument("--sf", type=float, default=None)
    args = ap.parse_args(argv)
    import run as bench_run

    config = qgen.load_json("configs", args.config + ".json")
    sf = config["scale_factor"] if args.sf is None else args.sf
    conn = bench_run.make_connector(config["connector"], sf)
    found = faults(bench_run.Tables(conn), config,
                   sf / config["scale_factor"])
    for line in found:
        print(line)
    print(json.dumps({"config": args.config, "sf": sf,
                      "rules_broken": len(found)}))
    return 1 if found else 0


if __name__ == "__main__":
    sys.exit(main())
