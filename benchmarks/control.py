#!/usr/bin/env python3
"""The control of `correct`: the plain reference computed in float32 and
put in the program's place, judged by the same comparison.

    python3 benchmarks/control.py --workload <cell> --seeds 1,2,3 [--statements 20]

For each seed it takes the statements the cell's window would send (the
first `--statements` of every client), computes each in float64 (the
reference) and in float32 (the control), and prints the run's compared
numbers as `compare.judge` gives them: the control has to come out not
correct. It starts no cluster and needs no chip: the data come from the
connector, the arithmetic is numpy's. The benchmark's own runs never
run it; `tests/test_control.py` keeps it at SF0.01.
"""

from __future__ import annotations

import argparse
import itertools
import json
import os
import sys

HERE = os.path.dirname(os.path.abspath(__file__))
REPO = os.path.dirname(HERE)
for p in (HERE, REPO):
    if p not in sys.path:
        sys.path.insert(0, p)

import numpy as np  # noqa: E402

import compare  # noqa: E402
import qgen  # noqa: E402


def control_run(tables, traffic: dict, queries: dict, seed: int,
                statements: int, dtype=np.float32) -> dict:
    """One seed: the window's statements answered by the reference in
    `dtype`, judged against the float64 reference."""
    records, wanted, cache = [], [], {}
    for c in range(traffic["clients"]):
        stream = qgen.client_stream(traffic, queries, seed, c)
        for name, params, _sql in itertools.islice(stream, statements):
            key = (name, json.dumps(params, sort_keys=True))
            if key not in cache:
                ref = compare.load_reference(queries[name])
                cache[key] = (ref(tables, params),
                              ref(tables, params, dtype))
            want, got = cache[key]
            records.append({"template": name, "params": params,
                            "rows": got})
            wanted.append(want)
    return compare.judge(records, wanted,
                         {t: q["limits"] for t, q in queries.items()})


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--statements", type=int, default=20)
    ap.add_argument("--sf", type=float, default=None)
    args = ap.parse_args(argv)
    import run as bench_run

    _b, cell, config, traffic, queries = bench_run.load_cell(args.workload)
    sf = config["scale_factor"] if args.sf is None else args.sf
    tables = bench_run.Tables(
        bench_run.make_connector(config["connector"], sf))
    ok = True
    for seed in (int(s) for s in args.seeds.split(",")):
        verdict = control_run(tables, traffic, queries, seed,
                              args.statements)
        ok &= not verdict["correct"]
        print(json.dumps({"workload": cell["name"], "seed": seed, "sf": sf,
                          "control_correct": verdict["correct"],
                          "compared": verdict["compared"]}), flush=True)
    return 0 if ok else 1  # 0: the control failed on every seed, as it must


if __name__ == "__main__":
    sys.exit(main())
