"""The control of `correct`, kept at a size a test run can hold (SF0.01):
the float32 reference in the program's place comes out not correct in
every cell, on three seeds, while the float64 reference passes itself.
No cluster: numpy over the connector's arrays.

    JAX_PLATFORMS=cpu python -m pytest benchmarks/tests/test_control.py -q
"""

import json
import os
import sys

import numpy as np
import pytest

HERE = os.path.dirname(os.path.abspath(__file__))
BENCH = os.path.dirname(HERE)
REPO = os.path.dirname(BENCH)
for p in (BENCH, REPO):
    if p not in sys.path:
        sys.path.insert(0, p)

import control  # noqa: E402
import run as bench_run  # noqa: E402

with open(os.path.join(REPO, "BENCHMARK.json")) as _f:
    CELLS = [w["name"] for w in json.load(_f)["workloads"]]


@pytest.fixture(scope="module")
def tables():
    return bench_run.Tables(bench_run.make_connector("tpch", 0.01))


@pytest.mark.parametrize("cell", CELLS)
@pytest.mark.parametrize("seed", [3, 2**31 + 17, 123456789])
def test_float32_control_is_not_correct(tables, cell, seed):
    _b, _c, _cfg, traffic, queries = bench_run.load_cell(cell)
    verdict = control.control_run(tables, traffic, queries, seed, 4)
    assert not verdict["correct"]
    failed = [k for k, s in verdict["compared"].items()
              if s["value"] > s["limit"]]
    assert failed and all(k.endswith("max_rel_err") for k in failed)


@pytest.mark.parametrize("cell", CELLS)
def test_float64_reference_passes_itself(tables, cell):
    _b, _c, _cfg, traffic, queries = bench_run.load_cell(cell)
    verdict = control.control_run(tables, traffic, queries, 5, 4,
                                  dtype=np.float64)
    assert verdict["correct"]
    assert all(s["value"] == 0 for s in verdict["compared"].values())
