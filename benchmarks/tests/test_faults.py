"""A whole run on the CPU (SF0.01, `--allow-cpu`: only the look for a chip
is skipped) with the timed path broken underneath: `correct` has to come
out false. Once for each fault a cell of a database can have: an answer
altered where it is produced, and half of the input left out (every
worker's split cut to its first half, the sums taken over the rest). A
sound run of the same cell comes out true. Each case starts a cluster and
compiles: a minute or so a cell, so this is no tier-1 test.

    JAX_PLATFORMS=cpu python -m pytest benchmarks/tests/test_faults.py -q
"""

import json
import os
import sys

import pytest

HERE = os.path.dirname(os.path.abspath(__file__))
BENCH = os.path.dirname(HERE)
REPO = os.path.dirname(BENCH)
for p in (BENCH, REPO):
    if p not in sys.path:
        sys.path.insert(0, p)

import run as bench_run  # noqa: E402

with open(os.path.join(REPO, "BENCHMARK.json")) as _f:
    CELLS = [w["name"] for w in json.load(_f)["workloads"]]


def drive(capsys, cell: str) -> dict:
    rc = bench_run.main(["--workload", cell, "--seed", "2147483699",
                         "--seconds", "1", "--trace", "0", "--allow-cpu",
                         "--sf", "0.01"])
    assert rc == 0
    return json.loads(capsys.readouterr().out.strip().splitlines()[-1])


def alter_an_answer(monkeypatch):
    """The first number of the first row, off by one part in a million."""
    from presto_tpu.server.cluster import TpuCluster
    real = TpuCluster.execute_sql

    def altered(self, sql, *a, **kw):
        rows = [list(r) for r in real(self, sql, *a, **kw)]
        for j, v in enumerate(rows[0]):
            if isinstance(v, float):
                rows[0][j] = v * (1 + 1e-6)
                break
        return rows
    monkeypatch.setattr(TpuCluster, "execute_sql", altered)


def leave_half_out(monkeypatch):
    """Every split a worker reads holds only the first half of its rows;
    the reference still reads the whole table."""
    from presto_tpu.connectors import tpch
    real = tpch.TpchConnector.table

    def halved(self, name, part=0, num_parts=1):
        t = real(self, name, part, num_parts)
        if num_parts == 1 or name != "lineitem":
            return t
        n = t.num_rows // 2
        return tpch.HostTable(name, n, {c: a[:n] for c, a in
                                        t.arrays.items()}, t.types, t.dicts)
    monkeypatch.setattr(tpch.TpchConnector, "table", halved)


@pytest.fixture(autouse=True)
def caps_cache_apart(tmp_path, monkeypatch):
    # a broken run must not teach the checkout's learned capacities
    monkeypatch.setenv("PRESTO_TPU_CAPS_CACHE", str(tmp_path / "caps.json"))


@pytest.mark.parametrize("cell", CELLS)
def test_sound_run_is_correct(capsys, cell):
    line = drive(capsys, cell)
    assert line["correct"] and line["failed"] == 0 and line["attempted"] > 0
    assert line["run"]["rehearsal_on_cpu"]
    assert line["device"]["platform"] == "cpu"


@pytest.mark.parametrize("cell", CELLS)
@pytest.mark.parametrize("fault", [alter_an_answer, leave_half_out])
def test_broken_timed_path_is_not_correct(capsys, monkeypatch, cell, fault):
    fault(monkeypatch)
    line = drive(capsys, cell)
    assert line["attempted"] > 0 and line["failed"] == 0
    assert not line["correct"]
    assert line["metrics"]["qph"]["value"] == 0  # no statement was right
    assert any(s["value"] > s["limit"] for s in line["compared"].values())


def test_without_a_tpu_there_is_no_result(capsys):
    rc = bench_run.main(["--workload", CELLS[0], "--seed", "1",
                         "--seconds", "1", "--trace", "0"])
    out = capsys.readouterr()
    assert rc != 0 and out.out == "" and "needs 1 TPU" in out.err
