"""Bench-regression detector (obs/bench_check.py): the fixture
quartet — regression caught, improvement passes, within-noise passes,
missing-lane tolerated — plus lane extraction and the CLI contract
against a landed BENCH history built in tmp_path."""

import json
import os

from presto_tpu.obs import bench_check
from presto_tpu.obs.bench_check import (check_dir, compare_rounds,
                                        extract_lanes, find_rounds)


def _round(n, value, unit="rows/s", metric="headline", detail=None):
    return {"n": n, "parsed": {"metric": metric, "value": value,
                               "unit": unit,
                               "detail": detail or {}}}


def _land(tmp_path, *docs):
    for doc in docs:
        p = tmp_path / f"BENCH_r{doc['n']:02d}.json"
        p.write_text(json.dumps(doc))
    return str(tmp_path)


# ----------------------------------------------------- fixture quartet
def test_regression_caught_and_exits_nonzero(tmp_path):
    d = _land(tmp_path, _round(1, 1000.0), _round(2, 500.0))
    verdict = check_dir(d)
    assert verdict["status"] == "regression"
    assert verdict["regressions"] == ["headline"]
    assert bench_check.main([d]) == 1


def test_improvement_passes(tmp_path):
    d = _land(tmp_path, _round(1, 1000.0), _round(2, 2000.0))
    verdict = check_dir(d)
    assert verdict["status"] == "ok" and verdict["regressions"] == []
    assert bench_check.main([d]) == 0


def test_within_noise_passes(tmp_path):
    # 12% down on a higher-is-better lane: inside the 20% tolerance
    d = _land(tmp_path, _round(1, 1000.0), _round(2, 880.0))
    verdict = check_dir(d)
    assert verdict["status"] == "ok"
    [lane] = verdict["lanes"]
    assert lane["verdict"] == "ok" and lane["ratio"] == 0.88


def test_missing_lane_tolerated(tmp_path):
    # rounds that measured different subsystems share no lanes — that
    # is "insufficient history", never a failure (the landed r09
    # memory round vs r10 serving round is exactly this shape)
    d = _land(tmp_path,
              _round(1, 38.7, unit="x", metric="memory_slowdown"),
              _round(2, 352.7, unit="stmt/s", metric="serve_round"))
    verdict = check_dir(d)
    assert verdict["status"] == "insufficient_history"
    assert set(verdict["skipped"]) == {"memory_slowdown",
                                      "serve_round"}
    assert bench_check.main([d]) == 0


# ------------------------------------------------------- directionality
def test_lower_is_better_units_regress_upward(tmp_path):
    # slowdown "x": bigger is worse
    up = compare_rounds(_round(1, 10.0, unit="x"),
                        _round(2, 20.0, unit="x"))
    assert up["status"] == "regression"
    down = compare_rounds(_round(1, 10.0, unit="x"),
                          _round(2, 5.0, unit="x"))
    assert down["status"] == "ok"


def test_detail_rows_per_sec_lanes_compared(tmp_path):
    base = _round(1, 100.0,
                  detail={"q01": {"rows_per_sec": 1000.0},
                          "q06": {"rows_per_sec": 500.0}})
    cur = _round(2, 100.0,
                 detail={"q01": {"rows_per_sec": 100.0},   # 10x down
                         "q06": {"rows_per_sec": 510.0}})
    verdict = compare_rounds(base, cur)
    assert verdict["status"] == "regression"
    assert verdict["regressions"] == ["q01_rows_per_sec"]


def test_unknown_unit_and_zero_baseline_skipped():
    verdict = compare_rounds(_round(1, 5.0, unit="furlongs"),
                             _round(2, 50.0, unit="furlongs"))
    assert verdict["status"] == "insufficient_history"
    assert verdict["skipped"] == ["headline"]
    verdict = compare_rounds(_round(1, 0.0), _round(2, 10.0))
    assert verdict["skipped"] == ["headline"]


# ----------------------------------------------------- lane extraction
def test_extract_lanes_headline_and_detail():
    lanes = extract_lanes(_round(
        3, 123.0, detail={"q01": {"rows_per_sec": 9.0},
                          "broken": {"error": "infra"},
                          "note": "not a dict"}))
    assert lanes["headline"] == {"value": 123.0, "unit": "rows/s"}
    assert lanes["q01_rows_per_sec"] == {"value": 9.0,
                                         "unit": "rows/s"}
    assert "broken" not in lanes and "note" not in lanes


def test_extract_lanes_top_level_fallback():
    # early rounds wrote the headline triple unnested
    lanes = extract_lanes({"metric": "old", "value": 7.0,
                           "unit": "rows/s"})
    assert lanes == {"old": {"value": 7.0, "unit": "rows/s"}}
    assert extract_lanes({"metric": "x", "value": None}) == {}


# ------------------------------------------------- landed BENCH history
def _landed_history(tmp_path):
    """An eleven-round history of the shape the repo has landed: round
    numbers that pass r09 -> r10, a steady lane, and newest rounds that
    measured different subsystems."""
    docs = [_round(n, 1000.0 + n) for n in range(1, 10)]
    docs.append(_round(10, 352.7, unit="stmt/s", metric="serve_round"))
    docs.append(_round(11, 11162.0, metric="cluster_mesh_round"))
    return _land(tmp_path, *docs)


def test_landed_history_found_in_round_order(tmp_path):
    rounds = find_rounds(_landed_history(tmp_path))
    assert len(rounds) >= 10
    nums = [int(os.path.basename(p)[7:-5]) for p in rounds]
    assert nums == sorted(nums), "round 10 must sort after round 9"


def test_landed_history_passes_the_gate(tmp_path):
    # the CLI exits 0 on a landed history (its two newest rounds share
    # no lane, as the repo's own r10/r11 do not)
    assert bench_check.main([_landed_history(tmp_path)]) == 0


def test_insufficient_history_single_round(tmp_path):
    d = _land(tmp_path, _round(1, 1000.0))
    verdict = check_dir(d)
    assert verdict["status"] == "insufficient_history"
    assert verdict["rounds_found"] == 1
    assert bench_check.main([str(tmp_path)]) == 0
