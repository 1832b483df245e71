"""`test_faults.py`'s whole CPU runs for `q18_serial`, with rows to break:
at SF0.01 the template's own threshold keeps no order, so here the
template is read with QUANTITY 250 (79 rows) and the same two faults are
planted under the timed path. A minute or so a case: no tier-1 test.

    JAX_PLATFORMS=cpu python -m pytest benchmarks/tests/test_q18_faults.py -q
"""

import pytest

from test_faults import (alter_an_answer, caps_cache_apart,  # noqa: F401
                         drive, leave_half_out)

import qgen  # noqa: E402 -- test_faults puts benchmarks/ on the path


@pytest.fixture(autouse=True)
def q18_with_rows(monkeypatch):
    real = qgen.load_query

    def at_250(name):
        query = real(name)
        if name == "q18":
            query["params"]["QUANTITY"]["value"] = 250
        return query
    monkeypatch.setattr(qgen, "load_query", at_250)


def test_sound_run_of_q18_with_rows_is_correct(capsys):
    line = drive(capsys, "q18_serial")
    assert line["correct"] and line["failed"] == 0 and line["attempted"] > 0
    assert line["run"]["warm_up"][0]["template"] == "q18"
    assert line["compared"]["q18.max_rel_err"]["value"] == 0


@pytest.mark.parametrize("fault", [alter_an_answer, leave_half_out])
def test_broken_timed_path_of_q18_is_not_correct(capsys, monkeypatch, fault):
    fault(monkeypatch)
    line = drive(capsys, "q18_serial")
    assert line["attempted"] > 0 and line["failed"] == 0
    assert not line["correct"]
    assert line["metrics"]["qph"]["value"] == 0  # no statement was right
    assert any(s["value"] > s["limit"] for s in line["compared"].values())
