"""Three tests of this directory were written when the benchmark had one
cell, `q03_serial`, and cannot hold a cell or a metric added since; a PR
that adds a cell edits no file that is there, so they are marked here,
each strictly (a case that starts to pass fails the run), and
`test_q18.py` holds what each was there to hold:

- `test_control.py::test_float32_control_is_not_correct[*-q18_serial]`
  and `test_faults.py::test_broken_timed_path_is_not_correct[*-q18_serial]`
  run at SF0.01, where no order's quantity passes Q18's validation
  parameter 300 (the connector's largest there is 293): no row, nothing
  for a control or a fault to alter.
- `test_spans.py::test_the_readers_are_the_benchmarks_entries` asserts
  that PR 26's thirteen readers are the *last* thirteen entries of
  `per_layer`. A PR that adds entries puts them at the end of their
  list (one put in the middle reads to the driver as a change to the
  entries that were there, and the PR is refused for it), so that line
  cannot hold once an entry is added, and the file is not this PR's to
  edit. `test_q18.py::test_the_span_readers_are_the_benchmarks_entries`
  makes every assertion of it (the names, their order, the sources, the
  `workloads` lists), with the thirteen pinned before what came since.
  The next `benchmark` PR pins them by name in `test_spans.py` and
  drops this row (PERF.md section 7, row 4).
"""

import pytest

NO_ROW = ("SF0.01 holds no order over Q18's QUANTITY 300: test_q18.py "
          "runs this with rows to alter")
OUTGROWN = {
    "test_control.py::test_float32_control_is_not_correct[3-q18_serial]":
        NO_ROW,
    "test_control.py::test_float32_control_is_not_correct"
    "[2147483665-q18_serial]": NO_ROW,
    "test_control.py::test_float32_control_is_not_correct"
    "[123456789-q18_serial]": NO_ROW,
    "test_faults.py::test_broken_timed_path_is_not_correct"
    "[alter_an_answer-q18_serial]": NO_ROW,
    "test_faults.py::test_broken_timed_path_is_not_correct"
    "[leave_half_out-q18_serial]": NO_ROW,
    "test_spans.py::test_the_readers_are_the_benchmarks_entries":
        "pins PR 26's readers as the LAST 13 entries of per_layer, and a "
        "new entry goes to the end; test_q18.py makes all its assertions",
}


def pytest_collection_modifyitems(items):
    for item in items:
        where = item.nodeid.split("benchmarks/tests/")[-1]
        if where in OUTGROWN:
            item.add_marker(pytest.mark.xfail(reason=OUTGROWN[where],
                                              strict=True))
