"""The even-numbered queries of `test_tpcds.py`, in a file of their own so
that xdist's `--dist loadfile` gives them a worker of their own: in one
file the 99 queries were the whole of tier-1's wall time (test_tpcds.py
says how much). Same fixtures, same oracle, same comparison."""

import pytest

from tests.test_tpcds import (  # noqa: F401  (the fixtures are used by name)
    QUERIES, _drop_compile_caches, engine, oracle, run_case,
)


@pytest.mark.parametrize("qnum", [q for q in sorted(QUERIES) if q % 2 == 0])
def test_tpcds(qnum, engine, oracle):  # noqa: F811
    run_case(qnum, engine, oracle)
