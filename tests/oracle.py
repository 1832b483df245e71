"""Pandas oracle: decode generated TPC-H HostTables into DataFrames so tests
can compute expected results independently of the engine (the analogue of
the reference's H2QueryRunner row-for-row comparisons,
presto-tests/.../H2QueryRunner.java)."""

import numpy as np
import pandas as pd


def table_df(conn, name: str, columns=None) -> pd.DataFrame:
    """The whole table, or only `columns` of it (decoding every string
    column of a large table costs more than the query under test)."""
    parts = {}
    t = conn.table(name)
    for col in (t.types if columns is None else columns):
        arr = t.arrays[col][:t.num_rows]
        if col in t.dicts:
            words = np.asarray(t.dicts[col].words, dtype=object)
            s = pd.Series(words[arr])
        else:
            s = pd.Series(arr)
        mask = t.null_mask(col)
        if mask is not None and mask.any():
            s = s.astype(object)
            s[np.asarray(mask, dtype=bool)] = None
        parts[col] = s
    return pd.DataFrame(parts)


def assert_rows_match(actual, expected, float_tol=1e-6, sort=False):
    """Row-for-row comparison with float tolerance."""
    if sort:
        actual = sorted(actual, key=_key)
        expected = sorted(expected, key=_key)
    assert len(actual) == len(expected), \
        f"row count {len(actual)} != {len(expected)}\n" \
        f"actual[:5]={actual[:5]}\nexpected[:5]={expected[:5]}"
    for i, (a, e) in enumerate(zip(actual, expected)):
        assert len(a) == len(e), f"row {i}: arity {len(a)} != {len(e)}"
        for j, (x, y) in enumerate(zip(a, e)):
            if x is None or y is None:
                assert x is None and y is None, \
                    f"row {i} col {j}: {x!r} != {y!r}"
            elif isinstance(x, float) or isinstance(y, float):
                rel = max(abs(float(y)), 1.0)
                assert abs(float(x) - float(y)) <= float_tol * rel, \
                    f"row {i} col {j}: {x!r} != {y!r}"
            else:
                assert x == y, f"row {i} col {j}: {x!r} != {y!r}"


def _key(row):
    return tuple((v is None, v) for v in row)
