import numpy as np
import pytest

from presto_tpu import BIGINT, DOUBLE, VARCHAR
from presto_tpu.data.column import (
    Column, Page, StringDict, bucket_capacity, compact,
)


def test_bucket_capacity():
    assert bucket_capacity(1) == 256
    assert bucket_capacity(256) == 256
    assert bucket_capacity(257) == 1024
    assert bucket_capacity(20_000_000) % 16777216 == 0


def test_column_from_numpy_pads_with_sentinel():
    c = Column.from_numpy(np.array([3, 1, 2]), BIGINT)
    v, n = c.to_numpy()
    assert c.capacity == 256
    assert list(v[:3]) == [3, 1, 2]
    assert not n[:3].any() and n[3:].all()
    assert (v[3:] == np.iinfo(np.int64).max).all()


def test_nulls_get_sentinel():
    c = Column.from_numpy(np.array([3.0, 1.0]), DOUBLE,
                          nulls=np.array([False, True]))
    v, n = c.to_numpy(2)
    assert v[0] == 3.0 and np.isinf(v[1]) and n[1]


def test_string_dict_sorted_codes():
    c = Column.from_strings(["banana", "apple", None, "cherry", "apple"])
    v, n = c.to_numpy(5)
    d = c.dictionary
    assert list(d.words) == sorted(d.words)
    assert d[int(v[0])] == "banana"
    assert d[int(v[1])] == "apple"
    assert n[2]
    assert d.code_of("zzz") == -1
    assert d.code_of("apple") == int(v[1])


def test_page_roundtrip():
    p = Page.from_pydict(
        {"a": [1, 2, None], "b": ["x", None, "y"]},
        {"a": BIGINT, "b": VARCHAR})
    assert p.to_pylist() == [(1, "x"), (2, None), (None, "y")]


def test_compact():
    p = Page.from_pydict({"a": [1, 2, 3, 4, 5]}, {"a": BIGINT})
    import jax.numpy as jnp
    keep = jnp.asarray(
        np.array([True, False, True, False, True] + [True] * 251))
    out = compact(p, keep)
    assert int(out.num_rows) == 3
    assert out.to_pylist() == [(1,), (3,), (5,)]


@pytest.mark.parametrize("capacity", [768, 1024, 256, 2048], ids=[
    "smaller", "as_it_was", "overflowing", "larger_is_ignored"])
def test_compact_into_a_smaller_capacity(capacity):
    """`capacity` cuts the result to that many slots (what a filtering
    semi join's learned capacity asks for): the first survivors in
    order, num_rows clamped, and never more slots than the page had."""
    import jax.numpy as jnp
    n = 1000
    vals = list(range(n))
    p = Page.from_pydict({"a": vals, "s": [f"w{v % 7}" for v in vals]},
                         {"a": BIGINT, "s": VARCHAR})
    assert p.capacity == 1024
    mask = np.ones(p.capacity, dtype=bool)    # padding never survives
    mask[:n] = np.arange(n) % 5 != 1
    mask[700:n] = False
    want = [(v, f"w{v % 7}") for v in vals if mask[v]]
    assert len(want) == 560
    out = compact(p, jnp.asarray(mask), capacity)
    assert out.capacity == min(capacity, 1024)
    assert int(out.num_rows) == min(560, capacity)
    assert out.to_pylist() == want[:capacity]


def test_merge_string_dicts_of_one_shared_object_is_the_identity():
    """One dictionary on every page (a table's, or the one the wire
    decode hands to all the pages that name it): the object itself and
    identity remaps, no union."""
    from presto_tpu.data.column import merge_string_dicts

    d = StringDict(["amy", "bob", "cat"])
    union, remaps = merge_string_dicts([d, d, d])
    assert union is d
    assert len(remaps) == 3
    for r in remaps:
        assert r.dtype == np.int32 and r.tolist() == [0, 1, 2]
    # equal words in another object are another dictionary: unioned
    other = StringDict(["amy", "bob", "dan"])
    union, remaps = merge_string_dicts([d, other])
    assert union is not d and union.words == ("amy", "bob", "cat", "dan")
    assert [r.tolist() for r in remaps] == [[0, 1, 2], [0, 1, 3]]
    # no dictionary at all stays the empty union
    union, remaps = merge_string_dicts([None, None])
    assert union.words == () and [len(r) for r in remaps] == [0, 0]


def test_compact_string_dict_keeps_the_words_in_use():
    from presto_tpu.data.column import compact_string_dict

    d = StringDict(["", "amy", "bob", "cat", "dan"], sparse=True)
    codes = np.array([3, 2147483647, 1, 3], dtype=np.int32)
    nulls = np.array([False, True, False, False])
    out, new = compact_string_dict(d, codes, nulls)
    assert out.words == ("", "amy", "cat") and not out.sparse
    assert new[~nulls].tolist() == [2, 1, 2]
    # no null row: "" goes with the other words no row uses
    out, new = compact_string_dict(d, np.array([4, 4], np.int32),
                                   np.zeros(2, bool))
    assert out.words == ("dan",) and new.tolist() == [0, 0]
    # every word in use: still a new object, as a decoded page's was
    out, new = compact_string_dict(d, np.arange(5, dtype=np.int32),
                                   np.zeros(5, bool))
    assert out is not d and out.words == d.words
