"""Blocked-scan primitives + merge join unit tests (CPU mesh harness)."""

import numpy as np
import pytest

from presto_tpu import BIGINT, DATE, DOUBLE, INTEGER, VARCHAR
from presto_tpu.data.column import Page
from presto_tpu.ops.join import hash_join, merge_join
from presto_tpu.ops.scan import cumsum, fill_forward, segment_sums


def _page(data, types):
    return Page.from_pydict(data, types)


def test_blocked_cumsum_matches_numpy():
    rng = np.random.RandomState(0)
    for n in (1, 7, 2048, 2049, 10000):
        x = rng.randint(-5, 5, n).astype(np.int64)
        import jax.numpy as jnp
        got = np.asarray(cumsum(jnp.asarray(x)))
        assert (got == np.cumsum(x)).all(), n


def test_fill_forward_matches_loop():
    rng = np.random.RandomState(1)
    import jax.numpy as jnp
    n = 6000
    vals = rng.randint(0, 100, n).astype(np.int64)
    pres = rng.rand(n) < 0.05
    got = np.asarray(fill_forward(jnp.asarray(vals), jnp.asarray(pres)))
    exp, last = np.zeros(n, np.int64), 0
    for i in range(n):
        if pres[i]:
            last = vals[i]
        exp[i] = last
    assert (got == exp).all()


def test_segment_sums_contiguous():
    import jax.numpy as jnp
    vals = jnp.asarray(np.array([1.0, 2.0, 3.0, 4.0, 5.0, 0.0, 0.0]))
    starts = jnp.asarray(np.array([0, 2, 5], dtype=np.int32))
    ends = jnp.asarray(np.array([2, 5, 5], dtype=np.int32))
    got = np.asarray(segment_sums(vals, starts, ends))
    assert got.tolist() == [3.0, 12.0, 0.0]


# ------------------------------------------------------------- merge join

def _mj(probe, build, jt):
    out, dup, _match = merge_join(probe, build, [0], [0], jt)
    return out, int(dup)


def test_merge_join_inner_unique():
    probe = _page({"k": [3, 1, 4, 9, 1], "v": [30.0, 10.0, 40.0, 90.0, 11.0]},
                  {"k": BIGINT, "v": DOUBLE})
    build = _page({"k": [1, 2, 3, 4], "w": [100.0, 200.0, 300.0, 400.0]},
                  {"k": BIGINT, "w": DOUBLE})
    out, dup = _mj(probe, build, "inner")
    assert dup == 0
    rows = sorted(out.to_pylist())
    assert rows == [(1, 10.0, 1, 100.0), (1, 11.0, 1, 100.0),
                    (3, 30.0, 3, 300.0), (4, 40.0, 4, 400.0)]


def test_merge_join_left_nulls():
    probe = _page({"k": [3, 9, None], "v": [1.0, 2.0, 3.0]},
                  {"k": BIGINT, "v": DOUBLE})
    build = _page({"k": [3], "w": [33.0]}, {"k": BIGINT, "w": DOUBLE})
    out, dup = _mj(probe, build, "left")
    assert dup == 0
    rows = sorted(out.to_pylist(), key=lambda r: (r[1]))
    assert rows == [(3, 1.0, 3, 33.0), (9, 2.0, None, None),
                    (None, 3.0, None, None)]


def test_merge_join_detects_duplicates():
    probe = _page({"k": [1, 2], "v": [1.0, 2.0]},
                  {"k": BIGINT, "v": DOUBLE})
    build = _page({"k": [1, 1, 2], "w": [9.0, 8.0, 7.0]},
                  {"k": BIGINT, "w": DOUBLE})
    _out, dup = _mj(probe, build, "inner")
    assert dup > 0


def test_merge_join_semi_anti_with_dups_and_nulls():
    probe = _page({"k": [1, 2, None, 5], "v": [1.0, 2.0, 3.0, 4.0]},
                  {"k": BIGINT, "v": DOUBLE})
    build = _page({"k": [1, 1, 7], "w": [0.0, 0.0, 0.0]},
                  {"k": BIGINT, "w": DOUBLE})
    out, _d = _mj(probe, build, "semi")
    flags = [bool(f) for f in np.asarray(out.columns[-1].values)[:4]]
    assert flags == [True, False, False, False]
    out, _d = _mj(probe, build, "anti_exists")
    flags = [bool(f) for f in np.asarray(out.columns[-1].values)[:4]]
    assert flags == [False, True, True, True]
    # NOT IN with a NULL build key -> nothing survives
    build_n = _page({"k": [1, None], "w": [0.0, 0.0]},
                    {"k": BIGINT, "w": DOUBLE})
    out, _d = _mj(probe, build_n, "anti")
    flags = [bool(f) for f in np.asarray(out.columns[-1].values)[:4]]
    assert flags == [False, False, False, False]


def test_merge_join_string_keys():
    probe = _page({"k": ["apple", "kiwi", "pear"], "v": [1.0, 2.0, 3.0]},
                  {"k": VARCHAR, "v": DOUBLE})
    build = _page({"k": ["pear", "apple"], "w": [10.0, 20.0]},
                  {"k": VARCHAR, "w": DOUBLE})
    out, dup = _mj(probe, build, "inner")
    assert dup == 0
    rows = sorted(out.to_pylist())
    assert rows == [("apple", 1.0, "apple", 20.0),
                    ("pear", 3.0, "pear", 10.0)]


def test_merge_join_matches_hash_join_random():
    rng = np.random.RandomState(7)
    pk = rng.randint(0, 50, 300)
    bk = rng.permutation(60)[:40]          # unique build keys
    probe = _page({"k": pk.tolist(),
                   "v": rng.rand(300).round(3).tolist()},
                  {"k": BIGINT, "v": DOUBLE})
    build = _page({"k": bk.tolist(),
                   "w": rng.rand(40).round(3).tolist()},
                  {"k": BIGINT, "w": DOUBLE})
    m, dup = _mj(probe, build, "inner")
    assert dup == 0
    h, _tot = hash_join(probe, build, [0], [0], 1024, "inner")
    assert sorted(m.to_pylist()) == sorted(h.to_pylist())


# One algorithm for every join type and key shape: the equivalence
# matrix below holds merge_join to a plain Python oracle and to hash_join.

_NAN = float("nan")
_KEY_SHAPES = {
    # name: (key types, key domain)
    "bigint": ((BIGINT,), [(k,) for k in range(100, 112)]),
    "int_date": ((INTEGER, DATE),
                 [(i, 9000 + d) for i in range(4) for d in range(3)]),
    "string": ((VARCHAR,), [(w,) for w in (
        "ash", "birch", "cedar", "elm", "fir", "larch", "oak", "pine",
        "rowan", "yew")]),
    # -0.0 == +0.0 and NaN == NaN are one key each (SQL grouping
    # semantics, ops/keys.values_equal)
    "double": ((DOUBLE,), [(v,) for v in (
        0.0, _NAN, 1.5, -2.25, float("inf"), 7.0, 1e-300, -1e18)]),
}
_JOIN_TYPES = ("inner", "left", "full", "semi", "anti", "anti_exists")
_VARIANTS = ("nulls_both", "nulls_probe", "nulls_build",
             "dup_between_nulls")


def _key_equal(a, b):
    """Python image of the join's key equality: no NULL, NaN == NaN."""
    if any(x is None for x in a + b):
        return False
    return all(x == y or (x != x and y != y) for x, y in zip(a, b))


def _side(keys, stored, types, payload_name, payload_type, payload,
          live):
    """A page whose first `live` rows are live and whose other rows are
    dead but look alive (real values, null flags down); a NULL key
    physically stores `stored`, a real key of the domain."""
    import jax.numpy as jnp
    from presto_tpu.data.column import Column
    cap = 256
    cols = []
    for j, t in enumerate(types):
        phys = [s[j] if k[j] is None else k[j]
                for k, s in zip(keys, stored)]
        if t is VARCHAR:
            c = Column.from_strings(phys, capacity=cap)
        else:
            c = Column.from_numpy(np.asarray(phys, dtype=t.dtype), t,
                                  capacity=cap)
        nulls = np.ones(cap, bool)
        nulls[:len(keys)] = [k[j] is None for k in keys]
        cols.append(Column(c.values, jnp.asarray(nulls), c.type,
                           c.dictionary))
    cols.append(Column.from_numpy(
        np.asarray(payload, dtype=payload_type.dtype), payload_type,
        capacity=cap))
    return Page(tuple(cols), jnp.asarray(live, jnp.int32),
                tuple(f"k{j}" for j in range(len(types)))
                + (payload_name,))


def _join_case(shape, variant, seed):
    types, domain = _KEY_SHAPES[shape]
    rng = np.random.RandomState(seed)
    nk = len(types)
    null_probe = variant != "nulls_build"
    null_build = variant != "nulls_probe"

    def with_null(k):
        j = rng.randint(nk)
        return tuple(None if i == j else x for i, x in enumerate(k))

    order = [domain[i] for i in rng.permutation(len(domain))]
    bkeys = order[:len(domain) - 2]            # unique; two keys absent
    if shape == "double":                      # probe sends the other zero
        domain = domain + [(-0.0,)]
    bstored = list(bkeys)
    if null_build:
        # NULL-key rows that physically store a live row's key
        for at in (1, 4):
            bkeys.insert(at, with_null(bkeys[at]))
            bstored.insert(at, bstored[at])
    if variant == "dup_between_nulls":
        # k, NULL (storing k), k: only the nulls lane brings the two
        # live rows together
        k = bstored[2]
        bkeys[2:3] = [k, with_null(k), k]
        bstored[2:3] = [k, k, k]
    b_live = len(bkeys)
    bkeys += bstored[:3]                       # dead rows, live keys
    bstored += bstored[:3]

    pkeys = [domain[i] for i in rng.randint(0, len(domain), 40)]
    pstored = list(pkeys)
    if null_probe:
        for at in (0, 7, 19):
            pkeys[at] = with_null(pkeys[at])
    p_live = len(pkeys)
    pkeys += pstored[:5]
    pstored += pstored[:5]

    probe = _side(pkeys, pstored, types, "pv", DOUBLE,
                  [i + 0.5 for i in range(len(pkeys))], p_live)
    build = _side(bkeys, bstored, types, "bw", BIGINT,
                  [1000 + i for i in range(len(bkeys))], b_live)
    return probe, build, pkeys[:p_live], bkeys[:b_live]


def _norm(rows):
    return sorted(tuple(repr(v) for v in r) for r in rows)


def _oracle(jt, prows, brows, pkeys, bkeys):
    none_p = (None,) * len(prows[0])
    none_b = (None,) * len(brows[0])
    hits = [[j for j, bk in enumerate(bkeys) if _key_equal(pk, bk)]
            for pk in pkeys]
    if jt in ("semi", "anti", "anti_exists"):
        build_null = any(None in bk for bk in bkeys)
        out = []
        for r, pk, h in zip(prows, pkeys, hits):
            if jt == "semi":
                flag = bool(h)
            elif jt == "anti_exists":
                flag = not h
            else:
                flag = not h and None not in pk and not build_null
            out.append(r + (flag,))
        return out
    out = [r + brows[j] for r, h in zip(prows, hits) for j in h]
    if jt in ("left", "full"):
        out += [r + none_b for r, h in zip(prows, hits) if not h]
    if jt == "full":
        seen = {j for h in hits for j in h}
        out += [none_p + r for j, r in enumerate(brows) if j not in seen]
    return out


@pytest.mark.parametrize("variant", _VARIANTS)
@pytest.mark.parametrize("shape", list(_KEY_SHAPES))
@pytest.mark.parametrize("jt", _JOIN_TYPES)
def test_merge_join_equivalence(jt, shape, variant):
    probe, build, pkeys, bkeys = _join_case(
        shape, variant, seed=len(jt) * 31 + len(shape) * 7 + len(variant))
    fields = list(range(len(_KEY_SHAPES[shape][0])))
    out, dup, match = merge_join(probe, build, fields, fields, jt)
    assert out.capacity == probe.capacity + (
        build.capacity if jt == "full" else 0)
    if variant == "dup_between_nulls":
        assert int(dup) > 0
        if jt in ("inner", "left", "full"):
            return          # the caller re-lowers onto hash_join
    else:
        assert int(dup) == 0
    got = _norm(out.to_pylist())
    want = _oracle(jt, probe.to_pylist(), build.to_pylist(), pkeys, bkeys)
    assert got == _norm(want)
    if jt in ("left", "full"):
        hit = np.asarray(match)[:len(pkeys)]
        assert hit.tolist() == [
            any(_key_equal(pk, bk) for bk in bkeys) for pk in pkeys]
    if jt != "full":        # the expansion path has no full-outer form
        h, _total = hash_join(probe, build, fields, fields, 1024, jt)
        assert _norm(h.to_pylist()) == got


def test_merge_join_census():
    """The lanes ride the sorts, the payload is gathered once: a one-key
    inner merge_join holds two sorts over the concatenation and no gather
    over it, no sort carries a 64-bit lane that is not a key, and each
    side's columns move in one gather of their words and one of their
    DOUBLEs — so a later edit cannot quietly bring back the per-lane
    gathers (15-60 ms each on the chip) or argsort's int64 iota."""
    import jax
    probe = _page({"k": list(range(300)), "a": [1.0] * 300,
                   "b": [2] * 300}, {"k": BIGINT, "a": DOUBLE, "b": BIGINT})
    build = _page({"k": list(range(200)), "w": [3.0] * 200},
                  {"k": BIGINT, "w": DOUBLE})
    cap = probe.capacity + build.capacity
    assert probe.capacity != build.capacity

    def eqns(jaxpr):
        for e in jaxpr.eqns:
            yield e
            for v in e.params.values():
                for sub in v if isinstance(v, (list, tuple)) else [v]:
                    inner = getattr(sub, "jaxpr", sub)
                    if hasattr(inner, "eqns"):
                        yield from eqns(inner)

    closed = jax.make_jaxpr(
        lambda p, b: merge_join(p, b, [0], [0], "inner")[:2])(probe, build)
    sorts = gathers = 0
    for e in eqns(closed.jaxpr):
        if e.primitive.name == "sort":
            wide = [v.aval.dtype.itemsize > 4 for v in e.invars]
            assert not any(wide[e.params["num_keys"]:]), e
            assert not e.params["is_stable"], e
            sorts += cap in e.invars[0].aval.shape
        elif e.primitive.name == "gather":
            assert cap not in e.invars[0].aval.shape, e
            assert cap not in e.outvars[0].aval.shape, e
            gathers += 1
    assert (sorts, gathers) == (2, 4)


def test_fragmenter_structure():
    """add_exchanges + create_fragments produce the reference fragment
    shape: partial agg fragment (hash-partitioned) feeding a final
    fragment, SINGLE root for ORDER BY."""
    from presto_tpu.connectors import TpchConnector
    from presto_tpu.plan.fragment import add_exchanges, create_fragments
    from presto_tpu.plan.nodes import AggregationNode, Partitioning, Step
    from presto_tpu.sql.analyzer import Planner
    from presto_tpu.sql.parser import parse_sql

    planner = Planner(TpchConnector(0.01))
    plan = planner.plan_query(parse_sql(
        "select o_custkey, count(*) from orders group by o_custkey "
        "order by 2 desc limit 3"))
    exchanged = add_exchanges(plan)
    frags = create_fragments(exchanged)
    assert [f.fragment_id for f in frags] == [0, 1, 2]
    parts = {f.fragment_id: f.partitioning for f in frags}
    assert parts[0] == Partitioning.SINGLE          # root (sort/limit)
    assert Partitioning.HASH in parts.values()      # partial->final cut
    # Fragment sources form a tree reaching every fragment.
    reachable, todo = set(), [0]
    by_id = {f.fragment_id: f for f in frags}
    while todo:
        f = by_id[todo.pop()]
        reachable.add(f.fragment_id)
        todo.extend(f.remote_sources)
    assert reachable == {0, 1, 2}

    def steps(n, acc):
        if isinstance(n, AggregationNode):
            acc.append(n.step)
        for c in n.children():
            if c is not None:
                steps(c, acc)
    acc = []
    for f in frags:
        steps(f.root, acc)
    assert Step.PARTIAL in acc and Step.FINAL in acc
