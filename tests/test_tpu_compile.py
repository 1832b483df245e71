"""The TPU's own compiler, asked without a chip: programs of the main path
are compiled for a *described* v5e 2x2 (jax.experimental.topologies), so a
lowering the chip refuses fails here, on the CPU, in tier-1.

Nothing runs — these tests say nothing about results or times. The
topology is described inside a module-scoped fixture (never at import:
only one process may load libtpu, and every xdist worker imports every
test file), the persistent compile cache is off around the compiles (an
entry written for a described chip cannot be read back), and every
compile happens in this process. Keep all such tests in this one file."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P, \
    SingleDeviceSharding

from presto_tpu.data.column import Column, Page, bucket_capacity
from presto_tpu.parallel.mesh import AXIS, stack_pages
from presto_tpu.types import BIGINT, DOUBLE

from tpch_queries import QUERIES

#: TPC-H SF1 lineitem cardinality (specification 4.2.5): fixes the scan
#: capacity bucket without generating the table
SF1_LINEITEM_ROWS = 6_001_215


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies
    try:
        return topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:   # noqa: BLE001 — no libtpu / lock held
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


@pytest.fixture(scope="module")
def mesh4(topo):
    assert len(topo.devices) == 4
    return Mesh(np.asarray(topo.devices), (AXIS,))


@pytest.fixture(scope="module")
def no_compile_cache():
    from jax.experimental.compilation_cache import compilation_cache as cc
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    cc.reset_cache()
    yield
    jax.config.update("jax_enable_compilation_cache", was)
    cc.reset_cache()


@pytest.fixture(scope="module")
def engine():
    from presto_tpu.connectors import TpchConnector
    from presto_tpu.exec import LocalEngine
    return LocalEngine(TpchConnector(0.01))


def _plan(engine, sql):
    from presto_tpu.sql.parser import parse_sql
    return engine.planner.plan_query(parse_sql(sql))


def _shapes(tree, sharding, capacity=None):
    """ShapeDtypeStructs for a Page pytree on a described device; with
    `capacity`, row-wise leaves take that leading size instead."""
    def one(x):
        shape = x.shape
        if capacity is not None and shape:
            shape = (capacity,) + shape[1:]
        return jax.ShapeDtypeStruct(shape, x.dtype, sharding=sharding)
    return jax.tree_util.tree_map(one, tree)


def test_fused_q06_at_sf1_capacity(engine, one_chip, no_compile_cache,
                                   monkeypatch):
    """The whole q06 program at the SF1 scan capacity, from shapes alone:
    the 0.01 connector plans it, the executor is told SF1's row count."""
    from presto_tpu.expr.params import lift_plan
    ex = engine.executor
    monkeypatch.setattr(ex, "_scan_rows", lambda node: SF1_LINEITEM_ROWS)
    # as the executor lowers it: the five literals are inputs (0-d)
    lifted = lift_plan(_plan(engine, QUERIES[6]))
    assert len(lifted.values) == 5
    fn, scans, _watch = ex._lower(lifted.plan, {})
    cap = bucket_capacity(SF1_LINEITEM_ROWS)
    assert [s.capacity for s in scans] == [cap]
    small = engine.connector.table("lineitem").page(
        columns=list(scans[0].columns))
    compiled = jax.jit(fn).lower(
        [_shapes(small, one_chip, capacity=cap)],
        tuple(jax.ShapeDtypeStruct((), v.dtype, sharding=one_chip)
              for v in lifted.values)).compile()
    mem = compiled.memory_analysis()
    # four 8-byte columns and their null masks at 8M rows
    assert mem.argument_size_in_bytes > 4 * 8 * cap


@pytest.mark.slow
def test_q03_join_island_at_sf001(engine, one_chip, no_compile_cache):
    """One q03 join island (the customer x orders hash join with the
    filters feeding it) as the island executor lowers it. Out of tier-1
    like dist_aggregate and q01: the TPU compiler takes ~70 s over a hash
    join even at these 16k/2k-row capacities (PR 22; ROADMAP S2)."""
    from presto_tpu.plan.nodes import JoinNode
    ex = engine.executor
    plan = ex._prepare(_plan(engine, QUERIES[3]))

    def joins(n):
        for c in n.children():
            yield from joins(c)
        if isinstance(n, JoinNode):
            yield n

    node = next(joins(plan))    # innermost join: leaf scans only
    mini, children, _base = ex._island_of(node)
    assert not children, "innermost join island reads scans, not islands"
    fn, scans, _watch = ex._lower(mini, {})
    pages = [ex._fetch(s) for s in scans]
    compiled = jax.jit(fn).lower(
        [_shapes(p, one_chip) for p in pages]).compile()
    assert compiled.memory_analysis().generated_code_size_in_bytes > 0


def _stacked_kv_shapes(mesh, cap):
    """Four local (bigint key, double value) pages of capacity `cap`,
    stacked and sharded over the mesh — as shapes."""
    local = Page.from_columns(
        [Column.from_numpy(np.zeros(1, np.int64), BIGINT, capacity=cap),
         Column.from_numpy(np.zeros(1, np.float64), DOUBLE, capacity=cap)],
        1, ("k", "v"))
    stacked = stack_pages([local] * mesh.devices.size)
    return _shapes(stacked, NamedSharding(mesh, P(AXIS)))


@pytest.mark.parametrize("broadcast,collective", [
    (False, "all-to-all"), (True, "all-gather")],
    ids=["dist_hash_join", "broadcast_hash_join"])
def test_mesh_join_compiles_for_four_chips(mesh4, no_compile_cache,
                                           broadcast, collective):
    """Guard for the int64 mesh reductions: the TPU compiler lowers only
    Sum all-reduces for 64-bit integers, so the "needed" counters must
    not ride a lax.pmax."""
    from presto_tpu.parallel import dist_hash_join
    cap = 4096
    shapes = _stacked_kv_shapes(mesh4, cap)

    def prog(probe, build):
        return dist_hash_join(mesh4, probe, build, [0], [0], cap,
                              broadcast=broadcast)

    text = jax.jit(prog).lower(shapes, shapes).compile().as_text()
    assert collective in text


def test_dist_executor_counters_reduce_on_four_chips(mesh4,
                                                     no_compile_cache):
    """DistExecutor._wrap's reduction of the stacked int64 counters, on a
    dummy vector: what every multi-device program the mesh executors
    build ends with."""
    from presto_tpu.exec.dist_executor import DistExecutor
    ex = DistExecutor.__new__(DistExecutor)
    ex.mesh, ex.ndev = mesh4, 4

    def fn(pages, params=()):
        return pages[0], jnp.arange(6, dtype=jnp.int64) + pages[0][0]

    wrapped = ex._wrap(fn)
    vec = jax.ShapeDtypeStruct((4, 8), jnp.int64,
                               sharding=NamedSharding(mesh4, P(AXIS)))
    compiled = jax.jit(wrapped).lower([vec]).compile()
    assert "all-gather" in compiled.as_text()
