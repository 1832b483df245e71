"""presto_tpu — a TPU-native distributed SQL execution framework.

A ground-up re-design of the capabilities of Presto (reference:
/root/reference, see SURVEY.md) for TPU hardware:

- columnar Pages are fixed-capacity padded device arrays (Column = values +
  null mask; strings are codes into *sorted* host-side dictionaries), so every
  operator is a statically-shaped XLA program — no recompilation storms
  (SURVEY.md §7.3 hard part #1);
- operators (scan/filter/project, grouped aggregation, joins, sort/topN,
  window) are jit-compiled whole-fragment kernels rather than the reference's
  pull-based Operator.getOutput/addInput driver loop
  (reference: presto-main-base/.../operator/Driver.java:70);
- the repartitioned exchange (reference:
  presto-main-base/.../operator/repartition/PartitionedOutputOperator.java:57)
  is a hash-partitioned `all_to_all` over a `jax.sharding.Mesh` (ICI) inside a
  multi-chip worker, and Presto's pull-based HTTP SerializedPage protocol
  across hosts (DCN);
- the coordinator-facing protocol (PlanFragment / TaskUpdateRequest /
  TaskInfo; reference: presto-main-base/.../server/TaskUpdateRequest.java:37)
  is implemented as plain dataclasses + JSON codec so the worker grafts onto
  an unmodified Java coordinator exactly like presto-native-execution's C++
  worker (reference: presto-native-execution/presto_cpp/main/TaskResource.cpp).
"""

import os as _os

import jax

# SQL semantics need exact 64-bit integers (BIGINT) and doubles. TPU emulates
# f64/i64; the hot paths (filter masks, hashes, group codes) stay in 32-bit.
jax.config.update("jax_enable_x64", True)

# XLA's CPU compiler recurses deeply on large fragment programs (multi-join
# TPC-H fragments segfault at the default 8 MiB stack); the main-thread
# stack grows on demand up to RLIMIT_STACK, so raise it to the hard limit.
try:
    import resource

    _soft, _hard = resource.getrlimit(resource.RLIMIT_STACK)
    if _soft != resource.RLIM_INFINITY:
        resource.setrlimit(resource.RLIMIT_STACK, (_hard, _hard))
except (ImportError, ValueError, OSError):  # non-POSIX or locked down
    pass

# Persistent compilation cache: big fragment programs take tens of
# seconds to compile and every worker task builds its programs anew, so a
# repeated statement or a process restart (bench per-query subprocesses,
# worker restarts) loads cached executables instead of compiling again.
# The cache is placed from outside: where JAX_COMPILATION_CACHE_DIR is set
# JAX reads it itself and no directory is set here; otherwise it is the
# fixed <checkout>/.jax_cache (the path is part of the cache key, so it
# must not move between runs).
if not _os.environ.get("JAX_COMPILATION_CACHE_DIR"):
    jax.config.update(
        "jax_compilation_cache_dir",
        _os.path.join(_os.path.dirname(_os.path.dirname(
            _os.path.abspath(__file__))), ".jax_cache"))
# What is kept: every program whose compile took this long. Whether the
# next process finds a program must follow from what the program is, not
# from how long one compile of it happened to take, so the line lies in
# a gap of the compile times read on a v5e (PERF.md section 6, PR 35):
# below it the eager one-op programs of the host path (jit_add,
# jit_convert_element_type, ...) and the one-row finals of Q6 and Q3,
# 0.04-0.59 s, which every process compiles anew (the same section has
# the reading at 0.0); above it every fused island, from
# Q6's scan-filter-sum at 2.1 s over Q1's 3.2-8.7 s to Q3's and Q18's
# joins and aggregations at 17-150 s.
PERSISTENT_CACHE_MIN_COMPILE_SECS = 1.0
jax.config.update("jax_persistent_cache_min_compile_time_secs",
                  PERSISTENT_CACHE_MIN_COMPILE_SECS)
jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)

from presto_tpu.types import (  # noqa: E402
    BOOLEAN, TINYINT, SMALLINT, INTEGER, BIGINT, REAL, DOUBLE, VARCHAR, DATE,
    TIMESTAMP, DecimalType, Type,
)
from presto_tpu.data.column import Column, Page  # noqa: E402

__version__ = "0.1.0"

__all__ = [
    "BOOLEAN", "TINYINT", "SMALLINT", "INTEGER", "BIGINT", "REAL", "DOUBLE",
    "VARCHAR", "DATE", "TIMESTAMP", "DecimalType", "Type", "Column", "Page",
]
