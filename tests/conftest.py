"""Test harness: run everything on a virtual 8-device CPU mesh.

Mirrors the reference's in-JVM multi-node trick (DistributedQueryRunner,
presto-tests/.../DistributedQueryRunner.java:114): N devices inside one
process, real collectives between them. Tests never touch an accelerator:
JAX_PLATFORMS=cpu goes into os.environ before jax is imported, so this
process and every child it starts (the CLI under test, cluster workers)
stay on the CPU.
"""

import os

flags = os.environ.get("XLA_FLAGS", "")
if "xla_force_host_platform_device_count" not in flags:
    os.environ["XLA_FLAGS"] = (
        flags + " --xla_force_host_platform_device_count=8").strip()

os.environ["JAX_PLATFORMS"] = "cpu"

# One temp root per test process, inherited by the children it starts.
# The driver runs six xdist workers that would otherwise share the
# system temp directory, and the stray-directory guards
# (test_spool_chaos.py, test_elastic.py, test_memory_chaos.py) can only
# answer for the spill / spool / shuffle directories their own process
# made: another worker's live cluster is not this one's leak.
import shutil  # noqa: E402
import tempfile  # noqa: E402

_TMP_ROOT = tempfile.mkdtemp(prefix="presto_tpu_tests_")
tempfile.tempdir = os.environ["TMPDIR"] = _TMP_ROOT

# Hermetic learned-capacity store: without this, a previous session's
# grown caps warm-start plans and tests that assert on cold-start
# behavior (overflow retries, compile counts) become order-dependent.
# setdefault so a harness that pins its own path wins.
os.environ.setdefault(
    "PRESTO_TPU_CAPS_CACHE",
    os.path.join(tempfile.mkdtemp(prefix="presto_tpu_caps_"),
                 "caps.json"))

import jax  # noqa: E402

assert len(jax.devices()) == 8 and jax.devices()[0].platform == "cpu", \
    f"test harness needs 8 CPU devices, got {jax.devices()}"

# Lock-order sanitizer: every Lock/RLock/Condition allocated from repo
# code during the suite is instrumented; pytest_sessionfinish fails the
# run if the global acquisition-order graph picked up a cycle. Opt out
# with PRESTO_TPU_LOCKSAN=0.
if os.environ.get("PRESTO_TPU_LOCKSAN", "1").lower() not in ("0", "false"):
    from presto_tpu.analysis import locksan

    locksan.install()


def pytest_configure(config):
    config.addinivalue_line(
        "markers",
        "slow: excluded from the smoke tier (-m 'not slow'); heavy XLA "
        "collective compiles or large scale factors")


def pytest_sessionfinish(session, exitstatus):
    from presto_tpu.analysis import locksan

    shutil.rmtree(_TMP_ROOT, ignore_errors=True)
    san = locksan.active()
    if san is None:
        return
    print("\n" + san.report())
    if san.cycles() and session.exitstatus == 0:
        session.exitstatus = 1
