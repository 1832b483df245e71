"""chip_smoke.py rehearsed on the CPU: the script the driver runs on the
chip, in-process at SF0.01 with --allow-cpu (one cluster, module-scoped),
and refused without it."""

import json
import os
import sys

import pytest

sys.path.insert(0, os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))

import chip_smoke  # noqa: E402


@pytest.fixture(scope="module")
def rehearsal(tmp_path_factory):
    """(exit code, stdout lines as JSON) of one allowed CPU run, on a
    compile cache of its own: the cold phase is cold whatever another test
    or an earlier run has left in `<checkout>/.jax_cache`."""
    import contextlib
    import io

    import jax
    from jax._src import compilation_cache
    kept = jax.config.jax_compilation_cache_dir
    jax.config.update("jax_compilation_cache_dir",
                      str(tmp_path_factory.mktemp("jax_cache")))
    compilation_cache.reset_cache()  # the directory is read once, at first use
    buf = io.StringIO()
    try:
        with contextlib.redirect_stdout(buf):
            rc = chip_smoke.main(["--sf", "0.01", "--allow-cpu"])
    finally:
        jax.config.update("jax_compilation_cache_dir", kept)
        compilation_cache.reset_cache()
    return rc, [json.loads(ln) for ln in buf.getvalue().splitlines()]


def test_rehearsal_exits_zero_with_contract_last_line(rehearsal):
    import jax
    rc, lines = rehearsal
    assert rc == 0
    dev = jax.devices()[0]
    assert lines[-1] == {"ok": True, "device": {
        "platform": dev.platform, "kind": dev.device_kind,
        "count": len(jax.devices())}}
    assert lines[0]["native_codec"] in (True, False)
    assert "compile_cache_dir" in lines[0]


@pytest.mark.parametrize("query,rows", [("q06", 1), ("q01", 4),
                                        ("q03", 10)])
def test_rehearsal_query_is_exact(rehearsal, query, rows):
    _rc, lines = rehearsal
    [line] = [ln for ln in lines if ln.get("query") == query]
    assert line["exact"] is True and line["rows"] == rows
    assert line["cold_s"] > 0 and line["warm_s"] > 0
    assert line["compilations"]["cold"] >= 1


def test_rehearsal_answers_q06_at_every_discount_of_its_clause(rehearsal):
    """All eight DISCOUNT values against the benchmark's reference, and
    no program built for them: the first q06 built it."""
    _rc, lines = rehearsal
    [line] = [ln for ln in lines if ln.get("query") == "q06_sweep"]
    assert line["discounts"] == [f"0.0{d}" for d in range(2, 10)]
    assert line["exact"] is True and line["not_exact_at"] == []
    assert line["wrong_cells"] == 0 and line["max_rel_err"] <= 1e-9
    assert line["compilations"] == 0


def test_rehearsal_answers_q01_at_both_ends_of_its_clause(rehearsal):
    """DELTA 60 and 120 after the two statements at 90, against the
    benchmark's reference: the folded DATE is an input of the programs
    the first two statements built, so nothing compiles."""
    _rc, lines = rehearsal
    [line] = [ln for ln in lines if ln.get("query") == "q01_sweep"]
    assert line["deltas"] == [60, 120]
    assert line["exact"] is True and line["not_exact_at"] == []
    assert line["wrong_cells"] == 0 and line["max_rel_err"] <= 1e-9
    assert line["compilations"] == 0


def test_refused_without_a_tpu(capsys):
    rc = chip_smoke.main(["--sf", "0.01"])
    out = capsys.readouterr().out
    assert rc != 0
    assert '"ok": true' not in out and out.strip() == ""


REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


@pytest.mark.parametrize("outside", [None, "somewhere/else"],
                         ids=["unset", "set"])
def test_compile_cache_is_placed_from_outside(outside, tmp_path):
    """With JAX_COMPILATION_CACHE_DIR set the package sets no directory;
    unset, the cache is the fixed <checkout>/.jax_cache."""
    import subprocess
    env = {k: v for k, v in os.environ.items()
           if k != "JAX_COMPILATION_CACHE_DIR"}
    want = os.path.join(REPO, ".jax_cache")
    if outside is not None:
        want = env["JAX_COMPILATION_CACHE_DIR"] = str(tmp_path / outside)
    out = subprocess.run(
        [sys.executable, "-c", "import presto_tpu, jax; "
         "print(jax.config.jax_compilation_cache_dir)"],
        cwd=REPO, env=env, capture_output=True, text=True, timeout=120)
    assert out.returncode == 0, out.stderr[-500:]
    assert out.stdout.strip().splitlines()[-1] == want

