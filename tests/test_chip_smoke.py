"""``chip_smoke.py`` off the chip: its serving-and-comparison body at
LUBM(1) on the CPU (auto tiers), its refusal to run without a TPU, and the
entry points' compile-cache directory rule."""
import importlib.util
import json
from pathlib import Path

import jax
import pytest
from jax.experimental.compilation_cache import compilation_cache

from repro.graph import lubm
from repro.launch import serve

REPO = Path(__file__).resolve().parent.parent


@pytest.fixture(scope="module")
def smoke():
    spec = importlib.util.spec_from_file_location("chip_smoke",
                                                  REPO / "chip_smoke.py")
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def test_smoke_body_matches_numpy_executor(smoke, monkeypatch):
    """Every query_batch of the smoke's phases returns NumpyExecutor's
    bindings; the adaptation round (the smoke's AdaptConfig) is accepted
    and drains fully; the write lands."""
    # the smoke writes into the service's store in place: keep the
    # memoized datasets other tests share out of its reach
    monkeypatch.setattr(lubm, "_CACHE", {})
    lines = []
    res = smoke.serve_and_compare(1, 8, 0, log=lines.append)
    assert res["mismatches"] == []
    assert res["batches"] == 3 + res["chunks"]      # cold, warm, final
    assert res["accepted"] and res["drained"]
    assert res["drained_chunks"] == res["chunks"] > 0
    assert res["inserted"] == smoke.WRITE_ROWS
    # auto tiers on a CPU: the fused join pipeline serves on the host
    assert res["counters"]["kernels.dispatch.join.pipeline.host"] > 0
    assert {"load", "bootstrap", "cold_batch", "warm_batch", "adapt",
            "write", "final_batch"} <= set(res["phases"])
    assert sum("equal NumpyExecutor" in ln for ln in lines) == res["batches"]


def test_canon_ignores_row_and_column_order(smoke):
    import numpy as np

    a = {3: np.array([2, 1, 1]), 1: np.array([5, 7, 6])}
    b = {1: np.array([7, 6, 5]), 3: np.array([1, 1, 2])}
    assert smoke._same(a, b)
    assert not smoke._same(a, {1: b[1], 3: np.array([1, 2, 2])})
    assert not smoke._same(a, {1: b[1]})
    assert smoke._same({}, {})


def test_main_refuses_to_run_without_a_tpu(smoke, monkeypatch, capsys):
    monkeypatch.setenv("JAX_PLATFORMS", "cpu")
    with pytest.raises(SystemExit) as e:
        smoke.main([])
    assert e.value.code not in (0, None)
    assert "no TPU" in str(e.value.code)
    out = capsys.readouterr().out
    assert '"ok"' not in out and not any(
        line.startswith("{") and json.loads(line).get("ok")
        for line in out.splitlines())


@pytest.fixture()
def restore_cache_dir():
    was = jax.config.jax_compilation_cache_dir
    yield
    jax.config.update("jax_compilation_cache_dir", was)
    compilation_cache.reset_cache()


@pytest.mark.parametrize("env_dir", [None, "/some/dir"])
def test_compile_cache_dir_rule(monkeypatch, restore_cache_dir, env_dir):
    """With JAX_COMPILATION_CACHE_DIR set the entry points use exactly
    that directory; without it, the checkout's fixed, git-ignored one."""
    if env_dir is None:
        monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
        want = str(REPO / ".jax_cache")
        assert ".jax_cache/" in (REPO / ".gitignore").read_text().split()
    else:
        monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", env_dir)
        want = env_dir
    assert serve.setup_compile_cache() == want
    assert jax.config.jax_compilation_cache_dir == want
