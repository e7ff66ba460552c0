"""chip_smoke.py off the chip: its cells and checks on a reduced
configuration with the jnp reference, and its refusal to run without a
TPU.  (On the chip it runs at full width with the Pallas kernels.)"""
import importlib.util
import os
import subprocess
import sys
from pathlib import Path

import pytest

SCRIPT = Path(__file__).resolve().parents[1] / "chip_smoke.py"


@pytest.fixture(scope="module")
def smoke():
    spec = importlib.util.spec_from_file_location("chip_smoke", SCRIPT)
    module = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(module)
    return module


def test_main_path_on_reduced_config(smoke, tmp_path, monkeypatch):
    """attach, train, sparse cell, both checkouts: restores bit-identical,
    every kernel dispatched (the reference on CPU), pack/codec/scatter
    each used, no fallback."""
    from repro.models.config import get_config
    from repro.models.testing import reduced

    for gate in ("KISHU_DEVICE_DELTA", "KISHU_DEVICE_HASH",
                 "KISHU_DEVICE_SCATTER"):
        monkeypatch.setenv(gate, "1")
    cfg = reduced(get_config(smoke.ARCH), n_layers=4)
    failed = smoke.main_path(cfg, smoke.Phases("cpu"), str(tmp_path), 0,
                             backend="ref", chunk_bytes=1 << 12, layer=2)
    assert failed == []


def test_refuses_without_tpu():
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    r = subprocess.run([sys.executable, str(SCRIPT)], capture_output=True,
                       text=True, env=env, timeout=300)
    assert r.returncode != 0
    assert "no TPU found" in r.stderr
    assert '"ok"' not in r.stdout          # no result line


def test_compile_cache_dir(monkeypatch):
    """JAX_COMPILATION_CACHE_DIR wins and nothing is set in code; without
    it the cache goes to the checkout's fixed .jax_cache directory."""
    import jax

    from repro.launch.compile_cache import use_compile_cache

    prev = jax.config.jax_compilation_cache_dir
    try:
        monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", "/cache/from/env")
        assert use_compile_cache() == "/cache/from/env"
        assert jax.config.jax_compilation_cache_dir == prev
        monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR")
        path = use_compile_cache()
        assert path == str(SCRIPT.parent / ".jax_cache")
        assert jax.config.jax_compilation_cache_dir == path
        assert use_compile_cache() == path         # the same every time
    finally:
        jax.config.update("jax_compilation_cache_dir", prev)
