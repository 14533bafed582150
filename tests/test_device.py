"""Device policy: importing the package takes no backend, one interpret
decision, and the compile cache placed from outside."""
import os
import pathlib
import subprocess
import sys

import pytest

import jax

from repro import device

ROOT = pathlib.Path(__file__).resolve().parents[1]


def _python(code: str, **env) -> str:
    """Run ``code`` in a fresh interpreter (its JAX state is its own)."""
    full = {**os.environ, "PYTHONPATH": str(ROOT / "src"),
            "JAX_PLATFORMS": "cpu", **env}
    for key in [k for k, v in env.items() if v is None]:
        full.pop(key)
    out = subprocess.run([sys.executable, "-c", code], capture_output=True,
                         text=True, timeout=300, env=full, cwd=str(ROOT),
                         check=True)
    return out.stdout.strip()


def test_import_initialises_no_backend():
    # a parent that imports the package must leave the chip to a child
    out = _python(
        "import repro, repro.kernels, repro.core.analysis\n"
        "from jax._src import xla_bridge\n"
        "print(xla_bridge.backends_are_initialized(), "
        "len(xla_bridge._backends))")
    assert out == "False 0"


def test_interpret_follows_the_backend(monkeypatch):
    assert jax.default_backend() == "cpu"
    assert device.resolve_interpret() is True
    assert device.resolve_interpret(True) is True
    # an explicit False compiles anywhere (the TPU compile tests)
    assert device.resolve_interpret(False) is False
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    assert device.resolve_interpret() is False
    with pytest.raises(RuntimeError, match="hide the device"):
        device.resolve_interpret(True)
    monkeypatch.setattr(jax, "default_backend", lambda: "gpu")
    with pytest.raises(RuntimeError, match="gpu"):
        device.resolve_interpret()


def test_compile_cache_defaults_to_the_checkout():
    out = _python(
        "import jax\nfrom repro import device\n"
        "print(device.use_compile_cache())\n"
        "print(jax.config.jax_compilation_cache_dir)",
        JAX_COMPILATION_CACHE_DIR=None)
    want = str(ROOT / ".jax_cache")
    assert out.splitlines() == [want, want]
    assert ".jax_cache/" in (ROOT / ".gitignore").read_text().splitlines()


def test_compile_cache_env_stands(tmp_path):
    cache = tmp_path / "cache"
    out = _python(
        "import jax, jax.numpy as jnp\nfrom repro import device\n"
        "print(device.use_compile_cache())\n"
        "print(jax.config.jax_compilation_cache_dir)\n"
        "jax.jit(lambda x: jnp.sin(x) @ x)(jnp.ones((64, 64))).block_until_ready()",
        JAX_COMPILATION_CACHE_DIR=str(cache),
        JAX_PERSISTENT_CACHE_MIN_COMPILE_TIME_SECS="0")
    assert out.splitlines() == [str(cache), str(cache)]
    assert any(cache.iterdir()), "nothing was cached under the env dir"
