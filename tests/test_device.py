"""kubernetes_tpu/device.py: where compiled programs are kept, and a run
that finds no TPU."""

import os
import subprocess
import sys

import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
PROBE = """
import jax, jax.numpy as jnp
from kubernetes_tpu.device import use_compile_cache
print(use_compile_cache())
jax.jit(lambda x: x * 2 + 1)(jnp.ones(3)).block_until_ready()
print(jax.config.jax_compilation_cache_dir)
"""


def _run(env, code=PROBE):
    return subprocess.run([sys.executable, "-c", code], cwd=REPO, env=env,
                          capture_output=True, text=True, timeout=120)


def test_compile_cache_placed_by_env(tmp_path):
    """JAX_COMPILATION_CACHE_DIR set: the helper sets nothing and entries
    land there."""
    cache = tmp_path / "cache"
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               JAX_COMPILATION_CACHE_DIR=str(cache),
               JAX_PERSISTENT_CACHE_MIN_COMPILE_TIME_SECS="0")
    proc = _run(env)
    assert proc.returncode == 0, proc.stderr[-2000:]
    assert proc.stdout.split() == [str(cache), str(cache)]
    assert os.listdir(cache)


def test_compile_cache_defaults_to_checkout():
    env = {k: v for k, v in os.environ.items()
           if k != "JAX_COMPILATION_CACHE_DIR"}
    env["JAX_PLATFORMS"] = "cpu"
    proc = _run(env)
    assert proc.returncode == 0, proc.stderr[-2000:]
    want = os.path.join(REPO, ".jax_cache")
    assert proc.stdout.split() == [want, want]


def test_require_tpu_names_the_platform_it_found(monkeypatch):
    """No TPU and no named CPU rehearsal: an error naming the platform found,
    never a silent CPU run. (The backend here is already the CPU; only the
    rehearsal's name is taken away.)"""
    from kubernetes_tpu.device import NoTPUError, require_tpu

    monkeypatch.delenv("JAX_PLATFORMS")
    with pytest.raises(NoTPUError, match="platform 'cpu'"):
        require_tpu()
    monkeypatch.setenv("JAX_PLATFORMS", "cpu")
    assert require_tpu()["platform"] == "cpu"
