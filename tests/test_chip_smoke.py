"""``chip_smoke.py`` and the compile-cache placement it relies on.

The smoke's own checks run here at a tiny scale on the CPU (Pallas in
interpret mode): the device-path client must match the numpy client on
patterns, every read and the cache stats.  The script itself must refuse
to run without a TPU.
"""

import importlib.util
import os
import subprocess
import sys
from pathlib import Path

import jax
import pytest

from repro import compile_cache

ROOT = Path(__file__).resolve().parents[1]


@pytest.fixture
def chip_smoke(monkeypatch):
    monkeypatch.syspath_prepend(str(ROOT))      # for benchmarks.workloads
    spec = importlib.util.spec_from_file_location("chip_smoke",
                                                  ROOT / "chip_smoke.py")
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def test_smoke_checks_hold_at_tiny_scale(chip_smoke, capsys):
    checks = chip_smoke.run(n_blocks=3_000, warm_sessions=200,
                            measured_sessions=40, seed=1)
    # interpret mode lowers to plain XLA, not to the Mosaic custom call
    assert checks.pop("compiled Mosaic kernel") is False
    assert checks and all(checks.values()), checks
    assert "parity reads: identical" in capsys.readouterr().out


def test_smoke_refuses_without_tpu(tmp_path):
    env = {**os.environ, "JAX_PLATFORMS": "cpu"}
    out = subprocess.run([sys.executable, str(ROOT / "chip_smoke.py")],
                         cwd=tmp_path, env=env, capture_output=True,
                         text=True, timeout=300)
    assert out.returncode != 0
    assert '"ok"' not in out.stdout
    assert "needs a TPU" in out.stderr


@pytest.fixture
def cache_config():
    """Restore the process-wide JAX cache settings the test changes."""
    keys = ("jax_compilation_cache_dir",
            "jax_persistent_cache_min_compile_time_secs")
    saved = {k: getattr(jax.config, k) for k in keys}
    yield
    for k, v in saved.items():
        jax.config.update(k, v)


def test_compile_cache_defaults_to_ignored_checkout_dir(monkeypatch,
                                                        cache_config):
    monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
    path = compile_cache.enable_compile_cache()
    assert path == str(ROOT / ".jax_cache")
    assert jax.config.jax_compilation_cache_dir == path
    ignored = (ROOT / ".gitignore").read_text().splitlines()
    assert ".jax_cache/" in ignored


def test_compile_cache_uses_environment_dir(tmp_path):
    """With the variable set, compiled programs land there and the
    checkout's default directory is never configured."""
    cache = tmp_path / "cache"
    prog = ("import jax, jax.numpy as jnp\n"
            "from repro.compile_cache import enable_compile_cache\n"
            "print(enable_compile_cache())\n"
            "print(jax.config.jax_compilation_cache_dir)\n"
            "jax.jit(lambda x: x * 2 + 1)(jnp.arange(8)).block_until_ready()\n")
    env = {**os.environ, "JAX_PLATFORMS": "cpu",
           "JAX_COMPILATION_CACHE_DIR": str(cache),
           "PYTHONPATH": str(ROOT / "src")}
    out = subprocess.run([sys.executable, "-c", prog], cwd=tmp_path, env=env,
                         capture_output=True, text=True, timeout=300)
    assert out.returncode == 0, out.stderr
    assert out.stdout.split() == [str(cache), str(cache)]
    assert any(cache.iterdir())
