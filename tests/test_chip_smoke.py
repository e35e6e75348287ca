"""``chip_smoke.py`` and what it relies on, rehearsed on the CPU.

The script itself refuses any platform but a TPU, so these tests run its
phase functions directly at tiny sizes (Pallas kernels in interpret mode,
the four-chip phase on four host placeholder devices), and check the
pieces the chip run depends on: importing the package claims no device,
and the compile cache lives at a fixed path.
"""
from __future__ import annotations

import os
import shutil
import subprocess
import sys

import jax
import pytest

from conftest import CHIP_SMOKE as SMOKE
from conftest import load_chip_smoke, run_multidevice

REPO = os.path.abspath(os.path.join(os.path.dirname(__file__), ".."))


def test_package_import_starts_no_backend():
    """On a TPU host a process that only imports the package must not hold
    the chip: no JAX backend may start at import."""
    code = ("import repro.core, repro.engine, repro.serve, repro.shard, "
            "repro.kernels\n"
            "from jax._src import xla_bridge\n"
            "assert not xla_bridge.backends_are_initialized()\n"
            "print('NO BACKEND')\n")
    env = dict(os.environ, PYTHONPATH=os.path.join(REPO, "src"))
    r = subprocess.run([sys.executable, "-c", code], env=env,
                       capture_output=True, text=True, timeout=300)
    assert r.returncode == 0 and "NO BACKEND" in r.stdout, r.stderr


def test_compile_cache_honours_env(monkeypatch):
    from repro.runtime import compile_cache
    before = jax.config.jax_compilation_cache_dir
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", "/somewhere/else")
    assert compile_cache.enable_compile_cache() == "/somewhere/else"
    assert jax.config.jax_compilation_cache_dir == before  # nothing set


def test_compile_cache_defaults_to_repo(monkeypatch):
    from repro.runtime import compile_cache
    before = jax.config.jax_compilation_cache_dir
    monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
    try:
        path = compile_cache.enable_compile_cache()
        assert path == os.path.join(REPO, ".jax_cache")
        assert jax.config.jax_compilation_cache_dir == path
        assert compile_cache.enable_compile_cache() == path  # fixed path
    finally:
        jax.config.update("jax_compilation_cache_dir", before)


@pytest.mark.parametrize("where", ["repo", "alone"])
def test_chip_smoke_refuses_without_tpu(tmp_path, where):
    """On the CPU, or copied away from the package, the script exits
    non-zero and never prints a result line."""
    script = SMOKE
    if where == "alone":
        script = str(tmp_path / "chip_smoke.py")
        shutil.copy(SMOKE, script)
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    env.pop("PYTHONPATH", None)
    r = subprocess.run([sys.executable, script], env=env, cwd=tmp_path,
                       capture_output=True, text=True, timeout=300)
    assert r.returncode != 0
    assert '"ok"' not in r.stdout


def test_chip_smoke_phases_end_to_end():
    """The one-chip phases at a tiny size: every rung answers, every
    sampled reply equals the host reference, the six kernels match the
    jnp path (interpret mode here), and no fallback path is taken."""
    smoke = load_chip_smoke()
    out = smoke.serve_phase(scale=9, rounds=2, batch_ops=8)
    assert all(out["rungs"].values()) and out["references_matched"] >= 9
    out = smoke.kernel_phase(scale=8, n_srcs=8, src_chunk=64)
    assert len(out["kernels_matched"]) == 6


def test_chip_smoke_sharded_phase_four_devices():
    """``--chips 4``'s phase on four host placeholder devices: bands on
    four distinct devices, every answer equal to the host reference."""
    out = run_multidevice("""
from conftest import load_chip_smoke
out = load_chip_smoke().sharded_phase(scale=9, rounds=2, batch_ops=8)
assert out["devices"] == 4 and out["references_matched"] == 36, out
assert out["rungs"]["delta"] > 0, out
print("SHARDED OK")
""")
    assert "SHARDED OK" in out
