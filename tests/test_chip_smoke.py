"""Start-up guards of the chip path that a CPU host can check.

``chip_smoke.py`` refuses to run without a TPU; the launcher mesh has
``Auto`` axes (the backbone's sharding constraints are refused on
``Explicit`` ones); the compile cache goes where it is told; the peaks
table refuses a device kind it does not know; and importing
the package starts no backend (so the kernels decide interpret mode when
called, on the backend that runs them).
"""
import os
import subprocess
import sys

import jax
import pytest
from jax.sharding import AxisType

from repro.launch import cache, peaks
from repro.launch.mesh import make_mesh

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def test_chip_smoke_fails_without_tpu():
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    out = subprocess.run([sys.executable, "chip_smoke.py"], cwd=ROOT, env=env,
                         capture_output=True, text=True, timeout=300)
    assert out.returncode != 0
    assert '"ok": true' not in out.stdout
    assert "not a TPU" in out.stderr


def test_launcher_mesh_axes_are_auto():
    mesh = make_mesh()
    assert mesh.devices.shape == (1, 1)
    assert mesh.axis_names == ("data", "model")
    assert all(t == AxisType.Auto for t in mesh.axis_types)


def test_compile_cache_dir(monkeypatch):
    was = jax.config.jax_compilation_cache_dir
    try:
        monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
        path = cache.enable_compile_cache()
        assert path == os.path.join(ROOT, ".jax_cache")
        assert jax.config.jax_compilation_cache_dir == path
        monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", "/elsewhere")
        jax.config.update("jax_compilation_cache_dir", was)
        assert cache.enable_compile_cache() == "/elsewhere"
        assert jax.config.jax_compilation_cache_dir == was
    finally:
        jax.config.update("jax_compilation_cache_dir", was)


def test_compile_cache_stays_off_outside_a_checkout(monkeypatch, tmp_path):
    """An installed copy (no pyproject.toml above ``src/repro``) has no
    checkout to keep a cache in: nothing is written beside site-packages."""
    was = jax.config.jax_compilation_cache_dir
    installed = tmp_path / "lib" / "python3" / "site-packages" / "repro"
    monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
    monkeypatch.setattr(cache, "__file__",
                        str(installed / "launch" / "cache.py"))
    assert cache.checkout_root() is None
    assert cache.enable_compile_cache() is None
    assert jax.config.jax_compilation_cache_dir == was


def test_peaks_are_keyed_by_device_kind():
    assert peaks.peaks(peaks.V5E).hbm_bytes == 819e9
    with pytest.raises(KeyError, match="no published peaks"):
        peaks.peaks("cpu")


def test_importing_the_package_starts_no_backend():
    code = ("import pkgutil, importlib, repro\n"
            "from jax._src import xla_bridge\n"
            "for m in pkgutil.walk_packages(repro.__path__, 'repro.'):\n"
            "    importlib.import_module(m.name)\n"
            "assert not xla_bridge.backends_are_initialized()\n")
    env = dict(os.environ, PYTHONPATH=os.path.join(ROOT, "src"),
               JAX_PLATFORMS="cpu")
    out = subprocess.run([sys.executable, "-c", code], env=env, cwd=ROOT,
                         capture_output=True, text=True, timeout=300)
    assert out.returncode == 0, out.stderr[-2000:]
