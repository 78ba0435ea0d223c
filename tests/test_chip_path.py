"""The chip path, exercised on the CPU.

- the data plane resolves from the platform: compiled Pallas on a TPU,
  the f64 "np" mirror (and the Pallas interpreter) elsewhere;
- the persistent compile cache honours ``JAX_COMPILATION_CACHE_DIR``
  and otherwise lives at a fixed path in the checkout;
- ``chip_smoke.py``'s phases run end to end at a tiny size, on the
  platform's own backend and on the Pallas kernel (interpreted here),
  with every answer checked against the f64 oracle; its ``main()``
  refuses to run without a TPU.
"""
import os
import sys

import jax
import pytest

from repro import compile_cache
from repro.core import IndexConfig, TileIndex
from repro.data import make_synthetic_dataset
from repro.kernels import ops

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


@pytest.fixture(scope="module")
def chip_smoke():
    sys.path.insert(0, ROOT)
    try:
        import chip_smoke as mod
    finally:
        sys.path.remove(ROOT)
    return mod


@pytest.fixture(scope="module")
def tiny(chip_smoke):
    return chip_smoke.make_data(20_000, 3, 20)


@pytest.mark.parametrize("platform,backend,interpret",
                         [("tpu", "pallas", False), ("cpu", "np", True),
                          ("gpu", "np", True)])
def test_data_plane_resolves_from_platform(monkeypatch, platform, backend,
                                           interpret):
    monkeypatch.setattr(jax, "default_backend", lambda: platform)
    assert ops.index_backend() == backend
    assert ops.resolve_interpret() is interpret
    assert ops.resolve_interpret(True) is True   # explicit asks win
    ds = make_synthetic_dataset(n=2_000, seed=1)
    assert TileIndex(ds, IndexConfig())._backend == backend
    assert TileIndex(ds, IndexConfig(backend="jnp"))._backend == "jnp"


def test_compile_cache_honours_env(monkeypatch, tmp_path):
    before = jax.config.jax_compilation_cache_dir
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(tmp_path))
    old_min = jax.config.jax_persistent_cache_min_compile_time_secs
    try:
        assert compile_cache.enable_compile_cache() == str(tmp_path)
        # JAX reads the variable itself; no directory is set in code
        assert jax.config.jax_compilation_cache_dir == before
    finally:
        jax.config.update("jax_persistent_cache_min_compile_time_secs",
                          old_min)


def test_compile_cache_default_is_fixed_in_checkout(monkeypatch):
    monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
    before = jax.config.jax_compilation_cache_dir
    old_min = jax.config.jax_persistent_cache_min_compile_time_secs
    try:
        path = compile_cache.enable_compile_cache()
        assert path == os.path.join(ROOT, ".jax_cache")
        assert jax.config.jax_compilation_cache_dir == path
        assert compile_cache.enable_compile_cache() == path   # stable
    finally:
        jax.config.update("jax_compilation_cache_dir", before)
        jax.config.update("jax_persistent_cache_min_compile_time_secs",
                          old_min)
    with open(os.path.join(ROOT, ".gitignore")) as f:
        assert ".jax_cache/" in f.read().split()


def test_chip_smoke_phases_on_platform_backend(chip_smoke, tiny):
    ds, windows = tiny
    eng, host = chip_smoke.host_phase(ds, windows)
    assert host["backend"] == ops.index_backend()
    assert host["answers_checked"] == 26 and host["kernel_calls"] > 0
    serve = chip_smoke.serving_phase(eng, windows[8:])
    assert serve["answers_checked"] == 12 and serve["kernel_calls"] > 0
    spmd = chip_smoke.spmd_phase(ds, windows, jax.devices()[:1])
    assert spmd["answers_checked"] == 7
    assert spmd["shard_devices"] == [jax.devices()[0].id]


def test_chip_smoke_phases_on_pallas_kernel(chip_smoke, tiny):
    """The chip's own data plane — every index, split and serving pass
    through the Pallas kernel (interpreted off the chip) — answers
    within the oracle checks."""
    ds, windows = tiny
    eng, host = chip_smoke.host_phase(ds, windows[:8], n_scalar=3,
                                      backend="pallas")
    assert host["backend"] == "pallas" and host["kernel_calls"] > 0
    serve = chip_smoke.serving_phase(eng, windows[8:], n_sessions=2,
                                     n_ticks=2)
    assert serve["kernel_calls"] > 0


def test_chip_smoke_refuses_without_tpu(chip_smoke, capsys):
    assert chip_smoke.main(["--rows", "1000"]) != 0
    out = capsys.readouterr().out
    assert '"ok"' not in out and out.strip() == ""


def test_chip_smoke_alone_fails(tmp_path):
    """Copied into a directory with nothing else of the repository, the
    script fails on import and prints no result."""
    import shutil
    import subprocess
    shutil.copy(os.path.join(ROOT, "chip_smoke.py"), tmp_path)
    env = {k: v for k, v in os.environ.items() if k != "PYTHONPATH"}
    out = subprocess.run([sys.executable, "chip_smoke.py"], cwd=tmp_path,
                         env=env, capture_output=True, text=True,
                         timeout=120)
    assert out.returncode != 0
    assert '"ok"' not in out.stdout
