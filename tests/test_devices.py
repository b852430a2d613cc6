"""Device policy (``utils/devices.py``): the platform is what the
environment says, the compile cache can be placed from outside, and the
peak table knows only chips someone observed."""

import os
import subprocess
import sys
import types

import jax
import pytest

from sparknet_tpu.utils import devices

_REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def test_require_chip_raises_on_the_cpu(monkeypatch):
    monkeypatch.setenv("JAX_PLATFORMS", "cpu")
    assert devices.cpu_requested()
    with pytest.raises(RuntimeError, match="needs the TPU"):
        devices.require_chip()
    # the environment not pinning the CPU is not enough: jax's first
    # device must be a TPU
    monkeypatch.setenv("JAX_PLATFORMS", "")
    assert jax.devices()[0].platform == "cpu"
    with pytest.raises(RuntimeError, match="jax found cpu"):
        devices.require_chip()


def test_ensure_devices_never_switches_platform(monkeypatch):
    devices.ensure_devices(8)  # the suite's virtual CPU mesh
    monkeypatch.setenv("JAX_PLATFORMS", "")
    with pytest.raises(RuntimeError, match="need 64 devices"):
        devices.ensure_devices(64)


def test_describe_devices_is_what_jax_reports():
    d = jax.devices()
    assert devices.describe_devices() == {
        "platform": d[0].platform,
        "device_kind": d[0].device_kind,
        "device_count": len(d),
    }


def test_compile_cache_env_wins(monkeypatch):
    """JAX_COMPILATION_CACHE_DIR set: jax reads it, the code sets no
    directory of its own."""
    before = jax.config.jax_compilation_cache_dir
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", "/somewhere/outside")
    assert devices.enable_compile_cache() == "/somewhere/outside"
    assert jax.config.jax_compilation_cache_dir == before


def test_compile_cache_default_is_fixed_across_processes():
    """Unset: <checkout>/.jax_cache, the same in every process (the path
    is part of what makes a cache entry findable)."""
    code = (
        "import jax; from sparknet_tpu.utils.devices import "
        "enable_compile_cache as e; d = e(); "
        "assert jax.config.jax_compilation_cache_dir == d; print(d)"
    )
    env = {
        k: v for k, v in os.environ.items()
        if k != "JAX_COMPILATION_CACHE_DIR"
    }
    env.update(PYTHONPATH=_REPO, JAX_PLATFORMS="cpu")
    seen = {
        subprocess.run(
            [sys.executable, "-c", code], env=env, cwd=cwd, check=True,
            capture_output=True, text=True, timeout=120,
        ).stdout.strip()
        for cwd in (_REPO, "/")
    }
    assert seen == {os.path.join(_REPO, ".jax_cache")}
    assert devices.DEFAULT_COMPILE_CACHE_DIR in seen


def test_peak_table_knows_only_observed_chips():
    cpu = jax.devices()[0]
    assert devices.peak_bf16_flops(cpu) is None  # MFU is omitted there
    v5e = types.SimpleNamespace(platform="tpu", device_kind="TPU v5 lite")
    assert devices.peak_bf16_flops(v5e) == 197e12
    unknown = types.SimpleNamespace(platform="tpu", device_kind="TPU v5")
    with pytest.raises(KeyError, match="no peak recorded"):
        devices.peak_bf16_flops(unknown)
