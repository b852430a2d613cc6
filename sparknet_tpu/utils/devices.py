"""Device policy: which platform a process runs on, where compiled
programs are cached, and what the chip can do at its peak.

The platform is what the environment says.  ``JAX_PLATFORMS=cpu`` (the
tier-1 tests, the CPU-mesh dryrun) means the CPU, with as many virtual
devices as the caller asks for — multi-chip shardings are validated on
``--xla_force_host_platform_device_count`` devices running the real
``shard_map`` paths.  Anything else means the accelerator: a path that
needs the chip and finds none raises instead of carrying on slower.
"""

import os
import re
from typing import Dict, Optional

_COUNT_FLAG = "xla_force_host_platform_device_count"

# <checkout>/.jax_cache: a fixed path, because the directory is part of
# the cache key's lookup — one that moves (tempfile, pid, time) never hits
_CHECKOUT = os.path.dirname(
    os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
)
DEFAULT_COMPILE_CACHE_DIR = os.path.join(_CHECKOUT, ".jax_cache")

# bf16 MXU peak FLOP/s per jax device, keyed by the exact ``device_kind``
# string observed on the chip.  "TPU v5 lite": 197 TFLOP/s — Google Cloud
# documentation, "TPU v5e" system architecture (device_kind observed on
# the v5e machine, PR 21).  A TPU that is not in the table is an error,
# not a default: add its row with the source.
PEAK_BF16_FLOPS = {
    "TPU v5 lite": 197e12,
}


def cpu_requested() -> bool:
    """True when the environment pins jax to the CPU."""
    return os.environ.get("JAX_PLATFORMS", "").strip().lower() == "cpu"


def force_virtual_cpu_devices(n_devices: int) -> None:
    """Put jax on at least ``n_devices`` virtual CPU devices.

    Must run before the first *use* of a backend in this process: XLA
    parses ``XLA_FLAGS`` once, so a backend that already came up with
    fewer devices cannot grow — that case raises."""
    flags = os.environ.get("XLA_FLAGS", "")
    m = re.search(rf"--{_COUNT_FLAG}=(\d+)", flags)
    if m is None:
        os.environ["XLA_FLAGS"] = (
            flags + f" --{_COUNT_FLAG}={n_devices}"
        ).strip()
    elif int(m.group(1)) < n_devices:
        os.environ["XLA_FLAGS"] = (
            flags[: m.start()] + f"--{_COUNT_FLAG}={n_devices}" + flags[m.end() :]
        )
    os.environ["JAX_PLATFORMS"] = "cpu"

    import jax

    jax.config.update("jax_platforms", "cpu")
    if len(jax.devices()) < n_devices:
        raise RuntimeError(
            f"need {n_devices} virtual CPU devices but jax sees "
            f"{len(jax.devices())}; a backend initialised before "
            f"XLA_FLAGS could take effect — set XLA_FLAGS="
            f"--{_COUNT_FLAG}={n_devices} JAX_PLATFORMS=cpu in the "
            f"environment before starting Python"
        )


def ensure_devices(n_devices: int) -> None:
    """At least ``n_devices`` devices of the platform the environment
    chose: virtual CPU devices under ``JAX_PLATFORMS=cpu``, otherwise the
    devices there are — too few is an error, never a switch to the CPU."""
    if cpu_requested():
        force_virtual_cpu_devices(n_devices)
        return
    import jax

    if len(jax.devices()) < n_devices:
        raise RuntimeError(
            f"need {n_devices} devices, jax sees {len(jax.devices())} "
            f"({jax.devices()[0].platform}); run on a host with enough "
            "chips, or set JAX_PLATFORMS=cpu for the virtual CPU mesh"
        )


def require_chip():
    """The accelerator's devices, or RuntimeError: for paths whose result
    only means something on the chip (the smoke, device benchmarks)."""
    if cpu_requested():
        raise RuntimeError(
            "this path needs the TPU but JAX_PLATFORMS=cpu pins the CPU"
        )
    import jax

    devices = jax.devices()
    if devices[0].platform != "tpu":
        raise RuntimeError(
            f"this path needs the TPU; jax found {devices[0].platform} "
            f"({devices[0].device_kind})"
        )
    return devices


def describe_devices() -> Dict[str, object]:
    """What every benchmark result carries: the device as jax reports it."""
    import jax

    devices = jax.devices()
    return {
        "platform": devices[0].platform,
        "device_kind": devices[0].device_kind,
        "device_count": len(devices),
    }


def peak_bf16_flops(device) -> Optional[float]:
    """bf16 peak FLOP/s of ``device``; None on the CPU, which has no
    meaningful peak (utilization is omitted there).  An unknown TPU
    raises."""
    if device.platform != "tpu":
        return None
    try:
        return PEAK_BF16_FLOPS[device.device_kind]
    except KeyError:
        raise KeyError(
            f"no peak recorded for device_kind {device.device_kind!r}; "
            "add it to PEAK_BF16_FLOPS with its source"
        ) from None


def enable_compile_cache() -> str:
    """Turn on jax's persistent compilation cache; returns its directory.

    Where ``JAX_COMPILATION_CACHE_DIR`` is set jax reads it and this sets
    no other; otherwise the cache lives at ``<checkout>/.jax_cache``.  Every
    entry point calls this before its first compile, so processes of one
    chip call — and calls on a machine that keeps that directory — share
    compiled programs."""
    import jax

    directory = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if not directory:
        directory = DEFAULT_COMPILE_CACHE_DIR
        jax.config.update("jax_compilation_cache_dir", directory)
    if not cpu_requested():
        # jax skips programs that compile in under a second; a process on
        # the chip compiles over a hundred of those (17 of the smoke's 29
        # warm compile seconds, PERF.md "On the chip"), so keep them all,
        # wherever the cache lives: a program that is never written reads
        # ``miss`` in its build record for ever (obs/programs.py)
        jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    return directory
