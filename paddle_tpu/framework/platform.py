"""Platform helpers: the chip-peaks table, the one compile-cache site, and
the CPU pin the tests and rehearsals use.
"""
from __future__ import annotations

import os
import re

_COUNT_FLAG = re.compile(r"--xla_force_host_platform_device_count=(\d+)")

# Per-chip peaks keyed on a ``device_kind`` substring (lowercased match):
# (dense-MXU bf16 peak FLOPs/s, HBM bandwidth bytes/s).  The single source
# of truth for every MFU / roofline computation inside the program
# (telemetry's device feed; the benchmark keeps a copy with its source
# as benchmarks/peaks.json).  There is
# deliberately NO catch-all TPU entry: a TPU whose kind is not listed is
# an error wherever an MFU or a roofline is computed, not a guess.
DEVICE_PEAKS: dict = {
    "v4": (275e12, 1.23e12),
    "v5p": (459e12, 2.77e12),
    "v5 lite": (197e12, 0.82e12),
    "v5e": (197e12, 0.82e12),
    "v6 lite": (918e12, 1.64e12),
    "v6e": (918e12, 1.64e12),
    "v6": (918e12, 1.64e12),
    "trillium": (918e12, 1.64e12),
}


def device_peaks(device_kind: str | None, platform: str | None) -> tuple:
    """(peak_flops, peak_hbm_bytes_per_s) of a jax device, from its
    ``.device_kind`` and ``.platform``.  Off a TPU there is no peak to
    compare with: (None, None), and MFU reports as null.  On a TPU the
    kind must be in :data:`DEVICE_PEAKS`; one that is not raises."""
    if (platform or "").lower() != "tpu":
        return (None, None)
    kind = (device_kind or "").lower()
    for k, peaks in DEVICE_PEAKS.items():
        if k in kind:
            return peaks
    raise ValueError(
        f"no peaks known for TPU device_kind {device_kind!r}: add it to "
        f"framework.platform.DEVICE_PEAKS with its source")


def peak_flops(device_kind: str | None, platform: str | None):
    """bf16 peak FLOPs/s for a device, or None off a TPU."""
    return device_peaks(device_kind, platform)[0]


# <checkout>/.jax_cache: resolved from this file, so every process of one
# checkout agrees on it (the directory is part of the cache's key)
_DEFAULT_CACHE_DIR = os.path.join(
    os.path.dirname(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__)))), ".jax_cache")


def init_compile_cache() -> str:
    """Place jax's persistent compilation cache — THE one site that does.

    Where ``JAX_COMPILATION_CACHE_DIR`` is set, jax has already read it
    and nothing is set here; otherwise the cache is ``.jax_cache`` at
    the root of this checkout (git-ignored).  Sub-second executables
    (a per-bucket prefill, a decode step of a small model) are cached
    too.  Returns the active directory.  To run without the cache use
    jax's own ``jax_enable_compilation_cache`` option."""
    import jax

    path = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if not path:
        path = _DEFAULT_CACHE_DIR
        jax.config.update("jax_compilation_cache_dir", path)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    return path


def force_cpu(n_devices: int = 1):
    """Pin the CPU platform with >= ``n_devices`` virtual devices.

    Must run BEFORE any jax backend initializes; raises if a non-CPU backend
    already won or the virtual-device flag landed too late.  Returns the
    first ``n_devices`` devices.
    """
    flags = os.environ.get("XLA_FLAGS", "")
    m = _COUNT_FLAG.search(flags)
    if m is None:
        os.environ["XLA_FLAGS"] = (
            flags + f" --xla_force_host_platform_device_count={n_devices}")
    elif int(m.group(1)) < n_devices:
        os.environ["XLA_FLAGS"] = _COUNT_FLAG.sub(
            f"--xla_force_host_platform_device_count={n_devices}", flags)
    os.environ["JAX_PLATFORMS"] = "cpu"

    import jax

    jax.config.update("jax_platforms", "cpu")
    devs = jax.devices()
    if devs[0].platform != "cpu":
        raise RuntimeError(
            f"need the CPU platform but got {devs[0].platform!r}; a non-CPU "
            f"backend was already initialized before force_cpu() was called")
    if len(devs) < n_devices:
        raise RuntimeError(
            f"need {n_devices} virtual CPU devices, have {len(devs)}; "
            f"XLA_FLAGS was set too late (backend already initialized). Set "
            f"XLA_FLAGS=--xla_force_host_platform_device_count={n_devices} "
            f"before importing jax")
    return devs[:n_devices]
