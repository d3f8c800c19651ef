"""Device utilities + memory observability.

Reference analogs: python/paddle/device/__init__.py (get/set_device,
synchronize) and the allocator stat surface
paddle/phi/core/memory/stats.cc + python/paddle/device/cuda/
max_memory_allocated/memory_allocated/... .

TPU formulation: PJRT owns allocation, so the stats come from
Device.memory_stats() (bytes_in_use / peak_bytes_in_use on TPU). Backends
whose PJRT client doesn't publish stats (CPU tests) fall back to summing
jax.live_arrays() per device, with the peak tracked across queries and op
dispatches in this process.
"""

from __future__ import annotations

import numpy as np

import jax

__all__ = [
    "device_count",
    "get_device",
    "set_device",
    "synchronize",
    "memory_allocated",
    "max_memory_allocated",
    "memory_reserved",
    "max_memory_reserved",
    "reset_max_memory_allocated",
    "memory_stats",
]

_peaks: dict[int, int] = {}


def _dev(device=None):
    if device is None:
        return jax.devices()[0]
    if isinstance(device, int):
        return jax.devices()[device]
    return device


def device_count() -> int:
    return jax.device_count()


def get_device() -> str:
    d = jax.devices()[0]
    return f"{d.platform}:{d.id}"


def set_device(device: str):
    # single-process placement is owned by jax; accepted for API parity
    return device


def synchronize(device=None):
    """Block until all dispatched work on the device finishes (reference
    paddle.device.synchronize / cudaDeviceSynchronize)."""
    for a in jax.live_arrays():
        try:
            a.block_until_ready()
        except Exception:
            pass


def _live_bytes(dev) -> int:
    total = 0
    for a in jax.live_arrays():
        try:
            for s in a.addressable_shards:
                if s.device == dev:
                    total += int(np.dtype(a.dtype).itemsize
                                 * int(np.prod(s.data.shape)))
        except Exception:
            continue
    return total


def memory_stats(device=None) -> dict:
    """Raw PJRT allocator stats dict (reference stats.cc DeviceMemoryStat*).
    The CPU backend publishes none, so there — and only there — the dict is
    synthesized from live arrays as a test convenience. A TPU that reports
    no stats is an error: every HBM budget (KV pool sizing, peak memory)
    would otherwise be read off a guess."""
    d = _dev(device)
    stats = d.memory_stats()
    if stats is None:
        if d.platform != "cpu":
            raise RuntimeError(
                f"{d} ({d.device_kind}) reports no memory_stats(); refusing "
                "to synthesize them off the CPU backend")
        in_use = _live_bytes(d)
        peak = max(_peaks.get(d.id, 0), in_use)
        _peaks[d.id] = peak
        stats = {"bytes_in_use": in_use, "peak_bytes_in_use": peak,
                 "synthesized": True}
    return stats


def memory_allocated(device=None) -> int:
    """reference python/paddle/device/cuda/__init__.py memory_allocated."""
    return int(memory_stats(device).get("bytes_in_use", 0))


def max_memory_allocated(device=None) -> int:
    """reference max_memory_allocated (stats.cc peak tracking).

    PJRT's peak counter cannot be rewound, so after
    reset_max_memory_allocated() this reports the real peak only once it
    exceeds the recorded baseline; until then it reports current usage."""
    d = _dev(device)
    s = memory_stats(device)
    peak = int(s.get("peak_bytes_in_use", s.get("bytes_in_use", 0)))
    base = _reset_baseline.get(d.id)
    if base is not None and peak <= base:
        return int(s.get("bytes_in_use", 0))
    return peak


def memory_reserved(device=None) -> int:
    s = memory_stats(device)
    return int(s.get("bytes_reserved", s.get("bytes_in_use", 0)))


def max_memory_reserved(device=None) -> int:
    s = memory_stats(device)
    return int(s.get("peak_bytes_reserved", s.get("peak_bytes_in_use", 0)))


_reset_baseline: dict[int, int] = {}


def reset_max_memory_allocated(device=None):
    d = _dev(device)
    _peaks[d.id] = _live_bytes(d)
    try:
        s = d.memory_stats()
    except Exception:
        s = None
    if s:
        _reset_baseline[d.id] = int(s.get("peak_bytes_in_use", 0))
