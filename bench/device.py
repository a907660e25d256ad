"""The accelerator a run stands on: chip check, compile cache, seeds, facts.

Nothing here touches JAX at import; `open_devices` is the first JAX call of a
run, so the compile cache is configured before anything compiles.
"""
from __future__ import annotations

import pathlib
import zlib

ROOT = pathlib.Path(__file__).resolve().parents[1]
# Fixed path inside the checkout: the cache directory is part of every
# entry's key, so a directory that moved between runs would never hit.
CACHE_DIR = ROOT / ".jax_cache"


class NoAccelerator(RuntimeError):
    """JAX found no TPU, or fewer chips than the cell asks for."""


def open_devices(chips: int, require_accelerator: bool = True) -> list:
    """Configure the compile cache, then return the devices of this run."""
    import jax

    # JAX writes its entries into the directory but does not create it.
    pathlib.Path(CACHE_DIR).mkdir(parents=True, exist_ok=True)
    jax.config.update("jax_compilation_cache_dir", str(CACHE_DIR))
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)
    devices = jax.devices()
    if require_accelerator:
        if devices[0].platform != "tpu":
            raise NoAccelerator(f"needs a TPU, JAX found {devices[0].platform!r}")
        if len(devices) < chips:
            raise NoAccelerator(f"the cell needs {chips} chips, JAX found {len(devices)}")
    return devices[:chips]


def device_facts(devices) -> dict:
    """platform, device_kind, count, and the peak bytes in use on the fullest
    chip so far (0 where the backend keeps no memory statistics)."""
    peak = 0
    for d in devices:
        stats = d.memory_stats() or {}
        peak = max(peak, int(stats.get("peak_bytes_in_use", 0)))
    d = devices[0]
    return {"platform": d.platform, "kind": d.device_kind, "count": len(devices),
            "memory_peak_bytes": peak}


def prng_key(seed: int, stream: str):
    """A JAX key for one named stream of ``seed``; every bit of a seed of up
    to 64 bits reaches the key (`jax.random.PRNGKey` keeps only 32)."""
    import jax

    seed = int(seed)
    key = jax.random.PRNGKey(seed & 0xFFFFFFFF)
    key = jax.random.fold_in(key, (seed >> 32) & 0x7FFFFFFF)
    return jax.random.fold_in(key, zlib.crc32(stream.encode()) & 0x7FFFFFFF)
