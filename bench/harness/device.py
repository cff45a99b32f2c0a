"""The chip the run is on: its check, its peaks, its memory, and the
compile cache kept inside the checkout."""
from __future__ import annotations

import json
import os

from harness.spec import BENCH_DIR, ROOT


class NoAccelerator(RuntimeError):
    """JAX found no TPU, or fewer chips than the cell asks for."""


def peaks(kind: str) -> dict:
    """Published peaks of one chip of ``kind`` (``peaks.json``). A kind
    that is not in the table is an error, never a default."""
    with open(os.path.join(BENCH_DIR, "peaks.json")) as f:
        table = json.load(f)["devices"]
    if kind not in table:
        raise KeyError(f"no published peaks for device kind {kind!r}; "
                       f"known: {sorted(table)}")
    return table[kind]


def require_chips(chips: int):
    """The first ``chips`` TPU devices; raises :class:`NoAccelerator`
    where JAX sees another platform or fewer chips."""
    import jax
    devs = jax.devices()
    if devs[0].platform != "tpu":
        raise NoAccelerator(f"no TPU: JAX found {devs[0].platform} devices")
    if len(devs) < chips:
        raise NoAccelerator(f"the cell needs {chips} chips; JAX found "
                            f"{len(devs)}")
    return devs[:chips]


#: the persistent compilation cache, at a fixed path in the checkout
CACHE_DIR = os.path.join(ROOT, ".jax_cache")
#: bytes the cache may hold. JAX never stores an entry larger than
#: this; the serving stepper's sweep holds its matrix as constants
#: (1.4 GB at n = 8192) and is compiled anew for every new matrix, so
#: storing it would only write 1.4 GB to disk per run.
CACHE_BYTES = 512 << 20


def enable_cache() -> str:
    """JAX's persistent compilation cache in :data:`CACHE_DIR`, whatever
    the environment names, capped at :data:`CACHE_BYTES`. Every other
    program is cached, however quick its compile, so that a second run
    of a cell loads all of them."""
    import jax
    jax.config.update("jax_compilation_cache_dir", CACHE_DIR)
    jax.config.update("jax_compilation_cache_max_size", CACHE_BYTES)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)
    return CACHE_DIR


def describe(devs) -> dict:
    """The result line's ``device``: as JAX reports it, with the peak
    bytes in use on the fullest chip."""
    peak = 0
    for d in devs:
        stats = d.memory_stats() or {}
        peak = max(peak, int(stats.get("peak_bytes_in_use", 0)))
    return {"platform": devs[0].platform, "kind": devs[0].device_kind,
            "count": len(devs), "memory_peak_bytes": peak}
