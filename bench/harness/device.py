"""The device a run measures, its compile cache and its compile events."""

from __future__ import annotations

import os
from pathlib import Path

import jax

CACHE_ENV = "JAX_COMPILATION_CACHE_DIR"
BACKEND_COMPILE = "/jax/core/compile/backend_compile_duration"


def enable_compile_cache(checkout: Path) -> str:
    """JAX's persistent compilation cache at a fixed path inside the
    checkout (the path is part of the cache key), or where
    ``JAX_COMPILATION_CACHE_DIR`` says.  Every program is kept, however
    fast it compiled, so a second run compiles nothing."""
    path = os.environ.get(CACHE_ENV) or str(checkout / ".jax_cache")
    jax.config.update("jax_compilation_cache_dir", path)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", -1)
    return path


class CompileMeter:
    """Counts backend compiles (a persistent-cache load counts too: it is
    a new executable)."""

    def __init__(self):
        self.count = 0
        jax.monitoring.register_event_duration_secs_listener(self._on)

    def _on(self, name, secs, **_):
        if name == BACKEND_COMPILE:
            self.count += 1


def describe(devices) -> dict:
    d = devices[0]
    return {"platform": d.platform, "kind": d.device_kind,
            "count": len(devices)}


def memory_peak_bytes(devices):
    """Peak bytes in use on the fullest chip, where the backend reports it."""
    peaks = [(d.memory_stats() or {}).get("peak_bytes_in_use")
             for d in devices]
    peaks = [p for p in peaks if p is not None]
    return max(peaks) if peaks else None
