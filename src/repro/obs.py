"""Spans and counters on the served path (DESIGN.md Sec 12, "Tracing").

Two instruments, both always on:

  * :func:`span` — a host span on the profiler's clock
    (``jax.profiler.TraceAnnotation``).  Inert unless a trace is being
    captured (about 0.85 us on the host CPU of a TPU v5e machine, 1.9 us
    with :func:`timed`'s clock reads); under ``jax.profiler.start_trace``
    it lands on the host plane of the same trace as the device's
    operations, with its keyword arguments (``plan=...``) attached.
  * :func:`timed` — the same span plus an always-on counter: the wall
    time of the block, ``time.perf_counter_ns``, added to
    ``stats[key]`` as integer nanoseconds.

:func:`host_read` is the one device->host read of the request path: it
bumps ``stats["host_syncs"]``.

Spans and timers sit at per-plan or per-pass boundaries, never per
request, and never inside a jitted body.
"""

from __future__ import annotations

import time
from typing import Dict, Optional

import jax


def span(name: str, **args):
    """A host span named ``name`` carrying ``args`` in the trace."""
    return jax.profiler.TraceAnnotation(name, **args)


class timed:
    """``with timed(stats, key, name, **args):`` — :func:`span` around the
    block, and its elapsed ``perf_counter_ns`` added to ``stats[key]``."""

    __slots__ = ("_stats", "_key", "_span", "_t0")

    def __init__(self, stats: Dict[str, int], key: str, name: str, **args):
        self._stats = stats
        self._key = key
        self._span = jax.profiler.TraceAnnotation(name, **args)

    def __enter__(self) -> None:
        self._t0 = time.perf_counter_ns()
        self._span.__enter__()

    def __exit__(self, *exc) -> None:
        self._span.__exit__(*exc)
        self._stats[self._key] += time.perf_counter_ns() - self._t0


def host_read(stats: Optional[Dict[str, int]], x):
    """Read ``x`` (a device value, or a tuple of them: one transfer) to the
    host, counting one sync in ``stats["host_syncs"]``."""
    if stats is not None:
        stats["host_syncs"] = stats.get("host_syncs", 0) + 1
    return jax.device_get(x)
