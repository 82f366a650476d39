"""The profiler trace of a run's window, reduced to intervals.

``capture`` wraps the window in ``jax.profiler`` tracing (Python tracing
off: it would slow the host path being measured), ``load`` turns the
written ``.xplane.pb`` into a :class:`Trace`:

  * device operations (the ``XLA Ops`` line of each ``/device:`` plane),
    whose union over the window is the device's busy time;
  * executed modules (the ``XLA Modules`` line), summed by name pattern
    by the per-layer metric readers;
  * the benchmark's own host spans (``bench.*`` TraceAnnotations), which
    name what the host was doing in each idle gap of the device.
"""

from __future__ import annotations

import bisect
import contextlib
import fnmatch
import glob
import os
import shutil
import tempfile
from typing import Dict, Iterable, List, Optional, Sequence, Tuple

import jax

Interval = Tuple[float, float]          # seconds on the trace's clock
WINDOW_SPAN = "bench.window"


def union(intervals: Iterable[Interval]) -> List[Interval]:
    """Sorted, disjoint union of intervals."""
    out: List[List[float]] = []
    for a, b in sorted(intervals):
        if out and a <= out[-1][1]:
            out[-1][1] = max(out[-1][1], b)
        else:
            out.append([a, b])
    return [(a, b) for a, b in out]


def clip(intervals: Iterable[Interval], lo: float, hi: float) -> List[Interval]:
    return [(max(a, lo), min(b, hi)) for a, b in intervals
            if min(b, hi) > max(a, lo)]


class Trace:
    """Device intervals per chip, named modules and host spans of a window."""

    def __init__(self, device_ops: Sequence[Sequence[Interval]],
                 modules: Sequence[Tuple[str, float, float]],
                 spans: Sequence[Tuple[str, float, float]],
                 window: Interval):
        self.window = window
        lo, hi = window
        self.device_ops = [union(clip(ops, lo, hi)) for ops in device_ops]
        self.modules = [(n, a, b) for n, a, b in modules
                        if min(b, hi) > max(a, lo)]
        self.spans = sorted((a, b, n) for n, a, b in spans
                            if n != WINDOW_SPAN and min(b, hi) > max(a, lo))

    @property
    def window_s(self) -> float:
        return self.window[1] - self.window[0]

    @property
    def busy_s(self) -> float:
        """Seconds in which an operation ran, averaged over the chips."""
        if not self.device_ops:
            return 0.0
        return sum(b - a for ops in self.device_ops for a, b in ops) \
            / len(self.device_ops)

    def module_seconds(self, patterns: Sequence[str]) -> Optional[float]:
        """Device seconds of the modules matching any pattern, in the
        window, summed over chips; ``None`` where no module matches, so a
        renamed program reads as nothing rather than as no time."""
        lo, hi = self.window
        spans = [min(b, hi) - max(a, lo) for n, a, b in self.modules
                 if any(fnmatch.fnmatchcase(n, p) for p in patterns)]
        return sum(spans) if spans else None

    def top_modules(self, k: int = 10) -> List[List]:
        tot: Dict[str, float] = {}
        lo, hi = self.window
        for n, a, b in self.modules:
            tot[n] = tot.get(n, 0.0) + min(b, hi) - max(a, lo)
        return [[n, s] for n, s in
                sorted(tot.items(), key=lambda x: -x[1])[:k]]

    def gaps(self) -> List[Interval]:
        """Idle intervals of the first chip within the window."""
        lo, hi = self.window
        busy = self.device_ops[0] if self.device_ops else []
        out, at = [], lo
        for a, b in busy:
            if a > at:
                out.append((at, a))
            at = max(at, b)
        if hi > at:
            out.append((at, hi))
        return out

    def idle_by_span(self, k: int = 10) -> List[List]:
        """Idle seconds by the host span that overlaps each gap most
        (``host.other`` where no span does), largest first."""
        # the benchmark's spans follow one another without overlap, so
        # those meeting a gap are the run that ends at the last one
        # starting before the gap's end
        starts = [s[0] for s in self.spans]
        tot: Dict[str, float] = {}
        for a, b in self.gaps():
            best, name = 0.0, "host.other"
            j = bisect.bisect_left(starts, b) - 1
            while j >= 0 and self.spans[j][1] > a:
                sa, sb, sn = self.spans[j]
                ov = min(b, sb) - max(a, sa)
                if ov > best:
                    best, name = ov, sn
                j -= 1
            tot[name] = tot.get(name, 0.0) + (b - a)
        return [[n, s] for n, s in
                sorted(tot.items(), key=lambda x: -x[1])[:k]]


@contextlib.contextmanager
def capture(enabled: bool):
    """Trace the enclosed block into a temporary directory; yields a list
    that holds the ``.xplane.pb`` path once the block has ended."""
    found: List[str] = []
    if not enabled:
        yield found
        return
    logdir = tempfile.mkdtemp(prefix="bench-trace-")
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    jax.profiler.start_trace(logdir, profiler_options=opts)
    try:
        yield found
    finally:
        jax.profiler.stop_trace()
        found.extend(glob.glob(os.path.join(logdir, "**", "*.xplane.pb"),
                               recursive=True))
        found.append(logdir)


def load(path: str) -> Trace:
    """Reduce one ``.xplane.pb`` to a :class:`Trace` (seconds)."""
    from jax.profiler import ProfileData

    pd = ProfileData.from_file(path)
    device_ops, modules, spans = [], [], []
    window = None
    for plane in pd.planes:
        if plane.name.startswith("/device:"):
            ops = []
            for line in plane.lines:
                if line.name == "XLA Ops":
                    ops.extend((e.start_ns * 1e-9,
                                (e.start_ns + e.duration_ns) * 1e-9)
                               for e in line.events)
                elif line.name == "XLA Modules":
                    modules.extend((e.name, e.start_ns * 1e-9,
                                    (e.start_ns + e.duration_ns) * 1e-9)
                                   for e in line.events)
            if ops:
                device_ops.append(ops)
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                for e in line.events:
                    if e.name.startswith("bench."):
                        a = e.start_ns * 1e-9
                        b = a + e.duration_ns * 1e-9
                        spans.append((e.name, a, b))
                        if e.name == WINDOW_SPAN:
                            window = (a, b)
    if window is None:
        raise ValueError(f"no {WINDOW_SPAN} span in {path}")
    return Trace(device_ops, modules, spans, window)


def discard(found: List[str]) -> None:
    """Delete the trace directory ``capture`` made."""
    if found:
        shutil.rmtree(found[-1], ignore_errors=True)
