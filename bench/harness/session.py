"""One run of one cell: set-up, the measured window, the check, the line.

The program is reached only through ``repro.api`` and ``repro.serve``.
"""

from __future__ import annotations

import dataclasses
import gc
import sys
import time
import traceback
import types
from typing import Dict, List, Optional

import numpy as np

from harness import build, checks, device, manifest, profile
from harness import semantics as sem
from harness.loop import STALL_S, ClosedLoop, Records, span
from harness.traffic import Traffic

READBACK_WIDTH = 4096


class GcPauses:
    """The collector's pauses while installed, as (generation, seconds)."""

    def __init__(self):
        self.pauses = []
        self._t = None

    def __call__(self, phase, info):
        if phase == "start":
            self._t = time.perf_counter()
        elif self._t is not None:
            self.pauses.append((info["generation"],
                                time.perf_counter() - self._t))
            self._t = None

    def __enter__(self):
        gc.callbacks.append(self)
        return self

    def __exit__(self, *exc):
        gc.callbacks.remove(self)


@dataclasses.dataclass
class Served:
    """What one window produced, with what the check needs."""

    records: Records
    loaded_keys: np.ndarray
    loaded_vals: np.ndarray
    floor: int                      # the clock when the store was adopted
    read_keys: np.ndarray
    read_values: np.ndarray
    setup_s: float
    t0: float
    seconds: float
    counters: Dict[str, int]        # change over the window
    compiles_in_window: int
    trace: Optional[profile.Trace]
    memory_peak_bytes: Optional[int]
    crash: Optional[str]
    gc_pauses: List
    slow_turns: List


def stalls(s: Served) -> str:
    """Where the latency tail comes from: the latency quantiles, the gaps
    between completions longer than ``STALL_S`` (offset from the window's
    start and length), the collector's pauses, the loop's turns over
    ``STALL_S`` with their CPU time, and in a traced run the device's idle
    gaps over ``STALL_S``.  Diagnostics, not metrics."""
    r = s.records
    sel = r.done & (r.submit_t >= s.t0) & (r.submit_t <= s.t0 + s.seconds)
    lat = (r.done_t[sel] - r.submit_t[sel]) * 1e3
    q = np.percentile(lat, [50, 90, 99, 99.9, 100]) if len(lat) else []
    done = np.sort(r.done_t[r.done & (r.done_t >= s.t0)
                            & (r.done_t <= s.t0 + s.seconds)])
    d = np.diff(done)
    big = np.nonzero(d > STALL_S)[0]
    gaps = sorted(((round(float(done[i] - s.t0), 2), round(float(d[i]) * 1e3, 1))
                   for i in big), key=lambda x: -x[1])
    pause = [t * 1e3 for _, t in s.gc_pauses]
    lines = [
        "latency_ms p50/p90/p99/p99.9/max = "
        + "/".join(f"{x:.1f}" for x in q),
        f"completion gaps > {STALL_S * 1e3:.0f} ms: {len(gaps)}, "
        f"{sum(g for _, g in gaps):.0f} ms in all, longest (at s, ms) "
        f"{gaps[:8]}",
        f"gc pauses: {len(pause)} (gen2 {sum(g == 2 for g, _ in s.gc_pauses)}), "
        f"{sum(pause):.1f} ms in all, longest "
        f"{[round(x, 1) for x in sorted(pause)[-5:]]} ms",
    ]
    turns = sorted(s.slow_turns, key=lambda x: -x[1])
    lines.append(
        f"loop turns > {STALL_S * 1e3:.0f} ms: {len(turns)}, longest (ms: "
        "turn/pump/thread cpu/process cpu) "
        + str([tuple(round(v * 1e3, 1) for v in x[1:]) for x in turns[:8]]))
    if s.trace is not None:
        lo = s.trace.window[0]
        idle = sorted(((round(a - lo, 2), round((b - a) * 1e3, 1))
                       for a, b in s.trace.gaps() if b - a > STALL_S),
                      key=lambda x: -x[1])
        lines.append(f"device idle gaps > {STALL_S * 1e3:.0f} ms: {len(idle)},"
                     f" longest (at s, ms) {idle[:8]}")
    return "\n".join(lines)


def _counters(co, db) -> Dict[str, int]:
    """Host-side counters only: reading them never waits for the device."""
    return {**co.stats, **db.executor.stats, "queued": len(co.queue)}


def _read_back(db, keys: np.ndarray, api) -> np.ndarray:
    """Latest values through ``Uruv.lookup``, in fixed-width chunks."""
    out = [np.zeros(0, np.int64)]
    for i in range(0, len(keys), READBACK_WIDTH):
        chunk = keys[i:i + READBACK_WIDTH]
        pad = np.full(READBACK_WIDTH - len(chunk), api.KEY_MAX, np.int32)
        got = db.lookup(np.concatenate([chunk, pad]))
        out.append(np.asarray(got, np.int64)[:len(chunk)])
    return np.concatenate(out)


def serve(cell: Dict, *, seed: int, seconds: float, trace: bool,
          t_start: float, devices) -> Served:
    """Load, warm up, serve the window, drain, read back."""
    from repro import api
    from repro.serve.coalescer import AdmissionPolicy, Coalescer

    bad = sem.disagreements(api)
    if bad:
        raise RuntimeError(f"the program's ADT encoding changed: {bad}")
    config, mix = cell["config_data"], cell["mix_data"]
    meter = device.CompileMeter()

    db, keys, vals = build.load(api, config, seed)
    floor = db.ts
    co = Coalescer(db, AdmissionPolicy(**config["admission"]))
    loop = ClosedLoop(co, Traffic(config, mix, seed, keys),
                      int(config["clients"]), api.OpBatch)
    loop.start()
    target = co.stats["plans"] + int(mix.get("warm", {}).get("plans", 0))
    loop.run(lambda: co.stats["plans"] >= target)
    # what set-up built lives for the whole run: keep the collector from
    # walking it again inside the window
    gc.collect()
    gc.freeze()
    setup_s = time.monotonic() - t_start

    before = _counters(co, db)
    compiles0 = meter.count
    crash, tr, t0 = None, None, time.monotonic()
    pauses = GcPauses()
    loop.slow_turns.clear()                # keep the window's alone
    try:
        with profile.capture(trace) as found:
            with GcPauses() as pauses:
                t0 = loop.run_for(seconds)
            after = _counters(co, db)
            compiles = meter.count - compiles0
            # drained before the profiler stops: its seconds of writing
            # the trace would count in the latency of the last requests
            loop.drain()
    except Exception:                      # the program failed: report it
        crash = traceback.format_exc()
        after, compiles = _counters(co, db), meter.count - compiles0
    if trace and crash is None:
        tr = profile.load(found[0])
        profile.discard(found)
    peak = device.memory_peak_bytes(devices)

    rec = loop.records()
    with span("bench.check"):
        rb_keys = checks.readback_keys(
            rec, keys, np.random.default_rng([seed, 2]), READBACK_WIDTH)
        rb_vals = (_read_back(db, rb_keys, api) if crash is None
                   else np.full(len(rb_keys), sem.NOT_FOUND - 1, np.int64))
    return Served(
        records=rec, loaded_keys=keys, loaded_vals=vals, floor=floor,
        read_keys=rb_keys, read_values=rb_vals, setup_s=setup_s, t0=t0,
        seconds=seconds,
        counters={k: after.get(k, 0) - before.get(k, 0) for k in after},
        compiles_in_window=compiles, trace=tr, memory_peak_bytes=peak,
        crash=crash, gc_pauses=pauses.pauses,
        slow_turns=loop.slow_turns)


def report(s: Served, numbers, metric_specs: List[Dict], devices) -> Dict:
    """The result line: ``correct``, counts, metrics, device, checks."""
    rec = s.records
    t1 = s.t0 + s.seconds
    ctx = types.SimpleNamespace(
        setup_s=s.setup_s, seconds=s.seconds, t0=s.t0, t1=t1, records=rec,
        counters=s.counters, compiles_in_window=s.compiles_in_window,
        trace=s.trace,
        dispatched_ops=s.counters["ops"] - s.counters["queued"])
    metrics = {}
    for spec in metric_specs:
        value = manifest.reader(spec["name"])(ctx)
        if value is not None:
            metrics[spec["name"]] = {"value": float(value), "unit": spec["unit"]}
    submitted = (rec.submit_t >= s.t0) & (rec.submit_t <= t1)
    dev = {**device.describe(devices), "memory_peak_bytes": s.memory_peak_bytes}
    out = {
        "correct": s.crash is None and checks.passed(numbers),
        "attempted": int(submitted.sum()),
        "failed": int((submitted & ~rec.done).sum()),
        "metrics": metrics,
        "device": dev,
    }
    if s.trace is not None:
        dev["busy_s"] = s.trace.busy_s
        dev["window_s"] = s.trace.window_s
        out["breakdown"] = {"device_ops": s.trace.top_modules(),
                            "idle_gaps": s.trace.idle_by_span()}
    out["checks"] = {name: {"value": v, "limit": lim}
                     for name, v, lim in numbers}
    return out


def run(cell: Dict, metric_specs: List[Dict], *, seed: int, seconds: float,
        trace: bool, t_start: float, devices) -> Dict:
    """Serve the cell once, free the program's state, check, report."""
    s = serve(cell, seed=seed, seconds=seconds, trace=trace,
              t_start=t_start, devices=devices)
    with span("bench.check"):
        numbers = checks.compare_served(s)
    if s.crash is not None:
        print(s.crash, file=sys.stderr)
    print(f"setup_s={s.setup_s} window: compiles={s.compiles_in_window} "
          f"counters={s.counters}", file=sys.stderr)
    print(stalls(s), file=sys.stderr)
    return report(s, numbers, metric_specs, devices)
