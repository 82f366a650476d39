"""The closed loop on the served path, and the record of every request.

``clients`` clients each keep one single-operation request outstanding,
as YCSB's client threads and the paper's threads do.  A client submits
through ``Coalescer.submit``; the loop drives ``Coalescer.pump`` as a
client blocked on its future would, and when a request completes its
client submits the next request of the stream.  Latency runs from the
coalescer's submit stamp to its result stamp (both ``time.monotonic``).
"""

from __future__ import annotations

import collections
import dataclasses
import time
from typing import Callable, Dict

import numpy as np
import jax

from harness import semantics as sem

STALL_S = 0.1           # a turn or a gap between completions this long is a stall


def span(name: str):
    """A host span in the profiler's trace (inert when not tracing)."""
    return jax.profiler.TraceAnnotation(name)


@dataclasses.dataclass
class Records:
    """Every request in submit order, and what came back."""

    codes: np.ndarray
    keys: np.ndarray
    vals: np.ndarray
    submit_t: np.ndarray
    done_t: np.ndarray
    done: np.ndarray
    value: np.ndarray
    found: np.ndarray
    ts: np.ndarray
    pages: Dict[int, np.ndarray]


class ClosedLoop:
    def __init__(self, coalescer, traffic, clients: int, op_batch):
        self.co = coalescer
        self.traffic = traffic
        self.clients = clients
        self.op_batch = op_batch          # repro.api.OpBatch
        self.outstanding = collections.deque()
        self._codes, self._keys, self._vals = [], [], []
        self._submit_t, self._done_t = [], []
        self._value, self._found, self._ts = [], [], []
        self.pages: Dict[int, np.ndarray] = {}
        self.n = 0
        self.submitting = True
        self.slow_turns = []

    def _submit(self, n: int) -> None:
        with span("bench.generate"):
            codes, keys, vals = self.traffic.next(n)
            reqs = [self.op_batch(codes[i:i + 1], keys[i:i + 1], vals[i:i + 1])
                    for i in range(n)]
        with span("bench.submit"):
            futs = [self.co.submit(req) for req in reqs]
        self.outstanding.extend(zip(range(self.n, self.n + n), futs))
        self.n += n
        self._codes.append(codes)
        self._keys.append(keys)
        self._vals.append(vals)
        self._submit_t.extend(f.submit_t for f in futs)
        self._done_t.extend([np.nan] * n)
        self._value.extend([sem.NOT_FOUND] * n)
        self._found.extend([False] * n)
        self._ts.extend([-1] * n)

    def _collect(self) -> int:
        k = 0
        with span("bench.collect"):
            while self.outstanding and self.outstanding[0][1].done:
                i, fut = self.outstanding.popleft()
                r = fut.result()
                self._done_t[i] = fut.done_t
                self._value[i] = int(r.values[0])
                self._found[i] = bool(r.found[0])
                self._ts[i] = int(r.timestamps[0])
                if len(r.range_pages):
                    self.pages[i] = np.asarray(r.range_pages[0], np.int32)
                k += 1
        return k

    def start(self) -> None:
        self._submit(self.clients)

    def run(self, stop: Callable[[], bool]) -> None:
        """Serve until ``stop()``; completed clients submit again.  Each
        turn longer than ``STALL_S`` is kept in ``slow_turns``: its
        start, its seconds, the seconds in ``pump``, and the CPU seconds of
        this thread and of the whole process in it."""
        while not stop():
            w0, c0, p0 = time.perf_counter(), time.thread_time(), time.process_time()
            with span("bench.pump"):
                self.co.pump(force=True)
            w1 = time.perf_counter()
            k = self._collect()
            if k and self.submitting:
                self._submit(k)
            w2 = time.perf_counter()
            if w2 - w0 > STALL_S:
                self.slow_turns.append((w0, w2 - w0, w1 - w0,
                                        time.thread_time() - c0,
                                        time.process_time() - p0))

    def run_for(self, seconds: float) -> float:
        """Serve for ``seconds`` of host clock; returns the window's start."""
        t0 = time.monotonic()
        end = t0 + seconds
        with span("bench.window"):
            self.run(lambda: time.monotonic() >= end)
        return t0

    def drain(self) -> None:
        """Stop submitting and complete every outstanding request."""
        self.submitting = False
        with span("bench.pump"):
            self.co.flush()
        self._collect()

    def records(self) -> Records:
        done_t = np.asarray(self._done_t, np.float64)
        return Records(
            codes=np.concatenate(self._codes),
            keys=np.concatenate(self._keys),
            vals=np.concatenate(self._vals),
            submit_t=np.asarray(self._submit_t, np.float64),
            done_t=done_t,
            done=~np.isnan(done_t),
            value=np.asarray(self._value, np.int64),
            found=np.asarray(self._found, bool),
            ts=np.asarray(self._ts, np.int64),
            pages=self.pages,
        )
