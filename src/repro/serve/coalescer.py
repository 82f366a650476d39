"""Async pipelined admission: coalesce client requests into batched plans.

The paper's wait-free claim is about progress under heavy concurrent
traffic; this layer is where that traffic actually lands.  Many small
client requests (each a few CRUD/range ops) are admitted into a FIFO
queue, coalesced into power-of-two-bucketed `OpBatch` plans, and executed
with the host and the device overlapped:

  * plan N is dispatched with ``Uruv.apply_nowait`` — no host sync; the
    client adopts the speculative store immediately;
  * while the device executes N, the host drains the queue and builds and
    routes plan N+1 (numpy work, future bookkeeping) and dispatches it
    behind N — two plans in flight;
  * only when the pipeline is full (or a client blocks on its future) is
    the OLDEST plan settled: ``Uruv.confirm`` blocks on the accept flag
    (the deferred ``jax.block_until_ready``), materialises per-client
    results, and resolves futures.  A rejected plan (capacity / leaf-batch
    overflow — atomic, store untouched) rolls the client back and replays
    that plan and every later unconfirmed plan through the synchronous
    ``apply`` path at the exact same announce timestamps, so pipelining is
    invisible in results.

Each client gets an :class:`OpFuture` that slices its ops out of the
batched result: values, found mask, per-op linearization timestamps, and
complete range pages are bit-exact with issuing the same coalesced plans
synchronously (property-tested).  The coalescer keeps requests per plan,
in columns: a queued request is a ``(future, plan)`` tuple, a plan's
build concatenates each column once, and a plan's futures resolve in
one pass: a running count of RANGE positions gives each request its
RANGE entries, a request with none shares read-only empty range fields,
and every future of the plan gets one ``done_t``
(``stats["plans_range_free"]`` counts the plans with no RANGE op).

Coalescing is SKEW-AWARE (contention-adapting trees, arXiv:1709.00722):
zipfian hot-key traffic is exactly where a fixed batch width/deadline
falls over — wide batches concentrate same-leaf structural updates
(leaf-batch rejections -> slow-path rounds) and pile same-key versions
into deep chains.  The admission policy therefore (a) halves its target
width whenever a plan is rejected and doubles it back only while plans run
clean with a backlog, and (b) estimates skew per drained segment (the
duplicate-key fraction) — hot traffic halves the effective width and
shortens the deadline so hot keys drain in many small linearization
steps instead of one conflicted pass.

RANGE-bearing requests coalesce too, but their plans execute through the
synchronous ``apply`` (their pagination loop is host-driven); the
coalescer drains the pipeline first so linearization order stays FIFO.

Every plan's host cycle is measured (DESIGN.md Sec 12, "Tracing"): spans
``uruv.coalescer.build`` / ``.retire`` / ``.materialize`` / ``.replay``
carry ``plan=<plan number>``, and ``stats`` sums ``build_ns``,
``materialize_ns`` and ``queue_wait_ns`` (submit to leaving the queue,
over ``requests_dispatched`` requests).
"""

from __future__ import annotations

import collections
import dataclasses
import itertools
import time
from typing import Deque, Dict, List, Optional, Sequence, Tuple

import numpy as np

from repro.analysis.marks import device_pass
from repro.api import OP_NOP, OP_RANGE, OpBatch, Result, Uruv
from repro.obs import span, timed


@dataclasses.dataclass(frozen=True)
class AdmissionPolicy:
    """Coalescing knobs (DESIGN.md Sec 12).

    ``start_width``/``min_width``/``max_width`` bound the adaptive target
    plan width (always a power of two — plans NOP-pad to ``pow2_width``,
    so jit shape buckets stay O(log max_width)).  ``base_deadline_s`` is
    how long the oldest queued request may wait for the batch to fill
    before dispatching a partial plan; under hot traffic (duplicate-key
    fraction of a drained segment > ``hot_dup_frac``) the deadline
    contracts by ``hot_deadline_scale`` and the effective width halves.
    ``inflight_depth`` is the number of unconfirmed plans kept in flight
    (2 = host builds N+1 while the device executes N).
    """

    start_width: int = 64
    min_width: int = 8
    max_width: int = 1024
    base_deadline_s: float = 2e-3
    hot_dup_frac: float = 0.5
    hot_deadline_scale: float = 0.25
    inflight_depth: int = 2


class OpFuture:
    """One client request's slice of a batched result.

    ``result()`` drives the coalescer until this request's plan has been
    dispatched and settled, then returns the per-request :class:`Result`
    (values / found / per-op timestamps / complete range pages, announce
    positions rebased to the request).  ``submit_t``/``done_t`` (host
    monotonic clock) bracket queueing + batching + execution.
    """

    __slots__ = ("_coalescer", "n_ops", "submit_t", "done_t", "_result")

    def __init__(self, coalescer: "Coalescer", n_ops: int):
        self._coalescer = coalescer
        self.n_ops = n_ops
        self.submit_t = time.monotonic()
        self.done_t: Optional[float] = None
        self._result: Optional[Result] = None

    @property
    def done(self) -> bool:
        return self._result is not None

    def result(self) -> Result:
        while self._result is None:
            if not self._coalescer.pump(force=True):
                raise RuntimeError(
                    "coalescer made no progress with futures outstanding")
        return self._result


# A queued request: its future and its plan (host arrays, builder-validated).
_Queued = Tuple[OpFuture, OpBatch]

# the range fields of every request with no RANGE op: shared, read-only,
# zero-length
_NO_RANGE = np.zeros(0, np.int32)
_NO_RANGE.flags.writeable = False


@dataclasses.dataclass
class _InFlight:
    pending: object        # api.PendingPlan
    futs: Sequence[OpFuture]
    bounds: Sequence[int]  # request i's ops are plan[bounds[i]:bounds[i + 1]]
    plan_no: int           # the plan's number (stats["plans"] at build)


class Coalescer:
    """The admission pipeline over one `Uruv` client (module docstring).

    ``exclusive=True`` additionally donates the store pools into each pass
    (`donate_store`): only for a coalescer that exclusively owns its
    client's store buffers, and it caps the pipeline at ONE unconfirmed
    plan (a second speculative pass would consume the rollback buffers a
    rejected pass passes through) — host-build/device-execute overlap
    remains.  Sharded clients (no ``apply_nowait``) degrade to coalesced
    synchronous plans; everything else is unchanged.
    """

    def __init__(self, db: Uruv, policy: AdmissionPolicy = AdmissionPolicy(),
                 *, exclusive: bool = False, record: bool = False):
        self.db = db
        self.policy = policy
        self.exclusive = exclusive
        self.queue: Deque[_Queued] = collections.deque()
        self.inflight: Deque[_InFlight] = collections.deque()
        self.target_width = policy.start_width
        self.dispatch_log: Optional[List[Tuple[OpBatch, List[Tuple[OpFuture, int, int]]]]] = \
            [] if record else None
        self._last_dup = 0.0
        self._queued_ops = 0
        # executors without async dispatch (sharded) raise
        # NotImplementedError on first use; we then degrade to coalesced
        # synchronous plans for the life of the coalescer
        self._pipelined = True
        self._depth = max(1, 1 if exclusive else policy.inflight_depth)
        self.stats: Dict[str, int] = {
            "requests": 0, "ops": 0, "plans": 0, "plans_sync": 0,
            "plans_rejected": 0, "replays": 0, "padded_ops": 0,
            "max_queue_depth": 0, "hot_segments": 0,
            # host cycle of the plans (ns); keys distinct from the
            # executor's stats, which readers merge with these
            "build_ns": 0, "materialize_ns": 0, "queue_wait_ns": 0,
            "requests_dispatched": 0,
            # plans whose futures took the range-free materialize path
            "plans_range_free": 0,
        }

    # -------------------------------------------------------------- admission
    def submit(self, plan: OpBatch) -> OpFuture:
        """Admit one client request (an already-built `OpBatch`) and
        return its future.  Ops keep FIFO announce order across requests."""
        n = plan.codes.shape[0]           # len(plan), without the call
        if n == 0:
            raise ValueError("empty request")
        fut = OpFuture(self, n)
        queue, stats = self.queue, self.stats
        queue.append((fut, plan))
        self._queued_ops += n
        stats["requests"] += 1
        stats["ops"] += n
        if len(queue) > stats["max_queue_depth"]:
            stats["max_queue_depth"] = len(queue)
        return fut

    # --------------------------------------------------------------- policy
    def _deadline_s(self) -> float:
        if self._last_dup > self.policy.hot_dup_frac:
            return self.policy.base_deadline_s * self.policy.hot_deadline_scale
        return self.policy.base_deadline_s

    def _effective_width(self) -> int:
        w = self.target_width
        if self._last_dup > self.policy.hot_dup_frac:
            w = max(self.policy.min_width, w // 2)
        return w

    def _adapt(self, rejected: bool) -> None:
        if rejected:
            self.target_width = max(self.policy.min_width,
                                    self.target_width // 2)
        elif (self._queued_ops >= self.target_width
              and self.target_width < self.policy.max_width):
            self.target_width *= 2

    def _note_skew(self, keys: np.ndarray, codes: np.ndarray) -> None:
        real = keys[codes != OP_NOP]
        if real.size:
            self._last_dup = 1.0 - len(np.unique(real)) / real.size
            if self._last_dup > self.policy.hot_dup_frac:
                self.stats["hot_segments"] += 1

    # ------------------------------------------------------------------ pump
    def pump(self, force: bool = False, now: Optional[float] = None) -> bool:
        """One admission step: build the next plan from the queue head
        (host work that overlaps the in-flight device pass), settle the
        oldest in-flight plan if the pipeline is full, dispatch.  Returns
        False when there was nothing to do (queue below width with an
        unexpired deadline and nothing to force)."""
        now = time.monotonic() if now is None else now
        width = self._effective_width()
        if self.queue and (
            force or self._queued_ops >= width
            or now - self.queue[0][0].submit_t >= self._deadline_s()
        ):
            plan_no = self.stats["plans"]
            with timed(self.stats, "build_ns", "uruv.coalescer.build",
                       plan=plan_no):
                reqs = self._take(width)
                plan, futs, bounds, has_range = self._build(reqs)
                # the taken requests and their one-op batches are freed
                # here, a cost of the plan's build
                del reqs
            self._dispatch(plan, futs, bounds, has_range, plan_no)
            return True
        if force and self.inflight:
            self._retire_oldest()
            return True
        return False

    def flush(self) -> None:
        """Dispatch everything queued and settle every in-flight plan.

        For a durable client this also closes the group-commit window:
        with ``group_commit > 1`` up to that many confirmed plans may be
        awaiting one shared fsync (the bounded relaxation of the
        confirm-after-fsync contract, DESIGN.md Sec 14) — after flush
        every released result is on disk."""
        while self.queue or self.inflight:
            self.pump(force=True)
        self.db.sync_durable()
        self.db.lifecycle_tick()

    def _take(self, width: int) -> List[_Queued]:
        queue = self.queue
        take = [queue.popleft()]
        total = take[0][0].n_ops
        while queue and total + queue[0][0].n_ops <= width:
            q = queue.popleft()
            take.append(q)
            total += q[0].n_ops
        self._queued_ops -= total
        return take

    def _build(self, reqs: List[_Queued]):
        """The plan of the taken requests (padded to a power of two), their
        futures, the bounds of each request's slice of the plan, and
        whether the plan carries a RANGE; sums the requests' queue wait,
        from submit to the start of this build."""
        futs, plans = zip(*reqs)
        taken = time.monotonic()
        k = len(futs)
        submit_t = np.fromiter((f.submit_t for f in futs), np.float64, k)
        self.stats["queue_wait_ns"] += int((taken - submit_t).sum() * 1e9)
        self.stats["requests_dispatched"] += k
        # plan arrays are host numpy (built by OpBatch on the host)
        codes = np.concatenate([p.codes for p in plans])
        keys = np.concatenate([p.keys for p in plans])
        at = len(codes)
        plan = OpBatch(codes, keys, np.concatenate([p.values for p in plans])
                       ).pad_to_pow2()
        bounds = [0, *itertools.accumulate(f.n_ops for f in futs)]
        self.stats["plans"] += 1
        self.stats["padded_ops"] += len(plan) - at
        self._note_skew(keys, codes)
        if self.dispatch_log is not None:
            self.dispatch_log.append(
                (plan, list(zip(futs, bounds, bounds[1:]))))
        return plan, futs, bounds, bool((codes == OP_RANGE).any())

    # -------------------------------------------------------------- dispatch
    @device_pass(static=("has_range", "plan_no"))  # host flags / counter
    def _dispatch(self, plan: OpBatch, futs: Sequence[OpFuture],
                  bounds: Sequence[int], has_range: bool,
                  plan_no: int) -> None:
        if not (has_range or not self._pipelined):
            while len(self.inflight) >= self._depth:
                self._retire_oldest()
            try:
                pending = self.db.apply_nowait(
                    plan, donate_store=self.exclusive)
            except NotImplementedError:
                self._pipelined = False
            else:
                self.inflight.append(_InFlight(pending, futs, bounds, plan_no))
                return
        # host-driven pagination (RANGE) or a sync-only executor: drain
        # the pipeline (FIFO order), then one coalesced synchronous plan
        while self.inflight:
            self._retire_oldest()
        self.stats["plans_sync"] += 1
        self._materialize(futs, bounds, self.db.apply(plan), plan_no)
        self._adapt(rejected=False)

    def _retire_oldest(self) -> None:
        entry = self.inflight.popleft()
        with span("uruv.coalescer.retire", plan=entry.plan_no):
            res = self.db.confirm(entry.pending)
            if res is not None:
                self._materialize(entry.futs, entry.bounds, res,
                                  entry.plan_no)
                self._adapt(rejected=False)
                return
            # atomic rejection: the client rolled back to the pre-plan
            # store; every unconfirmed plan behind it ran on speculative
            # state and is invalid too — replay all of them synchronously,
            # in order, at the timestamps the restored clock re-derives
            # (bit-exact)
            self.stats["plans_rejected"] += 1
            self._adapt(rejected=True)
            replay = [entry] + list(self.inflight)
            self.inflight.clear()
            with span("uruv.coalescer.replay", plan=entry.plan_no):
                for e in replay:
                    self.stats["replays"] += 1
                    self._materialize(e.futs, e.bounds,
                                      self.db.apply(e.pending.batch),
                                      e.plan_no)

    # ------------------------------------------------------------- futures
    def _materialize(self, futs: Sequence[OpFuture], bounds: Sequence[int],
                     res: Result, plan_no: int) -> None:
        """Slice the batched Result into per-request Results and resolve
        the futures.  Every field keeps the batch's values verbatim —
        only announce positions (range_index) rebase to the request.  A
        request with no RANGE op gets the shared read-only empty range
        fields; every future of the plan gets one ``done_t``, read after
        the last of them is resolved."""
        with timed(self.stats, "materialize_ns", "uruv.coalescer.materialize",
                   plan=plan_no):
            values = np.asarray(res.values)
            found = np.asarray(res.found)
            ts = np.asarray(res.timestamps)
            rng_index = np.asarray(res.range_index)
            rng_resume = np.asarray(res.range_resume)
            pages = res.range_pages
            if not rng_index.size:
                self.stats["plans_range_free"] += 1
            # range_index is in announce order, so request i owns the RANGE
            # entries cut[i]:cut[i + 1], cut[i] = # of them before bounds[i]
            before = np.zeros(len(values) + 1, np.int64)
            np.cumsum(np.bincount(rng_index, minlength=len(values)),
                      out=before[1:])
            cut = before[bounds].tolist()
            for fut, a, b, lo, hi in zip(futs, bounds, bounds[1:],
                                         cut, cut[1:]):
                # positional, in field order: values, found, timestamps,
                # range_index, range_pages, range_resume
                if lo == hi:
                    fut._result = Result(values[a:b], found[a:b], ts[a:b],
                                         _NO_RANGE, (), _NO_RANGE)
                else:
                    fut._result = Result(
                        values[a:b], found[a:b], ts[a:b],
                        (rng_index[lo:hi] - a).astype(np.int32),
                        tuple(pages[lo:hi]),
                        rng_resume[lo:hi].astype(np.int32))
            done_t = time.monotonic()
            for fut in futs:
                fut.done_t = done_t
