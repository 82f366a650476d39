"""`Uruv` — the one front door to the paper's ADT.

One client serves every topology: construct with a `UruvConfig` for a
single device, with ``Uruv.sharded(cfg, mesh)`` for a key-partitioned
mesh — every verb below then runs through the pluggable executor without
the caller ever branching on topology.

    from repro.api import OpBatch, Uruv, UruvConfig

    db = Uruv(UruvConfig(leaf_cap=32))
    db.insert([1, 2, 3], [10, 20, 30])
    res = db.apply(OpBatch.concat(
        OpBatch.searches([2]), OpBatch.deletes([1]), OpBatch.ranges(0, 99),
    ))                       # one linearized announce array, one device pass
    with db.snapshot() as ts:            # registered + auto-released
        page = db.range(0, 99, ts)       # consistent under later updates

The client is the ONLY stateful object in the stack: it holds the current
store pytree (every prior value remains a valid frozen snapshot — the
paper's freeze-for-free) and mutates nothing else.  All heavy lifting is
the executor's; the client adds the announce-order timestamp accounting
(`Result.timestamps`) and the snapshot-tracker hygiene.
"""

from __future__ import annotations

import contextlib
from typing import Iterator, List, Optional, Tuple

import numpy as np

from repro.analysis.marks import device_pass
from repro.core import store as _store
from repro.core.ref import KEY_MAX, OP_RANGE
from repro.obs import host_read, span, timed

from repro.api.executors import (
    LifecyclePolicy, LocalExecutor, RangeOptions, ShardedExecutor,
)
from repro.api.futures import PendingPlan
from repro.api.opbatch import (
    OpBatch, RangePage, Result, make_result, pow2_width,
)


class Uruv:
    """Stateful client over an immutable store + a pluggable executor.

    The store is SELF-SIZING by default (DESIGN.md Sec 10): capacity
    pressure grows the flagged pool in place (device-resident power-of-two
    doubling, bit-exact) and incremental ``maintain`` passes reclaim
    retired split-leavings and merge underfull leaves when the frozen
    fraction crosses the policy trigger — a client created with a small
    ``UruvConfig`` serves an arbitrarily large working set without ever
    raising ``CapacityError``.  Pass ``policy=LifecyclePolicy(
    auto_grow=False, auto_maintain=False)`` for the fixed-footprint
    (seed) behaviour.
    """

    def __init__(self, config: Optional[_store.UruvConfig] = None, *,
                 executor=None, store=None, backend: Optional[str] = None,
                 policy: Optional[LifecyclePolicy] = None,
                 durable_dir: Optional[str] = None, group_commit: int = 1):
        if executor is None:
            executor = LocalExecutor(config, backend=backend, policy=policy)
        self.executor = executor
        self._store = store if store is not None else executor.create()
        self.recovery = None       # set by Uruv.recover()
        if durable_dir is not None:
            from repro.durability.recovery import Durability

            dur = Durability(durable_dir, group_commit=group_commit)
            if dur.has_history:
                raise ValueError(
                    f"{durable_dir} already holds durable history; a fresh "
                    "client would fork it — use Uruv.recover()")
            dur.write_config(self.config)
            self._attach_durability(dur)

    # ----------------------------------------------------------- constructors
    @classmethod
    def sharded(cls, config, mesh, *, route_factor: int = 2,
                routed: bool = True, store=None,
                policy: Optional[LifecyclePolicy] = None) -> "Uruv":
        """A client over a key-range-partitioned store on ``mesh`` (the
        ``config`` is a ``repro.core.sharded.ShardedConfig``)."""
        return cls(executor=ShardedExecutor(
            config, mesh, route_factor=route_factor, routed=routed,
            policy=policy,
        ), store=store)

    @classmethod
    def from_store(cls, store, *, backend: Optional[str] = None,
                   policy: Optional[LifecyclePolicy] = None) -> "Uruv":
        """Adopt an existing single-device store pytree (zero copies —
        stores are immutable, so the donor keeps its snapshot)."""
        return cls(executor=LocalExecutor(store.cfg, backend=backend,
                                          policy=policy),
                   store=store)

    @classmethod
    def recover(cls, durable_dir: str, *, backend: Optional[str] = None,
                policy: Optional[LifecyclePolicy] = None,
                group_commit: int = 1) -> "Uruv":
        """Rebuild a ``durable_dir=...`` client after a crash: restore the
        newest complete checkpoint (full or delta chain), replay the WAL
        tail at its recorded timestamps — bit-identical values AND version
        timestamps — and keep logging into the same directory.  The
        :class:`repro.durability.recovery.RecoveryInfo` lands on
        ``db.recovery`` (DESIGN.md Sec 14)."""
        from repro.durability.recovery import recover as _recover

        return _recover(durable_dir, backend=backend, policy=policy,
                        group_commit=group_commit)

    # ------------------------------------------------------------ durability
    def _attach_durability(self, durability) -> None:
        self.executor.durability = durability

    @property
    def durability(self):
        """The attached durability sidecar (None for a volatile client)."""
        return getattr(self.executor, "durability", None)

    def sync_durable(self) -> None:
        """Close the group-commit window: fsync every logged-but-pending
        plan.  A no-op for a volatile client."""
        dur = self.durability
        if dur is not None:
            dur.sync()

    def checkpoint(self, *, delta: bool = True) -> int:
        """Checkpoint the current store into the durable directory (delta
        against the previous checkpoint when one exists — first save is
        always full) and prune WAL segments the checkpoint covers.
        Returns the checkpoint step (the store clock)."""
        dur = self.durability
        if dur is None:
            raise ValueError(
                "checkpoint() requires a durable client "
                "(Uruv(durable_dir=...) or Uruv.recover())")
        return dur.checkpoint(
            self._store, delta=delta,
            compactions=self.executor.stats.get("compactions", 0))

    # ----------------------------------------------------------------- state
    @property
    def store(self):
        """The current store pytree (an immutable snapshot)."""
        return self._store

    @property
    def config(self):
        return self.executor.config

    @property
    def stats(self):
        """Executor counters: ``device_passes`` / ``slow_path_rounds`` /
        ``compactions`` plus the lifecycle trio ``grows`` /
        ``maintain_passes`` / ``leaves_reclaimed``, merged with the
        device-resident index counters ``index_delta_passes`` (structural
        batches that ran the bounded separator-delta pass) and
        ``index_propagations`` (node updates that propagated above the
        bottom level — the observable O(touched·depth) bound of
        DESIGN.md Sec 11; sharded stores sum their shards), and the host
        cost of the request path: ``dispatch_ns`` (in ``apply_nowait``),
        ``device_wait_ns`` (blocked in ``confirm``) and ``host_syncs``
        (device-to-host reads; DESIGN.md Sec 12, "Tracing")."""
        s = dict(self.executor.stats)
        idx = getattr(self._store, "index", None)
        if idx is not None:
            s["index_delta_passes"] = int(
                np.asarray(idx.stat_delta_passes).sum())
            s["index_propagations"] = int(
                np.asarray(idx.stat_propagations).sum())
        return s

    @property
    def capacity(self):
        """The LIVE capacities (``store.cfg``) — these move as the store
        grows; the construction-time config keeps the initial sizes."""
        return self._store.cfg

    @property
    def ts(self) -> int:
        """The global clock (the paper's FAA counter)."""
        return self.executor.ts(self._store)

    @property
    def active_snapshots(self) -> int:
        """Registered-and-unreleased snapshots in the version tracker."""
        act = np.asarray(self._store.trk_active)
        if act.ndim == 2:        # sharded: the tracker ring is replicated
            act = act[0]
        return int(act.sum())

    # ----------------------------------------------------------------- write
    def apply(self, batch: OpBatch, *, light_path: bool = True,
              pad_to_pow2: bool = False,
              range_opts: RangeOptions = RangeOptions()) -> Result:
        """Linearize one announce array: op i at timestamp ``ts + i``.

        One device pass on the fast path (CRUD-only batches); RANGE ops
        segment the array (each range snapshots at its own announce
        timestamp and is answered COMPLETELY).  Capacity rejections retry
        via the bounded slow path; ``CapacityError`` if the store cannot
        fit the working set even after compaction.

        ``pad_to_pow2`` NOP-pads the plan to the next power-of-two width
        before dispatch, bounding jit retraces to O(log max_width) shape
        buckets for callers with ragged batch sizes (serving admission);
        results keep the caller's width, but the clock advances by the
        padded width (NOP slots still occupy announce positions).
        """
        with span("uruv.apply"):
            base = self.ts
            n = len(batch)
            if pad_to_pow2 and n:
                batch = batch.pad_to(pow2_width(n))
            self._store, values, range_items = self.executor.apply(
                self._store, batch, light_path=light_path,
                range_opts=range_opts,
            )
            return make_result(values[:n], np.asarray(batch.codes)[:n], base,
                               range_items)

    # ------------------------------------------------- pipelined (deferred)
    @device_pass(static=("pad_to_pow2", "donate_store"))
    def apply_nowait(self, batch: OpBatch, *, pad_to_pow2: bool = False,
                     donate_store: bool = False) -> PendingPlan:
        """Dispatch a CRUD-only plan WITHOUT waiting for the device.

        Returns a :class:`PendingPlan` immediately — the device pass (and
        its accept/reject decision) is still in flight; the client adopts
        the speculative store so the next plan can be built and dispatched
        behind it (the serving pipeline's two-plans-in-flight overlap,
        DESIGN.md Sec 12).  Settle with :meth:`confirm` IN DISPATCH ORDER
        before using any synchronous verb.  Plans with RANGE ops must take
        :meth:`apply` (their pagination loop is host-driven).

        ``donate_store=True`` additionally donates the store pools into
        the pass — only for an exclusive owner (it invalidates every other
        live reference to this client's store buffers, e.g. a
        ``from_store`` donor), and only with at most one unconfirmed plan
        in flight (a second speculative pass would consume the rejected
        pass's rollback buffers).
        """
        n = len(batch)
        if n == 0:
            raise ValueError("apply_nowait requires a non-empty plan")
        # host->device copies, the store pytree and the jitted call's
        # enqueue: the client's dispatch cost (stats["dispatch_ns"])
        with timed(self.executor.stats, "dispatch_ns", "uruv.apply_nowait"):
            # plan marshalling is host-side BY DESIGN: OpBatch arrays are
            # numpy before dispatch, so these never sync the device
            codes = np.asarray(batch.codes)  # uruvlint: disable=device-pass-purity
            if bool((codes == OP_RANGE).any()):  # uruvlint: disable=device-pass-purity
                raise ValueError(
                    "apply_nowait is CRUD-only; RANGE plans take apply()")
            host = OpBatch(codes,  # uruvlint: disable=device-pass-purity
                           np.asarray(batch.keys),  # uruvlint: disable=device-pass-purity
                           np.asarray(batch.values))  # uruvlint: disable=device-pass-purity
            if pad_to_pow2:
                host = host.pad_to(pow2_width(n))
            store_before = self._store
            self._store, values, ok = self.executor.apply_nowait(
                self._store, host, donate_store=donate_store,
            )
            return PendingPlan(
                batch=host, n_user=n,
                store_before=None if donate_store else store_before,
                store_after=self._store, values=values, ok=ok,
            )

    def confirm(self, pending: PendingPlan) -> Optional[Result]:
        """Settle one :meth:`apply_nowait` dispatch (the deferred host
        sync).  On acceptance returns the plan's :class:`Result` (sliced
        back to the caller's pre-padding width).  On rejection rolls the
        client back to the pre-plan store and returns ``None`` — the
        caller replays ``pending.batch`` (and every later unconfirmed
        plan, whose speculative results are invalid) through :meth:`apply`,
        which owns the slow-path and lifecycle machinery and re-derives
        the exact same announce timestamps from the restored clock.
        """
        stats = self.executor.stats
        # the pipelined path's one blocking wait: the device finishing the
        # pass (stats["device_wait_ns"])
        with timed(stats, "device_wait_ns", "uruv.confirm.wait"):
            ok = bool(host_read(stats, pending.ok))
        if not ok:
            self._store = pending.rollback_store()
            return None
        with span("uruv.confirm.fetch"):
            base = int(host_read(stats, pending.store_after.ts)) \
                - len(pending.batch)
            dur = self.durability
            if dur is not None:
                # log-on-confirm (the pipelined half of confirm-after-
                # fsync): an ACCEPTED plan is logged here, before its
                # Result exists; a rejected plan is never logged — its
                # replay logs through apply(), so the WAL carries exactly
                # one record per base_ts
                dur.log_plan(base, np.asarray(pending.batch.codes),
                             np.asarray(pending.batch.keys),
                             np.asarray(pending.batch.values))
            values = host_read(stats, pending.values)[:pending.n_user]
            return make_result(
                values, np.asarray(pending.batch.codes)[:pending.n_user],
                base, ())

    def lifecycle_tick(self) -> None:
        """Run the policy's proactive grow/maintain triggers now.  The
        pipelined front end calls this between plans (it reads occupancy,
        i.e. syncs the host) instead of on the dispatch path."""
        self._store = self.executor.lifecycle_tick(self._store)

    def insert(self, keys, values) -> Result:
        """Batched INSERT; ``Result.values`` holds the previous values."""
        return self.apply(OpBatch.inserts(keys, values))

    def delete(self, keys) -> Result:
        """Batched DELETE (tombstones; physical reclaim via compact())."""
        return self.apply(OpBatch.deletes(keys))

    def search(self, keys) -> Result:
        """Batched SEARCH as announce ops (advances the clock; op i sees
        every earlier in-batch op).  For read-only probes at an explicit
        snapshot use :meth:`lookup`."""
        return self.apply(OpBatch.searches(keys))

    # ------------------------------------------------------------------ read
    def lookup(self, keys, snap_ts=None, *,
               pad_to_pow2: bool = False) -> np.ndarray:
        """Read-only batched SEARCH at ``snap_ts`` (default: current clock).

        Does not advance the clock or register a snapshot; padded keys
        (KEY_MAX) return NOT_FOUND.  ``pad_to_pow2`` bounds jit retraces
        for ragged probe widths (reads are side-effect free, so padding
        costs nothing but the wider pass).

        KEY_MAX stays the documented mask-out/padding encoding; the
        internal pad sentinel KEY_MAX - 1 is rejected (a key the store
        can publish but never find — the silent-loss guard of the
        ``OpBatch`` builders, DESIGN.md Sec 12).
        """
        if snap_ts is None:
            snap_ts = self.ts
        keys = np.atleast_1d(np.asarray(keys, np.int32))
        if keys.size and bool(np.any(keys == KEY_MAX - 1)):
            raise ValueError(
                f"lookup key {KEY_MAX - 1} is the internal pad sentinel "
                f"(valid keys are < {KEY_MAX - 1}; KEY_MAX masks out)")
        n = len(keys)
        if pad_to_pow2 and n:
            pad = pow2_width(n) - n
            keys = np.concatenate([keys, np.full(pad, KEY_MAX, np.int32)])
            snap = np.asarray(snap_ts, np.int32)
            if snap.ndim:            # per-op snaps pad too (padded keys are
                snap_ts = np.concatenate(   # KEY_MAX -> NOT_FOUND anyway)
                    [snap, np.zeros(pad, np.int32)])
        return np.asarray(self.executor.lookup(
            self._store, keys, snap_ts,
        ))[:n]

    def range(self, k1: int, k2: int, snap_ts: Optional[int] = None, *,
              max_results: int = 1024, scan_leaves: int = 16,
              max_rounds: int = 8) -> List[Tuple[int, int]]:
        """[k1, k2] answered completely at one snapshot -> (key, value) list.

        ``snap_ts=None`` registers a fresh snapshot for the duration of
        the scan (and always releases it — a leaked registration would pin
        ``min_active_ts`` and starve GC).
        """
        return self.range_all([k1], [k2], snap_ts,
                              max_results=max_results,
                              scan_leaves=scan_leaves,
                              max_rounds=max_rounds)[0]

    def range_all(self, k1s, k2s, snap_ts: Optional[int] = None, *,
                  max_results: int = 1024, scan_leaves: int = 16,
                  max_rounds: int = 8) -> List[List[Tuple[int, int]]]:
        """Q intervals answered completely — ONE batched device pass per
        pagination round shared by ALL still-truncated queries (the pooled
        in-pass budget of DESIGN.md Sec 8), at one consistent snapshot."""
        opts = RangeOptions(max_results=max_results,
                            scan_leaves=scan_leaves, max_rounds=max_rounds)
        if snap_ts is None:
            with self.snapshot() as ts:
                return self.executor.range_all(
                    self._store, k1s, k2s, ts, opts)
        return self.executor.range_all(self._store, k1s, k2s, snap_ts, opts)

    def range_page(self, k1s, k2s, snap_ts, *, max_results: int = 1024,
                   scan_leaves: int = 16, max_rounds: int = 8) -> RangePage:
        """ONE bounded device pass over Q intervals (the wait-free unit);
        resume truncated queries from ``page.resume_k1``."""
        return self.executor.range_page(
            self._store, k1s, k2s, snap_ts,
            RangeOptions(max_results=max_results, scan_leaves=scan_leaves,
                         max_rounds=max_rounds),
        )

    def scan_page(self, k1: int, k2: int, snap_ts, *,
                  max_scan_leaves: int = 64,
                  max_results: int = 1024) -> RangePage:
        """The paper's single-interval RANGEQUERY pass: exactly
        ``max_scan_leaves`` chained leaves, one device call (the seed
        contract; kept as the baseline unit for benchmarks)."""
        return self.executor.scan_page(
            self._store, k1, k2, snap_ts,
            max_scan_leaves=max_scan_leaves, max_results=max_results,
        )

    # --------------------------------------------------------- snapshots, GC
    def acquire_snapshot(self) -> int:
        """Register a snapshot in the version tracker and return its ts.
        Pair with :meth:`release_snapshot`; prefer :meth:`snapshot`."""
        self._store, ts = self.executor.snapshot(self._store)
        return ts

    def release_snapshot(self, snap_ts: int) -> None:
        self._store = self.executor.release(self._store, snap_ts)

    @contextlib.contextmanager
    def snapshot(self) -> Iterator[int]:
        """Registered snapshot as a context manager.

            with db.snapshot() as ts:
                view = db.range(0, hi, ts)     # immune to later updates

        Released on exit even on error (GC never starves).
        """
        ts = self.acquire_snapshot()
        try:
            yield ts
        finally:
            self.release_snapshot(ts)

    def compact(self) -> int:
        """Physically reclaim versions no active snapshot can read and
        repack leaves (paper Appendix E); returns the live-key count.
        Stop-the-world — prefer :meth:`maintain` for steady-state leaf
        reclamation; compact remains the version-pool GC."""
        self._store, n_live = self.executor.compact(self._store)
        return n_live

    def reindex(self) -> None:
        """Repack the internal fat-node index at pack_fill occupancy
        (DESIGN.md Sec 11).  Runs automatically when a structural batch
        rejects with ``OFLOW_INDEX`` (node-pool fragmentation after heavy
        delete/merge churn); call it directly to defragment off-peak.
        Every result — including reads at registered snapshots — is
        byte-identical before and after."""
        self._store = self.executor.reindex(self._store)

    # ------------------------------------------------------------- lifecycle
    def maintain(self, budget: Optional[int] = None, *,
                 phase: int = 0) -> Tuple[int, int]:
        """ONE bounded incremental maintenance pass (DESIGN.md Sec 10):
        purge tracker-dead keys, merge underfull neighbours, reclaim up to
        ``budget`` retired leaf slots.  Returns ``(leaves_reclaimed,
        pairs_merged)``; results at every registered snapshot are
        byte-identical before and after.  Runs automatically on the policy
        trigger — call it directly to schedule maintenance explicitly
        (e.g. off-peak)."""
        self._store, reclaimed, merged = self.executor.maintain(
            self._store, budget, phase=phase,
        )
        return reclaimed, merged

    def grow(self, *, leaves: bool = False, versions: bool = False,
             tracker: bool = False) -> None:
        """Double the selected pools now (device-resident, bit-exact).
        Runs automatically on capacity pressure — call it directly to
        pre-size ahead of a known ingest."""
        self._store = self.executor.grow(
            self._store, leaves=leaves, versions=versions, tracker=tracker,
        )

    # ------------------------------------------------------------ inspection
    def live_items(self) -> List[Tuple[int, int]]:
        """All (key, latest live value) pairs — host-side, O(n); tests."""
        store = self._store
        if np.asarray(store.ts).ndim:          # sharded: walk every shard
            import jax

            shards = [
                jax.tree.map(lambda x, s=s: x[s], store)
                for s in range(np.asarray(store.ts).shape[0])
            ]
            out = []
            for sh in shards:
                out.extend(_store.live_items(sh))
            return sorted(out)
        return _store.live_items(store)

    def __len__(self) -> int:
        return len(self.live_items())

    def __repr__(self) -> str:
        return (f"Uruv(executor={type(self.executor).__name__}, "
                f"ts={self.ts}, leaf_cap={self.config.base.leaf_cap if hasattr(self.config, 'base') else self.config.leaf_cap})")
