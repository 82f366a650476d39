"""Pluggable execution backends for the `Uruv` client.

An executor owns HOW a plan runs — which device passes, on what topology —
while the client owns the store value and the ADT surface.  The contract
(DESIGN.md Sec 9):

  * ``create()``                        -> a fresh store pytree
  * ``apply(store, batch, ...)``        -> (store, values[P], range_items)
        linearizes the announce array in announce order (op i at
        ``base_ts + i``), answering RANGE ops COMPLETELY (the executor
        paginates internally); never returns a partially-applied store —
        capacity failures raise ``CapacityError`` after bounded retries.
  * ``lookup(store, keys, snap_ts)``    -> values (read-only, no clock)
  * ``range_page(store, k1s, k2s, snap_ts, ...)`` -> RangePage
        ONE bounded device pass over Q intervals (wait-free unit).
  * ``range_all(store, k1s, k2s, snap_ts, ...)``  -> per-query page lists
        complete answers; re-enters only still-truncated queries.
  * ``snapshot / release / compact / ts`` — tracker + clock surface.

``stats`` (shared with the client) counts ``device_passes``,
``slow_path_rounds`` and ``compactions`` — the observable wait-free bound
(benchmarks assert "one device pass per fast-path batch" through it) —
and the client's host cost of the pipelined path: ``dispatch_ns``,
``device_wait_ns`` and ``host_syncs`` (DESIGN.md Sec 12, "Tracing").

`LocalExecutor` runs on one device via ``repro.core.batch``;
`ShardedExecutor` runs the same plans over a mesh axis via the
``repro.core.sharded`` SPMD factories (replicated or routed announce
distribution + all_gather'ed range merge) with bit-identical
linearization, including version timestamps.
"""

from __future__ import annotations

import dataclasses
from typing import Dict, List, Optional, Tuple

import numpy as np
import jax
import jax.numpy as jnp

from repro.analysis.marks import device_pass
from repro.core import batch as _batch
from repro.core import lifecycle as _lifecycle
from repro.core import sharded as _sharded
from repro.core import store as _store
from repro.obs import host_read
from repro.api.opbatch import OpBatch, RangePage

CapacityError = _batch.CapacityError
LifecyclePolicy = _lifecycle.LifecyclePolicy


def _new_stats() -> Dict[str, int]:
    return {"device_passes": 0, "slow_path_rounds": 0, "compactions": 0,
            "grows": 0, "maintain_passes": 0, "leaves_reclaimed": 0,
            "dispatch_ns": 0, "device_wait_ns": 0, "host_syncs": 0}


@dataclasses.dataclass(frozen=True)
class RangeOptions:
    """Leaf/result budget of one bounded range pass (DESIGN.md Sec 8)."""

    max_results: int = 1024
    scan_leaves: int = 16
    max_rounds: int = 8


class LocalExecutor:
    """Single-device execution over ``repro.core.store`` / ``core.batch``.

    ``backend`` pins the kernel backend (xla | pallas | pallas_interpret)
    for every pass this executor issues; None follows the process-wide
    ``repro.core.backend`` resolution (URUV_BACKEND / set_backend).

    ``policy`` is the store lifecycle (DESIGN.md Sec 10): with the default
    self-sizing policy the executor grows the rejected pool on capacity
    overflow (power-of-two device-resident doubling, bit-exact) and
    interleaves bounded incremental ``lifecycle.maintain`` passes when the
    frozen/dead fraction of the leaf pool crosses the trigger — no
    steady-state ``CapacityError``.  ``policy=LifecyclePolicy(
    auto_grow=False, auto_maintain=False)`` restores the seed
    fixed-footprint behaviour.  Note the live capacities are carried by
    ``store.cfg`` (the construction-time ``config`` keeps the *initial*
    sizes once growth has occurred).
    """

    def __init__(self, config: Optional[_store.UruvConfig] = None, *,
                 backend: Optional[str] = None,
                 policy: Optional[LifecyclePolicy] = None):
        self.config = config or _store.UruvConfig()
        self.backend = backend
        self.policy = policy if policy is not None \
            else _lifecycle.DEFAULT_POLICY
        self.stats = _new_stats()
        # durability sidecar (repro.durability.recovery.Durability),
        # attached by Uruv(durable_dir=...) / Uruv.recover(): every
        # committed plan is logged to the WAL before its result reaches
        # the caller (DESIGN.md Sec 14)
        self.durability = None

    # ------------------------------------------------------------- lifecycle
    def create(self):
        return _store.create(self.config)

    def ts(self, store) -> int:
        return int(host_read(self.stats, store.ts))

    def grow(self, store, *, leaves: bool = False, versions: bool = False,
             tracker: bool = False):
        self.stats["grows"] += 1
        return _lifecycle.grow(store, leaves=leaves, versions=versions,
                               tracker=tracker)

    def maintain(self, store, budget: Optional[int] = None, *,
                 phase: int = 0):
        store, reclaimed, merged = _lifecycle.maintain(
            store,
            budget if budget is not None else self.policy.maintain_budget,
            phase=phase,
        )
        self.stats["maintain_passes"] += 1
        self.stats["leaves_reclaimed"] += reclaimed
        return store, reclaimed, merged

    def _lifecycle_tick(self, store):
        """Post-apply lifecycle interleave: proactive growth ahead of the
        allocator wall, plus a bounded maintain burst on the frozen-
        fraction trigger (both policy-gated; results are unaffected)."""
        return _lifecycle.lifecycle_tick(
            store, self.policy, stats=self.stats,
            grow_fn=lambda st: self.grow(st, leaves=True),
        )

    # ----------------------------------------------------------------- write
    def apply(self, store, batch: OpBatch, *, light_path: bool = True,
              range_opts: RangeOptions = RangeOptions()):
        base = int(host_read(self.stats, store.ts)) \
            if self.durability is not None else 0
        store, values, range_pages = _batch.apply_mixed(
            store, batch.codes, batch.keys, batch.values,
            light_path=light_path, backend=self.backend,
            max_results=range_opts.max_results,
            scan_leaves=range_opts.scan_leaves,
            max_rounds=range_opts.max_rounds,
            stats=self.stats, policy=self.policy,
        )
        store = self._lifecycle_tick(store)
        if self.durability is not None and len(batch):
            # log-on-commit: apply_mixed either applied the WHOLE plan or
            # raised — a logged record is a committed plan, and it hits
            # the WAL before the caller ever sees the result (the sync
            # half of the confirm-after-fsync contract; the pipelined
            # half is Uruv.confirm).  fsync cadence is the sidecar's
            # group-commit window (1 = every plan).
            self.durability.log_plan(
                base, np.asarray(batch.codes), np.asarray(batch.keys),
                np.asarray(batch.values))
        k2 = np.asarray(batch.values)
        range_items = [(pos, page, int(k2[pos])) for pos, page in range_pages]
        return store, values, range_items

    @device_pass(static=("donate_store",))
    def apply_nowait(self, store, batch: OpBatch, *,
                     donate_store: bool = False):
        """Dispatch ONE fast-path pass for a CRUD-only plan and return
        ``(store, values, ok)`` with the result and accept flag still
        device-resident — zero host syncs (the serving pipeline's deferred
        ``block_until_ready``; DESIGN.md Sec 12).  ``donate_store``
        donates the pools into the pass — the pipeline's in-place double
        buffer, exclusive-owner mode only.  Rejection handling (slow
        path, lifecycle) is the caller's, via ``Uruv.confirm`` + replay
        through :meth:`apply`.
        """
        self.stats["device_passes"] += 1
        return _store.bulk_apply(
            store, jnp.asarray(batch.codes), jnp.asarray(batch.keys),
            jnp.asarray(batch.values), backend=self.backend,
            donate_store=donate_store,
        )

    def lifecycle_tick(self, store):
        """Run the policy's proactive grow/maintain triggers now (the
        serving pipeline calls this between plans, off the latency path)."""
        return self._lifecycle_tick(store)

    # ------------------------------------------------------------------ read
    def lookup(self, store, keys, snap_ts):
        self.stats["device_passes"] += 1
        return _store.bulk_lookup(
            store, jnp.asarray(keys, jnp.int32),
            jnp.asarray(snap_ts, jnp.int32), backend=self.backend,
        )

    def range_page(self, store, k1s, k2s, snap_ts,
                   opts: RangeOptions = RangeOptions()) -> RangePage:
        self.stats["device_passes"] += 1
        keys, vals, cnt, trunc, resume = _store.bulk_range(
            store, np.atleast_1d(np.asarray(k1s, np.int32)),
            np.atleast_1d(np.asarray(k2s, np.int32)), snap_ts,
            max_results=opts.max_results, scan_leaves=opts.scan_leaves,
            max_rounds=opts.max_rounds, backend=self.backend,
        )
        return RangePage(keys, vals, cnt, trunc, resume)

    def scan_page(self, store, k1: int, k2: int, snap_ts,
                  *, max_scan_leaves: int = 64,
                  max_results: int = 1024) -> RangePage:
        """The paper's single-interval bounded RANGEQUERY pass (exactly
        ``max_scan_leaves`` leaves — the seed contract), as a Q=1 page."""
        self.stats["device_passes"] += 1
        keys, vals, cnt, trunc = _store.range_query(
            store, k1, k2, snap_ts,
            max_scan_leaves=max_scan_leaves, max_results=max_results,
            backend=self.backend,
        )
        # resume frontier: last kept key + 1 when the page has hits (never
        # skips overflow-dropped hits); a truncated ZERO-hit page (all
        # scanned keys dead at this snapshot) resumes at the first
        # unscanned leaf's separator — resuming at k1 would livelock
        i32 = jnp.int32
        sep = _store.scan_resume_sep(store, k1, max_scan_leaves, k2)
        c = jnp.maximum(cnt - 1, 0)
        resume = jnp.where(
            cnt > 0, keys[c] + 1,
            jnp.where(trunc, sep, jnp.asarray(k1, i32)),
        )
        return RangePage(keys[None], vals[None], cnt[None], trunc[None],
                         resume[None])

    def range_all(self, store, k1s, k2s, snap_ts,
                  opts: RangeOptions = RangeOptions()
                  ) -> List[List[Tuple[int, int]]]:
        return _batch.bulk_range_all(
            store, k1s, k2s, snap_ts,
            max_results=opts.max_results, scan_leaves=opts.scan_leaves,
            max_rounds=opts.max_rounds, backend=self.backend,
            stats=self.stats,
        )

    # --------------------------------------------------------- snapshots, GC
    def snapshot(self, store):
        # proactive tracker growth: a full ring would silently drop the
        # registration (OFLOW_TRACKER) — grow it first instead
        if (self.policy.auto_grow
                and int(np.asarray(store.trk_active).sum())
                >= store.cfg.tracker_cap):
            store = self.grow(store, tracker=True)
        store, ts = _store.snapshot(store)
        return store, int(ts)

    def release(self, store, snap_ts: int):
        return _store.release(store, snap_ts)

    def compact(self, store):
        self.stats["compactions"] += 1
        store, n_live = _store.compact(store)
        return store, int(n_live)

    def reindex(self, store):
        """Stop-the-world index repack (OFLOW_INDEX recovery / defrag);
        results are unchanged by construction (DESIGN.md Sec 11)."""
        self.stats["reindexes"] = self.stats.get("reindexes", 0) + 1
        return _store.reindex(store)


class ShardedExecutor:
    """Key-range-partitioned execution over a mesh axis (``core.sharded``).

    Wraps the jitted SPMD factories — ``make_apply`` (replicated announce),
    ``make_routed_apply`` (all_to_all routed announce, used first when the
    global width divides the shard count) and ``make_range_apply`` (per-
    shard bulk_range + on-device frontier-clamped merge) — behind the same
    executor contract as `LocalExecutor`, so `Uruv` callers never branch
    on topology.  Linearization is bit-identical to single-device
    execution including version timestamps (per-op global timestamps +
    the replicated clock; DESIGN.md Sec 3/8).

    Lifecycle decisions are REPLICATED across shards by construction: the
    stacked store has one shape, so ``grow`` doubles every shard's pools
    in the same device call and ``maintain`` runs vmapped over all shards
    — shard shapes can never diverge, and because lifecycle passes touch
    neither the clock nor version timestamps, sharded execution stays
    bit-identical to local execution even when the two interleave
    different grow/maintain schedules.  Capacity rejections relieve the
    flagged pool (maintain burst / doubling / tracker-gated compact) and
    retry, bounded by ``MAX_SLOWPATH_ROUNDS``; with ``auto_grow=False``
    a fully-rejected announce raises ``CapacityError`` (size shards for
    the working set).  An announce that routes more than ``leaf_cap`` new
    keys to one leaf of a shard (``OFLOW_LEAFBATCH``: a wide insert plan
    into a nearly empty store) is applied in halves at its original
    timestamps, the slow path of ``core.batch``.
    """

    def __init__(self, config: _sharded.ShardedConfig, mesh, *,
                 route_factor: int = 2, routed: bool = True,
                 policy: Optional[LifecyclePolicy] = None):
        self.config = config
        self.mesh = mesh
        self.n_shards = mesh.shape[config.axis_name]
        self.route_factor = route_factor
        self.routed = routed
        self.policy = policy if policy is not None \
            else _lifecycle.DEFAULT_POLICY
        self.stats = _new_stats()
        # SPMD factories are built lazily, cached per static config
        # (light_path for the apply passes, RangeOptions for range)
        self._apply_fns: Dict[bool, object] = {}
        self._routed_fns: Dict[bool, object] = {}
        self._lookup_fn = None
        self._range_fns: Dict[RangeOptions, object] = {}

    # ------------------------------------------------------------- lifecycle
    def create(self):
        return _sharded.create(self.config, self.mesh)

    def ts(self, store) -> int:
        return _sharded.global_ts(store)

    def _set_ts(self, store, ts: int):
        return dataclasses.replace(
            store, ts=jnp.full_like(store.ts, np.int32(ts))
        )

    def _reshard(self, store):
        """Pin a lifecycle-produced store back to the mesh sharding (grow /
        vmapped maintain can leave leaves with inferred placements)."""
        from jax.sharding import NamedSharding, PartitionSpec as P

        return jax.device_put(
            store, NamedSharding(self.mesh, P(self.config.axis_name))
        )

    def grow(self, store, *, leaves: bool = False, versions: bool = False,
             tracker: bool = False):
        """Double the selected pools on EVERY shard (one stacked device
        op; shard shapes stay equal — the replicated-decision rule)."""
        self.stats["grows"] += 1
        return self._reshard(_lifecycle.grow(
            store, leaves=leaves, versions=versions, tracker=tracker,
        ))

    def maintain(self, store, budget: Optional[int] = None, *,
                 phase: int = 0):
        """One vmapped incremental maintenance pass over all shards."""
        store, reclaimed, merged = _lifecycle.maintain(
            store,
            budget if budget is not None else self.policy.maintain_budget,
            phase=phase,
        )
        self.stats["maintain_passes"] += 1
        self.stats["leaves_reclaimed"] += reclaimed
        return self._reshard(store), reclaimed, merged

    def _lifecycle_tick(self, store):
        def maintain_fn(st, budget, phase):
            st, rec, mer = _lifecycle.maintain(st, budget, phase=phase)
            return self._reshard(st), rec, mer

        return _lifecycle.lifecycle_tick(
            store, self.policy, stats=self.stats,
            grow_fn=lambda st: self.grow(st, leaves=True),
            maintain_fn=maintain_fn,
        )

    # ----------------------------------------------------------------- write
    def _apply_crud(self, store, codes, keys, values, light_path: bool):
        """One CRUD segment; timestamps come from the replicated clock
        (``store.ts``, restated after range segments by the shared
        apply_mixed loop), so op i of the segment runs at the global
        ``store.ts + i``.  Capacity rejections relieve pressure on every
        shard at once (the stacked pools share one shape) and retry —
        lifecycle steps never move the clock, so the retried pass applies
        at exactly the rejected pass's timestamps."""
        for _ in range(_batch.MAX_SLOWPATH_ROUNDS):
            apply_fn = self._apply_fns.get(light_path)
            if apply_fn is None:
                apply_fn = _sharded.make_apply(self.config, self.mesh,
                                               light_path=light_path)
                self._apply_fns[light_path] = apply_fn
            routed = None
            if self.routed and len(codes) % self.n_shards == 0:
                routed = self._routed_fns.get(light_path)
                if routed is None:
                    routed = _sharded.make_routed_apply(
                        self.config, self.mesh,
                        route_factor=self.route_factor,
                        light_path=light_path,
                    )
                    self._routed_fns[light_path] = routed
            try:
                store, res = _sharded.sharded_apply_batch(
                    store, codes, keys, values,
                    apply_fn=apply_fn, routed_fn=routed, stats=self.stats,
                )
                return store, np.asarray(res)
            except RuntimeError as e:    # full rejection: relieve + retry
                reason = getattr(e, "oflow_reason", 0)
                if reason & _store.OFLOW_LEAFBATCH and len(codes) > 1:
                    # > L new keys for one leaf of some shard: help in
                    # rounds as core.batch does — each half runs at its
                    # own slice of the announce timestamps (the clock
                    # advances per half), so results are unchanged
                    self.stats["slow_path_rounds"] += 1
                    mid = len(codes) // 2
                    store, res_a = self._apply_crud(
                        store, codes[:mid], keys[:mid], values[:mid],
                        light_path)
                    store, res_b = self._apply_crud(
                        store, codes[mid:], keys[mid:], values[mid:],
                        light_path)
                    return store, np.concatenate([res_a, res_b])
                grow_bits = reason & (_store.OFLOW_LEAVES
                                      | _store.OFLOW_VERSIONS)
                index_bit = reason & _store.OFLOW_INDEX
                # reindex is reclamation, not growth: allowed under every
                # policy; pool doubling stays behind auto_grow
                if not (index_bit or (self.policy.auto_grow and grow_bits)):
                    raise CapacityError(str(e), store=store,
                                        oflow=reason) from e
                self.stats["slow_path_rounds"] += 1
                relief = index_bit | (
                    grow_bits if self.policy.auto_grow else 0)
                store = self._reshard(_lifecycle.relieve_pressure(
                    store, relief, len(codes), self.policy,
                    stats=self.stats,
                ))
        raise CapacityError(
            "sharded capacity retries failed to converge", store=store,
        )

    def apply(self, store, batch: OpBatch, *, light_path: bool = True,
              range_opts: RangeOptions = RangeOptions()):
        # ONE copy of the announce-segmentation semantics: the shared
        # core.batch.apply_mixed loop, with the sharded SPMD passes as its
        # hooks (a CRUD segment's timestamps derive from the replicated
        # clock, which the loop restates after every range segment)
        store, values, range_pages = _batch.apply_mixed(
            store, batch.codes, batch.keys, batch.values,
            crud_fn=lambda st, c, k, v, op_ts, next_ts:
                self._apply_crud(st, c, k, v, light_path),
            range_all_fn=lambda st, k1, k2, snaps:
                self.range_all(st, k1, k2, snaps, range_opts),
            get_ts_fn=self.ts,
            set_ts_fn=self._set_ts,
        )
        store = self._lifecycle_tick(store)
        k2 = np.asarray(batch.values)
        range_items = [(pos, page, int(k2[pos])) for pos, page in range_pages]
        return store, values, range_items

    def apply_nowait(self, store, batch: OpBatch, *,
                     donate_store: bool = False):
        """Sharded passes route/collect on the host, so a deferred-sync
        dispatch is not available; the coalescer detects this and falls
        back to coalesced synchronous :meth:`apply` plans."""
        raise NotImplementedError(
            "apply_nowait is single-device only; use apply()")

    def lifecycle_tick(self, store):
        return self._lifecycle_tick(store)

    # ------------------------------------------------------------------ read
    def lookup(self, store, keys, snap_ts):
        if self._lookup_fn is None:
            _, self._lookup_fn, _ = _sharded.make_ops(self.config, self.mesh)
        self.stats["device_passes"] += 1
        keys = jnp.atleast_1d(jnp.asarray(keys, jnp.int32))
        return self._lookup_fn(store, keys, jnp.asarray(snap_ts, jnp.int32))

    def range_page(self, store, k1s, k2s, snap_ts,
                   opts: RangeOptions = RangeOptions()) -> RangePage:
        k1 = np.atleast_1d(np.asarray(k1s, np.int32))
        k2 = np.atleast_1d(np.asarray(k2s, np.int32))
        snaps = np.broadcast_to(np.asarray(snap_ts, np.int32), k1.shape)
        fn = self._range_fns.get(opts)
        if fn is None:
            fn = _sharded.make_range_apply(
                self.config, self.mesh, max_results=opts.max_results,
                scan_leaves=opts.scan_leaves, max_rounds=opts.max_rounds,
            )
            self._range_fns[opts] = fn
        self.stats["device_passes"] += 1
        keys, vals, cnt, trunc, resume = fn(
            store, jnp.asarray(k1), jnp.asarray(k2), jnp.asarray(snaps)
        )
        return RangePage(keys, vals, cnt, trunc, resume)

    def scan_page(self, store, k1: int, k2: int, snap_ts, *,
                  max_scan_leaves: int = 64,
                  max_results: int = 1024) -> RangePage:
        opts = RangeOptions(max_results=max_results,
                            scan_leaves=max_scan_leaves, max_rounds=1)
        return self.range_page(store, [k1], [k2], snap_ts, opts)

    def range_all(self, store, k1s, k2s, snap_ts,
                  opts: RangeOptions = RangeOptions()
                  ) -> List[List[Tuple[int, int]]]:
        """Complete Q-interval answers: the shared ``bulk_range_all``
        pagination loop (power-of-two active-set compaction, exact resume)
        driven by the sharded all_gather-merged bounded pass."""
        def page_fn(st, lo, hi, sn):
            page = self.range_page(st, lo, hi, sn, opts)
            return (page.keys, page.values, page.count, page.truncated,
                    page.resume_k1)

        return _batch.bulk_range_all(store, k1s, k2s, snap_ts,
                                     page_fn=page_fn)

    # --------------------------------------------------------- snapshots, GC
    def snapshot(self, store):
        if (self.policy.auto_grow
                and int(np.asarray(store.trk_active)[0].sum())
                >= store.cfg.tracker_cap):
            store = self.grow(store, tracker=True)   # replicated ring is full
        store, snap = _sharded.sharded_snapshot(store)
        return store, int(snap)

    def release(self, store, snap_ts: int):
        return _sharded.sharded_release(store, snap_ts)

    def compact(self, store):
        self.stats["compactions"] += 1
        store, n_live = jax.vmap(_store.compact)(store)
        return store, int(np.asarray(n_live).sum())

    def reindex(self, store):
        """Repack every shard's index in one stacked pass (replicated
        decision: shard shapes stay equal, results unchanged)."""
        self.stats["reindexes"] = self.stats.get("reindexes", 0) + 1
        return self._reshard(_store.reindex(store))
