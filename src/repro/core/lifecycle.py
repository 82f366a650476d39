"""Store lifecycle — proactive growth + incremental maintenance.

The paper's headline property is *proactive* structural maintenance:
splits and helping happen ahead of need, so operations never stall on a
structural wall.  The seed store had exactly such a wall — a fixed
``max_leaves`` pool behind a bump allocator, ``OFLOW_LEAVES`` when splits
exhaust it, and a stop-the-world :func:`repro.core.store.compact` as the
only reclamation.  This module removes it (DESIGN.md Sec 10):

  * :func:`grow` — device-resident pytree doubling of the leaf / version /
    tracker pools.  Pools are bucketed to powers of two (the same trick as
    ``Uruv.apply(pad_to_pow2=True)``), so a run that grows from 4K to 4M
    leaves recompiles O(log capacity) times, not once per grow.  Existing
    leaf ids, version slots and timestamps are preserved bit-exactly: the
    pools extend at the tail, nothing moves.
  * :func:`maintain` — a *bounded incremental* pass: reclaim frozen
    split-leavings and merge underfull neighbours (the paper's merge/MIN
    protocol) for at most ``budget`` leaf pairs + ``budget`` relocations
    per call.  Dead keys (head version is a tombstone at or below
    ``min_active_ts``) are physically dropped, gated by the version
    tracker — every *registered* snapshot reads byte-identical results
    before and after a pass (the same retention contract as ``compact``).
  * :class:`LifecyclePolicy` + the host triggers (:func:`lifecycle_tick`,
    :func:`relieve_pressure`) — the policy the combining layer and the
    ``repro.api`` executors wire in: auto-grow on ``OFLOW_LEAVES`` /
    ``OFLOW_VERSIONS`` instead of raising, and interleaved maintenance on
    an occupancy / frozen-fraction trigger, replacing most stop-the-world
    ``compact()`` calls.  ``CapacityError`` becomes an opt-in condition
    (``auto_grow=False``), not a steady-state failure mode.

Everything here is functional: each entry point returns a new store
pytree; prior pytrees remain valid frozen snapshots.  ``maintain`` never
touches the clock, the version pool or the tracker, and ``grow`` only
appends — so neither changes the result of any operation, and sharded
executions that interleave different lifecycle decisions stay bit-exact
with local ones.
"""

from __future__ import annotations

import dataclasses
import functools
from typing import Dict, Optional, Tuple

import numpy as np
import jax
import jax.numpy as jnp
from jax import lax

from repro.core import index as I
from repro.core import store as S
from repro.core.ref import KEY_MAX, TOMBSTONE
from repro.obs import host_read, span


# ---------------------------------------------------------------------------
# Policy
# ---------------------------------------------------------------------------

@dataclasses.dataclass(frozen=True)
class LifecyclePolicy:
    """Host-side lifecycle policy (DESIGN.md Sec 10).

    The defaults make the store self-sizing: capacity rejections grow the
    rejected pool (power-of-two doubling) and retry, and maintenance runs
    incrementally whenever the frozen/dead fraction of the allocated pool
    crosses ``frozen_trigger``.  Set ``auto_grow=False`` to restore the
    seed behaviour (compact-then-``CapacityError``) for fixed-footprint
    deployments.
    """

    auto_grow: bool = True          # grow pools on OFLOW instead of raising
    auto_maintain: bool = True      # interleave maintain() after applies
    maintain_budget: int = 128      # leaf pairs + relocations per pass
    maintain_passes: int = 2        # max passes per interleaved trigger
    frozen_trigger: float = 0.25    # dead fraction of n_alloc that triggers
    min_dead_leaves: int = 32       # ignore dead fractions of tiny pools
    grow_occupancy: float = 0.9     # proactive: grow before the wall
    version_gc_fraction: float = 0.5  # compact() before growing versions
    pressure_passes: int = 64       # maintain burst bound under OFLOW_LEAVES


DEFAULT_POLICY = LifecyclePolicy()


# ---------------------------------------------------------------------------
# grow — device-resident pool doubling (pow2 shape bucketing)
# ---------------------------------------------------------------------------

def next_pool_size(n: int) -> int:
    """The next power-of-two bucket strictly above ``n`` (2n when n is a
    power of two) — grows are O(log capacity) distinct shapes per run."""
    return 1 << int(n).bit_length()


def _pad_dim(x: jax.Array, axis: int, size: int, fill) -> jax.Array:
    """Extend ``x`` along ``axis`` (negative: layout-agnostic, so the same
    code path serves local [ML, ...] and sharded [n_shards, ML, ...]
    stores) to ``size`` with ``fill``; existing entries are untouched."""
    old = x.shape[axis]
    if size == old:
        return x
    shape = list(x.shape)
    shape[axis % len(shape)] = size - old
    return jnp.concatenate(
        [x, jnp.full(shape, fill, x.dtype)], axis=axis % len(shape)
    )


@functools.partial(jax.jit, static_argnames=("new_ml", "new_mv", "new_mt"))
def _grow(store: S.UruvStore, *, new_ml: int, new_mv: int,
          new_mt: int) -> S.UruvStore:
    cfg = store.cfg
    new_cfg = dataclasses.replace(
        cfg, max_leaves=new_ml, max_versions=new_mv, tracker_cap=new_mt
    )
    return dataclasses.replace(
        store,
        leaf_keys=_pad_dim(store.leaf_keys, -2, new_ml, KEY_MAX),
        leaf_vhead=_pad_dim(store.leaf_vhead, -2, new_ml, -1),
        leaf_count=_pad_dim(store.leaf_count, -1, new_ml, 0),
        leaf_next=_pad_dim(store.leaf_next, -1, new_ml, -1),
        leaf_newnext=_pad_dim(store.leaf_newnext, -1, new_ml, -1),
        leaf_frozen=_pad_dim(store.leaf_frozen, -1, new_ml, False),
        leaf_ts=_pad_dim(store.leaf_ts, -1, new_ml, 0),
        index=I.grow_to(
            store.index, I.index_config(new_ml, cfg.index_fanout), new_ml,
        ),
        ver_value=_pad_dim(store.ver_value, -1, new_mv, 0),
        ver_ts=_pad_dim(store.ver_ts, -1, new_mv, 0),
        ver_next=_pad_dim(store.ver_next, -1, new_mv, -1),
        trk_ts=_pad_dim(store.trk_ts, -1, new_mt, 0),
        trk_active=_pad_dim(store.trk_active, -1, new_mt, False),
        cfg=new_cfg,
    )


def grow(store: S.UruvStore, *, leaves: bool = False, versions: bool = False,
         tracker: bool = False) -> S.UruvStore:
    """Double the selected pools on device; everything else is bit-exact.

    Capacities move to the next power-of-two bucket (``next_pool_size``),
    so repeated growth recompiles jitted consumers O(log capacity) times.
    Leaf ids, version slots, index node ids/ordinals and every timestamp
    are preserved — the pools extend at the tail (growing the leaf pool
    tail-extends every index level under the same pow2 discipline and
    stacks fresh root levels when the depth model deepens — Sec 11).
    Works on local stores and
    on stacked (sharded) stores alike: the leading device axis is left
    untouched, so every shard grows together and shard shapes stay equal
    (the sharded executor's replicated-decision requirement).
    """
    if not (leaves or versions or tracker):
        raise ValueError("grow(): select at least one pool "
                         "(leaves=, versions=, tracker=)")
    cfg = store.cfg
    with span("uruv.lifecycle.grow", leaves=leaves, versions=versions,
              tracker=tracker):
        return _grow(
            store,
            new_ml=next_pool_size(cfg.max_leaves) if leaves
            else cfg.max_leaves,
            new_mv=next_pool_size(cfg.max_versions) if versions
            else cfg.max_versions,
            new_mt=next_pool_size(cfg.tracker_cap) if tracker
            else cfg.tracker_cap,
        )


# ---------------------------------------------------------------------------
# maintain — bounded incremental reclamation + merge (paper's MIN protocol)
# ---------------------------------------------------------------------------

@functools.partial(jax.jit, static_argnames=("budget",))
def _maintain(store: S.UruvStore, phase: jax.Array, *, budget: int):
    cfg = store.cfg
    ML, L = cfg.max_leaves, cfg.leaf_cap
    B = budget
    i32 = jnp.int32
    floor = S.min_active_ts(store)
    allpos = jnp.arange(ML, dtype=i32)

    # ---- dead-at-floor mask (tracker-gated, same retention rule as
    # compact): a slot is dead iff its head version is a tombstone at or
    # below the floor — every registered snapshot resolves it to NOT_FOUND
    # already, so dropping the key is invisible to them. -------------------
    vh = store.leaf_vhead
    vhc = jnp.maximum(vh, 0)
    occupied = jnp.arange(L, dtype=i32)[None, :] < store.leaf_count[:, None]
    dead_slot = (
        occupied & (vh >= 0)
        & (store.ver_value[vhc] == TOMBSTONE)
        & (store.ver_ts[vhc] <= floor)
    )
    live_slot = occupied & ~dead_slot
    live_cnt = jnp.sum(live_slot.astype(i32), axis=1)          # [ML]

    # ---- pair selection: adjacent leaf ordinals (p, p+1) with
    # p ≡ phase (mod 2); alternating the phase between calls covers every
    # boundary.  Eligible: the pair has purgeable dead keys, or merging
    # the live keys fits one leaf with a member under MIN (paper's merge
    # trigger).  The first `budget` eligible pairs are rewritten. --------
    NP = ML // 2
    pos = phase + 2 * jnp.arange(NP, dtype=i32)                # left ordinal
    valid = (pos + 1) < store.n_leaves
    nl1 = jnp.maximum(store.n_leaves - 1, 0)
    la = jnp.where(valid, I.leaf_at(store.index, jnp.minimum(pos, nl1)), 0)
    lb = jnp.where(
        valid, I.leaf_at(store.index, jnp.minimum(pos + 1, nl1)), 0)
    live_a, live_b = live_cnt[la], live_cnt[lb]
    # merge when a member is under the paper's MIN, or when the pair is
    # jointly at most half-full (the merged leaf then needs >= L/2 fresh
    # inserts before it can split again — no split/merge thrash).  The
    # right member's separator must be deletable from its bottom index
    # node (slot >= 1: entry keys are subtree lower bounds — Sec 11);
    # skipped pairs become eligible again after a reindex repack.
    mergeable = valid & (live_a + live_b <= L) & (
        (live_a < cfg.min_fill) | (live_b < cfg.min_fill)
        | (live_a + live_b <= L // 2)
    ) & I.merge_deletable(store.index, jnp.minimum(pos + 1, nl1))
    has_dead = valid & (
        (live_a < store.leaf_count[la]) | (live_b < store.leaf_count[lb])
    )
    eligible = mergeable | has_dead
    rank = jnp.cumsum(eligible.astype(i32)) - 1
    sel = jnp.where(eligible & (rank < B), rank, B)            # scatter idx
    pair_pos = jnp.full((B,), ML, i32).at[sel].set(pos, mode="drop")
    pair_a = jnp.full((B,), 0, i32).at[sel].set(la, mode="drop")
    pair_b = jnp.full((B,), 0, i32).at[sel].set(lb, mode="drop")
    pair_merge = jnp.zeros((B,), bool).at[sel].set(mergeable, mode="drop")
    pair_real = pair_pos < ML

    # ---- rewrite the selected pairs: purge dead keys; merge when the
    # union fits (right leaf cleared + marked frozen = retired garbage) --
    keys_a = jnp.where(live_slot[pair_a], store.leaf_keys[pair_a], KEY_MAX)
    vh_a = jnp.where(live_slot[pair_a], store.leaf_vhead[pair_a], -1)
    keys_b = jnp.where(live_slot[pair_b], store.leaf_keys[pair_b], KEY_MAX)
    vh_b = jnp.where(live_slot[pair_b], store.leaf_vhead[pair_b], -1)
    mk, mv_ = lax.sort(
        (jnp.concatenate([keys_a, keys_b], axis=1),
         jnp.concatenate([vh_a, vh_b], axis=1)),
        dimension=1, num_keys=1,
    )                                                          # [B, 2L]
    pk_a, pv_a = lax.sort((keys_a, vh_a), dimension=1, num_keys=1)
    pk_b, pv_b = lax.sort((keys_b, vh_b), dimension=1, num_keys=1)
    la_live, lb_live = live_cnt[pair_a], live_cnt[pair_b]
    merge = pair_real & pair_merge
    out_a_keys = jnp.where(merge[:, None], mk[:, :L], pk_a)
    out_a_vh = jnp.where(merge[:, None], mv_[:, :L], pv_a)
    out_a_cnt = jnp.where(merge, la_live + lb_live, la_live)
    out_b_keys = jnp.where(merge[:, None], KEY_MAX, pk_b)
    out_b_vh = jnp.where(merge[:, None], -1, pv_b)
    out_b_cnt = jnp.where(merge, 0, lb_live)

    wa = jnp.where(pair_real, pair_a, ML)
    wb = jnp.where(pair_real, pair_b, ML)
    leaf_keys = store.leaf_keys.at[wa].set(out_a_keys, mode="drop")
    leaf_vhead = store.leaf_vhead.at[wa].set(out_a_vh, mode="drop")
    leaf_count = store.leaf_count.at[wa].set(out_a_cnt, mode="drop")
    leaf_keys = leaf_keys.at[wb].set(out_b_keys, mode="drop")
    leaf_vhead = leaf_vhead.at[wb].set(out_b_vh, mode="drop")
    leaf_count = leaf_count.at[wb].set(out_b_cnt, mode="drop")
    leaf_frozen = store.leaf_frozen.at[
        jnp.where(merge, pair_b, ML)
    ].set(True, mode="drop")
    n_merged = jnp.sum(merge.astype(i32))

    # ---- index delta: delete the right members' separators (bounded —
    # O(budget · F); replaces the old O(ML) directory compaction).  The
    # left member keeps its separator (all right keys exceed it), so the
    # separator order stays strict and ordinal 0 stays KEY_MIN. ----------
    index1 = I.apply_merge_delta(
        store.index, jnp.minimum(pair_pos + 1, ML - 1), pair_b, merge)
    n_leaves1 = store.n_leaves - n_merged
    # chain splice: the left member inherits the merged-away successor
    leaf_next = store.leaf_next.at[
        jnp.where(merge, pair_a, ML)
    ].set(store.leaf_next[pair_b], mode="drop")

    # ---- bounded relocation: move up to `budget` of the highest live
    # leaves into the lowest dead slots, then release the all-dead tail
    # of the bump allocator.  Dead slots that stay below the new n_alloc
    # remain frozen garbage for a later pass — the work per call is
    # bounded, the reclamation is incremental.  The reverse map makes the
    # index fixup O(budget) (the old path remapped the whole directory).
    ref = index1.leaf_ent >= 0              # referenced by the index
    alloc = allpos < store.n_alloc
    dead = alloc & ~ref
    drank = jnp.cumsum(dead.astype(i32)) - 1
    dst = jnp.full((B,), ML, i32).at[
        jnp.where(dead & (drank < B), drank, B)
    ].set(allpos, mode="drop")
    rrank = jnp.cumsum(ref[::-1].astype(i32))[::-1] - 1        # from the top
    src = jnp.full((B,), -1, i32).at[
        jnp.where(ref & (rrank < B), rrank, B)
    ].set(allpos, mode="drop")
    do = (dst < ML) & (src >= 0) & (src > dst)
    srcc = jnp.where(do, src, 0)
    dstc = jnp.where(do, dst, ML)
    leaf_keys = leaf_keys.at[dstc].set(leaf_keys[srcc], mode="drop")
    leaf_vhead = leaf_vhead.at[dstc].set(leaf_vhead[srcc], mode="drop")
    leaf_count = leaf_count.at[dstc].set(leaf_count[srcc], mode="drop")
    leaf_ts = store.leaf_ts.at[dstc].set(store.leaf_ts[srcc], mode="drop")
    leaf_frozen = leaf_frozen.at[dstc].set(False, mode="drop")
    leaf_newnext = store.leaf_newnext.at[dstc].set(-1, mode="drop")

    # chain fixups for the moved leaves (bounded scatters): the copied
    # next pointer and the predecessor's link follow the relocation map
    remap = allpos.at[jnp.where(do, src, ML)].set(
        jnp.where(do, dst, 0), mode="drop")
    nxt_src = leaf_next[srcc]
    leaf_next = leaf_next.at[dstc].set(
        jnp.where(nxt_src >= 0, remap[jnp.maximum(nxt_src, 0)], -1),
        mode="drop")
    Fi = cfg.index_fanout
    ent = index1.leaf_ent[srcc]
    ordv = I.leaf_ordinal(index1, jnp.maximum(ent, 0) // Fi,
                          jnp.maximum(ent, 0) % Fi)
    has_pred = do & (ordv > 0)
    pred = I.leaf_at(index1, jnp.maximum(ordv - 1, 0))
    leaf_next = leaf_next.at[
        jnp.where(has_pred, remap[jnp.maximum(pred, 0)], ML)
    ].set(jnp.where(do, dst, -1), mode="drop")

    # index entry retarget (reverse-map lookup; O(budget))
    index2 = I.retarget_leaves(index1, src, dst, do)
    ref2 = index2.leaf_ent >= 0
    n_alloc = jnp.maximum(jnp.max(jnp.where(ref2, allpos + 1, 0)), 1) \
        .astype(i32)

    # freed tail: scrub so the bump allocator can hand the slots out again
    freed = alloc & (allpos >= n_alloc)
    leaf_keys = jnp.where(freed[:, None], KEY_MAX, leaf_keys)
    leaf_vhead = jnp.where(freed[:, None], -1, leaf_vhead)
    leaf_count = jnp.where(freed, 0, leaf_count)
    leaf_frozen = jnp.where(freed, False, leaf_frozen)
    leaf_newnext = jnp.where(freed, -1, leaf_newnext)
    leaf_ts = jnp.where(freed, 0, leaf_ts)
    leaf_next = jnp.where(freed, -1, leaf_next)

    reclaimed = store.n_alloc - n_alloc
    new = dataclasses.replace(
        store,
        leaf_keys=leaf_keys,
        leaf_vhead=leaf_vhead,
        leaf_count=leaf_count,
        leaf_next=leaf_next,
        leaf_newnext=leaf_newnext,
        leaf_frozen=leaf_frozen,
        leaf_ts=leaf_ts,
        n_alloc=n_alloc,
        index=index2,
        n_leaves=n_leaves1,
    )
    return new, reclaimed, n_merged


def maintain(
    store: S.UruvStore, budget: int = 128, *, phase: int = 0,
    stats: Optional[Dict[str, int]] = None,
) -> Tuple[S.UruvStore, int, int]:
    """ONE bounded incremental maintenance pass (device-resident).

    Rewrites at most ``budget`` adjacent leaf pairs — purging keys whose
    head version is a tombstone at or below ``min_active_ts`` (the version
    tracker gate) and merging neighbours whose live keys fit one leaf with
    a member under the paper's MIN — then relocates at most ``budget``
    live leaves downward to release the dead tail of the leaf bump
    allocator (frozen split-leavings and merged-away leaves).

    Returns ``(store, leaves_reclaimed, pairs_merged)``.  Never touches
    the clock, the version pool, or the tracker: every operation result —
    including reads at any *registered* snapshot — is byte-identical
    before and after the pass.  Alternate ``phase`` (0/1) between calls so
    both pair parities of the directory are covered.  A stacked (sharded)
    store dispatches through ``jax.vmap`` — every shard maintains in the
    same call, so shard shapes stay equal (the replicated-decision rule).
    ``stats`` counts the pass's one host read (``host_syncs``).
    """
    ph = jnp.asarray(phase % 2, jnp.int32)
    if np.ndim(store.ts):                  # stacked (sharded) store
        fn = jax.vmap(functools.partial(_maintain, budget=budget),
                      in_axes=(0, None))
        new, reclaimed, merged = fn(store, ph)
    else:
        new, reclaimed, merged = _maintain(store, ph, budget=budget)
    reclaimed, merged = host_read(stats, (reclaimed, merged))
    return new, int(np.sum(reclaimed)), int(np.sum(merged))


# ---------------------------------------------------------------------------
# Per-pool dirty watermarks (delta checkpoints, DESIGN.md Sec 14)
# ---------------------------------------------------------------------------

@dataclasses.dataclass(frozen=True)
class PoolWatermarks:
    """Host snapshot of the pool allocators at one clock value.

    The delta-checkpoint writer (``repro.checkpoint.manager``) compares
    two of these to decide what can be skipped without reading it:
    ``grow`` only tail-extends (pow2 buckets, prefixes bit-exact) and the
    version pool is a bump allocator that structural passes never rewrite
    except :func:`repro.core.store.compact` — so between saves with no
    compaction, version slots below the older ``n_vers`` are immutable
    and the delta is exactly the tail slice.  ``compactions`` is the
    caller-supplied pass counter (``Uruv.stats``) that invalidates that
    reasoning when it moves.
    """

    ts: int
    n_alloc: int
    n_vers: int
    n_leaves: int
    max_leaves: int
    max_versions: int
    tracker_cap: int
    compactions: int = 0


def pool_watermarks(store: S.UruvStore, *,
                    compactions: int = 0) -> PoolWatermarks:
    """Read the allocator watermarks (one host transfer; sharded stores
    report per-shard maxima — the tail fast path below then disables
    itself, see :func:`version_tail_start`)."""
    ts, n_alloc, n_vers, n_leaves = jax.device_get(
        (store.ts, store.n_alloc, store.n_vers, store.n_leaves))
    return PoolWatermarks(
        ts=int(np.asarray(ts).max()),
        n_alloc=int(np.asarray(n_alloc).max()),
        n_vers=int(np.asarray(n_vers).max()),
        n_leaves=int(np.asarray(n_leaves).max()),
        max_leaves=int(store.cfg.max_leaves),
        max_versions=int(store.cfg.max_versions),
        tracker_cap=int(store.cfg.tracker_cap),
        compactions=compactions,
    )


def version_tail_start(before: PoolWatermarks, store: S.UruvStore, *,
                       compactions: int = 0) -> Optional[int]:
    """The append-only fast path for delta checkpoints: the first version
    slot that may differ from the state ``before`` describes, or ``None``
    when tail stability cannot be guaranteed (a compaction ran, the pool
    is stacked/sharded, or the allocator moved backwards) and the writer
    must fall back to a full row diff."""
    if compactions != before.compactions:
        return None
    if np.asarray(store.ts).ndim:          # stacked: per-shard allocators
        return None
    n_vers = int(np.asarray(store.n_vers))
    if n_vers < before.n_vers:
        return None
    return before.n_vers


# ---------------------------------------------------------------------------
# Host-side occupancy accounting + triggers
# ---------------------------------------------------------------------------

def leaf_accounting(store: S.UruvStore) -> Dict[str, int]:
    """Bump-allocator accounting (host-side; sharded stores sum shards).

    Invariant (tested): every allocated slot is either live (referenced by
    the directory, not frozen) or dead (frozen — a retired split-leaving
    or merged-away leaf awaiting reclamation):
    ``n_alloc == live + dead`` and ``dead == frozen_allocated``.
    """
    n_alloc = int(np.asarray(store.n_alloc).sum())
    live = int(np.asarray(store.n_leaves).sum())
    frozen = np.asarray(store.leaf_frozen)
    alloc_mask = (
        np.arange(frozen.shape[-1])[None, :]
        < np.asarray(store.n_alloc).reshape(-1, 1)
    )
    dead = int((frozen.reshape(alloc_mask.shape) & alloc_mask).sum())
    return {
        "n_alloc": n_alloc,
        "live": live,
        "dead": dead,
        "capacity": int(store.cfg.max_leaves)
        * (np.asarray(store.ts).size),
    }


def live_key_count(store: S.UruvStore,
                   stats: Optional[Dict[str, int]] = None) -> int:
    """Total keys held by index-referenced leaves (host-side; frozen
    leavings keep stale counts and are excluded).  Tombstoned keys count
    until maintenance purges them — this is a pool-occupancy figure, not
    a liveness oracle."""
    lc, ent = host_read(stats, (store.leaf_count, store.index.leaf_ent))
    return int(lc[ent >= 0].sum())   # same shape, incl. stacked


def dead_fraction(store: S.UruvStore,
                  stats: Optional[Dict[str, int]] = None) -> float:
    """Dead (unreferenced-but-allocated) fraction of the leaf pool."""
    n_alloc, n_leaves = host_read(stats, (store.n_alloc, store.n_leaves))
    n_alloc = int(np.sum(n_alloc))
    live = int(np.sum(n_leaves))
    return (n_alloc - live) / max(n_alloc, 1)


def run_maintenance(
    store: S.UruvStore, policy: LifecyclePolicy, *,
    stats: Optional[Dict[str, int]] = None, max_passes: Optional[int] = None,
    maintain_fn=None,
) -> S.UruvStore:
    """Bounded burst of maintain passes with alternating phase.

    Stops after ``max_passes`` (default ``policy.maintain_passes``), when a
    pass reclaims and merges nothing, or when the dead fraction falls
    under half the trigger.  ``maintain_fn(store, budget, phase)``
    overrides the local pass (the sharded executor supplies its vmapped
    one); the burst/trigger/accounting loop is shared either way.
    """
    if maintain_fn is None:
        def maintain_fn(st, budget, phase):
            return maintain(st, budget, phase=phase, stats=stats)
    passes = max_passes if max_passes is not None else policy.maintain_passes
    with span("uruv.lifecycle.maintain", max_passes=passes):
        for p in range(passes):
            store, reclaimed, merged = maintain_fn(
                store, policy.maintain_budget, p % 2
            )
            if stats is not None:
                stats["maintain_passes"] = stats.get("maintain_passes", 0) + 1
                stats["leaves_reclaimed"] = (
                    stats.get("leaves_reclaimed", 0) + reclaimed
                )
            if reclaimed == 0 and merged == 0:
                break
            if dead_fraction(store, stats) < policy.frozen_trigger / 2:
                break
    return store


def lifecycle_tick(
    store: S.UruvStore, policy: LifecyclePolicy, *,
    stats: Optional[Dict[str, int]] = None, grow_fn=None, maintain_fn=None,
) -> S.UruvStore:
    """The post-apply interleave BOTH executors share: a bounded maintain
    burst on the frozen-fraction trigger FIRST (reclaiming frozen leaves
    is cheaper than a permanent doubling and often drops occupancy back
    under the growth trigger), then proactive growth re-checked on the
    maintained store.  One ``device_get`` serves both triggers — the
    apply path already synced on ``ok``, so the tick adds at most one
    extra blocking transfer.  ``grow_fn(store)`` / ``maintain_fn(store,
    budget, phase)`` let a topology wrap its own passes (the sharded
    executor reshards after each) without duplicating the trigger logic.
    """
    if not (policy.auto_grow or policy.auto_maintain):
        return store
    with span("uruv.lifecycle.tick"):
        n_alloc_raw, n_leaves_raw = host_read(
            stats, (store.n_alloc, store.n_leaves))
        n_alloc = int(np.asarray(n_alloc_raw).sum())
        dead = n_alloc - int(np.asarray(n_leaves_raw).sum())
        if (policy.auto_maintain and dead >= policy.min_dead_leaves
                and dead / max(n_alloc, 1) >= policy.frozen_trigger):
            store = run_maintenance(store, policy, stats=stats,
                                    maintain_fn=maintain_fn)
            n_alloc_raw = host_read(stats, store.n_alloc)
        if (policy.auto_grow
                and int(np.asarray(n_alloc_raw).max())
                > policy.grow_occupancy * store.cfg.max_leaves):
            if grow_fn is None:
                if stats is not None:
                    stats["grows"] = stats.get("grows", 0) + 1
                store = grow(store, leaves=True)
            else:
                store = grow_fn(store)
        return store


def relieve_pressure(
    store: S.UruvStore, reason: int, width: int, policy: LifecyclePolicy, *,
    stats: Optional[Dict[str, int]] = None,
) -> S.UruvStore:
    """One pressure-relief step for a capacity-rejected batch (host policy).

    ``OFLOW_LEAVES``: when the dead fraction is above the trigger, burst
    ``maintain`` (reclaiming frozen garbage is cheaper than growing);
    otherwise — or if the burst freed nothing — double the leaf pool.
    ``OFLOW_VERSIONS``: ``compact()`` first when the pool is mostly-full
    garbage candidate (the tracker-gated GC), then double the version pool
    until the batch provably fits.  ``OFLOW_INDEX``: the fat-node pools
    are fragmented (or the root overflowed) — :func:`S.reindex` repacks
    them at pack_fill, which always frees enough slots for the retry.
    The caller retries the device pass after each step; every step
    strictly increases free capacity, so the retry loop converges.
    """
    if reason & S.OFLOW_INDEX:
        if stats is not None:
            stats["reindexes"] = stats.get("reindexes", 0) + 1
        store = S.reindex(store)
    if reason & S.OFLOW_LEAVES:
        before = int(np.sum(host_read(stats, store.n_alloc)))
        if dead_fraction(store, stats) >= policy.frozen_trigger:
            store = run_maintenance(
                store, policy, stats=stats,
                max_passes=policy.pressure_passes,
            )
        if int(np.sum(host_read(stats, store.n_alloc))) >= before:
            store = grow(store, leaves=True)
            if stats is not None:
                stats["grows"] = stats.get("grows", 0) + 1
    if reason & S.OFLOW_VERSIONS:
        cfg = store.cfg
        n_vers_all = host_read(stats, store.n_vers)
        n_vers = int(np.max(n_vers_all))
        # compact() can reclaim at most sum(n_vers) - live_keys versions
        # (every key that survives retains >= 1): pure-ingest pools with
        # no version history have nothing to give back — skip the
        # stop-the-world pass and grow directly.
        reclaimable_bound = (
            int(np.sum(n_vers_all)) - live_key_count(store, stats)
        )
        if (reclaimable_bound >= width
                and n_vers >= policy.version_gc_fraction * cfg.max_versions):
            if stats is not None:
                stats["compactions"] = stats.get("compactions", 0) + 1
            if np.ndim(store.ts):                  # stacked (sharded) store
                store, _ = jax.vmap(S.compact)(store)
            else:
                store, _ = S.compact(store)
            n_vers = int(np.max(host_read(stats, store.n_vers)))
        while n_vers + width > store.cfg.max_versions:
            store = grow(store, versions=True)
            if stats is not None:
                stats["grows"] = stats.get("grows", 0) + 1
    return store
