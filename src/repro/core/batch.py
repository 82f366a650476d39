"""Wait-free combining layer — the announce/help construction, batched.

The paper's fast-path/slow-path (Kogan–Petrank [16], Sec 4) maps to:

  * fast path  — the whole *mixed* announce array (SEARCH / INSERT /
    DELETE / NOP) is applied in ONE deterministic data-parallel pass
    (`store.bulk_apply`): updates append versions at their per-op
    timestamps and searches resolve at their per-op snapshots, all in the
    same device call.  This succeeds unless the batch over-concentrates
    structural inserts (> L new keys into one leaf) or a pool fills up.
  * slow path  — on rejection the combining layer *helps in rounds*: it
    halves the announce array and re-applies with the ORIGINAL per-op
    timestamps (`op_ts` plumbing), so the linearization is bit-identical
    to the one-pass application; capacity overflows trigger `compact()`
    (the GC the paper performs during split/merge, gated by the version
    tracker).  Recursion terminates: a single op can never violate the
    per-leaf bound, so every op completes in a bounded number of rounds —
    wait-freedom.

This module is host-side control flow around jitted kernels (the usual
launcher/runtime split in a TPU system: device passes are bounded and
deterministic, the host sequences them).
"""

from __future__ import annotations

import dataclasses
import warnings
from typing import Dict, List, Optional, Sequence, Tuple

import numpy as np
import jax.numpy as jnp

from repro.core import lifecycle as LC
from repro.core import store as S
from repro.core.ref import KEY_MAX, NOT_FOUND, OP_RANGE
from repro.obs import host_read, span


class CapacityError(RuntimeError):
    """The store cannot fit the working set under the active policy.

    With the default self-sizing lifecycle (``LifecyclePolicy.auto_grow``)
    this is no longer a steady-state condition — it is raised only when
    growth is disabled, a single op violates ``leaf_cap``, or the bounded
    retry loops fail to converge.  Carries diagnostics:

      * ``oflow``      — the ``OFLOW_*`` bitmask of the last rejection
      * ``occupancy``  — leaf-allocator occupancy ``n_alloc / max_leaves``
      * ``frozen_fraction`` — dead (unreferenced-but-allocated) fraction
      * ``n_vers`` / ``max_versions`` — version-pool fill
    """

    def __init__(self, message: str, *, store: Optional[S.UruvStore] = None,
                 oflow: int = 0):
        self.oflow = int(oflow)
        self.occupancy = 0.0
        self.frozen_fraction = 0.0
        self.n_vers = 0
        self.max_versions = 0
        if store is not None:
            n_alloc = int(np.asarray(store.n_alloc).sum())
            self.occupancy = n_alloc / max(
                int(store.cfg.max_leaves) * np.asarray(store.ts).size, 1
            )
            self.frozen_fraction = LC.dead_fraction(store)
            self.n_vers = int(np.asarray(store.n_vers).max())
            self.max_versions = int(store.cfg.max_versions)
            message = (
                f"{message} [oflow={self.oflow:#x} "
                f"occupancy={self.occupancy:.2f} "
                f"frozen_fraction={self.frozen_fraction:.2f} "
                f"versions={self.n_vers}/{self.max_versions}]"
            )
        super().__init__(message)


MAX_SLOWPATH_ROUNDS = 64


def _clear_oflow(store: S.UruvStore) -> S.UruvStore:
    return dataclasses.replace(store, oflow=jnp.zeros_like(store.oflow))


def _bump(stats: Optional[Dict[str, int]], key: str, by: int = 1) -> None:
    if stats is not None:
        stats[key] = stats.get(key, 0) + by


def _apply_rounds(
    store: S.UruvStore,
    codes: np.ndarray,
    keys: np.ndarray,
    values: np.ndarray,
    op_ts: Optional[np.ndarray],
    next_ts,
    *,
    light_path: bool = True,
    backend: Optional[str] = None,
    stats: Optional[Dict[str, int]] = None,
    policy: Optional[LC.LifecyclePolicy] = None,
    _depth: int = 0,
) -> Tuple[S.UruvStore, np.ndarray]:
    """One fast-path attempt + bounded help-rounds on rejection.

    ``op_ts is None`` is the common entry: the device pass assigns
    ``store.ts + i`` itself (zero host syncs on the fast path).  Slow-path
    recursion materialises the timestamps once and slices them, so every
    round applies its ops at exactly the timestamps the one-pass
    application would have used.  ``stats`` (see ``repro.api``) counts
    every device pass, slow-path round and host read (``host_syncs``: one
    per pass on the fast path, the accept flag and the results in one
    transfer).

    Capacity policy (DESIGN.md Sec 10): with a ``policy`` whose
    ``auto_grow`` is set (the ``repro.api`` default), ``OFLOW_LEAVES`` /
    ``OFLOW_VERSIONS`` rejections run one ``lifecycle.relieve_pressure``
    step (incremental maintain, pool doubling, or tracker-gated compact)
    and retry — no steady-state ``CapacityError``.  ``policy=None`` keeps
    the legacy fixed-footprint behaviour: compact-then-retry, error when
    compaction frees nothing.  Lifecycle choices never alter results or
    timestamps, only where the arrays live.
    """
    if _depth > MAX_SLOWPATH_ROUNDS:
        raise CapacityError("slow path failed to converge; store too small",
                            store=store)
    _bump(stats, "device_passes")
    new_store, res, ok = S.bulk_apply(
        store, codes, keys, values, op_ts=op_ts, next_ts=next_ts,
        light_path=light_path, backend=backend,
    )
    ok, res = host_read(stats, (ok, res))
    if bool(ok):
        return new_store, res
    _bump(stats, "slow_path_rounds")
    new_oflow, old_oflow = host_read(stats, (new_store.oflow, store.oflow))
    reason = int(new_oflow) & ~int(old_oflow)
    if reason & S.OFLOW_INDEX:
        # fat-node pools fragmented (or root overflow): reindex repacks
        # them at pack_fill — no capacity growth, results unchanged —
        # then retry at the SAME timestamps.  Available under every
        # policy (it is reclamation, not growth).
        _bump(stats, "reindexes")
        return _apply_rounds(S.reindex(_clear_oflow(store)), codes, keys,
                             values, op_ts, next_ts, light_path=light_path,
                             backend=backend, stats=stats, policy=policy,
                             _depth=_depth + 1)
    if reason & (S.OFLOW_VERSIONS | S.OFLOW_LEAVES):
        if policy is not None and policy.auto_grow:
            relieved = LC.relieve_pressure(
                _clear_oflow(store), reason, len(keys), policy, stats=stats,
            )
            return _apply_rounds(relieved, codes, keys, values, op_ts,
                                 next_ts, light_path=light_path,
                                 backend=backend, stats=stats, policy=policy,
                                 _depth=_depth + 1)
        _bump(stats, "compactions")
        compacted, _ = S.compact(_clear_oflow(store))
        # progress check on the actual constrained resources: the version
        # pool and the leaf bump-allocator (compact() resets both)
        c_vers, s_vers, c_alloc, s_alloc = host_read(stats, (
            compacted.n_vers, store.n_vers, compacted.n_alloc, store.n_alloc))
        progressed = int(c_vers) < int(s_vers) or int(c_alloc) < int(s_alloc)
        if not progressed and not (reason & S.OFLOW_LEAFBATCH):
            raise CapacityError(
                f"store full (versions={int(store.n_vers)}/"
                f"{store.cfg.max_versions}, "
                f"leaves={int(store.n_alloc)}/{store.cfg.max_leaves})",
                store=store, oflow=reason,
            )
        return _apply_rounds(compacted, codes, keys, values, op_ts, next_ts,
                             light_path=light_path, backend=backend,
                             stats=stats, policy=policy, _depth=_depth + 1)
    # OFLOW_LEAFBATCH: help in rounds — halve the announce array, keeping
    # the per-op timestamp assignment of the rejected one-pass attempt.
    if len(keys) == 1:
        raise CapacityError("single op rejected; leaf_cap too small",
                            store=store, oflow=reason)
    if op_ts is None:
        base = int(host_read(stats, store.ts))
        op_ts = (base + np.arange(len(keys))).astype(np.int32)
        if next_ts is None:
            next_ts = base + len(keys)
    mid = len(keys) // 2
    st = _clear_oflow(store)
    st, res_a = _apply_rounds(st, codes[:mid], keys[:mid], values[:mid],
                              op_ts[:mid], int(op_ts[mid]),
                              light_path=light_path, backend=backend,
                              stats=stats, policy=policy, _depth=_depth + 1)
    st, res_b = _apply_rounds(st, codes[mid:], keys[mid:], values[mid:],
                              op_ts[mid:], next_ts,
                              light_path=light_path, backend=backend,
                              stats=stats, policy=policy, _depth=_depth + 1)
    return st, np.concatenate([res_a, res_b])


def apply_updates(
    store: S.UruvStore,
    keys: np.ndarray,
    values: np.ndarray,
) -> Tuple[S.UruvStore, np.ndarray]:
    """DEPRECATED — use ``repro.api.Uruv.apply(OpBatch.updates(keys, values))``.

    Legacy INSERT/DELETE announce array (DELETE == value TOMBSTONE, padded
    keys KEY_MAX are no-ops); returns (store, prev_values).  Delegates to
    the ``repro.api`` client, so results and linearization are bit-exact
    with the client path.
    """
    warnings.warn(
        "repro.core.batch.apply_updates is deprecated; use "
        "repro.api.Uruv.apply(OpBatch.updates(keys, values))",
        DeprecationWarning, stacklevel=2,
    )
    from repro import api

    client = api.Uruv.from_store(store)
    res = client.apply(api.OpBatch.updates(keys, values))
    return client.store, np.asarray(res.values)


def apply_mixed(
    store: S.UruvStore,
    codes: np.ndarray,
    keys: np.ndarray,
    values: np.ndarray,
    *,
    light_path: bool = True,
    backend: Optional[str] = None,
    max_results: int = 1024,
    scan_leaves: int = 16,
    max_rounds: int = 8,
    stats: Optional[Dict[str, int]] = None,
    policy: Optional[LC.LifecyclePolicy] = None,
    crud_fn=None,
    range_all_fn=None,
    get_ts_fn=None,
    set_ts_fn=None,
) -> Tuple[S.UruvStore, np.ndarray, List[Tuple[int, List[Tuple[int, int]]]]]:
    """Array-level mixed announce sequencer — the host half of the ADT.

    Linearizes ``(codes[i], keys[i], values[i])`` in announce order (op i
    at ts base+i), matching ``RefStore.apply_batch``.  Returns
    ``(store, results[n] int64, range_pages)`` where ``range_pages`` is a
    list of (announce_pos, complete (key, value) page) for every RANGE op
    (``results`` carries their live-key counts).

    Fast path: one device pass (`store.bulk_apply`) for a pure-CRUD array
    (zero host syncs).  With range ops, the array executes in segments at
    range boundaries: each CRUD run is one `bulk_apply` at its original
    announce timestamps and each run of consecutive range ops is ONE
    batched `store.bulk_range` pass against the store state that precedes
    it — so a range snapshot resolves every key at chain depth 0 and stays
    exact no matter how many same-key updates FOLLOW it in the batch
    (resolving post-hoc would walk those later versions and silently lose
    keys past cfg.max_chain; the segment order is the range analogue of
    the in-pass predecessor short-circuit that makes SEARCH exact,
    DESIGN.md Sec 3/8).

    The four hooks let another topology reuse THIS loop (one copy of the
    segmentation semantics, mirroring bulk_range_all's ``page_fn``):
    ``crud_fn(store, codes, keys, values, op_ts, next_ts)`` applies one
    CRUD segment (default: the local help-rounds; a custom fn may ignore
    ``op_ts`` if its passes derive timestamps from the store clock),
    ``range_all_fn(store, k1, k2, snaps)`` answers one RANGE segment
    completely, ``get_ts_fn(store)`` reads the global clock, and
    ``set_ts_fn(store, ts)`` restates it after a RANGE segment (range ops
    occupy announce slots but their passes do not advance the clock).
    """
    codes = np.asarray(codes, np.int32)
    keys = np.asarray(keys, np.int32)
    vals = np.asarray(values, np.int32)
    if crud_fn is None:
        def crud_fn(st, c, k, v, op_ts, next_ts):
            return _apply_rounds(st, c, k, v, op_ts, next_ts,
                                 light_path=light_path, backend=backend,
                                 stats=stats, policy=policy)
    if range_all_fn is None:
        def range_all_fn(st, k1, k2, snaps):
            return bulk_range_all(
                st, k1, k2, snaps,
                max_results=max_results, scan_leaves=scan_leaves,
                max_rounds=max_rounds, backend=backend, stats=stats,
            )
    if get_ts_fn is None:
        get_ts_fn = lambda st: int(host_read(stats, st.ts))  # noqa: E731
    if set_ts_fn is None:
        def set_ts_fn(st, ts):
            return dataclasses.replace(st, ts=jnp.asarray(ts, jnp.int32))
    n = len(codes)
    if n == 0:
        return store, np.zeros(0, np.int64), []
    rmask = codes == OP_RANGE
    if not rmask.any():
        with span("uruv.segment", kind="crud", width=n):
            store, res = crud_fn(store, codes, keys, vals, None, None)
        return store, np.asarray(res).astype(np.int64), []
    base = get_ts_fn(store)
    op_ts = (base + np.arange(n)).astype(np.int32)
    results = np.full(n, NOT_FOUND, np.int64)
    range_pages: List[Tuple[int, List[Tuple[int, int]]]] = []
    i = 0
    while i < n:
        j = i
        while j < n and bool(rmask[j]) == bool(rmask[i]):
            j += 1
        if rmask[i]:
            with span("uruv.segment", kind="range", width=j - i):
                pages = range_all_fn(store, keys[i:j], vals[i:j],
                                     op_ts[i:j])
            results[i:j] = [len(p) for p in pages]
            range_pages.extend(zip(range(i, j), pages))
            # CRUD passes advance the clock themselves (next_ts / the
            # replicated counter); range segments must restate it
            store = set_ts_fn(store, base + j)
        else:
            with span("uruv.segment", kind="crud", width=j - i):
                store, res = crud_fn(store, codes[i:j], keys[i:j],
                                     vals[i:j], op_ts[i:j], base + j)
            results[i:j] = res
        i = j
    return store, results, range_pages


def apply_batch(
    store: S.UruvStore, ops: Sequence[Tuple[int, int, int]]
) -> Tuple[S.UruvStore, List[int]]:
    """Mixed announce array of (op, key, value) tuples; thin wrapper over
    :func:`apply_mixed` keeping the oracle-shaped (store, list) signature.

    RANGEQUERY rides in the same announce array: ``(OP_RANGE, k1, k2)`` at
    announce index i scans [k1, k2] at snapshot ``base + i`` — it observes
    every earlier in-batch update and none of the later ones — and its
    result is the live-key count (full pages via ``repro.api.Uruv.apply``
    or :func:`bulk_range_all`).
    """
    codes = np.array([o[0] for o in ops], np.int32)
    keys = np.array([o[1] for o in ops], np.int32)
    vals = np.array([o[2] for o in ops], np.int32)
    store, results, _ = apply_mixed(store, codes, keys, vals)
    return store, results.tolist()


# ---------------------------------------------------------------------------
# Batched range search sequencing (host side of store.bulk_range)
# ---------------------------------------------------------------------------

# sentinel interval that can never match a key (retired queries re-enter the
# device pass as no-ops: lo > every key, k2 < every key => zero work)
_DONE_LO = KEY_MAX
_DONE_HI = -(2**31)


def bulk_range_all(
    store: S.UruvStore,
    k1s,
    k2s,
    snap_ts,
    *,
    max_results: int = 1024,
    scan_leaves: int = 16,
    max_rounds: int = 8,
    backend: Optional[str] = None,
    stats: Optional[Dict[str, int]] = None,
    page_fn=None,
) -> List[List[Tuple[int, int]]]:
    """Answer Q range queries COMPLETELY; returns per-query (key, value) lists.

    One `store.bulk_range` device pass answers all Q intervals at once (the
    pooled in-pass budget covers Q * max_rounds * scan_leaves leaves,
    distributed by need); only queries still truncated after that re-enter
    the next pass, resuming from their exact ``resume_k1`` — so a giant
    scan costs O(pages) device rounds TOTAL, not O(pages) per query.  The
    active set is compacted (to power-of-two widths, bounding retraces)
    between passes, so tail pages only pay for the queries still scanning.
    Read-only: ``snap_ts`` (scalar or [Q]) must already be registered if
    isolation across later updates is required (see store.snapshot /
    release).

    ``page_fn(store, k1[W], k2[W], snap[W]) -> (keys, vals, count,
    truncated, resume_k1)`` overrides the bounded pass itself (the sharded
    executor supplies its all_gather-merged pass); the pagination loop —
    active-set compaction, resume, convergence bound — is shared either
    way, so the topologies cannot drift.
    """
    if page_fn is None:
        def page_fn(st, lo_p, hi_p, sn_p):
            _bump(stats, "device_passes")
            return S.bulk_range(
                st, lo_p, hi_p, sn_p,
                max_results=max_results, scan_leaves=scan_leaves,
                max_rounds=max_rounds, backend=backend,
            )
    k1 = np.asarray(k1s, np.int32).reshape(-1)
    k2 = np.asarray(k2s, np.int32).reshape(-1)
    Q = len(k1)
    snaps = np.broadcast_to(np.asarray(snap_ts, np.int32), (Q,))
    out: List[List[Tuple[int, int]]] = [[] for _ in range(Q)]
    idx = np.arange(Q)                    # active query -> caller position
    lo, hi, sn = k1.copy(), k2.copy(), snaps.copy()
    for _ in range(MAX_SLOWPATH_ROUNDS * 64):
        W = max(1, 1 << int(len(idx) - 1).bit_length())   # pad: bounded shapes
        pad = W - len(idx)
        lo_p = np.concatenate([lo, np.full(pad, _DONE_LO, np.int32)])
        hi_p = np.concatenate([hi, np.full(pad, _DONE_HI, np.int32)])
        sn_p = np.concatenate([sn, np.zeros(pad, np.int32)])
        keys, vals, cnt, trunc, resume = host_read(
            stats, page_fn(store, lo_p, hi_p, sn_p))
        trunc = trunc[: len(idx)]
        for a, q in enumerate(idx):
            c = int(cnt[a])
            out[q].extend(zip(keys[a, :c].tolist(), vals[a, :c].tolist()))
        if not trunc.any():
            break
        act = np.nonzero(trunc)[0]
        idx = idx[act]
        lo = resume[act].astype(np.int32)
        hi = hi[act]
        sn = sn[act]
    else:
        raise CapacityError(
            "bulk_range_all failed to converge: "
            f"{len(idx)} queries still truncated after "
            f"{MAX_SLOWPATH_ROUNDS * 64} passes; widen max_results or the "
            "scan_leaves * max_rounds leaf budget"
        )
    return out


def range_query_all(
    store: S.UruvStore,
    k1: int,
    k2: int,
    snap_ts: Optional[int] = None,
    *,
    max_scan_leaves: int = 64,
    max_results: int = 1024,
) -> Tuple[S.UruvStore, List[Tuple[int, int]]]:
    """DEPRECATED — use ``repro.api.Uruv.range(k1, k2, snap_ts)``.

    Paginated snapshot range scan covering [k1, k2] completely, with the
    legacy (store, items) signature.  Registers/releases the snapshot in
    the version tracker when ``snap_ts`` is None.  Delegates to the
    ``repro.api`` client, so pages are bit-exact with the client path.
    """
    warnings.warn(
        "repro.core.batch.range_query_all is deprecated; use "
        "repro.api.Uruv.range(k1, k2, snap_ts)",
        DeprecationWarning, stacklevel=2,
    )
    from repro import api

    client = api.Uruv.from_store(store)
    out = client.range(k1, k2, snap_ts,
                       max_results=max_results,
                       scan_leaves=max_scan_leaves,
                       max_rounds=1)
    return client.store, out
