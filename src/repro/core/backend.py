"""Backend dispatch for the Uruv hot-path primitives (DESIGN.md Sec 7).

The store's three inner loops — ``locate`` (multi-level fat-node descent
+ in-leaf rank; DESIGN.md Sec 11), ``resolve`` (versioned chain read),
and ``range_scan`` (fused leaf-window gather + versioned resolve for
batched range queries) — have three implementations with one contract:

  * ``xla``              — pure-jnp formulation (gather/compare-reduce
    descent via ``repro.core.index``, ``while_loop`` chain walk).  Compiles
    for every platform, the TPU v5e included; the default everywhere.
  * ``pallas``           — the Pallas TPU kernels
    (``repro.kernels.uruv_search`` + ``repro.kernels.versioned_read`` +
    ``repro.kernels.uruv_range``), an explicit opt-in.  ``leaf_slots``
    compiles for v5e; ``index_descend``, ``versioned_read`` and
    ``range_scan`` gather rows from whole pools held in VMEM, which
    Mosaic does not lower, so a store pass on this backend fails with the
    compiler's own error.  Nothing catches it or swaps in another
    backend.
  * ``pallas_interpret`` — the same kernels under the Pallas interpreter;
    kernel coverage on CPU.

Resolution order: :func:`set_backend` override > ``URUV_BACKEND`` env var >
``xla``.  The chosen backend is threaded as a *static* argument through the
store's jitted entry points, so switching backends retraces rather than
silently reusing a stale compilation.
"""

from __future__ import annotations

import os

import jax.numpy as jnp
from jax import lax

from repro.analysis.marks import device_pass
from repro.core.ref import KEY_MAX, NOT_FOUND, TOMBSTONE

XLA = "xla"
PALLAS = "pallas"
PALLAS_INTERPRET = "pallas_interpret"
BACKENDS = (XLA, PALLAS, PALLAS_INTERPRET)

ENV_VAR = "URUV_BACKEND"

_override: str | None = None


def _validate(name: str) -> str:
    if name not in BACKENDS:
        raise ValueError(f"unknown Uruv backend {name!r}; expected one of {BACKENDS}")
    return name


def set_backend(name: str | None) -> None:
    """Process-wide override (None restores env/auto resolution)."""
    global _override
    _override = None if name is None else _validate(name)


def get_backend() -> str:
    """Resolve the active backend: override > env > ``xla``."""
    if _override is not None:
        return _override
    env = os.environ.get(ENV_VAR)
    if env:
        return _validate(env)
    return XLA


# ---------------------------------------------------------------------------
# descend / locate: multi-level fat-node descent -> leaf gather -> in-leaf
# slot (+ vhead gather).  DESIGN.md Sec 11.
# ---------------------------------------------------------------------------

@device_pass(static=("backend",))
def descend(index, queries, *, backend: str):
    """Root->leaf blocked F-way descent over ``repro.core.index``.

    Returns (bottom_node, bottom_slot, leaf_id) of the last separator
    <= q.  Trace-time dispatch: ``backend`` must be static.
    """
    if backend == XLA:
        from repro.core import index as _index

        return _index.descend(index, queries)
    from repro.kernels.uruv_search.uruv_search import index_descend

    return index_descend(
        index.node_keys, index.node_child, queries,
        interpret=(backend == PALLAS_INTERPRET),
    )


@device_pass(static=("backend",))
def locate(index, leaf_keys, leaf_vhead, queries, *, backend: str):
    """Full traversal: returns (bnode, bslot, leaf_id, slot, exists,
    vhead).  ``(bnode, bslot)`` is the bottom index entry covering the
    query (the structural delta's grouping key); ``vhead`` is -1 where
    the key is absent.  Trace-time dispatch: ``backend`` must be static.
    """
    L = leaf_keys.shape[1]
    bnode, bslot, leaf_id = descend(index, queries, backend=backend)
    rows = leaf_keys[leaf_id]                              # [P, L]
    if backend == XLA:
        slot = jnp.sum(rows < queries[:, None], axis=1).astype(jnp.int32)
        hit = jnp.take_along_axis(
            rows, jnp.minimum(slot, L - 1)[:, None], axis=1
        )[:, 0]
        exists = (slot < L) & (hit == queries)
    else:
        from repro.kernels.uruv_search.uruv_search import leaf_slots

        slot, exists = leaf_slots(rows, queries,
                                  interpret=(backend == PALLAS_INTERPRET))
    vhead = jnp.where(
        exists,
        jnp.take_along_axis(
            leaf_vhead[leaf_id], jnp.minimum(slot, L - 1)[:, None], axis=1
        )[:, 0],
        -1,
    )
    return bnode, bslot, leaf_id, slot, exists, vhead


# ---------------------------------------------------------------------------
# resolve: first version with ts <= snap (the paper's read()/vCAS path)
# ---------------------------------------------------------------------------

@device_pass(static=("max_chain", "backend"))
def resolve(vhead, snap_ts, ver_ts, ver_next, ver_value, *, max_chain: int,
            backend: str):
    """Versioned read over the chain pool; snap_ts broadcasts to vhead."""
    snap_ts = jnp.broadcast_to(jnp.asarray(snap_ts, jnp.int32), vhead.shape)
    if backend != XLA:
        from repro.kernels.versioned_read.versioned_read import versioned_read

        return versioned_read(
            vhead, snap_ts, ver_ts, ver_next, ver_value,
            max_chain=max_chain, interpret=(backend == PALLAS_INTERPRET),
        )

    def body(state):
        cur, steps = state
        ts_cur = jnp.where(cur >= 0, ver_ts[jnp.maximum(cur, 0)], 0)
        advance = (cur >= 0) & (ts_cur > snap_ts)
        nxt = jnp.where(advance, ver_next[jnp.maximum(cur, 0)], cur)
        return nxt, steps + 1

    def cond(state):
        cur, steps = state
        ts_cur = jnp.where(cur >= 0, ver_ts[jnp.maximum(cur, 0)], 0)
        return jnp.any((cur >= 0) & (ts_cur > snap_ts)) & (steps < max_chain)

    cur, _ = lax.while_loop(cond, body, (vhead, jnp.array(0, jnp.int32)))
    ok = cur >= 0
    ts_cur = jnp.where(ok, ver_ts[jnp.maximum(cur, 0)], 0)
    ok = ok & (ts_cur <= snap_ts)
    val = jnp.where(ok, ver_value[jnp.maximum(cur, 0)], NOT_FOUND)
    return jnp.where(val == TOMBSTONE, NOT_FOUND, val)


# ---------------------------------------------------------------------------
# range_scan: fused leaf-window gather + in-interval mask + versioned resolve
# (the candidate phase of store.bulk_range; paper Sec 3.4)
# ---------------------------------------------------------------------------

@device_pass(static=("max_chain", "backend"))
def range_scan(lids, pvalid, k1, k2, snap_ts, leaf_keys, leaf_vhead,
               leaf_count, ver_ts, ver_next, ver_value, *, max_chain: int,
               backend: str):
    """Candidate keys/values for Q leaf windows: (cand_keys, cand_vals) [Q, S*L].

    ``lids[q, s]`` is the s-th leaf of query q's scan window (``pvalid``
    masks non-participating slots).  Hits carry (key, value-at-snapshot);
    non-hits are (KEY_MAX, NOT_FOUND) — tombstones already dropped.
    Trace-time dispatch: call only where ``backend`` is static.
    """
    if backend != XLA:
        from repro.kernels.uruv_range.uruv_range import range_scan as _pallas_rs

        return _pallas_rs(
            lids, pvalid, k1, k2, snap_ts,
            leaf_keys, leaf_vhead, leaf_count, ver_ts, ver_next, ver_value,
            max_chain=max_chain, interpret=(backend == PALLAS_INTERPRET),
        )
    Q, S = lids.shape
    L = leaf_keys.shape[1]
    rows = leaf_keys[lids]                                 # [Q, S, L]
    vhs = leaf_vhead[lids]
    cnt = leaf_count[lids]
    slot_ok = jnp.arange(L, dtype=jnp.int32)[None, None, :] < cnt[..., None]
    cand = (
        pvalid[..., None] & slot_ok
        & (rows >= k1[:, None, None]) & (rows <= k2[:, None, None])
    )
    flat_vh = jnp.where(cand, vhs, -1).reshape(-1)
    snap = jnp.broadcast_to(snap_ts[:, None, None], cand.shape).reshape(-1)
    vals = resolve(flat_vh, snap, ver_ts, ver_next, ver_value,
                   max_chain=max_chain, backend=XLA).reshape(Q, S, L)
    hit = cand & (vals != NOT_FOUND)
    cand_keys = jnp.where(hit, rows, KEY_MAX).reshape(Q, S * L)
    cand_vals = jnp.where(hit, vals, NOT_FOUND).reshape(Q, S * L)
    return cand_keys, cand_vals
