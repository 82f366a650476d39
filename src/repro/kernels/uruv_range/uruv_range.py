"""Pallas TPU kernel: fused batched range scan (paper Sec 3.4 / Fig. 11).

One kernel answers the candidate phase of Q range queries at once: for each
query's window of leaf ids it gathers the leaf rows + version-chain heads,
masks the in-interval slots, and walks every candidate's version chain to
its snapshot — the leaf gather and ``versioned_read`` resolve of the
single-query path fused into one VMEM-resident pass.

Layout mirrors the other Uruv kernels (DESIGN.md Sec 7): the leaf pool
(``[ML, L]`` keys/vheads + ``[ML]`` counts) and the version pool
(ts/next/value) are pinned in VMEM while query tiles stream through, so a
chain step is a VMEM-latency gather instead of an HBM round-trip.  For the
default capacities that is ~1.3 MiB of tables — far under the ~16 MiB VMEM
budget.  The scan window loop (``scan_leaves``) and the chain walk
(``max_chain``) are static unrolls; compaction of hits into the per-query
result block stays in XLA (sort-based, see ``store.bulk_range``).

Hardware note (DESIGN.md Sec 7): Mosaic does not lower this kernel for
TPU v5e — the leaf-row gather ``lkeys[lid]`` is refused ("Shape mismatch
in input, indices and output"), and at the paper configuration the
whole-pool VMEM blocks alone exceed 20 MiB.  It runs in interpret mode
only; ref.py provides the pure-jnp oracle that the default ``xla``
backend serves.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from repro.core.ref import KEY_MAX, NOT_FOUND, TOMBSTONE

from repro.analysis.marks import device_pass


def _range_kernel(
    lids_ref, pvalid_ref, k1_ref, k2_ref, snap_ref,
    lkeys_ref, lvh_ref, lcnt_ref, ts_ref, nxt_ref, val_ref,
    okeys_ref, ovals_ref, *, max_chain, scan_leaves,
):
    k1 = k1_ref[...]                       # [BQ]
    k2 = k2_ref[...]
    snap = snap_ref[...]
    lkeys = lkeys_ref[...]                 # [ML, L]   (VMEM resident)
    lvh = lvh_ref[...]
    lcnt = lcnt_ref[...]                   # [ML]
    ts_tab = ts_ref[...]                   # [MV]
    nxt_tab = nxt_ref[...]
    val_tab = val_ref[...]
    L = lkeys.shape[1]
    for s in range(scan_leaves):
        lid = lids_ref[:, s]               # [BQ] leaf ids for window slot s
        pv = pvalid_ref[:, s] != 0
        rows = lkeys[lid]                  # [BQ, L] leaf gather
        vhs = lvh[lid]
        cnt = lcnt[lid]
        slot_ok = jax.lax.broadcasted_iota(jnp.int32, rows.shape, 1) < cnt[:, None]
        cand = (
            pv[:, None] & slot_ok
            & (rows >= k1[:, None]) & (rows <= k2[:, None])
        )
        # fused versioned read: first version with ts <= snap per candidate
        cur = jnp.where(cand, vhs, -1)
        for _ in range(max_chain):
            safe = jnp.maximum(cur, 0)
            adv = (cur >= 0) & (ts_tab[safe] > snap[:, None])
            cur = jnp.where(adv, nxt_tab[safe], cur)
        safe = jnp.maximum(cur, 0)
        ok = (cur >= 0) & (ts_tab[safe] <= snap[:, None])
        val = jnp.where(ok, val_tab[safe], NOT_FOUND)
        val = jnp.where(val == TOMBSTONE, NOT_FOUND, val)
        hit = cand & (val != NOT_FOUND)
        okeys_ref[:, s * L:(s + 1) * L] = jnp.where(hit, rows, KEY_MAX)
        ovals_ref[:, s * L:(s + 1) * L] = jnp.where(hit, val, NOT_FOUND)


@device_pass(static=("max_chain", "block_q", "interpret"))
@functools.partial(
    jax.jit, static_argnames=("max_chain", "block_q", "interpret")
)
def range_scan(
    lids: jax.Array,       # int32 [Q, S]  leaf ids per query window slot
    pvalid: jax.Array,     # bool  [Q, S]  window slot participates
    k1: jax.Array,         # int32 [Q]
    k2: jax.Array,         # int32 [Q]
    snap_ts: jax.Array,    # int32 [Q]
    leaf_keys: jax.Array,  # int32 [ML, L]
    leaf_vhead: jax.Array,  # int32 [ML, L]
    leaf_count: jax.Array,  # int32 [ML]
    ver_ts: jax.Array,     # int32 [MV]
    ver_next: jax.Array,   # int32 [MV]
    ver_value: jax.Array,  # int32 [MV]
    *,
    max_chain: int = 16,
    block_q: int = 128,
    interpret: bool = False,
):
    """Candidate phase of Q range queries: (cand_keys, cand_vals) [Q, S*L].

    Non-hits are (KEY_MAX, NOT_FOUND); hits carry the key and its value
    resolved at the query's snapshot (tombstones already dropped).
    """
    Q, S = lids.shape
    ML, L = leaf_keys.shape
    MV = ver_ts.shape[0]
    bq = min(block_q, Q)
    pad = (-Q) % bq
    lids_p = jnp.pad(lids, ((0, pad), (0, 0)))
    pv_p = jnp.pad(pvalid.astype(jnp.int32), ((0, pad), (0, 0)))
    k1_p = jnp.pad(k1, (0, pad), constant_values=KEY_MAX - 1)
    k2_p = jnp.pad(k2, (0, pad))
    sn_p = jnp.pad(snap_ts, (0, pad))

    okeys, ovals = pl.pallas_call(
        functools.partial(_range_kernel, max_chain=max_chain, scan_leaves=S),
        grid=((Q + pad) // bq,),
        in_specs=[
            pl.BlockSpec((bq, S), lambda i: (i, 0)),
            pl.BlockSpec((bq, S), lambda i: (i, 0)),
            pl.BlockSpec((bq,), lambda i: (i,)),
            pl.BlockSpec((bq,), lambda i: (i,)),
            pl.BlockSpec((bq,), lambda i: (i,)),
            pl.BlockSpec((ML, L), lambda i: (0, 0)),
            pl.BlockSpec((ML, L), lambda i: (0, 0)),
            pl.BlockSpec((ML,), lambda i: (0,)),
            pl.BlockSpec((MV,), lambda i: (0,)),
            pl.BlockSpec((MV,), lambda i: (0,)),
            pl.BlockSpec((MV,), lambda i: (0,)),
        ],
        out_specs=[
            pl.BlockSpec((bq, S * L), lambda i: (i, 0)),
            pl.BlockSpec((bq, S * L), lambda i: (i, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((Q + pad, S * L), jnp.int32),
            jax.ShapeDtypeStruct((Q + pad, S * L), jnp.int32),
        ],
        interpret=interpret,
    )(lids_p, pv_p, k1_p, k2_p, sn_p,
      leaf_keys, leaf_vhead, leaf_count, ver_ts, ver_next, ver_value)
    return okeys[:Q], ovals[:Q]
