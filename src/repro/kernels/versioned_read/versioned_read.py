"""Pallas TPU kernel: snapshot version resolution (the paper's read()/vCAS).

Per query key, walk its descending-ts version chain until the first version
with ``ts <= snap`` (paper Sec 3.4 RANGEQUERY / Appendix D read).  Chains are
short by construction (compact() bounds retention), so the walk is a fixed
``max_chain`` unroll of *vectorized gathers*: the whole version pool
(ts/next/value, 12 B per entry — 768 KiB at the default 64 Ki entries) is
pinned in VMEM while query tiles stream through, so every chain step is a
VMEM-latency gather instead of an HBM round-trip.  That is the TPU analogue
of the paper's pointer walk staying in L1/L2.

Hardware note (DESIGN.md Sec 7): Mosaic does not lower this kernel for
TPU v5e — the 1-D table gather ``ts_tab[safe]`` is refused with "Only 2D
gather is supported".  It runs in interpret mode only; the store's
default ``xla`` backend is the chain walk that compiles for the chip, and
ops.py exposes the XLA-gather oracle.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from repro.core.ref import NOT_FOUND, TOMBSTONE

from repro.analysis.marks import device_pass


def _vread_kernel(vh_ref, snap_ref, ts_ref, nxt_ref, val_ref, out_ref, *, max_chain):
    cur = vh_ref[...]                       # [BQ]
    snap = snap_ref[...]                    # [BQ]
    ts_tab = ts_ref[...]                    # [MV] (VMEM resident)
    nxt_tab = nxt_ref[...]
    val_tab = val_ref[...]
    for _ in range(max_chain):
        safe = jnp.maximum(cur, 0)
        ts_c = ts_tab[safe]
        adv = (cur >= 0) & (ts_c > snap)
        cur = jnp.where(adv, nxt_tab[safe], cur)
    safe = jnp.maximum(cur, 0)
    ok = (cur >= 0) & (ts_tab[safe] <= snap)
    val = jnp.where(ok, val_tab[safe], NOT_FOUND)
    out_ref[...] = jnp.where(val == TOMBSTONE, NOT_FOUND, val)


@device_pass(static=("max_chain", "block_q", "interpret"))
@functools.partial(
    jax.jit, static_argnames=("max_chain", "block_q", "interpret")
)
def versioned_read(
    vhead: jax.Array,
    snap_ts: jax.Array,
    ver_ts: jax.Array,
    ver_next: jax.Array,
    ver_value: jax.Array,
    *,
    max_chain: int = 16,
    block_q: int = 256,
    interpret: bool = False,
) -> jax.Array:
    P = vhead.shape[0]
    MV = ver_ts.shape[0]
    bq = min(block_q, P)
    pad = (-P) % bq
    vh = jnp.pad(vhead, (0, pad), constant_values=-1)
    sn = jnp.pad(jnp.broadcast_to(snap_ts, vhead.shape), (0, pad))
    out = pl.pallas_call(
        functools.partial(_vread_kernel, max_chain=max_chain),
        grid=((P + pad) // bq,),
        in_specs=[
            pl.BlockSpec((bq,), lambda i: (i,)),
            pl.BlockSpec((bq,), lambda i: (i,)),
            pl.BlockSpec((MV,), lambda i: (0,)),
            pl.BlockSpec((MV,), lambda i: (0,)),
            pl.BlockSpec((MV,), lambda i: (0,)),
        ],
        out_specs=pl.BlockSpec((bq,), lambda i: (i,)),
        out_shape=jax.ShapeDtypeStruct((P + pad,), jnp.int32),
        interpret=interpret,
    )(vh, sn, ver_ts, ver_next, ver_value)
    return out[:P]
