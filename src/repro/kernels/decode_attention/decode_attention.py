"""Pallas TPU kernel: flash-decode — one query token vs a long KV cache.

Decode attention is memory-bound: per step the whole KV cache streams
HBM->VMEM once while compute is O(S·D) MACs per head.  The kernel therefore
optimizes for pure streaming:

  grid = (batch, kv_heads, Skv/BK)

with the (G, D) grouped-query tile (G = H/KVH q-heads sharing one kv head)
resident in VMEM scratch across the KV loop, online-softmax accumulation,
and per-sequence KV length masking (continuous batching serves ragged
cache lengths — lengths come from the Uruv page table, see repro.serve).

The same kernel is the shard-local body of the sequence-parallel decode
path: shards compute partial (m, l, acc) over their KV slice and the
combine is an all-reduce of rescaled partials (repro.models.attention).
This kernel returns (out, m, l) so the combine can be fused downstream.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.analysis.marks import device_pass

NEG_INF = -1e30


def _decode_kernel(
    len_ref, q_ref, k_ref, v_ref, o_ref, m_out_ref, l_out_ref,
    m_ref, l_ref, acc_ref, *, block_k, scale,
):
    ik = pl.program_id(2)

    @pl.when(ik == 0)
    def _init():
        m_ref[...] = jnp.full_like(m_ref, NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)
        acc_ref[...] = jnp.zeros_like(acc_ref)

    k_start = ik * block_k
    kv_len = len_ref[0]
    relevant = k_start < kv_len

    @pl.when(relevant)
    def _step():
        q = q_ref[0, 0].astype(jnp.float32) * scale          # [G, D]
        k = k_ref[0, 0].astype(jnp.float32)                  # [BK, D]
        v = v_ref[0, 0].astype(jnp.float32)                  # [BK, D]
        s = jax.lax.dot_general(
            q, k, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32,
        )                                                     # [G, BK]
        ki = k_start + jax.lax.broadcasted_iota(jnp.int32, s.shape, 1)
        mask = ki < kv_len
        s = jnp.where(mask, s, NEG_INF)
        m_prev = m_ref[...]
        m_new = jnp.maximum(m_prev, s.max(axis=1, keepdims=True))
        alpha = jnp.exp(m_prev - m_new)
        p = jnp.where(mask, jnp.exp(s - m_new), 0.0)
        l_ref[...] = l_ref[...] * alpha + p.sum(axis=1, keepdims=True)
        acc_ref[...] = acc_ref[...] * alpha + jax.lax.dot_general(
            p, v, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32,
        )
        m_ref[...] = m_new

    @pl.when(ik == pl.num_programs(2) - 1)
    def _flush():
        l = l_ref[...]
        safe = jnp.where(l == 0.0, 1.0, l)
        o_ref[0, 0] = (acc_ref[...] / safe).astype(o_ref.dtype)
        m_out_ref[0, 0] = m_ref[...]
        l_out_ref[0, 0] = l_ref[...]


@device_pass(static=("block_k", "interpret", "return_stats"))
@functools.partial(
    jax.jit, static_argnames=("block_k", "interpret", "return_stats")
)
def decode_attention(
    q: jax.Array,        # [B, H, D]   one new token per sequence
    k: jax.Array,        # [B, KVH, S, D]
    v: jax.Array,        # [B, KVH, S, D]
    lengths: jax.Array,  # [B] int32 — valid cache length per sequence
    *,
    block_k: int = 512,
    interpret: bool = False,
    return_stats: bool = False,
):
    B, H, D = q.shape
    _, KVH, S, _ = k.shape
    assert H % KVH == 0
    G = H // KVH
    scale = 1.0 / (D ** 0.5)
    qg = q.reshape(B, KVH, G, D)

    bk = min(block_k, S)
    pad_k = (-S) % bk
    kp = jnp.pad(k, ((0, 0), (0, 0), (0, pad_k), (0, 0)))
    vp = jnp.pad(v, ((0, 0), (0, 0), (0, pad_k), (0, 0)))

    out, m, l = pl.pallas_call(
        functools.partial(_decode_kernel, block_k=bk, scale=scale),
        grid=(B, KVH, (S + pad_k) // bk),
        in_specs=[
            pl.BlockSpec((1,), lambda b, h, j: (b,)),
            pl.BlockSpec((1, 1, G, D), lambda b, h, j: (b, h, 0, 0)),
            pl.BlockSpec((1, 1, bk, D), lambda b, h, j: (b, h, j, 0)),
            pl.BlockSpec((1, 1, bk, D), lambda b, h, j: (b, h, j, 0)),
        ],
        out_specs=[
            pl.BlockSpec((1, 1, G, D), lambda b, h, j: (b, h, 0, 0)),
            pl.BlockSpec((1, 1, G, 1), lambda b, h, j: (b, h, 0, 0)),
            pl.BlockSpec((1, 1, G, 1), lambda b, h, j: (b, h, 0, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((B, KVH, G, D), q.dtype),
            jax.ShapeDtypeStruct((B, KVH, G, 1), jnp.float32),
            jax.ShapeDtypeStruct((B, KVH, G, 1), jnp.float32),
        ],
        scratch_shapes=[
            pltpu.VMEM((G, 1), jnp.float32),
            pltpu.VMEM((G, 1), jnp.float32),
            pltpu.VMEM((G, D), jnp.float32),
        ],
        interpret=interpret,
    )(lengths.astype(jnp.int32), qg, kp, vp)
    out = out.reshape(B, H, D)
    if return_stats:
        return out, m.reshape(B, H, 1), l.reshape(B, H, 1)
    return out
