"""Pallas TPU kernel: blockwise (flash) attention for prefill/training.

Online-softmax attention with explicit BlockSpec VMEM tiling:

  grid = (batch, q_heads, Sq/BQ, Skv/BK)

Per grid step a (BQ, D) query tile and a (BK, D) key/value tile live in
VMEM; the (BQ, BK) score tile hits the MXU; running max / sum / accumulator
stay in VMEM scratch across the KV loop (innermost grid axis).  Supports:

  * causal masking,
  * sliding-window (gemma3-style local) masking,
  * GQA — the kv head for q-head h is h // (H // KVH), applied in the
    k/v BlockSpec index maps (no KV replication in HBM),
  * KV-length masking for padded sequences (static pad amount).

Block shapes default to (128, 128): MXU-aligned (multiples of 128 on both
matmul dims) and, at D = 128, a comfortable VMEM footprint of
~(BQ + 2·BK)·D·2 B + (BQ·BK)·4 B ≈ 160 KiB per step.

Fully-masked KV tiles (beyond the causal frontier or the sliding window)
are skipped via pl.when — the dominant prefill win for local-attention
layers: work per q tile drops from O(Skv) to O(window).
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.analysis.marks import device_pass

NEG_INF = -1e30


def _flash_kernel(
    q_ref, k_ref, v_ref, o_ref, m_ref, l_ref, acc_ref,
    *, causal, window, kv_len, block_q, block_k, scale,
):
    iq = pl.program_id(2)
    ik = pl.program_id(3)

    @pl.when(ik == 0)
    def _init():
        m_ref[...] = jnp.full_like(m_ref, NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)
        acc_ref[...] = jnp.zeros_like(acc_ref)

    q_start = iq * block_q
    k_start = ik * block_k
    # tile-level skip: strictly above causal diagonal or below window floor
    relevant = jnp.asarray(True)
    if causal:
        relevant &= k_start <= q_start + block_q - 1
    if window > 0:
        relevant &= k_start + block_k - 1 >= q_start - window + 1
    relevant &= k_start < kv_len

    @pl.when(relevant)
    def _step():
        q = q_ref[0, 0].astype(jnp.float32) * scale        # [BQ, D]
        k = k_ref[0, 0].astype(jnp.float32)                # [BK, D]
        v = v_ref[0, 0].astype(jnp.float32)                # [BK, D]
        s = jax.lax.dot_general(
            q, k, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32,
        )                                                   # [BQ, BK]
        qi = q_start + jax.lax.broadcasted_iota(jnp.int32, s.shape, 0)
        ki = k_start + jax.lax.broadcasted_iota(jnp.int32, s.shape, 1)
        mask = ki < kv_len
        if causal:
            mask &= qi >= ki
        if window > 0:
            mask &= qi - ki < window
        s = jnp.where(mask, s, NEG_INF)

        m_prev = m_ref[...]                                 # [BQ, 1]
        m_new = jnp.maximum(m_prev, s.max(axis=1, keepdims=True))
        alpha = jnp.exp(m_prev - m_new)
        p = jnp.exp(s - m_new)
        p = jnp.where(mask, p, 0.0)
        l_ref[...] = l_ref[...] * alpha + p.sum(axis=1, keepdims=True)
        acc_ref[...] = acc_ref[...] * alpha + jax.lax.dot_general(
            p, v, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32,
        )
        m_ref[...] = m_new

    @pl.when(ik == pl.num_programs(3) - 1)
    def _flush():
        l = l_ref[...]
        safe = jnp.where(l == 0.0, 1.0, l)
        o_ref[0, 0] = (acc_ref[...] / safe).astype(o_ref.dtype)


@device_pass(static=("causal", "window", "block_q", "block_k", "interpret"))
@functools.partial(
    jax.jit,
    static_argnames=("causal", "window", "block_q", "block_k", "interpret"),
)
def flash_attention(
    q: jax.Array,       # [B, H, Sq, D]
    k: jax.Array,       # [B, KVH, Skv, D]
    v: jax.Array,       # [B, KVH, Skv, D]
    *,
    causal: bool = True,
    window: int = 0,    # 0 = full attention; >0 = sliding window size
    block_q: int = 128,
    block_k: int = 128,
    interpret: bool = False,
) -> jax.Array:
    B, H, Sq, D = q.shape
    _, KVH, Skv, _ = k.shape
    assert H % KVH == 0, "GQA requires H % KVH == 0"
    group = H // KVH
    scale = 1.0 / (D ** 0.5)

    bq = min(block_q, Sq)
    bk = min(block_k, Skv)
    pad_q = (-Sq) % bq
    pad_k = (-Skv) % bk
    qp = jnp.pad(q, ((0, 0), (0, 0), (0, pad_q), (0, 0)))
    kp = jnp.pad(k, ((0, 0), (0, 0), (0, pad_k), (0, 0)))
    vp = jnp.pad(v, ((0, 0), (0, 0), (0, pad_k), (0, 0)))

    out = pl.pallas_call(
        functools.partial(
            _flash_kernel,
            causal=causal, window=window, kv_len=Skv,
            block_q=bq, block_k=bk, scale=scale,
        ),
        grid=(B, H, (Sq + pad_q) // bq, (Skv + pad_k) // bk),
        in_specs=[
            pl.BlockSpec((1, 1, bq, D), lambda b, h, i, j: (b, h, i, 0)),
            pl.BlockSpec(
                (1, 1, bk, D),
                lambda b, h, i, j, group=group: (b, h // group, j, 0),
            ),
            pl.BlockSpec(
                (1, 1, bk, D),
                lambda b, h, i, j, group=group: (b, h // group, j, 0),
            ),
        ],
        out_specs=pl.BlockSpec((1, 1, bq, D), lambda b, h, i, j: (b, h, i, 0)),
        out_shape=jax.ShapeDtypeStruct((B, H, Sq + pad_q, D), q.dtype),
        scratch_shapes=[
            pltpu.VMEM((bq, 1), jnp.float32),
            pltpu.VMEM((bq, 1), jnp.float32),
            pltpu.VMEM((bq, D), jnp.float32),
        ],
        interpret=interpret,
    )(qp, kp, vp)
    return out[:, :, :Sq, :]
