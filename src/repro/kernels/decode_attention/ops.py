"""Public decode-attention entry point: Pallas kernel or XLA oracle."""

from __future__ import annotations

import functools

import jax

from repro.kernels.decode_attention.decode_attention import decode_attention
from repro.kernels.decode_attention.ref import decode_attention_ref

from repro.analysis.marks import device_pass


@device_pass(static=("use_pallas", "interpret", "block_k"))
@functools.partial(
    jax.jit, static_argnames=("use_pallas", "interpret", "block_k")
)
def decode(q, k, v, lengths, *, use_pallas=False, interpret=False, block_k=512):
    if use_pallas:
        return decode_attention(
            q, k, v, lengths, block_k=block_k, interpret=interpret
        )
    return decode_attention_ref(q, k, v, lengths)
