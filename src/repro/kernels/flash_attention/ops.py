"""Public attention entry point: Pallas kernel or XLA oracle."""

from __future__ import annotations

import functools

import jax

from repro.analysis.marks import device_pass
from repro.kernels.flash_attention.flash_attention import flash_attention
from repro.kernels.flash_attention.ref import attention_ref


@device_pass(static=("causal", "window", "use_pallas", "interpret",
                     "block_q", "block_k"))
@functools.partial(
    jax.jit,
    static_argnames=("causal", "window", "use_pallas", "interpret",
                     "block_q", "block_k"),
)
def attention(
    q, k, v, *, causal=True, window=0,
    use_pallas=False, interpret=False, block_q=128, block_k=128,
):
    if use_pallas:
        return flash_attention(
            q, k, v, causal=causal, window=window,
            block_q=block_q, block_k=block_k, interpret=interpret,
        )
    return attention_ref(q, k, v, causal=causal, window=window)
