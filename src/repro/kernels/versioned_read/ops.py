"""Public wrapper: Pallas kernel with XLA-oracle fallback."""

from __future__ import annotations

import functools

import jax

from repro.kernels.versioned_read.versioned_read import versioned_read
from repro.kernels.versioned_read.ref import versioned_read_ref

from repro.analysis.marks import device_pass


@device_pass(static=("max_chain", "use_pallas", "interpret"))
@functools.partial(
    jax.jit, static_argnames=("max_chain", "use_pallas", "interpret")
)
def resolve(
    vhead, snap_ts, ver_ts, ver_next, ver_value,
    *, max_chain: int = 16, use_pallas: bool = True, interpret: bool = False,
):
    if use_pallas:
        return versioned_read(
            vhead, snap_ts, ver_ts, ver_next, ver_value,
            max_chain=max_chain, interpret=interpret,
        )
    return versioned_read_ref(
        vhead, snap_ts, ver_ts, ver_next, ver_value, max_chain=max_chain
    )
