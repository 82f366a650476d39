"""Jitted public wrapper for the uruv_range kernel.

``range_scan()`` is the fused candidate-phase contract (leaf-window gather +
in-interval mask + versioned resolve), switchable between the Pallas path
and the pure-jnp oracle.  The store routes through
`repro.core.backend.range_scan` (xla | pallas | pallas_interpret, same
resolution as locate/resolve); this module remains the kernel-level entry
used by the interpret-mode parity sweeps (tests/test_kernels.py).
"""

from __future__ import annotations

import functools

import jax

from repro.kernels.uruv_range.ref import range_scan_ref
from repro.kernels.uruv_range.uruv_range import range_scan as range_scan_pallas

from repro.analysis.marks import device_pass


@device_pass(static=("max_chain", "block_q", "use_pallas", "interpret"))
@functools.partial(
    jax.jit, static_argnames=("max_chain", "block_q", "use_pallas", "interpret")
)
def range_scan(
    lids, pvalid, k1, k2, snap_ts,
    leaf_keys, leaf_vhead, leaf_count, ver_ts, ver_next, ver_value,
    *, max_chain: int = 16, block_q: int = 128, use_pallas: bool = True,
    interpret: bool = False,
):
    """(cand_keys, cand_vals) [Q, S*L]; non-hits are (KEY_MAX, NOT_FOUND)."""
    if use_pallas:
        return range_scan_pallas(
            lids, pvalid, k1, k2, snap_ts,
            leaf_keys, leaf_vhead, leaf_count, ver_ts, ver_next, ver_value,
            max_chain=max_chain, block_q=block_q, interpret=interpret,
        )
    return range_scan_ref(
        lids, pvalid, k1, k2, snap_ts,
        leaf_keys, leaf_vhead, leaf_count, ver_ts, ver_next, ver_value,
        max_chain=max_chain,
    )
