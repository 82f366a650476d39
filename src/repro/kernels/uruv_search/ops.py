"""Jitted public wrappers for the uruv_search kernels.

``locate()`` is the full traversal contract (multi-level fat-node descent
-> leaf gather -> in-leaf slot), switchable between the Pallas path and
the XLA oracle.  The store routes through `repro.core.backend.locate`
(``xla`` unless `URUV_BACKEND` or `set_backend` picks a Pallas backend);
this module remains the kernel-level entry used by the interpret-mode
sweeps (see DESIGN.md Sec 7 / Sec 11).
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from repro.kernels.uruv_search.uruv_search import index_descend, leaf_slots
from repro.kernels.uruv_search.ref import index_descend_ref, leaf_slots_ref

from repro.analysis.marks import device_pass


@device_pass(static=("use_pallas", "interpret"))
@functools.partial(jax.jit, static_argnames=("use_pallas", "interpret"))
def locate(
    level_keys,            # tuple l=0..D-1 of int32 [C_l, F] (bottom first)
    level_child,           # tuple l=0..D-1 of int32 [C_l, F]
    leaf_keys: jax.Array,
    queries: jax.Array,
    *,
    use_pallas: bool = True,
    interpret: bool = False,
):
    """Returns (bottom_node, bottom_slot, leaf_id, slot, exists)."""
    if use_pallas:
        bnode, bslot, leaf_id = index_descend(
            tuple(level_keys), tuple(level_child), queries,
            interpret=interpret)
    else:
        bnode, bslot, leaf_id = index_descend_ref(
            tuple(level_keys), tuple(level_child), queries)
    rows = leaf_keys[leaf_id]
    if use_pallas:
        slot, exists = leaf_slots(rows, queries, interpret=interpret)
    else:
        slot, exists = leaf_slots_ref(rows, queries)
    return bnode, bslot, leaf_id, slot, exists
