"""Pallas TPU kernel: batched B+-tree descent (the paper's traversal).

The hot loop of every Uruv ADT op is the root->leaf descent (paper Fig. 1,
line 18: "binary search over curr's keys").  In the TPU-native store the
internal index is the sorted leaf directory; locating a key is computing its
*rank* in that directory.  A pointer-chasing binary search is hostile to the
TPU (serial, scalar); the roofline-optimal formulation is a **blocked
compare-reduce**:

    pos(q) = (# directory keys <= q) - 1

streamed over directory tiles held in VMEM while a tile of queries sits in
VREGs — O(P·ML) cheap VPU compares, perfectly vectorized, directory read
from HBM exactly once per query block.  For ML = 4096 int32 separators a
whole directory tile burst is 16 KiB — far under the ~16 MiB VMEM budget, so
the kernel is compute-light and bandwidth-bound on the query stream, which
is the right trade at the leaf counts Uruv serves (see DESIGN.md Sec 7).

A second tiny kernel computes the in-leaf slot (rank within a gathered leaf
row) for the batch — the paper's in-leaf linear search, vectorized.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.core.ref import KEY_MAX

from repro.analysis.marks import device_pass


def _search_kernel(dir_ref, q_ref, pos_ref, acc_ref):
    j = pl.program_id(1)

    @pl.when(j == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    d = dir_ref[...]                      # [BD]  directory tile (VMEM)
    q = q_ref[...]                        # [BQ]  query tile
    # rank contribution of this directory tile
    acc_ref[...] += jnp.sum(
        (d[None, :] <= q[:, None]).astype(jnp.int32), axis=1
    )

    @pl.when(j == pl.num_programs(1) - 1)
    def _flush():
        pos_ref[...] = acc_ref[...] - 1


@device_pass(static=("block_q", "block_dir", "interpret"))
@functools.partial(
    jax.jit, static_argnames=("block_q", "block_dir", "interpret")
)
def search_positions(
    dir_keys: jax.Array,
    queries: jax.Array,
    *,
    block_q: int = 256,
    block_dir: int = 512,
    interpret: bool = False,
) -> jax.Array:
    """pos[i] = searchsorted(dir_keys, queries[i], side='right') - 1."""
    P = queries.shape[0]
    ML = dir_keys.shape[0]
    bq = min(block_q, P)
    bd = min(block_dir, ML)
    pad_p = (-P) % bq
    pad_d = (-ML) % bd
    q = jnp.pad(queries, (0, pad_p), constant_values=KEY_MAX - 1)
    d = jnp.pad(dir_keys, (0, pad_d), constant_values=KEY_MAX)

    pos = pl.pallas_call(
        _search_kernel,
        grid=((P + pad_p) // bq, (ML + pad_d) // bd),
        in_specs=[
            pl.BlockSpec((bd,), lambda i, j: (j,)),
            pl.BlockSpec((bq,), lambda i, j: (i,)),
        ],
        out_specs=pl.BlockSpec((bq,), lambda i, j: (i,)),
        out_shape=jax.ShapeDtypeStruct(((P + pad_p),), jnp.int32),
        scratch_shapes=[pltpu.VMEM((bq,), jnp.int32)],
        interpret=interpret,
    )(d, q)
    return jnp.maximum(pos[:P], 0)


def _index_descend_kernel(q_ref, *refs, depth):
    """Blocked F-way descent over the multi-level fat-node index.

    ``refs`` carries (keys_l, child_l) for l = depth-1 .. 0 followed by
    the three outputs.  Every level's node pool is VMEM-resident (the
    whole index is ~ML/F * F ints = O(ML) — a fraction of the leaf pool);
    a query tile descends all levels with one dynamic row gather + one
    F-wide compare-reduce per level: O(P * F * depth) VPU compares
    instead of the flat O(P * ML) rank of the directory era.
    """
    node_ref, slot_ref, leaf_ref = refs[2 * depth:]
    q = q_ref[...]                        # [BQ]
    cur = jnp.zeros_like(q)               # root is node 0
    slot = jnp.zeros_like(q)
    nxt = cur
    for i in range(depth):                # level l = depth-1-i
        keys = refs[2 * i][...]           # [C_l, F]
        child = refs[2 * i + 1][...]
        rows = keys[cur]                  # [BQ, F] dynamic row gather
        # live entries only (KEY_MAX = padding; q may be a KEY_MAX sentinel)
        slot = jnp.maximum(
            jnp.sum(((rows <= q[:, None]) & (rows < KEY_MAX))
                    .astype(jnp.int32), axis=1) - 1, 0)
        nxt = jnp.take_along_axis(child[cur], slot[:, None], axis=1)[:, 0]
        if i < depth - 1:
            cur = nxt
    node_ref[...] = cur
    slot_ref[...] = slot
    leaf_ref[...] = nxt


@device_pass(static=("block_q", "interpret"))
@functools.partial(jax.jit, static_argnames=("block_q", "interpret"))
def index_descend(
    level_keys,            # tuple l=0..D-1 of int32 [C_l, F]
    level_child,           # tuple l=0..D-1 of int32 [C_l, F]
    queries: jax.Array,
    *,
    block_q: int = 256,
    interpret: bool = False,
):
    """Root->leaf descent: returns (bottom_node, bottom_slot, leaf_id)
    of the last separator <= q — the kernel twin of
    ``repro.core.index.descend``."""
    depth = len(level_keys)
    P = queries.shape[0]
    bq = min(block_q, P)
    pad = (-P) % bq
    q = jnp.pad(queries, (0, pad), constant_values=KEY_MAX - 1)
    tables = []
    in_specs = [pl.BlockSpec((bq,), lambda i: (i,))]
    for l in range(depth - 1, -1, -1):
        for t in (level_keys[l], level_child[l]):
            tables.append(t)
            in_specs.append(pl.BlockSpec(t.shape, lambda i: (0, 0)))
    out = pl.pallas_call(
        functools.partial(_index_descend_kernel, depth=depth),
        grid=((P + pad) // bq,),
        in_specs=in_specs,
        out_specs=[pl.BlockSpec((bq,), lambda i: (i,))] * 3,
        out_shape=[jax.ShapeDtypeStruct((P + pad,), jnp.int32)] * 3,
        interpret=interpret,
    )(q, *tables)
    return out[0][:P], out[1][:P], out[2][:P]


def _slot_kernel(rows_ref, q_ref, slot_ref, exists_ref):
    rows = rows_ref[...]                  # [BQ, L]
    q = q_ref[...]                        # [BQ]
    slot = jnp.sum((rows < q[:, None]).astype(jnp.int32), axis=1)
    # membership as a compare-reduce: the key at ``slot`` equals q (an
    # in-kernel row gather does not lower on Mosaic); slot == L matches
    # no column, so a full leaf with every key < q reports absent
    col = jax.lax.broadcasted_iota(jnp.int32, rows.shape, 1)
    hit = (col == slot[:, None]) & (rows == q[:, None])
    slot_ref[...] = slot
    exists_ref[...] = jnp.max(hit.astype(jnp.int32), axis=1)


@device_pass(static=("block_q", "interpret"))
@functools.partial(jax.jit, static_argnames=("block_q", "interpret"))
def leaf_slots(
    rows: jax.Array,
    queries: jax.Array,
    *,
    block_q: int = 1024,
    interpret: bool = False,
):
    """In-leaf rank + membership for pre-gathered leaf rows [P, L].

    ``block_q`` defaults to 1024: a 1-D int32 block of a longer array
    must match XLA's T(1024) tiling on TPU."""
    P, L = rows.shape
    bq = min(block_q, P)
    pad = (-P) % bq
    rows_p = jnp.pad(rows, ((0, pad), (0, 0)), constant_values=KEY_MAX)
    q_p = jnp.pad(queries, (0, pad), constant_values=KEY_MAX - 1)
    slot, exists = pl.pallas_call(
        _slot_kernel,
        grid=((P + pad) // bq,),
        in_specs=[
            pl.BlockSpec((bq, L), lambda i: (i, 0)),
            pl.BlockSpec((bq,), lambda i: (i,)),
        ],
        out_specs=[
            pl.BlockSpec((bq,), lambda i: (i,)),
            pl.BlockSpec((bq,), lambda i: (i,)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((P + pad,), jnp.int32),
            jax.ShapeDtypeStruct((P + pad,), jnp.int32),
        ],
        interpret=interpret,
    )(rows_p, q_p)
    return slot[:P], exists[:P].astype(bool)
