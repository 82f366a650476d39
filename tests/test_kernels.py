"""Per-kernel interpret-mode sweeps vs pure-jnp oracles (shapes x dtypes)."""

import numpy as np
import jax.numpy as jnp
import pytest

from repro.core.ref import KEY_MAX, NOT_FOUND
from repro.kernels.uruv_search.uruv_search import leaf_slots, search_positions
from repro.kernels.uruv_search.ref import leaf_slots_ref, search_positions_ref
from repro.kernels.uruv_search.ops import locate
from repro.kernels.uruv_range.ops import range_scan
from repro.kernels.versioned_read.versioned_read import versioned_read
from repro.kernels.versioned_read.ref import versioned_read_ref
from repro.kernels.flash_attention.flash_attention import flash_attention
from repro.kernels.flash_attention.ref import attention_ref
from repro.kernels.decode_attention.decode_attention import decode_attention
from repro.kernels.decode_attention.ref import decode_attention_ref

RNG = np.random.default_rng(42)


@pytest.mark.parametrize("n_dir,n_q,bq,bd", [
    (64, 16, 8, 16), (1000, 333, 64, 128), (4096, 256, 256, 512),
    (7, 5, 8, 8),
])
def test_search_positions_sweep(n_dir, n_q, bq, bd):
    d = np.sort(RNG.choice(10**6, n_dir, replace=False)).astype(np.int32)
    d[0] = -(2**31)
    q = RNG.integers(-10, 10**6 + 10, n_q).astype(np.int32)
    got = search_positions(jnp.asarray(d), jnp.asarray(q),
                           block_q=bq, block_dir=bd, interpret=True)
    want = search_positions_ref(jnp.asarray(d), jnp.asarray(q))
    np.testing.assert_array_equal(got, want)


@pytest.mark.parametrize("fanout,n_sep,n_q,bq", [
    (4, 40, 64, 16), (8, 200, 333, 64), (16, 250, 64, 256),
])
def test_index_descend_sweep(fanout, n_sep, n_q, bq):
    """Blocked F-way multi-level descent kernel vs the pure-jnp oracle
    (and the flat searchsorted rank) across fanouts/depths."""
    from repro.core import index as I
    from repro.kernels.uruv_search.uruv_search import index_descend
    from repro.kernels.uruv_search.ref import index_descend_ref

    ML = 256
    seps = np.sort(RNG.choice(10**6, n_sep, replace=False)).astype(np.int32)
    seps[0] = -(2**31)
    pad_k = np.full(ML, KEY_MAX, np.int32)
    pad_k[:n_sep] = seps
    pad_l = np.full(ML, -1, np.int32)
    pad_l[:n_sep] = np.arange(n_sep, dtype=np.int32)
    idx = I.build(I.index_config(ML, fanout), ML, pad_k, pad_l,
                  jnp.asarray(n_sep, jnp.int32))
    q = np.concatenate([
        RNG.integers(-10, 10**6 + 10, n_q).astype(np.int32),
        seps[:8], seps[:8] + 1, np.array([KEY_MAX - 1], np.int32),
    ])
    got = index_descend(idx.node_keys, idx.node_child, jnp.asarray(q),
                        block_q=bq, interpret=True)
    want = index_descend_ref(idx.node_keys, idx.node_child, jnp.asarray(q))
    for g, w in zip(got, want):
        np.testing.assert_array_equal(np.asarray(g), np.asarray(w))
    # descent rank == flat searchsorted over the live separators
    ordgot = np.asarray(I.leaf_ordinal(idx, got[0], got[1]))
    ordwant = np.maximum(
        np.searchsorted(seps, q, side="right").astype(np.int32) - 1, 0)
    np.testing.assert_array_equal(ordgot, ordwant)


@pytest.mark.parametrize("P,L,bq", [(16, 8, 8), (100, 32, 32), (257, 16, 64)])
def test_leaf_slots_sweep(P, L, bq):
    rows = np.sort(RNG.integers(0, 500, (P, L)), axis=1).astype(np.int32)
    q = RNG.integers(0, 520, P).astype(np.int32)
    s1, e1 = leaf_slots(jnp.asarray(rows), jnp.asarray(q), block_q=bq,
                        interpret=True)
    s2, e2 = leaf_slots_ref(jnp.asarray(rows), jnp.asarray(q))
    np.testing.assert_array_equal(s1, s2)
    np.testing.assert_array_equal(e1, e2)


def test_locate_end_to_end_matches_store():
    from repro.core import store as S
    from repro.core import batch as B

    st = S.create(S.UruvConfig(leaf_cap=8, max_leaves=128, max_versions=4096))
    keys = RNG.choice(1000, 100, replace=False).astype(np.int32)
    for i in range(0, 100, 16):
        st, _ = B.apply_updates(st, keys[i:i+16], keys[i:i+16])
    q = RNG.integers(0, 1100, 64).astype(np.int32)
    bnode, bslot, leaf, slot, exists = locate(
        st.index.node_keys, st.index.node_child, st.leaf_keys,
        jnp.asarray(q), use_pallas=True, interpret=True)
    vals = np.where(np.asarray(exists),
                    np.asarray(q), -1)
    live = dict(S.live_items(st))
    for k, e in zip(q.tolist(), np.asarray(exists).tolist()):
        assert e == (k in live)


@pytest.mark.parametrize("MV,P,chain", [(128, 64, 4), (1024, 200, 16)])
def test_versioned_read_sweep(MV, P, chain):
    ts = RNG.integers(0, 50, MV).astype(np.int32)
    nxt = RNG.integers(-1, MV, MV).astype(np.int32)
    val = RNG.integers(0, 99, MV).astype(np.int32)
    vh = RNG.integers(-1, MV, P).astype(np.int32)
    snap = RNG.integers(0, 50, P).astype(np.int32)
    a = versioned_read(jnp.asarray(vh), jnp.asarray(snap), jnp.asarray(ts),
                       jnp.asarray(nxt), jnp.asarray(val),
                       max_chain=chain, block_q=64, interpret=True)
    b = versioned_read_ref(jnp.asarray(vh), jnp.asarray(snap),
                           jnp.asarray(ts), jnp.asarray(nxt),
                           jnp.asarray(val), max_chain=chain)
    np.testing.assert_array_equal(a, b)


@pytest.mark.parametrize("Q,Sw,ML,L,MV,chain,bq", [
    (16, 2, 64, 8, 256, 4, 8),
    (100, 4, 128, 16, 1024, 8, 32),
    (257, 3, 64, 8, 512, 16, 64),
])
def test_range_scan_kernel_sweep(Q, Sw, ML, L, MV, chain, bq):
    """uruv_range pallas (interpret) vs the pure-jnp oracle on random
    pools: same candidate keys AND snapshot-resolved values."""
    lkeys = np.sort(RNG.integers(0, 1000, (ML, L)), axis=1).astype(np.int32)
    lvh = RNG.integers(-1, MV, (ML, L)).astype(np.int32)
    lcnt = RNG.integers(0, L + 1, ML).astype(np.int32)
    vts = RNG.integers(0, 60, MV).astype(np.int32)
    vnxt = RNG.integers(-1, MV, MV).astype(np.int32)
    vval = RNG.integers(-2, 99, MV).astype(np.int32)   # includes NOT_FOUND-ish
    lids = RNG.integers(0, ML, (Q, Sw)).astype(np.int32)
    pvalid = RNG.random((Q, Sw)) < 0.8
    k1 = RNG.integers(0, 1000, Q).astype(np.int32)
    k2 = (k1 + RNG.integers(-50, 400, Q)).astype(np.int32)  # some inverted
    snap = RNG.integers(0, 60, Q).astype(np.int32)
    args = (jnp.asarray(lids), jnp.asarray(pvalid), jnp.asarray(k1),
            jnp.asarray(k2), jnp.asarray(snap), jnp.asarray(lkeys),
            jnp.asarray(lvh), jnp.asarray(lcnt), jnp.asarray(vts),
            jnp.asarray(vnxt), jnp.asarray(vval))
    gk, gv = range_scan(*args, max_chain=chain, block_q=bq, use_pallas=True,
                        interpret=True)
    wk, wv = range_scan(*args, max_chain=chain, use_pallas=False)
    np.testing.assert_array_equal(gk, wk)
    np.testing.assert_array_equal(gv, wv)


def test_bulk_range_backend_parity_end_to_end():
    """store.bulk_range: pallas_interpret backend == xla backend on a real
    store (keys, values, counts, truncation flags, resume points)."""
    from repro.core import store as S
    from repro.core import batch as B

    st = S.create(S.UruvConfig(leaf_cap=8, max_leaves=128, max_versions=4096))
    keys = RNG.choice(500, 120, replace=False).astype(np.int32)
    for i in range(0, 120, 16):
        st, _ = B.apply_updates(st, keys[i:i+16], keys[i:i+16] % 97)
    ts = int(st.ts)
    k1 = RNG.integers(0, 500, 32).astype(np.int32)
    k2 = (k1 + RNG.integers(-20, 200, 32)).astype(np.int32)
    snap = np.full(32, ts, np.int32)
    a = S.bulk_range(st, k1, k2, snap, max_results=32, scan_leaves=2,
                     max_rounds=3, backend="xla")
    b = S.bulk_range(st, k1, k2, snap, max_results=32, scan_leaves=2,
                     max_rounds=3, backend="pallas_interpret")
    for x, y in zip(a, b):
        np.testing.assert_array_equal(np.asarray(x), np.asarray(y))


@pytest.mark.parametrize("B,H,KVH,S,D,causal,win,dtype", [
    (2, 4, 2, 96, 32, True, 0, np.float32),
    (1, 4, 1, 64, 16, False, 0, np.float32),
    (2, 8, 4, 80, 32, True, 24, np.float32),
    (1, 2, 2, 64, 64, True, 0, np.float32),
    (1, 4, 2, 64, 32, True, 16, "bfloat16"),
])
def test_flash_attention_sweep(B, H, KVH, S, D, causal, win, dtype):
    q = RNG.standard_normal((B, H, S, D)).astype(np.float32)
    k = RNG.standard_normal((B, KVH, S, D)).astype(np.float32)
    v = RNG.standard_normal((B, KVH, S, D)).astype(np.float32)
    if dtype == "bfloat16":
        q, k, v = (jnp.asarray(x, jnp.bfloat16) for x in (q, k, v))
        tol = 2e-2
    else:
        q, k, v = map(jnp.asarray, (q, k, v))
        tol = 2e-5
    a = flash_attention(q, k, v, causal=causal, window=win,
                        block_q=32, block_k=32, interpret=True)
    b = attention_ref(q, k, v, causal=causal, window=win)
    np.testing.assert_allclose(np.asarray(a, np.float32),
                               np.asarray(b, np.float32),
                               atol=tol, rtol=tol)


@pytest.mark.parametrize("B,H,KVH,S,D,bk", [
    (3, 8, 2, 100, 32, 32), (2, 4, 4, 64, 16, 16), (1, 8, 1, 130, 64, 64),
])
def test_decode_attention_sweep(B, H, KVH, S, D, bk):
    q = jnp.asarray(RNG.standard_normal((B, H, D)), jnp.float32)
    k = jnp.asarray(RNG.standard_normal((B, KVH, S, D)), jnp.float32)
    v = jnp.asarray(RNG.standard_normal((B, KVH, S, D)), jnp.float32)
    lens = jnp.asarray(RNG.integers(1, S + 1, B), jnp.int32)
    a = decode_attention(q, k, v, lens, block_k=bk, interpret=True)
    b = decode_attention_ref(q, k, v, lens)
    np.testing.assert_allclose(a, b, atol=2e-5, rtol=2e-5)


def test_decode_attention_partial_stats_combine():
    """Sequence-sharded decode: combining per-shard (m, l, acc) equals the
    unsharded result — the long-context distribution path."""
    B, H, KVH, S, D = 2, 4, 2, 64, 16
    q = jnp.asarray(RNG.standard_normal((B, H, D)), jnp.float32)
    k = jnp.asarray(RNG.standard_normal((B, KVH, S, D)), jnp.float32)
    v = jnp.asarray(RNG.standard_normal((B, KVH, S, D)), jnp.float32)
    lens = jnp.full((B,), S, jnp.int32)
    full = decode_attention_ref(q, k, v, lens)
    halves = []
    for sl in (slice(0, S // 2), slice(S // 2, S)):
        o, m, l = decode_attention(
            q, k[:, :, sl], v[:, :, sl],
            jnp.full((B,), sl.stop - sl.start, jnp.int32),
            block_k=16, return_stats=True, interpret=True)
        halves.append((np.asarray(o, np.float64), np.asarray(m, np.float64),
                       np.asarray(l, np.float64)))
    (o1, m1, l1), (o2, m2, l2) = halves
    m = np.maximum(m1, m2)
    l = l1 * np.exp(m1 - m) + l2 * np.exp(m2 - m)
    o = (o1 * (l1 * np.exp(m1 - m)) + o2 * (l2 * np.exp(m2 - m))) / l
    np.testing.assert_allclose(o, np.asarray(full, np.float64),
                               atol=1e-5, rtol=1e-5)
