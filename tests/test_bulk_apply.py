"""bulk_apply — the fused mixed-op pass (DESIGN.md Sec 3).

Linearization equivalence against the sequential oracle, per-op timestamp
plumbing, fast-path single-device-pass guarantee, backend dispatch, and
sharded-vs-single-device equivalence (results AND version timestamps).
"""

import subprocess
import sys
from pathlib import Path

import numpy as np
import jax.numpy as jnp
import pytest

from repro.core import backend as BK
from repro.core import batch as B
from repro.core import store as S
from repro.core.ref import (
    KEY_MAX, NOT_FOUND, TOMBSTONE, OP_DELETE, OP_INSERT, OP_NOP, OP_SEARCH,
    RefStore,
)

CFG = S.UruvConfig(leaf_cap=8, max_leaves=512, max_versions=1 << 14,
                   max_chain=16)


def random_ops(rng, n, key_hi=70):
    codes = rng.choice(
        [OP_INSERT, OP_INSERT, OP_DELETE, OP_SEARCH, OP_SEARCH, OP_NOP], n
    ).astype(np.int32)
    keys = rng.integers(0, key_hi, n).astype(np.int32)
    vals = rng.integers(0, 1000, n).astype(np.int32)
    return codes, keys, vals


def test_mixed_announce_vs_oracle_deterministic():
    """Interleaved SEARCH/INSERT/DELETE with duplicate keys, announce order."""
    st = S.create(CFG)
    ref = RefStore()
    ops = [
        (OP_SEARCH, 5, 0),          # absent
        (OP_INSERT, 5, 10),
        (OP_SEARCH, 5, 0),          # sees 10 (in-batch predecessor)
        (OP_INSERT, 5, 20),
        (OP_DELETE, 5, 0),
        (OP_SEARCH, 5, 0),          # sees tombstone -> NOT_FOUND
        (OP_INSERT, 7, 70),
        (OP_SEARCH, 7, 0),
        (OP_NOP, 99, 1),
        (OP_INSERT, 5, 30),
        (OP_SEARCH, 5, 0),          # sees 30
    ]
    st, res = B.apply_batch(st, ops)
    assert res == ref.apply_batch(ops)
    assert int(st.ts) == ref.ts
    S.check_invariants(st)
    assert S.live_items(st) == ref.live_items()


def test_search_past_long_in_batch_chain():
    """A search after > max_chain same-key updates is exact (predecessor
    short-circuit, not a bounded chain walk)."""
    st = S.create(CFG)
    ref = RefStore()
    ops = [(OP_INSERT, 3, i) for i in range(CFG.max_chain + 10)]
    ops.append((OP_SEARCH, 3, 0))
    st, res = B.apply_batch(st, ops)
    assert res == ref.apply_batch(ops)
    assert res[-1] == CFG.max_chain + 9


@pytest.mark.parametrize("width", [1, 2, 3, 7, 16, 33, 64])
def test_width_sweep_vs_oracle(width):
    rng = np.random.default_rng(width)
    st = S.create(CFG)
    ref = RefStore()
    for it in range(6):
        codes, keys, vals = random_ops(rng, width)
        ops = [(int(c), int(k), int(v)) for c, k, v in zip(codes, keys, vals)]
        st, res = B.apply_batch(st, ops)
        assert res == ref.apply_batch(ops), (width, it)
        assert int(st.ts) == ref.ts
    S.check_invariants(st)
    assert S.live_items(st) == ref.live_items()


def test_explicit_op_ts_subset_application():
    """Applying a routed subset with explicit global timestamps equals the
    full-array application (the sharded-store contract)."""
    full_st = S.create(CFG)
    sub_st = S.create(CFG)
    rng = np.random.default_rng(0)
    codes, keys, vals = random_ops(rng, 24, key_hi=40)
    full_st, full_res, ok = S.bulk_apply(full_st, codes, keys, vals)
    assert bool(ok)
    # split by key parity into two "shards", apply each subset with its ops'
    # original announce positions as op_ts
    n = len(keys)
    for parity in (0, 1):
        mask = (keys % 2) == parity
        c = np.where(mask, codes, OP_NOP).astype(np.int32)
        k = np.where(mask, keys, KEY_MAX).astype(np.int32)
        sub_st, sub_res, ok = S.bulk_apply(
            sub_st, c, k, vals,
            op_ts=jnp.arange(n, dtype=jnp.int32),
            next_ts=jnp.asarray(n if parity else 0, jnp.int32),
        )
        assert bool(ok)
        want = np.where(mask, np.asarray(full_res), NOT_FOUND)
        np.testing.assert_array_equal(np.asarray(sub_res), want)
    assert int(sub_st.ts) == int(full_st.ts)
    assert S.live_items(sub_st) == S.live_items(full_st)
    # version timestamps agree key-by-key
    for key, _ in S.live_items(full_st):
        q = jnp.asarray([key], jnp.int32)
        _, _, _, _, _, vh_a = S._locate(full_st, q)
        _, _, _, _, _, vh_b = S._locate(sub_st, q)
        assert int(full_st.ver_ts[int(vh_a[0])]) == int(sub_st.ver_ts[int(vh_b[0])])


def test_fast_path_is_one_device_pass(monkeypatch):
    """apply_batch on a mixed announce array must issue exactly one
    bulk_apply call and NO separate bulk_lookup call on the fast path."""
    st = S.create(CFG)
    rng = np.random.default_rng(1)
    keys = rng.integers(0, 40, 16).astype(np.int32)
    st, _ = B.apply_updates(st, keys, keys)

    calls = {"apply": 0, "lookup": 0}
    orig_apply = S.bulk_apply
    monkeypatch.setattr(
        S, "bulk_apply",
        lambda *a, **kw: (calls.__setitem__("apply", calls["apply"] + 1),
                          orig_apply(*a, **kw))[1],
    )
    monkeypatch.setattr(
        S, "bulk_lookup",
        lambda *a, **kw: (_ for _ in ()).throw(
            AssertionError("separate bulk_lookup on the fast path")),
    )
    ops = [(OP_SEARCH, int(keys[0]), 0), (OP_INSERT, int(keys[1]), 9),
           (OP_DELETE, int(keys[2]), 0), (OP_SEARCH, 999, 0)]
    st, res = B.apply_batch(st, ops)
    assert calls["apply"] == 1
    assert res[3] == NOT_FOUND


def test_bulk_update_lookup_are_thin_wrappers():
    """Wrapper equivalence: bulk_update == bulk_apply with derived codes."""
    rng = np.random.default_rng(2)
    keys = rng.integers(0, 50, 20).astype(np.int32)
    vals = rng.integers(0, 100, 20).astype(np.int32)
    vals[::5] = TOMBSTONE
    keys[3] = KEY_MAX
    st1 = S.create(CFG)
    st2 = S.create(CFG)
    st1, prev1, ok1 = S.bulk_update(st1, jnp.asarray(keys), jnp.asarray(vals))
    codes = np.where(
        keys >= KEY_MAX, OP_NOP,
        np.where(vals == TOMBSTONE, OP_DELETE, OP_INSERT),
    ).astype(np.int32)
    st2, prev2, ok2 = S.bulk_apply(st2, codes, keys, vals)
    assert bool(ok1) == bool(ok2)
    np.testing.assert_array_equal(np.asarray(prev1), np.asarray(prev2))
    assert S.live_items(st1) == S.live_items(st2)
    got = S.bulk_lookup(st1, jnp.asarray(keys[:4]),
                        jnp.asarray(int(st1.ts), jnp.int32))
    _, want, _ = S.bulk_apply(
        st2, np.full(4, OP_SEARCH, np.int32), keys[:4], np.zeros(4, np.int32),
        op_ts=jnp.full((4,), int(st2.ts), jnp.int32),
        next_ts=st2.ts,
    )
    np.testing.assert_array_equal(np.asarray(got), np.asarray(want))


@pytest.mark.parametrize("backend", [BK.XLA, BK.PALLAS_INTERPRET])
def test_backend_dispatch_equivalence(backend):
    """The Pallas kernels (interpret mode off-TPU) and the XLA oracle give
    identical bulk_apply results."""
    rng = np.random.default_rng(3)
    st = S.create(CFG)
    ref = RefStore()
    for it in range(3):
        codes, keys, vals = random_ops(rng, 16, key_hi=30)
        ops = [(int(c), int(k), int(v)) for c, k, v in zip(codes, keys, vals)]
        st2, res, ok = S.bulk_apply(st, codes, keys, vals, backend=backend)
        rres = ref.apply_batch(ops)
        if not bool(ok):
            # keep oracle in sync by replaying via the slow path
            st, bres = B.apply_batch(st, ops)
            assert bres == rres
            continue
        st = st2
        assert np.asarray(res).tolist() == rres, (backend, it)


def test_backend_resolution_env_and_override(monkeypatch):
    monkeypatch.setenv(BK.ENV_VAR, BK.PALLAS_INTERPRET)
    assert BK.get_backend() == BK.PALLAS_INTERPRET
    BK.set_backend(BK.XLA)
    try:
        assert BK.get_backend() == BK.XLA
    finally:
        BK.set_backend(None)
    monkeypatch.delenv(BK.ENV_VAR)
    assert BK.get_backend() == BK.XLA      # default on every platform
    with pytest.raises(ValueError):
        BK.set_backend("tpu9000")


SHARDED_EQUIV_SCRIPT = r"""
import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=4"
import sys
sys.path.insert(0, "src")
import numpy as np, jax, jax.numpy as jnp
from repro.compat import make_mesh
from repro.core import store as S, sharded as SH, batch as B
from repro.core.ref import RefStore, OP_INSERT, OP_DELETE, OP_SEARCH

mesh = make_mesh((4,), ("data",))
base = S.UruvConfig(leaf_cap=8, max_leaves=128, max_versions=2048)
cfg = SH.ShardedConfig(base=base, key_lo=0, key_hi=400)
st = SH.create(cfg, mesh)
apply_fn = SH.make_apply(cfg, mesh)
routed_fn = SH.make_routed_apply(cfg, mesh, route_factor=2)
single = S.create(base)
ref = RefStore()
rng = np.random.default_rng(7)
for it in range(6):
    G = 16
    codes = rng.choice([OP_INSERT, OP_INSERT, OP_DELETE, OP_SEARCH], G).astype(np.int32)
    keys = rng.integers(0, 400, G).astype(np.int32)
    vals = rng.integers(0, 1000, G).astype(np.int32)
    st, res = SH.sharded_apply_batch(st, codes, keys, vals,
                                     apply_fn=apply_fn, routed_fn=routed_fn)
    ops = [(int(c), int(k), int(v)) for c, k, v in zip(codes, keys, vals)]
    single, sres = B.apply_batch(single, ops)
    rres = ref.apply_batch(ops)
    assert res.tolist() == rres == sres, (it, res.tolist(), rres)
    assert SH.global_ts(st) == int(single.ts) == ref.ts
assert np.unique(np.asarray(st.ts)).size == 1   # replicated clock agrees
# per-key version timestamps identical between sharded and single-device
sh = jax.device_get(st)
checked = 0
for shard in range(4):
    ents = np.asarray(sh.index.leaf_ent[shard])
    for lid in np.nonzero(ents >= 0)[0]:
        lid = int(lid)
        for j in range(int(sh.leaf_count[shard][lid])):
            k = int(sh.leaf_keys[shard][lid, j])
            vh = int(sh.leaf_vhead[shard][lid, j])
            _, _, _, _, ex, vh1 = S._locate(single, jnp.asarray([k], jnp.int32))
            assert bool(ex[0]), k
            assert int(sh.ver_ts[shard][vh]) == int(single.ver_ts[int(vh1[0])]), k
            checked += 1
assert checked > 0
print("SHARDED_EQUIV_OK")
"""


def test_sharded_bulk_apply_matches_single_device():
    r = subprocess.run(
        [sys.executable, "-c", SHARDED_EQUIV_SCRIPT],
        capture_output=True, text=True, timeout=900,
        cwd=str(Path(__file__).resolve().parents[1]),
    )
    assert r.returncode == 0, r.stderr[-3000:]
    assert "SHARDED_EQUIV_OK" in r.stdout
