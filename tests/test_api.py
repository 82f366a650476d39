"""repro.api — the public client surface (ISSUE 3).

OpBatch/Result pytree + jit/donation safety, one-compile-per-shape through
the client, client-vs-oracle linearization, deprecation shims (warning +
bit-exact equivalence with the client path), snapshot-context hygiene,
the layering gate (non-core modules go through repro.api only), and
sharded-executor equivalence on 4 fake devices (subprocess).
"""

import subprocess
import sys
import warnings
from pathlib import Path

import numpy as np
import jax
import jax.numpy as jnp
import pytest

from repro.api import (
    KEY_MAX, NOT_FOUND, TOMBSTONE, OP_INSERT, OP_NOP, OP_RANGE, OP_SEARCH,
    OpBatch, RangePage, Result, Uruv, UruvConfig, make_result,
)
from repro.core.ref import OP_DELETE, RefStore

CFG = UruvConfig(leaf_cap=8, max_leaves=512, max_versions=1 << 14,
                 max_chain=16)


def mixed_plan():
    return OpBatch.concat(
        OpBatch.searches([5, 7]),
        OpBatch.inserts([5, 7, 9], [50, 70, 90]),
        OpBatch.ranges([0, 6], [8, 2**31 - 3]),
        OpBatch.deletes([7]),
        OpBatch.searches([7]),
    )


def plan_ops(batch: OpBatch):
    return [(int(c), int(k), int(v)) for c, k, v in
            zip(np.asarray(batch.codes), np.asarray(batch.keys),
                np.asarray(batch.values))]


# ---------------------------------------------------------------------------
# OpBatch / Result: pytree + jit + donation safety
# ---------------------------------------------------------------------------

def test_opbatch_pytree_roundtrip():
    b = mixed_plan()
    leaves, treedef = jax.tree_util.tree_flatten(b)
    assert all(isinstance(l, np.ndarray) for l in leaves)
    b2 = jax.tree_util.tree_unflatten(treedef, leaves)
    assert isinstance(b2, OpBatch)
    for f in ("codes", "keys", "values"):
        np.testing.assert_array_equal(getattr(b, f), getattr(b2, f))


def test_result_pytree_roundtrip():
    res = make_result(
        np.array([1, NOT_FOUND, 3], np.int64),
        np.array([OP_INSERT, OP_NOP, OP_RANGE], np.int32),
        base_ts=7,
        range_items=[(2, [(4, 40), (5, 50)], 9)],
    )
    leaves, treedef = jax.tree_util.tree_flatten(res)
    res2 = jax.tree_util.tree_unflatten(treedef, leaves)
    assert isinstance(res2, Result)
    assert res2.values.tolist() == [1, NOT_FOUND, 3]
    assert res2.found.tolist() == [True, False, True]
    assert res2.timestamps.tolist() == [7, 8, 9]
    assert res2.page(2) == [(4, 40), (5, 50)]
    assert res2.range_resume.tolist() == [9]


def test_opbatch_jit_and_donation_safe():
    b = OpBatch(jnp.asarray([OP_INSERT] * 4, jnp.int32),
                jnp.arange(4, dtype=jnp.int32),
                jnp.full((4,), 3, jnp.int32))

    @jax.jit
    def through(batch):
        merged = OpBatch.concat(batch, batch).pad_to(16)
        return OpBatch(merged.codes, merged.keys, merged.values + 1)

    out = through(b)
    assert len(out) == 16
    assert out.values[:4].tolist() == [4, 4, 4, 4]
    assert int(out.keys[-1]) == KEY_MAX            # NOP padding
    assert int(out.codes[-1]) == OP_NOP

    @jax.jit
    def donating(batch):
        return OpBatch(batch.codes, batch.keys * 2, batch.values)

    donating_d = jax.jit(
        lambda batch: OpBatch(batch.codes, batch.keys * 2, batch.values),
        donate_argnums=0,
    )
    with warnings.catch_warnings():
        # CPU backend may decline the donation; aliasing must still be safe
        warnings.simplefilter("ignore")
        out = donating_d(b)
    assert out.keys.tolist() == [0, 2, 4, 6]


def test_result_jit_safe():
    res = make_result(
        np.array([1, 2], np.int64), np.array([OP_INSERT, OP_INSERT], np.int32),
        base_ts=0,
    )
    bumped = jax.jit(
        lambda r: Result(r.values + 1, r.found, r.timestamps, r.range_index,
                         r.range_pages, r.range_resume)
    )(res)
    assert np.asarray(bumped.values).tolist() == [2, 3]


def test_opbatch_builders_and_pad():
    b = OpBatch.updates(np.array([1, KEY_MAX, 3], np.int32),
                        np.array([10, 0, TOMBSTONE], np.int32))
    assert b.codes.tolist() == [OP_INSERT, OP_NOP, OP_DELETE]
    with pytest.raises(ValueError):
        b.pad_to(2)
    p = b.pad_to(5)
    assert p.codes.tolist()[3:] == [OP_NOP, OP_NOP]
    assert p.keys.tolist()[3:] == [KEY_MAX, KEY_MAX]
    assert mixed_plan().range_positions.tolist() == [5, 6]


def test_pow2_width_and_pad_to_pow2():
    """The serving front end's shape-bucketing helpers: `pow2_width` is
    next-power-of-two (1 for empty), `pad_to_pow2` NOP-pads to it with
    the KEY_MAX sentinel the builders themselves can never emit (they
    reject both sentinel keys at the front door)."""
    from repro.api import pow2_width
    assert [pow2_width(n) for n in (0, 1, 2, 3, 4, 5, 8, 9)] == \
        [1, 1, 2, 4, 4, 8, 8, 16]
    b = OpBatch.inserts([1, 2, 3], [7, 7, 7]).pad_to_pow2()
    assert len(b) == 4
    assert b.codes.tolist()[-1] == OP_NOP and b.keys.tolist()[-1] == KEY_MAX
    assert len(OpBatch.searches([1, 2]).pad_to_pow2()) == 2  # already pow2
    with pytest.raises(ValueError, match="sentinel"):
        OpBatch.inserts([KEY_MAX - 1], [1])


# ---------------------------------------------------------------------------
# One compile per shape through the client
# ---------------------------------------------------------------------------

def test_one_compile_per_shape_through_client():
    from repro.core import store as S

    db = Uruv(CFG)
    rng = np.random.default_rng(0)
    for i in range(0, 64, 8):                   # spread prefill: fast-path
        db.insert(np.arange(i, i + 8, dtype=np.int32), 0)  # overwrites below
    W = 37                                      # distinctive width
    cache0 = S._bulk_apply._cache_size()

    def batch():                                # overwrite-only: light path
        return OpBatch.inserts(rng.integers(0, 64, W).astype(np.int32),
                               rng.integers(0, 100, W).astype(np.int32))

    passes0 = db.stats["device_passes"]
    db.apply(batch())
    grown = S._bulk_apply._cache_size() - cache0
    assert grown >= 1
    for _ in range(4):                          # same shape: NO retrace
        db.apply(batch())
    assert S._bulk_apply._cache_size() - cache0 == grown
    # ... and the fast path stays one device pass per batch
    assert db.stats["device_passes"] - passes0 == 5

    # pad_to_pow2 buckets ragged widths into one shape
    cache1 = S._bulk_apply._cache_size()
    for w in (33, 40, 57, 64):
        db.apply(OpBatch.searches(rng.integers(0, 30, w).astype(np.int32)),
                 pad_to_pow2=True)
    assert S._bulk_apply._cache_size() - cache1 <= 1


# ---------------------------------------------------------------------------
# Client linearization vs the sequential oracle
# ---------------------------------------------------------------------------

def test_client_mixed_plan_vs_oracle():
    db = Uruv(CFG)
    ref = RefStore()
    plan = mixed_plan()
    res = db.apply(plan)
    want = ref.apply_batch(plan_ops(plan))
    assert res.values.tolist() == want
    assert db.ts == ref.ts
    assert res.timestamps.tolist() == list(range(ref.ts - len(plan), ref.ts))
    # complete pages at the range ops' announce snapshots
    assert res.range_index.tolist() == [5, 6]
    assert res.page(5) == ref.range_query(0, 8, int(res.timestamps[5]))
    assert res.page(6) == ref.range_query(6, 2**31 - 3,
                                          int(res.timestamps[6]))
    assert db.live_items() == ref.live_items()
    assert res.found.tolist() == [v != NOT_FOUND for v in want]


def test_client_random_plans_vs_oracle():
    rng = np.random.default_rng(42)
    db = Uruv(CFG)
    ref = RefStore()
    for it in range(6):
        n = int(rng.integers(1, 40))
        codes = rng.choice(
            [OP_INSERT, OP_INSERT, OP_DELETE, OP_SEARCH, OP_RANGE, OP_NOP], n
        ).astype(np.int32)
        keys = rng.integers(0, 60, n).astype(np.int32)
        vals = rng.integers(0, 1000, n).astype(np.int32)
        vals = np.where(codes == OP_RANGE, keys + rng.integers(0, 30, n),
                        vals).astype(np.int32)
        batch = OpBatch(codes, keys, vals)
        res = db.apply(batch)
        want = ref.apply_batch(plan_ops(batch))
        assert res.values.tolist() == want, it
        assert db.ts == ref.ts
    assert db.live_items() == ref.live_items()


def test_client_verbs_and_lookup():
    db = Uruv(CFG)
    db.insert([1, 2, 3], [10, 20, 30])
    assert db.lookup([1, 2, 99]).tolist() == [10, 20, NOT_FOUND]
    assert db.lookup([1, 2, 99], pad_to_pow2=True).tolist() == \
        [10, 20, NOT_FOUND]
    prev = db.delete([2])
    assert prev.values.tolist() == [20]
    assert db.search([2]).values.tolist() == [NOT_FOUND]
    assert db.range(0, 100) == [(1, 10), (3, 30)]
    assert len(db) == 2


def test_snapshot_context_releases_on_error():
    db = Uruv(CFG)
    db.insert([1], [10])
    with pytest.raises(RuntimeError, match="boom"):
        with db.snapshot() as ts:
            assert db.active_snapshots == 1
            assert db.range(0, 5, ts) == [(1, 10)]
            raise RuntimeError("boom")
    assert db.active_snapshots == 0


def test_snapshot_isolation_through_client():
    db = Uruv(CFG)
    db.insert(np.arange(20), np.arange(20) * 2)
    with db.snapshot() as ts:
        db.insert(np.arange(20), np.arange(20) * 100)
        old = db.range(0, 19, ts)
        assert old == [(k, 2 * k) for k in range(20)]
    db.compact()
    assert db.range(0, 19) == [(k, 100 * k) for k in range(20)]


def test_range_page_bounded_pass_resume():
    db = Uruv(CFG)
    db.insert(np.arange(100), np.arange(100))
    # max_results overflow on query 0 -> truncated + exact resume frontier
    page = db.range_page([0, 50], [99, 59], db.ts, max_results=16,
                         scan_leaves=16, max_rounds=1)
    assert isinstance(page, RangePage)
    cnt = np.asarray(page.count)
    assert int(cnt[1]) == 10 and not bool(np.asarray(page.truncated)[1])
    assert bool(np.asarray(page.truncated)[0])
    resume = int(np.asarray(page.resume_k1)[0])
    rest = db.range(resume, 99, db.ts)
    assert page.items(0) + rest == [(k, k) for k in range(100)]


# ---------------------------------------------------------------------------
# Deprecation shims: warning + bit-exact equivalence with the client path
# ---------------------------------------------------------------------------

def test_apply_updates_shim_warns_and_matches_client():
    from repro.core import batch as B, store as S

    rng = np.random.default_rng(1)
    keys = rng.integers(0, 50, 24).astype(np.int32)
    vals = rng.integers(0, 100, 24).astype(np.int32)
    vals[::4] = TOMBSTONE
    keys[5] = KEY_MAX

    st = S.create(CFG)
    with pytest.warns(DeprecationWarning, match="apply_updates"):
        st, prev = B.apply_updates(st, keys, vals)

    db = Uruv(CFG)
    res = db.apply(OpBatch.updates(keys, vals))
    np.testing.assert_array_equal(prev, np.asarray(res.values))
    assert S.live_items(st) == db.live_items()
    assert int(st.ts) == db.ts


def test_range_query_all_shim_warns_and_matches_client():
    from repro.core import batch as B, store as S

    db = Uruv(CFG)
    db.insert(np.arange(60), np.arange(60) * 3)
    st = db.store
    with pytest.warns(DeprecationWarning, match="range_query_all"):
        st, items = B.range_query_all(st, 5, 40, None)
    assert items == db.range(5, 40)
    # the shim registered AND released its snapshot through the client
    assert not bool(np.asarray(st.trk_active).any())


def test_bulk_update_shim_warns_and_matches_client():
    from repro.core import store as S

    rng = np.random.default_rng(2)
    # <= leaf_cap new keys per leaf: the raw pass must accept (ok=True) so
    # it stays comparable with the client path (which would slow-path)
    keys = np.arange(8, dtype=np.int32)
    vals = rng.integers(0, 100, 8).astype(np.int32)
    vals[::5] = TOMBSTONE

    st = S.create(CFG)
    with pytest.warns(DeprecationWarning, match="bulk_update"):
        st, prev, ok = S.bulk_update(st, keys, vals)
    assert bool(ok)

    db = Uruv(CFG)
    res = db.apply(OpBatch.updates(keys, vals))
    np.testing.assert_array_equal(np.asarray(prev), np.asarray(res.values))
    assert S.live_items(st) == db.live_items()
    assert int(st.ts) == db.ts


def test_internal_layers_raise_no_deprecation_warnings():
    """Engine/pipeline/checkpoint must be fully migrated: exercising them
    must not route through the deprecated entry points."""
    from repro.data.pipeline import StreamingSampleStore

    with warnings.catch_warnings():
        warnings.filterwarnings("error", category=DeprecationWarning,
                                module=r"repro(\..*)?")
        store = StreamingSampleStore(CFG)
        ids = np.arange(40, dtype=np.int32)
        store.ingest(ids, ids * 2)
        snap = store.epoch_view()
        assert store.read_shard(0, 39, snap) == [(int(i), int(i) * 2)
                                                 for i in ids]
        store.release(snap)
        store.retire_below(10)
        store.compact()
        assert store.live_count() == 30


# ---------------------------------------------------------------------------
# Layering gate: outside repro.core (and repro.api, which implements the
# facade), nothing imports core.store / core.batch / core.sharded — proven
# by uruvlint's AST import analysis (repro.analysis, DESIGN.md Sec 13),
# which replaced the old regex scan: it resolves relative imports and never
# trips on prose mentions in docstrings.
# ---------------------------------------------------------------------------

def test_layering_only_api_touches_core_internals():
    from repro.analysis.engine import run_paths
    from repro.analysis.rules import LayeringApiRule, LayeringIndexRule

    root = Path(__file__).resolve().parents[1]
    scan_dirs = [
        root / "src" / "repro", root / "benchmarks", root / "examples",
        root / "scripts",
    ]
    findings = run_paths(
        scan_dirs, rules=[LayeringApiRule(), LayeringIndexRule()], root=root)
    assert not findings, "layering violations:\n" + "\n".join(
        f.render() for f in findings)


# ---------------------------------------------------------------------------
# ShardedExecutor == LocalExecutor (4 fake devices, subprocess)
# ---------------------------------------------------------------------------

SHARDED_API_SCRIPT = r"""
import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=4"
import sys
sys.path.insert(0, "src")
import numpy as np
from repro.compat import make_mesh
from repro.api import OpBatch, ShardedConfig, Uruv, UruvConfig
from repro.core.ref import RefStore, OP_INSERT, OP_DELETE, OP_SEARCH, OP_RANGE

mesh = make_mesh((4,), ("data",))
base = UruvConfig(leaf_cap=8, max_leaves=128, max_versions=2048)
db = Uruv.sharded(ShardedConfig(base=base, key_lo=0, key_hi=400), mesh)
local = Uruv(base)
ref = RefStore()
rng = np.random.default_rng(11)

def check(batch, ops):
    r_sh = db.apply(batch)
    r_lo = local.apply(batch)
    want = ref.apply_batch(ops)
    assert r_sh.values.tolist() == r_lo.values.tolist() == want, (
        r_sh.values.tolist(), r_lo.values.tolist(), want)
    assert r_sh.pages() == r_lo.pages()
    assert db.ts == local.ts == ref.ts

for it in range(4):
    G = 16                       # divisible by 4: exercises the routed pass
    codes = rng.choice([OP_INSERT, OP_INSERT, OP_DELETE, OP_SEARCH],
                       G).astype(np.int32)
    keys = rng.integers(0, 400, G).astype(np.int32)
    vals = rng.integers(0, 1000, G).astype(np.int32)
    check(OpBatch(codes, keys, vals),
          [(int(c), int(k), int(v)) for c, k, v in zip(codes, keys, vals)])

# mixed plan with RANGE segments through the same client surface
plan = OpBatch.concat(
    OpBatch.ranges([0, 100], [99, 399]),
    OpBatch.inserts([5], [55]),
    OpBatch.ranges([0], [9]),
)
check(plan, [(OP_RANGE, 0, 99), (OP_RANGE, 100, 399),
             (OP_INSERT, 5, 55), (OP_RANGE, 0, 9)])

assert db.live_items() == local.live_items() == ref.live_items()
assert db.lookup(np.arange(0, 400, 7)).tolist() == \
    local.lookup(np.arange(0, 400, 7)).tolist()
with db.snapshot() as s1, local.snapshot() as s2:
    assert s1 == s2
    assert db.range_all([0, 50], [399, 250], s1) == \
        local.range_all([0, 50], [399, 250], s2)
assert db.active_snapshots == 0
print("SHARDED_API_OK")
"""


SHARDED_WIDE_INSERT_SCRIPT = r"""
import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=4"
import sys
sys.path.insert(0, "src")
import numpy as np
from repro.compat import make_mesh
from repro.api import OpBatch, ShardedConfig, Uruv, UruvConfig
from repro.core.ref import RefStore, OP_INSERT

mesh = make_mesh((4,), ("data",))
base = UruvConfig(leaf_cap=8, max_leaves=64, max_versions=1024)
db = Uruv.sharded(ShardedConfig(base=base, key_lo=0, key_hi=4000), mesh)
local = Uruv(base)
ref = RefStore()
rng = np.random.default_rng(5)
for _ in range(3):
    keys = rng.choice(4000, 128, replace=False).astype(np.int32)
    vals = rng.integers(1, 1000, 128).astype(np.int32)
    r_sh = db.apply(OpBatch.inserts(keys, vals))
    r_lo = local.apply(OpBatch.inserts(keys, vals))
    want = ref.apply_batch([(OP_INSERT, int(k), int(v))
                            for k, v in zip(keys, vals)])
    assert r_sh.values.tolist() == r_lo.values.tolist() == want
    assert db.ts == local.ts == ref.ts
assert db.stats["slow_path_rounds"] > 0
assert db.live_items() == local.live_items() == ref.live_items()
print("SHARDED_WIDE_OK")
"""


def test_sharded_wide_insert_into_empty_store_halves():
    """A 128-wide insert plan routes > leaf_cap new keys to one leaf of
    each empty shard; the sharded executor applies it in halves at the
    original timestamps (the local slow path) instead of raising."""
    proc = subprocess.run(
        [sys.executable, "-c", SHARDED_WIDE_INSERT_SCRIPT],
        cwd=Path(__file__).resolve().parents[1],
        capture_output=True, text=True, timeout=900,
    )
    assert proc.returncode == 0, proc.stderr[-4000:]
    assert "SHARDED_WIDE_OK" in proc.stdout


def test_sharded_executor_matches_local_subprocess():
    proc = subprocess.run(
        [sys.executable, "-c", SHARDED_API_SCRIPT],
        cwd=Path(__file__).resolve().parents[1],
        capture_output=True, text=True, timeout=900,
    )
    assert proc.returncode == 0, proc.stderr[-4000:]
    assert "SHARDED_API_OK" in proc.stdout
