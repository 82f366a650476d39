"""Benchmark harness — one function per paper table/figure.

Prints ``name,us_per_call,derived`` CSV (derived = Mops/s for ADT
workloads; see each section).  Figures:

  * fig8 a–c   — dictionary workloads, Uruv vs the flat-chunk baseline
                 (the paper's LF-B+Tree/OpenBw-Tree role), sweeping the
                 announce width (the paper's thread-count axis).
  * fig9 a–f   — range-query mixes, Uruv MVCC snapshot scans vs
                 validate-retry multi-scan (the paper's VCAS-BST role).
  * table_complexity — measured wait-free bound: passes per op vs
                 conflict concentration (the paper's m = f(I_C) bound).
  * kernels    — Uruv hot-path kernels, XLA path (CPU relative numbers).
  * mixed      — the fused one-pass ``bulk_apply`` vs the pre-fusion
                 two-pass path (update pass + host sync + lookup pass)
                 on a mixed announce array; writes BENCH_mixed.json.
  * range      — the batched device-resident ``bulk_range`` (Q intervals,
                 ONE jitted pass) vs the host-paginated per-query
                 ``range_query`` loop; writes BENCH_range.json.
  * lifecycle  — self-sizing store costs: incremental ``maintain`` vs
                 stop-the-world ``compact`` at matched reclamation, and
                 auto-grow amortization vs a pre-sized pool; writes
                 BENCH_lifecycle.json.
  * index      — multi-level fat-node index: structural-batch latency
                 under delta maintenance vs the flat full-rebuild
                 discipline (4k -> 64k leaves), and locate throughput at
                 depth 1 vs multi-level; writes BENCH_index.json.
  * wal        — durability: group-commit WAL ingest vs fsync-per-plan,
                 delta vs full checkpoint bytes + latency, and crash-
                 recovery replay throughput (gated: delta <= 25% of the
                 full save, replay >= 50k ops/s); writes BENCH_wal.json.

Run: PYTHONPATH=src python -m benchmarks.run [--quick]
"""

from __future__ import annotations

import argparse
import json
from pathlib import Path

import numpy as np

from benchmarks import workloads as W
from repro.api import (
    KEY_MAX, NOT_FOUND, TOMBSTONE, OP_DELETE, OP_INSERT, OP_SEARCH,
    LifecyclePolicy, OpBatch, Uruv, UruvConfig,
)
from repro.launch.cache import enable_compile_cache

WIDTHS = [64, 256, 1024, 4096]


def emit(name: str, us: float, derived: str) -> None:
    print(f"{name},{us:.2f},{derived}", flush=True)


def fig8(quick: bool = False) -> None:
    rng = np.random.default_rng(0)
    uruv = W.prefill_uruv(rng)
    flat = W.prefill_flat(rng)
    widths = WIDTHS[:2] if quick else WIDTHS
    for name, w in W.FIG8.items():
        for width in widths:
            uruv, sec = W.run_uruv(uruv, rng, w, width)
            emit(f"{name}_uruv_w{width}", sec * 1e6,
                 f"{width/sec/1e6:.3f}Mops/s")
            flat, fsec = W.run_flat(flat, rng, w, width)
            emit(f"{name}_flatbase_w{width}", fsec * 1e6,
                 f"{width/fsec/1e6:.3f}Mops/s")


def fig9(quick: bool = False) -> None:
    rng = np.random.default_rng(1)
    uruv = W.prefill_uruv(rng)
    flat = W.prefill_flat(rng)
    widths = [1024] if quick else [1024, 4096]
    figs = dict(list(W.FIG9.items())[:2]) if quick else W.FIG9
    for name, w in figs.items():
        for width in widths:
            uruv, sec = W.run_uruv(uruv, rng, w, width)
            emit(f"{name}_uruv_w{width}", sec * 1e6,
                 f"{width/sec/1e6:.3f}Mops/s")
            flat, fsec = W.run_flat(flat, rng, w, width)
            emit(f"{name}_validate_retry_w{width}", fsec * 1e6,
                 f"{width/fsec/1e6:.3f}Mops/s")


def table_complexity() -> None:
    """Wait-free bound: slow-path rounds vs conflict concentration.

    The paper bounds restarts by m = min(f + s*t, I_C) (interval
    contention).  The batch analogue: a prefilled store receives 1024 NEW
    keys concentrated in a span of the key space — the narrower the span,
    the more structural inserts collide on the same leaves and the more
    bounded help-rounds the combining layer runs.  Wide spans take the
    fast path (1 round) — observable through the client's device-pass
    counter (``Uruv.stats``)."""
    rng = np.random.default_rng(2)
    base_keys = rng.choice(1_000_000, 100_000, replace=False) \
        .astype(np.int32) * 2           # even keys prefilled
    for span in (1_000_000, 65_536, 8_192, 2_048):
        db = Uruv(UruvConfig(leaf_cap=16, max_leaves=1 << 15,
                             max_versions=1 << 19))
        for i in range(0, 100_000, 4096):
            db.apply(OpBatch.updates(base_keys[i:i+4096],
                                     base_keys[i:i+4096]))
        new = (rng.choice(span // 2, 1024, replace=False)
               .astype(np.int32) * 2 + 1)      # odd keys: all new
        before = db.stats["device_passes"]
        db.apply(OpBatch.updates(new, new))
        passes = db.stats["device_passes"] - before
        emit(f"complexity_span{span}_passes", float(passes),
             f"{passes}rounds")


def kernels(quick: bool = False) -> None:
    rng = np.random.default_rng(3)
    db = W.prefill_uruv(rng)
    q = rng.integers(0, W.UNIVERSE, 4096).astype(np.int32)
    ts = db.ts
    sec = W.timed(lambda: db.lookup(q, ts))    # np round-trip == block
    emit("kernel_locate_resolve_4096", sec * 1e6,
         f"{4096/sec/1e6:.2f}Mlookups/s")
    sec = W.timed(lambda: db.scan_page(
        100_000, 101_000, ts, max_scan_leaves=64,
        max_results=2048).keys.block_until_ready())
    emit("kernel_range1k_snapshot", sec * 1e6, "1scan")


MIXED_CFG = UruvConfig(leaf_cap=64, max_leaves=1 << 13,
                       max_versions=1 << 19, max_chain=64)
MIXED_RESIDENT = 200_000


def _two_pass_apply(db: Uruv, codes, keys, vals):
    """The pre-bulk_apply execution path (seed `batch.apply_batch`): one
    device pass for INSERT/DELETE, a host sync, a second device pass for
    SEARCH at per-op snapshots, host-side result assembly.  The update pass
    runs with ``light_path=False`` — the seed rebuilt the structure
    unconditionally (validated against the actual seed checkout)."""
    n = len(codes)
    base = db.ts
    upd_mask = (codes == OP_INSERT) | (codes == OP_DELETE)
    ukeys = np.where(upd_mask, keys, KEY_MAX).astype(np.int32)
    uvals = np.where(codes == OP_DELETE, TOMBSTONE, vals).astype(np.int32)
    rounds = db.stats["slow_path_rounds"]
    res_u = db.apply(OpBatch.updates(ukeys, uvals), light_path=False)
    assert db.stats["slow_path_rounds"] == rounds, \
        "baseline update pass rejected; resize MIXED_CFG"
    results = np.full(n, NOT_FOUND, np.int64)
    results[upd_mask] = res_u.values[upd_mask]
    smask = codes == OP_SEARCH
    skeys = np.where(smask, keys, KEY_MAX).astype(np.int32)
    snaps = (base + np.arange(n)).astype(np.int32)
    results[smask] = db.lookup(skeys, snaps)[smask]
    return results


def mixed(quick: bool = False, out_path: str = "BENCH_mixed.json") -> None:
    """Fused mixed-op pass vs the old two-pass path (DESIGN.md Sec 3).

    Workload: 90% SEARCH / 5% INSERT / 5% DELETE over a resident working
    set (updates overwrite live keys — the serving-table traffic pattern).
    Both paths run through the `repro.api` client and produce bit-identical
    announce-order results; the fused path is ONE device call per batch
    (asserted via the client's device-pass counter)."""
    rng = np.random.default_rng(5)
    db0 = Uruv(MIXED_CFG)
    resident = rng.choice(W.UNIVERSE, MIXED_RESIDENT,
                          replace=False).astype(np.int32)
    for i in range(0, MIXED_RESIDENT, 4096):
        db0.apply(OpBatch.updates(resident[i:i+4096],
                                  resident[i:i+4096] % 1000 + 1))
    widths = [1024] if quick else [1024, 4096]
    report = {}
    for width in widths:
        batches = []
        for _ in range(4):
            r = rng.random(width)
            codes = np.where(
                r < 0.90, OP_SEARCH,
                np.where(r < 0.95, OP_INSERT, OP_DELETE),
            ).astype(np.int32)
            keys = resident[rng.integers(0, MIXED_RESIDENT, width)] \
                .astype(np.int32)
            vals = (keys % 1000 + 1).astype(np.int32)
            batches.append((codes, keys, vals))

        # the two paths must agree before we time them — and the fused
        # client path must stay ONE device pass (the PR-1 guard)
        db_chk = Uruv.from_store(db0.store)
        passes = db_chk.stats["device_passes"]
        res_f = db_chk.apply(OpBatch(*batches[0]))
        assert db_chk.stats["device_passes"] == passes + 1, \
            "client fast path issued more than one device pass"
        db_chk2 = Uruv.from_store(db0.store)
        res_t = _two_pass_apply(db_chk2, *batches[0])
        assert res_f.values.tolist() == res_t.tolist(), \
            "fused and two-pass paths disagree"

        db_f = Uruv.from_store(db0.store)

        def run_fused():
            for c, k, v in batches:
                db_f.apply(OpBatch(c, k, v))

        fsec = W.timed(run_fused) / len(batches)

        db_t = Uruv.from_store(db0.store)

        def run_two_pass():
            for c, k, v in batches:
                _two_pass_apply(db_t, c, k, v)

        tsec = W.timed(run_two_pass) / len(batches)
        emit(f"mixed_fused_w{width}", fsec * 1e6,
             f"{width/fsec/1e6:.3f}Mops/s")
        emit(f"mixed_two_pass_w{width}", tsec * 1e6,
             f"{width/tsec/1e6:.3f}Mops/s")
        emit(f"mixed_speedup_w{width}", tsec / fsec, f"{tsec/fsec:.2f}x")
        report[f"w{width}"] = {
            "fused_us": round(fsec * 1e6, 1),
            "two_pass_us": round(tsec * 1e6, 1),
            "speedup": round(tsec / fsec, 2),
        }
    Path(out_path).write_text(json.dumps(report, indent=2) + "\n")


RANGE_CFG = UruvConfig(leaf_cap=64, max_leaves=1 << 13,
                       max_versions=1 << 19, max_chain=64)
RANGE_RESIDENT = 100_000
RANGE_UNIVERSE = 1_000_000


def _host_paged_ranges(db: Uruv, k1s, k2s, ts, *, max_scan_leaves,
                       max_results):
    """The pre-bulk_range serving shape: one jitted `scan_page` call per
    interval, host sync per page, resume from last key + 1 (the seed
    `range_query_all` loop, batched over queries by a host for-loop)."""
    out = []
    for a, b in zip(k1s, k2s):
        lo, items = int(a), []
        while True:
            page = db.scan_page(lo, int(b), ts,
                                max_scan_leaves=max_scan_leaves,
                                max_results=max_results)
            cnt = int(page.count[0])
            k = np.asarray(page.keys)[0, :cnt]
            items.extend(zip(k.tolist(),
                             np.asarray(page.values)[0, :cnt].tolist()))
            if not bool(page.truncated[0]):
                break
            lo = int(k[-1]) + 1 if cnt else lo + 1
        out.append(items)
    return out


def range_bench(quick: bool = False, out_path: str = "BENCH_range.json") -> None:
    """Batched `bulk_range` vs the host-paginated per-query loop.

    Workload: Q mixed-width intervals (widths log-spread from point-ish
    scans to ~4k-key spans) over a 100k-key resident store — the serve
    engine's snapshot_view / data pipeline epoch-reader traffic.  Both
    paths return identical (key, value) pages; the fused path is ONE
    device call for all Q queries (in-pass pagination)."""
    rng = np.random.default_rng(7)
    db = Uruv(RANGE_CFG)
    resident = rng.choice(RANGE_UNIVERSE, RANGE_RESIDENT,
                          replace=False).astype(np.int32)
    for i in range(0, RANGE_RESIDENT, 4096):
        db.apply(OpBatch.updates(resident[i:i+4096],
                                 resident[i:i+4096] % 1000 + 1))
    ts = db.ts
    # both Q points always run (the acceptance evidence in BENCH_range.json
    # covers Q=64 and Q=256); quick mode trims the timing repeats instead
    qs = [64, 256]
    repeats = (3, 1) if quick else (5, 2)
    widths = np.array([100, 1_000, 10_000, 40_000])     # mixed-width mix
    report = {}
    for Q in qs:
        k1 = rng.integers(0, RANGE_UNIVERSE - 50_000, Q).astype(np.int32)
        k2 = (k1 + widths[np.arange(Q) % len(widths)]).astype(np.int32)

        # the two paths must agree before we time them
        pages = db.range_all(k1, k2, ts, max_results=4096,
                             scan_leaves=32, max_rounds=1)
        paged = _host_paged_ranges(db, k1, k2, ts,
                                   max_scan_leaves=128, max_results=4096)
        assert pages == paged, "bulk_range and host-paginated loop disagree"

        def run_bulk():
            db.range_all(k1, k2, ts, max_results=4096,
                         scan_leaves=32, max_rounds=1)

        bsec = W.timed(run_bulk, repeats=repeats[0], warmup=1)

        def run_paged():
            _host_paged_ranges(db, k1, k2, ts,
                               max_scan_leaves=128, max_results=4096)

        psec = W.timed(run_paged, repeats=repeats[1], warmup=1)
        emit(f"range_bulk_q{Q}", bsec * 1e6, f"{Q/bsec/1e3:.2f}Kq/s")
        emit(f"range_host_paged_q{Q}", psec * 1e6, f"{Q/psec/1e3:.2f}Kq/s")
        emit(f"range_speedup_q{Q}", psec / bsec, f"{psec/bsec:.2f}x")
        report[f"q{Q}"] = {
            "bulk_us": round(bsec * 1e6, 1),
            "host_paged_us": round(psec * 1e6, 1),
            "speedup": round(psec / bsec, 2),
        }
    Path(out_path).write_text(json.dumps(report, indent=2) + "\n")


def lifecycle_bench(quick: bool = False,
                    out_path: str = "BENCH_lifecycle.json") -> None:
    """Self-sizing lifecycle costs (DESIGN.md Sec 10); BENCH_lifecycle.json.

    (a) *Incremental maintain vs stop-the-world compact* at matched
    reclamation: a store is driven to heavy garbage (frozen split-leavings
    from sustained ingest + tombstones from a bulk delete), then the same
    start state is reclaimed two ways — bounded ``maintain`` passes until
    quiescence vs ONE ``compact()`` — and we report total time, per-pass
    pause, and us per reclaimed leaf slot.  ``maintain``'s per-pass pause
    is the serving-relevant number: it bounds the latency a reclamation
    step can inject into an admission path.

    (b) *Grow amortization*: ingest a working set that is ~32x the initial
    leaf pool with auto-grow on, vs the same ingest into a pre-sized pool;
    the delta is the total cost of all grow events + regrowth recompiles.
    """
    import time as _time

    rng = np.random.default_rng(11)
    ML0 = 1 << 10 if quick else 1 << 12
    n_keys = (ML0 * 24)                     # ~75% of pool after splits
    cfg = UruvConfig(leaf_cap=32, max_leaves=ML0, max_versions=1 << 18,
                     max_chain=64)
    manual = LifecyclePolicy(auto_grow=True, auto_maintain=False)
    db = Uruv(cfg, policy=manual)
    keys = rng.choice(20_000_000, n_keys, replace=False).astype(np.int32)
    for i in range(0, n_keys, 2048):
        db.apply(OpBatch.inserts(keys[i:i + 2048], keys[i:i + 2048] % 997 + 1))
    dels = keys[rng.random(n_keys) < 0.6]
    for i in range(0, len(dels), 2048):
        db.apply(OpBatch.deletes(dels[i:i + 2048]))
    s0 = db.store
    n_alloc0 = int(np.asarray(s0.n_alloc))
    budget = 256
    report = {}

    def drain(store):
        """Maintain to quiescence -> (store, reclaimed, passes, max_pause_s)."""
        from repro.api import LocalExecutor
        ex = LocalExecutor(store.cfg, policy=manual)
        total = passes = 0
        max_pause = 0.0
        while True:
            t0 = _time.perf_counter()
            store, rec, mer = ex.maintain(store, budget, phase=passes)
            max_pause = max(max_pause, _time.perf_counter() - t0)
            total += rec
            passes += 1
            if (rec == 0 and mer == 0) or passes > 256:
                break
        return store, total, passes, max_pause

    drain(s0)                                # warmup (compiles)
    times, recs, pauses, npasses = [], [], [], []
    for _ in range(2 if quick else 3):
        t0 = _time.perf_counter()
        _, rec, passes, pause = drain(s0)
        times.append(_time.perf_counter() - t0)
        recs.append(rec)
        pauses.append(pause)
        npasses.append(passes)
    m_us = float(np.min(times)) * 1e6
    m_rec = recs[0]

    db_c = Uruv.from_store(s0, policy=manual)
    db_c.compact()                           # warmup (compiles)
    ctimes, crecs = [], []
    for _ in range(2 if quick else 3):
        db_c = Uruv.from_store(s0, policy=manual)
        t0 = _time.perf_counter()
        db_c.compact()
        ctimes.append(_time.perf_counter() - t0)
        crecs.append(n_alloc0 - int(np.asarray(db_c.store.n_alloc)))
    c_us = float(np.min(ctimes)) * 1e6
    c_rec = crecs[0]

    m_per_leaf = m_us / max(m_rec, 1)
    c_per_leaf = c_us / max(c_rec, 1)
    emit("lifecycle_maintain_total", m_us,
         f"{m_rec}leaves/{npasses[0]}passes")
    emit("lifecycle_maintain_max_pause", pauses[0] * 1e6, "1pass")
    emit("lifecycle_compact_total", c_us, f"{c_rec}leaves/1pass")
    emit("lifecycle_us_per_leaf_speedup", c_per_leaf / m_per_leaf,
         f"{c_per_leaf / m_per_leaf:.2f}x")
    report["maintain_vs_compact"] = {
        "start_n_alloc": n_alloc0,
        "maintain_total_us": round(m_us, 1),
        "maintain_reclaimed": m_rec,
        "maintain_passes": npasses[0],
        "maintain_max_pause_us": round(pauses[0] * 1e6, 1),
        "compact_total_us": round(c_us, 1),
        "compact_reclaimed": c_rec,
        "maintain_us_per_leaf": round(m_per_leaf, 2),
        "compact_us_per_leaf": round(c_per_leaf, 2),
        "speedup_us_per_leaf": round(c_per_leaf / m_per_leaf, 2),
    }

    # ---- (b) grow amortization: auto-grown vs pre-sized ingest ----------
    g_keys = rng.choice(20_000_000, 1 << (14 if quick else 16),
                        replace=False).astype(np.int32)
    small = UruvConfig(leaf_cap=32, max_leaves=256, max_versions=1 << 12,
                       max_chain=64)

    def ingest(config):
        dbi = Uruv(config)
        t0 = _time.perf_counter()
        for i in range(0, len(g_keys), 2048):
            dbi.apply(OpBatch.inserts(g_keys[i:i + 2048],
                                      g_keys[i:i + 2048] % 997 + 1))
        return _time.perf_counter() - t0, dbi

    ingest(small)                            # warmup (compiles every bucket)
    g_sec, dbg = ingest(small)
    big = UruvConfig(leaf_cap=32, max_leaves=dbg.capacity.max_leaves,
                     max_versions=dbg.capacity.max_versions, max_chain=64)
    ingest(big)                              # warmup
    p_sec, _ = ingest(big)
    overhead = (g_sec - p_sec) / p_sec
    emit("lifecycle_grow_ingest", g_sec * 1e6,
         f"{dbg.stats['grows']}grows")
    emit("lifecycle_presized_ingest", p_sec * 1e6, "0grows")
    emit("lifecycle_grow_overhead", overhead * 100, f"{overhead:+.1%}")
    report["grow_amortization"] = {
        "n_keys": len(g_keys),
        "initial_max_leaves": small.max_leaves,
        "final_max_leaves": dbg.capacity.max_leaves,
        "grows": dbg.stats["grows"],
        "auto_grow_ingest_us": round(g_sec * 1e6, 1),
        "presized_ingest_us": round(p_sec * 1e6, 1),
        "overhead_fraction": round(overhead, 3),
    }
    Path(out_path).write_text(json.dumps(report, indent=2) + "\n")


def index_bench(quick: bool = False,
                out_path: str = "BENCH_index.json") -> None:
    """Multi-level fat-node index costs (DESIGN.md Sec 11); BENCH_index.json.

    (a) *Structural maintenance, delta vs flat rebuild, vs leaf count*:
    ONE jitted index-maintenance pass for a heavy structural batch (128
    leaf splits) applied two ways — the bounded bottom-up separator
    delta (`index.apply_split_delta`, O(touched·F·depth) — the shipped
    path) vs the flat full-rebuild discipline (`reindex`: repack the
    whole index, the pre-Sec-11 O(ML) behaviour).  The delta pass stays
    ~flat as the leaf count grows 4k -> 64k while the rebuild scales
    with ML — the per-ML speedup is the acceptance evidence.  The
    end-to-end structural `apply` latency under both disciplines is
    reported alongside for context (it folds in the leaf/version pool
    writes common to both paths, so its ratio is structurally smaller).

    (b) *Locate throughput, depth 1 vs multi-level*: the same resident
    set indexed with one flat root fat node (fanout >= ML: descent is the
    directory-era O(P·ML) compare-reduce) vs the default multi-level tree
    (O(P·F·depth)).
    """
    import time as _time

    import jax
    import jax.numpy as jnp
    from repro.core import index as _index   # isolated-pass microbench only

    rng = np.random.default_rng(13)
    report = {"structural": {}, "locate": {}}
    mls = [1 << 12, 1 << 14] if quick else [1 << 12, 1 << 14, 1 << 16]
    n_batches = 4
    width = 1024
    for ML in mls:
        n_res = ML * 6                       # ~60-75% leaf occupancy
        mv = max(1 << 16, 1 << int(np.ceil(np.log2(n_res * 1.5))))
        cfg = UruvConfig(leaf_cap=16, max_leaves=ML, max_versions=mv,
                         max_chain=16)
        db0 = Uruv(cfg, policy=LifecyclePolicy(auto_grow=False,
                                               auto_maintain=False))
        resident = (np.arange(n_res, dtype=np.int64) * 2).astype(np.int32)
        perm = rng.permutation(n_res)
        for i in range(0, n_res, 4096):
            b = resident[perm[i:i + 4096]]
            db0.apply(OpBatch.inserts(b, b % 997 + 1))
        n_leaves = int(np.asarray(db0.store.n_leaves))

        # ---- isolated maintenance pass: delta vs flat rebuild ----------
        K = 128                              # splits per structural batch
        seps, leaves = _index.directory(db0.store.index, n_leaves)
        pos = rng.choice(n_leaves - 1, K, replace=False) + 1
        st0 = db0.store
        n_alloc0 = int(np.asarray(st0.n_alloc))
        d_valid = jnp.ones((K,), bool)
        d_gkey = jnp.asarray(seps[pos])
        d_old = jnp.asarray(leaves[pos])
        d_left = jnp.arange(K, dtype=jnp.int32) * 2 + n_alloc0
        d_right = d_left + 1
        d_rkey = jnp.asarray(seps[pos] + 1)

        @jax.jit
        def delta_pass(idx):
            new, oflow = _index.apply_split_delta(
                idx, d_valid, d_gkey, d_old, d_left, d_right, d_rkey)
            return new

        def rebuild_pass(idx):
            return _index.reindex(idx, st0.n_leaves, ML)    # jitted inside

        jax.block_until_ready(delta_pass(st0.index))        # compile
        jax.block_until_ready(rebuild_pass(st0.index))
        dsec = W.timed(
            lambda: jax.block_until_ready(delta_pass(st0.index)))
        rsec = W.timed(
            lambda: jax.block_until_ready(rebuild_pass(st0.index)))
        emit(f"index_delta_pass_ml{ML}", dsec * 1e6, f"{K}splits")
        emit(f"index_rebuild_pass_ml{ML}", rsec * 1e6, f"{n_leaves}leaves")
        emit(f"index_pass_speedup_ml{ML}", rsec / dsec, f"{rsec/dsec:.2f}x")

        fresh = rng.choice(n_res * 2, n_batches * width * 2,
                           replace=False).astype(np.int64)
        fresh = (fresh[fresh % 2 == 1][: n_batches * width]) \
            .astype(np.int32)                # odd keys: all structural
        batches = [fresh[i * width:(i + 1) * width]
                   for i in range(n_batches)]

        def run(rebuild_every_batch):
            db = Uruv.from_store(db0.store,
                                 policy=LifecyclePolicy(
                                     auto_grow=False, auto_maintain=False))
            for b in batches:                # warmup: compile both paths
                db.apply(OpBatch.inserts(b[:width], b[:width] % 997 + 1))
                if rebuild_every_batch:
                    db.reindex()
                break
            db = Uruv.from_store(db0.store,
                                 policy=LifecyclePolicy(
                                     auto_grow=False, auto_maintain=False))
            t0 = _time.perf_counter()
            for b in batches:
                db.apply(OpBatch.inserts(b, b % 997 + 1))
                if rebuild_every_batch:
                    db.reindex()
            jax_block(db.store)
            return (_time.perf_counter() - t0) / n_batches
        delta_s = min(run(False) for _ in range(2))
        rebuild_s = min(run(True) for _ in range(2))
        emit(f"index_apply_delta_ml{ML}", delta_s * 1e6,
             f"{n_leaves}leaves")
        emit(f"index_apply_rebuild_ml{ML}", rebuild_s * 1e6,
             f"{n_leaves}leaves")
        report["structural"][f"ml{ML}"] = {
            "n_leaves": n_leaves,
            "delta_pass_us": round(dsec * 1e6, 1),
            "flat_rebuild_pass_us": round(rsec * 1e6, 1),
            "pass_speedup": round(rsec / dsec, 2),
            "apply_delta_us": round(delta_s * 1e6, 1),
            "apply_rebuild_us": round(rebuild_s * 1e6, 1),
            "apply_speedup": round(rebuild_s / delta_s, 2),
        }

    # ---- (b) locate: depth 1 (flat compare-reduce) vs multi-level -------
    ML = 1 << 10
    n_res = ML * 6
    resident = (np.arange(n_res, dtype=np.int64) * 2).astype(np.int32)
    perm = rng.permutation(n_res)
    probes = resident[rng.integers(0, n_res, 4096)].astype(np.int32)
    for label, fanout, bwidth in (("depth1", ML, 512),
                                  ("multilevel", 16, 4096)):
        cfg = UruvConfig(leaf_cap=16, max_leaves=ML, max_versions=1 << 16,
                         max_chain=16, index_fanout=fanout)
        db = Uruv(cfg, policy=LifecyclePolicy(auto_grow=False,
                                              auto_maintain=False))
        for i in range(0, n_res, bwidth):
            b = resident[perm[i:i + bwidth]]
            db.apply(OpBatch.inserts(b, b % 997 + 1))
        depth = db.store.index.cfg.depth
        ts = db.ts
        sec = W.timed(lambda: db.lookup(probes, ts))
        emit(f"index_locate_{label}", sec * 1e6,
             f"depth{depth};{len(probes)/sec/1e6:.2f}Mlookups/s")
        report["locate"][label] = {
            "depth": depth, "fanout": fanout,
            "us_per_4096": round(sec * 1e6, 1),
            "mlookups_per_s": round(len(probes) / sec / 1e6, 2),
        }
    Path(out_path).write_text(json.dumps(report, indent=2) + "\n")


def jax_block(tree) -> None:
    import jax

    jax.block_until_ready(tree)


def roofline_summary() -> None:
    """Dry-run roofline: dominant term for the hillclimbed cells (full
    table in EXPERIMENTS.md; reads experiments/dryrun artifacts)."""
    from pathlib import Path
    from repro.launch.roofline import analyze_cell

    cells = [
        ("llama3_2_1b", "decode_32k", "single"),
        ("olmoe_1b_7b", "train_4k", "single"),
        ("internvl2_76b", "train_4k", "single"),
    ]
    base = Path(__file__).resolve().parents[1] / "experiments" / "dryrun"
    for a, s, m in cells:
        p = base / f"{a}__{s}__{m}.json"
        if not p.exists():
            continue
        r = analyze_cell(p)
        if r.get("status") != "OK":
            continue
        step = max(r["t_compute_s"], r["t_memory_s"], r["t_collective_s"])
        emit(f"roofline_{a}_{s}", step * 1e6,
             f"{r['bottleneck']}-bound;mfu={r['roofline_fraction_mfu']:.3f}")


WAL_CFG = UruvConfig(leaf_cap=32, max_leaves=1 << 10, max_versions=1 << 16,
                     max_chain=64)
WAL_WIDTH = 1024
WAL_RESIDENT = 8192


def _du(path) -> int:
    return sum(f.stat().st_size for f in Path(path).rglob("*") if f.is_file())


def wal_bench(quick: bool = False, out_path: str = "BENCH_wal.json") -> None:
    """Durability costs (DESIGN.md Sec 14); BENCH_wal.json.

    Workload: a resident working set (prefilled + checkpointed), then the
    serving-table traffic pattern from the ``mixed`` bench — 90% SEARCH /
    5% INSERT / 5% DELETE over live keys — as the WAL tail.

    (a) *Group commit vs fsync-per-plan*: the same traffic through a
    durable client with ``group_commit=1`` (every confirmed plan is
    fsynced before its result is released) vs ``group_commit=16`` (one
    fsync amortizes a window of plans; the coalescer's ``flush`` closes
    it).  A volatile client runs alongside so the WAL overhead itself is
    visible.

    (b) *Delta vs full checkpoint* after a small dirty batch: full save of
    the resident store, ONE narrow update plan, then a delta save — bytes
    on disk and save latency.  GATED: the delta must be <= 25% of the full
    save's bytes (the version-tail fast path + per-leaf row diffs are the
    whole point of the delta chain).

    (c) *Recovery*: reopen the ``group_commit=1`` directory from (a) —
    checkpoint restore + WAL-tail replay at the recorded timestamps.  The
    restore cost is isolated by also recovering a copy of the directory
    taken before the tail was written, so the replay rate is
    (tail ops) / (total - restore).  GATED: >= 50k replayed ops/s on CPU.
    """
    import shutil
    import tempfile
    import time as _time

    rng = np.random.default_rng(17)
    n_traffic = 12 if quick else 24
    manual = LifecyclePolicy(auto_grow=True, auto_maintain=False)
    resident = np.arange(WAL_RESIDENT, dtype=np.int32)
    prefill = [OpBatch.updates(resident[i:i + WAL_WIDTH],
                               resident[i:i + WAL_WIDTH] % 997 + 1)
               for i in range(0, WAL_RESIDENT, WAL_WIDTH)]
    traffic = []
    for _ in range(n_traffic):
        r = rng.random(WAL_WIDTH)
        codes = np.where(r < 0.90, OP_SEARCH,
                         np.where(r < 0.95, OP_INSERT, OP_DELETE),
                         ).astype(np.int32)
        keys = rng.integers(0, WAL_RESIDENT, WAL_WIDTH).astype(np.int32)
        traffic.append(OpBatch(codes, keys, (keys % 997 + 1).astype(np.int32)))
    report = {}
    root = Path(tempfile.mkdtemp(prefix="uruv_wal_bench_"))
    try:
        # ---- (a) group-commit throughput vs fsync-per-plan --------------
        def ingest(tag, gc):
            db = Uruv(WAL_CFG, policy=manual,
                      **({} if gc is None else
                         {"durable_dir": str(root / tag), "group_commit": gc}))
            for p in prefill:
                db.apply(p)
            if gc is not None:
                db.checkpoint()              # prune prefill: WAL = tail only
            if tag == "gc1":                 # restore-only baseline for (c)
                shutil.copytree(root / tag, root / "restore_base")
            t0 = _time.perf_counter()
            for p in traffic:
                db.apply(p)
            if gc is not None:
                db.sync_durable()            # close the group-commit window
            sec = _time.perf_counter() - t0
            if gc is not None:
                db.durability.close()
            return sec, db

        ingest("warmup", None)               # compiles every pass shape
        v_sec, _ = ingest("volatile", None)
        s_sec, _ = ingest("gc1", 1)
        g_sec, _ = ingest("gc16", 16)
        for tag, sec in (("volatile", v_sec), ("fsync_per_plan", s_sec),
                         ("group_commit16", g_sec)):
            emit(f"wal_ingest_{tag}", sec / n_traffic * 1e6,
                 f"{n_traffic * WAL_WIDTH / sec / 1e6:.3f}Mops/s")
        emit("wal_group_commit_speedup", s_sec / g_sec,
             f"{s_sec / g_sec:.2f}x")
        report["group_commit"] = {
            "plans": n_traffic, "width": WAL_WIDTH,
            "volatile_us_per_plan": round(v_sec / n_traffic * 1e6, 1),
            "fsync_per_plan_us": round(s_sec / n_traffic * 1e6, 1),
            "group_commit16_us_per_plan": round(g_sec / n_traffic * 1e6, 1),
            "speedup_vs_fsync_per_plan": round(s_sec / g_sec, 2),
        }

        # ---- (b) delta vs full checkpoint bytes + latency ---------------
        db = Uruv(WAL_CFG, durable_dir=str(root / "delta"), policy=manual)
        for p in prefill:
            db.apply(p)
        t0 = _time.perf_counter()
        db.checkpoint()                      # first save is always full
        full_sec = _time.perf_counter() - t0
        full_step = db.durability.ckpt.latest_step()
        full_bytes = _du(root / "delta" / "ckpt" / f"step_{full_step:08d}")

        dirty = rng.choice(resident, 256, replace=False).astype(np.int32)
        db.apply(OpBatch.updates(dirty, dirty % 31 + 1))   # small dirty batch
        t0 = _time.perf_counter()
        db.checkpoint(delta=True)
        delta_sec = _time.perf_counter() - t0
        delta_step = db.durability.ckpt.latest_step()
        delta_bytes = _du(root / "delta" / "ckpt" / f"step_{delta_step:08d}")
        db.durability.close()
        frac = delta_bytes / full_bytes
        emit("wal_ckpt_full", full_sec * 1e6, f"{full_bytes}B")
        emit("wal_ckpt_delta", delta_sec * 1e6, f"{delta_bytes}B")
        emit("wal_ckpt_delta_fraction", frac * 100, f"{frac:.3f}of_full")
        assert frac <= 0.25, \
            f"delta checkpoint is {frac:.1%} of the full save (gate: <=25%)"
        report["checkpoint"] = {
            "full_bytes": full_bytes, "full_us": round(full_sec * 1e6, 1),
            "delta_bytes": delta_bytes, "delta_us": round(delta_sec * 1e6, 1),
            "delta_fraction_of_full": round(frac, 4),
        }

        # ---- (c) recovery: checkpoint restore + WAL-tail replay ----------
        t0 = _time.perf_counter()
        db_b = Uruv.recover(str(root / "restore_base"), policy=manual)
        base_sec = _time.perf_counter() - t0
        assert db_b.recovery.replayed_plans == 0, db_b.recovery
        db_b.durability.close()
        t0 = _time.perf_counter()
        db_r = Uruv.recover(str(root / "gc1"), policy=manual)
        total_sec = _time.perf_counter() - t0
        assert db_r.recovery.replayed_plans == n_traffic, db_r.recovery
        db_r.durability.close()
        ops = n_traffic * WAL_WIDTH
        replay_sec = max(total_sec - base_sec, 1e-9)
        ops_s = ops / replay_sec
        emit("wal_recovery_restore", base_sec * 1e6, "0replayed")
        emit("wal_recovery_total", total_sec * 1e6, f"{n_traffic}plans")
        emit("wal_recovery_replay", replay_sec * 1e6,
             f"{ops_s / 1e3:.1f}Kops/s")
        assert ops_s >= 50_000, \
            f"recovery replayed {ops_s:.0f} ops/s (gate: >=50k ops/s)"
        report["recovery"] = {
            "restore_us": round(base_sec * 1e6, 1),
            "total_us": round(total_sec * 1e6, 1),
            "replayed_plans": n_traffic,
            "replayed_ops": ops,
            "replay_ops_per_s": round(ops_s),
        }
    finally:
        shutil.rmtree(root, ignore_errors=True)
    Path(out_path).write_text(json.dumps(report, indent=2) + "\n")


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--quick", action="store_true")
    ap.add_argument("--only", default=None,
                    help="fig8|fig9|complexity|kernels|mixed|range|"
                         "lifecycle|index|wal|roofline")
    args = ap.parse_args()
    enable_compile_cache()
    print("name,us_per_call,derived")
    sections = {
        "fig8": lambda: fig8(args.quick),
        "fig9": lambda: fig9(args.quick),
        "complexity": table_complexity,
        "kernels": lambda: kernels(args.quick),
        "mixed": lambda: mixed(args.quick),
        "range": lambda: range_bench(args.quick),
        "lifecycle": lambda: lifecycle_bench(args.quick),
        "index": lambda: index_bench(args.quick),
        "wal": lambda: wal_bench(args.quick),
        "roofline": roofline_summary,
    }
    if args.only:
        sections[args.only]()
        return
    for fn in sections.values():
        fn()


if __name__ == "__main__":
    main()
