"""Serving front end (ISSUE 7): pipelined apply_nowait/confirm, the
coalescer's bit-exact future semantics, sentinel-key regressions, and the
deep admission queue.

The coalescer property test is the load-bearing one: per-client results
under skewed bursty closed-loop load must be BIT-EXACT (values, found,
timestamps, range pages) against replaying the coalescer's own dispatch
log through a synchronous client — pipelining, speculation, rejection
replay, and future slicing must all be invisible in results.
"""

import collections
import time

import numpy as np
import pytest

from repro.api import (
    KEY_MAX, NOT_FOUND, OP_RANGE, OpBatch, Uruv, UruvConfig,
)
from repro.serve.coalescer import AdmissionPolicy, Coalescer, OpFuture
from repro.serve.engine import prefix_hash

CFG = UruvConfig(leaf_cap=8, max_leaves=512, max_versions=1 << 14,
                 max_chain=16)


# --------------------------------------------------------------- sentinels
def test_prefix_hash_never_emits_sentinel_keys():
    """Regression for the sentinel-key silent-loss bug: DEMONSTRABLY FAILS
    on the pre-fix ``prefix_hash`` (``& 0x7FFFFFFF`` then ``or 1``).

    The adversarial token below makes the pre-fix single-token hash land
    exactly on ``0x7FFFFFFF`` = 2**31 - 1 = KEY_MAX, the padding sentinel:
    the store accepts the INSERT and ``lookup`` then never finds it, so
    the cached prefix is silently lost forever.  The fixed hash clamps
    into [1, 2**31 - 4] — always a valid, findable key.
    """
    fnv, mul = 2166136261, 16777619
    t_keymax = (0x7FFFFFFF - 1 - fnv * mul) % (2 ** 31)
    t_pad = (0x7FFFFFFE - 1 - fnv * mul) % (2 ** 31)
    # pre-fix: ((fnv * mul + t + 1) & 0x7FFFFFFF) == the two sentinels
    assert (fnv * mul + t_keymax + 1) & 0x7FFFFFFF == KEY_MAX
    assert (fnv * mul + t_pad + 1) & 0x7FFFFFFF == KEY_MAX - 1

    for tokens in ([t_keymax], [t_pad], [0], [1, 2, 3], list(range(64))):
        h = prefix_hash(tokens)
        assert 1 <= h <= 2 ** 31 - 4, (tokens, h)

    # end-to-end: the adversarial prefix round-trips through the table
    db = Uruv(CFG)
    for t in ([t_keymax], [t_pad]):
        k = prefix_hash(t)
        db.apply(OpBatch.inserts([k], [777]))
        assert int(db.lookup([k])[0]) == 777


def test_prefix_hash_stable_across_calls():
    toks = [5, 17, 5, 99]
    assert prefix_hash(toks) == prefix_hash(list(toks))
    assert prefix_hash(toks[:2]) != prefix_hash(toks)  # prefixes differ


@pytest.mark.parametrize("bad", [KEY_MAX, KEY_MAX - 1])
@pytest.mark.parametrize("build", [
    lambda k: OpBatch.inserts([k], [1]),
    lambda k: OpBatch.deletes([3, k]),
    lambda k: OpBatch.searches([k]),
    lambda k: OpBatch.ranges([k], [5]),
    lambda k: OpBatch.ranges([1], [k]),
    lambda k: OpBatch.from_ops([(0, k, 1)]),
])
def test_builders_reject_both_sentinel_keys(build, bad):
    """Front-door guard (satellite of the silent-loss fix): every plan
    builder raises on BOTH sentinels — KEY_MAX (the padding sentinel) and
    KEY_MAX - 1 (the kernels' internal pad) — before any device work."""
    with pytest.raises(ValueError, match="sentinel"):
        build(bad)


def test_updates_and_lookup_keep_keymax_as_mask_encoding():
    """`OpBatch.updates` and `Uruv.lookup` keep KEY_MAX as the DOCUMENTED
    NOP/mask-out encoding (the legacy announce shape); only the
    undocumented KEY_MAX - 1 is rejected."""
    b = OpBatch.updates([5, KEY_MAX], [50, 1])
    assert np.asarray(b.codes).tolist()[1] == 3  # OP_NOP
    with pytest.raises(ValueError):
        OpBatch.updates([KEY_MAX - 1], [1])
    db = Uruv(CFG)
    db.apply(OpBatch.inserts([5], [50]))
    assert db.lookup([5, KEY_MAX]).tolist() == [50, NOT_FOUND]
    with pytest.raises(ValueError):
        db.lookup([KEY_MAX - 1])


# --------------------------------------------------- apply_nowait / confirm
def test_apply_nowait_confirm_matches_sync_apply():
    """Deferred dispatch is invisible: values AND timestamps bit-exact
    with the synchronous path on an identical store."""
    rng = np.random.default_rng(3)
    db_a, db_b = Uruv(CFG), Uruv(CFG)
    for _ in range(8):
        n = int(rng.integers(1, 20))
        keys = rng.integers(1, 500, n).astype(np.int32)
        codes = rng.integers(0, 3, n).astype(np.int32)  # INSERT/DELETE/SEARCH
        plan = OpBatch(codes, keys, (keys % 97 + 1).astype(np.int32))
        pending = db_a.apply_nowait(plan, pad_to_pow2=True)
        ra = db_a.confirm(pending)
        if ra is None:                       # rejected: the documented path
            full = db_a.apply(pending.batch)
            ra = type(full)(
                values=np.asarray(full.values)[:n],
                found=np.asarray(full.found)[:n],
                timestamps=np.asarray(full.timestamps)[:n],
                range_index=full.range_index,
                range_pages=full.range_pages,
                range_resume=full.range_resume)
        rb = db_b.apply(plan, pad_to_pow2=True)
        np.testing.assert_array_equal(np.asarray(ra.values)[:n],
                                      np.asarray(rb.values))
        np.testing.assert_array_equal(np.asarray(ra.timestamps)[:n],
                                      np.asarray(rb.timestamps))
        np.testing.assert_array_equal(np.asarray(ra.found)[:n],
                                      np.asarray(rb.found))
    assert db_a.ts == db_b.ts


def test_apply_nowait_rejects_range_and_empty():
    db = Uruv(CFG)
    with pytest.raises(ValueError, match="RANGE"):
        db.apply_nowait(OpBatch.ranges([1], [5]))
    with pytest.raises(ValueError, match="non-empty"):
        db.apply_nowait(OpBatch.empty())


def test_rejection_rolls_back_and_replays_bit_exact():
    """A capacity-rejected speculative plan leaves no trace: confirm
    returns None, the clock is restored, and replaying the SAME padded
    plan through apply() lands on the same timestamps a never-pipelined
    client would produce."""
    keys = np.arange(1, 33, dtype=np.int32)  # 32 new keys, one leaf region
    db = Uruv(CFG)
    ts0 = db.ts
    pending = db.apply_nowait(OpBatch.inserts(keys, keys * 10),
                              pad_to_pow2=True)
    assert db.confirm(pending) is None          # leaf_cap=8 -> fast-path reject
    assert db.ts == ts0                          # clock rolled back
    res = db.apply(pending.batch)                # slow-path replay
    assert np.asarray(res.timestamps)[0] == ts0
    # mirror client that never speculated
    db2 = Uruv(CFG)
    res2 = db2.apply(OpBatch.inserts(keys, keys * 10), pad_to_pow2=True)
    np.testing.assert_array_equal(np.asarray(res.values)[:32],
                                  np.asarray(res2.values))
    assert db.ts == db2.ts
    np.testing.assert_array_equal(db.lookup(keys), db2.lookup(keys))


def test_depth_two_speculation_sees_prior_plan():
    db = Uruv(CFG)
    p1 = db.apply_nowait(OpBatch.inserts([10, 11], [100, 110]),
                         pad_to_pow2=True)
    p2 = db.apply_nowait(OpBatch.searches([10, 11]), pad_to_pow2=True)
    r1, r2 = db.confirm(p1), db.confirm(p2)
    assert r1 is not None and r2 is not None
    assert np.asarray(r2.values).tolist() == [100, 110]


# ------------------------------------------------------------- coalescer
def _mirror_check(coalescer, futures, cfg, prefill_plan):
    """Replay the coalescer's dispatch log through a fresh synchronous
    client and demand bit-exact per-client results."""
    db2 = Uruv(cfg)
    if prefill_plan is not None:
        db2.apply(prefill_plan)
    resolved = {}
    for plan, spans in coalescer.dispatch_log:
        res = db2.apply(plan)  # plan is exactly as dispatched (padded)
        for fut, a, b in spans:
            resolved[id(fut)] = (
                np.asarray(res.values)[a:b],
                np.asarray(res.found)[a:b],
                np.asarray(res.timestamps)[a:b],
                [(int(p) - a, res.page(int(p)))
                 for p in np.asarray(res.range_index) if a <= int(p) < b],
            )
    assert len(resolved) == len(futures)
    for fut in futures:
        got = fut.result()
        want_v, want_f, want_t, want_pages = resolved[id(fut)]
        np.testing.assert_array_equal(np.asarray(got.values), want_v)
        np.testing.assert_array_equal(np.asarray(got.found), want_f)
        np.testing.assert_array_equal(np.asarray(got.timestamps), want_t)
        got_pages = [(int(p), got.page(int(p)))
                     for p in np.asarray(got.range_index)]
        assert got_pages == want_pages


def test_coalescer_bit_exact_under_skewed_bursty_load():
    """THE property test: zipfian-skewed bursty closed-loop traffic with
    RANGE-mixed requests through the pipelined coalescer produces, per
    client, the bit-exact values / found / TIMESTAMPS / range pages of
    the same coalesced plans applied synchronously — speculation,
    rejection replay (leaf_cap=8 guarantees rejections), sync-path
    RANGE detours, and future slicing are all invisible."""
    cfg = CFG
    rng = np.random.default_rng(17)
    hot = rng.choice(2000, 24, replace=False).astype(np.int32) + 1
    prefill = OpBatch.inserts(hot, hot * 3)
    db = Uruv(cfg)
    db.apply(prefill)
    c = Coalescer(db, AdmissionPolicy(start_width=16, max_width=64,
                                      base_deadline_s=1e-4), record=True)
    futures = []
    for wave in range(12):
        for _ in range(int(rng.integers(2, 10))):   # bursty wave sizes
            n = int(rng.integers(1, 5))
            parts = []
            for _ in range(n):
                r = rng.random()
                # zipfian-ish: 70% of traffic on the 24 hot keys
                k = int(hot[rng.integers(0, 4)]) if r < 0.7 \
                    else int(rng.integers(1, 4000))
                if r < 0.25:
                    parts.append(OpBatch.inserts([k], [k % 89 + 1]))
                elif r < 0.4:
                    parts.append(OpBatch.deletes([k]))
                elif r < 0.9:
                    parts.append(OpBatch.searches([k]))
                else:                                # RANGE mixed into CRUD
                    parts.append(OpBatch.ranges([k], [k + 50]))
            futures.append(c.submit(OpBatch.concat(*parts)))
        c.pump(force=bool(wave % 3 == 0))
        if wave % 4 == 1:
            futures[int(rng.integers(0, len(futures)))].result()
    c.flush()
    assert all(f.done for f in futures)
    assert c.stats["plans"] == len(c.dispatch_log)
    assert c.stats["plans_sync"] > 0              # RANGE detours happened
    _mirror_check(c, futures, cfg, prefill)


def test_coalescer_rejection_replay_bit_exact():
    """Force fast-path rejections WITH a trailing speculative plan in
    flight; the replay path must still be bit-exact vs the mirror."""
    cfg = CFG
    db = Uruv(cfg)
    c = Coalescer(db, AdmissionPolicy(start_width=64, max_width=64),
                  record=True)
    futures = [c.submit(OpBatch.inserts(np.arange(100, 132, dtype=np.int32),
                                        np.int32(7)))]
    c.pump(force=True)                      # dispatch (will reject: 1 leaf)
    futures.append(c.submit(OpBatch.searches(np.arange(100, 104,
                                                       dtype=np.int32))))
    c.pump(force=True)                      # second plan speculates behind it
    c.flush()
    assert c.stats["plans_rejected"] >= 1 and c.stats["replays"] >= 2
    _mirror_check(c, futures, cfg, None)


def test_coalescer_deep_queue_drains_fifo():
    """10k-deep admission queue (the O(n) list.pop(0) regression class):
    submits are O(1), the drain is linear in plans, results stay FIFO."""
    db = Uruv(UruvConfig(leaf_cap=64, max_leaves=1 << 11,
                         max_versions=1 << 15))
    c = Coalescer(db, AdmissionPolicy(start_width=64, max_width=64))
    n = 10_000
    keys = np.random.default_rng(5).choice(200_000, n, replace=False) \
        .astype(np.int32) + 1
    futs = [c.submit(OpBatch.inserts([int(k)], [int(k) % 50 + 1]))
            for k in keys]
    assert isinstance(c.queue, collections.deque)
    assert c.stats["max_queue_depth"] == n
    c.flush()
    ts = np.array([int(np.asarray(f.result().timestamps)[0]) for f in futs])
    assert (np.diff(ts) > 0).all()          # FIFO linearization order
    assert db.lookup(keys[:100]).tolist() == \
        [int(k) % 50 + 1 for k in keys[:100]]


def test_coalescer_exclusive_store_donation_single_depth():
    db = Uruv(CFG)
    c = Coalescer(db, AdmissionPolicy(start_width=8), exclusive=True)
    assert c._depth == 1
    futs = [c.submit(OpBatch.inserts([k], [k * 2])) for k in range(1, 9)]
    c.flush()
    assert [int(np.asarray(f.result().values)[0]) for f in futs] == [-1] * 8
    assert db.lookup(np.arange(1, 9)).tolist() == \
        (np.arange(1, 9) * 2).tolist()


def test_coalescer_adapts_width_on_rejection():
    db = Uruv(CFG)
    c = Coalescer(db, AdmissionPolicy(start_width=64, max_width=64))
    c.submit(OpBatch.inserts(np.arange(1, 33, dtype=np.int32), np.int32(1)))
    c.flush()                               # rejects -> halves target
    assert c.target_width < 64

    # hot (all-duplicate) traffic marks the segment and contracts policy
    c2 = Coalescer(db, AdmissionPolicy(start_width=8))
    for _ in range(8):
        c2.submit(OpBatch.inserts([77], [1]))
    c2.flush()
    assert c2.stats["hot_segments"] >= 1
    assert c2._deadline_s() < c2.policy.base_deadline_s

    of = OpFuture(c2, 1)
    assert not of.done


def _request_stream(rng, n):
    """``n`` range-free requests of one to four ops over a few hundred
    keys, with one 32-key insert run that a leaf of 8 cannot take in a
    fast-path pass (so a pipelined plan is rejected and replayed)."""
    reqs = []
    for i in range(n):
        if i == n // 3:
            keys = np.arange(3000, 3032, dtype=np.int32)
            reqs.append(OpBatch.inserts(keys, keys % 13 + 1))
            continue
        parts = []
        for _ in range(int(rng.integers(1, 5)) if rng.random() < 0.4 else 1):
            k, r = int(rng.integers(1, 400)), rng.random()
            parts.append(OpBatch.inserts([k], [k % 89 + 1]) if r < 0.4
                         else OpBatch.deletes([k]) if r < 0.55
                         else OpBatch.searches([k]))
        reqs.append(OpBatch.concat(*parts))
    return reqs


def _expected_results(dispatch_log, cfg, prefill):
    """Each request's Result, sliced the general way from ``Uruv.apply``
    of the coalesced plans, in order, on a fresh client."""
    db = Uruv(cfg)
    db.apply(prefill)
    want = {}
    for plan, spans in dispatch_log:
        res = db.apply(plan)
        pos = np.asarray(res.range_index).tolist()
        for fut, a, b in spans:
            mine = [j for j, p in enumerate(pos) if a <= p < b]
            want[id(fut)] = (
                np.asarray(res.values)[a:b], np.asarray(res.found)[a:b],
                np.asarray(res.timestamps)[a:b],
                np.asarray([pos[j] - a for j in mine], np.int32),
                [np.asarray(res.range_pages[j]) for j in mine],
                np.asarray([int(np.asarray(res.range_resume)[j])
                            for j in mine], np.int32))
    return want


@pytest.mark.parametrize("with_ranges", [False, True])
def test_range_free_and_general_materialize_match_apply(with_ranges):
    """One stream of one-op and multi-op requests, served range-free (no
    plan carries a RANGE op, a rejected-plan replay included) or with
    RANGE requests mixed in (their plans run synchronously): every
    future's Result matches ``Uruv.apply`` of the same coalesced plans,
    sliced request by request, field for field; ``plans_range_free``
    counts exactly the plans with no RANGE op; a request with no RANGE op
    gets the shared read-only empty range fields; and each ``done_t`` lies
    between its plan's settle start and the clock after the pump that
    settled it."""
    rng = np.random.default_rng(23)
    prefill = OpBatch.inserts(np.arange(1, 400, 3, dtype=np.int32),
                              np.int32(5))
    db = Uruv(CFG)
    db.apply(prefill)
    co = Coalescer(db, AdmissionPolicy(start_width=32, max_width=32),
                   record=True)
    settle_t = {}
    materialize = co._materialize

    def recording(futs, bounds, res, plan_no):
        t = time.monotonic()
        for f in futs:
            settle_t[id(f)] = t
        return materialize(futs, bounds, res, plan_no)

    co._materialize = recording
    futs, pending = [], []

    def step(drive):
        drive()
        now = time.monotonic()
        for f in [f for f in pending if f.done]:
            assert settle_t[id(f)] <= f.done_t <= now
            pending.remove(f)

    for i, req in enumerate(_request_stream(rng, 120)):
        if with_ranges and i % 9 == 4:
            k = int(rng.integers(1, 350))
            rq = OpBatch.ranges([k], [k + 40])
            if i % 2 == 0:               # two RANGE ops behind a SEARCH
                rq = OpBatch.concat(OpBatch.searches([k]), OpBatch.ranges(
                    [k, k + 90], [k + 40, k + 120]))
            futs.append(co.submit(rq))
            pending.append(futs[-1])
        futs.append(co.submit(req))
        pending.append(futs[-1])
        if i % 7 == 6:
            step(lambda: co.pump(force=True))
    step(co.flush)
    assert not pending and all(f.done for f in futs)

    has_range = [bool((np.asarray(plan.codes) == OP_RANGE).any())
                 for plan, _ in co.dispatch_log]
    assert co.stats["plans"] == len(co.dispatch_log)
    assert co.stats["plans_range_free"] == has_range.count(False)
    assert co.stats["plans_rejected"] >= 1
    if with_ranges:
        assert any(has_range) and co.stats["plans_sync"] > 0
    else:
        assert co.stats["plans_range_free"] == co.stats["plans"]

    want = _expected_results(co.dispatch_log, CFG, prefill)
    assert len(want) == len(futs)
    n_pages = 0
    for f in futs:
        got = f.result()
        v, fd, ts, idx, pages, resume = want[id(f)]
        for g, w in ((got.values, v), (got.found, fd), (got.timestamps, ts),
                     (got.range_index, idx), (got.range_resume, resume)):
            g = np.asarray(g)
            assert g.dtype == w.dtype and g.shape == w.shape
            np.testing.assert_array_equal(g, w)
        assert isinstance(got.range_pages, tuple)
        assert len(got.range_pages) == len(pages)
        for g, w in zip(got.range_pages, pages):
            np.testing.assert_array_equal(np.asarray(g), w)
        n_pages += len(pages)
        if not pages:                    # the shared, read-only empties
            assert not got.range_index.flags.writeable
            assert not got.range_resume.flags.writeable
    assert n_pages == sum(int((np.asarray(p.codes) == OP_RANGE).sum())
                          for p, _ in co.dispatch_log)
    assert (n_pages > 0) == with_ranges
