"""Spans and counters on the served path (DESIGN.md Sec 12, "Tracing").

A `Coalescer` over a small store serves a few pipelined plans of
single-operation requests; its counters and the executor's must account
for every plan, and the spans it leaves in a profiler trace must nest
inside the caller's annotation, carry the plan number and sum to the
counters.
"""

import glob
import os
import time

import jax
import numpy as np
import pytest
from jax.profiler import ProfileData, TraceAnnotation

from repro.api import OpBatch, Uruv, UruvConfig
from repro.serve.coalescer import AdmissionPolicy, Coalescer

CFG = UruvConfig(leaf_cap=16, max_leaves=1024, max_versions=1 << 15,
                 max_chain=32)
KEYS = np.arange(1, 1025, dtype=np.int32) * 7
WIDTH = 256
PLANS = 6

# counter (in Coalescer.stats or the executor's stats) -> its span
TIMED = {
    "build_ns": "uruv.coalescer.build",
    "materialize_ns": "uruv.coalescer.materialize",
    "dispatch_ns": "uruv.apply_nowait",
    "device_wait_ns": "uruv.confirm.wait",
}


def _loaded_coalescer():
    db = Uruv(CFG)
    for i in range(0, len(KEYS), WIDTH):      # plans of the served width
        db.insert(KEYS[i:i + WIDTH], KEYS[i:i + WIDTH] + 1)
    co = Coalescer(db, AdmissionPolicy(start_width=WIDTH, max_width=WIDTH))
    return db, co


def _serve(co, rng, plans=PLANS):
    """Submit ``plans`` plans' worth of single-op searches and updates of
    loaded keys (no structural change, so no plan is rejected) and pump
    until every future is resolved — without ``flush``, whose lifecycle
    tick is a host read of its own."""
    futs = []
    for _ in range(plans * WIDTH):
        k = int(rng.choice(KEYS))
        op = (OpBatch.searches([k]) if rng.random() < 0.5
              else OpBatch.inserts([k], [int(rng.integers(1, 1 << 20))]))
        futs.append(co.submit(op))
    while not all(f.done for f in futs):
        assert co.pump(force=True)
    return futs


def _delta(after, before):
    return {k: after[k] - before.get(k, 0) for k in after}


def _record_builds(co):
    """Wrap ``co._build``: per plan, the clock just before and after the
    build, the futures it took, and the ``queue_wait_ns`` it added."""
    builds, build = [], co._build

    def recording(reqs):
        wait0, t0 = co.stats["queue_wait_ns"], time.monotonic()
        out = build(reqs)
        builds.append((t0, time.monotonic(), [fut for fut, _ in reqs],
                       co.stats["queue_wait_ns"] - wait0))
        return out

    co._build = recording
    return builds


def test_counters_account_for_every_plan_and_request():
    db, co = _loaded_coalescer()
    rng = np.random.default_rng(5)
    _serve(co, rng, plans=2)                     # compile the plan width
    builds = _record_builds(co)
    c0, e0 = dict(co.stats), dict(db.executor.stats)
    futs = _serve(co, rng)
    c, e = _delta(co.stats, c0), _delta(db.executor.stats, e0)

    assert c["plans"] == PLANS and c["plans_rejected"] == 0
    assert c["plans_sync"] == 0
    assert c["requests_dispatched"] == c["requests"] == len(futs)
    # the pipelined path reads the accept flag, the clock and the values
    assert e["host_syncs"] == 3 * c["plans"]
    for key in ("build_ns", "materialize_ns", "queue_wait_ns"):
        assert c[key] > 0, key
    for key in ("dispatch_ns", "device_wait_ns"):
        assert e[key] > 0, key
    # queue_wait_ns is the sum over each plan's requests of (the one
    # instant its build took them - submit_t): that instant lies inside
    # the build, after every submit and before every result
    assert len(builds) == PLANS
    assert sum(len(fs) for _, _, fs, _ in builds) == len(futs)
    assert sum(w for *_, w in builds) == c["queue_wait_ns"]
    for t0, t1, fs, wait_ns in builds:
        taken = (wait_ns * 1e-9 + sum(f.submit_t for f in fs)) / len(fs)
        assert t0 - 1e-6 <= taken <= t1 + 1e-6
        for f in fs:
            assert f.submit_t <= t0 and t1 <= f.done_t


def test_queue_counters_match_a_per_request_count():
    """``requests``, ``ops``, ``max_queue_depth`` and ``requests_dispatched``
    against a count kept request by request, over waves of one-op and
    multi-op requests that sometimes fit the plan whole and sometimes do
    not (the greedy take)."""
    db, co = _loaded_coalescer()
    rng = np.random.default_rng(11)
    taken, take = [], co._take

    def counting(width):
        reqs = take(width)
        taken.append(sum(fut.n_ops for fut, _ in reqs))
        assert taken[-1] <= width or len(reqs) == 1
        requests[0] += len(reqs)
        return reqs

    co._take = counting
    requests = [0]               # requests taken off the queue so far
    c0 = dict(co.stats)
    n_req = n_ops = depth = 0
    for wave in range(8):
        for _ in range(int(rng.integers(50, 3 * WIDTH))):
            n = 1 if rng.random() < 0.7 else int(rng.integers(2, 9))
            co.submit(OpBatch.searches(rng.choice(KEYS, n)))
            n_req, n_ops = n_req + 1, n_ops + n
            depth = max(depth, n_req - requests[0])
        for _ in range(int(rng.integers(0, 4))):
            co.pump(force=True)
    co.flush()
    c = _delta(co.stats, c0)
    assert c["requests"] == c["requests_dispatched"] == n_req == requests[0]
    assert c["ops"] == n_ops == sum(taken)
    assert co.stats["max_queue_depth"] == depth
    assert c["plans"] == len(taken) and len(co.queue) == 0


def test_counter_keys_exist_from_construction_and_do_not_collide():
    db, co = _loaded_coalescer()
    front = {"build_ns", "materialize_ns", "queue_wait_ns",
             "requests_dispatched"}
    client = {"dispatch_ns", "device_wait_ns", "host_syncs"}
    assert front <= set(co.stats)
    assert client <= set(db.executor.stats)
    assert not set(co.stats) & set(db.executor.stats)


def test_sync_apply_counts_its_host_reads():
    """``Uruv.apply`` of a CRUD plan: the clock, one read of the accept
    flag and results, and the lifecycle tick's occupancy read."""
    db, _ = _loaded_coalescer()
    before = db.executor.stats["host_syncs"]
    db.apply(OpBatch.searches(KEYS[:8]))
    assert db.executor.stats["host_syncs"] - before == 3


def _host_events(path):
    pd = ProfileData.from_file(path)
    out = []
    for plane in pd.planes:
        if not plane.name.startswith("/host:"):
            continue
        for line in plane.lines:
            for ev in line.events:
                out.append((ev.name, ev.start_ns, ev.start_ns + ev.duration_ns,
                            dict(ev.stats)))
    return out


# jax's profiler bindings build the type of an event's arguments lazily,
# and Python warns that the type names no module
@pytest.mark.filterwarnings(
    "ignore:builtin type event_stats has no __module__:DeprecationWarning")
def test_spans_nest_in_the_caller_carry_the_plan_and_sum_to_counters(tmp_path):
    db, co = _loaded_coalescer()
    rng = np.random.default_rng(9)
    _serve(co, rng, plans=2)                     # compile outside the trace
    c0, e0 = dict(co.stats), dict(db.executor.stats)
    jax.profiler.start_trace(str(tmp_path))
    try:
        with TraceAnnotation("test.serve"):
            _serve(co, rng)
    finally:
        jax.profiler.stop_trace()
    c, e = _delta(co.stats, c0), _delta(db.executor.stats, e0)
    counters = {**c, **e}
    path, = glob.glob(os.path.join(tmp_path, "**", "*.xplane.pb"),
                      recursive=True)
    events = _host_events(path)

    (_, outer_a, outer_b, _), = [x for x in events if x[0] == "test.serve"]
    ours = [x for x in events if x[0].startswith("uruv.")]
    names = {x[0] for x in ours}
    assert set(TIMED.values()) | {"uruv.coalescer.retire",
                                  "uruv.confirm.fetch"} <= names
    for name, a, b, _ in ours:
        assert outer_a <= a and b <= outer_b, name

    plans = set(range(c0["plans"], c0["plans"] + PLANS))
    for name in ("uruv.coalescer.build", "uruv.coalescer.retire",
                 "uruv.coalescer.materialize"):
        seen = {int(args["plan"]) for n, _, _, args in ours if n == name}
        assert seen == plans, name
    # every confirm span lies inside the retire span of its plan
    retire = [(a, b) for n, a, b, _ in ours if n == "uruv.coalescer.retire"]
    for name, a, b, _ in ours:
        if name.startswith("uruv.confirm."):
            assert any(ra <= a and b <= rb for ra, rb in retire), name

    for key, name in TIMED.items():
        spans_ns = sum(b - a for n, a, b, _ in ours if n == name)
        assert spans_ns == pytest.approx(counters[key], rel=0.10), key
