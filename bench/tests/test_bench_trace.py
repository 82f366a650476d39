"""The trace reduction, on a small synthetic trace with known intervals."""

import types

import pytest

from harness import manifest
from harness.profile import Trace, clip, union


def test_union_merges_overlapping_and_touching_intervals():
    assert union([(5, 6), (0, 2), (1, 3), (3, 4), (8, 9)]) == \
        [(0, 4), (5, 6), (8, 9)]
    assert clip([(0, 4), (5, 6), (8, 9)], 1, 8.5) == \
        [(1, 4), (5, 6), (8, 8.5)]


def _trace():
    # window [10, 20]; device ops overlap, one starts before the window
    ops = [(9.0, 11.0), (10.5, 12.0), (14.0, 15.0), (14.5, 16.0), (19.0, 21.0)]
    modules = [("jit__bulk_apply", 9.0, 12.0), ("jit__bulk_range", 14.0, 16.0),
               ("jit__maintain", 19.0, 21.0), ("jit_compact", 30.0, 31.0)]
    spans = [("bench.window", 10.0, 20.0), ("bench.pump", 10.0, 12.5),
             ("bench.generate", 12.5, 13.5), ("bench.submit", 13.5, 16.5),
             ("bench.collect", 16.5, 20.0)]
    return Trace([ops], modules, spans, (10.0, 20.0))


def test_busy_and_gaps_within_the_window():
    tr = _trace()
    assert tr.window_s == 10.0
    assert tr.device_ops[0] == [(10.0, 12.0), (14.0, 16.0), (19.0, 20.0)]
    assert tr.busy_s == pytest.approx(5.0)
    assert tr.gaps() == [(12.0, 14.0), (16.0, 19.0)]


def test_busy_is_averaged_over_chips():
    tr = Trace([[(0.0, 4.0)], [(0.0, 2.0)]], [], [], (0.0, 10.0))
    assert tr.busy_s == pytest.approx(3.0)


def test_idle_gaps_are_named_by_the_span_that_covers_most_of_them():
    idle = dict(_trace().idle_by_span())
    # gap 12-14: generate covers 1.0, pump 0.5, submit 0.5 -> generate
    # gap 16-19: collect covers 2.5 -> collect
    assert idle == {"bench.generate": pytest.approx(2.0),
                    "bench.collect": pytest.approx(3.0)}


def test_module_seconds_are_clipped_to_the_window():
    tr = _trace()
    assert tr.module_seconds(["jit__bulk_apply*"]) == pytest.approx(2.0)
    assert tr.module_seconds(["jit__maintain*", "jit_compact*"]) == \
        pytest.approx(1.0)
    assert [n for n, _ in tr.top_modules()] == \
        ["jit__bulk_apply", "jit__bulk_range", "jit__maintain"]


def test_module_seconds_are_none_where_no_module_matches():
    tr = _trace()
    assert tr.module_seconds(["jit__bulk_lookup*"]) is None
    # a module that ran wholly outside the window still matched nothing in it
    assert tr.module_seconds(["jit_compact*"]) is None


@pytest.mark.parametrize("module, counted", [
    ("jit__bulk_apply", True),
    ("jit__bulk_apply_dstore", True),
    ("jit__bulk_range", False),
    ("jit__bulk_lookup", False),
    ("jit_apply_plan", False),
])
def test_the_pass_metric_sums_only_its_own_modules(module, counted):
    """A module the metric does not name (a renamed pass) makes it read
    nothing, never 0 us/op."""
    tr = Trace([[(0.0, 1.0)]], [(module, 0.0, 1.0)], [], (0.0, 1.0))
    run = types.SimpleNamespace(trace=tr, dispatched_ops=1000)
    value = manifest.reader("dev.apply_us_per_op")(run)
    if counted:
        assert value == pytest.approx(1000.0)
    else:
        assert value is None


def test_trace_metrics_read_nothing_without_a_trace():
    run = types.SimpleNamespace(trace=None, dispatched_ops=1000)
    for metric in ("dev.apply_us_per_op", "device.idle_share"):
        assert manifest.reader(metric)(run) is None


def test_idle_share_of_the_synthetic_trace():
    run = types.SimpleNamespace(trace=_trace())
    assert manifest.reader("device.idle_share")(run) == pytest.approx(50.0)
