"""The readers of the program's own counters (``Coalescer.stats`` and the
executor's ``stats``), on a synthetic run with known counter changes."""

import types

import pytest

from harness import manifest

# a 10 s window of 20 plans, 5,000 requests
COUNTERS = {
    "plans": 20, "requests_dispatched": 5000,
    "build_ns": 100_000_000, "materialize_ns": 250_000_000,
    "queue_wait_ns": 40_000_000_000, "dispatch_ns": 30_000_000,
    "device_wait_ns": 1_500_000_000, "host_syncs": 60,
}

EXPECTED = {
    "frontend.build_ms_per_plan": 5.0,
    "frontend.materialize_ms_per_plan": 12.5,
    "frontend.queue_wait_ms": 8.0,
    "client.dispatch_ms_per_plan": 1.5,
    "client.device_wait_share": 15.0,
    "client.host_syncs_per_plan": 3.0,
}

# the counter each reader needs besides its denominator
NUMERATOR = {
    "frontend.build_ms_per_plan": "build_ns",
    "frontend.materialize_ms_per_plan": "materialize_ns",
    "frontend.queue_wait_ms": "queue_wait_ns",
    "client.dispatch_ms_per_plan": "dispatch_ns",
    "client.device_wait_share": "device_wait_ns",
    "client.host_syncs_per_plan": "host_syncs",
}


def _run(counters, seconds=10.0):
    return types.SimpleNamespace(counters=counters, seconds=seconds,
                                 trace=None, dispatched_ops=0)


@pytest.mark.parametrize("name", sorted(EXPECTED))
def test_reader_divides_its_counter_by_its_denominator(name):
    assert manifest.reader(name)(_run(dict(COUNTERS))) == \
        pytest.approx(EXPECTED[name])


@pytest.mark.parametrize("name", sorted(EXPECTED))
def test_reader_reads_nothing_where_the_program_has_no_counter(name):
    """A program without the counter (the parent of the change that added
    it) reads as nothing, never 0."""
    counters = dict(COUNTERS)
    del counters[NUMERATOR[name]]
    assert manifest.reader(name)(_run(counters)) is None


@pytest.mark.parametrize("name", sorted(EXPECTED))
def test_reader_reads_nothing_over_an_empty_denominator(name):
    counters = dict(COUNTERS, plans=0, requests_dispatched=0)
    assert manifest.reader(name)(_run(counters, seconds=0.0)) is None


def test_the_six_metrics_are_per_layer_program_counters_of_the_cell():
    spec = {m["name"]: m for m in manifest.load()["per_layer"]}
    for name in EXPECTED:
        assert spec[name]["source"] == "program_counter"
        assert spec[name]["workloads"] == ["paper.fig8c"]
