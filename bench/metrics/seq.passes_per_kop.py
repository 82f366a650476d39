"""Sequencer (core/batch.py apply_mixed, api/executors.py): device passes
per 1000 operations dispatched in the window (``Uruv.stats``)."""


def read(run):
    if run.dispatched_ops <= 0:
        return None
    return run.counters.get("device_passes", 0) * 1000.0 / run.dispatched_ops
