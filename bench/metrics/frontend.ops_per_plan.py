"""Front end (serve/coalescer.py): real, unpadded operations per plan the
coalescer dispatched in the window (``Coalescer.stats``)."""


def read(run):
    plans = run.counters.get("plans", 0)
    return run.dispatched_ops / plans if plans else None
