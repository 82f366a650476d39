"""Front end (serve/coalescer.py): host milliseconds per plan spent
building it — taking its requests off the queue, concatenating and
padding them, the skew probe (``Coalescer.stats["build_ns"]``, the span
``uruv.coalescer.build``)."""


def read(run):
    plans = run.counters.get("plans", 0)
    ns = run.counters.get("build_ns")
    return ns / plans / 1e6 if plans and ns is not None else None
