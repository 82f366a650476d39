"""Front end (serve/coalescer.py): host milliseconds per plan spent
slicing the plan's result into per-request results and resolving their
futures (``Coalescer.stats["materialize_ns"]``, the span
``uruv.coalescer.materialize``)."""


def read(run):
    plans = run.counters.get("plans", 0)
    ns = run.counters.get("materialize_ns")
    return ns / plans / 1e6 if plans and ns is not None else None
