"""Client (api/client.py): host milliseconds per plan in
``Uruv.apply_nowait`` — the host-to-device copies, the store pytree and
the jitted pass's enqueue (executor ``stats["dispatch_ns"]``, the span
``uruv.apply_nowait``)."""


def read(run):
    plans = run.counters.get("plans", 0)
    ns = run.counters.get("dispatch_ns")
    return ns / plans / 1e6 if plans and ns is not None else None
