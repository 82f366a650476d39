"""Front end (serve/coalescer.py): mean milliseconds a request waited in
the admission queue, from its submit to its plan taking it
(``Coalescer.stats["queue_wait_ns"]`` over ``["requests_dispatched"]``)."""


def read(run):
    n = run.counters.get("requests_dispatched", 0)
    ns = run.counters.get("queue_wait_ns")
    return ns / n / 1e6 if n and ns is not None else None
