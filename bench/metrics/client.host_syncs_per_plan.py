"""Client (api/client.py): device-to-host reads per plan on the request
path (executor ``stats["host_syncs"]``): the accept flag, the clock and
the values of each pipelined plan, and every read of the synchronous
path and the lifecycle."""


def read(run):
    plans = run.counters.get("plans", 0)
    n = run.counters.get("host_syncs")
    return n / plans if plans and n is not None else None
