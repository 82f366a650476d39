"""Client (api/client.py): share of the window the host spent blocked in
``Uruv.confirm`` waiting for the device to finish a pass (executor
``stats["device_wait_ns"]``, the span ``uruv.confirm.wait``)."""


def read(run):
    ns = run.counters.get("device_wait_ns")
    if ns is None or run.seconds <= 0:
        return None
    return 100.0 * ns / (run.seconds * 1e9)
