"""Device pass (core/store.py _bulk_apply): device time of the CRUD pass
modules in the window per operation dispatched, from the trace.  Nothing
where no module matches (a renamed pass)."""

MODULES = ("jit__bulk_apply*",)


def read(run):
    if run.trace is None or run.dispatched_ops <= 0:
        return None
    seconds = run.trace.module_seconds(MODULES)
    return None if seconds is None else seconds * 1e6 / run.dispatched_ops
