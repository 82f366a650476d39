"""Operations completed in the window over the window's seconds."""


def read(run):
    r = run.records
    done = r.done & (r.done_t >= run.t0) & (r.done_t <= run.t1)
    return int(done.sum()) / run.seconds
