"""Set-up seconds: process start to the window (load, reclaim, warm-up,
compiles or compile-cache loads), host clock."""


def read(run):
    return run.setup_s
