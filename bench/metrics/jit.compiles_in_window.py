"""JIT / launch: executables compiled or loaded from the compile cache
inside the window (``jax.monitoring`` backend-compile events)."""


def read(run):
    return run.compiles_in_window
