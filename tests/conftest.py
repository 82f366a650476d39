"""Hygiene shared by every test module."""

import gc

import jax
import pytest


def _map_limit() -> int:
    try:
        with open("/proc/sys/vm/max_map_count") as f:
            return int(f.read())
    except (OSError, ValueError):
        return 0


_MAP_LIMIT = _map_limit()


@pytest.fixture(autouse=True)
def _release_compiled_code():
    """Drop jax's compile caches once a worker holds half the kernel's
    memory-map limit.

    XLA's CPU backend maps memory for every compiled program (thousands
    of mappings for one store pass) and keeps it while the jit caches hold
    the executable.  A test worker that runs many store tests reaches
    ``vm.max_map_count`` (65530 by default) and the next compile aborts
    the whole process.  Clearing the caches unmaps that code; tests that
    follow only recompile what they use.
    """
    yield
    if not _MAP_LIMIT:
        return
    try:
        with open("/proc/self/maps") as f:
            n_maps = sum(1 for _ in f)
    except OSError:
        return
    if n_maps > _MAP_LIMIT // 2:
        jax.clear_caches()
        gc.collect()
