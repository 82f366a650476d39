"""Ahead-of-time compiles of the store's device passes for a TPU v5e.

The TPU compiler is installed without a chip: a described ``v5e:2x2``
topology lets XLA and Mosaic compile (not run) the main path at the
paper configuration's shapes (``configs/uruv_paper.py``), so a change
that the chip's compiler would refuse fails here.  The topology is
described inside a module fixture, never at import: only one process
at a time may load the TPU library, and every test worker imports this
file.
"""

import functools

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.configs.uruv_paper import config as paper_config
from repro.core import store as S
from repro.kernels.uruv_search.uruv_search import leaf_slots

PAPER = paper_config()


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies

    try:
        return topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # no TPU compiler in this environment
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


@pytest.fixture(scope="module", autouse=True)
def no_persistent_cache():
    """A compile for a described chip is written to the persistent cache
    but cannot be read back without one; keep the cache out of it."""
    from jax.experimental.compilation_cache import compilation_cache as cc

    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    cc.reset_cache()
    yield
    jax.config.update("jax_enable_compilation_cache", was)
    cc.reset_cache()


def _spec(sharding, shape, dtype=jnp.int32):
    return jax.ShapeDtypeStruct(shape, dtype, sharding=sharding)


@pytest.fixture(scope="module")
def paper_store(one_chip):
    """The paper configuration's store as shapes on one described chip."""
    shapes = jax.eval_shape(functools.partial(S.create, PAPER.store))
    return jax.tree.map(lambda a: _spec(one_chip, a.shape, a.dtype), shapes)


def _compile(fn, *args):
    compiled = jax.jit(fn).lower(*args).compile()
    mem = compiled.memory_analysis()
    assert mem.argument_size_in_bytes > 0
    return compiled


def test_bulk_apply_compiles_for_v5e(one_chip, paper_store):
    P = PAPER.batch
    arr = _spec(one_chip, (P,))
    _compile(functools.partial(S._bulk_apply, backend="xla"),
             paper_store, arr, arr, arr, None, None, None)


def test_bulk_range_compiles_for_v5e(one_chip, paper_store):
    Q = 64
    arr = _spec(one_chip, (Q,))
    _compile(functools.partial(S._bulk_range, max_results=1024,
                               scan_leaves=16, max_rounds=8, backend="xla"),
             paper_store, arr, arr, arr)


def test_bulk_lookup_compiles_for_v5e(one_chip, paper_store):
    arr = _spec(one_chip, (PAPER.batch,))
    _compile(functools.partial(S._bulk_lookup, backend="xla"),
             paper_store, arr, arr)


def test_leaf_slots_kernel_compiles_for_v5e(one_chip):
    P, L = PAPER.batch, PAPER.store.leaf_cap
    compiled = _compile(functools.partial(leaf_slots, interpret=False),
                        _spec(one_chip, (P, L)), _spec(one_chip, (P,)))
    assert "tpu_custom_call" in compiled.as_text()
