"""Shims over the installed jax (0.9) for the APIs the store relies on.

  * :func:`shard_map` — ``jax.shard_map`` with the varying-manual-axes
    check (``check_vma``) off by default.  The store's passes were written
    against shard_map's untyped replication semantics: branches of a
    ``lax.cond`` inside a shard (``store._bulk_apply_impl``'s search
    resolve) may differ in which mesh axes they vary over, and every
    collective in ``repro.core.sharded`` is explicit.
  * :func:`make_mesh` — ``jax.make_mesh`` with Auto axis types.

Import from here instead of calling jax at each call site.
"""

from __future__ import annotations

import jax
from jax.sharding import AxisType


def shard_map(f, **kwargs):
    """``jax.shard_map`` with ``check_vma=False`` unless the caller sets it."""
    kwargs.setdefault("check_vma", False)
    return jax.shard_map(f, **kwargs)


def make_mesh(axis_shapes, axis_names):
    """``jax.make_mesh`` with Auto axis types."""
    return jax.make_mesh(
        axis_shapes, axis_names,
        axis_types=(AxisType.Auto,) * len(axis_names),
    )
