"""Production mesh builders.

Functions (never module-level constants) so importing this module never
touches jax device state.  Mesh construction goes through
``repro.compat.make_mesh`` (Auto axis types; DESIGN.md Sec 2 notes the
compat rule).
"""

from __future__ import annotations

from repro.compat import make_mesh


def make_production_mesh(*, multi_pod: bool = False):
    """16x16 chips per pod; the multi-pod mesh adds a leading pod axis."""
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return make_mesh(shape, axes)


def make_host_mesh(data: int = 1, model: int = 1):
    """Small mesh over local devices (tests, examples)."""
    return make_mesh((data, model), ("data", "model"))
