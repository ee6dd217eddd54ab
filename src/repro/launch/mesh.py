"""Production mesh construction.

A function (not a module-level constant) so importing this module never
touches jax device state — device count is locked at first jax init, and
only ``dryrun.py`` forces the 512-placeholder-device environment.
"""
from __future__ import annotations

import jax
from jax.sharding import AxisType


def _auto_mesh(shape, axes):
    """A mesh whose axes are all Auto: the models place activations with
    ``with_sharding_constraint``, which JAX refuses on Explicit axes (the
    ``jax.make_mesh`` default)."""
    return jax.make_mesh(shape, axes, axis_types=(AxisType.Auto,) * len(axes))


def make_production_mesh(*, multi_pod: bool = False):
    """16×16 single-pod (256 chips) or 2×16×16 two-pod (512 chips) mesh."""
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return _auto_mesh(shape, axes)


def make_host_mesh(model: int | None = None):
    """Small mesh over whatever devices exist (tests / CPU examples)."""
    n = len(jax.devices())
    model = model or (2 if n % 2 == 0 and n > 1 else 1)
    data = n // model
    return _auto_mesh((data, model), ("data", "model"))
