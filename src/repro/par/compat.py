"""One seam for the JAX collective surface the distributed code paths use.

The sharded hot paths (Gram psum, sharded top-k merge, compressed-gradient
all-reduce) call these helpers rather than ``jax.shard_map`` /
``jax.lax.pcast`` directly, so a future API move is a one-file change:

  * ``shard_map(f, mesh=..., in_specs=..., out_specs=..., check_vma=...)``
  * ``mark_varying(tree, axes)`` — ``jax.lax.pcast(..., to="varying")``
      over a pytree; a scan carry created inside ``shard_map`` must be
      varying before collectives see it.
  * ``abstract_mesh(shape, names)`` — a device-less ``AbstractMesh``.
  * ``axis_size(axis)`` / ``axis_index(axis)`` — size and flat row-major
      index over one named mesh axis or a tuple of them.
  * ``shard_map_eqn_body(eqn)`` / ``shard_map_eqn_device_count(eqn)``
      Jaxpr-introspection helpers for cost accounting: the sub-jaxpr and
      global device multiplier of a ``shard_map`` equation.

Written against the installed JAX (see ``pyproject.toml``).
"""
from __future__ import annotations

from typing import Any, Sequence

import jax
from jax.sharding import AbstractMesh

__all__ = [
    "shard_map", "mark_varying", "abstract_mesh", "axis_size", "axis_index",
    "shard_map_eqn_body", "shard_map_eqn_device_count",
]


def shard_map(f, *, mesh, in_specs, out_specs, check_vma: bool | None = None,
              **kwargs):
    """``jax.shard_map``; ``check_vma=None`` keeps JAX's default."""
    if check_vma is not None:
        kwargs["check_vma"] = check_vma
    return jax.shard_map(f, mesh=mesh, in_specs=in_specs,
                         out_specs=out_specs, **kwargs)


def mark_varying(tree: Any, axes: Sequence[str] | None) -> Any:
    """Mark every leaf varying over ``axes`` (identity for no axes)."""
    if not axes:
        return tree
    return jax.tree.map(
        lambda x: jax.lax.pcast(x, tuple(axes), to="varying"), tree)


def abstract_mesh(shape: Sequence[int], names: Sequence[str]) -> AbstractMesh:
    """Device-less mesh from parallel ``shape`` / ``names`` sequences."""
    shape, names = tuple(shape), tuple(names)
    if len(shape) != len(names):
        raise ValueError(f"shape {shape} and names {names} length mismatch")
    return AbstractMesh(shape, names)


def axis_size(axis) -> int:
    """Size of a named mesh axis (or product over a tuple of axes)."""
    return jax.lax.axis_size(axis)


def axis_index(axis) -> jax.Array:
    """Flat linear index over one or more named mesh axes (row-major)."""
    return jax.lax.axis_index(axis if isinstance(axis, str) else tuple(axis))


# ---------------------------------------------------------------------------
# Jaxpr introspection (cost accounting)
# ---------------------------------------------------------------------------


def shard_map_eqn_body(eqn) -> Any | None:
    """The (open) body jaxpr of a ``shard_map`` equation, or None."""
    cj = eqn.params.get("jaxpr")
    if cj is None:
        return None
    return cj.jaxpr if hasattr(cj, "jaxpr") else cj


def shard_map_eqn_device_count(eqn) -> float:
    """Global device multiplier of a ``shard_map`` equation: body shapes
    are per-shard, so costs scale back to global by the mesh size."""
    return float(eqn.params["mesh"].size)
