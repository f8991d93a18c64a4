"""The JAX API names the repo uses, resolved in one place.

The repo runs on the installed JAX (0.9). Names whose home moved between
releases are imported here, once, so call sites never reach into
``jax.core`` or ``jax.experimental`` themselves:

  shard_map    — ``jax.shard_map`` (``check_vma`` keyword).
  axis_size    — ``jax.lax.axis_size``.
  make_mesh    — ``jax.make_mesh`` with ``Auto`` axis types by default.
  use_mesh     — ``jax.set_mesh`` context manager.
  Jaxpr,
  Literal      — jaxpr node types for the static analyzers
                 (``jax.extend.core``).

tests/test_compat.py asserts the whole public API imports cleanly.
"""
from __future__ import annotations

from typing import Any

import jax
from jax.extend.core import Jaxpr, Literal  # noqa: F401 — re-exported

AxisType = jax.sharding.AxisType


def shard_map(f, *, mesh, in_specs, out_specs, check_vma: bool = True,
              **kwargs):
    """``jax.shard_map`` with the repo's keyword signature."""
    return jax.shard_map(f, mesh=mesh, in_specs=in_specs,
                         out_specs=out_specs, check_vma=check_vma, **kwargs)


def axis_size(axis_name):
    """``jax.lax.axis_size`` (valid inside shard_map bodies)."""
    return jax.lax.axis_size(axis_name)


def make_mesh(axis_shapes, axis_names, *, axis_types: Any = None,
              devices=None):
    """``jax.make_mesh`` with ``Auto`` axis types unless told otherwise."""
    kwargs = {}
    if devices is not None:
        kwargs["devices"] = devices
    if axis_types is None:
        axis_types = (AxisType.Auto,) * len(tuple(axis_names))
    return jax.make_mesh(tuple(axis_shapes), tuple(axis_names),
                         axis_types=axis_types, **kwargs)


def use_mesh(mesh):
    """Context manager making `mesh` the ambient mesh."""
    return jax.set_mesh(mesh)
