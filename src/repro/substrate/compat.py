"""The jax mesh and ``shard_map`` API, spelled once for the installed jax (0.9).

Everything else in the repo imports ``shard_map``, ``axis_size``,
``make_mesh`` and ``auto_axis_types`` from here instead of from jax
directly, so a version bump is a change to this one module.
"""

from __future__ import annotations

from typing import Any, Optional, Sequence

import jax
from jax import lax

AxisType = jax.sharding.AxisType


def shard_map(f, *, mesh, in_specs, out_specs, check_vma: Optional[bool] = None,
              **kwargs):
    """``jax.shard_map``; ``check_vma=None`` keeps jax's default."""
    if check_vma is not None:
        kwargs["check_vma"] = check_vma
    return jax.shard_map(f, mesh=mesh, in_specs=in_specs,
                         out_specs=out_specs, **kwargs)


def axis_size(name):
    """``lax.axis_size``: the static size of a named mesh axis."""
    return lax.axis_size(name)


def auto_axis_types(n: int):
    """``(AxisType.Auto,) * n``: the axis types every mesh here is built
    with, so sharding propagation stays implicit inside ``jit``."""
    return (AxisType.Auto,) * n


def make_mesh(axis_shapes: Sequence[int], axis_names: Sequence[str], *,
              devices: Optional[Sequence[Any]] = None, axis_types=None):
    """``jax.make_mesh`` with ``Auto`` axis types unless told otherwise."""
    axis_names = tuple(axis_names)
    kw: dict[str, Any] = {"axis_types": axis_types if axis_types is not None
                          else auto_axis_types(len(axis_names))}
    if devices is not None:
        kw["devices"] = devices
    return jax.make_mesh(tuple(axis_shapes), axis_names, **kw)
