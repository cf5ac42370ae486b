"""Single home for the jax symbols whose location has moved between
releases.

Every module in this repo that needs ``AxisType``, ``shard_map``,
``axis_size`` or mesh construction imports it from here, so the next jax
bump is a one-file change.

Supported: jax 0.9.0.
"""
from __future__ import annotations

import jax

# Stable across all supported versions — re-exported so callers never
# import from jax.sharding directly.
# repro-lint: disable=R8 -- re-export surface: parallel/*, core.rao, launch.dryrun import these from here
from jax.sharding import AxisType, Mesh, NamedSharding, PartitionSpec  # noqa: F401


def _version_tuple(v: str):
    parts = []
    for tok in v.split(".")[:3]:
        digits = "".join(ch for ch in tok if ch.isdigit())
        parts.append(int(digits) if digits else 0)
    return tuple(parts)


JAX_VERSION = _version_tuple(jax.__version__)

#: meshes carry explicit per-axis types
HAS_AXIS_TYPE = True


def shard_map(f, *, mesh, in_specs, out_specs, check_vma=None):
    """``jax.shard_map``; ``check_vma=None`` keeps jax's default."""
    kwargs = {} if check_vma is None else {"check_vma": check_vma}
    return jax.shard_map(f, mesh=mesh, in_specs=in_specs,
                         out_specs=out_specs, **kwargs)


def axis_size(axis_name):
    """Size of the named mesh axis inside ``shard_map``."""
    return jax.lax.axis_size(axis_name)


def make_mesh(shape, axes, *, axis_types=None, devices=None):
    """``jax.make_mesh`` with every axis ``Auto`` unless ``axis_types``
    says otherwise."""
    axes = tuple(axes)
    if axis_types is None:
        axis_types = (AxisType.Auto,) * len(axes)
    return jax.make_mesh(tuple(shape), axes, axis_types=tuple(axis_types),
                         devices=devices)
