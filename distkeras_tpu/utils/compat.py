"""The one surface the engines write ``shard_map`` and ``axis_size`` against.

Thin pass-throughs to ``jax.shard_map`` and ``lax.axis_size``: the wrapper
keeps every engine's call sites (and dkshape's resolution through them) on
one name and one keyword spelling — ``axis_names=None`` means "manual over
every mesh axis", which ``jax.shard_map`` spells by omitting the argument.
"""

from __future__ import annotations

import jax
from jax import lax

__all__ = ["shard_map", "axis_size"]


def shard_map(f, mesh, in_specs, out_specs, check_vma=True, axis_names=None):
    kwargs = {"axis_names": axis_names} if axis_names else {}
    return jax.shard_map(f, mesh=mesh, in_specs=in_specs,
                         out_specs=out_specs, check_vma=check_vma, **kwargs)


def axis_size(axis_name):
    """Static size of a named mapped axis."""
    return lax.axis_size(axis_name)
