"""DK123 fixture: compat.shard_map — compat/direct parity of the axis
checks; a partial-manual map is valid and stays silent.
Parsed only."""

from jax.sharding import PartitionSpec as P

from distkeras_tpu.parallel.mesh import make_mesh_grid
from distkeras_tpu.utils import compat
from distkeras_tpu.utils.compat import shard_map as compat_shard_map


def partial_manual(f):
    mesh = make_mesh_grid(2, 4, axis_names=("stages", "tp"))
    return compat.shard_map(  # NOT flagged: partial-manual is valid
        f, mesh, in_specs=(P("stages"),), out_specs=P("stages"),
        axis_names=("stages",),
    )


def full_manual(f):
    mesh = make_mesh_grid(2, 4, axis_names=("stages", "tp"))
    return compat.shard_map(  # NOT flagged: every mesh axis is manual
        f, mesh, in_specs=(P("stages"),), out_specs=P("stages"),
        axis_names=("stages", "tp"),
    )


def default_auto(f):
    mesh = make_mesh_grid(2, 4, axis_names=("stages", "tp"))
    return compat.shard_map(  # NOT flagged: axis_names=None (all manual)
        f, mesh, in_specs=(P("stages"),), out_specs=P("stages"),
    )


def compat_bad_axis(f):
    mesh = make_mesh_grid(2, 4, axis_names=("stages", "tp"))
    return compat.shard_map(  # line 37: DK123 same axis check as direct
        f, mesh, in_specs=(P("model"),), out_specs=P(),
    )


def aliased_bad_axis(f):
    mesh = make_mesh_grid(2, 4, axis_names=("stages", "tp"))
    return compat_shard_map(  # line 44: DK123 through the import alias too
        f, mesh, in_specs=(P("model"),), out_specs=P(),
    )
