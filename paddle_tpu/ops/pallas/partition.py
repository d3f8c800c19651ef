"""Run a Pallas kernel per shard under a multi-device mesh.

GSPMD cannot partition a Mosaic kernel: jax 0.9 refuses to lower one inside
a multi-device jit ("Mosaic kernels cannot be automatically partitioned.
Please wrap the call in a shard_map"), and inside a shard_map it accepts one
only when EVERY mesh axis is manual. The models express parallelism as
sharding annotations and leave the collectives to GSPMD, so each kernel
entry point wraps itself: under a multi-device mesh the kernel runs inside
a `jax.shard_map` over every axis that is not manual yet — nested, when the
caller is already inside the pipeline's or the MoE layer's partial-manual
shard_map — on its local block.

The specs follow the framework's layout conventions, by the ROLE of each
dimension: a batch dimension is split over the data axes (dp, sharding, ep),
a heads dimension over mp, a sequence dimension over sep, an experts
dimension over ep. A dimension whose size an axis does not divide stays
whole. A guess that differs from where GSPMD actually put the operand is
still correct — shard_map reshards to the spec it is given — and costs a
collective; the kernels themselves are row-, head- or expert-independent, so
every such split is exact.
"""

from __future__ import annotations

import jax
from jax.sharding import PartitionSpec as P

__all__ = ["shard_plan"]

_ROLE_AXES = {
    "batch": ("dp", "sharding", "ep"),
    "heads": ("mp",),
    "seq": ("sep",),
    "experts": ("ep",),
    "cols": ("dp", "sharding"),
}


class _Plan:
    def __init__(self, mesh, auto):
        self.mesh = mesh
        self.auto = auto

    def axes(self, role, size):
        """The mesh axes (a name, a tuple of names, or None) that split a
        dimension of `size` playing `role`."""
        picked, n = [], 1
        for a in _ROLE_AXES[role]:
            k = self.mesh.shape.get(a, 1)
            if a in self.auto and k > 1 and size % (n * k) == 0:
                picked.append(a)
                n *= k
        if not picked:
            return None
        return picked[0] if len(picked) == 1 else tuple(picked)

    def run(self, fn, args, in_specs, out_specs):
        return jax.shard_map(
            fn, mesh=self.mesh, in_specs=tuple(in_specs), out_specs=out_specs,
            axis_names=set(self.auto), check_vma=False)(*args)


def _spans_devices(x) -> bool:
    if isinstance(x, jax.core.Tracer):
        return True  # traced under the mesh's jit
    sharding = getattr(x, "sharding", None)
    return sharding is not None and len(sharding.device_set) > 1


def shard_plan(*operands):
    """A plan for running a kernel over `operands` per shard, or None when
    it can be called directly: no multi-device mesh is active, every mesh
    axis is manual already, or the operands are concrete single-device
    arrays (an eager call — its one-device jit lowers Mosaic as is)."""
    ctx = jax.sharding.get_abstract_mesh()
    if not ctx.empty and ctx.manual_axes:
        mesh, manual = ctx, set(ctx.manual_axes)  # inside a shard_map
    else:
        from ...distributed import env as _env

        mesh, manual = _env.get_global_mesh(), set()
        if mesh is None or mesh.size == 1:
            return None
        if not any(_spans_devices(x) for x in operands):
            return None
    auto = tuple(a for a in mesh.axis_names if a not in manual)
    return _Plan(mesh, auto) if auto else None

