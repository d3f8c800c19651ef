"""Distributed environment + global mesh state.

Reference: python/paddle/distributed/parallel.py (init_parallel_env :978,
ParallelEnv) and the per-axis comm groups of HybridCommunicateGroup.

TPU-native model: one controller process per host; "world size" is the number
of devices (chips), not processes. Collectives are compiled XLA ops over a
global `jax.sharding.Mesh` whose named axes are the hybrid-parallel dims
[dp, pp, sharding, sep, mp] — the direct analog of the reference's
CommunicateTopology order (fleet/base/topology.py:73-80).
"""

from __future__ import annotations

import os
import threading

import numpy as np

_state = threading.local()

AXIS_ORDER = ("dp", "pp", "sharding", "sep", "ep", "mp")


def _jax():
    import jax

    return jax


def is_initialized() -> bool:
    return getattr(_state, "initialized", False)


def init_parallel_env(strategy=None):
    """reference: paddle.distributed.init_parallel_env (parallel.py:978).

    Multi-host: if PADDLE_MASTER/PADDLE_TRAINER_ENDPOINTS-style envs (or
    JAX_COORDINATOR_ADDRESS) are present, bootstrap jax.distributed — the
    TCPStore-equivalent rendezvous (reference: phi TCPStore tcp_store.h:121).
    """
    if is_initialized():
        return ParallelEnv()
    coord = (
        os.environ.get("JAX_COORDINATOR_ADDRESS")
        or os.environ.get("PADDLE_MASTER")
        or os.environ.get("MASTER_ADDR")
    )
    nproc = int(os.environ.get("PADDLE_TRAINERS_NUM", os.environ.get("WORLD_SIZE", "1")))
    pid = int(os.environ.get("PADDLE_TRAINER_ID", os.environ.get("RANK", "0")))
    if coord and nproc > 1:
        port = os.environ.get("MASTER_PORT")
        addr = coord if ":" in coord else f"{coord}:{port or 8476}"
        _jax().distributed.initialize(
            coordinator_address=addr, num_processes=nproc, process_id=pid
        )
    _state.initialized = True
    return ParallelEnv()


def get_rank(group=None):
    """Device-rank of this controller's first addressable device within the
    group (process-level rank on multi-host)."""
    if group is not None:
        return group.rank
    return _jax().process_index()


def get_world_size(group=None):
    if group is not None:
        return group.nranks
    try:
        return _jax().device_count()
    except Exception:
        return 1


def get_process_count():
    return _jax().process_count()


class ParallelEnv:
    """reference: paddle.distributed.ParallelEnv."""

    @property
    def rank(self):
        return get_rank()

    @property
    def local_rank(self):
        return get_rank()

    @property
    def world_size(self):
        return get_world_size()

    @property
    def nranks(self):
        return get_world_size()

    @property
    def device_id(self):
        return 0

    @property
    def dev_id(self):
        return 0


# --------------------------------------------------------------------------- #
# global mesh
# --------------------------------------------------------------------------- #

_mesh_lock = threading.Lock()
_global_mesh = None


def set_global_mesh(mesh):
    global _global_mesh
    with _mesh_lock:
        _global_mesh = mesh


def get_global_mesh():
    return _global_mesh


def build_mesh(dp=1, pp=1, sharding=1, sep=1, mp=1, ep=1, devices=None):
    """Build the hybrid mesh with named axes in reference topology order.

    Axis placement on hardware: trailing axes change fastest over the device
    list, so mp (highest-bandwidth collectives) lands on neighbouring chips —
    the same locality rule the reference uses when carving NCCL rings from
    the rank grid. `ep` (expert parallelism — the MoE dispatch/combine
    all-to-all axis, ISSUE-14) sits just outside mp for the same reason:
    a2a volume per token beats everything but mp's per-layer all-reduces.
    """
    jax = _jax()
    if devices is None:
        devices = np.array(jax.devices())
    else:
        devices = np.array(devices)
    total = dp * pp * sharding * sep * ep * mp
    if total > devices.size:
        raise ValueError(
            f"mesh {dp}x{pp}x{sharding}x{sep}x{ep}x{mp}={total} exceeds "
            f"{devices.size} devices"
        )
    devices = devices[:total].reshape(dp, pp, sharding, sep, ep, mp)
    from jax.sharding import Mesh

    mesh = Mesh(devices, AXIS_ORDER)
    set_global_mesh(mesh)
    return mesh


def mesh_shape(mesh=None) -> dict:
    """axis -> size of `mesh` (default: the global mesh) over AXIS_ORDER,
    with absent axes reported as 1 — the shape the planner's MeshPlan
    artifact stores, so a live mesh and a stored plan compare directly."""
    m = mesh if mesh is not None else get_global_mesh()
    if m is None:
        return {a: 1 for a in AXIS_ORDER}
    return {a: int(m.shape.get(a, 1)) for a in AXIS_ORDER}


def default_mesh():
    """Global mesh, defaulting to pure-dp over all devices."""
    m = get_global_mesh()
    if m is None:
        m = build_mesh(dp=len(_jax().devices()))
    return m


def constrain_array(a, spec):
    """with_sharding_constraint on a raw array against the global mesh,
    stripping axes that are Manual in the current shard_map context (a
    concrete all-Auto mesh sharding poisons downstream op types there).
    Shared by the mpu layers and MoE; returns `a` unchanged when no mesh."""
    import warnings

    import jax
    from jax.sharding import NamedSharding, PartitionSpec as P

    mesh = get_global_mesh()
    if mesh is None:
        return a

    def strip(entry, manual):
        if entry is None:
            return None
        if isinstance(entry, tuple):
            kept = tuple(e for e in entry if e not in manual)
            return kept if kept else None
        return None if entry in manual else entry

    try:
        ctx = jax.sharding.get_abstract_mesh()
        if not ctx.empty and ctx.manual_axes:
            manual = set(ctx.manual_axes)
            spec = P(*[strip(s, manual) for s in spec])
            return jax.lax.with_sharding_constraint(a, NamedSharding(ctx, spec))
        return jax.lax.with_sharding_constraint(a, NamedSharding(mesh, spec))
    except Exception as e:  # pragma: no cover - diagnostic path
        warnings.warn(f"sharding constraint {spec} skipped: {e}")
        return a
