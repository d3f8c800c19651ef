"""Communication groups + collectives.

Reference: ProcessGroup (paddle/phi/core/distributed/collective/process_group.h:48)
with NCCL/Gloo backends, python Group objects (python/paddle/distributed/
communication/group.py), functional collectives (communication/*.py).

TPU-native redesign (SURVEY §5.8): there is no runtime comm library to wrap.
A Group names a set of mesh axes; collectives exist in two forms:

1. **Compiled form** (the performance path): `primitives.*` — thin wrappers
   over lax.psum/all_gather/ppermute/all_to_all for use INSIDE shard_map'd
   programs. XLA lowers these to ICI/DCN collectives.
2. **Eager form** (API parity with `dist.all_reduce(t)`): in the
   single-controller model every rank's tensor is a slice of a global,
   leading-axis-stacked array [nranks, ...]. The eager ops are jitted
   global-array transformations with identical per-rank semantics
   (all_reduce -> every slice becomes the reduction; all_gather -> the
   stacked array; etc.). On sharded global arrays XLA executes these as real
   cross-chip collectives; on replicated arrays they are local math.
"""

from __future__ import annotations

import numpy as np

from ..framework.core import Tensor, to_tensor
from . import env as _env

__all__ = [
    "Group",
    "new_group",
    "get_group",
    "all_reduce",
    "all_gather",
    "all_gather_object",
    "reduce",
    "reduce_scatter",
    "broadcast",
    "broadcast_object_list",
    "scatter",
    "scatter_object_list",
    "alltoall",
    "alltoall_single",
    "send",
    "recv",
    "isend",
    "irecv",
    "barrier",
    "record_collective_traffic",
    "ReduceOp",
    "P2POp",
    "batch_isend_irecv",
    "wait",
    "destroy_process_group",
]


class ReduceOp:
    SUM = "sum"
    MAX = "max"
    MIN = "min"
    PROD = "prod"
    AVG = "avg"


# Telemetry: every eager collective bumps per-op call/byte counters in the
# observability registry. The compiled-form `primitives` are deliberately
# uninstrumented — they execute inside traces, where emitting a host-side
# metric is exactly the GL006 hazard graftlint flags.
_obs_handles = None  # lazy HandleCache (metrics imported on first use)


def record_collective_traffic(op: str, nbytes: int, calls: int = 1):
    """Bump collective_{calls,bytes}_total{op=} directly — the byte-count
    form for callers that know the volume without holding the tensors
    (the MoE compiled-path a2a accounting, distributed/moe_comm.py)."""
    global _obs_handles
    if _obs_handles is None:
        from ..observability.metrics import HandleCache

        _obs_handles = HandleCache(lambda reg: (
            reg.counter("collective_calls_total",
                        "eager collective invocations", ("op",)),
            reg.counter("collective_bytes_total",
                        "payload bytes through eager collectives", ("op",)),
        ))
    calls_, bytes_ = _obs_handles.get()
    calls_.inc(calls, op=op)
    if nbytes:
        bytes_.inc(int(nbytes), op=op)


def _tensor_bytes(*tensors):
    nbytes = 0
    for t in tensors:
        v = getattr(t, "_value", t)
        shape = getattr(v, "shape", None)
        if shape is not None:
            nbytes += int(np.prod(shape)) * np.dtype(v.dtype).itemsize
    return nbytes


def _record_collective(op: str, *tensors):
    record_collective_traffic(op, _tensor_bytes(*tensors))


_groups: dict[int, "Group"] = {}
_next_gid = [0]


class Group:
    """A set of ranks; on TPU it corresponds to mesh axis positions.

    `axis_names` ties the group to mesh axes for the compiled path; for eager
    semantics only `nranks` matters.
    """

    def __init__(self, ranks=None, gid=None, axis_names=None, mesh=None):
        self.id = gid if gid is not None else _next_gid[0]
        _next_gid[0] = max(_next_gid[0], self.id) + 1
        if ranks is None:
            ranks = list(range(_env.get_world_size()))
        self.ranks = list(ranks)
        self.nranks = len(self.ranks)
        self.axis_names = tuple(axis_names) if axis_names else None
        self.mesh = mesh
        _groups[self.id] = self

    @property
    def rank(self):
        r = _env.get_rank()
        return self.ranks.index(r) if r in self.ranks else -1

    @property
    def world_size(self):
        return self.nranks

    @property
    def process_group(self):
        return self

    def get_group_rank(self, rank):
        return self.ranks.index(rank) if rank in self.ranks else -1

    def __repr__(self):
        return f"Group(id={self.id}, nranks={self.nranks}, axes={self.axis_names})"


_default_group: Group | None = None


def _get_default_group() -> Group:
    global _default_group
    if _default_group is None:
        _default_group = Group(gid=0)
    return _default_group


def get_group(gid=0) -> Group:
    if gid in _groups:
        return _groups[gid]
    return _get_default_group()


def new_group(ranks=None, backend=None, timeout=None) -> Group:
    """reference: python/paddle/distributed/collective.py new_group."""
    return Group(ranks=ranks)


def destroy_process_group(group=None):
    global _default_group
    # sweep this rank's residual store keys for the group's communicators
    # (bounded leak otherwise — see eager_multiproc.cleanup_group_keys)
    from . import eager_multiproc as mp

    if mp.nprocs() > 1 and mp._group_seq:
        from .store import create_or_get_global_tcp_store

        try:
            mp.cleanup_group_keys(create_or_get_global_tcp_store(),
                                  gid=None if group is None else group.id)
        except Exception:
            pass
    if group is None:
        _groups.clear()
        _default_group = None
    else:
        _groups.pop(group.id, None)


def _grp(group):
    return group if group is not None else _get_default_group()


class _Task:
    """Completed-task handle (collectives dispatch synchronously into XLA's
    async runtime; Wait is a device sync — reference ProcessGroup::Task)."""

    def __init__(self, tensor=None):
        self._tensor = tensor

    def wait(self):
        if self._tensor is not None:
            # block until the XLA computation materializes
            _ = self._tensor._value.block_until_ready() if hasattr(self._tensor._value, "block_until_ready") else None
        return True

    def is_completed(self):
        return True


def _reduce_over_axis(val, op, axis):
    import jax.numpy as jnp

    fns = {
        ReduceOp.SUM: jnp.sum, "sum": jnp.sum,
        ReduceOp.MAX: jnp.max, "max": jnp.max,
        ReduceOp.MIN: jnp.min, "min": jnp.min,
        ReduceOp.PROD: jnp.prod, "prod": jnp.prod,
        ReduceOp.AVG: jnp.mean, "avg": jnp.mean,
    }
    if op not in fns:
        raise ValueError(f"unsupported reduce op {op!r}")
    return fns[op](val, axis=axis)


def _reduce_stacked(val, op, n):
    import jax.numpy as jnp

    if op in (ReduceOp.SUM, "sum"):
        red = jnp.sum(val, axis=0, keepdims=True)
    elif op in (ReduceOp.MAX, "max"):
        red = jnp.max(val, axis=0, keepdims=True)
    elif op in (ReduceOp.MIN, "min"):
        red = jnp.min(val, axis=0, keepdims=True)
    elif op in (ReduceOp.PROD, "prod"):
        red = jnp.prod(val, axis=0, keepdims=True)
    elif op in (ReduceOp.AVG, "avg"):
        red = jnp.mean(val, axis=0, keepdims=True)
    else:
        raise ValueError(f"unknown reduce op {op}")
    return jnp.broadcast_to(red, val.shape)


def _is_stacked(tensor, group):
    return tensor.ndim >= 1 and tensor.shape[0] == group.nranks


def _mp_active(group, allow_subgroup=False):
    """The cross-process eager backend when jax.distributed has N > 1
    controllers (multi-controller CPU/TPU pods), else None. Subgroup eager
    collectives are refused rather than silently wrong, except where the
    caller has a subgroup implementation (allow_subgroup)."""
    from . import eager_multiproc as mp

    n = mp.nprocs()
    if n <= 1:
        return None
    if group.nranks not in (n,) and not allow_subgroup:
        raise NotImplementedError(
            "eager collectives over subgroups are not supported in "
            "multi-process mode; use the compiled shard_map primitives")
    return mp


def _op_name(op):
    return op if isinstance(op, str) else str(op)


def all_reduce(tensor, op=ReduceOp.SUM, group=None, sync_op=True):
    """Every rank slice becomes the group reduction. For a stacked global
    array [nranks, ...] this reduces over the rank axis; XLA turns it into an
    ICI all-reduce when the axis is sharded. Updates `tensor` in place and
    returns a task, like the reference. Under multi-controller
    (jax.process_count() > 1) each process contributes its local tensor and
    the reduction runs over the global device set."""
    _record_collective("all_reduce", tensor)
    import jax.numpy as jnp

    g = _grp(group)
    if g.nranks == 1:
        return _Task(tensor)
    mp = _mp_active(g, allow_subgroup=True)
    if mp is not None:
        if g.nranks == mp.nprocs():
            tensor._value = jnp.asarray(
                mp.allreduce_value(np.asarray(tensor._value), _op_name(op)))
        else:
            # subgroup (new_group semantics / the mp group of a dp x mp
            # topology): member-only reduce over the TCPStore — non-members
            # are not involved, so member-only call patterns are safe
            from .store import create_or_get_global_tcp_store

            tensor._value = jnp.asarray(mp.store_allreduce_group(
                create_or_get_global_tcp_store(), np.asarray(tensor._value),
                g.ranks, _op_name(op), gid=g.id))
        return _Task(tensor)
    if _is_stacked(tensor, g):
        tensor._value = _reduce_stacked(tensor._value, op, g.nranks)
    # replicated tensor in single-controller: every rank already holds the
    # same value; reduction over identical copies is the value itself for
    # SUM only when contributions differ per process — multi-host handles
    # that inside compiled steps, not here.
    return _Task(tensor)


def reduce(tensor, dst, op=ReduceOp.SUM, group=None, sync_op=True):
    _record_collective("reduce", tensor)
    import jax.numpy as jnp

    g = _grp(group)
    if g.nranks == 1:
        return _Task(tensor)
    mp = _mp_active(g)
    if mp is not None:
        red = mp.allreduce_value(np.asarray(tensor._value), _op_name(op))
        if mp.rank() == dst:
            tensor._value = jnp.asarray(red)
        return _Task(tensor)
    if _is_stacked(tensor, g):
        red = _reduce_stacked(tensor._value, op, g.nranks)
        # only dst's slice carries the result; others keep their input
        idx = g.get_group_rank(dst) if dst in g.ranks else dst
        tensor._value = tensor._value.at[idx].set(red[idx])
    return _Task(tensor)


def all_gather(tensor_list, tensor, group=None, sync_op=True):
    """reference: dist.all_gather(list, t) — after the call the list holds
    every rank's tensor. Global-array view: slices of the stacked array;
    multi-controller: one compiled all-gather over the processes."""
    _record_collective("all_gather", tensor)
    g = _grp(group)
    if g.nranks == 1:
        tensor_list.append(Tensor(tensor._value))
        return _Task()
    mp = _mp_active(g)
    if mp is not None:
        import jax.numpy as jnp

        rows = mp.allgather_values(np.asarray(tensor._value))
        for i in range(rows.shape[0]):
            tensor_list.append(Tensor(jnp.asarray(rows[i])))
        return _Task()
    if _is_stacked(tensor, g) and tensor.ndim >= 1:
        for i in range(g.nranks):
            tensor_list.append(Tensor(tensor._value[i]))
    else:
        for _ in range(g.nranks):
            tensor_list.append(Tensor(tensor._value))
    return _Task()


def all_gather_object(object_list, obj, group=None):
    _record_collective("all_gather_object")
    g = _grp(group)
    if g.nranks == 1:
        object_list.append(obj)
        return _Task()
    mp = _mp_active(g)
    if mp is not None:
        object_list.extend(mp.allgather_objects(obj))
        return _Task()
    for _ in range(g.nranks):
        object_list.append(obj)
    return _Task()


def reduce_scatter(tensor, tensor_list, op=ReduceOp.SUM, group=None, sync_op=True):
    """Each rank gets one shard of the reduction. Input: list of [nranks,...]
    stacked tensors (or tensors per destination)."""
    _record_collective("reduce_scatter", *tensor_list)
    import jax.numpy as jnp

    g = _grp(group)
    vals = [t._value if isinstance(t, Tensor) else jnp.asarray(t) for t in tensor_list]
    if g.nranks == 1:
        tensor._value = vals[0]
        return _Task(tensor)
    mp = _mp_active(g)
    if mp is not None:
        # rank r's output = reduction over processes of their tensor_list[r]
        rows = mp.allgather_values(np.stack([np.asarray(v) for v in vals]))
        mine = rows[:, mp.rank()]  # [nprocs, ...]
        red = {"sum": np.sum, "max": np.max, "min": np.min,
               "prod": np.prod, "avg": np.mean}[_op_name(op)](mine, axis=0)
        tensor._value = jnp.asarray(red)
        return _Task(tensor)
    stacked = jnp.stack(vals, axis=0)  # [nranks(dst), nranks(src)?...]
    if vals[0].ndim >= 1 and vals[0].shape[0] == g.nranks:
        # each list entry is itself stacked per-source: reduce over source
        # (axis 1 of [dst, src, ...]) so entry j keeps dst j's result
        red = _reduce_over_axis(stacked, op, axis=1)
        tensor._value = red if red.shape == tensor._value.shape else red.reshape(tensor._value.shape)
    else:
        red = _reduce_stacked(stacked, op, g.nranks)[0]
        tensor._value = jnp.broadcast_to(red, tensor._value.shape)
    return _Task(tensor)


def broadcast(tensor, src, group=None, sync_op=True):
    _record_collective("broadcast", tensor)
    import jax.numpy as jnp

    g = _grp(group)
    if g.nranks == 1:
        return _Task(tensor)
    mp = _mp_active(g)
    if mp is not None:
        tensor._value = jnp.asarray(
            mp.broadcast_value(np.asarray(tensor._value), src))
        return _Task(tensor)
    if _is_stacked(tensor, g):
        idx = g.get_group_rank(src) if src in g.ranks else src
        tensor._value = jnp.broadcast_to(tensor._value[idx:idx + 1], tensor._value.shape)
    return _Task(tensor)


def broadcast_object_list(object_list, src=0, group=None):
    _record_collective("broadcast_object_list")
    g = _grp(group)
    mp = _mp_active(g)
    if mp is not None:
        object_list[:] = mp.broadcast_objects(list(object_list), src)
    return _Task()


def scatter(tensor, tensor_list=None, src=0, group=None, sync_op=True):
    _record_collective("scatter", *(tensor_list or [tensor]))
    import jax.numpy as jnp

    g = _grp(group)
    if g.nranks == 1:
        if tensor_list:
            tensor._value = tensor_list[0]._value
        return _Task(tensor)
    mp = _mp_active(g)
    if mp is not None:
        payload = ([np.asarray(t._value) for t in tensor_list]
                   if mp.rank() == src and tensor_list else None)
        rows = mp.allgather_objects(payload)
        tensor._value = jnp.asarray(rows[src][mp.rank()])
        return _Task(tensor)
    if tensor_list:
        stacked = jnp.stack([t._value for t in tensor_list], axis=0)
        r = max(g.rank, 0)
        tensor._value = stacked[r]
    return _Task(tensor)


def scatter_object_list(out_object_list, in_object_list=None, src=0, group=None):
    _record_collective("scatter_object_list")
    g = _grp(group)
    if g.nranks == 1:
        if in_object_list:
            out_object_list.append(in_object_list[0])
        return _Task()
    mp = _mp_active(g)
    if mp is not None:
        payload = in_object_list if mp.rank() == src else None
        rows = mp.allgather_objects(payload)
        out_object_list.append(rows[src][mp.rank()])
        return _Task()
    if in_object_list:
        out_object_list.append(in_object_list[0])
    return _Task()


def alltoall(out_tensor_list, in_tensor_list, group=None, sync_op=True):
    """rank i sends in[j] to rank j: transpose of the (src, dst) grid.
    Counted under the canonical op="all_to_all" label (shared with
    alltoall_single and the MoE global_scatter/gather paths — which add
    the kind="a2a" comm_task intervals at THEIR level, so per-desc
    exposure reports never double-attribute the same wall time to a
    nested pair; ISSUE-14 satellite)."""
    _record_collective("all_to_all", *in_tensor_list)
    import jax.numpy as jnp

    g = _grp(group)
    n = g.nranks
    vals = [t._value for t in in_tensor_list]
    if n == 1:
        for v in vals:
            out_tensor_list.append(Tensor(v))
        return _Task()
    mp = _mp_active(g)
    if mp is not None:
        rows = mp.allgather_values(np.stack([np.asarray(v) for v in vals]))
        for j in range(n):  # out[j] = what process j put at slot my_rank
            out_tensor_list.append(Tensor(jnp.asarray(rows[j, mp.rank()])))
        return _Task()
    # single-controller stacked view: in_tensor_list[j][i] is what rank i
    # sends to rank j when entries are stacked; plain view: identity permute
    if vals and vals[0].ndim >= 1 and vals[0].shape[0] == n:
        stacked = jnp.stack(vals, axis=0)  # [dst, src, ...]
        swapped = jnp.swapaxes(stacked, 0, 1)
        for j in range(n):
            out_tensor_list.append(Tensor(swapped[j]))
    else:
        for v in vals:
            out_tensor_list.append(Tensor(v))
    return _Task()


def alltoall_single(out_tensor, in_tensor, in_split_sizes=None, out_split_sizes=None, group=None, sync_op=True):
    """Single-tensor all-to-all. Stacked global view [src, dst_chunks...]:
    rank i's row is the concat of chunks for each destination, so the global
    transform is the (src, dst) chunk-grid transpose — identical to what
    lax.all_to_all compiles to over a mesh axis. Counted as
    op="all_to_all"; interval attribution lives with the MoE-level
    wrappers (moe_utils global_scatter/gather) so nested calls never
    double-report the same wall time."""
    _record_collective("all_to_all", in_tensor)
    import jax.numpy as jnp

    g = _grp(group)
    n = g.nranks
    v = in_tensor._value
    if in_split_sizes is not None or out_split_sizes is not None:
        raise NotImplementedError(
            "unequal split sizes are not supported by the eager "
            "alltoall_single; use equal chunks or the compiled primitives"
        )
    if n == 1:
        out_tensor._value = v
        return _Task(out_tensor)
    mp = _mp_active(g)
    if mp is not None:
        out_tensor._value = jnp.asarray(
            mp.alltoall_single_value(np.asarray(v), n))
        return _Task(out_tensor)
    if n > 1 and v.ndim >= 1 and v.shape[0] % (n * n) == 0:
        # full stacked view: [src(n) * dst(n) * per, ...]
        per = v.shape[0] // (n * n)
        grid = v.reshape(n, n, per, *v.shape[1:])  # [src, dst, per, ...]
        out_tensor._value = jnp.swapaxes(grid, 0, 1).reshape(v.shape)
    elif n > 1:
        # the stacked-view heuristic cannot represent this shape; a silent
        # identity here would be wrong data, not a degraded mode
        raise ValueError(
            f"eager alltoall_single needs a [src*dst*k, ...] stacked view "
            f"(leading dim divisible by nranks^2={n * n}); got shape "
            f"{tuple(v.shape)}. Use the compiled primitives inside "
            f"shard_map for per-rank tensors.")
    else:
        out_tensor._value = v
    return _Task(out_tensor)


# -- p2p: host-side mailbox for single-controller API parity ----------------- #
# FIFO channels keyed (group id, src, dst). The single controller plays every
# rank, so recv matches on src and falls back to any destination — a
# send(dst=j) / recv(src=i) pair always pairs up regardless of which "rank"
# the caller is emulating (reference: ncclSend/ncclRecv rendezvous).

_mailbox: dict = {}


def send(tensor, dst=0, group=None, sync_op=True):
    _record_collective("send", tensor)
    g = _grp(group)
    mp = _mp_active(g)
    if mp is not None:
        from .store import create_or_get_global_tcp_store

        mp.p2p_send(create_or_get_global_tcp_store(), tensor._value,
                    mp.rank(), dst)
        return _Task()
    src = max(g.rank, 0)
    _mailbox.setdefault((g.id, src, dst), []).append(tensor._value)
    return _Task()


def recv(tensor, src=0, group=None, sync_op=True):
    _record_collective("recv", tensor)
    import jax.numpy as jnp

    g = _grp(group)
    mp = _mp_active(g)
    if mp is not None:
        from .store import create_or_get_global_tcp_store

        tensor._value = jnp.asarray(
            mp.p2p_recv(create_or_get_global_tcp_store(), src, mp.rank()))
        return _Task(tensor)
    me = max(g.rank, 0)
    # single-controller: the process plays every rank, so src/dst stamps on
    # both sides reflect the controller's rank, not the emulated one. Match
    # progressively: exact channel, then same-src any-dst, then any pending
    # message in the group (FIFO pairing, like an in-order rendezvous).
    box = _mailbox.get((g.id, src, me))
    if not box:
        box = next(
            (b for (gid, s, _d), b in _mailbox.items() if gid == g.id and s == src and b),
            None,
        )
    if not box:
        box = next(
            (b for (gid, _s, _d), b in _mailbox.items() if gid == g.id and b),
            None,
        )
    if box:
        tensor._value = box.pop(0)
    return _Task(tensor)


isend = send
irecv = recv


class P2POp:
    def __init__(self, op, tensor, peer, group=None):
        self.op = op
        self.tensor = tensor
        self.peer = peer
        self.group = group


def batch_isend_irecv(p2p_op_list):
    """reference: python/paddle/distributed/communication/batch_isend_irecv.py."""
    tasks = []
    for op in p2p_op_list:
        tasks.append(op.op(op.tensor, op.peer, group=op.group))
    return tasks


def barrier(group=None):
    _record_collective("barrier")
    import jax

    g = _grp(group)
    if g.nranks == 1:
        return _Task()
    mp = _mp_active(g)
    if mp is not None:
        mp.barrier()
        return _Task()
    jax.effects_barrier()
    return _Task()


def wait(tensor, group=None, use_calc_stream=True):
    if isinstance(tensor, Tensor) and hasattr(tensor._value, "block_until_ready"):
        tensor._value.block_until_ready()


# --------------------------------------------------------------------------- #
# compiled-form primitives (use inside shard_map)
# --------------------------------------------------------------------------- #


class primitives:
    """Collectives for use inside shard_map'd programs; `axis` is a mesh axis
    name (or tuple). These ARE the ICI collectives after XLA lowering —
    the compiled counterpart of NCCLCommContext::AllReduce
    (paddle/phi/core/distributed/nccl_comm_context.cc:184)."""

    @staticmethod
    def all_reduce(x, axis="mp", op="sum"):
        import jax

        if op == "sum":
            return jax.lax.psum(x, axis)
        if op == "max":
            return jax.lax.pmax(x, axis)
        if op == "min":
            return jax.lax.pmin(x, axis)
        if op == "avg":
            return jax.lax.pmean(x, axis)
        raise ValueError(op)

    @staticmethod
    def all_gather(x, axis="mp", concat_axis=0, tiled=True):
        import jax

        return jax.lax.all_gather(x, axis, axis=concat_axis, tiled=tiled)

    @staticmethod
    def reduce_scatter(x, axis="mp", scatter_axis=0):
        import jax

        return jax.lax.psum_scatter(x, axis, scatter_dimension=scatter_axis, tiled=True)

    @staticmethod
    def all_to_all(x, axis="mp", split_axis=0, concat_axis=0):
        import jax

        return jax.lax.all_to_all(x, axis, split_axis=split_axis, concat_axis=concat_axis, tiled=True)

    @staticmethod
    def ppermute(x, axis, perm):
        import jax

        return jax.lax.ppermute(x, axis, perm)

    @staticmethod
    def axis_index(axis):
        import jax

        return jax.lax.axis_index(axis)
