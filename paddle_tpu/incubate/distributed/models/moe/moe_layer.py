"""Mixture-of-experts layer with expert parallelism.

Reference: python/paddle/incubate/distributed/models/moe/moe_layer.py:261
(MoELayer) — there, routing produces index lists, tokens are exchanged with
custom `global_scatter`/`global_gather` NCCL all-to-alls, and each rank runs
its local experts.

Two TPU formulations share one gate (gate.py `_probs_and_keep`), selected by
`PADDLE_TPU_MOE_FAST` (default on, read once per forward trace and captured
into the traced program like the PR-7 safe-softmax / PR-12 fused-kernel
toggles — an env flip between forward and backward can never mix paths):

**Dense reference path** (`PADDLE_TPU_MOE_FAST=0` — the parity oracle).
The GShard einsum formulation: routing produces dense dispatch/combine
tensors and the whole layer is three einsums

    xe  = einsum('tec,tm->ecm', dispatch, x)      # dispatch
    ye  = expert_ffn(xe)                          # [E,C,M] batched GEMMs
    out = einsum('tec,ecm->tm', combine, ye)      # combine

Correct, but the one-hot dispatch/combine einsums burn O(T·E·C·M) FLOPs on
masks that are almost entirely zeros.

**Sorted fast path** (default). Routing keeps only (expert id, weight) per
(token, choice); tokens are scattered by a cheap positional permutation into
a uniform-stride [E, R, M] buffer (R = per-expert row stride; capacity
overflow is a `pos >= capacity` drop mask on the scatter, not one-hot
pruning), the experts run as a Pallas grouped/ragged GEMM over the
contiguous per-expert row groups (ops/pallas/grouped_gemm.py — dead row
tiles skip the MXU entirely), and outputs gather back through the saved
permutation. Dispatch+combine cost drops from O(T·E·C·M) to O(T·k·M) index
arithmetic; expert FLOPs scale with routed tokens, not capacity.

**Expert parallelism.** With `ep_axis` set and that mesh axis > 1, the
[E, R, M] buffer is split into `PADDLE_TPU_MOE_A2A_CHUNKS` row chunks; each
chunk is constrained to the expert-sharded layout (the dispatch all-to-all
GSPMD materializes from the token-sharded producer), runs its grouped GEMMs
under shard_map over `ep` (expert-stacked weights sharded on `ep`, the
SpecLayout `expert_stacked` group), and combines back per chunk — so chunk
k+1's all-to-all overlaps chunk k's expert GEMM (the T3 chunking pattern,
arxiv 2401.16677). Per-step a2a volume is registered at trace time
(distributed/moe_comm.py) and re-emitted host-side each step as
`collective_{calls,bytes}_total{op="all_to_all"}` + `comm_task(kind="a2a")`
intervals, so `overlap_fraction` covers MoE traffic (docs/MOE.md).
"""

from __future__ import annotations

import math
import os

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import PartitionSpec as P

import paddle_tpu.nn as nn
from .....framework.core import run_op
from ..... import distributed as _dist_pkg  # noqa: F401  (package init ordering)
from .....distributed import env as _env
from .....distributed import moe_comm as _moe_comm
from .gate import BaseGate, GShardGate, NaiveGate, SwitchGate

__all__ = ["MoELayer", "ExpertFFN", "moe_fast_on", "moe_a2a_chunks",
           "rank_in_group"]


_constrain_value = _env.constrain_array


def moe_fast_on() -> bool:
    """PADDLE_TPU_MOE_FAST toggle, default ON. Read once per forward trace
    and captured into the traced program; =0 keeps the dense einsum path as
    the reference oracle for A/B and parity tests."""
    return os.environ.get("PADDLE_TPU_MOE_FAST", "1") != "0"


def moe_a2a_chunks() -> int:
    """PADDLE_TPU_MOE_A2A_CHUNKS (default 2, clamped to [1, 8]): row chunks
    the expert buffer is split into under expert parallelism so dispatch
    all-to-alls pipeline against expert GEMMs. 1 disables chunking (one
    exposed a2a each way, the A/B baseline)."""
    try:
        n = int(os.environ.get("PADDLE_TPU_MOE_A2A_CHUNKS", "2"))
    except ValueError:
        n = 2
    return max(1, min(n, 8))


def rank_in_group(key, n_groups):
    """(pos, counts): the rank of every entry among the entries of its group,
    in the flat order given, and the entries per group. `key` [n] int32 is
    the group of each entry, `n_groups` for an entry that takes part in
    nothing (its pos is meaningless). A stable sort by group, then position
    = index - the group's run start: O(n log n), no one-hot."""
    n = key.shape[0]
    order = jnp.argsort(key, stable=True)
    counts = jax.ops.segment_sum(
        jnp.ones_like(key), key, num_segments=n_groups + 1)[:n_groups]
    start = jnp.cumsum(counts) - counts
    srt = key[order]
    pos_sorted = (jnp.arange(n, dtype=jnp.int32)
                  - start[jnp.clip(srt, 0, n_groups - 1)].astype(jnp.int32))
    return jnp.zeros((n,), jnp.int32).at[order].set(pos_sorted), counts


class ExpertFFN(nn.Layer):
    """Stacked expert FFN: all experts' weights in one [E, ...] tensor so the
    expert dimension is a real mesh-shardable axis and the per-expert GEMMs
    batch onto the MXU (replaces the reference's per-expert Linear list +
    fused_moe cutlass grouped GEMM, fusion/cutlass/fused_moe_kernel.cu)."""

    def __init__(self, num_experts, d_model, d_hidden, activation="gelu",
                 ep_axis=None):
        super().__init__()
        self.num_experts = num_experts
        self.w1 = self.create_parameter([num_experts, d_model, d_hidden])
        self.b1 = self.create_parameter([num_experts, 1, d_hidden], is_bias=True)
        self.w2 = self.create_parameter([num_experts, d_hidden, d_model])
        self.b2 = self.create_parameter([num_experts, 1, d_model], is_bias=True)
        self.activation = activation
        if ep_axis:
            for p in (self.w1, self.b1, self.w2, self.b2):
                p.dist_attr = P(ep_axis, *([None] * (len(p.shape) - 1)))
                p.is_distributed = True

    def forward(self, xe):
        """xe: [E, C, M] -> [E, C, M]."""
        act = getattr(jax.nn, self.activation)

        def fn(x, w1, b1, w2, b2):
            h = jnp.einsum("ecm,emh->ech", x, w1) + b1
            h = act(h)
            return jnp.einsum("ech,ehm->ecm", h, w2) + b2

        return run_op("expert_ffn", fn, [xe, self.w1, self.b1, self.w2, self.b2])


class MoELayer(nn.Layer):
    """reference: moe_layer.py:261 — MoELayer(d_model, experts, gate, moe_group).

    `experts` is either an ExpertFFN (stacked fast path, expert-parallel
    capable) or a list of nn.Layer (reference-parity path; each expert applied
    to its [C, M] slice — replicated, eager/jit both fine).
    `gate` is a BaseGate instance or a config dict {"type": "gshard"|"switch"|
    "naive", "top_k": k} exactly like the reference's gate config.
    `ep_axis` names the mesh axis experts shard over (the analog of
    moe_group — the reference uses the data-parallel group; the planner's
    canonical axis is "ep", env.AXIS_ORDER)."""

    def __init__(self, d_model, experts, gate=None, moe_group=None, mp_group=None,
                 recompute_interval=0, ep_axis=None, name=None):
        super().__init__()
        self.d_model = d_model
        self.ep_axis = ep_axis
        if isinstance(experts, ExpertFFN):
            self.experts = experts
            self.num_expert = experts.num_experts
            self._stacked = True
        else:
            self.experts = nn.LayerList(experts)
            self.num_expert = len(experts)
            self._stacked = False

        if isinstance(gate, BaseGate):
            self.gate = gate
        else:
            cfg = dict(gate or {})
            gtype = cfg.pop("type", "gshard")
            topk = cfg.pop("top_k", 2)
            cls = {"gshard": GShardGate, "switch": SwitchGate, "naive": NaiveGate}[gtype]
            self.gate = cls(d_model, self.num_expert, topk=topk, **cfg)

    @property
    def l_aux(self):
        return self.gate.l_aux

    # ------------------------------------------------------------------ #
    # sorted fast path
    # ------------------------------------------------------------------ #

    def _ep_size(self):
        mesh = _env.get_global_mesh()
        if not self.ep_axis or mesh is None:
            return 1
        return int(mesh.shape.get(self.ep_axis, 1))

    def _fast_fn(self, cap, Rc, chunks, ep):
        """The whole fast layer as ONE pure fn of the raw arrays (a single
        dispatch-cache entry / trace). `cap`/`Rc`/`chunks`/`ep` are static
        (python ints captured per trace)."""
        gate = self.gate
        E = self.num_expert
        k = gate.top_k
        act = getattr(jax.nn, self.experts.activation)
        ep_axis = self.ep_axis
        mesh = _env.get_global_mesh()
        R = Rc * chunks

        # Pallas only on TPU or under the interpreter (the nn.functional
        # kernel-dispatch rule); the CPU fallback keeps the SAME sorted
        # layout and runs the groups as one batched einsum — dead rows are
        # zero by the scatter's construction, so values are identical
        from .....ops.pallas import kernels_available
        from .....ops.pallas.grouped_gemm import grouped_matmul

        use_kernel = kernels_available()

        def gmm3(x3, w, sizes):
            """[E, Rc, K] @ [E, K, N] grouped — under shard_map over `ep`
            when expert-parallel (weights/rows/sizes all sharded on the
            expert dim; other mesh axes stay on GSPMD auto)."""

            def body(xl, wl, sl):
                El = xl.shape[0]
                if not use_kernel:
                    return jnp.einsum("erk,ekn->ern",
                                      xl.astype(wl.dtype), wl)
                out = grouped_matmul(xl.reshape(El * Rc, xl.shape[-1]),
                                     wl, sl)
                return out.reshape(El, Rc, out.shape[-1])

            if ep > 1:
                spec3 = P(ep_axis, None, None)
                return jax.shard_map(
                    body, mesh=mesh, in_specs=(spec3, spec3, P(ep_axis)),
                    out_specs=spec3, axis_names={ep_axis},
                    check_vma=False)(x3, w, sizes)
            return body(x3, w, sizes)

        def fn(xv, gw, gb, w1, b1, w2, b2):
            S, M = xv.shape
            topi, topv, keep, l_aux = gate._route(xv, gw, gb)

            # flat (choice, token) arrays in choice-major order j*S+s — the
            # dense path's capacity priority (all 1st choices rank before
            # any 2nd choice)
            eid = topi.T.reshape(-1).astype(jnp.int32)       # [k*S]
            wts = topv.T.reshape(-1)
            valid = keep.T.reshape(-1)
            tok = jnp.tile(jnp.arange(S, dtype=jnp.int32), k)

            # rank within expert among valid entries, in flat order: stable
            # sort by expert (invalid entries sort to the E sentinel), then
            # position = index - run start. Identical to the dense path's
            # cumsum-over-one-hot slot assignment, at O(kS log kS).
            pos, counts = rank_in_group(jnp.where(valid, eid, E), E)

            # capacity overflow: a cheap drop mask, not one-hot pruning
            kept = valid & (pos < cap)
            slot = jnp.where(kept, eid * R + pos, E * R)     # E*R == drop
            xs = jnp.zeros((E * R, M), xv.dtype).at[slot].set(
                xv[tok], mode="drop")
            sizes = jnp.minimum(counts, cap).astype(jnp.int32)  # live rows/E

            xs3 = xs.reshape(E, R, M)
            spec3 = P(ep_axis, None, None) if ep > 1 else None
            g = jnp.zeros((k * S, M), xv.dtype)
            for c in range(chunks):
                xc = xs3[:, c * Rc:(c + 1) * Rc]
                if spec3 is not None:
                    # the dispatch all-to-all: token-sharded producer ->
                    # expert-sharded consumer, materialized by GSPMD per
                    # chunk so chunk c+1's exchange overlaps chunk c's GEMM
                    xc = _constrain_value(xc, spec3)
                sc = jnp.clip(sizes - c * Rc, 0, Rc)
                h = act(gmm3(xc, w1, sc) + b1)
                yc = gmm3(h, w2, sc) + b2                    # [E, Rc, M]
                # per-chunk combine gather (the reverse a2a, also chunked):
                # each (token, choice) lands in exactly one chunk, so the
                # running sum only ever adds zeros elsewhere
                local = pos - c * Rc
                in_c = kept & (local >= 0) & (local < Rc)
                slot_c = jnp.where(in_c, eid * Rc + local, E * Rc)
                g = g + jnp.take(yc.reshape(E * Rc, M), slot_c, axis=0,
                                 mode="fill", fill_value=0)
            out = (wts[:, None].astype(xv.dtype) * g).reshape(k, S, M).sum(0)
            return out, l_aux

        return fn

    def _forward_fast(self, x):
        S = int(x.shape[0])
        cap = self.gate.capacity(S)
        ep = self._ep_size()
        if ep > 1 and self.num_expert % ep:
            raise ValueError(
                f"expert count {self.num_expert} not divisible by the "
                f"'{self.ep_axis}' mesh axis size {ep}")
        chunks = moe_a2a_chunks() if ep > 1 else 1
        from .....ops.pallas.grouped_gemm import row_stride

        Rc = row_stride(int(math.ceil(cap / chunks)))
        fn = self._fast_fn(cap, Rc, chunks, ep)
        e = self.experts
        out, l_aux = run_op(
            "moe_fast", fn,
            [x, self.gate.gate.weight, self.gate.gate.bias,
             e.w1, e.b1, e.w2, e.b2], n_outputs=2)
        self.gate.set_loss(l_aux)
        if ep > 1:
            # per-step a2a volume for the host-side emission
            # (DistributedTrainStep._post_dispatch): analytic — bytes that
            # change shards when the routed rows reshard token->expert and
            # back. Registered once per trace, replayed per executed step.
            itemsize = np.dtype(str(x.dtype)).itemsize
            rows = min(self.gate.top_k * S, self.num_expert * cap)
            nbytes = int(2 * rows * self.d_model * itemsize * (ep - 1) / ep)
            _moe_comm.note_a2a(
                f"moe/a2a/{self.ep_axis}x{ep}", nbytes, calls=2 * chunks,
                overlapped=chunks > 1)
        return out

    def forward(self, inp):
        shape = inp.shape
        x = inp.reshape([-1, self.d_model])

        gate_cls = type(self.gate)
        fast_capable = (
            self._stacked and moe_fast_on()
            # gates must expose the shared router math (a custom BaseGate
            # subclass that only implements dense _routing stays dense),
            # and must NOT override the dense _routing itself — a custom
            # dispatch there would silently diverge from _route's routing
            and gate_cls._probs_and_keep is not BaseGate._probs_and_keep
            and gate_cls._routing is BaseGate._routing
            and getattr(self.gate, "gate", None) is not None)
        if fast_capable:
            out = self._forward_fast(x)
        else:
            out = self._forward_dense(x)
        return out.reshape(list(shape[:-1]) + [self.d_model])

    def _forward_dense(self, x):
        combine, dispatch, _l_aux = self.gate(x)

        ep = self._ep_size()
        spec_e = (P(self.ep_axis, None, None)
                  if self.ep_axis and ep > 1 else None)
        if spec_e is not None:
            # the oracle leg of the fast-vs-einsum A/B does REAL a2a too:
            # GSPMD reshards the full capacity-padded [E, C, M] buffer
            # (empty slots included — that's the dense formulation's wire
            # cost) each way, unchunked, so register it like the fast path
            # does or the baseline reads as comm-free
            S = int(x.shape[0])
            cap = self.gate.capacity(S)
            itemsize = np.dtype(str(x.dtype)).itemsize
            nbytes = int(2 * self.num_expert * cap * self.d_model
                         * itemsize * (ep - 1) / ep)
            _moe_comm.note_a2a(f"moe/a2a/{self.ep_axis}x{ep}", nbytes,
                               calls=2, overlapped=False)

        def dispatch_fn(d, xv):
            xe = jnp.einsum("tec,tm->ecm", d, xv)
            if spec_e is not None:
                xe = _constrain_value(xe, spec_e)
            return xe

        xe = run_op("moe_dispatch", dispatch_fn, [dispatch, x])

        if self._stacked:
            ye = self.experts(xe)
        else:
            outs = [self.experts[e](xe[e]) for e in range(self.num_expert)]
            ye = run_op("moe_stack", lambda *ys: jnp.stack(ys, 0), outs)

        def combine_fn(c, yv):
            if spec_e is not None:
                yv = _constrain_value(yv, spec_e)
            return jnp.einsum("tec,ecm->tm", c, yv)

        return run_op("moe_combine", combine_fn, [combine, ye])
