"""An expert layer that is told which experts it holds.

One chip's part of a layer whose experts are spread over several chips by
expert parallelism (`model-configs` guide, section 4): the router keeps its
published width and its experts per token and routes over ALL experts; of
each token's picks, those that name an expert held here are computed, the
others are discarded before the scatter and contribute nothing. What comes
out is this chip's partial sum `sum over the picked experts held here of
gate_e * expert_e(x)`; the parts of all shares add up to the whole layer.
Nothing here stands in for the other chips or their exchange.

No token is ever dropped: rows are sorted by held expert into
`grouped_gemm`'s uniform stride (`MoELayer`'s sort, `rank_in_group`) with
the stride set to the worst case, every row to one expert. A dead tile costs
the grouped GEMM no MXU work, so the price of the worst case is the zeroed
scatter target, not arithmetic.

Routing: top-k by router logit, gates = softmax over the picked k logits
only. Experts are gated MLPs, `w_out (silu(a) * b)` with `[a | b] = w_in x`.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

import paddle_tpu.nn as nn
from paddle_tpu.nn import initializer as I
from .....framework.core import run_op
from .moe_layer import rank_in_group

__all__ = ["HeldExpertsMoE", "STAT_NAMES"]

# what `forward(..., with_stats=True)` counts, in the order of its int32 row
STAT_NAMES = ("routed_pairs_held", "expert_rows_max", "expert_rows_sum",
              "dropped_pairs")


def _row_tile(stride):
    """The grouped GEMM's row tile for this stride: the largest of 256, 128,
    ... that divides it. A tile as tall as the stride reads an expert's
    weights once; beyond 256 rows the lhs block no longer fits VMEM beside
    them."""
    bm = 256
    while stride % bm:
        bm //= 2
    return bm


class HeldExpertsMoE(nn.Layer):
    """Routed experts `first .. first + count - 1` of `num_experts`.

    forward(x [..., d_model], live=None, with_stats=False): `live`
    [tokens] bool leaves rows out of the routing altogether (batch padding,
    free decode rows). With `with_stats` also returns an int32 [4] row, see
    STAT_NAMES (rows are counted over the held experts)."""

    def __init__(self, d_model, d_expert, num_experts, top_k, held=None,
                 weight_attr=None):
        super().__init__()
        first, count = held if held is not None else (0, num_experts)
        if not (0 <= first and count >= 1 and first + count <= num_experts):
            raise ValueError(f"held experts ({first}, {count}) outside "
                             f"0..{num_experts}")
        if top_k > num_experts:
            raise ValueError("top_k larger than the number of experts")
        self.d_model, self.d_expert = int(d_model), int(d_expert)
        self.num_experts, self.top_k = int(num_experts), int(top_k)
        self.held = (int(first), int(count))
        init = (weight_attr.initializer if weight_attr is not None
                else I.Normal(0.0, 0.02))
        self.router = self.create_parameter(
            [d_model, num_experts], default_initializer=init)
        self.w_in = self.create_parameter(
            [count, d_model, 2 * d_expert], default_initializer=init)
        self.w_out = self.create_parameter(
            [count, d_expert, d_model], default_initializer=init)
        self._fns = {}   # (tokens, has_live) -> the pure function below

    def _fn(self, tokens, has_live):
        """The layer as ONE pure function of arrays, built once per shape
        (the eager dispatch cache keys on the function)."""
        key = (tokens, has_live)
        if key not in self._fns:
            self._fns[key] = self._build_fn(tokens, has_live)
        return self._fns[key]

    def _build_fn(self, tokens, has_live):
        from .....ops.pallas import kernels_available
        from .....ops.pallas.autotune import pick_block_sizes
        from .....ops.pallas.grouped_gemm import grouped_matmul, row_stride

        first, held = self.held
        k, f = self.top_k, self.d_expert
        stride = row_stride(tokens)          # worst case: every row to one
        block = (_row_tile(stride), 128)
        use_kernel = kernels_available()

        def gmm(rows, w, sizes):
            if not use_kernel:
                out = jnp.einsum("erk,ekn->ern",
                                 rows.reshape(held, stride, -1), w)
                return out.reshape(held * stride, -1)
            # the tile follows from the stride, so it is the tuner's only
            # candidate: nothing is swept inside a serving process, and
            # chosen_tiles()["grouped_gemm"] counts the consults
            tile = pick_block_sizes(
                "grouped_gemm", rows.shape[0], w.shape[2], block,
                lambda bm, bn: None, allow_measure=False,
                signature=(held, stride, w.shape[1], w.shape[2],
                           str(rows.dtype)),
                candidates=[block])
            return grouped_matmul(rows, w, sizes, block=tuple(tile))

        def fn(x, router, w_in, w_out, *live):
            logits = jnp.matmul(x, router,
                                preferred_element_type=jnp.float32)
            top_logit, top_expert = jax.lax.top_k(logits, k)
            gates = jax.nn.softmax(top_logit, axis=-1)          # [T, k] f32
            local = top_expert.astype(jnp.int32) - first
            here = (local >= 0) & (local < held)
            if has_live:
                here = here & live[0][:, None]
            # flat (token, choice) pairs; a pair that is not computed here
            # sorts behind every held expert and scatters nowhere
            key = jnp.where(here, local, held).reshape(-1)
            pos, counts = rank_in_group(key, held)
            kept = (key < held) & (pos < stride)
            slot = jnp.where(kept, key * stride + pos, held * stride)
            token = jnp.repeat(jnp.arange(tokens, dtype=jnp.int32), k)
            rows = jnp.zeros((held * stride, x.shape[1]), x.dtype).at[
                slot].set(x[token], mode="drop")
            sizes = jnp.minimum(counts, stride).astype(jnp.int32)
            ab = gmm(rows, w_in, sizes)
            hidden = (jax.nn.silu(ab[:, :f].astype(jnp.float32))
                      * ab[:, f:].astype(jnp.float32)).astype(x.dtype)
            y = gmm(hidden, w_out, sizes)
            picked = jnp.take(y, slot, axis=0, mode="fill", fill_value=0)
            out = (gates.reshape(-1, 1) * picked.astype(jnp.float32)
                   ).reshape(tokens, k, -1).sum(1).astype(x.dtype)
            pairs = jnp.sum(key < held)
            stats = jnp.stack([pairs, counts.max(), counts.sum(),
                               pairs - jnp.sum(kept)]).astype(jnp.int32)
            return out, stats

        return fn

    def forward(self, x, live=None, with_stats=False):
        shape = x.shape
        flat = x.reshape([-1, self.d_model])
        inputs = [flat, self.router, self.w_in, self.w_out]
        if live is not None:
            inputs.append(live)
        out, stats = run_op(
            "held_experts_moe", self._fn(int(flat.shape[0]), live is not None),
            inputs, n_outputs=2)
        out = out.reshape(list(shape))
        return (out, stats) if with_stats else out
