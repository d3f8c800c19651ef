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

The gate is part of the layer's definition (`gate=`):

- `"softmax"`: top-k by router logit, gates = softmax over the picked k
  logits only;
- `"sigmoid"`: scores = sigmoid(router logits) in f32 over all experts, the
  k experts with the largest score PLUS `expert_bias` (a per-expert buffer
  that steers the choice and never the weight), gates = the picked scores
  over their sum (+ 1e-20), times `route_scale`.

Experts are gated MLPs, `w_out (silu(a) * b)` with `[a | b] = w_in x`.

A call of more than `CHUNK_TOKENS` tokens (a long prefill) runs as a scan
over chunks of that many: the zeroed scatter target is `held x stride x
d_model` with the stride the worst case of the CALL's tokens, which a 16k
bucket would make gigabytes; chunked it is bounded whatever the bucket. A
chunk runs at its own worst-case stride (the grouped GEMM's dead tiles fetch
nothing, so that costs zero writes only), and a chunk with no live token
(the tail of a padded bucket) is skipped whole. A call of `CHUNK_TOKENS` or
fewer is one chunk and compiles as it always did.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

import paddle_tpu.nn as nn
from paddle_tpu.nn import initializer as I
from .....framework.core import Tensor, run_op
from .moe_layer import rank_in_group

__all__ = ["HeldExpertsMoE", "STAT_NAMES", "CHUNK_TOKENS", "chunks_for"]

# the most tokens one pass of the layer takes; a larger call is a scan
CHUNK_TOKENS = 1024


def chunks_for(tokens: int) -> int:
    """Passes of the layer a call of `tokens` tokens makes."""
    return -(-tokens // CHUNK_TOKENS)

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


def _column_tile(n):
    """The widest of 512, 256, 128 that divides the N the kernel pads to:
    beside a 256-row tile of 2048-wide rows it still fits VMEM twice over."""
    n = -(-n // 128) * 128
    return next(bn for bn in (512, 256, 128) if n % bn == 0)


class HeldExpertsMoE(nn.Layer):
    """Routed experts `first .. first + count - 1` of `num_experts`.

    `gate`, `route_scale`: the module docstring; a "sigmoid" layer has the
    buffer `expert_bias` [num_experts] f32.

    forward(x [..., d_model], live=None, with_stats=False): `live`
    [tokens] bool leaves rows out of the routing altogether (batch padding,
    free decode rows). With `with_stats` also returns an int32 [4] row, see
    STAT_NAMES (rows are counted over the held experts)."""

    def __init__(self, d_model, d_expert, num_experts, top_k, held=None,
                 weight_attr=None, gate="softmax", route_scale=1.0):
        super().__init__()
        if gate not in ("softmax", "sigmoid"):
            raise ValueError(f"unknown gate {gate!r}")
        self.gate, self.route_scale = gate, float(route_scale)
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
        if gate == "sigmoid":
            # a buffer, not a parameter: the published model moves it by
            # load, not by gradient. Zero here; a seeded model draws it
            self.register_buffer(
                "expert_bias", Tensor(jnp.zeros(num_experts, jnp.float32)))
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
        sigmoid, route_scale = self.gate == "sigmoid", self.route_scale
        whole, tokens = tokens, min(tokens, CHUNK_TOKENS)
        stride = row_stride(tokens)          # worst case: every row to one
        use_kernel = kernels_available()

        def gmm(rows, w, sizes, wide):
            if not use_kernel:
                out = jnp.einsum("erk,ekn->ern",
                                 rows.reshape(held, stride, -1), w)
                return out.reshape(held * stride, -1)
            # the tile follows from the stride, so it is the tuner's only
            # candidate: nothing is swept inside a serving process, and
            # chosen_tiles()["grouped_gemm"] counts the consults
            block = (_row_tile(stride),
                     _column_tile(w.shape[2]) if wide else 128)
            tile = pick_block_sizes(
                "grouped_gemm", rows.shape[0], w.shape[2], block,
                lambda bm, bn: None, allow_measure=False,
                signature=(held, stride, w.shape[1], w.shape[2],
                           str(rows.dtype)),
                candidates=[block])
            return grouped_matmul(rows, w, sizes, block=tuple(tile))

        def route(x, router, bias, live):
            """Flat (token, choice) pairs: `key` the held expert's local
            index (`held` for a pair not computed here, which sorts behind
            every held expert), `pos` its rank in its expert's group,
            `counts` [held], `gates` [T, k] f32."""
            logits = jnp.matmul(x, router,
                                preferred_element_type=jnp.float32)
            if sigmoid:
                score = jax.nn.sigmoid(logits)                  # [T, E] f32
                _, top_expert = jax.lax.top_k(
                    score + bias[0].astype(jnp.float32), k)
                picked = jnp.take_along_axis(score, top_expert, axis=-1)
                gates = (picked / (picked.sum(-1, keepdims=True) + 1e-20)
                         * route_scale)
            else:
                top_logit, top_expert = jax.lax.top_k(logits, k)
                gates = jax.nn.softmax(top_logit, axis=-1)      # [T, k] f32
            local = top_expert.astype(jnp.int32) - first
            here = (local >= 0) & (local < held)
            if live:
                here = here & live[0][:, None]
            key = jnp.where(here, local, held).reshape(-1)
            pos, counts = rank_in_group(key, held)
            return key, pos, counts, gates

        def experts(x, w_in, w_out, key, pos, counts, gates, wide=False):
            """(out [T, d], kept [T * k] bool): the routed pairs through the
            held experts at the worst-case row stride; a pair is not kept,
            and scatters nowhere, if it is not computed here. `wide`: the
            grouped GEMM's widest column tile (a chunk's calls: long
            contiguous weight rows, a quarter of the grid)."""
            kept = (key < held) & (pos < stride)
            slot = jnp.where(kept, key * stride + pos, held * stride)
            token = jnp.repeat(jnp.arange(tokens, dtype=jnp.int32), k)
            rows = jnp.zeros((held * stride, x.shape[1]), x.dtype).at[
                slot].set(x[token], mode="drop")
            sizes = jnp.minimum(counts, stride).astype(jnp.int32)
            ab = gmm(rows, w_in, sizes, wide)
            hidden = (jax.nn.silu(ab[:, :f].astype(jnp.float32))
                      * ab[:, f:].astype(jnp.float32)).astype(x.dtype)
            y = gmm(hidden, w_out, sizes, wide)
            picked = jnp.take(y, slot, axis=0, mode="fill", fill_value=0)
            out = (gates.reshape(-1, 1) * picked.astype(jnp.float32)
                   ).reshape(tokens, k, -1).sum(1).astype(x.dtype)
            return out, kept

        def stats_of(key, counts, kept):
            pairs = jnp.sum(key < held)
            return jnp.stack([pairs, counts.max(), counts.sum(),
                              pairs - jnp.sum(kept)]).astype(jnp.int32)

        def fn(x, router, w_in, w_out, *rest):
            bias, live = rest[:sigmoid], rest[sigmoid:]
            key, pos, counts, gates = route(x, router, bias, live)
            out, kept = experts(x, w_in, w_out, key, pos, counts, gates)
            return out, stats_of(key, counts, kept)

        if whole <= tokens:
            return fn
        n, pad = chunks_for(whole), -whole % tokens

        def chunk_fn(x, router, w_in, w_out, bias, live):
            key, pos, counts, gates = route(x, router, bias, live)
            out, kept = experts(x, w_in, w_out, key, pos, counts, gates,
                                wide=True)
            return out, stats_of(key, counts, kept), counts

        def chunked(x, router, w_in, w_out, *rest):
            """The layer over `n` chunks of the call's tokens, one after the
            other; the padding behind the last is dead, and a chunk with no
            live token (a bucket's tail) is skipped whole. An expert's rows
            are counted over the whole call."""
            bias, live = rest[:sigmoid], rest[sigmoid:]
            xs = jnp.pad(x, ((0, pad), (0, 0))).reshape(n, tokens, -1)
            if not (has_live or pad):
                def one(_, xc):
                    return None, chunk_fn(xc, router, w_in, w_out, bias, ())
                scanned = xs
            else:
                real = live[0] if has_live else jnp.ones(whole, bool)
                scanned = (xs, jnp.pad(real, (0, pad)).reshape(n, tokens))

                def one(_, chunk):
                    xc, alive = chunk
                    return None, jax.lax.cond(
                        alive.any(),
                        lambda: chunk_fn(xc, router, w_in, w_out, bias,
                                         (alive,)),
                        lambda: (jnp.zeros_like(xc),
                                 jnp.zeros(4, jnp.int32),
                                 jnp.zeros(held, jnp.int32)))

            _, (out, stats, counts) = jax.lax.scan(one, None, scanned)
            counts = counts.sum(0)
            stats = jnp.stack([stats[:, 0].sum(), counts.max(),
                               counts.sum(), stats[:, 3].sum()])
            return out.reshape(n * tokens, -1)[:whole], stats

        return chunked

    def forward(self, x, live=None, with_stats=False):
        shape = x.shape
        flat = x.reshape([-1, self.d_model])
        inputs = [flat, self.router, self.w_in, self.w_out]
        if self.gate == "sigmoid":
            inputs.append(self.expert_bias)
        if live is not None:
            inputs.append(live)
        out, stats = run_op(
            "held_experts_moe", self._fn(int(flat.shape[0]), live is not None),
            inputs, n_outputs=2)
        out = out.reshape(list(shape))
        return (out, stats) if with_stats else out
