"""An expert layer that is told which experts it holds.

One chip's part of a layer whose experts are spread over several chips by
expert parallelism (`model-configs` guide, section 4): the router keeps its
published width and its experts per token and routes over ALL experts; of
each token's picks, those that name an expert held here are computed, the
others are computed nowhere and contribute nothing. What comes
out is this chip's partial sum `sum over the picked experts held here of
gate_e * expert_e(x)`; the parts of all shares add up to the whole layer.
Nothing here stands in for the other chips or their exchange.

The layout has ONE form. The (token, choice) pairs are sorted by held
expert once (stable; a pair not computed here, an expert of another share or
a row that is not live, sorts behind every held expert) and their tokens'
rows are gathered in that order into one dense `[tokens x top_k, d_model]`
buffer: the held experts' rows lie END TO END, each group starting where the
one before ended, with no stride between them. `grouped_gemm` takes the
groups ragged (`ops/pallas/grouped_gemm.ragged_matmul`), `y` is gathered
back by the inverse order, and each token's gated picks are summed. Every
held pair has its row by construction, so no token can be dropped, whatever
the routing: `dropped_pairs` is 0 and stays a stat for the series it feeds.

The gate is part of the layer's definition (`gate=`):

- `"softmax"`: top-k by router logit, gates = softmax over the picked k
  logits only;
- `"sigmoid"`: scores = sigmoid(router logits) in f32 over all experts, the
  k experts with the largest score PLUS `expert_bias` (a per-expert buffer
  that steers the choice and never the weight), gates = the picked scores
  over their sum (+ 1e-20), times `route_scale`.

Experts are gated MLPs, `w_out (silu(a) * b)` with `[a | b] = w_in x`.

A pass's largest buffer is `tokens x top_k x d_model`, which bounds the
tokens of one pass: a call of more than `CHUNK_TOKENS` tokens (a prefill
bucket of 8192 or 16384) runs as a scan over passes of that many, the held
weights read once a pass, and a pass with no live token (the tail of a
padded bucket) is skipped whole. A call of `CHUNK_TOKENS` or fewer is one
pass with no scan.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

import paddle_tpu.nn as nn
from paddle_tpu.nn import initializer as I
from .....framework.core import Tensor, run_op

__all__ = ["HeldExpertsMoE", "STAT_NAMES", "CHUNK_TOKENS", "chunks_for",
           "total_stats"]

# the most tokens one pass of the layer takes; a larger call is a scan. From
# bytes: at 4096 tokens, 8 picks and 2048-wide bf16 rows the routed buffer
# is 134 MB, and a 4096-token pass has the arithmetic to hide one reading of
# the held weights
CHUNK_TOKENS = 4096


def chunks_for(tokens: int) -> int:
    """Passes of the layer a call of `tokens` tokens makes."""
    return -(-tokens // CHUNK_TOKENS)

# what `forward(..., with_stats=True)` counts, in the order of its int32 row
STAT_NAMES = ("routed_pairs_held", "expert_rows_max", "expert_rows_sum",
              "dropped_pairs", "tile_rows")


def total_stats(*rows):
    """The stat rows of a model's expert layers as ONE row: every count
    summed over the layers, `expert_rows_max` the largest."""
    rows = jnp.stack(rows)
    return jnp.stack([rows[:, 0].sum(), rows[:, 1].max(), rows[:, 2].sum(),
                      rows[:, 3].sum(), rows[:, 4].sum()])


def _sort_by_expert(key, held):
    """(order, back, counts): the stable sort of the pairs by `key` (0 ..
    held, `held` for a pair not computed here) as a COUNTING sort. `back`
    [pairs] is where each pair goes, `order` its inverse (which pair lies at
    each sorted place), `counts` [held] the held experts' rows.

    A pair's place is its expert's first row, plus the pairs of that expert
    in the blocks before its own, plus its rank inside its block of 256,
    which is a lower-triangular matrix times the block's one-hot keys: small
    matmuls of zeros and ones, exact in any precision. `jnp.argsort` gives
    the same order, but the chip's compiler takes 15 s over a sort of 32768
    keys (a 4096-token pass), in every expert layer of every prefill
    program."""
    n, block = key.shape[0], 256
    keys = jnp.pad(key, (0, -n % block), constant_values=held).reshape(
        -1, block)
    onehot = (keys[..., None] == jnp.arange(held + 1, dtype=jnp.int32)
              ).astype(jnp.float32)                       # [blocks, 256, E]
    within = jnp.einsum("ij,bje->bie",
                        jnp.tril(jnp.ones((block, block), jnp.float32)),
                        onehot)                           # rank in the block
    per_block = within[:, -1]
    counts = per_block.sum(0)
    first = (jnp.cumsum(counts) - counts
             + jnp.cumsum(per_block, axis=0) - per_block)  # [blocks, E]
    # a pair's own expert's entry, read as a sum against its one-hot key (a
    # gather of 32768 scalars takes the chip ten times as long)
    back = ((first[:, None, :] + within - 1) * onehot).sum(-1)
    back = back.reshape(-1)[:n].astype(jnp.int32)
    order = jnp.zeros(n, jnp.int32).at[back].set(
        jnp.arange(n, dtype=jnp.int32))
    return order, back, counts[:held].astype(jnp.int32)


def _row_tile(pairs, num_experts):
    """The grouped GEMM's row tile for a pass of `pairs` routed rows over
    `num_experts` experts: 256 where an expert gets 128 rows or more on
    average (a prefill pass of thousands of rows: the MXU runs 256-row tiles
    at 73 % of its peak and 128-row tiles at 52 %), else 128 (a decode tick,
    a short bucket: the groups are a few rows each and the call is bound by
    reading the weights; smaller tiles only lengthen the grid), halved
    until it divides the pairs. Measured on the chip, PERF.md §6 (PR 34)."""
    bm = 256 if pairs >= 128 * num_experts else 128
    while bm > 16 and pairs % bm:
        bm //= 2
    return bm


# what the grouped GEMM's double-buffered operand blocks (whole-K: a row tile
# of `lhs`, a column tile of one expert's matrix) may take of VMEM; the
# output block and the f32 accumulator come on top, under the compiler's 16 MiB
_OPERAND_VMEM = 13 * 2 ** 20


def _column_tile(n, k, bm, itemsize=2):
    """The widest of 512, 256, 128 that divides the N the kernel pads to and
    whose [K, bn] block fits VMEM beside a [bm, K] row tile, both double
    buffered: 512 beside a 256-row tile of 4096-wide rows, 256 beside a
    128-row tile of 7168-wide ones."""
    n = -(-n // 128) * 128
    fits = [bn for bn in (512, 256, 128) if n % bn == 0
            and 2 * (bn + bm) * k * itemsize <= _OPERAND_VMEM]
    return fits[0] if fits else 128


class HeldExpertsMoE(nn.Layer):
    """Routed experts `first .. first + count - 1` of `num_experts`.

    `gate`, `route_scale`: the module docstring; a "sigmoid" layer has the
    buffer `expert_bias` [num_experts] f32.

    forward(x [..., d_model], live=None, with_stats=False): `live`
    [tokens] bool leaves rows out of the routing altogether (batch padding,
    free decode rows). With `with_stats` also returns an int32 [5] row, see
    STAT_NAMES (rows are counted over the held experts; `tile_rows` is the
    rows the grouped GEMM's visited row tiles cover, so `expert_rows_sum`
    over it is the share of the kernel's rows that are real)."""

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
        from .....ops.pallas.grouped_gemm import (end_to_end_visits,
                                                  ragged_matmul)

        first, held = self.held
        k, f = self.top_k, self.d_expert
        sigmoid, route_scale = self.gate == "sigmoid", self.route_scale
        whole, tokens = tokens, min(tokens, CHUNK_TOKENS)
        pairs = tokens * k
        bm = _row_tile(pairs, self.num_experts)
        use_kernel = kernels_available()

        def gmm(rows, w, counts, visits):
            if not use_kernel:
                return jax.lax.ragged_dot(
                    rows, w, counts, preferred_element_type=jnp.float32
                ).astype(rows.dtype)
            # the tiles follow from the shapes, so they are the tuner's only
            # candidate: nothing is swept inside a serving process, and
            # chosen_tiles()["grouped_gemm"] counts the consults
            block = (bm, _column_tile(w.shape[2], w.shape[1], bm,
                                      w.dtype.itemsize))
            tile = pick_block_sizes(
                "grouped_gemm", pairs, w.shape[2], block,
                lambda bm, bn: None, allow_measure=False,
                signature=(held, pairs, w.shape[1], w.shape[2],
                           str(rows.dtype)),
                candidates=[block])
            return ragged_matmul(rows, w, counts, tuple(tile), visits)

        def route(x, router, bias, live):
            """The flat (token, choice) pairs: `key` [T * k] the held
            expert's local index (`held` for a pair not computed here: an
            expert of another share, a row that is not live), `gates`
            [T, k] f32."""
            logits = jnp.matmul(x, router,
                                preferred_element_type=jnp.float32)
            if sigmoid:
                score = jax.nn.sigmoid(logits)                  # [T, E] f32
                _, top_expert = jax.lax.top_k(
                    score + bias[0].astype(jnp.float32), k)
                # the picked scores, each read as a sum against its pick's
                # one-hot (exact: one score and zeros; a gather of scalars
                # is slow on the chip)
                picked = (score[:, None, :] * jax.nn.one_hot(
                    top_expert, score.shape[-1], dtype=score.dtype)).sum(-1)
                gates = (picked / (picked.sum(-1, keepdims=True) + 1e-20)
                         * route_scale)
            else:
                top_logit, top_expert = jax.lax.top_k(logits, k)
                gates = jax.nn.softmax(top_logit, axis=-1)      # [T, k] f32
            local = top_expert.astype(jnp.int32) - first
            here = (local >= 0) & (local < held)
            if live:
                here = here & live[0][:, None]
            return jnp.where(here, local, held).reshape(-1), gates

        def experts(x, w_in, w_out, key, gates):
            """(out [T, d], counts [held], rows the visited tiles cover):
            the pairs sorted by held expert ONCE, their tokens' rows
            gathered end to end in that order, both grouped GEMMs over the
            same groups, and `y` gathered back by the inverse order. A pair
            not computed here lies behind every group, where the kernel
            writes zeros: it adds nothing to its token."""
            order, back, counts = _sort_by_expert(key, held)
            visits = end_to_end_visits(counts, pairs, bm)
            ab = gmm(x[order // k], w_in, counts, visits)
            hidden = (jax.nn.silu(ab[:, :f].astype(jnp.float32))
                      * ab[:, f:].astype(jnp.float32)).astype(x.dtype)
            y = gmm(hidden, w_out, counts, visits)
            # gathered choice-major and summed pick after pick, so that the
            # k terms are k contiguous slices of ONE gather and the sum is
            # one fusion over them ([tokens, k, d] with k = 10 is a relayout
            # of the whole buffer in f32)
            picked = y[back.reshape(tokens, k).T.reshape(-1)]
            out = None
            for j in range(k):
                term = gates[:, j, None] * picked[
                    j * tokens:(j + 1) * tokens].astype(jnp.float32)
                out = term if out is None else out + term
            out = out.astype(x.dtype)
            return out, counts, visits.n[0] * bm

        def stats_of(counts, tile_rows):
            # every held pair has its row by construction: dropped is 0
            return jnp.stack([counts.sum(), counts.max(), counts.sum(),
                              0, tile_rows]).astype(jnp.int32)

        def one_pass(x, router, w_in, w_out, bias, live):
            key, gates = route(x, router, bias, live)
            return experts(x, w_in, w_out, key, gates)

        def fn(x, router, w_in, w_out, *rest):
            out, counts, tile_rows = one_pass(
                x, router, w_in, w_out, rest[:sigmoid], rest[sigmoid:])
            return out, stats_of(counts, tile_rows)

        if whole <= tokens:
            return fn
        n, pad = chunks_for(whole), -whole % tokens

        def chunked(x, router, w_in, w_out, *rest):
            """The layer over `n` passes of the call's tokens, one after the
            other; the padding behind the last is dead, and a pass with no
            live token (a bucket's tail) is skipped whole. An expert's rows
            are counted over the whole call."""
            bias, live = rest[:sigmoid], rest[sigmoid:]
            xs = jnp.pad(x, ((0, pad), (0, 0))).reshape(n, tokens, -1)
            if not (has_live or pad):
                def one(_, xc):
                    return None, one_pass(xc, router, w_in, w_out, bias, ())
                scanned = xs
            else:
                real = live[0] if has_live else jnp.ones(whole, bool)
                scanned = (xs, jnp.pad(real, (0, pad)).reshape(n, tokens))

                def one(_, chunk):
                    xc, alive = chunk
                    return None, jax.lax.cond(
                        alive.any(),
                        lambda: one_pass(xc, router, w_in, w_out, bias,
                                         (alive,)),
                        lambda: (jnp.zeros_like(xc),
                                 jnp.zeros(held, jnp.int32),
                                 jnp.zeros((), jnp.int32)))

            _, (out, counts, tile_rows) = jax.lax.scan(one, None, scanned)
            return (out.reshape(n * tokens, -1)[:whole],
                    stats_of(counts.sum(0), tile_rows.sum()))

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
