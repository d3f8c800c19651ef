"""A decoder whose sliding-window layers keep more KV heads than its full
ones, with keys wider than values, a learned sink in the sliding layers'
softmax and sigmoid-routed experts with no shared one (the `mimo_v2` family:
Xiaomi MiMo-V2-Flash).

`hybrid_layer_pattern[l]` says layer l's attention, 0 full and 1 sliding;
`moe_layer_freq[l]` its MLP, 0 dense and 1 experts. Pre-norm, two RMSNorms a
layer (eps `layernorm_epsilon`), `h = E[ids]` going in:

    a = norm_in(h)
    q = Wq a -> [H, Dk];  k = Wk a -> [Hkv_t, Dk];
    v = attention_value_scale * (Wv a) -> [Hkv_t, Dv]
          Hkv_t: num_key_value_heads (full), swa_num_key_value_heads
          (sliding); Dk = head_dim, Dv = v_head_dim; no bias
    rotate-half RoPE on values 0 .. R - 1 of each q and k head, R =
          `rotary_dim` (partial_rotary_factor x head_dim to the nearest even
          number: 64 of 192), base rope_theta (full) or swa_rope_theta
          (sliding); values R .. Dk - 1 unrotated
    s_ij = q_i . k_j / sqrt(Dk);  full: j <= i;
                                  sliding: 0 <= i - j < sliding_window
    full:     p_ij = exp(s_ij) / sum_j' exp(s_ij')
    sliding:  p_ij = exp(s_ij) / (exp(b_head) + sum_j' exp(s_ij'))
          b: `sink`, H learned logits a sliding layer; it adds no value
    h = h + Wo [sum_j p_ij v_j]          ([H x Dv] -> hidden)
    m = norm_post(h)
    y = Wd (silu(Wg m) * Wu m)                      a dense layer
    y = sum over the picked experts held here of w_e expert_e(m)    else:
          score = sigmoid(Wr m) in f32 over all experts; the k largest of
          score + e_score_correction_bias (in the choice only);
          w = picked / (their sum + 1e-20); no shared expert
    h = h + y
    logits = Wh norm_out(h)                                  untied head

The routed experts are `HeldExpertsMoE` with the "sigmoid" gate and
`route_scale` 1: this chip's share of them. The plain float32 reference with
the equations written out is `benchmark/reference/mimo_v2.py`.

The cache contract is the serving engines' (`tok, pos, caches, off,
block_tables=`), with pages of TWO shapes out of one pool (`cache_specs()`):
`PagedKV(Hkv_full, Dk, value_dim=Dv)` for a full layer,
`WindowKV(Hkv_sliding, Dk, window, value_dim=Dv)` for a sliding one, whose
page is `Hkv_sliding / Hkv_full` adjacent units of the full layers' and
expires. The pool's arrays are in units (`inference/paged/block_pool`), so a
decode step gets one cache entry per ARRAY, `[units, Hkv_full, ps, W]`, and
one block table per GROUP; a layer sees the entry through ITS head count
(`[units / span, Hkv_t, ps, W]`: the same memory), threads it through in
layer order, and a sliding layer reads its table from the row's first cached
position (`window_starts`). With `caches` and no `block_tables` the call is
a prefill from position 0, which reads no cache and returns every layer's K
and V (`seq_lens`: the prompts' real lengths inside the padded bucket; the
padding is routed to no expert, and the per-token work of the bucket's
pieces that lie wholly past the prompt is skipped: `_live_pieces`).
"""

from __future__ import annotations

import dataclasses
import math

import jax
import jax.numpy as jnp

from .. import nn
from ..framework.core import Tensor, run_op
from ..incubate.distributed.models.moe.held_moe import (HeldExpertsMoE,
                                                        chunks_for,
                                                        total_stats)
from ..nn import initializer as I
from .afmoe import AfmoeMLP, _attr, _cast, _linear, _norm
from .gpt import hidden_at

__all__ = ["MimoV2Config", "MimoV2ForCausalLM", "mimo_v2_tiny"]

# the published pattern: layer 0 full, then five sliding to one full
_PATTERN = (0, 1, 1, 1, 1) + (0, 1, 1, 1, 1, 1) * 7 + (0,)


@dataclasses.dataclass
class MimoV2Config:
    """Keys as in the family's public `config.json`, plus `held_experts`:
    (first, count) of the routed experts this model holds, None for all."""

    vocab_size: int = 152576
    hidden_size: int = 4096
    intermediate_size: int = 16384        # a dense layer's MLP
    moe_intermediate_size: int = 2048     # one routed expert
    hybrid_layer_pattern: tuple = _PATTERN     # 0 full, 1 sliding
    moe_layer_freq: tuple = (0,) + (1,) * 47   # 0 dense, 1 experts
    num_attention_heads: int = 64
    num_key_value_heads: int = 4
    head_dim: int = 192
    v_head_dim: int = 128
    swa_num_attention_heads: int = 64
    swa_num_key_value_heads: int = 8
    swa_head_dim: int = 192
    swa_v_head_dim: int = 128
    sliding_window: int = 128
    rope_theta: float = 5000000.0
    swa_rope_theta: float = 10000.0
    partial_rotary_factor: float = 0.334
    attention_value_scale: float = 0.707
    add_swa_attention_sink_bias: bool = True
    add_full_attention_sink_bias: bool = False
    layernorm_epsilon: float = 1e-5
    n_routed_experts: int = 256
    num_experts_per_tok: int = 8
    held_experts: tuple | None = None
    initializer_range: float = 0.02
    # what a seeded model draws its norm weights round 1, its experts' bias
    # round 0 and its sinks round 0 with (a loaded model overwrites them)
    norm_weight_std: float = 0.1
    expert_bias_std: float = 0.02
    sink_std: float = 1.0
    # the parameters' dtype; the model is cast a layer at a time as it is
    # built and the float32 form freed (`afmoe._cast`)
    dtype: str = "float32"

    def __post_init__(self):
        self.hybrid_layer_pattern = tuple(
            int(v) for v in self.hybrid_layer_pattern)
        self.moe_layer_freq = tuple(
            int(v) for v in self.moe_layer_freq)[:self.num_layers]
        if len(self.moe_layer_freq) != self.num_layers:
            raise ValueError("moe_layer_freq shorter than the layers")
        if set(self.hybrid_layer_pattern) - {0, 1}:
            raise ValueError("hybrid_layer_pattern holds 0 (full) and 1 "
                             "(sliding) only")
        same = ((self.swa_num_attention_heads, self.num_attention_heads),
                (self.swa_head_dim, self.head_dim),
                (self.swa_v_head_dim, self.v_head_dim))
        if any(a != b for a, b in same):
            raise ValueError("sliding layers of other query heads or head "
                             "widths than the full ones are not supported")
        if self.held_experts is None:
            self.held_experts = (0, self.n_routed_experts)
        self.held_experts = tuple(int(v) for v in self.held_experts)

    @property
    def num_layers(self):
        return len(self.hybrid_layer_pattern)

    @property
    def rms_norm_eps(self):     # what `afmoe._norm` reads
        return self.layernorm_epsilon

    @property
    def rotary_dim(self):
        """Values of a head that RoPE turns: the factor's share of the head,
        to the nearest even number (0.334 x 192 = 64.1 -> 64)."""
        return 2 * round(self.partial_rotary_factor * self.head_dim / 2)

    def kv_heads_of(self, sliding):
        return (self.swa_num_key_value_heads if sliding
                else self.num_key_value_heads)


def _rope(x, pos, theta, rotary):
    """Rotate-half RoPE over the first `rotary` values of each head, the
    rest as they are. x [B, S, heads, D] f32, pos [B, S] positions."""
    half = rotary // 2
    inv = theta ** (-jnp.arange(half, dtype=jnp.float32) / half)
    ang = pos.astype(jnp.float32)[..., None] * inv          # [B, S, half]
    cos, sin = jnp.cos(ang)[:, :, None], jnp.sin(ang)[:, :, None]
    a, b = x[..., :half], x[..., half:rotary]
    return jnp.concatenate(
        [a * cos - b * sin, b * cos + a * sin, x[..., rotary:]], axis=-1)


def _masked_attention(q, k, v, window, scale, sink):
    """The composite where no kernel runs (a bare CPU): causal, with
    `window` only the last `window` keys, with `sink` [H] one more term in
    the denominator."""
    S, H = q.shape[1], q.shape[2]
    rep = H // k.shape[2]
    k, v = jnp.repeat(k, rep, axis=2), jnp.repeat(v, rep, axis=2)
    s = jnp.einsum("bshd,bthd->bhst", q, k,
                   preferred_element_type=jnp.float32) * scale
    i, j = jnp.arange(S)[:, None], jnp.arange(S)[None, :]
    seen = j <= i
    if window is not None:
        seen = seen & (i - j < window)
    s = jnp.where(seen, s, -1e30)
    if sink is not None:
        b = jnp.broadcast_to(sink.astype(jnp.float32)[None, :, None, None],
                             s.shape[:3] + (1,))
        p = jax.nn.softmax(jnp.concatenate([s, b], axis=-1), axis=-1)[..., :-1]
    else:
        p = jax.nn.softmax(s, axis=-1)
    return jnp.einsum("bhst,bthd->bshd", p.astype(v.dtype), v).astype(q.dtype)


# A prefill runs over a power-of-two bucket that the prompt fills to 64 % in
# the mean. Its per-token work (projections, the dense MLP, a full layer's
# attention rows) is done in this many pieces of the token axis, and a piece
# wholly past the prompt's length is skipped (`lax.cond`: zeros, which
# nothing reads), as the expert layer already skips its dead passes. Only
# buckets whose pieces are at least `_MIN_PIECE` tokens.
_PIECES = 8
_MIN_PIECE = 256


def _pieces(S, n_live):
    """Tokens a piece of an `S`-token prefill holds, 0 where the call is not
    cut (no length given, a short bucket)."""
    piece = S // _PIECES
    if n_live is None or S % _PIECES or piece < _MIN_PIECE:
        return 0
    return piece


def _live_pieces(fn, n_live, piece, *xs):
    """`fn` over `piece`-token cuts of the token axis (axis 1) of the arrays
    `xs`, side by side again; a cut that starts at or past `n_live` is
    zeros. `fn(start, *cuts)` returns a tuple of [B, piece, ...] arrays."""
    S = xs[0].shape[1]
    cuts = [[x[:, at:at + piece] for x in xs] for at in range(0, S, piece)]
    shapes = jax.eval_shape(lambda *c: fn(0, *c), *cuts[0])
    outs = [jax.lax.cond(
        at < n_live, lambda at=at, cut=cut: fn(at, *cut),
        lambda: tuple(jnp.zeros(o.shape, o.dtype) for o in shapes))
        for at, cut in zip(range(0, S, piece), cuts)]
    return tuple(jnp.concatenate(parts, axis=1) for parts in zip(*outs))


def _by_pieces(layer_fn, n_live, piece, *tensors):
    """`layer_fn` (Tensors in, a Tensor out) over the live pieces of its
    inputs' token axis; whole where `piece` is 0."""
    if not piece:
        return layer_fn(*tensors)
    (out,) = _live_pieces(
        lambda at, *cut: (layer_fn(*(Tensor(c) for c in cut))._value,),
        n_live._value, piece, *(t._value for t in tensors))
    return Tensor(out)


class MimoV2Attention(nn.Layer):
    """Grouped-query attention, keys `head_dim` and values `v_head_dim`
    wide, RoPE on part of a head; `sliding`: its own KV head count and base,
    a causal window and a learned sink a head."""

    def __init__(self, cfg: MimoV2Config, sliding: bool):
        super().__init__()
        self.cfg, self.sliding = cfg, sliding
        h, H = cfg.hidden_size, cfg.num_attention_heads
        self.kv_heads = cfg.kv_heads_of(sliding)
        self.q_proj = _linear(cfg, h, H * cfg.head_dim)
        self.k_proj = _linear(cfg, h, self.kv_heads * cfg.head_dim)
        self.v_proj = _linear(cfg, h, self.kv_heads * cfg.v_head_dim)
        self.o_proj = _linear(cfg, H * cfg.v_head_dim, h)
        from ..inference.paged.block_pool import stored_width

        # zeros behind a key up to the width the pool stores it in
        self._key_pad = stored_width(cfg.head_dim) - cfg.head_dim
        self.has_sink = (cfg.add_swa_attention_sink_bias if sliding
                         else cfg.add_full_attention_sink_bias)
        if self.has_sink:
            self.sink = self.create_parameter(
                [H], default_initializer=I.Normal(0.0, cfg.sink_std))

    def _stored(self, x):
        """Keys (or a query against them) as the pool stores them: zeros
        behind the head's values up to `block_pool.stored_width`."""
        return jnp.pad(x, ((0, 0),) * (x.ndim - 1) + ((0, self._key_pad),))

    def _prepare(self, q, k, v, pos):
        """q, k turned by RoPE on their rotated part and v scaled, in the
        projections' dtype: what attention takes and the cache keeps."""
        cfg = self.cfg
        theta = cfg.swa_rope_theta if self.sliding else cfg.rope_theta
        q32 = _rope(q.astype(jnp.float32), pos, theta, cfg.rotary_dim)
        k32 = _rope(k.astype(jnp.float32), pos, theta, cfg.rotary_dim)
        v32 = v.astype(jnp.float32) * cfg.attention_value_scale
        return q32.astype(q.dtype), k32.astype(k.dtype), v32.astype(v.dtype)

    def forward(self, u, pos, cache=None, cache_offset=None, table=None,
                start=None, n_live=None):
        """Prefill (`table` None): (out, (k, v)) with K and V as cached (K
        as wide as the pool stores it); `n_live`: the prompt's length, past
        which the bucket's pieces are skipped (`_live_pieces`).
        Decode: `cache` the pool's (K, V) arrays in units, `table` this
        layer's group's (pages of this layer's head count), `cache_offset`
        [B] tokens cached, `start` [B] the first cached position of a
        sliding layer's table."""
        cfg = self.cfg
        B, S = u.shape[0], u.shape[1]
        H, Hkv = cfg.num_attention_heads, self.kv_heads
        Dk, Dv = cfg.head_dim, cfg.v_head_dim
        window = cfg.sliding_window if self.sliding else None
        scale = 1.0 / math.sqrt(Dk)
        piece = _pieces(S, n_live) if table is None else 0
        q, k, v = (_by_pieces(proj, n_live, piece, u)
                   for proj in (self.q_proj, self.k_proj, self.v_proj))
        q = q.reshape([B, S, H, Dk])
        k = k.reshape([B, S, Hkv, Dk])
        v = v.reshape([B, S, Hkv, Dv])
        sink = [self.sink] if self.has_sink else []
        if table is not None:
            def step(q, k, v, pos, kc, vc, table, lengths, *rest):
                from ..ops.pallas.decode_attention import (
                    paged_decode_attention, paged_kv_write)

                rest = list(rest)
                b = rest.pop(0) if self.has_sink else None
                q, k, v = self._prepare(q, k, v, pos)
                lengths = lengths.astype(jnp.int32)
                if rest:   # a sliding layer's table starts at `start`
                    lengths = lengths - rest[0].astype(jnp.int32)
                # the pool's arrays through this layer's head count: a page
                # of Hkv heads is Hkv / (the unit's heads) adjacent units
                k_units, v_units = kc.shape, vc.shape
                kc = kc.reshape((-1, Hkv) + kc.shape[2:])
                vc = vc.reshape((-1, Hkv) + vc.shape[2:])
                kc = paged_kv_write(kc, self._stored(k[:, 0]), table, lengths)
                vc = paged_kv_write(vc, v[:, 0], table, lengths)
                o = paged_decode_attention(
                    self._stored(q[:, 0]), kc, vc, table, lengths + 1,
                    scale=scale, window=window, sink=b)
                return (o[:, None], kc.reshape(k_units),
                        vc.reshape(v_units))

            out, kc, vc = run_op(
                "mimo_v2_paged_attention", step,
                [q, k, v, pos, cache[0], cache[1], table, cache_offset]
                + sink + ([start] if self.sliding else []), n_outputs=3)
            new_cache = (kc, vc)
        else:
            def whole(q, k, v, pos, *rest):
                from ..nn.functional.flash_attention import _use_pallas_kernel

                rest = list(rest)
                b = rest.pop(0) if self.has_sink else None
                q, k, v = self._prepare(q, k, v, pos)
                if not _use_pallas_kernel():
                    o = _masked_attention(q, k, v, window, scale, b)
                elif window is not None:
                    from ..ops.pallas.flash_attention import flash_window_fwd

                    o = flash_window_fwd(q, k, v, window, scale=scale, sink=b)
                elif b is None:
                    from ..ops.pallas.flash_attention import (
                        flash_attention_fwd)

                    if not rest:
                        o = flash_attention_fwd(q, k, v, causal=True,
                                                scale=scale)
                    else:
                        # a piece of queries against the keys up to its
                        # end (the kernel's causal mask is aligned bottom
                        # right), the pieces past the prompt skipped
                        (o,) = _live_pieces(
                            lambda at, qc: (flash_attention_fwd(
                                qc, k[:, :at + piece], v[:, :at + piece],
                                causal=True, scale=scale),),
                            rest[0], piece, q)
                else:
                    raise NotImplementedError(
                        "a sink on a full attention layer's prefill")
                return o, self._stored(k), v

            out, k, v = run_op(
                "mimo_v2_attention", whole,
                [q, k, v, pos] + sink + ([n_live] if piece else []),
                n_outputs=3)
            new_cache = (k, v)   # a prefill from position 0: the prompt's own
        return _by_pieces(self.o_proj, n_live, piece,
                          out.reshape([B, S, H * Dv])), new_cache


class MimoV2Layer(nn.Layer):
    def __init__(self, cfg: MimoV2Config, sliding: bool, dense: bool):
        super().__init__()
        self.cfg, self.dense = cfg, dense
        self.kind = "sliding_attention" if sliding else "full_attention"
        self.input_layernorm = _norm(cfg)
        self.self_attn = MimoV2Attention(cfg, sliding)
        self.post_attention_layernorm = _norm(cfg)
        if dense:
            self.mlp = AfmoeMLP(cfg, cfg.intermediate_size)
        else:
            self.moe = HeldExpertsMoE(
                cfg.hidden_size, cfg.moe_intermediate_size,
                cfg.n_routed_experts, cfg.num_experts_per_tok,
                held=cfg.held_experts, weight_attr=_attr(cfg),
                gate="sigmoid", route_scale=1.0)
            # a seeded model's bias is small and not zero, so that it
            # decides some picks
            I.Normal(0.0, cfg.expert_bias_std)(self.moe.expert_bias)

    def forward(self, x, pos, cache, cache_offset, table, start, token_live,
                n_live=None):
        with jax.named_scope("ln"):
            u = self.input_layernorm(x)
        with jax.named_scope(self.kind):
            h, new_cache = self.self_attn(u, pos, cache, cache_offset, table,
                                          start, n_live)
        with jax.named_scope("ln"):
            x = x + h
            u = self.post_attention_layernorm(x)
        stats = None
        if self.dense:
            with jax.named_scope("mlp"):
                y = _by_pieces(self.mlp, n_live,
                               _pieces(u.shape[1], n_live), u)
        else:
            with jax.named_scope("moe"):
                y, stats = self.moe(u, live=token_live, with_stats=True)
        return x + y, new_cache, stats


class MimoV2ForCausalLM(nn.Layer):
    def __init__(self, config: MimoV2Config):
        super().__init__()
        self.config = config
        self.embed_tokens = _cast(nn.Embedding(
            config.vocab_size, config.hidden_size,
            weight_attr=_attr(config)), config.dtype)
        self.layers = nn.LayerList(
            [_cast(MimoV2Layer(config, bool(sliding), not experts),
                   config.dtype)
             for sliding, experts in zip(config.hybrid_layer_pattern,
                                         config.moe_layer_freq)])
        self.norm = _cast(_norm(config), config.dtype)
        self.lm_head = _cast(_linear(config, config.hidden_size,
                                     config.vocab_size), config.dtype)
        self._layout = None

    def cache_specs(self):
        """What each layer keeps per request, for the cache manager: pages
        of the layer's own head count, keys and values each of its width."""
        from ..inference.paged.block_pool import PagedKV, WindowKV

        cfg = self.config
        full = PagedKV(cfg.kv_heads_of(False), cfg.head_dim,
                       value_dim=cfg.v_head_dim)
        window = WindowKV(cfg.kv_heads_of(True), cfg.head_dim,
                          cfg.sliding_window, value_dim=cfg.v_head_dim)
        return [window if sliding else full
                for sliding in cfg.hybrid_layer_pattern]

    @property
    def moe_groups(self):
        """Expert layers x held experts: what `expert_rows_sum` sums over."""
        cfg = self.config
        return sum(cfg.moe_layer_freq) * cfg.held_experts[1]

    def prefill_span_attrs(self, bucket):
        """For the engine's `prefill` span: passes of each expert layer."""
        return {"chunks": chunks_for(bucket)}

    def _page_layout(self):
        if self._layout is None:
            from ..inference.paged.block_pool import page_layout

            self._layout = page_layout(self.cache_specs())
        return self._layout

    def forward(self, input_ids, position_ids=None, caches=None,
                cache_offset=None, block_tables=None, seq_lens=None,
                with_stats=False, logits_at=None, window_starts=None):
        """logits [B, S, vocab] (with `logits_at` [B]: of that one position
        a row, [B, 1, vocab]); with `caches` also the new caches, one a
        layer from a prefill, one a pool array from a decode step
        (`block_tables`: one table a page group); with `with_stats` also the
        int32 row of `held_moe.STAT_NAMES` summed over the expert layers
        (`expert_rows_max`: the largest)."""
        B, S = input_ids.shape[0], input_ids.shape[1]
        decode = caches is not None and block_tables is not None
        if position_ids is None:
            position_ids = Tensor(jnp.broadcast_to(jnp.arange(S)[None],
                                                   (B, S)))
        token_live = n_live = None
        if decode:
            _, entry_of, group_of = self._page_layout()
            if isinstance(block_tables, Tensor):
                block_tables = (block_tables,)
            token_live = run_op("live_rows", lambda t: t[:, 0] >= 0,
                                [block_tables[0]])
            caches = list(caches)
        elif seq_lens is not None:
            token_live = run_op(
                "real_tokens",
                lambda n: (jnp.arange(S)[None, :] < n[:, None]).reshape(-1),
                [seq_lens])
            # the longest prompt of the batch: pieces of the bucket wholly
            # past it are skipped
            n_live = run_op("longest_prompt", lambda n: n.max(), [seq_lens])
        with jax.named_scope("embed"):
            x = self.embed_tokens(input_ids)
        new_caches, stats = [], []
        for i, layer in enumerate(self.layers):
            if decode:
                x, new_cache, st = layer(
                    x, position_ids, caches[entry_of[i]], cache_offset,
                    block_tables[group_of[i]], window_starts, token_live)
                caches[entry_of[i]] = new_cache
            else:
                x, new_cache, st = layer(x, position_ids, None, None, None,
                                         None, token_live, n_live)
                new_caches.append(new_cache)
            if st is not None:
                stats.append(st)
        if logits_at is not None:
            x = hidden_at(x, logits_at)
        with jax.named_scope("ln"):
            x = self.norm(x)
        with jax.named_scope("lm_head"):
            logits = self.lm_head(x)
        out = (logits,)
        if caches is not None:
            out += (caches if decode else new_caches,)
        if with_stats:
            out += (run_op("moe_stats", total_stats, stats),)
        return out[0] if len(out) == 1 else out


def mimo_v2_tiny(**kw):
    """A CPU-test size with every mechanism: the published pattern's first
    seven layers (full, four sliding, full, sliding), layer 0 dense, sliding
    layers of twice the KV heads, keys of 24 beside values of 16, 8 of them
    rotated, a window of 16."""
    base = dict(vocab_size=256, hidden_size=64, intermediate_size=96,
                moe_intermediate_size=32,
                hybrid_layer_pattern=_PATTERN[:7],
                moe_layer_freq=(0,) + (1,) * 6,
                num_attention_heads=4, num_key_value_heads=1, head_dim=24,
                v_head_dim=16, swa_num_attention_heads=4,
                swa_num_key_value_heads=2, swa_head_dim=24, swa_v_head_dim=16,
                sliding_window=16, n_routed_experts=8, num_experts_per_tok=4)
    base.update(kw)
    return MimoV2Config(**base)
