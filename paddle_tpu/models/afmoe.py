"""A decoder with sliding-window and full attention layers side by side,
gated QK-normed heads and sigmoid-routed experts beside a shared one (the
`afmoe` family: Arcee Trinity).

`layer_types` lists each layer's attention, "sliding_attention" or
"full_attention". With four RMSNorms a layer (sandwich):

    a = norm_in(h);  q, k, v, g = Wq a, Wk a, Wv a, Wg a
    q, k RMS-normed per head (learned weight over the head's width)
    sliding: RoPE (rotate-half over the whole head) on q and k, key j visible
             to query i iff 0 <= i - j < sliding_window
    full:    NO positions, j <= i
    o = softmax(q k^T / sqrt(head_dim)) v
    h = h + norm_post_attn(Wo (o * sigmoid(g)))
    m = norm_pre_mlp(h)
    y = Wd (silu(Wg' m) * Wu m)                      for the leading dense layers
    y = shared(m) + sum over the picked experts of w_e expert_e(m)    after them
    h = h + norm_post_mlp(y)

`h = E[ids] * sqrt(hidden_size)` going in, RMSNorm and an untied head coming
out, no bias anywhere. The routed experts are `HeldExpertsMoE` with the
"sigmoid" gate: this chip's share of them. The plain float32 reference with
the equations written out is `benchmark/reference/afmoe.py`.

The cache contract is the serving engines' (`tok, pos, caches, off,
block_tables=`), with two kinds of paged cache (`cache_specs()`): `PagedKV`
for a full layer, `WindowKV` for a sliding one, whose pages expire. The pool
keeps the layers' K and V in `depth` arrays shared by page GROUPS
(`inference/paged/block_pool.page_layout`), so a decode step gets one cache
entry per ARRAY and one block table per GROUP, threads each entry through
the layers that share it in layer order, and a sliding layer reads its
table from the row's first cached position (`window_starts`). With `caches`
and no `block_tables` the call is a prefill from position 0, which reads no
cache and returns every layer's K and V (`seq_lens`: the prompts' real
lengths inside the padded bucket; the padding is routed to no expert).
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
from .gpt import hidden_at

__all__ = ["AfmoeConfig", "AfmoeForCausalLM", "afmoe_tiny"]

_KINDS = ("sliding_attention", "full_attention")


@dataclasses.dataclass
class AfmoeConfig:
    """Keys as in the family's public `config.json`, plus `held_experts`:
    (first, count) of the routed experts this model holds, None for all."""

    vocab_size: int = 200192
    hidden_size: int = 2048
    intermediate_size: int = 6144         # a leading dense layer's MLP
    moe_intermediate_size: int = 1024     # one routed or shared expert
    layer_types: tuple = _KINDS[:1] * 3 + _KINDS[1:]
    num_dense_layers: int = 2
    num_attention_heads: int = 32
    num_key_value_heads: int = 4
    head_dim: int = 128
    sliding_window: int = 2048
    rope_theta: float = 10000.0
    rms_norm_eps: float = 1e-5
    num_experts: int = 128
    num_experts_per_tok: int = 8
    num_shared_experts: int = 1
    route_scale: float = 2.826
    mup_enabled: bool = True
    held_experts: tuple | None = None
    initializer_range: float = 0.02
    # what a seeded model draws its norm weights round 1 and its experts'
    # bias round 0 with (a loaded model overwrites both)
    norm_weight_std: float = 0.1
    expert_bias_std: float = 0.02
    # the parameters' dtype; the model is cast a layer at a time as it is
    # built and the float32 form freed (`_cast`), so a bf16 model never
    # exists in float32
    dtype: str = "float32"

    def __post_init__(self):
        self.layer_types = tuple(self.layer_types)
        bad = set(self.layer_types) - set(_KINDS)
        if bad:
            raise ValueError(f"unknown layer types {sorted(bad)}")
        if self.held_experts is None:
            self.held_experts = (0, self.num_experts)
        self.held_experts = tuple(int(v) for v in self.held_experts)

    @property
    def num_layers(self):
        return len(self.layer_types)

    @property
    def kv_heads(self):
        return self.num_key_value_heads


def _cast(layer, dtype):
    """`layer.astype(dtype)`, returning only when the casts have run and
    with each array they replaced DELETED: once a part is built, the device
    holds its `dtype` form alone, and `memory_stats()` says so at once (a
    float32 head of 1.64 GB that waits for the collector would be counted
    against whatever is sized from the free memory next)."""
    tensors = [*layer.parameters(), *layer.buffers()]
    before = [t._value for t in tensors]
    layer.astype(dtype)
    after = jax.block_until_ready([t._value for t in tensors])
    for old, new in zip(before, after):
        if old is not new and not old.is_deleted():
            old.delete()
    return layer


def _attr(cfg):
    return nn.ParamAttr(initializer=I.Normal(0.0, cfg.initializer_range))


def _linear(cfg, n_in, n_out):
    return nn.Linear(n_in, n_out, weight_attr=_attr(cfg), bias_attr=False)


def _norm(cfg, width=None):
    norm = nn.RMSNorm(width or cfg.hidden_size, epsilon=cfg.rms_norm_eps)
    I.Normal(1.0, cfg.norm_weight_std)(norm.weight)   # drawn round 1
    return norm


def _head_norm(x, w, eps):
    """RMSNorm over each head's width, in f32. x [..., heads, D], w [D]."""
    x = x.astype(jnp.float32)
    return (x * jax.lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps)
            * w.astype(jnp.float32))


def _rope(x, pos, theta):
    """Rotate-half RoPE over the whole head. x [B, S, heads, D] f32,
    pos [B, S] absolute positions."""
    half = x.shape[-1] // 2
    inv = theta ** (-jnp.arange(half, dtype=jnp.float32) / half)
    ang = pos.astype(jnp.float32)[..., None] * inv          # [B, S, half]
    cos = jnp.concatenate([jnp.cos(ang)] * 2, axis=-1)[:, :, None]
    sin = jnp.concatenate([jnp.sin(ang)] * 2, axis=-1)[:, :, None]
    turned = jnp.concatenate([-x[..., half:], x[..., :half]], axis=-1)
    return x * cos + turned * sin


class AfmoeAttention(nn.Layer):
    """Grouped-query attention with per-head RMSNorm on q and k and a
    sigmoid gate on the output; `sliding`: RoPE and a causal window,
    otherwise no positions and plain causal."""

    def __init__(self, cfg: AfmoeConfig, sliding: bool):
        super().__init__()
        self.cfg, self.sliding = cfg, sliding
        h, D = cfg.hidden_size, cfg.head_dim
        H, Hkv = cfg.num_attention_heads, cfg.kv_heads
        self.q_proj = _linear(cfg, h, H * D)
        self.k_proj = _linear(cfg, h, Hkv * D)
        self.v_proj = _linear(cfg, h, Hkv * D)
        self.gate_proj = _linear(cfg, h, H * D)
        self.o_proj = _linear(cfg, H * D, h)
        self.q_norm = _norm(cfg, D)
        self.k_norm = _norm(cfg, D)

    def _prepare(self, q, k, qw, kw, pos):
        """q and k as attention takes them: normed per head, turned by RoPE
        on a sliding layer; in the projections' dtype."""
        cfg = self.cfg
        qn = _head_norm(q, qw, cfg.rms_norm_eps)
        kn = _head_norm(k, kw, cfg.rms_norm_eps)
        if self.sliding:
            qn = _rope(qn, pos, cfg.rope_theta)
            kn = _rope(kn, pos, cfg.rope_theta)
        return qn.astype(q.dtype), kn.astype(k.dtype)

    def forward(self, u, pos, cache=None, cache_offset=None, table=None,
                start=None):
        """Prefill (`table` None): (out, (k, v)) with K and V as cached.
        Decode: `cache` the pool's (K, V) arrays, `table` this layer's
        group's, `cache_offset` [B] tokens cached, `start` [B] the first
        cached position of a sliding layer's table."""
        cfg = self.cfg
        B, S = u.shape[0], u.shape[1]
        H, Hkv, D = cfg.num_attention_heads, cfg.kv_heads, cfg.head_dim
        window = cfg.sliding_window if self.sliding else None
        scale = 1.0 / math.sqrt(D)
        q = self.q_proj(u).reshape([B, S, H, D])
        k = self.k_proj(u).reshape([B, S, Hkv, D])
        v = self.v_proj(u).reshape([B, S, Hkv, D])
        gate = self.gate_proj(u)
        norms = [self.q_norm.weight, self.k_norm.weight]
        if table is not None:
            def step(q, k, v, qw, kw, pos, kc, vc, table, lengths, *start):
                from ..ops.pallas.decode_attention import (
                    paged_decode_attention, paged_kv_write)

                q, k = self._prepare(q, k, qw, kw, pos)
                lengths = lengths.astype(jnp.int32)
                if start:   # a sliding layer's table starts at `start`
                    lengths = lengths - start[0].astype(jnp.int32)
                kc = paged_kv_write(kc, k[:, 0], table, lengths)
                vc = paged_kv_write(vc, v[:, 0], table, lengths)
                o = paged_decode_attention(q[:, 0], kc, vc, table,
                                           lengths + 1, scale=scale,
                                           window=window)
                return o[:, None], kc, vc

            out, kc, vc = run_op(
                "afmoe_paged_attention", step,
                [q, k, v] + norms + [pos, cache[0], cache[1], table,
                                     cache_offset]
                + ([start] if self.sliding else []), n_outputs=3)
            new_cache = (kc, vc)
        else:
            def whole(q, k, v, qw, kw, pos):
                from ..nn.functional.flash_attention import _use_pallas_kernel

                q, k = self._prepare(q, k, qw, kw, pos)
                if _use_pallas_kernel():
                    from ..ops.pallas.flash_attention import (
                        flash_attention_fwd, flash_window_fwd)

                    if window is not None:
                        o = flash_window_fwd(q, k, v, window, scale=scale)
                    else:
                        o = flash_attention_fwd(q, k, v, causal=True,
                                                scale=scale)
                else:
                    o = _masked_attention(q, k, v, window, scale)
                return o, k

            out, k = run_op("afmoe_attention", whole, [q, k, v] + norms + [pos],
                            n_outputs=2)
            new_cache = (k, v)   # a prefill from position 0: the prompt's own

        def gated(o, g):
            return (o.reshape(g.shape).astype(jnp.float32)
                    * jax.nn.sigmoid(g.astype(jnp.float32))).astype(g.dtype)

        return self.o_proj(run_op("sigmoid_gate", gated, [out, gate])), \
            new_cache


def _masked_attention(q, k, v, window, scale):
    """The composite where no kernel runs (a bare CPU): causal, and with
    `window` only the last `window` keys."""
    B, S, H, D = q.shape
    rep = H // k.shape[2]
    k, v = jnp.repeat(k, rep, axis=2), jnp.repeat(v, rep, axis=2)
    s = jnp.einsum("bshd,bthd->bhst", q, k,
                   preferred_element_type=jnp.float32) * scale
    i, j = jnp.arange(S)[:, None], jnp.arange(S)[None, :]
    seen = j <= i
    if window is not None:
        seen = seen & (i - j < window)
    p = jax.nn.softmax(jnp.where(seen, s, -1e30), axis=-1)
    return jnp.einsum("bhst,bthd->bshd", p.astype(v.dtype), v).astype(q.dtype)


class AfmoeMLP(nn.Layer):
    """A gated MLP `width` wide: w_down (silu(a) * b), [a | b] = w_gate_up x
    (a dense layer's MLP, the shared expert)."""

    def __init__(self, cfg: AfmoeConfig, width: int):
        super().__init__()
        self.width = width
        self.gate_up_proj = _linear(cfg, cfg.hidden_size, 2 * width)
        self.down_proj = _linear(cfg, width, cfg.hidden_size)

    def forward(self, u):
        f = self.width

        def gate(ab):
            return (jax.nn.silu(ab[..., :f].astype(jnp.float32))
                    * ab[..., f:].astype(jnp.float32)).astype(ab.dtype)

        return self.down_proj(
            run_op("gated_silu", gate, [self.gate_up_proj(u)]))


class AfmoeLayer(nn.Layer):
    def __init__(self, cfg: AfmoeConfig, kind: str, dense: bool):
        super().__init__()
        self.cfg, self.kind, self.dense = cfg, kind, dense
        self.input_layernorm = _norm(cfg)
        self.self_attn = AfmoeAttention(cfg, kind == "sliding_attention")
        self.post_attention_layernorm = _norm(cfg)
        self.pre_mlp_layernorm = _norm(cfg)
        if dense:
            self.mlp = AfmoeMLP(cfg, cfg.intermediate_size)
        else:
            self.moe = HeldExpertsMoE(
                cfg.hidden_size, cfg.moe_intermediate_size, cfg.num_experts,
                cfg.num_experts_per_tok, held=cfg.held_experts,
                weight_attr=_attr(cfg), gate="sigmoid",
                route_scale=cfg.route_scale)
            # a seeded model's bias is small and not zero, so that it
            # decides some picks
            I.Normal(0.0, cfg.expert_bias_std)(self.moe.expert_bias)
            self.shared_experts = AfmoeMLP(
                cfg, cfg.moe_intermediate_size * cfg.num_shared_experts)
        self.post_mlp_layernorm = _norm(cfg)

    def forward(self, x, pos, cache, cache_offset, table, start, token_live):
        with jax.named_scope("ln"):
            u = self.input_layernorm(x)
        with jax.named_scope(self.kind):
            h, new_cache = self.self_attn(u, pos, cache, cache_offset, table,
                                          start)
        with jax.named_scope("ln"):
            x = x + self.post_attention_layernorm(h)
            u = self.pre_mlp_layernorm(x)
        stats = None
        if self.dense:
            with jax.named_scope("mlp"):
                y = self.mlp(u)
        else:
            with jax.named_scope("moe"):
                routed, stats = self.moe(u, live=token_live, with_stats=True)
                y = routed + self.shared_experts(u)
        with jax.named_scope("ln"):
            x = x + self.post_mlp_layernorm(y)
        return x, new_cache, stats


class AfmoeForCausalLM(nn.Layer):
    def __init__(self, config: AfmoeConfig):
        super().__init__()
        self.config = config
        # drawn sqrt(hidden) times smaller than the other weights, so that
        # what enters the residual stream has their scale
        scale = math.sqrt(config.hidden_size) if config.mup_enabled else 1.0
        self.embed_scale = scale
        self.embed_tokens = _cast(nn.Embedding(
            config.vocab_size, config.hidden_size,
            weight_attr=nn.ParamAttr(initializer=I.Normal(
                0.0, config.initializer_range / scale))), config.dtype)
        self.layers = nn.LayerList(
            [_cast(AfmoeLayer(config, kind, i < config.num_dense_layers),
                   config.dtype)
             for i, kind in enumerate(config.layer_types)])
        self.norm = _cast(_norm(config), config.dtype)
        self.lm_head = _cast(_linear(config, config.hidden_size,
                                     config.vocab_size), config.dtype)
        self._layout = None

    def cache_specs(self):
        """What each layer keeps per request, for the cache manager."""
        from ..inference.paged.block_pool import PagedKV, WindowKV

        cfg = self.config
        full = PagedKV(cfg.kv_heads, cfg.head_dim)
        window = WindowKV(cfg.kv_heads, cfg.head_dim, cfg.sliding_window)
        return [window if kind == "sliding_attention" else full
                for kind in cfg.layer_types]

    @property
    def moe_groups(self):
        """Expert layers x held experts: what `expert_rows_sum` sums over."""
        cfg = self.config
        return (cfg.num_layers - cfg.num_dense_layers) * cfg.held_experts[1]

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
        cfg = self.config
        B, S = input_ids.shape[0], input_ids.shape[1]
        decode = caches is not None and block_tables is not None
        if position_ids is None:
            position_ids = Tensor(jnp.broadcast_to(jnp.arange(S)[None],
                                                   (B, S)))
        token_live = None
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
        with jax.named_scope("embed"):
            x = run_op("scaled_embedding", lambda e: e * self.embed_scale,
                       [self.embed_tokens(input_ids)])
        new_caches, stats = [], []
        for i, layer in enumerate(self.layers):
            if decode:
                x, new_cache, st = layer(
                    x, position_ids, caches[entry_of[i]], cache_offset,
                    block_tables[group_of[i]], window_starts, token_live)
                caches[entry_of[i]] = new_cache
            else:
                x, new_cache, st = layer(x, position_ids, None, None, None,
                                         None, token_live)
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


def afmoe_tiny(**kw):
    """A CPU-test size with every mechanism: two periods of three sliding
    layers and a full one, two leading dense layers, a window of 32."""
    base = dict(vocab_size=256, hidden_size=64, intermediate_size=96,
                moe_intermediate_size=32, layer_types=_KINDS[:1] * 3
                + _KINDS[1:] + _KINDS[:1] * 3 + _KINDS[1:],
                num_dense_layers=2, num_attention_heads=4,
                num_key_value_heads=2, head_dim=16, sliding_window=32,
                num_experts=8, num_experts_per_tok=4)
    base.update(kw)
    return AfmoeConfig(**base)
