"""A decoder whose layer pattern is data: Mamba-2 layers beside attention
layers, routed experts plus a shared expert in every layer (the
`granitemoehybrid` family: Granite 4.0-H).

`layer_types` lists each layer's mixer, "mamba" or "attention". Whatever the
mixer, a layer is

    x = x + r * mixer(norm1(x));  u = norm2(x);  x = x + r * (moe(u) + shared(u))

with RMSNorm, r = `residual_multiplier`, the embedding scaled by
`embedding_multiplier`, the tied head's logits divided by `logits_scaling`,
and no positions of any kind. The attention mixer is grouped-query attention
whose softmax scale is `attention_multiplier`. The Mamba-2 mixer is in two
forms that agree: a chunked (SSD) scan over a whole prompt, which returns
the state after the prompt's last real token, and a one-token recurrence.
The routed experts are `HeldExpertsMoE`: this chip's share of them. The
plain float32 reference with the equations written out is
`benchmark/reference/granite_hybrid.py`.

The cache contract is the serving engines' (`tok, pos, caches, off,
block_tables=`): `caches[i]` is whatever layer i's kind defines, `(k, v)`
pages for "attention", `(conv_state, ssm_state)` rows for "mamba"
(`cache_specs()`; `inference/paged/block_pool.py`). With `caches` and no
`block_tables` the call is a prefill from position 0, which reads no cache
(the engines pass an empty `caches`: a recurrent layer starts from zeros made
here) and returns every layer's (`seq_lens` = the prompts' real lengths
inside the padded bucket); with both it is one decode
step over every row, in which a row whose table is empty is dead: its state
is left as it is and it is routed to no expert.
"""

from __future__ import annotations

import dataclasses

import jax
import jax.numpy as jnp

from .. import nn
from ..framework.core import Tensor, run_op
from ..incubate.distributed.models.moe.held_moe import (HeldExpertsMoE,
                                                        total_stats)
from ..nn import initializer as I
from .gpt import hidden_at

__all__ = ["GraniteHybridConfig", "GraniteHybridForCausalLM",
           "granite_hybrid_tiny", "ssd_chunked"]

_PERIOD = ("mamba",) * 5 + ("attention",) + ("mamba",) * 4
_HI = jax.lax.Precision.HIGHEST


@dataclasses.dataclass
class GraniteHybridConfig:
    """Keys as in the family's public `config.json`, plus `held_experts`:
    (first, count) of the routed experts this model holds, None for all."""

    vocab_size: int = 100352
    hidden_size: int = 4096
    layer_types: tuple = _PERIOD
    num_attention_heads: int = 32
    num_key_value_heads: int = 8
    attention_multiplier: float = 0.0078125
    embedding_multiplier: float = 12.0
    residual_multiplier: float = 0.22
    logits_scaling: float = 16.0
    rms_norm_eps: float = 1e-5
    mamba_n_heads: int = 128
    mamba_d_head: int = 64
    mamba_d_state: int = 128
    mamba_d_conv: int = 4
    mamba_chunk_size: int = 256
    intermediate_size: int = 768          # one routed expert's width
    shared_intermediate_size: int = 1536
    num_local_experts: int = 72
    num_experts_per_tok: int = 10
    held_experts: tuple | None = None
    initializer_range: float = 0.02
    # the parameters' dtype. The model is cast a layer at a time as it is
    # built, so a bf16 model of 5 B parameters never exists in float32
    dtype: str = "float32"

    def __post_init__(self):
        self.layer_types = tuple(self.layer_types)
        bad = set(self.layer_types) - {"mamba", "attention"}
        if bad:
            raise ValueError(f"unknown layer types {sorted(bad)}")
        if self.held_experts is None:
            self.held_experts = (0, self.num_local_experts)
        self.held_experts = tuple(int(v) for v in self.held_experts)

    @property
    def num_layers(self):
        return len(self.layer_types)

    @property
    def kv_heads(self):
        return self.num_key_value_heads

    @property
    def head_dim(self):
        return self.hidden_size // self.num_attention_heads

    @property
    def mamba_d_inner(self):
        return self.mamba_n_heads * self.mamba_d_head

    @property
    def mamba_conv_channels(self):
        return self.mamba_d_inner + 2 * self.mamba_d_state


# --------------------------------------------------------------------------- #
# the Mamba-2 mixer's two forms, as pure functions of arrays
# --------------------------------------------------------------------------- #


def ssd_chunked(x, dt, a_neg, b, c, chunk, init=None):
    """The recurrence S_t = exp(dt_t A) S_{t-1} + dt_t x_t (outer) B_t,
    y_t = S_t C_t over a whole sequence, in chunks (the SSD form): inside a
    chunk as one masked matrix product, between chunks as a scan over the
    chunks' states.

    x [B, L, H, P], dt [B, L, H] (0 where a position is padding: it then
    neither decays nor feeds the state), a_neg [H] = A < 0, b, c [B, L, N],
    all f32. Returns (y [B, L, H, P], the state after position L - 1
    [B, H, P, N]). Any L: the tail is padded with dt = 0."""
    B, L, H, P = x.shape
    N = b.shape[-1]
    Q = min(chunk, L)
    pad = -L % Q
    if pad:
        x = jnp.pad(x, ((0, 0), (0, pad), (0, 0), (0, 0)))
        dt = jnp.pad(dt, ((0, 0), (0, pad), (0, 0)))
        b = jnp.pad(b, ((0, 0), (0, pad), (0, 0)))
        c = jnp.pad(c, ((0, 0), (0, pad), (0, 0)))
    n = (L + pad) // Q
    with jax.named_scope("ssd_scan"):
        bq, cq = b.reshape(B, n, Q, N), c.reshape(B, n, Q, N)
        dtq = dt.reshape(B, n, Q, H).transpose(0, 1, 3, 2)       # [B,n,H,Q]
        dtx = (dt[..., None] * x).reshape(B, n, Q, H, P).transpose(
            0, 1, 3, 2, 4)                                       # [B,n,H,Q,P]
        acum = jnp.cumsum(dtq * a_neg[None, None, :, None], axis=-1)
        # inside a chunk: y_i += sum_{j<=i} (C_i.B_j) exp(acum_i - acum_j) dtx_j
        seg = acum[..., :, None] - acum[..., None, :]            # [B,n,H,Q,Q]
        lower = jnp.tril(jnp.ones((Q, Q), bool))
        decay = jnp.exp(jnp.where(lower, seg, -jnp.inf))
        cb = jnp.einsum("bnik,bnjk->bnij", cq, bq, precision=_HI)
        y = jnp.einsum("bnhij,bnhjp->bnhip", cb[:, :, None] * decay, dtx,
                       precision=_HI)
        # each chunk's own contribution to the state at its end
        to_end = jnp.exp(acum[..., -1:] - acum)                  # [B,n,H,Q]
        states = jnp.einsum("bnjk,bnhjp->bnhpk", bq,
                            to_end[..., None] * dtx, precision=_HI)
        total = jnp.exp(acum[..., -1])                           # [B,n,H]

        def carry(s, inp):
            st, dec = inp
            return s * dec[..., None, None] + st, s

        s0 = (jnp.zeros((B, H, P, N), jnp.float32) if init is None
              else init.astype(jnp.float32))
        final, before = jax.lax.scan(
            carry, s0, (states.transpose(1, 0, 2, 3, 4),
                        total.transpose(1, 0, 2)))
        # the state a chunk starts from, decayed to each of its positions
        y = y + jnp.einsum("bnik,bnhpk,bnhi->bnhip", cq,
                           before.transpose(1, 0, 2, 3, 4), jnp.exp(acum),
                           precision=_HI)
    y = y.transpose(0, 1, 3, 2, 4).reshape(B, n * Q, H, P)[:, :L]
    return y, final


def _split_projection(zxbcdt, cfg):
    d, n, h = cfg.mamba_d_inner, cfg.mamba_d_state, cfg.mamba_n_heads
    z = zxbcdt[..., :d]
    xbc = zxbcdt[..., d:2 * d + 2 * n]
    dt = zxbcdt[..., 2 * d + 2 * n:2 * d + 2 * n + h]
    return z, xbc, dt


def _mamba_sequence(cfg, has_lens):
    """[z | xBC | dt] of a whole sequence -> (y * silu(z), conv state, SSM
    state), from the given initial states."""
    d, n, H, P = (cfg.mamba_d_inner, cfg.mamba_d_state, cfg.mamba_n_heads,
                  cfg.mamba_d_head)
    K = cfg.mamba_d_conv

    def fn(zxbcdt, conv0, ssm0, conv_w, conv_b, dt_bias, a_log, d_skip,
           *lens):
        B, L, _ = zxbcdt.shape
        out_dtype = zxbcdt.dtype
        z, xbc, dt = _split_projection(zxbcdt, cfg)
        f32 = jnp.float32
        with jax.named_scope("mamba_conv"):
            padded = jnp.concatenate([conv0.astype(f32), xbc.astype(f32)],
                                     axis=1)                  # [B, K-1+L, C]
            w = conv_w.astype(f32)
            act = jax.nn.silu(
                sum(padded[:, k:k + L] * w[:, k] for k in range(K))
                + conv_b.astype(f32))
        x = act[..., :d].reshape(B, L, H, P)
        dt = jax.nn.softplus(dt.astype(f32) + dt_bias.astype(f32))
        if has_lens:
            real = jnp.arange(L)[None, :] < lens[0][:, None]
            dt = jnp.where(real[..., None], dt, 0.0)
            last = lens[0].astype(jnp.int32)
        else:
            last = jnp.full((B,), L, jnp.int32)
        init = ssm0.reshape(B, n, H, P).transpose(0, 2, 3, 1)
        y, final = ssd_chunked(
            x, dt, -jnp.exp(a_log.astype(f32)), act[..., d:d + n],
            act[..., d + n:], cfg.mamba_chunk_size, init=init)
        y = y + d_skip.astype(f32)[None, None, :, None] * x
        gated = y.reshape(B, L, d) * jax.nn.silu(z.astype(f32))
        # the last K-1 pre-activation rows before position `last`
        conv = jax.vmap(lambda p, at: jax.lax.dynamic_slice(
            p, (at, 0), (K - 1, p.shape[1])))(padded, last)
        ssm = final.transpose(0, 3, 1, 2).reshape(B, n, H * P)
        return (gated.astype(out_dtype), conv.astype(conv0.dtype),
                ssm.astype(ssm0.dtype))

    return fn


def _mamba_step(cfg):
    """One token a row: the recurrence in f32 on the stored state, through
    the `ssm_decode` kernel. Dead rows keep their conv and SSM state."""
    d, n, P = cfg.mamba_d_inner, cfg.mamba_d_state, cfg.mamba_d_head
    K = cfg.mamba_d_conv

    def fn(zxbcdt, conv0, ssm0, live, conv_w, conv_b, dt_bias, a_log,
           d_skip):
        from ..ops.pallas.ssm_decode import ssm_decode

        out_dtype = zxbcdt.dtype
        z, xbc, dt = _split_projection(zxbcdt[:, 0], cfg)
        f32 = jnp.float32
        window = jnp.concatenate([conv0.astype(f32),
                                  xbc.astype(f32)[:, None]], axis=1)
        w = conv_w.astype(f32)
        act = jax.nn.silu(sum(window[:, k] * w[:, k] for k in range(K))
                          + conv_b.astype(f32))
        x = act[:, :d]                                         # [B, H * P]
        dt = jax.nn.softplus(dt.astype(f32) + dt_bias.astype(f32))
        decay = jnp.exp(dt * -jnp.exp(a_log.astype(f32)))      # [B, H]
        ssm, y = ssm_decode(ssm0, jnp.repeat(decay, P, axis=1),
                            jnp.repeat(dt, P, axis=1) * x,
                            act[:, d:d + n], act[:, d + n:], live)
        y = y + jnp.repeat(d_skip.astype(f32), P)[None] * x
        gated = (y * jax.nn.silu(z.astype(f32)))[:, None]
        conv = jnp.where(live[:, None, None],
                         window[:, 1:].astype(conv0.dtype), conv0)
        return gated.astype(out_dtype), conv, ssm

    return fn


# --------------------------------------------------------------------------- #
# layers
# --------------------------------------------------------------------------- #


def _residual(x, h, r):
    """x + r * h in the model's dtype: summed in f32, rounded once. (A
    Tensor times a Python float would promote a bf16 model to float32.)"""
    return run_op(
        "scaled_residual",
        lambda a, b: (a.astype(jnp.float32)
                      + r * b.astype(jnp.float32)).astype(a.dtype), [x, h])


def _attr(cfg):
    return nn.ParamAttr(initializer=I.Normal(0.0, cfg.initializer_range))


def _linear(cfg, n_in, n_out):
    return nn.Linear(n_in, n_out, weight_attr=_attr(cfg), bias_attr=False)


class GraniteMambaMixer(nn.Layer):
    def __init__(self, cfg: GraniteHybridConfig):
        super().__init__()
        self.cfg = cfg
        H, d, C = cfg.mamba_n_heads, cfg.mamba_d_inner, cfg.mamba_conv_channels
        self.in_proj = _linear(cfg, cfg.hidden_size, d + C + H)
        self.conv_weight = self.create_parameter(
            [C, cfg.mamba_d_conv],
            default_initializer=I.Uniform(-0.5, 0.5))
        self.conv_bias = self.create_parameter([C], is_bias=True)
        # the usual Mamba-2 initial values: dt spread over 0.001 .. 0.1 at a
        # zero projection, A over -1 .. -16, D = 1
        self.dt_bias = self.create_parameter(
            [H], default_initializer=I.Uniform(-6.9, -2.3))
        self.A_log = self.create_parameter(
            [H], default_initializer=I.Uniform(0.0, 2.77))
        self.D = self.create_parameter(
            [H], default_initializer=I.Constant(1.0))
        self.norm = nn.RMSNorm(d, epsilon=cfg.rms_norm_eps)
        self.out_proj = _linear(cfg, d, cfg.hidden_size)

    def _weights(self):
        return [self.conv_weight, self.conv_bias, self.dt_bias, self.A_log,
                self.D]

    def forward(self, u, cache=None, live=None, seq_lens=None):
        """cache (conv_state, ssm_state); `live` [rows] marks a decode step,
        `seq_lens` [B] a padded prefill. Returns (out, new_cache)."""
        cfg = self.cfg
        proj = self.in_proj(u)
        if not cache:
            B = proj.shape[0]
            dtype = proj._value.dtype
            cache = (Tensor(jnp.zeros((B, cfg.mamba_d_conv - 1,
                                       cfg.mamba_conv_channels), dtype)),
                     Tensor(jnp.zeros((B, cfg.mamba_d_state,
                                       cfg.mamba_d_inner), dtype)))
        with jax.named_scope("mamba"):
            if live is not None:
                gated, conv, ssm = run_op(
                    "mamba_step", _mamba_step(cfg),
                    [proj, cache[0], cache[1], live] + self._weights(),
                    n_outputs=3)
            else:
                lens = [] if seq_lens is None else [seq_lens]
                gated, conv, ssm = run_op(
                    "mamba_sequence",
                    _mamba_sequence(cfg, seq_lens is not None),
                    [proj, cache[0], cache[1]] + self._weights() + lens,
                    n_outputs=3)
        return self.out_proj(self.norm(gated)), (conv, ssm)


class GraniteAttention(nn.Layer):
    """Grouped-query attention, no positions, softmax scale
    `attention_multiplier`."""

    def __init__(self, cfg: GraniteHybridConfig):
        super().__init__()
        self.cfg = cfg
        h, D = cfg.hidden_size, cfg.head_dim
        self.q_proj = _linear(cfg, h, cfg.num_attention_heads * D)
        self.k_proj = _linear(cfg, h, cfg.kv_heads * D)
        self.v_proj = _linear(cfg, h, cfg.kv_heads * D)
        self.o_proj = _linear(cfg, cfg.num_attention_heads * D, h)

    def forward(self, u, cache=None, cache_offset=None, block_tables=None):
        cfg = self.cfg
        B, S = u.shape[0], u.shape[1]
        H, Hkv, D = cfg.num_attention_heads, cfg.kv_heads, cfg.head_dim
        scale = cfg.attention_multiplier
        q = self.q_proj(u).reshape([B, S, H, D])
        k = self.k_proj(u).reshape([B, S, Hkv, D])
        v = self.v_proj(u).reshape([B, S, Hkv, D])
        if block_tables is not None:
            def step(q, k, v, kc, vc, tables, lengths):
                from ..ops.pallas.decode_attention import (
                    paged_decode_attention, paged_kv_write)

                lengths = lengths.astype(jnp.int32)
                kc = paged_kv_write(kc, k[:, 0], tables, lengths)
                vc = paged_kv_write(vc, v[:, 0], tables, lengths)
                o = paged_decode_attention(q[:, 0], kc, vc, tables,
                                           lengths + 1, scale=scale)
                return o[:, None], kc, vc

            out, kc, vc = run_op(
                "granite_paged_attention", step,
                [q, k, v, cache[0], cache[1], block_tables, cache_offset],
                n_outputs=3)
            new_cache = (kc, vc)
        else:
            def whole(q, k, v):
                from ..nn.functional.flash_attention import (
                    _ref_attention, _use_pallas_kernel)

                if _use_pallas_kernel():
                    from ..ops.pallas.flash_attention import (
                        flash_attention_fwd)

                    return flash_attention_fwd(q, k, v, causal=True,
                                               scale=scale)
                return _ref_attention(q, k, v, causal=True, scale=scale)

            out = run_op("granite_attention", whole, [q, k, v])
            new_cache = (k, v)   # a prefill from position 0: the prompt's own
        return self.o_proj(out.reshape([B, S, H * D])), new_cache


class GraniteSharedExpert(nn.Layer):
    """The always-on gated MLP: w_out (silu(a) * b), [a | b] = w_in x."""

    def __init__(self, cfg: GraniteHybridConfig):
        super().__init__()
        self.width = cfg.shared_intermediate_size
        self.input_linear = _linear(cfg, cfg.hidden_size, 2 * self.width)
        self.output_linear = _linear(cfg, self.width, cfg.hidden_size)

    def forward(self, u):
        f = self.width

        def gate(ab):
            return (jax.nn.silu(ab[..., :f].astype(jnp.float32))
                    * ab[..., f:].astype(jnp.float32)).astype(ab.dtype)

        return self.output_linear(
            run_op("gated_silu", gate, [self.input_linear(u)]))


class GraniteHybridLayer(nn.Layer):
    def __init__(self, cfg: GraniteHybridConfig, kind: str):
        super().__init__()
        self.cfg, self.kind = cfg, kind
        self.input_layernorm = nn.RMSNorm(cfg.hidden_size,
                                          epsilon=cfg.rms_norm_eps)
        self.mixer = (GraniteMambaMixer(cfg) if kind == "mamba"
                      else GraniteAttention(cfg))
        self.post_attention_layernorm = nn.RMSNorm(cfg.hidden_size,
                                                   epsilon=cfg.rms_norm_eps)
        self.moe = HeldExpertsMoE(
            cfg.hidden_size, cfg.intermediate_size, cfg.num_local_experts,
            cfg.num_experts_per_tok, held=cfg.held_experts,
            weight_attr=_attr(cfg))
        self.shared_mlp = GraniteSharedExpert(cfg)

    def forward(self, x, cache, cache_offset, block_tables, live, seq_lens,
                token_live):
        r = self.cfg.residual_multiplier
        with jax.named_scope("ln"):
            u = self.input_layernorm(x)
        with jax.named_scope(self.kind):
            if self.kind == "mamba":
                h, new_cache = self.mixer(u, cache, live=live,
                                          seq_lens=seq_lens)
            else:
                h, new_cache = self.mixer(u, cache, cache_offset,
                                          block_tables)
            x = _residual(x, h, r)
        with jax.named_scope("ln"):
            u = self.post_attention_layernorm(x)
        with jax.named_scope("moe"):
            routed, stats = self.moe(u, live=token_live, with_stats=True)
            x = _residual(x, routed + self.shared_mlp(u), r)
        return x, new_cache, stats


class GraniteHybridForCausalLM(nn.Layer):
    def __init__(self, config: GraniteHybridConfig):
        super().__init__()
        self.config = config
        # the embedding is drawn `embedding_multiplier` times smaller than
        # the other weights, so that what enters the residual stream has
        # their scale. At the same scale the tied head would read the last
        # input token back out of it (E[t].E[t] against E[v].E[t]): every
        # seeded model would repeat its prompt's last token whatever its
        # layers compute, and no comparison of outputs could tell them apart
        embed_attr = nn.ParamAttr(initializer=I.Normal(
            0.0, config.initializer_range / config.embedding_multiplier))
        self.embed_tokens = nn.Embedding(
            config.vocab_size, config.hidden_size,
            weight_attr=embed_attr).astype(config.dtype)
        self.layers = nn.LayerList(
            [GraniteHybridLayer(config, kind).astype(config.dtype)
             for kind in config.layer_types])
        self.norm = nn.RMSNorm(
            config.hidden_size,
            epsilon=config.rms_norm_eps).astype(config.dtype)

    def cache_specs(self):
        """What each layer keeps per request, for the cache manager."""
        from ..inference.paged.block_pool import PagedKV, RowState

        cfg = self.config
        state = RowState(((cfg.mamba_d_conv - 1, cfg.mamba_conv_channels),
                          (cfg.mamba_d_state, cfg.mamba_d_inner)))
        pages = PagedKV(cfg.kv_heads, cfg.head_dim)
        return [state if kind == "mamba" else pages
                for kind in cfg.layer_types]

    @property
    def moe_groups(self):
        """Layers x held experts: what `expert_rows_sum` is a sum over."""
        return self.config.num_layers * self.config.held_experts[1]

    def forward(self, input_ids, position_ids=None, caches=None,
                cache_offset=None, block_tables=None, seq_lens=None,
                with_stats=False, logits_at=None):
        """logits [B, S, vocab] (with `logits_at` [B]: of that one position
        a row, [B, 1, vocab]); with `caches` also the new caches; with
        `with_stats` also the int32 row of `held_moe.STAT_NAMES` summed over
        the layers (`expert_rows_max`: the largest)."""
        cfg = self.config
        B, S = input_ids.shape[0], input_ids.shape[1]
        live = token_live = None
        if caches is not None and block_tables is not None:
            live = run_op("live_rows", lambda t: t[:, 0] >= 0, [block_tables])
            token_live = live
        elif seq_lens is not None:
            token_live = run_op(
                "real_tokens",
                lambda n: (jnp.arange(S)[None, :] < n[:, None]).reshape(-1),
                [seq_lens])
        with jax.named_scope("embed"):
            x = run_op("scaled_embedding",
                       lambda e: e * cfg.embedding_multiplier,
                       [self.embed_tokens(input_ids)])
        new_caches, stats = [], []
        for i, layer in enumerate(self.layers):
            x, new_cache, st = layer(
                x, caches[i] if caches else None, cache_offset,
                block_tables, live, seq_lens, token_live)
            new_caches.append(new_cache)
            stats.append(st)
        if logits_at is not None:
            x = hidden_at(x, logits_at)
        with jax.named_scope("ln"):
            x = self.norm(x)
        with jax.named_scope("lm_head"):
            logits = run_op(
                "lm_head_tied",
                lambda a, w: jnp.matmul(a, w.T) / cfg.logits_scaling,
                [x, self.embed_tokens.weight])
        out = (logits,)
        if caches is not None:
            out += (new_caches,)
        if with_stats:
            out += (run_op("moe_stats", total_stats, stats),)
        return out[0] if len(out) == 1 else out


def granite_hybrid_tiny(**kw):
    """A CPU-test size with every mechanism: two periods of `m m a m`."""
    base = dict(vocab_size=128, hidden_size=64,
                layer_types=("mamba", "mamba", "attention", "mamba") * 2,
                num_attention_heads=4, num_key_value_heads=2,
                mamba_n_heads=8, mamba_d_head=16, mamba_d_state=16,
                mamba_chunk_size=8, intermediate_size=32,
                shared_intermediate_size=48, num_local_experts=8,
                num_experts_per_tok=4)
    base.update(kw)
    return GraniteHybridConfig(**base)
