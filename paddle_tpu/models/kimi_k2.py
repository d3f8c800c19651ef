"""A decoder with latent attention and sigmoid-routed experts beside a shared
one (the `kimi_k2` family: Moonshot Kimi K2, the `DeepseekV3ForCausalLM`
architecture).

What a token leaves in a layer's cache is ONE normed latent `kv_lora_rank`
wide and ONE rotated key `qk_rope_head_dim` wide that all heads share, not
keys and values per head. Pre-norm, two RMSNorms a layer:

    x  = norm_in(h)
    cq = RMSNorm(Wqa x);  q = Wqb cq, heads of [q_nope ; q_pe]
    [ckv ; k_pe] = Wkva x;  c = RMSNorm(ckv)
    RoPE (YaRN-scaled frequencies, adjacent pairs) on q_pe and k_pe
    expanded:  [k_nope_h ; v_h] = Wkvb^h c;  k_h = [k_nope_h ; k_pe]
               o_h = softmax(q_h . k_h * scale) v_h
    absorbed:  Wkvb^h = [Wk^h ; Wv^h];  ql_h = Wk^h^T q_nope_h
               score_j = (ql_h . c_j + q_pe_h . k_pe_j) * scale
               o_h = Wv^h sum_j p_j c_j
    h  = h + Wo concat_h(o_h)
    m  = norm_post(h)
    y  = Wd (silu(Wg m) * Wu m)                     the leading dense layers
    y  = shared(m) + sum over the picked experts of w_e expert_e(m)   after

The two forms are the same function of the same parameters. A call with
`caches` and no `block_tables` is a prefill from position 0 in the EXPANDED
form (`flash_fwd` with 192-wide q and k and 128-wide v) and returns each
layer's `[c ; k_pe ; 0]` rows as the pool stores them
(`inference/paged/block_pool.LatentKV`); a decode step is the ABSORBED form
over the pool's latent pages (`decode_latent`), `Wk^h` and `Wv^h` slices of
the one `kv_b_proj` parameter taken inside the program. `h = E[ids]` going in,
RMSNorm and an untied head coming out, no bias anywhere. The routed experts
are `HeldExpertsMoE` with the "sigmoid" gate: this chip's share of them. The
plain float32 reference with the equations written out is
`benchmark/reference/kimi_k2.py`.
"""

from __future__ import annotations

import dataclasses
import math

import jax
import jax.numpy as jnp
import numpy as np

from .. import nn
from ..framework.core import Tensor, run_op
from ..incubate.distributed.models.moe.held_moe import (HeldExpertsMoE,
                                                        chunks_for,
                                                        total_stats)
from ..nn import initializer as I
from .afmoe import (AfmoeMLP, _attr, _cast, _head_norm, _linear,
                    _masked_attention, _norm)
from .gpt import hidden_at

__all__ = ["KimiK2Config", "KimiK2ForCausalLM", "kimi_k2_tiny",
           "yarn_inv_freq", "softmax_scale"]

_YARN = {"type": "yarn", "factor": 32, "beta_fast": 1, "beta_slow": 1,
         "mscale": 1, "mscale_all_dim": 1,
         "original_max_position_embeddings": 4096}


@dataclasses.dataclass
class KimiK2Config:
    """Keys as in the family's public `config.json`, plus `held_experts`:
    (first, count) of the routed experts this model holds, None for all."""

    vocab_size: int = 163840
    hidden_size: int = 7168
    intermediate_size: int = 18432        # a leading dense layer's MLP
    moe_intermediate_size: int = 2048     # one routed or shared expert
    num_hidden_layers: int = 61
    first_k_dense_replace: int = 1
    num_attention_heads: int = 64
    q_lora_rank: int = 1536
    kv_lora_rank: int = 512
    qk_nope_head_dim: int = 128
    qk_rope_head_dim: int = 64
    v_head_dim: int = 128
    rope_theta: float = 50000.0
    rope_scaling: dict | None = dataclasses.field(
        default_factory=lambda: dict(_YARN))
    rms_norm_eps: float = 1e-6
    n_routed_experts: int = 384
    num_experts_per_tok: int = 8
    n_shared_experts: int = 1
    routed_scaling_factor: float = 2.827
    held_experts: tuple | None = None
    initializer_range: float = 0.02
    # what a seeded model draws its norm weights round 1 and its experts'
    # bias round 0 with (a loaded model overwrites both)
    norm_weight_std: float = 0.1
    expert_bias_std: float = 0.02
    # the parameters' dtype; the model is cast a layer at a time as it is
    # built and the float32 form freed (`afmoe._cast`)
    dtype: str = "float32"

    def __post_init__(self):
        if self.rope_scaling is not None and (
                self.rope_scaling.get("type") != "yarn"):
            raise ValueError("rope_scaling: only YaRN (or none) is computed")
        if self.held_experts is None:
            self.held_experts = (0, self.n_routed_experts)
        self.held_experts = tuple(int(v) for v in self.held_experts)

    @property
    def num_layers(self):
        return self.num_hidden_layers


def _mscale(factor, m):
    return 0.1 * m * math.log(factor) + 1.0 if factor > 1 else 1.0


def yarn_inv_freq(cfg: KimiK2Config) -> np.ndarray:
    """The rotation's frequencies [qk_rope_head_dim / 2], float32: pair i's
    `theta^(-2i/d)`, which YaRN leaves alone below pair `low` (wavelengths
    that turn at least `beta_fast` times in the original context), divides
    by `factor` from pair `high` on, and blends linearly between."""
    d = cfg.qk_rope_head_dim
    freq = cfg.rope_theta ** (-np.arange(0, d, 2, dtype=np.float64) / d)
    ys = cfg.rope_scaling
    if ys is None:
        return freq.astype(np.float32)

    def turns_at(rotations):
        return (d * math.log(ys["original_max_position_embeddings"]
                             / (rotations * 2 * math.pi))
                / (2 * math.log(cfg.rope_theta)))

    low = max(math.floor(turns_at(ys["beta_fast"])), 0)
    high = min(math.ceil(turns_at(ys["beta_slow"])), d - 1)
    if low == high:
        high += 0.001
    ramp = np.clip((np.arange(d // 2) - low) / (high - low), 0.0, 1.0)
    return (freq * (1 - ramp) + freq / ys["factor"] * ramp).astype(np.float32)


def _rope_amplitude(cfg: KimiK2Config) -> float:
    """What YaRN multiplies cos and sin by: mscale over mscale_all_dim."""
    ys = cfg.rope_scaling
    if ys is None:
        return 1.0
    return (_mscale(ys["factor"], ys["mscale"])
            / _mscale(ys["factor"], ys["mscale_all_dim"]))


def softmax_scale(cfg: KimiK2Config) -> float:
    """1 / sqrt(the q and k head width), times YaRN's mscale squared."""
    scale = (cfg.qk_nope_head_dim + cfg.qk_rope_head_dim) ** -0.5
    ys = cfg.rope_scaling
    if ys is not None and ys.get("mscale_all_dim"):
        scale *= _mscale(ys["factor"], ys["mscale_all_dim"]) ** 2
    return scale


def _rope(x, pos, inv_freq, amplitude):
    """RoPE on the adjacent pairs (2i, 2i+1) of the last axis, as the
    family's published code does it: the pairs are first parted into halves
    (evens, then odds), then turned rotate-half. q_pe and k_pe are parted
    alike, so their products are those of the interleaved form. x
    [B, S, ..., d] f32, pos [B, S]."""
    ang = pos.astype(jnp.float32)[..., None] * inv_freq        # [B, S, d/2]
    ang = ang.reshape(ang.shape[:2] + (1,) * (x.ndim - 3) + ang.shape[2:])
    cos, sin = jnp.cos(ang) * amplitude, jnp.sin(ang) * amplitude
    even, odd = x[..., 0::2], x[..., 1::2]
    return jnp.concatenate([even * cos - odd * sin, odd * cos + even * sin],
                           axis=-1)


def cache_spec(cfg: KimiK2Config):
    """What every layer keeps per request: a page of latents."""
    from ..inference.paged.block_pool import LatentKV

    return LatentKV(cfg.kv_lora_rank, cfg.qk_rope_head_dim)


class KimiK2Attention(nn.Layer):
    """Latent attention: low-rank q with a norm in the middle, one normed
    latent and one rotated key a token; expanded heads in a prefill, absorbed
    projections in a decode step."""

    def __init__(self, cfg: KimiK2Config):
        super().__init__()
        self.cfg = cfg
        h, H = cfg.hidden_size, cfg.num_attention_heads
        N, R, V = cfg.qk_nope_head_dim, cfg.qk_rope_head_dim, cfg.v_head_dim
        self.q_a_proj = _linear(cfg, h, cfg.q_lora_rank)
        self.q_a_layernorm = _norm(cfg, cfg.q_lora_rank)
        self.q_b_proj = _linear(cfg, cfg.q_lora_rank, H * (N + R))
        self.kv_a_proj_with_mqa = _linear(cfg, h, cfg.kv_lora_rank + R)
        self.kv_a_layernorm = _norm(cfg, cfg.kv_lora_rank)
        # per head [k_nope (N) ; v (V)] of the latent, as published
        self.kv_b_proj = _linear(cfg, cfg.kv_lora_rank, H * (N + V))
        self.o_proj = _linear(cfg, H * V, h)
        self._inv_freq = yarn_inv_freq(cfg)
        self._amplitude = _rope_amplitude(cfg)
        self._scale = softmax_scale(cfg)
        self._stored_dim = cache_spec(cfg).stored_dim

    def _latent_and_key(self, ckv, cw, pos):
        """(c, k_pe): the normed latent and the rotated shared key, in the
        projections' dtype. ckv [B, S, L + R]."""
        L = self.cfg.kv_lora_rank
        c = _head_norm(ckv[..., :L], cw,
                       self.cfg.rms_norm_eps).astype(ckv.dtype)
        k_pe = _rope(ckv[..., L:].astype(jnp.float32), pos, self._inv_freq,
                     self._amplitude).astype(ckv.dtype)
        return c, k_pe

    def _stored(self, c, k_pe):
        """A token's row as the pool stores it: [c ; k_pe ; 0]."""
        pad = self._stored_dim - c.shape[-1] - k_pe.shape[-1]
        return jnp.concatenate(
            [c, k_pe, jnp.zeros(c.shape[:-1] + (pad,), c.dtype)], axis=-1)

    def forward(self, u, pos, cache=None, cache_offset=None, table=None):
        """Prefill (`table` None): (out, (rows,)) with `rows` [B, S, W] what
        each token leaves in the cache. Decode: `cache` the pool's (pages,)
        of this layer, `table` [B, P], `cache_offset` [B] tokens cached."""
        cfg = self.cfg
        B, S = u.shape[0], u.shape[1]
        H, L = cfg.num_attention_heads, cfg.kv_lora_rank
        N, R, V = cfg.qk_nope_head_dim, cfg.qk_rope_head_dim, cfg.v_head_dim
        scale, inv_freq, amp = self._scale, self._inv_freq, self._amplitude
        q = self.q_b_proj(self.q_a_layernorm(self.q_a_proj(u))).reshape(
            [B, S, H, N + R])
        ckv = self.kv_a_proj_with_mqa(u)
        weights = [self.kv_b_proj.weight, self.kv_a_layernorm.weight]

        def turned(q, pos):
            return _rope(q[..., N:].astype(jnp.float32), pos, inv_freq,
                         amp).astype(q.dtype)

        if table is not None:
            def step(q, ckv, kvb, cw, pos, pages, table, lengths):
                from ..ops.pallas.decode_attention import (
                    latent_decode_attention, latent_kv_write)

                c, k_pe = self._latent_and_key(ckv, cw, pos)
                lengths = lengths.astype(jnp.int32)
                pages = latent_kv_write(pages, self._stored(c, k_pe)[:, 0],
                                        table, lengths)
                kvb = kvb.reshape(L, H, N + V)
                # the key projection absorbed into the query, the value
                # projection applied to the attended latents
                ql = jnp.einsum("bhn,lhn->bhl", q[:, 0, :, :N], kvb[..., :N],
                                preferred_element_type=jnp.float32)
                qf = self._stored(ql.astype(q.dtype), turned(q, pos)[:, 0])
                ol = latent_decode_attention(qf, pages, table, lengths + 1, L,
                                             scale)
                o = jnp.einsum("bhl,lhv->bhv", ol, kvb[..., N:],
                               preferred_element_type=jnp.float32)
                return o.astype(q.dtype)[:, None], pages

            out, pages = run_op(
                "kimi_latent_attention", step,
                [q, ckv] + weights + [pos, cache[0], table, cache_offset],
                n_outputs=2)
            new_cache = (pages,)
        else:
            def whole(q, ckv, kvb, cw, pos):
                from ..nn.functional.flash_attention import _use_pallas_kernel

                c, k_pe = self._latent_and_key(ckv, cw, pos)
                kv = jnp.matmul(c, kvb).reshape(B, S, H, N + V)
                k = jnp.concatenate(
                    [kv[..., :N],
                     jnp.broadcast_to(k_pe[:, :, None], (B, S, H, R))], -1)
                qf = jnp.concatenate([q[..., :N], turned(q, pos)], -1)
                if _use_pallas_kernel():
                    from ..ops.pallas.flash_attention import (
                        flash_attention_fwd)

                    o = flash_attention_fwd(qf, k, kv[..., N:], causal=True,
                                            scale=scale)
                else:
                    o = _masked_attention(qf, k, kv[..., N:], None, scale)
                return o, self._stored(c, k_pe)

            out, rows = run_op("kimi_expanded_attention", whole,
                               [q, ckv] + weights + [pos], n_outputs=2)
            new_cache = (rows,)   # a prefill from position 0: the prompt's own
        return self.o_proj(out.reshape([B, S, H * V])), new_cache


class KimiK2Layer(nn.Layer):
    def __init__(self, cfg: KimiK2Config, dense: bool):
        super().__init__()
        self.cfg, self.dense = cfg, dense
        self.input_layernorm = _norm(cfg)
        self.self_attn = KimiK2Attention(cfg)
        self.post_attention_layernorm = _norm(cfg)
        if dense:
            self.mlp = AfmoeMLP(cfg, cfg.intermediate_size)
        else:
            self.moe = HeldExpertsMoE(
                cfg.hidden_size, cfg.moe_intermediate_size,
                cfg.n_routed_experts, cfg.num_experts_per_tok,
                held=cfg.held_experts, weight_attr=_attr(cfg), gate="sigmoid",
                route_scale=cfg.routed_scaling_factor)
            # a seeded model's bias is small and not zero, so that it
            # decides some picks
            I.Normal(0.0, cfg.expert_bias_std)(self.moe.expert_bias)
            self.shared_experts = AfmoeMLP(
                cfg, cfg.moe_intermediate_size * cfg.n_shared_experts)

    def forward(self, x, pos, cache, cache_offset, table, token_live):
        with jax.named_scope("ln"):
            u = self.input_layernorm(x)
        with jax.named_scope("latent_attention"):
            h, new_cache = self.self_attn(u, pos, cache, cache_offset, table)
        with jax.named_scope("ln"):
            x = x + h
            u = self.post_attention_layernorm(x)
        stats = None
        if self.dense:
            with jax.named_scope("mlp"):
                y = self.mlp(u)
        else:
            with jax.named_scope("moe"):
                routed, stats = self.moe(u, live=token_live, with_stats=True)
                y = routed + self.shared_experts(u)
        return x + y, new_cache, stats


class KimiK2ForCausalLM(nn.Layer):
    def __init__(self, config: KimiK2Config):
        super().__init__()
        self.config = config
        self.embed_tokens = _cast(nn.Embedding(
            config.vocab_size, config.hidden_size,
            weight_attr=_attr(config)), config.dtype)
        # the head before the layers: its float32 form (4.7 GB at the
        # published vocabulary) is the largest array of the build, and is
        # made while the device holds least
        self.lm_head = _cast(_linear(config, config.hidden_size,
                                     config.vocab_size), config.dtype)
        self.norm = _cast(_norm(config), config.dtype)
        self.layers = nn.LayerList(
            [_cast(KimiK2Layer(config, i < config.first_k_dense_replace),
                   config.dtype)
             for i in range(config.num_hidden_layers)])

    def cache_specs(self):
        """What each layer keeps per request, for the cache manager."""
        return [cache_spec(self.config)] * self.config.num_hidden_layers

    @property
    def moe_groups(self):
        """Expert layers x held experts: what `expert_rows_sum` sums over."""
        cfg = self.config
        return (max(cfg.num_hidden_layers - cfg.first_k_dense_replace, 0)
                * cfg.held_experts[1])

    def prefill_span_attrs(self, bucket):
        """For the engine's `prefill` span: passes of each expert layer."""
        return {"chunks": chunks_for(bucket)}

    def forward(self, input_ids, position_ids=None, caches=None,
                cache_offset=None, block_tables=None, seq_lens=None,
                with_stats=False, logits_at=None):
        """logits [B, S, vocab] (with `logits_at` [B]: of that one position
        a row, [B, 1, vocab]); with `caches` also the new caches, one a
        layer: `(rows,)` from a prefill, `(pages,)` from a decode step; with
        `with_stats` also the int32 row of `held_moe.STAT_NAMES` summed over
        the expert layers (`expert_rows_max`: the largest)."""
        B, S = input_ids.shape[0], input_ids.shape[1]
        decode = caches is not None and block_tables is not None
        if position_ids is None:
            position_ids = Tensor(jnp.broadcast_to(jnp.arange(S)[None],
                                                   (B, S)))
        token_live = None
        if decode:
            token_live = run_op("live_rows", lambda t: t[:, 0] >= 0,
                                [block_tables])
        elif seq_lens is not None:
            # the bucket's padding is routed to no expert
            token_live = run_op(
                "real_tokens",
                lambda n: (jnp.arange(S)[None, :] < n[:, None]).reshape(-1),
                [seq_lens])
        with jax.named_scope("embed"):
            x = self.embed_tokens(input_ids)
        new_caches, stats = [], []
        for i, layer in enumerate(self.layers):
            x, new_cache, st = layer(
                x, position_ids, caches[i] if decode else None, cache_offset,
                block_tables if decode else None, token_live)
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
            out += (new_caches,)
        if with_stats:
            out += (run_op("moe_stats", total_stats, stats),)
        return out[0] if len(out) == 1 else out


def kimi_k2_tiny(**kw):
    """A CPU-test size with every mechanism: one leading dense layer and
    two expert layers, YaRN from an original context of 16 positions."""
    base = dict(vocab_size=256, hidden_size=64, intermediate_size=96,
                moe_intermediate_size=32, num_hidden_layers=3,
                first_k_dense_replace=1, num_attention_heads=4,
                q_lora_rank=48, kv_lora_rank=32, qk_nope_head_dim=16,
                qk_rope_head_dim=8, v_head_dim=16, n_routed_experts=8,
                num_experts_per_tok=4,
                rope_scaling=dict(_YARN, factor=4,
                                  original_max_position_embeddings=16))
    base.update(kw)
    return KimiK2Config(**base)
