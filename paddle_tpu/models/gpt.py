"""GPT-style decoder LM — the flagship training model.

Reference analogs: fleet TP building blocks
(python/paddle/distributed/fleet/layers/mpu/mp_layers.py:49,336,543,744),
fused transformer kernels (paddle/phi/kernels/fusion/gpu/
fused_multi_transformer_kernel.cu, fused_rope_kernel.cu,
fused_layernorm_kernel.cu), flash attention
(python/paddle/nn/functional/flash_attention.py:358).

TPU-first design decisions:
- One config drives both GPT-3 (pre-LN LayerNorm, GELU MLP, learned positions)
  and LLaMA (RMSNorm, SwiGLU, RoPE, GQA) shapes.
- All parallelism is expressed as sharding annotations: TP via
  Column/RowParallelLinear dist_attr specs, SP/SEP via activation
  constraints. The same model object runs single-chip or under a hybrid mesh
  unchanged — GSPMD inserts the collectives the reference codes by hand.
- Attention goes through F.scaled_dot_product_attention → Pallas flash
  attention on TPU; everything else is left to XLA fusion (the epilogues the
  reference hand-fuses are single jnp expressions here).
- Static shapes throughout; the decode path keeps a static-capacity KV cache
  updated with dynamic_update_slice (reference analog: paged/cached decode
  attention masked_multihead_attention_kernel.cu) — no dynamic shapes under jit.
- The cache contract is the serving engines' (`tok, pos, caches, off,
  block_tables=`). With `caches` EMPTY and no `block_tables` the call is a
  prefill from position 0, which reads no cache: causal attention over the
  prompt's own K and V (the flash kernel on TPU), each layer returning them.
  With dense caches `[B, S_max, Hkv, D]` it writes at `off` and attends under
  an explicit mask (`models/generation.py`, the dense engine's decode step);
  with pool pages and `block_tables` it is one paged decode step.
"""

from __future__ import annotations

import dataclasses
import functools
import math

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from ..framework.core import Tensor, run_op
from .. import nn
from ..nn import functional as F
from ..nn.functional.flash_attention import _ref_attention, _use_pallas_kernel
from ..nn import initializer as I
from ..distributed.fleet.layers.mpu.mp_layers import (
    ColumnParallelLinear,
    RowParallelLinear,
    VocabParallelEmbedding,
    ParallelCrossEntropy,
    mark_as_sequence_parallel,
    _constrain,
)
from ..incubate.nn.functional import fused_rotary_position_embedding, swiglu

__all__ = [
    "GPTConfig",
    "GPTModel",
    "GPTForCausalLM",
    "GPTPretrainingCriterion",
    "gpt3_tiny",
    "gpt3_125m",
    "gpt3_350m",
    "gpt3_1p3b",
    "gpt3_6p7b",
    "gpt3_13b",
]


@dataclasses.dataclass
class GPTConfig:
    vocab_size: int = 50304
    hidden_size: int = 768
    num_layers: int = 12
    num_heads: int = 12
    num_kv_heads: int | None = None  # GQA; None = MHA
    intermediate_size: int | None = None  # None → 4h (gelu) or 8h/3 rounded (swiglu)
    max_position_embeddings: int = 2048
    norm_type: str = "layernorm"  # "layernorm" | "rmsnorm"
    activation: str = "gelu"  # "gelu" | "swiglu"
    use_rope: bool = False  # False → learned position embeddings
    rope_theta: float = 10000.0
    use_neox_rotary_style: bool = True
    tie_word_embeddings: bool = True
    hidden_dropout_prob: float = 0.0
    attention_dropout_prob: float = 0.0
    initializer_range: float = 0.02
    layer_norm_epsilon: float = 1e-5
    sequence_parallel: bool = False
    use_recompute: bool = False
    # "flash" = causal Pallas flash attention; "flashmask" = the Pallas
    # flashmask kernel fed per-key startend row indices (reference:
    # flashmask_attention, flash_attention.py:1299) — causal by default but
    # accepts document masks via forward(attn_startend_row_indices=...)
    attn_variant: str = "flash"
    # context parallelism: shard the sequence over the `sep` mesh axis and use
    # ring attention (paddle_tpu.parallel.ring). TPU-native upgrade over the
    # reference's bare SEP plumbing (segment_parallel.py:26); implies
    # attention_dropout_prob == 0.
    context_parallel: bool = False

    @property
    def kv_heads(self):
        return self.num_kv_heads or self.num_heads

    @property
    def head_dim(self):
        return self.hidden_size // self.num_heads

    @property
    def ffn_size(self):
        if self.intermediate_size is not None:
            return self.intermediate_size
        if self.activation == "swiglu":
            # LLaMA sizing: 2/3 * 4h rounded up to a multiple of 256
            return int(math.ceil(8 * self.hidden_size / 3 / 256) * 256)
        return 4 * self.hidden_size

    def num_params(self, include_embeddings=True):
        h, L, V = self.hidden_size, self.num_layers, self.vocab_size
        d = self.head_dim
        attn = h * (self.num_heads * d) + 2 * h * (self.kv_heads * d) + (self.num_heads * d) * h
        if self.activation == "swiglu":
            mlp = 3 * h * self.ffn_size
        else:
            mlp = 2 * h * self.ffn_size
        per_layer = attn + mlp + 2 * h
        total = L * per_layer + h
        if include_embeddings:
            total += V * h
            if not self.use_rope:
                total += self.max_position_embeddings * h
            if not self.tie_word_embeddings:
                total += V * h
        return total


def _make_norm(config: GPTConfig):
    if config.norm_type == "rmsnorm":
        return nn.RMSNorm(config.hidden_size, epsilon=config.layer_norm_epsilon)
    return nn.LayerNorm(config.hidden_size, epsilon=config.layer_norm_epsilon)


def _init_attr(config: GPTConfig):
    return nn.ParamAttr(initializer=I.Normal(mean=0.0, std=config.initializer_range))


class GPTAttention(nn.Layer):
    """Multi-head / grouped-query causal self-attention, TP-sharded on heads.

    Reference: MultiHeadAttention (python/paddle/nn/layer/transformer.py) +
    the fused path (fused_attention_kernel.cu / flash_attn_kernel.cu); TP
    sharding as in mp_layers.py ColumnParallelLinear(gather_output=False) →
    RowParallelLinear(input_is_parallel=True).
    """

    def __init__(self, config: GPTConfig):
        super().__init__()
        self.config = config
        h, d = config.hidden_size, config.head_dim
        attr = _init_attr(config)
        bias = config.norm_type == "layernorm"  # GPT has biases, LLaMA doesn't
        self.q_proj = ColumnParallelLinear(h, config.num_heads * d, weight_attr=attr,
                                           has_bias=bias, gather_output=False)
        self.k_proj = ColumnParallelLinear(h, config.kv_heads * d, weight_attr=attr,
                                           has_bias=bias, gather_output=False)
        self.v_proj = ColumnParallelLinear(h, config.kv_heads * d, weight_attr=attr,
                                           has_bias=bias, gather_output=False)
        self.out_proj = RowParallelLinear(config.num_heads * d, h, weight_attr=attr,
                                          has_bias=bias, input_is_parallel=True)

    def forward(self, x, position_ids=None, cache=None, cache_offset=None,
                startend_row_indices=None, block_tables=None):
        cfg = self.config
        B, S = x.shape[0], x.shape[1]
        q = self.q_proj(x).reshape([B, S, cfg.num_heads, cfg.head_dim])
        k = self.k_proj(x).reshape([B, S, cfg.kv_heads, cfg.head_dim])
        v = self.v_proj(x).reshape([B, S, cfg.kv_heads, cfg.head_dim])
        # keep heads sharded over mp between the projections; batch dim
        # UNCONSTRAINED so its (dp, sharding) sharding survives (None would
        # force a replicate -> involuntary full remat in the backward)
        _U = P.UNCONSTRAINED
        q = _constrain(q, P(_U, None, "mp", None))
        k = _constrain(k, P(_U, None, "mp", None))
        v = _constrain(v, P(_U, None, "mp", None))
        if cfg.use_rope:
            q, k, _ = fused_rotary_position_embedding(
                q, k, position_ids=position_ids,
                use_neox_rotary_style=cfg.use_neox_rotary_style,
                rotary_emb_base=cfg.rope_theta,
            )
        new_cache = None
        if cache is not None and block_tables is not None:
            # paged KV cache: cache.k/v are [n_pages, Hkv, page_size, D];
            # block_tables [B, P] maps each row's logical pages to physical
            # ones. Single-token decode only — the step's K/V rows scatter
            # into each row's next slot, then the Pallas paged kernel streams
            # exactly the live pages (scalar-prefetched block table resolves
            # the physical index in the BlockSpec index_map; no gathered
            # cache copy is ever materialized). A 4-tuple cache is the
            # quantized layout (k, v, k_scale, v_scale): int8 payloads with
            # per-(page, head) f32 scales — the append requantizes under a
            # running abs-max and the kernel dequantizes in VMEM.
            if len(cache) == 4:
                k_all, k_sc = run_op(
                    "paged_kv_update_q8", _paged_update_q8,
                    [cache[0], cache[2], k, block_tables, cache_offset])
                v_all, v_sc = run_op(
                    "paged_kv_update_q8", _paged_update_q8,
                    [cache[1], cache[3], v, block_tables, cache_offset])
                new_cache = (k_all, v_all, k_sc, v_sc)
                out = run_op(
                    "paged_decode_attention_q8", _paged_attend_q8,
                    [q, k_all, v_all, k_sc, v_sc, block_tables,
                     cache_offset])
            else:
                k_all = run_op("paged_kv_update", _paged_update,
                               [cache[0], k, block_tables, cache_offset])
                v_all = run_op("paged_kv_update", _paged_update,
                               [cache[1], v, block_tables, cache_offset])
                new_cache = (k_all, v_all)
                out = run_op("paged_decode_attention", _paged_attend,
                             [q, k_all, v_all, block_tables, cache_offset])
        elif cache:
            # static-capacity KV cache: cache.k/v are [B, S_max, Hkv, D]
            k_all = run_op("kv_cache_update", _dyn_update, [cache[0], k, cache_offset])
            v_all = run_op("kv_cache_update", _dyn_update, [cache[1], v, cache_offset])
            new_cache = (k_all, v_all)
            mask = _decode_mask(int(k_all.shape[1]), cache_offset, S)
            out = F.scaled_dot_product_attention(
                q, k_all, v_all, attn_mask=mask, is_causal=False,
                dropout_p=cfg.attention_dropout_prob, training=self.training,
            )
        elif cache is not None:
            # an empty cache: a prefill from position 0 attends over the
            # prompt's own K and V and returns them (K after the rotation,
            # `kv_heads` of them)
            out = run_op(
                "prompt_attention",
                functools.partial(_prompt_attention,
                                  kernel=_use_pallas_kernel()), [q, k, v])
            new_cache = (k, v)
        elif cfg.context_parallel:
            assert cfg.attention_dropout_prob == 0.0, (
                "context_parallel ring attention does not support attention "
                "dropout; set attention_dropout_prob=0")
            q = _constrain(q, P(_U, "sep", "mp", None))
            k = _constrain(k, P(_U, "sep", "mp", None))
            v = _constrain(v, P(_U, "sep", "mp", None))
            out = F.ring_flash_attention(q, k, v, causal=True)
        elif cfg.attn_variant == "flashmask":
            assert cfg.attention_dropout_prob == 0.0, (
                "attn_variant='flashmask' does not support attention dropout "
                "(the flashmask kernel has no dropout path); set "
                "attention_dropout_prob=0")
            idx = startend_row_indices
            if idx is None:
                # trivial mask (= plain causal) so the flashmask kernel path
                # is exercised even without document boundaries
                idx = run_op(
                    "flashmask_causal_idx",
                    lambda qq: jnp.full((qq.shape[0], 1, qq.shape[1], 1), S,
                                        jnp.int32),
                    [q])
            out = F.flashmask_attention(
                q, k, v, startend_row_indices=idx, causal=True)
        else:
            out = F.scaled_dot_product_attention(
                q, k, v, is_causal=True,
                dropout_p=cfg.attention_dropout_prob, training=self.training,
            )
        out = out.reshape([B, S, cfg.num_heads * cfg.head_dim])
        out = self.out_proj(out)
        if cache is not None:
            return out, new_cache
        return out


@functools.partial(jax.jit, static_argnames="kernel")
def _prompt_attention(q, k, v, kernel):
    """Causal attention of a prompt over its own K and V [B, S, H, D]: the
    `flash_fwd` kernel the training step runs where the Pallas kernels are
    available (`kernel`), the causal composite elsewhere. Jitted so that the
    layers of a prefill program share ONE trace and ONE lowering of the
    kernel: a trace a layer cost the chip's host a quarter of a second a
    layer and bucket, 12 s of a 24-layer model's set-up."""
    if kernel:  # graftlint: disable=GL001 a static argument: a Python bool
        from ..ops.pallas.flash_attention import flash_attention_fwd

        return flash_attention_fwd(q, k, v, causal=True)
    return _ref_attention(q, k, v, causal=True)


def _dyn_update(buf, new, off):
    """Write `new` [B,S,H,D] into static cache `buf` at sequence offset
    `off`. A VECTOR off [B] writes per-row offsets (continuous-batching
    decode, S==1: each slot appends at its own length)."""
    off = jnp.asarray(off).astype(jnp.int32)
    if off.ndim == 0:
        return jax.lax.dynamic_update_slice(
            buf, new.astype(buf.dtype), (0, off.reshape(()), 0, 0))
    B = buf.shape[0]
    return buf.at[jnp.arange(B), off].set(new[:, 0].astype(buf.dtype))


def _paged_update(buf, new, tables, lengths):
    """Write this step's `new` [B, 1, H, D] K/V rows into the paged cache
    `buf` [n_pages, Hkv, ps, D] at each row's next slot (decode is S==1)."""
    from ..ops.pallas.decode_attention import paged_kv_write

    return paged_kv_write(buf, new[:, 0], tables,
                          jnp.asarray(lengths).astype(jnp.int32))


def _paged_attend(q, kc, vc, tables, lengths):
    """q [B, 1, H, D] (one decode step) against the paged cache; `lengths`
    counts tokens present BEFORE this step, and the step's K/V were just
    written by _paged_update, so the kernel sees lengths + 1 valid tokens."""
    from ..ops.pallas.decode_attention import paged_decode_attention

    B, S, H, D = q.shape
    o = paged_decode_attention(
        q.reshape(B, H, D), kc, vc, tables,
        jnp.asarray(lengths).astype(jnp.int32) + 1)
    return o.reshape(B, S, H, D)


def _paged_update_q8(buf, scales, new, tables, lengths):
    """Quantized decode append: write this step's `new` [B, 1, H, D] K/V
    rows into the int8 paged cache, growing each target page's running
    abs-max scale when needed. Returns (cache, scales)."""
    from ..ops.pallas.decode_attention import paged_kv_write_q8

    return paged_kv_write_q8(buf, scales, new[:, 0], tables,
                             jnp.asarray(lengths).astype(jnp.int32))


def _paged_attend_q8(q, kc, vc, k_sc, v_sc, tables, lengths):
    """Dequant-fused decode attention over the int8 paged cache (same
    lengths + 1 contract as _paged_attend)."""
    from ..ops.pallas.decode_attention import paged_decode_attention

    B, S, H, D = q.shape
    o = paged_decode_attention(
        q.reshape(B, H, D), kc, vc, tables,
        jnp.asarray(lengths).astype(jnp.int32) + 1, kv_scales=(k_sc, v_sc))
    return o.reshape(B, S, H, D)


def _decode_mask(s_max, offset, s_new):
    """Bool mask: position i (absolute off+i) attends to j<=off+i.
    Scalar offset -> [1,1,S_new,S_max] (shared); vector offset [B] ->
    [B,1,S_new,S_max] (per-slot lengths, continuous batching)."""
    def fn(off):
        off = jnp.asarray(off).astype(jnp.int32)
        cols = jnp.arange(s_max)[None, :]
        if off.ndim == 0:
            rows = off.reshape(()) + jnp.arange(s_new)[:, None]
            return (cols <= rows)[None, None]
        rows = off[:, None, None] + jnp.arange(s_new)[None, :, None]
        return (cols[None] <= rows)[:, None]

    return run_op("decode_mask", fn, [offset])


class GPTMLP(nn.Layer):
    """FFN: gelu 2-matmul or swiglu 3-matmul, TP column→row sharded
    (reference: fused_feedforward_kernel.cu; swiglu.py:26)."""

    def __init__(self, config: GPTConfig):
        super().__init__()
        h, f = config.hidden_size, config.ffn_size
        attr = _init_attr(config)
        bias = config.norm_type == "layernorm"
        self.activation = config.activation
        if config.activation == "swiglu":
            self.gate_proj = ColumnParallelLinear(h, f, weight_attr=attr, has_bias=bias,
                                                  gather_output=False)
            self.up_proj = ColumnParallelLinear(h, f, weight_attr=attr, has_bias=bias,
                                                gather_output=False)
            self.down_proj = RowParallelLinear(f, h, weight_attr=attr, has_bias=bias,
                                               input_is_parallel=True)
        else:
            self.fc1 = ColumnParallelLinear(h, f, weight_attr=attr, has_bias=bias,
                                            gather_output=False)
            self.fc2 = RowParallelLinear(f, h, weight_attr=attr, has_bias=bias,
                                         input_is_parallel=True)

    def forward(self, x):
        if self.activation == "swiglu":
            return self.down_proj(swiglu(self.gate_proj(x), self.up_proj(x)))
        return self.fc2(F.gelu(self.fc1(x)))


class GPTDecoderLayer(nn.Layer):
    """Pre-norm decoder block (reference: the block fused_multi_transformer
    implements in one kernel, fused_multi_transformer_kernel.cu — here a
    traceable composition XLA fuses)."""

    def __init__(self, config: GPTConfig):
        super().__init__()
        self.config = config
        self.input_layernorm = _make_norm(config)
        self.self_attn = GPTAttention(config)
        self.post_attention_layernorm = _make_norm(config)
        self.mlp = GPTMLP(config)
        self.dropout = nn.Dropout(config.hidden_dropout_prob)

    def forward(self, x, position_ids=None, cache=None, cache_offset=None,
                startend_row_indices=None, block_tables=None):
        # the scopes name the model's parts in a profiler trace (metadata
        # only; docs/OBSERVABILITY.md lists them)
        residual = x
        with jax.named_scope("ln"):
            h = self.input_layernorm(x)
        with jax.named_scope("attn"):
            if cache is not None:
                h, new_cache = self.self_attn(
                    h, position_ids, cache, cache_offset,
                    block_tables=block_tables)
            else:
                h = self.self_attn(
                    h, position_ids,
                    startend_row_indices=startend_row_indices)
                new_cache = None
            x = residual + self.dropout(h)
        residual = x
        with jax.named_scope("ln"):
            h = self.post_attention_layernorm(x)
        with jax.named_scope("mlp"):
            h = self.mlp(h)
            x = residual + self.dropout(h)
        if self.config.sequence_parallel:
            x = mark_as_sequence_parallel(x)
        if cache is not None:
            return x, new_cache
        return x


def hidden_at(h, at):
    """[B, S, d] -> [B, 1, d]: each row's hidden state at position `at[b]`,
    cut BEFORE the final norm and the head, so that a serving prefill makes
    one row of logits and not a bucket's."""
    return run_op(
        "hidden_at",
        lambda x, i: jnp.take_along_axis(
            x, i.astype(jnp.int32)[:, None, None], axis=1), [h, at])


class GPTModel(nn.Layer):
    """Embeddings + decoder stack + final norm."""

    def __init__(self, config: GPTConfig):
        super().__init__()
        self.config = config
        attr = _init_attr(config)
        self.embed_tokens = VocabParallelEmbedding(
            config.vocab_size, config.hidden_size, weight_attr=attr
        )
        if not config.use_rope:
            self.embed_positions = nn.Embedding(
                config.max_position_embeddings, config.hidden_size, weight_attr=attr
            )
        self.embed_dropout = nn.Dropout(config.hidden_dropout_prob)
        self.layers = nn.LayerList([GPTDecoderLayer(config) for _ in range(config.num_layers)])
        self.final_norm = _make_norm(config)

    def forward(self, input_ids, position_ids=None, caches=None,
                cache_offset=None, attn_startend_row_indices=None,
                block_tables=None, logits_at=None):
        B, S = input_ids.shape[0], input_ids.shape[1]
        if position_ids is None:
            if caches is not None and cache_offset is not None:
                # decode default: absolute positions start at the cache offset
                position_ids = run_op(
                    "decode_positions",
                    lambda off: jnp.broadcast_to(
                        jnp.asarray(off).astype(jnp.int32).reshape(())
                        + jnp.arange(S)[None, :],
                        (B, S),
                    ),
                    [cache_offset],
                )
            else:
                position_ids = Tensor(
                    jnp.broadcast_to(jnp.arange(S)[None, :], (B, S))
                )
        with jax.named_scope("embed"):
            h = self.embed_tokens(input_ids)
            if not self.config.use_rope:
                h = h + self.embed_positions(position_ids)
            h = self.embed_dropout(h)
        if self.config.sequence_parallel:
            h = mark_as_sequence_parallel(h)
        new_caches = [] if caches is not None else None
        if caches is not None and not len(caches):
            # `caches` given and empty: a prefill from position 0, which
            # reads no cache. Every layer attends over the prompt's own K
            # and V, causally, and returns them as its new cache
            caches = [()] * len(self.layers)

        if caches is not None and attn_startend_row_indices is not None:
            raise ValueError(
                "attn_startend_row_indices is not supported together with KV "
                "caches: the cached decode path would silently attend across "
                "document boundaries")

        def run_layer(layer, h, cache):
            if cache is not None:
                return layer(h, position_ids, cache, cache_offset,
                             block_tables=block_tables)
            return layer(h, position_ids,
                         startend_row_indices=attn_startend_row_indices)

        for i, layer in enumerate(self.layers):
            cache = caches[i] if caches is not None else None
            if self.config.use_recompute and self.training and cache is None:
                from ..distributed.fleet.recompute import recompute

                h = recompute(layer, h, position_ids,
                              startend_row_indices=attn_startend_row_indices)
            else:
                out = run_layer(layer, h, cache)
                if cache is not None:
                    h, nc = out
                    new_caches.append(nc)
                else:
                    h = out
        if logits_at is not None:
            h = hidden_at(h, logits_at)
        with jax.named_scope("ln"):
            h = self.final_norm(h)
        if caches is not None:
            return h, new_caches
        return h


class GPTForCausalLM(nn.Layer):
    """LM head on top of GPTModel. Tied embeddings (GPT) share the
    vocab-sharded embedding matrix; untied (LLaMA) use a vocab-sharded
    ColumnParallelLinear. Logits stay vocab-sharded into the parallel
    cross-entropy (reference: mp_layers.py:744 ParallelCrossEntropy)."""

    def __init__(self, config: GPTConfig):
        super().__init__()
        self.config = config
        self.gpt = GPTModel(config)
        if not config.tie_word_embeddings:
            self.lm_head = ColumnParallelLinear(
                config.hidden_size, config.vocab_size,
                weight_attr=_init_attr(config), has_bias=False, gather_output=False,
            )

    def forward(self, input_ids, position_ids=None, caches=None,
                cache_offset=None, attn_startend_row_indices=None,
                block_tables=None, logits_at=None):
        """`logits_at` [B] int32: logits of that one position a row only,
        [B, 1, vocab] (a serving prefill reads its prompt's last)."""
        out = self.gpt(input_ids, position_ids, caches, cache_offset,
                       attn_startend_row_indices=attn_startend_row_indices,
                       block_tables=block_tables, logits_at=logits_at)
        if caches is not None:
            h, new_caches = out
        else:
            h = out
        with jax.named_scope("lm_head"):
            if self.config.tie_word_embeddings:
                w = self.gpt.embed_tokens.weight
                logits = run_op("lm_head_tied",
                                lambda a, ww: jnp.matmul(a, ww.T), [h, w])
                logits = _constrain(
                    logits, P(P.UNCONSTRAINED, P.UNCONSTRAINED, "mp"))
            else:
                logits = self.lm_head(h)
        if caches is not None:
            return logits, new_caches
        return logits

    def init_kv_caches(self, batch_size, max_seq_len, dtype="float32"):
        """Static-capacity decode caches, one (k, v) pair per layer."""
        cfg = self.config
        shape = (batch_size, max_seq_len, cfg.kv_heads, cfg.head_dim)
        return [
            (Tensor(jnp.zeros(shape, jnp.dtype(dtype))), Tensor(jnp.zeros(shape, jnp.dtype(dtype))))
            for _ in range(cfg.num_layers)
        ]


class GPTPretrainingCriterion(nn.Layer):
    """Masked next-token cross entropy over (possibly vocab-sharded) logits."""

    def __init__(self, config: GPTConfig = None):
        super().__init__()
        self.ce = ParallelCrossEntropy()

    def forward(self, logits, labels, loss_mask=None):
        with jax.named_scope("loss"):
            losses = self.ce(logits, labels)  # [B, S]
            if loss_mask is not None:
                m = loss_mask.reshape(losses.shape).astype("float32")
                return ((losses.astype("float32") * m).sum()
                        / m.sum().clip(min=1.0))
            return losses.mean()


# ----------------------------------------------------------------------- #
# presets (sizes per GPT-3 paper table 2.1)
# ----------------------------------------------------------------------- #


def gpt3_tiny(**kw):
    return GPTConfig(vocab_size=1024, hidden_size=64, num_layers=2, num_heads=4,
                     max_position_embeddings=128, **kw)


def gpt3_125m(**kw):
    return GPTConfig(hidden_size=768, num_layers=12, num_heads=12, **kw)


def gpt3_350m(**kw):
    return GPTConfig(hidden_size=1024, num_layers=24, num_heads=16, **kw)


def gpt3_1p3b(**kw):
    return GPTConfig(hidden_size=2048, num_layers=24, num_heads=16, **kw)


def gpt3_6p7b(**kw):
    return GPTConfig(hidden_size=4096, num_layers=32, num_heads=32, **kw)


def gpt3_13b(**kw):
    return GPTConfig(hidden_size=5120, num_layers=40, num_heads=40, **kw)


def _generate_method(self, input_ids, **kwargs):
    """Autoregressive decoding (paddle_tpu.models.generation.generate)."""
    from .generation import generate as _generate

    return _generate(self, input_ids, **kwargs)


GPTForCausalLM.generate = _generate_method
