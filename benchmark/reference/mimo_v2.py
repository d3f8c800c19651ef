"""Plain reference of the `mimo_v2` decoder (Xiaomi MiMo-V2-Flash: sliding
layers of more KV heads than the full ones and a learned sink in their
softmax, keys wider than values, rotary positions on part of a head,
sigmoid-routed experts and no shared one) in float32 `jax.numpy`: no
kernels, no cache, no pages, no chunks of the expert layer, every matmul at
`highest` precision. It reads the parameters and buffers the program holds,
by the names `models/mimo_v2.py` gives them, cast to float32 a layer at a
time, and shares no code and no method with the program: attention is dense
scores under an explicit mask, computed a block of queries at a time so that
16k positions fit (a sliding layer's block against the keys its window can
reach, cut out of the whole sequence), the sink is a column of its own
beside the scores, the experts are a loop over the held experts with a dense
mask (the program sorts rows into a grouped GEMM). One jitted function per
layer. Linear weights are stored [in, out].

The equations (RMSNorm(x) = x / sqrt(mean(x^2) + eps) * w, eps =
layernorm_epsilon; no bias anywhere; two norms a layer). For layer l of kind
t = hybrid_layer_pattern[l] (0 full, 1 sliding):

    h_0 = E[ids]
    a   = norm_in(h)
    q   = Wq a -> [64, 192];  k = Wk a -> [Hkv_t, 192];
    v   = attention_value_scale * (Wv a) -> [Hkv_t, 128]
          Hkv = num_key_value_heads (4) full, swa_num_key_value_heads (8)
          sliding
    rotate-half RoPE on values 0 .. R - 1 of each q and k head (R = 64 of
          192: partial_rotary_factor x head_dim to the nearest even number),
          base rope_theta (5e6) full or swa_rope_theta (1e4) sliding; values
          R .. 191 unrotated
    s_ij = q_i . k_j / sqrt(192);  visible: full j <= i;
                                   sliding 0 <= i - j < sliding_window
    full:     p_ij = exp(s_ij) / sum_j' exp(s_ij')
    sliding:  p_ij = exp(s_ij) / (exp(b_head) + sum_j' exp(s_ij'))
          b = `sink`: 64 learned logits a sliding layer; it adds no value
    h   = h + Wo [sum_j p_ij v_j]                    ([64 x 128] -> 4096)
    m   = norm_post(h)
    y   = Wd (silu(Wg m) * Wu m)                     moe_layer_freq[l] = 0
    y   = sum_e w_e expert_e(m)                      moe_layer_freq[l] = 1:
          s = sigmoid(Wr m) in f32 over ALL experts; the k experts with the
          largest s + b (b: e_score_correction_bias, a buffer, in the choice
          only; n_group = topk_group = 1); w = s[picked] / (their sum +
          1e-20); scale 1.0; no shared expert
    h   = h + y
    logits = Wh norm_f(h)                                  (untied head)

`held` = (first, count) names the routed experts the parameters hold; what
the others would add is left out, as in the program (`model-configs` guide,
section 4).

Departures from the published description: none in the mathematics that
config.json fixes; what it has a key for and no formula (the sink's form,
where `attention_value_scale` acts, which values RoPE turns) is under
`assumed` in the configuration file. Left out: the three
multi-token-prediction layers (no config key; a tick yields one token a
row) and the V2.5 vision and audio towers.

`lower_precision=True` is the yardstick's second reading (PERF.md): the same
forward with every operand of every matrix product (attention's q, k, v and
probabilities among them) rounded to the 3 mantissa bits of an 8-bit float
(e4m3's precision at any exponent): the nearest precision below the bf16 the
configuration states. A comparison that such a forward passes is too loose.
"""

import functools

import jax
import jax.numpy as jnp

HIGHEST = jax.lax.Precision.HIGHEST
QUERY_BLOCK = 512


def _f32(x):
    return jnp.asarray(x).astype(jnp.float32)


def _round8(x):
    """x at 3 mantissa bits (and the implied one), exponent kept."""
    mantissa, exponent = jnp.frexp(x)
    return jnp.ldexp(jnp.round(mantissa * 16.0) / 16.0, exponent)


def _mm(x, w, low=False):
    w = _f32(w)
    if low:
        x, w = _round8(x), _round8(w)
    return jnp.matmul(x, w, precision=HIGHEST)


def _rms_norm(x, w, eps):
    return x / jnp.sqrt((x * x).mean(-1, keepdims=True) + eps) * _f32(w)


def _gated(u, w_in, w_out, low=False):
    ab = _mm(u, w_in, low)
    f = ab.shape[-1] // 2
    return _mm(jax.nn.silu(ab[..., :f]) * ab[..., f:], w_out, low)


def _turn(x, theta, rotary):
    """RoPE, rotate-half over the first `rotary` values of each head: x [T,
    heads, D] at positions 0 .. T - 1; value i < rotary / 2 pairs with value
    i + rotary / 2 at frequency theta^(-2i / rotary)."""
    T = x.shape[0]
    inv = 1.0 / theta ** (jnp.arange(0, rotary, 2, dtype=jnp.float32) / rotary)
    ang = jnp.arange(T, dtype=jnp.float32)[:, None] * inv[None, :]
    ang = jnp.concatenate([ang, ang], axis=-1)[:, None, :]   # [T, 1, rotary]
    part, rest = x[..., :rotary], x[..., rotary:]
    x1, x2 = part[..., :rotary // 2], part[..., rotary // 2:]
    turned = (part * jnp.cos(ang)
              + jnp.concatenate([-x2, x1], -1) * jnp.sin(ang))
    return jnp.concatenate([turned, rest], axis=-1)


def _attend(q, k, v, window, sink, low):
    """o [T, H, Dv] for q, k [T, H, Dk], v [T, H, Dv] (KV heads repeated):
    dense scores, `QUERY_BLOCK` queries at a time; `sink` [H] or None: one
    more column of the softmax, whose probability is thrown away."""
    T, H, D = q.shape
    if low:
        q, k, v = _round8(q), _round8(k), _round8(v)
    block = min(QUERY_BLOCK, T)
    pad = -T % block
    qp = jnp.pad(q, ((0, pad), (0, 0), (0, 0)))
    # the keys one block of queries can reach: all before its end, or, under
    # a window, those from `window - 1` before its start
    reach = T + pad if window is None else min(T + pad, block + window - 1)
    kp = jnp.pad(k, ((reach - block, pad), (0, 0), (0, 0)))
    vp = jnp.pad(v, ((reach - block, pad), (0, 0), (0, 0)))

    def one(_, start):
        qb = jax.lax.dynamic_slice_in_dim(qp, start, block, 0)
        # keys at positions start + block - reach .. start + block - 1
        kb = jax.lax.dynamic_slice_in_dim(kp, start, reach, 0)
        vb = jax.lax.dynamic_slice_in_dim(vp, start, reach, 0)
        i = start + jnp.arange(block)[:, None]
        j = start + block - reach + jnp.arange(reach)[None, :]
        seen = (j >= 0) & (j <= i)
        if window is not None:
            seen = seen & (i - j < window)
        s = jnp.einsum("qhd,khd->hqk", qb, kb, precision=HIGHEST) / D ** 0.5
        s = jnp.where(seen[None], s, -jnp.inf)
        if sink is not None:
            column = jnp.broadcast_to(sink[:, None, None], (H, block, 1))
            p = jax.nn.softmax(jnp.concatenate([s, column], -1), -1)[..., :-1]
        else:
            p = jax.nn.softmax(s, axis=-1)
        if low:
            p = _round8(p)
        return None, jnp.einsum("hqk,khd->qhd", p, vb, precision=HIGHEST)

    _, out = jax.lax.scan(one, None, jnp.arange(0, T + pad, block))
    return out.reshape(T + pad, H, v.shape[-1])[:T]


def _attention(a, p, s):
    T = a.shape[0]
    H, Hkv, low = s["heads"], s["kv_heads"], s["low"]
    q = _mm(a, p["self_attn.q_proj.weight"], low).reshape(T, H, s["key_dim"])
    k = _mm(a, p["self_attn.k_proj.weight"], low).reshape(
        T, Hkv, s["key_dim"])
    v = _mm(a, p["self_attn.v_proj.weight"], low).reshape(
        T, Hkv, s["value_dim"]) * s["value_scale"]
    q = _turn(q, s["theta"], s["rotary"])
    k = _turn(k, s["theta"], s["rotary"])
    k = jnp.repeat(k, H // Hkv, axis=1)
    v = jnp.repeat(v, H // Hkv, axis=1)
    sink = _f32(p["self_attn.sink"]) if s["sink"] else None
    o = _attend(q, k, v, s["window"], sink, low)
    return _mm(o.reshape(T, H * s["value_dim"]),
               p["self_attn.o_proj.weight"], low)


def _experts(m, p, s):
    """The held experts' part of the routed sum, m [T, h]."""
    low = s["low"]
    score = jax.nn.sigmoid(_mm(m, p["moe.router"], low))
    _, picked = jax.lax.top_k(score + _f32(p["moe.expert_bias"]), s["top_k"])
    chosen = jnp.take_along_axis(score, picked, axis=-1)
    weight = chosen / (chosen.sum(-1, keepdims=True) + 1e-20)

    def one(total, held):
        index, w_in, w_out = held
        w = jnp.where(picked == index + s["first"], weight, 0.0).sum(-1)
        return total + w[:, None] * _gated(m, w_in, w_out, low), None

    count = p["moe.w_in"].shape[0]
    routed, _ = jax.lax.scan(
        one, jnp.zeros_like(m),
        (jnp.arange(count), _f32(p["moe.w_in"]), _f32(p["moe.w_out"])))
    return routed


@functools.partial(jax.jit, static_argnames=("sizes",))
def _layer(h, p, sizes):
    s = dict(sizes)
    eps = s["eps"]
    h = h + _attention(_rms_norm(h, p["input_layernorm.weight"], eps), p, s)
    m = _rms_norm(h, p["post_attention_layernorm.weight"], eps)
    if s["dense"]:
        y = _gated(m, p["mlp.gate_up_proj.weight"],
                   p["mlp.down_proj.weight"], s["low"])
    else:
        y = _experts(m, p, s)
    return h + y


@jax.jit
def _embed(ids, table):
    return _f32(table)[ids]


@functools.partial(jax.jit, static_argnames=("eps", "low"))
def _head(x, norm_w, w, eps, low=False):
    return _mm(_rms_norm(x, norm_w, eps), w, low)


def hidden(params, ids, config, held=None, lower_precision=False):
    """The last layer's output [T, h], before the final norm, for one
    sequence `ids` [T]. `params`: the program's parameters AND buffers by
    name; `config`: the configuration's dict (the source's own keys); `held`
    = (first, count) of the routed experts the parameters hold, default all
    of `n_routed_experts`."""
    x = _embed(jnp.asarray(ids, jnp.int32), params["embed_tokens.weight"])
    layers = int(config["num_hidden_layers"])
    kinds = config["hybrid_layer_pattern"][:layers]
    experts = config["moe_layer_freq"][:layers]
    key_dim = int(config["head_dim"])
    for i, (sliding, routed) in enumerate(zip(kinds, experts)):
        sizes = tuple(sorted({
            "heads": int(config["num_attention_heads"]),
            "kv_heads": int(config["swa_num_key_value_heads"] if sliding
                            else config["num_key_value_heads"]),
            "key_dim": key_dim,
            "value_dim": int(config["v_head_dim"]),
            "rotary": 2 * round(
                float(config["partial_rotary_factor"]) * key_dim / 2),
            "value_scale": float(config["attention_value_scale"]),
            "eps": float(config["layernorm_epsilon"]),
            "theta": float(config["swa_rope_theta"] if sliding
                           else config["rope_theta"]),
            "window": int(config["sliding_window"]) if sliding else None,
            "sink": bool(config["add_swa_attention_sink_bias"] if sliding
                         else config["add_full_attention_sink_bias"]),
            "dense": not routed,
            "top_k": int(config["num_experts_per_tok"]),
            "first": 0 if held is None else int(held[0]),
            "low": bool(lower_precision),
        }.items(), key=lambda kv: kv[0]))
        prefix = f"layers.{i}."
        p = {k[len(prefix):]: v for k, v in params.items()
             if k.startswith(prefix)}
        x = _layer(x, p, sizes)
    return x


def logits(params, ids, config, held=None, rows=None, lower_precision=False):
    """float32 logits [T, vocab] of one sequence (or of its positions
    `rows` only: the head over a whole long sequence is the largest array of
    the forward)."""
    x = hidden(params, ids, config, held, lower_precision)
    if rows is not None:
        x = x[jnp.asarray(rows)]
    return _head(x, params["norm.weight"], params["lm_head.weight"],
                 float(config["layernorm_epsilon"]), bool(lower_precision))
