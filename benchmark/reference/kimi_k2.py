"""Plain reference of the `kimi_k2` decoder (Moonshot Kimi K2, the
`DeepseekV3ForCausalLM` architecture: latent attention, sigmoid-routed
experts beside a shared one) in float32 `jax.numpy`: the EXPANDED form only
(keys and values per head for every position), dense masked attention a block
of queries at a time so that 16k positions fit, no kernel, no cache, no page,
no absorbed projection, no chunk of the expert layer, every matmul at
`highest` precision. It reads the parameters and buffers the program holds,
by the names `models/kimi_k2.py` gives them, and shares no code and no method
with the program. The parameters stay bf16 on the device; one matrix (one
expert) at a time is cast to float32 where it is multiplied, so that 7 GB of
parameters and the forward's own arrays fit beside each other. One jitted
function per layer. Linear weights are stored [in, out].

The equations (RMSNorm(x) = x / sqrt(mean(x^2) + eps) * w, eps =
rms_norm_eps; no bias anywhere; pre-norm, two norms a layer):

    h_0 = E[ids]
    x   = norm_in(h)
    cq  = RMSNorm(Wqa x)  (q_lora_rank wide);  q = Wqb cq, H heads of
          [q_nope (qk_nope_head_dim) ; q_pe (qk_rope_head_dim)]
    [ckv (kv_lora_rank) ; k_pe (qk_rope_head_dim)] = Wkva x;  c = RMSNorm(ckv)
    RoPE on q_pe (per head) and k_pe (ONE for all heads), adjacent pairs
          (2i, 2i+1), theta = rope_theta, YaRN:  d = qk_rope_head_dim,
          f_i = theta^(-2i/d), x_r = d ln(original / (2 pi r)) / (2 ln theta),
          low = floor(x_beta_fast), high = ceil(x_beta_slow) (high += 0.001
          if equal), ramp_i = clip((i - low) / (high - low), 0, 1),
          inv_freq_i = f_i (1 - ramp_i) + f_i / factor * ramp_i;  cos and sin
          times mscale(factor, mscale) / mscale(factor, mscale_all_dim), with
          mscale(s, m) = 0.1 m ln s + 1
    [k_nope_h ; v_h] = Wkvb^h c;  k_h = [k_nope_h ; k_pe]
    o_h = softmax_causal(q_h . k_h * scale) v_h,
          scale = (nope + rope)^-0.5 * mscale(factor, mscale_all_dim)^2
    h   = h + Wo concat_h(o_h)
    m   = norm_post(h)
    y   = Wd (silu(Wg m) * Wu m)                layers < first_k_dense_replace
    y   = shared(m) + sum_e w_e expert_e(m)     the others:
          s = sigmoid(Wr m) in f32 over ALL experts; the k experts with the
          largest s + b (b: e_score_correction_bias, a buffer, in the choice
          only; n_group = topk_group = 1); w = s[picked] / (sum + 1e-20) *
          routed_scaling_factor
    h   = h + y
    logits = Wh norm_f(h)                       (untied head)

`held` = (first, count) names the routed experts the parameters hold; what
the others would add is left out, as in the program (`model-configs` guide,
section 4). The shared expert is always on.

`lower_precision=True` is the yardstick's second reading (PERF.md): the same
forward with every operand of every matrix product (attention's q, k, v and
probabilities among them) rounded to the 3 mantissa bits of an 8-bit float:
the nearest precision below the bf16 the configuration states. A comparison
that such a forward passes is too loose.
"""

import functools
import math

import jax
import jax.numpy as jnp
import numpy as np

HIGHEST = jax.lax.Precision.HIGHEST
QUERY_BLOCK = 256     # 64 heads x 256 queries x 16k keys of f32 scores: 1 GB
TOKEN_BLOCK = 2048    # the dense layer's 2 x 18432-wide activations, blockwise


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
    """w_out (silu(a) * b), [a | b] = w_in u, `TOKEN_BLOCK` tokens at a
    time."""
    T = u.shape[0]
    block = min(TOKEN_BLOCK, T)
    pad = -T % block

    def one(ub):
        ab = _mm(ub, w_in, low)
        f = ab.shape[-1] // 2
        return _mm(jax.nn.silu(ab[..., :f]) * ab[..., f:], w_out, low)

    out = jax.lax.map(one, jnp.pad(u, ((0, pad), (0, 0))).reshape(
        -1, block, u.shape[-1]))
    return out.reshape(T + pad, -1)[:T]


def _mscale(factor, m):
    return 0.1 * m * math.log(factor) + 1.0 if factor > 1 else 1.0


def inv_freq(config):
    """YaRN's frequencies for this configuration, [qk_rope_head_dim / 2]."""
    d, theta = int(config["qk_rope_head_dim"]), float(config["rope_theta"])
    f = theta ** (-np.arange(0, d, 2, dtype=np.float64) / d)
    ys = config.get("rope_scaling")
    if not ys:
        return f

    def x(r):
        return (d * math.log(ys["original_max_position_embeddings"]
                             / (2 * math.pi * r)) / (2 * math.log(theta)))

    low = max(math.floor(x(ys["beta_fast"])), 0)
    high = min(math.ceil(x(ys["beta_slow"])), d - 1)
    if low == high:
        high += 0.001
    ramp = np.clip((np.arange(d // 2) - low) / (high - low), 0, 1)
    return f * (1 - ramp) + f / ys["factor"] * ramp


def softmax_scale(config):
    scale = (config["qk_nope_head_dim"] + config["qk_rope_head_dim"]) ** -0.5
    ys = config.get("rope_scaling")
    if ys and ys.get("mscale_all_dim"):
        scale *= _mscale(ys["factor"], ys["mscale_all_dim"]) ** 2
    return scale


def _amplitude(config):
    ys = config.get("rope_scaling")
    if not ys:
        return 1.0
    return (_mscale(ys["factor"], ys["mscale"])
            / _mscale(ys["factor"], ys["mscale_all_dim"]))


def _turn(x, freqs, amplitude):
    """RoPE on adjacent pairs, in place: (x_2i, x_2i+1) turned by the angle
    position * freqs_i. x [T, ..., d] at positions 0 .. T - 1."""
    T, d = x.shape[0], x.shape[-1]
    ang = jnp.arange(T, dtype=jnp.float32)[:, None] * jnp.asarray(
        freqs, jnp.float32)[None, :]
    ang = ang.reshape((T,) + (1,) * (x.ndim - 2) + (d // 2,))
    cos, sin = jnp.cos(ang) * amplitude, jnp.sin(ang) * amplitude
    pairs = x.reshape(x.shape[:-1] + (d // 2, 2))
    a, b = pairs[..., 0], pairs[..., 1]
    return jnp.stack([a * cos - b * sin, b * cos + a * sin], -1).reshape(
        x.shape)


def _attend(q, k, v, scale, low):
    """o [T, H, Dv] for q, k [T, H, D], v [T, H, Dv]: dense causal scores,
    `QUERY_BLOCK` queries at a time against all the keys."""
    T, H, _ = q.shape
    if low:
        q, k, v = _round8(q), _round8(k), _round8(v)
    block = min(QUERY_BLOCK, T)
    pad = -T % block
    qp = jnp.pad(q, ((0, pad), (0, 0), (0, 0)))
    j = jnp.arange(T)[None, :]

    def one(_, start):
        qb = jax.lax.dynamic_slice_in_dim(qp, start, block, 0)
        i = start + jnp.arange(block)[:, None]
        s = jnp.einsum("qhd,khd->hqk", qb, k, precision=HIGHEST) * scale
        p = jax.nn.softmax(jnp.where((j <= i)[None], s, -jnp.inf), axis=-1)
        if low:
            p = _round8(p)
        return None, jnp.einsum("hqk,khd->qhd", p, v, precision=HIGHEST)

    _, out = jax.lax.scan(one, None, jnp.arange(0, T + pad, block))
    return out.reshape(T + pad, H, -1)[:T]


def _attention(x, p, s):
    T = x.shape[0]
    H, L, N, R, V = s["heads"], s["latent"], s["nope"], s["rope"], s["v"]
    low, eps = s["low"], s["eps"]
    freqs, amp = np.asarray(s["freqs"]), s["amplitude"]
    cq = _rms_norm(_mm(x, p["self_attn.q_a_proj.weight"], low),
                   p["self_attn.q_a_layernorm.weight"], eps)
    q = _mm(cq, p["self_attn.q_b_proj.weight"], low).reshape(T, H, N + R)
    ckv = _mm(x, p["self_attn.kv_a_proj_with_mqa.weight"], low)
    c = _rms_norm(ckv[:, :L], p["self_attn.kv_a_layernorm.weight"], eps)
    k_pe = _turn(ckv[:, L:], freqs, amp)                      # [T, R]
    q = jnp.concatenate([q[..., :N], _turn(q[..., N:], freqs, amp)], -1)
    kv = _mm(c, p["self_attn.kv_b_proj.weight"], low).reshape(T, H, N + V)
    k = jnp.concatenate(
        [kv[..., :N], jnp.broadcast_to(k_pe[:, None, :], (T, H, R))], -1)
    o = _attend(q, k, kv[..., N:], s["scale"], low).reshape(T, H * V)
    return _mm(o, p["self_attn.o_proj.weight"], low)


def _experts(m, p, s):
    """shared(m) + the held experts' part of the routed sum, m [T, h]."""
    low = s["low"]
    score = jax.nn.sigmoid(_mm(m, p["moe.router"], low))
    _, picked = jax.lax.top_k(score + _f32(p["moe.expert_bias"]), s["top_k"])
    chosen = jnp.take_along_axis(score, picked, axis=-1)
    weight = chosen / (chosen.sum(-1, keepdims=True) + 1e-20) * s["route"]

    def one(total, held):
        index, w_in, w_out = held      # one expert's matrices, still bf16
        w = jnp.where(picked == index + s["first"], weight, 0.0).sum(-1)
        return total + w[:, None] * _gated(m, w_in, w_out, low), None

    count = p["moe.w_in"].shape[0]
    routed, _ = jax.lax.scan(
        one, jnp.zeros_like(m),
        (jnp.arange(count), p["moe.w_in"], p["moe.w_out"]))
    return routed + _gated(m, p["shared_experts.gate_up_proj.weight"],
                           p["shared_experts.down_proj.weight"], low)


@functools.partial(jax.jit, static_argnames=("sizes",))
def _layer(h, p, sizes):
    s = dict(sizes)
    eps = s["eps"]
    h = h + _attention(_rms_norm(h, p["input_layernorm.weight"], eps), p, s)
    m = _rms_norm(h, p["post_attention_layernorm.weight"], eps)
    if s["dense"]:
        return h + _gated(m, p["mlp.gate_up_proj.weight"],
                          p["mlp.down_proj.weight"], s["low"])
    return h + _experts(m, p, s)


@jax.jit
def _embed(ids, table):
    return _f32(table[ids])


@functools.partial(jax.jit, static_argnames=("eps", "low"))
def _head(x, norm_w, w, eps, low=False):
    return _mm(_rms_norm(x, norm_w, eps), w, low)


def hidden(params, ids, config, held=None, lower_precision=False):
    """The last layer's output [T, h], before the final norm, for one
    sequence `ids` [T]. `params`: the program's parameters AND buffers by
    name; `config`: the configuration's dict (the source's own keys); `held`
    = (first, count) of the routed experts the parameters hold, default
    all."""
    x = _embed(jnp.asarray(ids, jnp.int32), params["embed_tokens.weight"])
    freqs = tuple(float(f) for f in inv_freq(config))
    for i in range(int(config["num_hidden_layers"])):
        sizes = tuple(sorted({
            "heads": int(config["num_attention_heads"]),
            "latent": int(config["kv_lora_rank"]),
            "nope": int(config["qk_nope_head_dim"]),
            "rope": int(config["qk_rope_head_dim"]),
            "v": int(config["v_head_dim"]),
            "eps": float(config["rms_norm_eps"]),
            "freqs": freqs,
            "amplitude": float(_amplitude(config)),
            "scale": float(softmax_scale(config)),
            "dense": i < int(config["first_k_dense_replace"]),
            "top_k": int(config["num_experts_per_tok"]),
            "route": float(config["routed_scaling_factor"]),
            "first": 0 if held is None else int(held[0]),
            "low": bool(lower_precision),
        }.items()))
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
                 float(config["rms_norm_eps"]), bool(lower_precision))
