"""Plain reference of the `afmoe` decoder (Arcee Trinity: sliding-window and
full attention layers, gated QK-normed heads, sigmoid-routed experts beside a
shared one) in float32 `jax.numpy`: no kernels, no cache, no pages, no chunks
of the expert layer, every matmul at `highest` precision. It reads the
parameters and buffers the program holds, by the names `models/afmoe.py`
gives them, cast to float32 a layer at a time, and shares no code and no
method with the program: attention is dense scores under an explicit mask,
computed a block of queries at a time so that 16k positions fit (a sliding
layer's block against the keys its window can reach, cut out of the whole
sequence), the experts are a loop over the held experts with a dense mask
(the program sorts rows into a grouped GEMM). One jitted function per layer.
Linear weights are stored [in, out].

The equations (RMSNorm(x) = x / sqrt(mean(x^2) + eps) * w, eps =
rms_norm_eps; no bias anywhere; four norms a layer):

    h_0 = E[ids] * sqrt(hidden_size)                       (mup_enabled)
    a   = norm_in(h);  q = Wq a (H heads of D), k = Wk a, v = Wv a (Hkv
          heads), g = Wg a (H * D wide)
    q, k  RMS-normed per head over D (learned weights q_norm, k_norm)
    sliding_attention: RoPE (theta, rotate-half over the whole head) on q and
          k; key j visible to query i iff 0 <= i - j < sliding_window
    full_attention:    NO positions; key j visible iff j <= i
    o   = softmax(q k^T / sqrt(D)) v
    h   = h + norm_post_attn(Wo (o * sigmoid(g)))
    m   = norm_pre_mlp(h)
    y   = Wd (silu(Wg' m) * Wu m)                          layers < num_dense_layers
    y   = shared(m) + sum_e w_e expert_e(m)                the others:
          s = sigmoid(Wr m) in f32 over ALL experts; the k experts with the
          largest s + b (b: the per-expert bias, a buffer, in the choice
          only); w = s[picked] / (sum of them + 1e-20) * route_scale
    h   = h + norm_post_mlp(y)
    logits = Wh norm_f(h)                                  (untied head)

`held` = (first, count) names the routed experts the parameters hold; what
the others would add is left out, as in the program (`model-configs` guide,
section 4). The shared expert is always on.

Departures from the published description: none in the mathematics; what
the public config has no key for is under `assumed` in the configuration
file (the gate's order of operations, the norms' places, the gate on the
attention output), from the family's published modelling code.

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


def _turn(x, theta):
    """RoPE, rotate-half over the whole head: x [T, heads, D] at positions
    0 .. T - 1."""
    T, _, D = x.shape
    inv = 1.0 / theta ** (jnp.arange(0, D, 2, dtype=jnp.float32) / D)
    ang = jnp.arange(T, dtype=jnp.float32)[:, None] * inv[None, :]
    ang = jnp.concatenate([ang, ang], axis=-1)[:, None, :]     # [T, 1, D]
    x1, x2 = x[..., :D // 2], x[..., D // 2:]
    return x * jnp.cos(ang) + jnp.concatenate([-x2, x1], -1) * jnp.sin(ang)


def _attend(q, k, v, window, low):
    """o [T, H, D] for q [T, H, D], k, v [T, H, D] (KV heads repeated): dense
    scores, `QUERY_BLOCK` queries at a time."""
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
        p = jax.nn.softmax(jnp.where(seen[None], s, -jnp.inf), axis=-1)
        if low:
            p = _round8(p)
        return None, jnp.einsum("hqk,khd->qhd", p, vb, precision=HIGHEST)

    _, out = jax.lax.scan(one, None, jnp.arange(0, T + pad, block))
    return out.reshape(T + pad, H, D)[:T]


def _attention(a, p, s):
    T = a.shape[0]
    H, Hkv, D, low = s["heads"], s["kv_heads"], s["head_dim"], s["low"]
    q = _mm(a, p["self_attn.q_proj.weight"], low).reshape(T, H, D)
    k = _mm(a, p["self_attn.k_proj.weight"], low).reshape(T, Hkv, D)
    v = _mm(a, p["self_attn.v_proj.weight"], low).reshape(T, Hkv, D)
    g = _mm(a, p["self_attn.gate_proj.weight"], low)
    q = _rms_norm(q, p["self_attn.q_norm.weight"], s["eps"])
    k = _rms_norm(k, p["self_attn.k_norm.weight"], s["eps"])
    window = None
    if s["sliding"]:
        q, k = _turn(q, s["theta"]), _turn(k, s["theta"])
        window = s["window"]
    k = jnp.repeat(k, H // Hkv, axis=1)
    v = jnp.repeat(v, H // Hkv, axis=1)
    o = _attend(q, k, v, window, low).reshape(T, H * D)
    return _mm(o * jax.nn.sigmoid(g), p["self_attn.o_proj.weight"], low)


def _experts(m, p, s):
    """shared(m) + the held experts' part of the routed sum, m [T, h]."""
    low = s["low"]
    score = jax.nn.sigmoid(_mm(m, p["moe.router"], low))
    _, picked = jax.lax.top_k(score + _f32(p["moe.expert_bias"]), s["top_k"])
    chosen = jnp.take_along_axis(score, picked, axis=-1)
    weight = chosen / (chosen.sum(-1, keepdims=True) + 1e-20) * s["scale"]

    def one(total, held):
        index, w_in, w_out = held
        w = jnp.where(picked == index + s["first"], weight, 0.0).sum(-1)
        return total + w[:, None] * _gated(m, w_in, w_out, low), None

    count = p["moe.w_in"].shape[0]
    routed, _ = jax.lax.scan(
        one, jnp.zeros_like(m),
        (jnp.arange(count), _f32(p["moe.w_in"]), _f32(p["moe.w_out"])))
    return routed + _gated(m, p["shared_experts.gate_up_proj.weight"],
                           p["shared_experts.down_proj.weight"], low)


@functools.partial(jax.jit, static_argnames=("sizes",))
def _layer(h, p, sizes):
    s = dict(sizes)
    eps = s["eps"]
    a = _rms_norm(h, p["input_layernorm.weight"], eps)
    h = h + _rms_norm(_attention(a, p, s),
                      p["post_attention_layernorm.weight"], eps)
    m = _rms_norm(h, p["pre_mlp_layernorm.weight"], eps)
    if s["dense"]:
        y = _gated(m, p["mlp.gate_up_proj.weight"],
                   p["mlp.down_proj.weight"], s["low"])
    else:
        y = _experts(m, p, s)
    return h + _rms_norm(y, p["post_mlp_layernorm.weight"], eps)


@functools.partial(jax.jit, static_argnames=("scale",))
def _embed(ids, table, scale):
    return _f32(table)[ids] * scale


@functools.partial(jax.jit, static_argnames=("eps", "low"))
def _head(x, norm_w, w, eps, low=False):
    return _mm(_rms_norm(x, norm_w, eps), w, low)


def hidden(params, ids, config, held=None, lower_precision=False):
    """The last layer's output [T, h], before the final norm, for one
    sequence `ids` [T]. `params`: the program's parameters AND buffers by
    name; `config`: the configuration's dict (the source's own keys); `held`
    = (first, count) of the routed experts the parameters hold, default all
    of `num_experts`."""
    h_size = int(config["hidden_size"])
    scale = float(h_size) ** 0.5 if config.get("mup_enabled") else 1.0
    x = _embed(jnp.asarray(ids, jnp.int32), params["embed_tokens.weight"],
               scale)
    kinds = config["layer_types"][:config["num_hidden_layers"]]
    for i, kind in enumerate(kinds):
        sizes = tuple(sorted({
            "heads": int(config["num_attention_heads"]),
            "kv_heads": int(config["num_key_value_heads"]),
            "head_dim": int(config["head_dim"]),
            "eps": float(config["rms_norm_eps"]),
            "theta": float(config["rope_theta"]),
            "window": int(config["sliding_window"]),
            "sliding": kind == "sliding_attention",
            "dense": i < int(config["num_dense_layers"]),
            "top_k": int(config["num_experts_per_tok"]),
            "scale": float(config["route_scale"]),
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
