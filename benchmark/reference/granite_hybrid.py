"""Plain reference of the `granitemoehybrid` decoder (Granite 4.0-H: Mamba-2
layers beside attention layers, routed experts plus a shared expert in every
layer) in float32 `jax.numpy`: no kernels, no cache, no chunking, every
matmul at `highest` precision. It reads the parameters the program holds, by
the names `models/granite_hybrid.py` gives them, cast to float32 a layer at a
time, and shares no code and no method with the program: the recurrence is a
sequential `lax.scan` over tokens (the program's prefill is chunked), the
conv is a window rolled through that scan, the experts are a loop over the
held experts with a dense mask (the program sorts rows into a grouped GEMM).
One jitted function per layer kind. Linear weights are stored [in, out].

The equations (h = hidden size; RMSNorm(x) = x / sqrt(mean(x^2) + eps) * w,
eps 1e-5; no bias anywhere but the conv's; no positions of any kind):

    x_0    = E[ids] * embedding_multiplier
    x      = x + r * mixer_i(norm1_i(x))              r = residual_multiplier
    u      = norm2_i(x)
    x      = x + r * (moe_i(u) + shared_i(u))
    logits = (norm_f(x) @ E^T) / logits_scaling       (tied head)

Attention mixer: q (H heads), k, v (Hkv heads), head width D = h / H;
out = o_proj(softmax_causal(q k^T * attention_multiplier) v): the scale is
the multiplier, NOT 1/sqrt(D), and nothing is rotated.

Mamba-2 mixer (H heads of width P, d = H P, state width N, one group, conv
width K):

    [z | xBC | dt] = in_proj(u)                widths d, d + 2N, H
    xBC_t  = silu(sum_k w[:, k] * xBC_{t-K+1+k} + conv_bias)   (causal,
             depthwise; positions before the sequence are 0)
    [x | B | C] = xBC                          widths d, N, N
    dt_t   = softplus(dt_t + dt_bias)          per head
    A      = -exp(A_log)                       per head
    S_t    = exp(dt_t A) S_{t-1} + dt_t x_t (outer) B_t    per head, [P, N]
    y_t    = S_t C_t + D x_t
    out    = out_proj(RMSNorm(y * silu(z)) * w)            over all d channels

Routed experts: logits_r = u @ W_r (over ALL experts); the top k by logit;
gates = softmax over those k logits only; expert e(u) = W_out_e (silu(a) * b)
with [a | b] = W_in_e u. moe(u) = sum over the picked experts HELD HERE of
gate_e * e(u): `held` = (first, count) names them, and what the others would
add is left out, as in the program (`model-configs` guide, section 4). The
shared expert is the same gated form, always on.

Departures from the published description: none in the mathematics. The
public config has no key of its own for one expert's width; like the
configuration file, this reads `intermediate_size` as that width (`w_in` is
[count, h, 2 * width]).

`lower_precision=True` is the yardstick's second reading (PERF.md): the same
forward with every operand of every matrix product and every stored value of
the recurrent state rounded to the 3 mantissa bits of an 8-bit float (e4m3's
precision at any exponent, so that nothing under- or overflows): the nearest
precision below the bf16 the configuration states. A comparison that such a
forward passes is too loose.
"""

import functools

import jax
import jax.numpy as jnp

HIGHEST = jax.lax.Precision.HIGHEST
EPS = 1e-5


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


def _rms_norm(x, w):
    return x / jnp.sqrt((x * x).mean(-1, keepdims=True) + EPS) * _f32(w)


def _gated(u, w_in, w_out, low=False):
    ab = _mm(u, w_in, low)
    f = ab.shape[-1] // 2
    return _mm(jax.nn.silu(ab[..., :f]) * ab[..., f:], w_out, low)


def _experts(u, p, top_k, first, low):
    """moe(u) + shared(u) for u [T, h]."""
    logits = _mm(u, p["moe.router"], low)
    top_logit, top_expert = jax.lax.top_k(logits, top_k)
    gates = jax.nn.softmax(top_logit, axis=-1)

    def one(total, held):
        index, w_in, w_out = held
        gate = jnp.where(top_expert == index + first, gates, 0.0).sum(-1)
        return total + gate[:, None] * _gated(u, w_in, w_out, low), None

    count = p["moe.w_in"].shape[0]
    routed, _ = jax.lax.scan(
        one, jnp.zeros_like(u),
        (jnp.arange(count), _f32(p["moe.w_in"]), _f32(p["moe.w_out"])))
    return routed + _gated(u, p["shared_mlp.input_linear.weight"],
                           p["shared_mlp.output_linear.weight"], low)


def _mamba(u, p, n_heads, d_state, low):
    """The mixer for one sequence u [T, h], token by token."""
    T = u.shape[0]
    w = _f32(p["mixer.conv_weight"])                         # [C, K]
    C, K = w.shape
    d = C - 2 * d_state
    P = d // n_heads
    proj = _mm(u, p["mixer.in_proj.weight"], low)
    z, xbc, dt = proj[:, :d], proj[:, d:d + C], proj[:, d + C:d + C + n_heads]
    dt = jax.nn.softplus(dt + _f32(p["mixer.dt_bias"]))
    a = -jnp.exp(_f32(p["mixer.A_log"]))
    skip = _f32(p["mixer.D"])
    bias = _f32(p["mixer.conv_bias"])

    def token(carry, inp):
        window, state = carry                  # [K, C] oldest first; [H,P,N]
        row, dt_t = inp
        window = jnp.concatenate([window[1:], row[None]], axis=0)
        act = jax.nn.silu((window * w.T).sum(0) + bias)
        x = act[:d].reshape(n_heads, P)
        b_t, c_t = act[d:d + d_state], act[d + d_state:]
        state = (jnp.exp(dt_t * a)[:, None, None] * state
                 + (dt_t[:, None] * x)[:, :, None] * b_t[None, None, :])
        if low:
            state = _round8(state)
        y = (state * c_t[None, None, :]).sum(-1) + skip[:, None] * x
        return (window, state), y.reshape(d)

    start = (jnp.zeros((K, C), jnp.float32),
             jnp.zeros((n_heads, P, d_state), jnp.float32))
    _, y = jax.lax.scan(token, start, (xbc, dt))
    return _mm(_rms_norm(y * jax.nn.silu(z), p["mixer.norm.weight"]),
               p["mixer.out_proj.weight"], low)


def _attention(u, p, n_heads, n_kv, scale, low):
    T, h = u.shape
    D = h // n_heads
    q = _mm(u, p["mixer.q_proj.weight"], low).reshape(T, n_heads, D)
    k = _mm(u, p["mixer.k_proj.weight"], low).reshape(T, n_kv, D)
    v = _mm(u, p["mixer.v_proj.weight"], low).reshape(T, n_kv, D)
    if low:
        q, k, v = _round8(q), _round8(k), _round8(v)
    k = jnp.repeat(k, n_heads // n_kv, axis=1)
    v = jnp.repeat(v, n_heads // n_kv, axis=1)
    scores = jnp.einsum("qhd,khd->hqk", q, k, precision=HIGHEST) * scale
    scores = jnp.where(jnp.tril(jnp.ones((T, T), bool)), scores, -jnp.inf)
    out = jnp.einsum("hqk,khd->qhd", jax.nn.softmax(scores, axis=-1), v,
                     precision=HIGHEST)
    return _mm(out.reshape(T, h), p["mixer.o_proj.weight"], low)


def _layer(x, p, mixed, sizes):
    r = sizes["residual_multiplier"]
    x = x + r * mixed
    u = _rms_norm(x, p["post_attention_layernorm.weight"])
    return x + r * _experts(u, p, sizes["top_k"], sizes["first"],
                            sizes["lower_precision"])


@functools.partial(jax.jit, static_argnames=("sizes",))
def _mamba_layer(x, p, sizes):
    s = dict(sizes)
    u = _rms_norm(x, p["input_layernorm.weight"])
    return _layer(x, p, _mamba(u, p, s["mamba_n_heads"], s["mamba_d_state"],
                               s["lower_precision"]), s)


@functools.partial(jax.jit, static_argnames=("sizes",))
def _attention_layer(x, p, sizes):
    s = dict(sizes)
    u = _rms_norm(x, p["input_layernorm.weight"])
    return _layer(x, p, _attention(u, p, s["num_attention_heads"],
                                   s["num_key_value_heads"],
                                   s["attention_multiplier"],
                                   s["lower_precision"]), s)


@functools.partial(jax.jit, static_argnames=("multiplier",))
def _embed(ids, table, multiplier):
    return _f32(table)[ids] * multiplier


@functools.partial(jax.jit, static_argnames=("scaling", "low"))
def _head(x, norm_w, table, scaling, low=False):
    return _mm(_rms_norm(x, norm_w), _f32(table).T, low) / scaling


def hidden(params, ids, config, held=None, lower_precision=False):
    """The last layer's output [T, h], before the final norm, for one
    sequence `ids` [T]. `config`: the configuration's dict (the source's own
    keys); `held` = (first, count) of the routed experts the parameters hold,
    default all of `num_local_experts`."""
    first = 0 if held is None else int(held[0])
    sizes = tuple(sorted({
        "residual_multiplier": float(config["residual_multiplier"]),
        "attention_multiplier": float(config["attention_multiplier"]),
        "top_k": int(config["num_experts_per_tok"]),
        "mamba_n_heads": int(config["mamba_n_heads"]),
        "mamba_d_state": int(config["mamba_d_state"]),
        "num_attention_heads": int(config["num_attention_heads"]),
        "num_key_value_heads": int(config["num_key_value_heads"]),
        "first": first, "lower_precision": bool(lower_precision),
    }.items()))
    x = _embed(jnp.asarray(ids, jnp.int32), params["embed_tokens.weight"],
               float(config["embedding_multiplier"]))
    kinds = config["layer_types"][:config["num_hidden_layers"]]
    for i, kind in enumerate(kinds):
        prefix = f"layers.{i}."
        p = {k[len(prefix):]: v for k, v in params.items()
             if k.startswith(prefix)}
        x = (_mamba_layer if kind == "mamba" else _attention_layer)(
            x, p, sizes)
    return x


def logits(params, ids, config, held=None, rows=None, lower_precision=False):
    """float32 logits [T, vocab] of one sequence (or of its positions
    `rows` only: the head over a whole long sequence is the largest array of
    the forward)."""
    x = hidden(params, ids, config, held, lower_precision)
    if rows is not None:
        x = x[jnp.asarray(rows)]
    return _head(x, params["norm.weight"], params["embed_tokens.weight"],
                 float(config["logits_scaling"]), bool(lower_precision))
