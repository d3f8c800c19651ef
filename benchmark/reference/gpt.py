"""Plain reference of the GPT-3 decoder (Brown et al. 2020, section 2.1:
the GPT-2 architecture, pre-norm blocks, learned positions, tied output head)
in float32 `jax.numpy`: no kernels, no cache, no batching tricks, every
matmul at `highest` precision (a float32 matmul on a TPU otherwise runs in
bf16 passes).

It reads the parameters the program holds, by the names `models/gpt.py`
gives them, cast to float32 one layer at a time, and shares no code with the
program. Linear weights are stored [in, out]. One jitted function per piece
(embed, one block, head), each of one shape per sequence length, so a
24-layer check compiles three small programs and not a 24-layer one.

Departures from the paper: none in the mathematics. GPT-3 alternates dense
and locally banded sparse attention; like the program (and every open
re-implementation) this is dense causal attention in every layer.
"""

import functools

import jax
import jax.numpy as jnp

HIGHEST = jax.lax.Precision.HIGHEST
EPS = 1e-5


def _f32(x):
    return jnp.asarray(x).astype(jnp.float32)


def _layer_norm(x, w, b):
    mean = x.mean(-1, keepdims=True)
    var = ((x - mean) ** 2).mean(-1, keepdims=True)
    return (x - mean) / jnp.sqrt(var + EPS) * _f32(w) + _f32(b)


def _linear(x, w, b):
    return jnp.matmul(x, _f32(w), precision=HIGHEST) + _f32(b)


@jax.jit
def _embed(ids, wte, wpe):
    pos = jnp.arange(ids.shape[1])
    return _f32(wte)[ids] + _f32(wpe)[pos][None]


@functools.partial(jax.jit, static_argnames="num_heads")
def _block(x, p, num_heads):
    B, S, h = x.shape
    d = h // num_heads
    a = _layer_norm(x, p["input_layernorm.weight"], p["input_layernorm.bias"])

    def heads(name):
        y = _linear(a, p[f"self_attn.{name}.weight"], p[f"self_attn.{name}.bias"])
        return y.reshape(B, S, num_heads, d).transpose(0, 2, 1, 3)

    q, k, v = heads("q_proj"), heads("k_proj"), heads("v_proj")
    scores = jnp.einsum("bhqd,bhkd->bhqk", q, k, precision=HIGHEST) / jnp.sqrt(
        jnp.float32(d))
    causal = jnp.tril(jnp.ones((S, S), bool))
    scores = jnp.where(causal, scores, -jnp.inf)
    att = jnp.einsum("bhqk,bhkd->bhqd", jax.nn.softmax(scores, axis=-1), v,
                     precision=HIGHEST)
    att = att.transpose(0, 2, 1, 3).reshape(B, S, h)
    x = x + _linear(att, p["self_attn.out_proj.weight"],
                    p["self_attn.out_proj.bias"])
    m = _layer_norm(x, p["post_attention_layernorm.weight"],
                    p["post_attention_layernorm.bias"])
    m = jax.nn.gelu(_linear(m, p["mlp.fc1.weight"], p["mlp.fc1.bias"]),
                    approximate=False)
    return x + _linear(m, p["mlp.fc2.weight"], p["mlp.fc2.bias"])


@jax.jit
def _head(x, norm_w, norm_b, wte):
    return jnp.matmul(_layer_norm(x, norm_w, norm_b), _f32(wte).T,
                      precision=HIGHEST)


def hidden(params, ids, num_layers, num_heads):
    """Final hidden states [B, S, h] (before the last norm) of token ids
    [B, S]; `params` maps the program's parameter names to arrays."""
    x = _embed(jnp.asarray(ids), params["gpt.embed_tokens.weight"],
               params["gpt.embed_positions.weight"])
    for i in range(num_layers):
        prefix = f"gpt.layers.{i}."
        layer = {k[len(prefix):]: v for k, v in params.items()
                 if k.startswith(prefix)}
        x = _block(x, layer, num_heads=num_heads)
    return x


def logits(params, ids, num_layers, num_heads, rows=None):
    """Logits [B, S, V], or only for the positions `rows` (a slice) — the
    vocabulary is wide, so a check that needs a few rows asks for those."""
    x = hidden(params, ids, num_layers, num_heads)
    if rows is not None:
        x = x[:, rows]
    return _head(x, params["gpt.final_norm.weight"],
                 params["gpt.final_norm.bias"],
                 params["gpt.embed_tokens.weight"])


def loss(params, ids, labels, num_layers, num_heads):
    """Mean cross entropy of logits[b, s] against labels[b, s] (the
    program's criterion applies no shift: the caller supplies the labels)."""
    lg = logits(params, ids, num_layers, num_heads)
    logp = jax.nn.log_softmax(lg, axis=-1)
    picked = jnp.take_along_axis(logp, jnp.asarray(labels)[..., None], axis=-1)
    return -picked.mean()
