"""Operations and bytes that a `granite_hybrid` configuration's work
requires, computed from sizes alone (`costs.py` does the same for GPT).
`config` is the dict of a `configs/<name>.json` file: the source's own keys,
with `num_hidden_layers` the layers built and `num_local_experts` the routed
experts HELD here (`published` has the counts of the whole model).

These are the yardstick's own: what a roofline share or a utilisation
divides by is fixed here and not in the program under test.
"""

import math


def _kinds(config):
    return config["layer_types"][:config["num_hidden_layers"]]


def _mamba_sizes(config):
    heads, p = config["mamba_n_heads"], config["mamba_d_head"]
    return heads, p, config["mamba_d_state"], heads * p


def expert_params(config):
    """Parameters of ONE routed expert: [a | b] = W_in x (2 f wide), then
    W_out (f -> h)."""
    h, f = config["hidden_size"], config["intermediate_size"]
    return h * 2 * f + f * h


def held_expert_weight_bytes_per_layer(config, itemsize=2):
    """Bytes of the routed experts' two stacked matrices one layer holds
    here: what a decode tick must read of them once every held expert is
    hit."""
    return sum(grouped_gemm_weight_bytes(config, itemsize))


def grouped_gemm_weight_bytes(config, itemsize=2):
    """(in, out): bytes of the held experts' matrix each of a layer's two
    `grouped_gemm` calls multiplies, whole."""
    h, f = config["hidden_size"], config["intermediate_size"]
    held = config["num_local_experts"]
    return held * h * 2 * f * itemsize, held * f * h * itemsize


def ssm_state_bytes_per_row(config, itemsize=2):
    """Bytes of ONE Mamba layer's SSM state of one request: heads x head
    width x state width."""
    heads, p, n, _ = _mamba_sizes(config)
    return heads * p * n * itemsize


def ssm_decode_bytes(config, decoded_rows, itemsize=2):
    """Bytes the decode-time state update must move for `decoded_rows` row
    steps: every Mamba layer reads and writes the row's whole state. The
    per-row vectors (a, u, B, C, y) are under 4 % beside it and are left
    out, so a roofline share built on this is a little low, never high."""
    layers = sum(kind == "mamba" for kind in _kinds(config))
    return 2 * ssm_state_bytes_per_row(config, itemsize) * layers * decoded_rows


def kv_bytes_per_token(config, itemsize=2):
    """Bytes of K and V one token of context holds over the attention
    layers built (grouped-query: the KV heads, not the query heads)."""
    layers = sum(kind == "attention" for kind in _kinds(config))
    d_head = config["hidden_size"] // config["num_attention_heads"]
    return 2 * layers * config["num_key_value_heads"] * d_head * itemsize


def matmul_params_per_token(config):
    """Parameters that sit in a matrix multiplication one token passes
    through on this chip: each layer's mixer, router, shared expert and the
    routed experts it meets HERE (experts per token x held / published: 5 of
    the 10 picks at 36 of 72), plus the tied head. Norms, the conv and the
    embedding look-up do no matmul."""
    h = config["hidden_size"]
    heads, p, n, d = _mamba_sizes(config)
    mamba = h * (2 * d + 2 * n + heads) + d * h
    d_head = h // config["num_attention_heads"]
    kv = config["num_key_value_heads"] * d_head
    attention = h * (h + 2 * kv) + h * h
    published = config["published"]["num_local_experts"]
    met = (config["num_experts_per_tok"] * config["num_local_experts"]
           / published)
    shared = 3 * h * config["shared_intermediate_size"]
    common = h * published + shared + met * expert_params(config)
    total = sum((mamba if kind == "mamba" else attention) + common
                for kind in _kinds(config))
    return total + config["vocab_size"] * h


def flops_per_token(config, context):
    """FLOP one token REQUIRES on this chip with `context` tokens before it
    (itself included): 2 per matmul parameter it passes through, plus per
    Mamba layer the recurrence's own (5 per state value: decay, outer
    product, add, and the multiply-add of y = S C; 2 K per conv channel) and
    per attention layer 4 per head value and context token (q.k and p.v)."""
    heads, p, n, d = _mamba_sizes(config)
    kinds = _kinds(config)
    scan = 5 * heads * p * n + 2 * config["mamba_d_conv"] * (d + 2 * n)
    attention = 4 * config["hidden_size"] * context
    own = sum(scan if kind == "mamba" else attention for kind in kinds)
    return 2 * matmul_params_per_token(config) + own


def window_flops(config, decoded_tokens, decode_context, admissions,
                 mean_prompt):
    """FLOP a serving window requires: its decoded tokens at their mean
    context, plus its admissions' prompts (a prompt token's mean context is
    half the prompt)."""
    decode = decoded_tokens * flops_per_token(config, decode_context)
    prompt = admissions * mean_prompt * flops_per_token(
        config, math.ceil(mean_prompt / 2))
    return decode + prompt
