"""Operations and bytes the `mimo_v2` family's work REQUIRES, from sizes
alone (the configuration's dict, the source's own keys): what the roofline
and the whole-window readers of the family's cell divide by. Every count is
of the published arithmetic, whatever the program stores, pads or recomputes
(its pool keeps a 192-wide key in 256 lanes: the required bytes are 192's),
so that a later change of layout reads against the same work.

A layer's attention is full (`hybrid_layer_pattern` 0: `num_key_value_heads`
KV heads, the whole context) or sliding (1: `swa_num_key_value_heads`, the
last `sliding_window` keys); keys and queries are `head_dim` wide, values
`v_head_dim`. A layer's MLP is dense (`moe_layer_freq` 0) or routed experts
of which this chip holds `n_routed_experts` of the published number."""

from benchmark.costs_afmoe import band_pairs, causal_pairs


def layer_kinds(config):
    """(sliding, routed) per layer built."""
    n = config["num_hidden_layers"]
    return list(zip(config["hybrid_layer_pattern"][:n],
                    config["moe_layer_freq"][:n]))


def layers_of(config, sliding):
    return sum(1 for s, _ in layer_kinds(config) if bool(s) == bool(sliding))


def kv_heads(config, sliding):
    return config["swa_num_key_value_heads" if sliding
                  else "num_key_value_heads"]


def kv_bytes_per_token_layer(config, sliding, itemsize=2):
    """Bytes one cached token's key and value take in one layer."""
    return (kv_heads(config, sliding)
            * (config["head_dim"] + config["v_head_dim"]) * itemsize)


def decode_full_bytes(config, context_tokens, itemsize=2):
    """Bytes the full layers' decode kernel must read: the decoded rows'
    whole contexts, in every full layer."""
    return (context_tokens * layers_of(config, False)
            * kv_bytes_per_token_layer(config, False, itemsize))


def decode_window_bytes(config, window_tokens, itemsize=2):
    """Bytes the sliding layers' decode kernel must read: `window_tokens`,
    the decoded rows' min(context, window) summed, in every sliding layer."""
    return (window_tokens * layers_of(config, True)
            * kv_bytes_per_token_layer(config, True, itemsize))


def pair_flops(config):
    """FLOP one (query, key) pair costs over all heads: q.k over `head_dim`
    and p.v over `v_head_dim`, 2 FLOP a head value each."""
    return (2 * config["num_attention_heads"]
            * (config["head_dim"] + config["v_head_dim"]))


def window_prefill_flops(config, prompt_lens):
    """FLOP the sliding layers' prefill kernel must do for these prompts:
    the band's pairs, every head, every sliding layer."""
    w = config["sliding_window"]
    return (sum(band_pairs(n, w) for n in prompt_lens) * pair_flops(config)
            * layers_of(config, True))


def attention_params(config, sliding):
    """Matrix parameters of one layer's attention: q, k, v and o."""
    h, heads = config["hidden_size"], config["num_attention_heads"]
    kv = kv_heads(config, sliding)
    return h * (heads * config["head_dim"] + kv * config["head_dim"]
                + kv * config["v_head_dim"]
                + heads * config["v_head_dim"])


def expert_params(config):
    return 3 * config["hidden_size"] * config["moe_intermediate_size"]


def matmul_params_per_token(config, head=True):
    """Parameters that sit in a matrix multiplication one token passes
    through on this chip: each layer's attention; a dense layer's MLP; an
    expert layer's router and the routed experts it meets HERE (experts per
    token x held / published: 0.25 of 8 picks at 8 of 256); with `head` the
    untied head (a prompt's tokens but the last do not pass it: the
    prefill's head runs on one row). Norms, sinks and the embedding look-up
    do no matmul."""
    h = config["hidden_size"]
    published = config["published"]["n_routed_experts"]
    met = (config["num_experts_per_tok"] * config["n_routed_experts"]
           / published)
    total = 0
    for sliding, routed in layer_kinds(config):
        total += attention_params(config, sliding)
        total += (h * published + met * expert_params(config) if routed
                  else 3 * h * config["intermediate_size"])
    return total + (config["vocab_size"] * h if head else 0)


def decode_flops_per_token(config, context, window_context):
    """FLOP one decoded token REQUIRES with `context` tokens before it
    (itself included), `window_context` = min(context, sliding_window) of
    them inside the window."""
    attention = pair_flops(config) * (
        layers_of(config, False) * context
        + layers_of(config, True) * window_context)
    return 2 * matmul_params_per_token(config) + attention


def prompt_flops(config, n):
    """FLOP the admission of an n-token prompt REQUIRES: every token through
    the layers, attention's causal pairs (full layers) and band pairs
    (sliding layers), the head once."""
    attention = pair_flops(config) * (
        layers_of(config, False) * causal_pairs(n)
        + layers_of(config, True) * band_pairs(n, config["sliding_window"]))
    return (2 * n * matmul_params_per_token(config, head=False)
            + 2 * config["vocab_size"] * config["hidden_size"] + attention)


def mean_window_context(config, prompts, answers, max_total):
    """Mean over the decoded tokens of a mix (every prompt level with every
    answer level, each token of each answer) of min(context,
    sliding_window): what a decoded token's sliding layers must attend to,
    from the mix's own sizes."""
    w = config["sliding_window"]
    total = count = 0
    for p in prompts:
        for a in answers:
            a = min(a, max_total - p)
            # token t of the answer (t = 1 .. a - 1 decoded; the first comes
            # from the prefill) has context p + t: past the window from
            # t = w - p on
            inside = max(0, min(a - 1, w - p - 1))   # decoded with p + t < w
            total += inside * p + inside * (inside + 1) // 2
            total += (max(a - 1, 0) - inside) * w
            count += max(a - 1, 0)
    return total / count if count else 0.0


def window_flops(config, decoded_tokens, decode_context, window_context,
                 admissions, prompts):
    """FLOP a serving window requires: its decoded tokens at their mean
    context, plus its admissions x the mean over the mix's prompt levels."""
    decode = decoded_tokens * decode_flops_per_token(
        config, decode_context, window_context)
    prompt = admissions * sum(prompt_flops(config, n)
                              for n in prompts) / len(prompts)
    return decode + prompt
