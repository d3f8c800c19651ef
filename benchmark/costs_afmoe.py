"""Operations and bytes that an `afmoe` configuration's work requires,
computed from sizes alone (`costs.py` does the same for GPT, `costs_hybrid.py`
for Granite). `config` is the dict of a `configs/<name>.json` file: the
source's own keys, with `num_hidden_layers` the layers built and
`num_experts` the routed experts HELD here (`published` has the counts of
the whole model).

These are the yardstick's own: what a roofline share or a utilisation
divides by is fixed here and not in the program under test.
"""


def _kinds(config):
    return config["layer_types"][:config["num_hidden_layers"]]


def layers_of(config, kind):
    return sum(k == kind for k in _kinds(config))


def attention_params(config):
    """q, gate and o (hidden x heads x head_dim each) and k, v (hidden x KV
    heads x head_dim each)."""
    h, d = config["hidden_size"], config["head_dim"]
    return h * d * (3 * config["num_attention_heads"]
                    + 2 * config["num_key_value_heads"])


def expert_params(config):
    """Parameters of ONE routed (or shared) expert: gate, up and down."""
    return 3 * config["hidden_size"] * config["moe_intermediate_size"]


def dense_mlp_params(config):
    return 3 * config["hidden_size"] * config["intermediate_size"]


def grouped_gemm_weight_bytes(config, itemsize=2):
    """(in, out): bytes of the held experts' matrix each of a layer's two
    `grouped_gemm` calls multiplies, whole."""
    h, f = config["hidden_size"], config["moe_intermediate_size"]
    held = config["num_experts"]
    return held * h * 2 * f * itemsize, held * f * h * itemsize


def kv_bytes_per_token_layer(config, itemsize=2):
    """Bytes of K and V one cached token holds in ONE layer (grouped-query:
    the KV heads)."""
    return 2 * config["num_key_value_heads"] * config["head_dim"] * itemsize


def decode_window_bytes(config, window_tokens, itemsize=2):
    """Bytes the sliding layers' decode kernel must read: `window_tokens` =
    sum over the decoded rows of min(context, sliding_window), in every
    sliding layer."""
    return (window_tokens * layers_of(config, "sliding_attention")
            * kv_bytes_per_token_layer(config, itemsize))


def decode_full_bytes(config, context_tokens, itemsize=2):
    """Bytes the full layers' decode kernel must read: the decoded rows'
    whole contexts, in every full layer."""
    return (context_tokens * layers_of(config, "full_attention")
            * kv_bytes_per_token_layer(config, itemsize))


def causal_pairs(n):
    """(query, key) pairs of a causal prompt of n tokens."""
    return n * (n + 1) // 2


def band_pairs(n, window):
    """(query, key) pairs of a causal prompt of n tokens under a window:
    query i sees min(i + 1, window) keys."""
    if n <= window:
        return causal_pairs(n)
    return causal_pairs(window) + (n - window) * window


def pair_flops(config):
    """FLOP one (query, key) pair costs over all heads: q.k and p.v, 2 FLOP
    a head value each."""
    return 4 * config["num_attention_heads"] * config["head_dim"]


def window_prefill_flops(config, prompt_lens):
    """FLOP the sliding layers' prefill kernel must do for these prompts:
    the band's pairs, every head, every sliding layer."""
    w = config["sliding_window"]
    return (sum(band_pairs(n, w) for n in prompt_lens) * pair_flops(config)
            * layers_of(config, "sliding_attention"))


def matmul_params_per_token(config, head=True):
    """Parameters that sit in a matrix multiplication one token passes
    through on this chip: each layer's attention; a leading dense layer's
    MLP; an expert layer's router, shared expert and the routed experts it
    meets HERE (experts per token x held / published: 4 of 8 picks at 64 of
    128); with `head` the untied head (a prompt's tokens but the last do
    not pass it: the prefill's head runs on one row). Norms and the
    embedding look-up do no matmul."""
    h = config["hidden_size"]
    layers = config["num_hidden_layers"]
    dense = min(config["num_dense_layers"], layers)
    published = config["published"]["num_experts"]
    met = config["num_experts_per_tok"] * config["num_experts"] / published
    expert_layer = (h * published
                    + config["num_shared_experts"] * expert_params(config)
                    + met * expert_params(config))
    total = (layers * attention_params(config)
             + dense * dense_mlp_params(config)
             + (layers - dense) * expert_layer)
    return total + (config["vocab_size"] * h if head else 0)


def decode_flops_per_token(config, context, window_context):
    """FLOP one decoded token REQUIRES with `context` tokens before it
    (itself included), `window_context` = min(context, sliding_window) of
    them inside the window."""
    attention = pair_flops(config) * (
        layers_of(config, "full_attention") * context
        + layers_of(config, "sliding_attention") * window_context)
    return 2 * matmul_params_per_token(config) + attention


def prompt_flops(config, n):
    """FLOP the admission of an n-token prompt REQUIRES: every token through
    the layers, attention's causal pairs (full layers) and band pairs
    (sliding layers), the head once."""
    attention = pair_flops(config) * (
        layers_of(config, "full_attention") * causal_pairs(n)
        + layers_of(config, "sliding_attention")
        * band_pairs(n, config["sliding_window"]))
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
            # from the prefill) has context p + t
            for t in range(1, a):
                total += min(p + t, w)
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
