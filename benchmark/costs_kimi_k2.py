"""Operations and bytes that a `kimi_k2` configuration's work requires,
computed from sizes alone (`costs.py` does the same for GPT, `costs_hybrid.py`
for Granite, `costs_afmoe.py` for Trinity). `config` is the dict of a
`configs/<name>.json` file: the source's own keys, with `num_hidden_layers`
the layers built and `n_routed_experts` the routed experts HELD here
(`published` has the counts of the whole model).

These are the yardstick's own: what a roofline share or a utilisation
divides by is fixed here and not in the program under test.
"""


def attention_params(config):
    """q_a, q_b, kv_a (latent and shared rotated key), kv_b (per head the
    key's unrotated part and the value) and o."""
    h, H = config["hidden_size"], config["num_attention_heads"]
    N, R = config["qk_nope_head_dim"], config["qk_rope_head_dim"]
    V, L, Q = (config["v_head_dim"], config["kv_lora_rank"],
               config["q_lora_rank"])
    return h * Q + Q * H * (N + R) + h * (L + R) + L * H * (N + V) + H * V * h


def expert_params(config):
    """Parameters of ONE routed (or shared) expert: gate, up and down."""
    return 3 * config["hidden_size"] * config["moe_intermediate_size"]


def dense_mlp_params(config):
    return 3 * config["hidden_size"] * config["intermediate_size"]


def expert_layers(config):
    return max(config["num_hidden_layers"] - config["first_k_dense_replace"],
               0)


def grouped_gemm_weight_bytes(config, itemsize=2):
    """(in, out): bytes of the held experts' matrix each of a layer's two
    `grouped_gemm` calls multiplies, whole."""
    h, f = config["hidden_size"], config["moe_intermediate_size"]
    held = config["n_routed_experts"]
    return held * h * 2 * f * itemsize, held * f * h * itemsize


def latent_bytes_per_token_layer(config, itemsize=2):
    """Bytes one cached token REQUIRES in ONE layer: its latent and its one
    rotated key (576 values), whatever padding the stored row has."""
    return (config["kv_lora_rank"] + config["qk_rope_head_dim"]) * itemsize


def latent_flops_per_token_layer(config):
    """FLOP one decode step's attention costs per cached token and layer in
    the absorbed form, all heads: the score over latent and rotated key, the
    weighted sum over the latent; 2 FLOP a value."""
    L, R = config["kv_lora_rank"], config["qk_rope_head_dim"]
    return 2 * config["num_attention_heads"] * ((L + R) + L)


def decode_latent_bytes(config, latent_tokens, itemsize=2):
    """Bytes `decode_latent` must read: `latent_tokens` = the decoded rows'
    whole contexts, in every layer."""
    return (latent_tokens * config["num_hidden_layers"]
            * latent_bytes_per_token_layer(config, itemsize))


def decode_latent_flops(config, latent_tokens):
    return (latent_tokens * config["num_hidden_layers"]
            * latent_flops_per_token_layer(config))


def causal_pairs(n):
    """(query, key) pairs of a causal prompt of n tokens."""
    return n * (n + 1) // 2


def prefill_pair_flops(config):
    """FLOP one (query, key) pair costs in the expanded form, all heads: q.k
    over the 192-wide heads and p.v over the 128-wide values, 2 FLOP a
    value."""
    return 2 * config["num_attention_heads"] * (
        config["qk_nope_head_dim"] + config["qk_rope_head_dim"]
        + config["v_head_dim"])


def mla_prefill_flops(config, prompt_lens):
    """FLOP the prefill's attention kernel must do for these prompts: the
    causal pairs, every head, every layer."""
    return (sum(causal_pairs(n) for n in prompt_lens)
            * prefill_pair_flops(config) * config["num_hidden_layers"])


def matmul_params_per_token(config, head=True):
    """Parameters that sit in a matrix multiplication one token passes
    through on this chip: each layer's attention (the absorbed form
    multiplies as many values as the expanded one: `kv_b`'s halves against
    the query and the attended latent); the leading dense layers' MLP; an
    expert layer's router, shared expert and the routed experts it meets
    HERE (experts per token x held / published: 0.25 of 8 picks at 12 of
    384); with `head` the untied head (a prompt's tokens but the last do
    not pass it). Norms and the embedding look-up do no matmul."""
    h = config["hidden_size"]
    layers = config["num_hidden_layers"]
    dense = min(config["first_k_dense_replace"], layers)
    published = config["published"]["n_routed_experts"]
    met = (config["num_experts_per_tok"] * config["n_routed_experts"]
           / published)
    expert_layer = (h * published
                    + config["n_shared_experts"] * expert_params(config)
                    + met * expert_params(config))
    total = (layers * attention_params(config)
             + dense * dense_mlp_params(config)
             + (layers - dense) * expert_layer)
    return total + (config["vocab_size"] * h if head else 0)


def decode_flops_per_token(config, context):
    """FLOP one decoded token REQUIRES with `context` tokens cached (itself
    included)."""
    return (2 * matmul_params_per_token(config)
            + latent_flops_per_token_layer(config)
            * config["num_hidden_layers"] * context)


def prompt_flops(config, n):
    """FLOP the admission of an n-token prompt REQUIRES: every token through
    the layers, attention's causal pairs in the expanded form, the head
    once."""
    return (2 * n * matmul_params_per_token(config, head=False)
            + 2 * config["vocab_size"] * config["hidden_size"]
            + prefill_pair_flops(config) * config["num_hidden_layers"]
            * causal_pairs(n))


def window_flops(config, decoded_tokens, decode_context, admissions, prompts):
    """FLOP a serving window requires: its decoded tokens at their mean
    context, plus its admissions x the mean over the mix's prompt levels."""
    decode = decoded_tokens * decode_flops_per_token(config, decode_context)
    prompt = admissions * sum(prompt_flops(config, n)
                              for n in prompts) / len(prompts)
    return decode + prompt
