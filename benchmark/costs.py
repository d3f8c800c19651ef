"""Operations and bytes that the work requires, computed from sizes alone.

These are the yardstick's own: what a roofline share or an MFU divides by is
fixed here and not in the program under test. `config` is the dict of a
`configs/<name>.json` file.
"""


def matmul_params(config):
    """Parameters that sit in a matrix multiplication of the forward pass:
    per layer q, k, v, out (4 h^2) and the two MLP matrices (2 h f), plus the
    tied output head (V h). Embedding look-ups, biases and norms do no
    matmul and are not counted."""
    h, f = config["hidden_size"], config["intermediate_size"]
    per_layer = 4 * h * h + 2 * h * f
    return config["num_layers"] * per_layer + config["vocab_size"] * h


def train_flops_per_token(config, seq):
    """FLOP that one token of a causal LM training step REQUIRES, forward
    plus backward (3x the forward), at sequence length `seq`.

    Matmuls: 2 FLOP per parameter per token forward. Attention: a token at
    position t attends to t+1 keys, so over a sequence the mean context is
    (seq+1)/2 and QK^T plus AV cost 2 * 2 * h * (seq+1)/2 per layer — the
    causal half is counted once, not the full seq^2 square. Recomputation in
    the backward pass is the program's choice and is not counted."""
    h = config["hidden_size"]
    attention = config["num_layers"] * 2 * h * (seq + 1)
    return 3 * (2 * matmul_params(config) + attention)


def kv_bytes_per_token(config, itemsize=2):
    """Bytes of K and V that ONE token of context holds over all layers
    (multi-head attention: kv heads = heads)."""
    return (2 * config["num_layers"] * config["num_heads"]
            * config["head_dim"] * itemsize)


def decode_attention_bytes(config, context_tokens, itemsize=2):
    """Bytes of K and V a decode tick must read: every live row's attention
    reads its whole context once per layer. `context_tokens` is the sum of
    the live rows' context lengths in that tick. Queries, outputs and the
    block table are small beside it and are left out, so a roofline share
    built on this is a little low, never high."""
    return context_tokens * kv_bytes_per_token(config, itemsize)
