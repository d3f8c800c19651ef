"""The `granite_hybrid` family: how a configuration file becomes the
program's model (`paddle_tpu.models.granite_hybrid`) behind a
PagedServingEngine, and how what it served is held against the plain
reference. Serving only: at 16 bytes a parameter no share of this model
that is still the model trains on one chip (PERF.md section 4)."""

import dataclasses
import sys

import numpy as np

from benchmark.reference import granite_hybrid as reference


def _model_config(config):
    from paddle_tpu.models.granite_hybrid import GraniteHybridConfig

    if config["mamba_n_heads"] * config["mamba_d_head"] != (
            config["mamba_expand"] * config["hidden_size"]):
        raise SystemExit("benchmark: mamba heads x head width is not "
                         "mamba_expand x hidden_size")
    if config["mamba_n_groups"] != 1:
        raise SystemExit("benchmark: models/granite_hybrid.py has one B/C "
                         "group")
    # the model's config has the source's own keys: take them by name
    shared = {f.name: config[f.name]
              for f in dataclasses.fields(GraniteHybridConfig)
              if f.name in config}
    shared.update(
        layer_types=config["layer_types"][:config["num_hidden_layers"]],
        # the router is as wide as the published model; this chip holds
        # `num_local_experts` of its experts
        num_local_experts=config["published"]["num_local_experts"],
        held_experts=held(config), dtype="bfloat16")
    return GraniteHybridConfig(**shared)


def held(config):
    """(first, count) of the routed experts this chip holds."""
    return config["held_experts_first"], config["num_local_experts"]


def build_server(config, seed, kv_budget):
    """The bf16 model behind a PagedServingEngine. The model casts itself a
    layer at a time as it is built (5 B parameters in float32 would not fit
    the chip). The engine takes `kv_budget()` bytes for every row's
    recurrent-state slot and, with what is left, the attention layer's
    pages."""
    import jax

    import paddle_tpu as paddle
    import paddle_tpu.distributed as dist
    from paddle_tpu.inference.paged import PagedServingEngine
    from paddle_tpu.models.granite_hybrid import GraniteHybridForCausalLM

    serve = config["serve"]
    dist.env.set_global_mesh(None)
    paddle.seed(seed)
    model = GraniteHybridForCausalLM(_model_config(config))
    # the float32 form of the layer built last is freed only once its cast
    # has run: wait, or what the device "has left" reads a layer too low
    jax.block_until_ready([p._value for p in model.parameters()])
    budget = kv_budget()
    print(f"[granite_hybrid] model on the device; {budget / 1e9:.3f} GB for "
          "state slots and pages", file=sys.stderr, flush=True)
    return PagedServingEngine(
        model, max_batch_size=serve["max_batch_size"],
        max_seq_len=serve["max_seq_len"], page_size=serve["page_size"],
        kv_budget_bytes=budget, seed=seed)


def check_served(config, model, samples, lower_precision=False):
    """(ok, detail): each sample is (prompt ids, served ids) of a greedy
    request. The reference runs prompt + answer in ONE forward (its
    recurrence token by token from a zero state, no cache, no chunks), the
    head only over the answered positions; at each of them the served
    token's reference logit may sit below the row's largest by at most
    `serve.gap_tolerance` of the row's standard deviation.

    Why a share of the spread: with random weights a row of logits is nearly
    flat and its top two lie close together, so bf16 rounding can swap them;
    a wrong state, page, mask or expert picks a token a good part of a
    spread below. (The model draws its embedding small, so that a row is
    NOT dominated by the last input token: a seeded model that only repeats
    its input would pass any comparison.) What the served path adds over the
    GPT family's check is the recurrent state: every token after the prompt
    is computed from a state that has been rounded to bf16 once a token and
    layer since the prompt's end, against a reference that never rounds, and
    a pick of the router that rounding flips swaps an expert whose gate is a
    twentieth of the routed output. PERF.md section 6 (PR 29) has the two
    readings the tolerance lies between: the largest share the served path
    gave over its seeds on the chip, and what the reference itself gives
    when computed at an 8-bit float's precision (`lower_precision=True`:
    every matmul operand and the stored state at 3 mantissa bits, thirty
    times bf16's rounding), which must come out NOT correct. A state kept
    in 8 bits is inside that second reading; a dropped expert is far
    outside it: a held pick carries about a tenth of the routed output, in
    every one of ten layers. So neither could pass as bf16."""
    import jax.numpy as jnp

    params = {k: p._value for k, p in model.named_parameters()}
    pad_to = config["serve"]["max_seq_len"]
    tol = config["serve"]["gap_tolerance"]
    shares = []
    for prompt, served in samples:
        n, g = len(prompt), len(served)
        ids = np.zeros(pad_to, np.int32)   # causal: the padding is unseen
        ids[:n] = prompt
        ids[n:n + g - 1] = served[:-1]
        rows = reference.logits(
            params, ids, config, held(config),
            rows=np.arange(n - 1, n - 1 + g),
            lower_precision=lower_precision)
        picked = jnp.take_along_axis(
            rows, jnp.asarray(served, jnp.int32)[:, None], axis=-1)[:, 0]
        share = (rows.max(axis=-1) - picked) / rows.std(axis=-1)
        shares.append({"prompt": n, "answer": g,
                       "worst_share": float(share.max())})
    ok = bool(shares) and all(s["worst_share"] <= tol for s in shares)
    return ok, {"samples": shares, "tolerance": tol}
