"""The `afmoe` family: how a configuration file becomes the program's model
(`paddle_tpu.models.afmoe`) behind a PagedServingEngine, and how what it
served is held against the plain reference. Serving only: at 16 bytes a
parameter no share of this model that is still the model trains on one chip
(PERF.md section 4)."""

import dataclasses
import sys

import numpy as np

from benchmark.reference import afmoe as reference

# a sample's prompt plus answer is padded to a multiple of this for the
# reference's one forward (causal: the padding is unseen), so that the
# reference compiles a few shapes and not one a sample
PAD_TO = 2048


def _model_config(config):
    from paddle_tpu.models.afmoe import AfmoeConfig

    unsupported = [
        f"{key}={config[key]!r}" for key, want in (
            ("score_func", "sigmoid"), ("route_norm", True), ("n_group", 1),
            ("topk_group", 1), ("rope_scaling", None),
            ("tie_word_embeddings", False), ("hidden_act", "silu"))
        if config[key] != want]
    if unsupported:
        raise SystemExit("benchmark: models/afmoe.py does not compute "
                         + ", ".join(unsupported))
    # the model's config has the source's own keys: take them by name
    shared = {f.name: config[f.name]
              for f in dataclasses.fields(AfmoeConfig) if f.name in config}
    shared.update(
        layer_types=config["layer_types"][:config["num_hidden_layers"]],
        # the router is as wide as the published model; this chip holds
        # `num_experts` of its experts
        num_experts=config["published"]["num_experts"],
        held_experts=held(config), dtype="bfloat16")
    return AfmoeConfig(**shared)


def held(config):
    """(first, count) of the routed experts this chip holds."""
    return config["held_experts_first"], config["num_experts"]


def build_server(config, seed, kv_budget):
    """The bf16 model behind a PagedServingEngine. The model casts itself a
    layer at a time as it is built and frees each float32 form before it
    returns, so what `kv_budget()` reads from the device is what the model
    left. The engine takes `kv_budget()` bytes for ONE pool of pages that
    the full layers' group and the window layers' groups share."""
    import paddle_tpu as paddle
    import paddle_tpu.distributed as dist
    from paddle_tpu.inference.paged import PagedServingEngine
    from paddle_tpu.models.afmoe import AfmoeForCausalLM

    serve = config["serve"]
    dist.env.set_global_mesh(None)
    paddle.seed(seed)
    model = AfmoeForCausalLM(_model_config(config))
    budget = kv_budget()
    print(f"[afmoe] model on the device; {budget / 1e9:.3f} GB for pages",
          file=sys.stderr, flush=True)
    return PagedServingEngine(
        model, max_batch_size=serve["max_batch_size"],
        max_seq_len=serve["max_seq_len"], page_size=serve["page_size"],
        kv_budget_bytes=budget, seed=seed)


def check_served(config, model, samples, lower_precision=False):
    """(ok, detail): each sample is (prompt ids, served ids) of a greedy
    request. The reference runs prompt + answer in ONE forward (dense masked
    attention, no cache, no pages, no chunks), the head only over the
    answered positions. At each of them the served token's reference logit
    sits some share of the row's standard deviation below the row's largest
    (0 where the reference picks the same token). Two limits, and a sample
    has to keep both: the MEAN of that share over its positions may be at
    most `serve.gap_tolerance`, and the share at its WORST position at most
    `serve.worst_gap_tolerance`.

    Why a share of the spread: with random weights a row of logits is nearly
    flat and its top two lie close together, so bf16 rounding can swap them;
    a wrong page, a window off by one, a released page still read, a missing
    rotation or a wrong expert moves every row it touches by a good part of
    a spread. Why two limits, where the other families have one a position:
    this router picks 8 of 128 by sigmoid score, the 8th and 9th scores lie
    0.06 apart in logit and bf16 moves a logit by 0.01, so at a few
    positions in a hundred a pick flips; a flipped pick carries a quarter of
    the routed output (2.826 / 8 of it with four of eight picks held)
    through a post-norm that rescales it, and the row moves by up to a whole
    spread at THAT position while its neighbours move by a hundredth. The
    model's own dense bf16 forward reads the same worst position as the
    served path, and so does the reference at an 8-bit float's precision
    (PERF.md section 6, PR 33: served 0.17-1.00, 8-bit 0.97-1.32), so no
    limit on the worst position tells PRECISION apart; the mean does (served
    0.005-0.029, 8-bit 0.212-0.248). But one wholly wrong token among a few
    hundred right ones adds only its share over the answer's length to the
    mean: the worst position is what sees it, since a token the reference
    has no reason to prefer lies four to five spreads under the row's
    largest of 200,192. So `worst_gap_tolerance` lies between the served
    path's largest worst position and a planted wrong token's reading. The
    readings of both limits are in PERF.md section 6 (PR 33); the reference
    at an 8-bit float's precision (`lower_precision=True`) must come out
    NOT correct, which it does by the mean.
    Each sample's entry says whether its context passed the window
    (`beyond_window`): a row that never left it has not shown that pages
    expire rightly."""
    import jax.numpy as jnp

    params = {k: p._value for k, p in model.named_parameters()}
    params.update({k: b._value for k, b in model.named_buffers()})
    tol = config["serve"]["gap_tolerance"]
    worst_tol = config["serve"]["worst_gap_tolerance"]
    shares = []
    for prompt, served in samples:
        n, g = len(prompt), len(served)
        ids = np.zeros(-(-(n + g) // PAD_TO) * PAD_TO, np.int32)
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
                       "beyond_window": bool(
                           n + g - 1 > config["sliding_window"]),
                       "mean_share": float(share.mean()),
                       "worst_share": float(share.max())})
    ok = bool(shares) and all(
        s["mean_share"] <= tol and s["worst_share"] <= worst_tol
        for s in shares)
    return ok, {"samples": shares, "tolerance": tol,
                "worst_tolerance": worst_tol}
