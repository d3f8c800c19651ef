"""The `mimo_v2` family: how a configuration file becomes the program's model
(`paddle_tpu.models.mimo_v2`) behind a PagedServingEngine, and how what it
served is held against the plain reference. Serving only: at 16 bytes a
parameter no share of this model that is still the model trains on one chip
(PERF.md section 4)."""

import dataclasses
import sys

import numpy as np

from benchmark.reference import mimo_v2 as reference

# a sample's prompt plus answer is padded to a multiple of this for the
# reference's one forward (causal: the padding is unseen), so that the
# reference compiles a few shapes and not one a sample
PAD_TO = 2048


def _model_config(config):
    from paddle_tpu.models.mimo_v2 import MimoV2Config

    unsupported = [
        f"{key}={config[key]!r}" for key, want in (
            ("scoring_func", "sigmoid"), ("norm_topk_prob", True),
            ("topk_method", "noaux_tc"), ("n_group", 1), ("topk_group", 1),
            ("n_shared_experts", None), ("routed_scaling_factor", None),
            ("attention_bias", False), ("tie_word_embeddings", False),
            ("hidden_act", "silu"),
            ("sliding_window_size", config["sliding_window"]))
        if config[key] != want]
    if unsupported:
        raise SystemExit("benchmark: models/mimo_v2.py does not compute "
                         + ", ".join(unsupported))
    # the model's config has the source's own keys: take them by name
    shared = {f.name: config[f.name]
              for f in dataclasses.fields(MimoV2Config) if f.name in config}
    layers = config["num_hidden_layers"]
    shared.update(
        hybrid_layer_pattern=config["hybrid_layer_pattern"][:layers],
        moe_layer_freq=config["moe_layer_freq"][:layers],
        # the router is as wide as the published model; this chip holds
        # `n_routed_experts` of its experts
        n_routed_experts=config["published"]["n_routed_experts"],
        held_experts=held(config), dtype="bfloat16")
    return MimoV2Config(**shared)


def held(config):
    """(first, count) of the routed experts this chip holds."""
    return config["held_experts_first"], config["n_routed_experts"]


def build_server(config, seed, kv_budget):
    """The bf16 model behind a PagedServingEngine. The model casts itself a
    layer at a time as it is built and frees each float32 form before it
    returns, so what `kv_budget()` reads from the device is what the model
    left. The engine takes `kv_budget()` bytes for ONE pool whose units the
    full layers' pages (4 KV heads) and the sliding layers' (8: two adjacent
    units) share."""
    import paddle_tpu as paddle
    import paddle_tpu.distributed as dist
    from paddle_tpu.inference.paged import PagedServingEngine
    from paddle_tpu.models.mimo_v2 import MimoV2ForCausalLM

    serve = config["serve"]
    dist.env.set_global_mesh(None)
    paddle.seed(seed)
    model = MimoV2ForCausalLM(_model_config(config))
    budget = kv_budget()
    print(f"[mimo_v2] model on the device; {budget / 1e9:.3f} GB for pages",
          file=sys.stderr, flush=True)
    return PagedServingEngine(
        model, max_batch_size=serve["max_batch_size"],
        max_seq_len=serve["max_seq_len"], page_size=serve["page_size"],
        kv_budget_bytes=budget, seed=seed)


def check_served(config, model, samples, lower_precision=False):
    """(ok, detail): each sample is (prompt ids, served ids) of a greedy
    request. The reference runs prompt + answer in ONE forward (dense masked
    attention in query blocks, the sink a column of its own, no cache, no
    pages, no chunks), the head only over the answered positions. At each of
    them the served token's reference logit sits some share of the row's
    standard deviation below the row's largest (0 where the reference picks
    the same token). Two limits, and a sample has to keep both: the MEAN of
    that share over its positions may be at most `serve.gap_tolerance`, and
    the share at its WORST position at most `serve.worst_gap_tolerance`.

    Why a share of the spread: with random weights a row of logits is nearly
    flat and its top two lie close together, so bf16 rounding can swap them;
    a wrong page, a unit of the wrong half of a block, a window off by one,
    a released page still read, a missing sink, a rotation over the wrong
    values or a wrong expert moves every row it touches by a good part of a
    spread. Why two limits: this router picks 8 of 256 by sigmoid score and
    bf16 flips a pick at a few positions in a hundred, which moves THAT
    position's row and hardly its neighbours', so the worst position says
    nothing of precision and the mean does; but one wholly wrong token among
    a thousand right ones adds a thousandth of its gap to the mean, and the
    worst position is what sees it. The readings of both limits on the chip
    are in the configuration's `_why` keys and PERF.md section 6 (PR 39);
    the reference at an 8-bit float's precision (`lower_precision=True`) must
    come out NOT correct, and so must a served answer with one planted wrong
    token.
    Each sample's entry says whether its context passed the window
    (`beyond_window`): a row that never left it has not shown that pages
    expire rightly (here every prompt is past it from its first tick)."""
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
