"""The `gpt` family: how a configuration of this family becomes the
program's model (`paddle_tpu.models.gpt`), its trainer and its paged server,
and how their outputs are held against the plain reference
(`benchmark/reference/gpt.py`).

A traffic kind calls `build_trainer` or `build_server`, drives what it gets
through the program's public entry points, and afterwards calls
`check_loss` or `check_served`. Recipes are those of `chip_smoke.py`
(the ones PR 22 ran on the chip).
"""

import numpy as np

from benchmark.reference import gpt as reference


def _gpt_config(config, **extra):
    from paddle_tpu.models import GPTConfig

    if config["num_heads"] * config["head_dim"] != config["hidden_size"]:
        raise ValueError("the program's GPT takes d_head = d_model / heads")
    if config["intermediate_size"] != 4 * config["hidden_size"]:
        raise ValueError("the program's GELU MLP is 4 x d_model wide")
    return GPTConfig(
        vocab_size=config["vocab_size"], hidden_size=config["hidden_size"],
        num_layers=config["num_layers"], num_heads=config["num_heads"],
        max_position_embeddings=config["max_position_embeddings"], **extra)


def build_trainer(config, devices, seed):
    """(step, model): `step(ids, labels)` is a DistributedTrainStep on the
    mesh the configuration's `train` section names — bf16 parameters (AMP
    O2), bf16 AdamW moments, per-layer recomputation."""
    import paddle_tpu as paddle
    import paddle_tpu.amp as amp
    import paddle_tpu.distributed as dist
    import paddle_tpu.optimizer as opt
    from paddle_tpu.models import GPTForCausalLM, GPTPretrainingCriterion

    recipe = config["train"]
    paddle.seed(seed)
    cfg = _gpt_config(config, use_recompute=True)
    model = GPTForCausalLM(cfg)
    crit = GPTPretrainingCriterion(cfg)
    amp.decorate(model, level="O2", dtype="bfloat16")
    optimizer = opt.AdamW(learning_rate=recipe["learning_rate"],
                          moment_dtype="bfloat16",
                          parameters=model.parameters())
    mesh = dist.build_mesh(devices=devices, **recipe["mesh"])
    kw = {}
    if recipe["sharding_stage"]:
        kw["sharding_stage"] = recipe["sharding_stage"]
    step = dist.DistributedTrainStep(
        model, lambda lg, lb: crit(lg, lb), optimizer, mesh=mesh,
        amp_level="O2", amp_dtype="bfloat16", **kw)
    return step, model


def build_server(config, seed, kv_budget):
    """The bf16 model behind a PagedServingEngine. The pool gets
    `kv_budget()` bytes, asked once the model is on the device."""
    import paddle_tpu as paddle
    import paddle_tpu.amp as amp
    import paddle_tpu.distributed as dist
    from paddle_tpu.inference.paged import PagedServingEngine
    from paddle_tpu.models import GPTForCausalLM

    serve = config["serve"]
    dist.env.set_global_mesh(None)
    paddle.seed(seed)
    model = GPTForCausalLM(_gpt_config(config))
    amp.decorate(model, level="O2", dtype="bfloat16")
    return PagedServingEngine(
        model, max_batch_size=serve["max_batch_size"],
        max_seq_len=serve["max_seq_len"], page_size=serve["page_size"],
        kv_budget_bytes=kv_budget(), seed=seed)


def check_loss(config, step, model, ids, labels):
    """(ok, detail): the loss of one sequence through the trainer's model
    (its kernels, bf16, the parameters as the window left them) against the
    reference's on the same parameters.

    Tolerance (`train.loss_tolerance`, absolute, on a loss between 6 and
    ln(vocab) = 10.8): bf16 keeps 8 bits, so a logit is off by about 2^-8 of
    its size and the mean over 4096 positions by much less: the chip read
    gaps of 0.0001 to 0.0002 (my chip runs, PR 25). 0.005 is some thirty
    times that, and under what a forward in 8-bit floats, a dropped layer or
    a wrong mask would move it by."""
    import jax
    import jax.numpy as jnp

    from paddle_tpu.framework.core import Tensor
    from paddle_tpu.jit import functional_call

    # as a user who evaluates after training: the model's own tensors take
    # the trained, sharded values, and the unsharded originals on device 0
    # are released (the reference needs the room on four chips)
    step.sync_weights()
    params = dict(step.params)
    buffers = {k: b._value for k, b in model.named_buffers()}

    @jax.jit
    def program_loss(p, b, tok, lab):
        lg, _ = functional_call(model, p, b, [Tensor(tok)], train=False)
        logp = jax.nn.log_softmax(lg.astype(jnp.float32), axis=-1)
        return -jnp.take_along_axis(logp, lab[..., None], axis=-1).mean()

    got = float(program_loss(params, buffers, jnp.asarray(ids),
                             jnp.asarray(labels)))
    want = float(reference.loss(params, ids, labels, config["num_layers"],
                                config["num_heads"]))
    tol = config["train"]["loss_tolerance"]
    detail = {"program_loss": got, "reference_loss": want,
              "gap": abs(got - want), "tolerance": tol}
    return bool(np.isfinite(got) and abs(got - want) <= tol), detail


def check_served(config, model, samples):
    """(ok, detail): each sample is (prompt ids, served ids) of a greedy
    request. The reference runs the whole prompt + answer in one forward;
    at every answered position the served token's reference logit may sit
    below the row's largest by at most `serve.gap_tolerance` of the row's
    standard deviation.

    Why a share of the spread: with random weights a row of logits is nearly
    flat and its top two lie close together, so bf16 noise can swap them
    (the chip read shares up to 0.006 over 41 positions; my chip runs,
    PR 25); a wrong page,
    mask or position picks a token a whole spread or more below. 0.15 lets
    the first through and not the second, nor int8 arithmetic under a bf16
    name."""
    import jax.numpy as jnp

    params = {k: p._value for k, p in model.named_parameters()}
    pad_to = config["serve"]["max_seq_len"]
    tol = config["serve"]["gap_tolerance"]
    shares = []
    for prompt, served in samples:
        n, g = len(prompt), len(served)
        ids = np.zeros((1, pad_to), np.int32)  # causal: the padding is unseen
        ids[0, :n] = prompt
        ids[0, n:n + g - 1] = served[:-1]
        rows = reference.logits(params, ids, config["num_layers"],
                                config["num_heads"])[0, n - 1:n - 1 + g]
        picked = jnp.take_along_axis(
            rows, jnp.asarray(served, jnp.int32)[:, None], axis=-1)[:, 0]
        share = (rows.max(axis=-1) - picked) / rows.std(axis=-1)
        shares.append({"prompt": n, "answer": g,
                       "worst_share": float(share.max())})
    ok = bool(shares) and all(s["worst_share"] <= tol for s in shares)
    return ok, {"samples": shares, "tolerance": tol}
