"""Per-component timing breakdown on the current backend (meant for TPU).

Times each suspect in isolation so the 1/MFU budget can be attributed:
  matmul peak sanity, qkvo-projection matmuls, MLP chain, flash-attention
  Pallas vs XLA composite (fwd and fwd+bwd), lm-head+CE, full train step.

Timing method (round-5): every kernel probe runs ITERS copies of the op
inside one jitted lax.scan, so per-dispatch overhead divides out; the loop
carry feeds each iteration so XLA cannot CSE or DCE the work. A
`dispatch_overhead` probe reports the per-call floor separately. Every
timing ends in a device->host fetch of the result.

Usage:  python tools/perf_breakdown.py [gpt3_125m|gpt3_350m]
Prints one JSON line per probe: {"probe", "ms", "tflops", "eff_vs_peak"}.
"""

import json
import math
import os
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import jax
import jax.numpy as jnp
import numpy as np

ITERS = 20


def _host_sync(out):
    """Force a REAL device->host fetch (see module docstring). Slices on
    DEVICE first so only one element crosses the bus."""
    leaf = jax.tree.leaves(out)[0]
    one = leaf.ravel()[0:1] if getattr(leaf, "ndim", 0) else leaf
    return np.asarray(jax.device_get(one))


def timeit_wall(fn, *args, reps=5, warmup=2):
    """Wall-clock per-call timing (includes dispatch overhead) — only for
    big probes (>=50ms) where the overhead is noise."""
    for _ in range(warmup):
        out = fn(*args)
    _host_sync(out)
    t0 = time.perf_counter()
    for _ in range(reps):
        out = fn(*args)
    _host_sync(out)  # in-order device stream => all reps done
    return (time.perf_counter() - t0) / reps


def timeit_scan(op, init, iters=ITERS):
    """Device-side loop timing: op (carry -> same-shaped carry) runs `iters`
    times inside ONE jitted scan, so per-dispatch overhead divides out."""
    f = jax.jit(
        lambda c: jax.lax.scan(lambda c, _: (op(c), None), c, None,
                               length=iters)[0])
    _host_sync(f(init))  # compile + warm
    t0 = time.perf_counter()
    _host_sync(f(init))
    return (time.perf_counter() - t0) / iters


def report(probe, dt, flops, peak, extra=None):
    tf = flops / dt / 1e12
    eff = flops / dt / peak
    line = {
        "probe": probe,
        "ms": round(dt * 1e3, 3),
        "tflops": round(tf, 1),
        "eff_vs_peak": round(eff, 3),
    }
    if eff > 1.1:
        # physically impossible — the timed loop did not synchronize
        line["invalid"] = "eff>110% of peak: timing not synchronized, discard"
    if extra:
        line.update(extra)
    print(json.dumps(line), flush=True)
    return line


def _keep_live(primary, *rest):
    """Fold scalars of auxiliary outputs into the carry so XLA cannot DCE
    the work that produced them (cost: one scalar add per aux)."""
    s = sum(r.sum().astype(jnp.float32) for r in rest)
    return primary + (s * 1e-30).astype(primary.dtype)


def main():
    cfg_name = sys.argv[1] if len(sys.argv) > 1 else "gpt3_125m"
    backend = jax.default_backend()
    print(json.dumps({"probe": "backend", "name": backend,
                      "device": str(getattr(jax.devices()[0], "device_kind", ""))}),
          flush=True)
    from bench import _peak_flops

    peak, kind = _peak_flops(jax.devices()[0])
    if backend == "cpu":
        peak = 1e12  # nominal, so the script still runs for smoke

    B, S = (8, 2048) if backend != "cpu" else (2, 256)
    import paddle_tpu as paddle
    from paddle_tpu.models import gpt3_125m, gpt3_350m, GPTForCausalLM, GPTPretrainingCriterion

    if backend == "cpu":
        from paddle_tpu.models import gpt3_tiny

        cfg = gpt3_tiny()
        cfg.max_position_embeddings = S
    else:
        cfg = {"gpt3_125m": gpt3_125m, "gpt3_350m": gpt3_350m}[cfg_name](
            max_position_embeddings=S)
    H, L, nh, D = cfg.hidden_size, cfg.num_layers, cfg.num_heads, cfg.head_dim
    V = cfg.vocab_size
    key = jax.random.PRNGKey(0)

    # 0. per-dispatch overhead floor (the number the scan method removes)
    tiny = jnp.zeros((8,), jnp.float32)
    f_id = jax.jit(lambda x: x + 1.0)
    dt = timeit_wall(f_id, tiny, reps=10, warmup=3)
    print(json.dumps({"probe": "dispatch_overhead", "ms": round(dt * 1e3, 3)}),
          flush=True)

    # 1. matmul peak sanity: can this chip hit its spec at all?
    for n in ((4096, 8192) if backend != "cpu" else (512,)):
        a = jax.random.normal(key, (n, n), jnp.bfloat16)
        scale = jnp.bfloat16(1.0 / math.sqrt(n))
        dt = timeit_scan(lambda c: (c @ a) * scale, a)
        report(f"matmul_bf16_{n}", dt, 2.0 * n ** 3, peak)

    # 2. qkv+out projection shape ([BS,H]@[H,H]), fwd and fwd+bwd
    x = jax.random.normal(key, (B * S, H), jnp.bfloat16)
    wq = jax.random.normal(key, (H, H), jnp.bfloat16) / math.sqrt(H)
    wo = jax.random.normal(key, (H, H), jnp.bfloat16) / math.sqrt(H)

    def proj2(c):
        return (c @ wq) @ wo

    dt = timeit_scan(proj2, x)
    proj_fwd = report("proj2_fwd", dt, 2 * 2 * B * S * H * H, peak)
    gp = jax.grad(lambda c, a_, b_: ((c @ a_) @ b_).astype(jnp.float32).sum(),
                  argnums=(0, 1, 2))

    def proj2_bwd(c):
        dx, dwa, dwb = gp(c, wq, wo)
        return _keep_live(dx, dwa, dwb)

    dt = timeit_scan(proj2_bwd, x)
    proj_bwd = report("proj2_fwdbwd", dt, 3 * 2 * 2 * B * S * H * H, peak)

    # 3. MLP-shaped matmul chain (the non-attention compute shape)
    w1 = jax.random.normal(key, (H, 4 * H), jnp.bfloat16) / math.sqrt(H)
    w2 = jax.random.normal(key, (4 * H, H), jnp.bfloat16) / math.sqrt(4 * H)

    def mlp(c, a_, b_):
        return jax.nn.gelu(c @ a_) @ b_

    dt = timeit_scan(lambda c: mlp(c, w1, w2), x)
    report("mlp_fwd", dt, 2 * 2 * B * S * H * 4 * H, peak)

    gm = jax.grad(lambda c, a_, b_: mlp(c, a_, b_).astype(jnp.float32).sum(),
                  argnums=(0, 1, 2))

    def mlp_bwd(c):
        dx, dw1, dw2 = gm(c, w1, w2)
        return _keep_live(dx, dw1, dw2)

    dt = timeit_scan(mlp_bwd, x)
    mlp_bwd_line = report("mlp_fwdbwd", dt, 3 * 2 * 2 * B * S * H * 4 * H, peak)

    # 4. attention: Pallas kernel vs XLA composite, fwd and fwd+bwd
    attn_flops_fwd = 2 * 2 * B * nh * S * S * D  # qk + pv (causal halves it)
    q = jax.random.normal(key, (B, S, nh, D), jnp.bfloat16)
    from paddle_tpu.ops.pallas.flash_attention import flash_attention_fwd
    from paddle_tpu.nn.functional.flash_attention import _ref_attention

    def pal(c):
        return flash_attention_fwd(c, c, c, causal=True)

    def comp(c):
        return _ref_attention(c, c, c, causal=True)

    ab = {}
    for name, fn in (("attn_pallas", pal), ("attn_xla", comp)):
        try:
            dt = timeit_scan(fn, q)
            ab[name + "_fwd"] = report(name + "_fwd", dt, attn_flops_fwd / 2, peak)
            gfn = jax.grad(lambda c: fn(c).astype(jnp.float32).sum())
            dt = timeit_scan(gfn, q)
            ab[name + "_fwdbwd"] = report(
                name + "_fwdbwd", dt, attn_flops_fwd / 2 * 3.5, peak)
        except Exception as e:
            print(json.dumps({"probe": name, "error": f"{type(e).__name__}: {e}"[:200]}),
                  flush=True)
    if "attn_pallas_fwdbwd" in ab and "attn_xla_fwdbwd" in ab:
        print(json.dumps({
            "probe": "attn_ab_verdict",
            "winner": ("pallas" if ab["attn_pallas_fwdbwd"]["ms"]
                       <= ab["attn_xla_fwdbwd"]["ms"] else "xla"),
            "pallas_ms": ab["attn_pallas_fwdbwd"]["ms"],
            "xla_ms": ab["attn_xla_fwdbwd"]["ms"],
        }), flush=True)

    # 5. lm head + cross entropy (tied-embedding shape)
    h = jax.random.normal(key, (B, S, H), jnp.bfloat16)
    w = jax.random.normal(key, (V, H), jnp.bfloat16) / math.sqrt(H)
    lab = jax.random.randint(key, (B, S), 0, V)

    def head_ce(h, w, lab):
        logits = h @ w.T
        logp = jax.nn.log_softmax(logits.astype(jnp.float32), axis=-1)
        return -jnp.take_along_axis(logp, lab[..., None], axis=-1).mean()

    dt = timeit_scan(
        lambda c: _keep_live(c, head_ce(c, w, lab)[None]), h, iters=5)
    head_fwd = report("head_ce_fwd", dt, 2 * B * S * H * V, peak)
    gh = jax.grad(head_ce, argnums=(0, 1))

    def head_bwd(c):
        dh, dw = gh(c, w, lab)
        return _keep_live(dh, dw)

    dt = timeit_scan(head_bwd, h, iters=5)
    head_bwd_line = report("head_ce_fwdbwd", dt, 3 * 2 * B * S * H * V, peak)

    # 6. full model fwd and full train step (wall-clock: >=50ms, overhead ok)
    paddle.seed(0)
    import paddle_tpu.distributed as dist
    import paddle_tpu.optimizer as opt

    model = GPTForCausalLM(cfg)
    crit = GPTPretrainingCriterion(cfg)
    optimizer = opt.AdamW(learning_rate=1e-4, parameters=model.parameters())
    mesh = dist.build_mesh(devices=jax.devices()[:1])
    step = dist.DistributedTrainStep(
        model, lambda lg, lb: crit(lg, lb), optimizer, mesh=mesh,
        amp_level="O2", amp_dtype="bfloat16")
    rng = np.random.default_rng(0)
    ids = paddle.to_tensor(rng.integers(0, V, (B, S)))
    labels = paddle.to_tensor(rng.integers(0, V, (B, S)))

    n_params = cfg.num_params(include_embeddings=False) + V * H
    tok = B * S
    step_flops = 6.0 * n_params * tok + 12.0 * L * H * S * tok

    dt_step = timeit_wall(lambda: step(ids, labels)._value, reps=5, warmup=2)
    report("train_step", dt_step, step_flops, peak)

    # 7. eval (fwd-only) pass through the same machinery
    dt = timeit_wall(lambda: step.evaluate(ids, labels)._value, reps=5, warmup=2)
    report("eval_fwd", dt, step_flops / 3.0, peak)

    # 8. do the components sum to ~the step? (sanity on the attribution)
    # per decoder layer fwd+bwd: qkvo (4 HxH matmuls = 2x proj2's pair) +
    # MLP + attention — keyed to the kernel the model ACTUALLY selects
    from paddle_tpu.nn.functional.flash_attention import _use_pallas_kernel

    attn_key = ("attn_pallas_fwdbwd" if _use_pallas_kernel()
                else "attn_xla_fwdbwd")
    if attn_key not in ab and ab:
        attn_key = next(iter(k for k in ab if k.endswith("fwdbwd")), None)
    if attn_key in ab:
        per_layer_ms = (2.0 * proj_bwd["ms"] + mlp_bwd_line["ms"]
                        + ab[attn_key]["ms"])
        comp_ms = L * per_layer_ms + head_bwd_line["ms"]
        cov = comp_ms / (dt_step * 1e3)
        line = {
            "probe": "components_sum",
            "layers_x_perlayer_plus_head_ms": round(comp_ms, 1),
            "train_step_ms": round(dt_step * 1e3, 1),
            "coverage": round(cov, 3),
        }
        # isolated probes cannot overlap with neighbors the way the fused
        # step does, so coverage > 1 is expected; far outside [0.7, 1.3]
        # means the attribution is not trustworthy for ranking components
        if not 0.7 <= cov <= 1.3:
            line["note"] = ("coverage outside [0.7, 1.3]: isolated-probe "
                            "attribution unreliable for this run")
        print(json.dumps(line), flush=True)


if __name__ == "__main__":
    main()
