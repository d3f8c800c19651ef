"""Training steps back to back on seeded token batches.

The window: dispatch a step, then wait for the one before it, so exactly one
step is queued behind the running one and the host never reads a value. The
clock stops after `block_until_ready` on the last step's loss; tokens per
second is all the window's tokens over all its time. Losses stay on the
device until the window is closed.
"""

import numpy as np

from benchmark import traffic_gen
from benchmark.harness import timed

CHECK_SEQUENCES = 2  # divisible over a two-way data axis


def run_cell(run, family):
    import jax
    import jax.numpy as jnp

    import paddle_tpu as paddle
    from paddle_tpu.ops.pallas import autotune

    mix, config = run.mix, run.config
    chips = run.cell["chips"]
    s_model, s_data = traffic_gen.seeds(run.args.seed, 2)
    with timed(run, "build model, optimizer and step"):
        step, model = family.build_trainer(config, run.devices, s_model)
    ids, labels = traffic_gen.train_batches(mix, config["vocab_size"], s_data)
    batches = [(paddle.to_tensor(i), paddle.to_tensor(l))
               for i, l in zip(ids, labels)]
    with timed(run, "first step (trace, lower, compile or load)"):
        first = step(*batches[0])
        jax.block_until_ready(first._value)
    with timed(run, "second step"):
        jax.block_until_ready(step(*batches[1 % len(batches)])._value)

    t_open = run.open_window()
    deadline = t_open + run.seconds
    losses, dispatch_ms, done_at = [], [], []
    prev, i = None, 0
    while True:
        run.poll_trace(i)
        t0 = run.clock()
        with run.span("train_step"):
            loss = step(*batches[i % len(batches)])
        dispatch_ms.append((run.clock() - t0) * 1e3)
        if prev is not None:
            with run.span("wait_step"):
                jax.block_until_ready(prev._value)
            done_at.append(run.clock())
        losses.append(loss._value)
        prev, i = loss, i + 1
        if run.clock() >= deadline:
            break
    jax.block_until_ready(prev._value)
    t_close = run.clock()
    done_at.append(t_close)
    run.close_window(t_close)

    values = np.asarray(jnp.stack(losses)).astype(np.float64)
    bad = int((~np.isfinite(values)).sum())
    run.log(f"{i} steps; loss first {values[0]:.4f} last {values[-1]:.4f}; "
            f"non-finite {bad}")
    tokens = i * mix["batch"] * mix["seq"]
    steps = [{"index": k, "dispatch_ms": dispatch_ms[k],
              "step_ms": (done_at[k] - done_at[k - 1]) * 1e3 if k else None}
             for k in range(i)]

    n = min(CHECK_SEQUENCES, mix["batch"])
    ok, detail = family.check_loss(config, step, model, ids[0][:n],
                                   labels[0][:n])
    tiles = autotune.chosen_tiles()
    missing = [k for k in mix["expected_kernels"]
               if tiles.get(k, {}).get("consults", 0) <= 0]
    detail["kernels_never_traced"] = missing
    return {
        "end_to_end": {"train_tok_s_chip": tokens / (t_close - t_open) / chips},
        "attempted": i, "failed": bad,
        "correct": bool(ok and not missing and bad == 0),
        "check": detail,
        "series": {"steps": steps},
        "values": {"batch": mix["batch"], "seq": mix["seq"], "chips": chips},
    }
