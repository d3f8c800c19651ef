"""A server kept saturated: a seeded endless stream, the engine's queue
topped up so that `min_waiting` requests always wait. Whatever limits
admission (here the KV pool) is always the limit. Set-up ends when the pool
is full: a first wave with residual answer lengths (traffic_gen.first_wave)
is admitted until a tick admits nobody.

End to end: generated tokens returned in the window over the window's
seconds (prompt tokens do not count).
"""

import numpy as np

from benchmark import serving, traffic_gen
from benchmark.harness import timed

MAX_FILL_TICKS = 8


def run_cell(run, family):
    mix = run.mix
    driver, stream = serving.start(run, family)
    engine, page = driver.engine, run.config["serve"]["page_size"]
    prompts = traffic_gen.levels(mix["prompt_len"])
    answers = traffic_gen.levels(mix["answer_len"])
    with timed(run, "warm the spill and restore programs"):
        serving.warm_spill_shapes(engine, -(-min(prompts) // page))

    def top_up():
        while engine.sched.waiting_prefill < mix["min_waiting"]:
            driver.send(stream.next())

    # as many residual-length requests as the pool holds in steady state
    per_row = np.mean(prompts) + 0.5 * np.mean(answers)
    wave = int(min(engine.B, engine.pool.pages_total * page / per_row))
    with timed(run, f"fill the pool (first wave of {wave})"):
        for req in stream.first_wave(wave):
            driver.send(req)
        for _ in range(MAX_FILL_TICKS):
            top_up()
            driver.tick()
            if driver.ticks[-1]["first_tokens"] == 0:
                break
    run.log(f"pool {driver.ticks[-1]['pool_share']:.1f}% full, "
            f"{engine.live_count} live rows")

    t_open, t_close = serving.measure(run, driver, top_up)
    summary = driver.summary(t_open, t_close)
    ticks, held = summary["ticks"], summary["held"]
    tokens = sum(t["tokens"] for t in ticks)
    run.log(f"{len(ticks)} ticks, {tokens} tokens, "
            f"{sum(t['first_tokens'] for t in ticks)} admissions, "
            f"{sum(t['spilled'] > 0 for t in ticks)} ticks with a spilled "
            f"request, {sum(r.done for r in held)} finished of {len(held)} held")
    return serving.finish(run, family, driver, summary,
                          {"serve_tok_s": tokens / (t_close - t_open)})
