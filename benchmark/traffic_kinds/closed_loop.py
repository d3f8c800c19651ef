"""A closed loop of `clients` callers with no think time: each sends a
request, waits for its last token, and sends the next at once. A slow server
so receives less load, and no rate has to be found.

Set-up sends every client's first request with a residual answer length
(traffic_gen.first_wave), so the window opens on a system in steady state.

End to end: `ttft_p50_ms`, the median over requests sent in the window of
the time from the client's send to the return of the tick that produced the
first token; `tpot_p95_ms`, the 95th percentile, pooled over all requests, of
the gaps between successive tokens that arrived in the window. The gap from
the first token to the second is left out: the engine emits the first at
admission and decodes the second in the same tick, so it is 0 by
construction and says nothing of the decode loop.
"""

from benchmark import serving
from benchmark.harness import percentile, timed

SETTLE_TICKS = 2


def run_cell(run, family):
    mix = run.mix
    driver, stream = serving.start(run, family)

    def resend():
        for rec in driver.finished_last:
            driver.send(stream.next(), client=rec.client)

    with timed(run, f"admit the first request of {mix['clients']} clients"):
        for client, req in enumerate(stream.first_wave(mix["clients"])):
            driver.send(req, client=client)
        for _ in range(SETTLE_TICKS):
            resend()
            driver.tick()

    t_open, t_close = serving.measure(run, driver, resend)
    summary = driver.summary(t_open, t_close)
    held = summary["held"]
    ttft = [r.ttft_ms for r in held
            if r.t_send >= t_open and r.token_at[0] <= t_close]
    gaps = []
    for r in held:
        t = r.token_at
        gaps += [(t[j] - t[j - 1]) * 1e3 for j in range(2, len(t))
                 if t_open < t[j] <= t_close]
    run.log(f"{len(ttft)} first tokens, {len(gaps)} gaps, "
            f"{sum(r.done for r in held)} finished of {len(held)} held")
    return serving.finish(run, family, driver, summary,
                          {"ttft_p50_ms": percentile(ttft, 50),
                           "tpot_p95_ms": percentile(gaps, 95)})
