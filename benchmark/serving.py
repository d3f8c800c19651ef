"""Drives a PagedServingEngine from the client's side: sends requests, ticks
the engine, and stamps every token with the return of the tick that produced
it. The traffic kinds `saturated` and `closed_loop` differ only in when they
send.

The engine is synchronous and single-threaded, so is this: one loop, no
threads. A request's tokens are read from `engine.active` and
`engine.finished` (its `generated` list) after each tick; the engine's own
TTFT is not used, because it starts at the request's construction inside the
engine and not at the client's send.
"""

import dataclasses

import numpy as np

from benchmark import traffic_gen
from benchmark.harness import timed


@dataclasses.dataclass
class Sent:
    rid: int
    prompt: np.ndarray
    want: int
    temperature: float
    t_send: float
    client: int = -1
    obj: object = None            # the engine's request, once seen
    token_at: list = dataclasses.field(default_factory=list)
    done: bool = False
    bad: str = ""

    @property
    def ttft_ms(self):
        return (self.token_at[0] - self.t_send) * 1e3 if self.token_at else None


class Driver:
    def __init__(self, run, engine, vocab_size):
        self.run, self.engine, self.vocab = run, engine, vocab_size
        self.sent = {}       # rid -> Sent
        self.live = {}       # rid -> Sent, holding or waiting for a row
        self.ticks = []
        self.finished_last = []   # what finished in the last tick
        self.refused = 0

    def send(self, req, client=-1):
        now = self.run.clock()
        try:
            rid = self.engine.add_request(
                req.prompt, max_new_tokens=req.max_new_tokens,
                temperature=req.temperature)
        except ValueError as e:
            self.refused += 1
            self.run.log(f"request refused: {e}")
            return None
        rec = Sent(rid, req.prompt, req.max_new_tokens, req.temperature, now,
                   client)
        self.sent[rid] = rec
        self.live[rid] = rec
        return rec

    def tick(self):
        """One `engine.step()`; `finished_last` then holds the requests that
        finished in it."""
        eng, run = self.engine, self.run
        t0 = run.clock()
        with run.span("engine_step"):
            out = eng.step()
        t1 = run.clock()
        finished, eng.finished = eng.finished, []
        for obj in list(eng.active) + finished:
            if obj is not None and obj.req_id in self.live:
                self.live[obj.req_id].obj = obj
        new_tokens = first_tokens = context = 0
        for rec in self.live.values():
            if rec.obj is None:
                continue
            have = len(rec.obj.generated)
            fresh = have - len(rec.token_at)
            if fresh:
                first_tokens += not rec.token_at
                rec.token_at.extend([t1] * fresh)
                new_tokens += fresh
            if rec.rid in out:
                # the decode kernel read this row's whole context: prompt
                # plus every token generated before the one it just made
                context += len(rec.prompt) + have - 1
        done = []
        for obj in finished:
            rec = self.live.pop(obj.req_id, None)
            if rec is None:
                continue
            rec.done = True
            got = obj.generated
            if obj.truncated:
                rec.bad = "truncated"
            elif len(got) != rec.want:
                rec.bad = f"{len(got)} tokens, asked {rec.want}"
            elif not all(0 <= t < self.vocab for t in got):
                rec.bad = "token outside the vocabulary"
            done.append(rec)
        pool = eng.pool
        self.ticks.append({
            "index": len(self.ticks), "t0": t0, "t1": t1,
            "ms": (t1 - t0) * 1e3, "tokens": new_tokens,
            "first_tokens": first_tokens, "decoded_rows": len(out),
            "context_tokens": context, "live": eng.live_count,
            "pool_share": 100.0 * (1 - pool.pages_free / pool.pages_total),
            "waiting": eng.sched.waiting_prefill,
            "spilled": eng.sched.waiting_resume,
        })
        self.finished_last = done

    # -- after the window ------------------------------------------------- #

    def summary(self, t_open, t_close):
        """The window's ticks, the requests that held a row in it (`held`),
        how many of them failed, and a record per request for the readers."""
        ticks = [t for t in self.ticks if t_open < t["t1"] <= t_close]
        held = [r for r in self.sent.values()
                if r.token_at and r.token_at[-1] > t_open]
        requests = [{"rid": r.rid, "prompt": len(r.prompt), "want": r.want,
                     "got": len(r.token_at), "done": r.done,
                     "sent_in_window": r.t_send >= t_open,
                     "ttft_ms": r.ttft_ms, "bad": r.bad} for r in held]
        return {"ticks": ticks, "held": held, "requests": requests,
                "attempted": len(held) + self.refused,
                "failed": sum(bool(r.bad) for r in held) + self.refused}

    def samples_to_check(self, held, n=2):
        """Up to n greedy requests with the longest answers so far, finished
        or cut by the window's end: (prompt ids, served ids)."""
        greedy = [r for r in held
                  if r.temperature == 0.0 and r.obj is not None
                  and len(r.obj.generated) >= 2]
        greedy.sort(key=lambda r: -len(r.obj.generated))
        return [(r.prompt, np.asarray(r.obj.generated, np.int32))
                for r in greedy[:n]]


def start(run, family):
    """(driver, stream): the model and its engine built from `--seed`, the
    pool sized from what the device has left, and the seeded stream of the
    cell's mix."""
    config = run.config
    s_model, s_traffic = traffic_gen.seeds(run.args.seed, 2)
    with timed(run, "build model and engine"):
        engine = family.build_server(config, s_model,
                                     lambda: kv_budget(run, config))
    run.log(f"pool {engine.pool.pages_total} pages x "
            f"{config['serve']['page_size']} tokens")
    return (Driver(run, engine, config["vocab_size"]),
            traffic_gen.RequestStream(run.mix, config["vocab_size"], s_traffic))


def measure(run, driver, feed):
    """The window: before every tick `feed()` sends what the kind wants
    sent. It closes with the return of the tick that passes the deadline.
    Returns (t_open, t_close)."""
    t_open = run.open_window()
    deadline = t_open + run.seconds
    while run.clock() < deadline:
        run.poll_trace(len(driver.ticks))
        with run.span("client_poll"):
            feed()
        driver.tick()
    t_close = driver.ticks[-1]["t1"]
    run.close_window(t_close)
    return t_open, t_close


def finish(run, family, driver, summary, end_to_end):
    """What `run_cell` returns, after the check: the pool is freed (the
    reference needs its memory), two greedy requests go against the plain
    reference, every request that finished did so as asked, and every kernel
    the mix expects was traced (a composite fallback cannot pass)."""
    import gc

    from paddle_tpu.ops.pallas import autotune

    engine, held = driver.engine, summary["held"]
    pool_dims = ",".join(str(d) for d in engine.pool.kv[0][0].shape)
    samples = driver.samples_to_check(held)
    engine.pool.kv = []
    driver.engine = None
    gc.collect()
    ok, detail = family.check_served(run.config, engine.model, samples)
    tiles = autotune.chosen_tiles()
    missing = [k for k in run.mix["expected_kernels"]
               if tiles.get(k, {}).get("consults", 0) <= 0]
    detail["kernels_never_traced"] = missing
    detail["bad_requests"] = [f"{r.rid}: {r.bad}" for r in held if r.bad][:5]
    return {
        "end_to_end": end_to_end,
        "attempted": summary["attempted"], "failed": summary["failed"],
        "correct": bool(ok and not missing and not detail["bad_requests"]
                        and not driver.refused),
        "check": detail,
        "series": {"ticks": summary["ticks"], "requests": summary["requests"]},
        "values": {"pool_dims": pool_dims},
    }


def kv_budget(run, config):
    """Bytes for the KV pool: what the device has left after the model, less
    the configuration's reserve for the decode and prefill programs."""
    serve = config["serve"]
    if "kv_budget_bytes" in serve:
        return serve["kv_budget_bytes"]
    stats = run.devices[0].memory_stats()
    return (stats["bytes_limit"] - stats["bytes_in_use"]
            - serve["hbm_reserve_bytes"])


def warm_spill_shapes(engine, min_pages):
    """The engine spills a preempted row's pages to the host and restores
    them with programs compiled per PAGE COUNT. Under a full pool
    preemption is part of the traffic, so set-up runs both once for every
    count a victim can have — on the still-empty pool, writing back what it
    read."""
    pool = engine.pool
    for count in range(min_pages, engine.P + 1):
        pages = list(range(1, count + 1))
        if count > pool.pages_total:
            break
        pool.restore_pages(pages, pool.read_pages(pages), list(range(count)))
