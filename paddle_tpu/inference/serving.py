"""Continuous-batching generation engine (the serving-engine depth of
reference L13 — fastdeploy/llm serving's dynamic batching scheduler — on
top of the decode path in models/generation.py).

TPU-first design: ONE compiled decode program of fixed shape
[max_batch_size, 1] runs every step regardless of how many requests are
live — slots hold per-row cache offsets (models/gpt.py _dyn_update /
_decode_mask vector-offset path), so admission/retirement never
recompiles. Prefill pads prompts to power-of-two length buckets to bound
compile count. This is the vLLM/fastdeploy scheduling idea expressed as
static shapes + masking instead of dynamic batch reshaping — the form XLA
wants.

Two engines share the scaffolding in `_ServingEngineBase`:

- `PagedServingEngine` (`paddle_tpu.inference.paged`) — THE serving
  engine: block-pool paged KV cache with prefix sharing, preemption and a
  two-queue scheduler; HBM is allocated per page actually used, not per
  slot capacity. See docs/SERVING.md.
- `ContinuousBatchingEngine` (this module) — dense per-slot KV caches,
  every slot reserves max_seq_len rows of HBM. It is the dense REFERENCE
  the paged engine's parity tests compare against token for token
  (tests/test_serving_paged.py, test_serving.py, test_tracing_spans.py),
  not a fallback: nothing selects it and no benchmark cell runs it.
"""

from __future__ import annotations

import collections
import itertools
import time

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax

from ..framework.core import Tensor
from ..observability import spans as _spans
from ..observability.spans import span
from .slo import BoundedCompileCache, serving_metrics

__all__ = ["GenerationRequest", "ContinuousBatchingEngine"]


class GenerationRequest:
    """One prompt in flight."""

    _ids = itertools.count()

    def __init__(self, prompt_ids, max_new_tokens=32, temperature=0.0,
                 eos_token_id=None, priority=0):
        self.req_id = next(self._ids)
        self.prompt = np.asarray(prompt_ids, np.int32).reshape(-1)
        self.max_new_tokens = int(max_new_tokens)
        self.temperature = float(temperature)
        self.eos_token_id = eos_token_id
        # scheduling weight: higher survives preemption longer (paged engine)
        self.priority = int(priority)
        self.generated: list[int] = []
        self.done = False
        # True iff the engine retired this request because the KV cache hit
        # max_seq_len before max_new_tokens/EOS — the output is shorter than
        # asked for (previously this truncation was silent)
        self.truncated = False
        self._t_arrival = time.perf_counter()
        self._t_admit: float | None = None  # set by the admitting engine
        self._t_first: float | None = None
        self.preemptions = 0  # times the paged engine spilled it to the host
        self._sample_key = None  # set by the admitting engine

    @property
    def output_ids(self):
        return np.concatenate([self.prompt,
                               np.asarray(self.generated, np.int32)])


def _bucket(n):
    b = 16
    while b < n:
        b *= 2
    return b


class _ServingEngineBase:
    """Model state, bucketed prefill compilation, sampling and SLO
    bookkeeping shared by the dense and paged engines. Subclasses own the
    KV representation and the admission policy."""

    engine_label = "base"

    def __init__(self, model, max_batch_size=8, max_seq_len=512, seed=0,
                 max_prefill_buckets=None, serve_w8=False):
        model.eval()
        # weight-only int8 serving: swap the model's Linear-family
        # projections for QuantizedLinear before the param/buffer snapshot
        # (construction is trace time for every program this engine
        # compiles), so the decode/prefill programs carry int8 weights + f32
        # scales instead of full-precision weight HBM. In-place on `model`
        # (idempotent) — build a fresh model per engine when A/B-ing.
        self.serve_w8 = bool(serve_w8)
        if self.serve_w8:
            from ..quantization import ptq_convert_for_serving

            ptq_convert_for_serving(model)
        self.model = model
        self.cfg = model.config
        self.B = int(max_batch_size)
        self.S = int(max_seq_len)
        if max_prefill_buckets is None:
            # default: room for EVERY bucket this max_seq_len can produce
            # (16, 32, ..., >=S) — a flat cap smaller than the bucket count
            # would thrash full prefill recompiles on a spread-out prompt
            # mix; pass an explicit cap to bound compiled-program memory
            max_prefill_buckets = 1
            while 16 << (max_prefill_buckets - 1) < self.S:
                max_prefill_buckets += 1
        self.params = {k: p._value for k, p in model.named_parameters()}
        self.buffers = {k: b._value for k, b in model.named_buffers()}
        # KV cache dtype flows from the model: a bf16 model gets bf16 pages
        # instead of silently paying 2x KV bytes through a hardcoded f32
        # default (embeddings stay full precision under serve_w8, so this
        # reads the pre-quantization compute dtype)
        self.kv_dtype = next(
            (jnp.dtype(v.dtype) for v in self.params.values()
             if jnp.issubdtype(v.dtype, jnp.floating)),
            jnp.dtype(jnp.float32))
        # one cache spec per layer where the model is not all attention
        # (inference/paged/block_pool.py); None: every layer keeps K and V
        self.cache_specs = (model.cache_specs()
                            if hasattr(model, "cache_specs") else None)
        self.last_logits = None  # last decode tick's [B, vocab] device array
        # per decode ROW, beside the subclass's caches or block tables: the
        # request seated there, the tokens it has cached, its last token, and
        # what the decode program samples it with (temperature 0: greedy; the
        # request's key stream, which lives here while the row is live)
        self.active: list[GenerationRequest | None] = [None] * self.B
        self.lengths = np.zeros(self.B, np.int32)
        self.last_tok = np.zeros(self.B, np.int32)
        self.temps = np.zeros(self.B, np.float32)
        self.keys = np.zeros((self.B, 2), np.uint32)
        self._greedy_inputs = (jnp.zeros(self.B, jnp.float32),
                               jnp.zeros((self.B, 2), jnp.uint32))
        self.finished: list[GenerationRequest] = []
        self._key = jax.random.PRNGKey(seed)
        self._req_seq = 0  # arrival index, keys each request's sample stream
        self._prefill_programs = BoundedCompileCache(max_prefill_buckets,
                                                     self.engine_label)
        self._decode_jit = None
        self._tick = 0
        m = serving_metrics()
        for name in ("tokens", "sampled_tokens", "requests", "truncations"):
            m[name].inc(0, engine=self.engine_label)  # series exists from t0

    def _make_request(self, prompt_ids, **kw):
        """Construct a request with its own sampling key, folded from the
        engine seed and the ARRIVAL index: sampled output is a function of
        (seed, arrival order, logits) only — invariant to slot assignment,
        batch composition and preemption/resume timing, so the paged and
        dense engines produce identical tokens for the same workload."""
        req = GenerationRequest(prompt_ids, **kw)
        req._sample_key = jax.random.fold_in(self._key, self._req_seq)
        self._req_seq += 1
        return req

    # -- shared forward plumbing ---------------------------------------- #

    def _functional_forward(self, p, b, tok, pos, caches, off, tables=None,
                            **model_kw):
        """The model's `(logits, new_caches)` — and whatever else a model
        asked for through `model_kw` returns behind them."""
        from ..jit import functional_call

        # per-layer cache entries are whatever the layer's kind defines:
        # (k, v), (k, v, k_scale, v_scale) for the quantized paged layout,
        # (conv_state, ssm_state) for a recurrent layer; pass tuples
        # through structurally
        c = [tuple(Tensor(x) for x in layer_c) for layer_c in caches]
        kwargs = {k: Tensor(v) if isinstance(v, jax.Array) else v
                  for k, v in model_kw.items()}
        if tables is not None:   # one table, or one a page group
            kwargs["block_tables"] = jax.tree.map(Tensor, tables)
        out, _ = functional_call(
            self.model, p, b, [Tensor(tok), Tensor(pos), c, Tensor(off)],
            kwargs=kwargs, train=False)
        return out

    def _run_prefill(self, req):
        """Batch-1 prefill of the prompt alone: a prefill from position 0
        reads no cache, so the host builds none. The program's per-request
        inputs are the bucket's tokens and the prompt's length; positions,
        `logits_at` and `seq_lens` are made inside it. Returns (the logits
        [V] of the prompt's last position, on the device, new_caches per
        layer — [1, Sp, Hkv, D] K and V, or a recurrent layer's state after
        token n - 1 — n, the seconds the host took to build and place the
        inputs and those until the jitted call returned). The model cuts the
        hidden state to that one position before its final norm and its head
        (`logits_at`): a bucket's worth of logits is never made."""
        n = len(req.prompt)
        Sp = _bucket(n)

        def compile_prefill():
            def prefill(p, b, tok, n):
                lens = n.reshape(1)
                # a recurrent layer sees the bucket's zero padding, which
                # attention never does: such a model is told the real length
                kw = {"seq_lens": lens} if self.cache_specs is not None else {}
                # `caches` given and empty: every layer returns the prompt's
                logits, new_c = self._functional_forward(
                    p, b, tok, jnp.arange(Sp, dtype=jnp.int32)[None], (),
                    jnp.int32(0), logits_at=lens - 1, **kw)
                return logits[0, 0], new_c

            return jax.jit(prefill)

        pf = self._prefill_programs.get_or_compile(Sp, compile_prefill)
        with span("inputs", rid=req.req_id) as inputs:
            tok = np.zeros((1, Sp), np.int32)
            tok[0, :n] = req.prompt
            tok, length = jax.device_put((tok, np.int32(n)))
        with span("call", rid=req.req_id) as call:
            row, new_c = pf(self.params, self.buffers, tok, length)
        return row, new_c, n, {"inputs_s": inputs.seconds,
                               "call_s": call.seconds}

    # -- sampling -------------------------------------------------------- #

    def _pick_token(self, logits_row, req):
        """A request's FIRST token, at admission, from the prefill's device
        row: argmax or one step of the request's key stream, eagerly; the
        token id and the advanced key cross to the host in one read. Every
        later token is chosen inside the decode program (`_choose_tokens`),
        which continues the stream."""
        if req.temperature == 0.0:
            return int(jnp.argmax(jnp.asarray(logits_row)))
        key, sub = jax.random.split(req._sample_key)
        tok, req._sample_key = jax.device_get((jax.random.categorical(
            sub, jnp.asarray(logits_row) / req.temperature), key))
        return int(tok)

    @staticmethod
    def _choose_tokens(last, temps, keys):
        """Every row's next token, INSIDE the decode program, from the last
        position's `[B, vocab]` logits: `argmax` where `temps [B]` is 0;
        where it is not, one step of the row's own stream in `keys [B, 2]`,
        `key, sub = split(key)` then `categorical(sub, row / temp)` in the
        logits' dtype — what `_pick_token` does, a row at a time, bit for
        bit. Returns (tokens [B] int32, the advanced keys; a greedy row's key
        comes back as it went in). A tick with no sampled row draws nothing:
        the `cond` reads that from `temps`."""
        greedy = jnp.argmax(last, axis=-1).astype(jnp.int32)
        sampled = temps > 0

        def step_stream(key, row, temp):
            key, sub = jax.random.split(key)
            return key, jax.random.categorical(sub, row / temp.astype(row.dtype))

        def draw():
            stepped, drawn = jax.vmap(step_stream)(
                keys, last, jnp.where(sampled, temps, 1.0))
            return (jnp.where(sampled, drawn.astype(jnp.int32), greedy),
                    jnp.where(sampled[:, None], stepped, keys))

        return lax.cond(jnp.any(sampled), draw, lambda: (greedy, keys))

    # -- SLO bookkeeping ------------------------------------------------- #

    def _note_token(self, req, tok):
        m = serving_metrics()
        m["tokens"].inc(engine=self.engine_label)
        if req._t_first is None:
            req._t_first = time.perf_counter()
            m["ttft"].observe(req._t_first - req._t_arrival,
                              engine=self.engine_label)

    def _limits(self, req, generated, row_len):
        """(budget done, capacity hit) once `req` has `generated` tokens and
        its row `row_len` cached: what ends a request whatever its tokens
        are, so the host knows it a tick ahead."""
        return generated >= req.max_new_tokens, row_len + 1 >= self.S

    def _retire_decision(self, req, tok, row_len):
        """(done, truncated) after appending `tok` with `row_len` tokens
        already in the cache. Capacity retirement that cut the request short
        is surfaced as truncation instead of silently ending it."""
        hit_eos = (req.eos_token_id is not None
                   and int(tok) == req.eos_token_id)
        budget_done, cap_hit = self._limits(req, len(req.generated), row_len)
        done = hit_eos or budget_done or cap_hit
        truncated = cap_hit and not hit_eos and not budget_done
        return done, truncated

    def _note_finished(self, req, truncated):
        req.done = True
        m = serving_metrics()
        m["requests"].inc(engine=self.engine_label)
        if truncated:
            req.truncated = True
            m["truncations"].inc(engine=self.engine_label)
        t_done = time.perf_counter()
        if req._t_first is not None and len(req.generated) > 1:
            dt = t_done - req._t_first
            if dt > 0:
                m["request_tps"].observe(len(req.generated) / dt,
                                         engine=self.engine_label)
        if _spans.live():
            # the request's life in one record (queue wait is
            # t_admit - t_arrival); seconds on time.perf_counter
            _spans.record_span(
                "request", int(req._t_arrival * 1e9), int(t_done * 1e9),
                rid=req.req_id, prompt_len=len(req.prompt),
                generated=len(req.generated), t_arrival=req._t_arrival,
                t_admit=req._t_admit, t_first=req._t_first, t_done=t_done,
                preemptions=req.preemptions)
        self.finished.append(req)

    def _record_admission(self, kind, req, row, t0_ns, **attrs):
        """One `admission` record a request that takes a row (`kind`
        "prefill", or "resume" for a spilled one coming back), from `t0_ns`
        to now, ALWAYS written (`spans.record`): the one thing a serving
        window is read for per request and not per traced tick. `attrs`:
        what the admitting engine knows, and its phases' own lengths in
        seconds from the child spans' two clock reads."""
        _spans.record("admission", t0_ns, time.perf_counter_ns(),
                      rid=req.req_id, kind=kind, tick=self._tick, row=row,
                      prompt_len=len(req.prompt), **attrs)

    def run(self):
        """Drain: step until every queued/live request finishes; returns
        the finished requests in completion order."""
        while self.has_work():
            self.step()
        done, self.finished = self.finished, []
        return done

    def step(self) -> dict:
        """One scheduler tick (the subclass's `_step`) under the
        `engine.step` span, whose own two clock reads feed
        `serving_step_seconds`. Returns {req_id: new_token} for the decode
        advance only, of the tick the step READ (the paged engine's was
        dispatched a step before) — each request's FIRST token is emitted
        at admission (onto req.generated and serving_tokens_total), not in
        this dict."""
        self._tick += 1
        with span("engine.step", tick=self._tick) as tick:
            out = self._step(tick)
        if out:
            serving_metrics()["step_seconds"].observe(
                tick.seconds, engine=self.engine_label)
        return out

    # -- rows ------------------------------------------------------------ #

    def _seat(self, row, req, length, last_tok):
        """`req` takes decode row `row` (admitted or resumed) with `length`
        tokens cached; a sampled request's key stream now lives in the row
        (on the host since `_pick_token`; a greedy one's is never read)."""
        self.active[row] = req
        self.lengths[row] = length
        self.last_tok[row] = last_tok
        self.temps[row] = req.temperature
        if req.temperature > 0:
            self.keys[row] = req._sample_key

    def _vacate(self, row):
        """The row's request leaves it (retired or spilled) and takes its
        key stream along, so a resume continues it."""
        if self.temps[row] > 0:
            self.active[row]._sample_key = self.keys[row].copy()
        self.active[row] = None
        self.lengths[row] = 0
        self.temps[row] = 0.0  # an empty row is a greedy row to the program

    def _sampling_inputs(self, sampled):
        """(`temps`, `keys`) on the device for the decode program. A tick
        with no sampled row places nothing: the program's `cond` reads
        zeros that have been there since construction."""
        if len(sampled):   # copies: the host's rows may move on meanwhile
            return (jnp.asarray(self.temps.copy()),
                    jnp.asarray(self.keys.copy()))
        return self._greedy_inputs

    # -- token emission -------------------------------------------------- #

    def _emit(self, row, tok):
        if self._take_token(self.active[row], tok, self.lengths[row]):
            self._release_row(row)

    def _take_token(self, req, tok, row_len) -> bool:
        """`tok` joins `req`'s output, its row holding `row_len` tokens;
        True when that ends the request (then noted finished)."""
        req.generated.append(int(tok))
        self._note_token(req, tok)
        done, truncated = self._retire_decision(req, tok, row_len)
        if done:
            self._note_finished(req, truncated)
        return done

    # subclass contract
    def has_work(self) -> bool:
        raise NotImplementedError

    def _step(self, tick) -> dict:
        raise NotImplementedError

    def _release_row(self, row):
        """Free what a retired request's row held (a subclass: its pages
        too)."""
        self._vacate(row)


class ContinuousBatchingEngine(_ServingEngineBase):
    """Admit-while-decoding scheduler over a slotted DENSE KV cache: the
    reference `PagedServingEngine` is held to in the tests, token for token.

    add_request() enqueues; step() admits waiting requests into free slots
    (prefill) and advances every live slot by one token (single fixed-shape
    decode). run() drains everything and returns finished requests.
    """

    engine_label = "dense"

    def __init__(self, model, max_batch_size=8, max_seq_len=512, seed=0,
                 max_prefill_buckets=None, serve_w8=False):
        super().__init__(model, max_batch_size, max_seq_len, seed,
                         max_prefill_buckets, serve_w8=serve_w8)
        cfg = self.cfg
        self.caches = [
            (jnp.zeros((self.B, self.S, cfg.kv_heads, cfg.head_dim),
                       self.kv_dtype),) * 2
            for _ in range(cfg.num_layers)]
        self.waiting: collections.deque = collections.deque()

    # ------------------------------------------------------------------ #

    def add_request(self, prompt_ids, **kw):
        req = self._make_request(prompt_ids, **kw)
        if len(req.prompt) >= self.S:
            raise ValueError(
                f"prompt length {len(req.prompt)} >= max_seq_len {self.S}")
        self.waiting.append(req)
        return req.req_id

    def has_work(self):
        return bool(self.waiting) or any(r is not None for r in self.active)

    # ------------------------------------------------------------------ #

    def _admit(self):
        free = [i for i in range(self.B) if self.active[i] is None]
        picked = 0
        while free and self.waiting:
            slot = free.pop(0)
            req = self.waiting.popleft()
            picked += 1
            t0_ns = time.perf_counter_ns()
            req._t_admit = time.perf_counter()
            bucket = _bucket(len(req.prompt))
            compiled = bucket not in self._prefill_programs
            with span("prefill", rid=req.req_id, prompt_len=len(req.prompt),
                      bucket=bucket, compiled=compiled) as prefill:
                logits_row, new_c, n, phases = self._run_prefill(req)
                # scatter the prompt's kv into this slot's cache rows [0, n)
                for li, (k_, v_) in enumerate(new_c):
                    bk, bv = self.caches[li]
                    bk = bk.at[slot, :n].set(k_[0, :n])
                    bv = bv.at[slot, :n].set(v_[0, :n])
                    self.caches[li] = (bk, bv)
            with span("first_token", rid=req.req_id) as first_token:
                first = self._pick_token(logits_row, req)
            self._seat(slot, req, n, first)
            self._emit(slot, first)
            # the paged engine's record; this engine has no page and no slot
            self._record_admission(
                "prefill", req, slot, t0_ns, bucket=bucket, compiled=compiled,
                pages_written=0, prefix_hits=0,
                queue_wait_s=req._t_admit - req._t_arrival,
                prefill_s=prefill.seconds, **phases, pages_s=0.0,
                write_pages_s=0.0, write_state_s=0.0,
                first_token_s=first_token.seconds)
        return picked

    # ------------------------------------------------------------------ #

    def _step(self, tick):
        """Admit, then decode-advance all live slots; the paged engine's
        span paths, where the dense engine has the phase."""
        with span("admit") as sp:
            sp.set(picked=self._admit())
        m = serving_metrics()
        live = [i for i in range(self.B) if self.active[i] is not None]
        tick.set(live=len(live), waiting=len(self.waiting))
        m["queue_depth"].set(len(self.waiting),
                             engine=self.engine_label, queue="prefill")
        m["queue_depth"].set(len(live),
                             engine=self.engine_label, queue="decode")
        if not live:
            return {}
        if self._decode_jit is None:
            def decode(p, b, tok, offs, temps, keys, caches):
                pos = offs[:, None]
                logits, new_c = self._functional_forward(
                    p, b, tok[:, None], pos, caches, offs)
                last = logits[:, -1]
                # every row's token picked ON DEVICE: the [B, vocab] logits
                # never cross to the host
                return *self._choose_tokens(last, temps, keys), last, new_c

            self._decode_jit = jax.jit(decode, donate_argnums=(6,))

        sampled = np.flatnonzero(self.temps > 0)  # live rows all: _vacate
        with span("decode_dispatch", rows=len(live),
                  sampled_rows=len(sampled)):
            offs = jnp.asarray(self.lengths)  # per-slot write offset
            tokens, keys, logits, self.caches = self._decode_jit(
                self.params, self.buffers, jnp.asarray(self.last_tok), offs,
                *self._sampling_inputs(sampled), self.caches)
            self.last_logits = logits  # device array; tests probe divergence
        with span("host_read"):
            tokens, keys = jax.device_get((tokens, keys))
        return self._emit_decoded(live, sampled, tokens, keys)

    def _emit_decoded(self, live, sampled, tokens, keys) -> dict:
        """The decoded tick's tail, on the host copy of what the program
        chose: the `sampled` rows keep their advanced keys, every live row's
        length and last token advance, the token is emitted. Returns
        {req_id: token}."""
        out = {}
        with span("emit", rows=len(live), sampled_rows=len(sampled)):
            if len(sampled):
                with span("sample", rows=len(sampled)):
                    self.keys[sampled] = keys[sampled]
                serving_metrics()["sampled_tokens"].inc(
                    len(sampled), engine=self.engine_label)
            for i in live:
                tok = int(tokens[i])
                self.lengths[i] += 1
                self.last_tok[i] = tok
                out[self.active[i].req_id] = tok
                self._emit(i, tok)
        return out
