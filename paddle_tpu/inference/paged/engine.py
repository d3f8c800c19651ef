"""PagedServingEngine: continuous batching over a block-pool paged KV cache.

The dense `ContinuousBatchingEngine` reserves `max_seq_len` cache rows per
slot, so HBM — not compute — caps concurrent users. Here a slot (decode
program row) holds only a block table; physical pages come from the shared
`BlockPool` on demand. Admission is by *pages available* against the
scheduler's watermark, not by slots free, so at equal HBM budget the engine
runs strictly more concurrent requests whenever prompts are shorter than
`max_seq_len` (and more again when they share prefixes).

Fixed shapes throughout, like the dense engine: ONE compiled decode program
of shape [max_batch_size, 1] runs every tick; the block tables and lengths
are data inputs, so admission/retirement/preemption/COW never recompile.
Page-table maintenance (allocation at page boundaries, copy-on-write off
shared pages, preemption spills) happens on host BETWEEN steps — it is per
page-boundary-crossing, never per token.

Preemption: when the pool runs dry mid-decode, the lowest-priority live
request (newest arrival among equals, never the row that triggered the
allocation) has its pages copied to a host spill buffer and released; the
request re-enters through the scheduler's resume queue and continues
decoding from exactly where it stopped — no tokens are lost or recomputed.
Spilled pages that were prefix-shared re-attach by hash on resume when the
shared copy still exists, and are restored from host otherwise.

Layer kinds. A model whose layers are not all attention gives one cache spec
per layer (`model.cache_specs()`, `block_pool.PagedKV` / `RowState`). A
recurrent layer's state is a fixed-size slot per decode row: admission
writes the slot from the prefill (`admit/write_state`), a spill carries it to
the host beside the pages, a resume writes it back, retirement just frees the
row. Admission is then by rows AND pages: a request needs a free row (its
slot) and its pages under the watermark, and whichever runs out first is the
limit. Prefix hits save such a model only the page writes: the prefill
always runs, because the state after the prompt is the request's own.

What a page IS belongs to its spec (`PagedKV`, `WindowKV`, `LatentKV`): the
arrays a layer keeps and their shapes, the bytes a page costs, the decode
kernel's pages per grid step. The engine asks the spec wherever bytes or
shapes matter (`kv_budget_bytes` -> `num_pages`, the page stacking of a
prefill, the `decode_dispatch` span's grid) and handles every paged kind
alike on the host: a latent-attention model's pages hold one latent and one
rotated key a token, and nothing here knows.

Pages of two shapes (a model whose sliding layers keep more KV heads than
its full ones; `block_pool` module docstring): the pool counts in UNITS of
the smaller page, a block table holds each page's handle (its first unit;
the decode program gets `handle // span`, the page's index in the layer's
own view of the arrays), and admission charges a request its units AND the
whole blocks its larger pages need (`_cost`: the scheduler compares both
with what is free, so a prompt is not admitted onto free bytes that lie
unpaired). A pool of one shape is charged in pages, as a plain number.

One decode tick in flight. Every row's next token is drawn inside the decode
program, so the inputs of tick N+1 exist on the device when tick N ends, and
what ends a request other than an EOS (its budget, the row's capacity) is
known on the host a tick ahead. `step()` therefore dispatches tick N+1 BEFORE
it reads tick N (`_step`): the host's page upkeep, the program's call, the
emit loop and whatever the caller does between steps run while the device
decodes. A step reads N first (it drains) only where N's tokens decide what
N+1 is: an admission the scheduler plans now, or write targets the pool
cannot give without a preemption. Each is decided once, by the function
whose answer the step then carries out: `TwoQueueScheduler.plan` (`pick`
takes exactly its admissions), `_target_plan` (`_write_targets` makes it)
and the allocator's walk (`BlockPool.fits`; `alloc` takes what it says).
Admissions, spills and tokens are those of a step that read each tick
before the next. A row whose request ends at N by an EOS was already put
into N+1: its N+1 token is dropped, and its pages go back at the next step.
"""

from __future__ import annotations

import dataclasses
import time

import jax
import jax.numpy as jnp
import numpy as np

from ...observability.spans import span
from ..serving import _bucket, _ServingEngineBase
from ..slo import serving_metrics
from .block_pool import (BlockPool, PagedKV, RowState, page_layout,
                         prefix_page_key)
from .scheduler import TwoQueueScheduler, _pages_for_prompt

__all__ = ["PagedServingEngine", "SpilledRequest"]


class SpilledRequest:
    """A preempted request parked on host: generation state, the contents
    of its pages and of its recurrent-state slot, enough to resume without
    recomputing anything."""

    __slots__ = ("req", "length", "last_tok", "kv_host", "keys",
                 "state_host", "group_pages", "window_start", "n_pages")

    def __init__(self, req, length, last_tok, kv_host, keys, state_host=(),
                 group_pages=None, window_start=0, cost=None):
        self.req = req
        self.length = int(length)
        self.last_tok = int(last_tok)
        self.kv_host = kv_host   # per page array (k, v) np [m, Hkv, ps, D]
        # per page, the groups' pages one after the other: prefix key or None
        self.keys = keys
        # per recurrent layer the row's slot, np arrays (BlockPool.read_state)
        self.state_host = state_host
        # pages held per page group, and the first logical page the window
        # groups still held
        self.group_pages = (list(group_pages) if group_pages is not None
                            else [len(keys)])
        self.window_start = int(window_start)
        # what its readmission is charged (`PagedServingEngine._cost`)
        self.n_pages = len(keys) if cost is None else cost


@dataclasses.dataclass(slots=True)
class _Tick:
    """A decode tick dispatched and not read yet: its rows and their
    requests, the lengths it was given (a copy: the host's move on), its
    sampled rows, and the program's outputs that are not the pool — the
    tokens and keys (the next tick's inputs, where it follows on the
    device), the routing counts and the logits."""

    rows: list
    reqs: list
    lengths: np.ndarray
    sampled: np.ndarray
    tokens: jax.Array
    keys: jax.Array
    stats: list
    logits: jax.Array


class PagedServingEngine(_ServingEngineBase):
    """Admit-while-decoding over paged KV with prefix sharing + preemption.

    Same surface as the dense engine (`add_request` / `step` / `run`), plus:
    `page_size`, `num_pages` (default: the dense engine's HBM budget,
    `max_batch_size * max_seq_len` tokens worth of pages), `prefix_sharing`,
    `watermark_pages`, `preemption`, and the quantized fast path:
    `kv_quant` (fixed at construction — trace time for the decode
    program) stores int8 pages + per-(page, head) f32 scales and decodes
    through the dequant-fused Pallas kernel; `kv_budget_bytes` sizes the
    pool by HBM bytes instead of page count (the equal-budget A/B knob — an
    int8 pool fits ~4x the pages of an f32 one in the same budget).
    """

    engine_label = "paged"

    def __init__(self, model, max_batch_size=8, max_seq_len=512, seed=0,
                 page_size=16, num_pages=None, prefix_sharing=True,
                 watermark_pages=None, preemption=True,
                 max_prefill_buckets=None, kv_quant=False,
                 kv_budget_bytes=None, serve_w8=False):
        super().__init__(model, max_batch_size, max_seq_len, seed,
                         max_prefill_buckets, serve_w8=serve_w8)
        cfg = self.cfg
        self.ps = int(page_size)
        self.P = _pages_for_prompt(self.S, self.ps)  # block-table width
        self.kv_quant = bool(kv_quant)
        if num_pages is not None and kv_budget_bytes is not None:
            raise ValueError(
                "pass num_pages OR kv_budget_bytes, not both — a page count "
                "would silently override the byte budget and break the "
                "equal-budget A/B contract")
        # what a page IS is its spec's to say (block_pool.PagedKV,
        # WindowKV, LatentKV): arrays, bytes, the decode kernel's grid
        specs = (self.cache_specs
                 or [PagedKV(cfg.kv_heads, cfg.head_dim)] * cfg.num_layers)
        groups = page_layout(specs)[0]
        if num_pages is None:
            if kv_budget_bytes is not None:
                # the pool's unit: the smallest page, in all its arrays
                unit = min(groups, key=lambda g: g.span)
                page_b = len(unit.layers) * unit.spec.page_nbytes(
                    self.ps, self.kv_dtype, self.kv_quant)
                # budget covers the whole pool, reserved null page included,
                # and first of all every row's recurrent-state slot
                slots = self.B * sum(
                    s.row_nbytes(self.kv_dtype) for s in specs
                    if isinstance(s, RowState))
                num_pages = (int(kv_budget_bytes) - slots) // page_b
                if num_pages < 2:
                    raise ValueError(
                        f"kv_budget_bytes={int(kv_budget_bytes)} fits "
                        f"{num_pages} pages at {page_b} bytes/page; need >= 2 "
                        "(the reserved null page plus one allocatable) — a "
                        "silently enlarged pool would break the equal-budget "
                        "A/B contract")
            else:
                num_pages = (self.B * self.S) // self.ps + 1  # +1: null page
        self.pool = BlockPool(len(specs), page_size=self.ps,
                              num_pages=num_pages, dtype=self.kv_dtype,
                              prefix_sharing=prefix_sharing,
                              quantized=self.kv_quant, specs=specs,
                              rows=self.B)
        if self.pool.state_layers:
            # the slot's two programs compile now, on the empty pool: a
            # preemption must not compile in the middle of serving
            self.pool.write_state(0, self.pool.read_state(0))
        # one block table per page group (`block_pool.page_layout`): a full
        # group's is as wide as a row can grow, a window group's as its
        # window. `tables` is the first group's, and the only one of a model
        # whose paged layers are of one kind. `window_start[row]`: the first
        # logical page the row's window groups still hold (slot 0 of theirs)
        self.groups = self.pool.groups
        self.group_tables = [
            np.full((self.B, min(self.P, g.spec.table_width(self.ps))
                     if g.window else self.P), -1, np.int32)
            for g in self.groups]
        self.tables = self.group_tables[0]
        self.window_start = np.zeros(self.B, np.int32)
        windows = {g.spec for g in self.groups if g.window}
        if len(windows) > 1:
            raise ValueError("window layers of several window lengths in "
                             "one model are not supported")
        # the window layers' one spec, None for a model without any
        self._window = windows.pop() if windows else None
        self._windowed = self._window is not None
        self._window_released = 0   # window pages released, lifetime
        # a model whose pages are not all one kind of full K and V reports
        # its live pages by kind, and a latent one what its kernel must read
        self._latent = any(g.spec.kind == "latent" for g in self.groups)
        kinds = sorted({g.spec.kind for g in self.groups})
        self._page_kinds = kinds if kinds != ["full"] else []
        # a pool of two shapes is charged in (units, whole blocks)
        total = self.pool.pages_total
        self._capacity = (total if self.pool.span == 1 else
                          np.array([total, total // self.pool.span]))
        self.sched = TwoQueueScheduler(
            self.ps, watermark_pages,
            pages_for=lambda n: self._cost(self._prompt_by_group(n)),
            groups=self._cost([1] * len(self.groups)))
        self.preemption = bool(preemption)
        self._inflight = None   # the `_Tick` on the device, unread
        self._targets = []   # the write targets `_must_drain` planned
        self._stack = None
        # the paged-decode kernels' grids, for the `decode_dispatch` span:
        # the steps a call's table holds are static, so computed once; the
        # steps a call walks are the tick's live ones (`_live_grid_steps`)
        pages0 = self.pool.kv[self.pool.page_entries[0]][0]
        self._decode_grid = {}
        self._grid_kinds = {}   # tag -> (kind of page group, window or None)
        for g, table in zip(self.groups, self.group_tables):
            width = table.shape[1]
            n = g.spec.pages_per_step(self.ps, width, pages0.dtype.itemsize)
            tag = "window_" if g.window else ""
            self._decode_grid.update({
                tag + "pages_per_step": n,
                tag + "grid_steps": self.B * -(-width // n)})
            self._grid_kinds[tag] = (
                g.spec.kind, g.spec.window if g.window else None)
        # a model with routed experts counts its routing in the decode
        # program (incubate/.../held_moe.STAT_NAMES)
        self._moe_groups = getattr(model, "moe_groups", 0)
        self.pool.update_gauges()
        # materialize the pool/preemption series at zero so an exported
        # snapshot carries them from the first tick, not only after the first
        # event (a dashboard must distinguish "no preemptions" from
        # "no data")
        m = serving_metrics()
        for name in ("preemptions", "resumes", "preempted_pages",
                     "prefix_hits", "prefix_lookups", "cow_copies",
                     "kv_quant_pages"):
            m[name].inc(0)
        if self._windowed:
            m["window_pages_released"].inc(0)
        if self.pool.span > 1:
            for kind in kinds:
                m["pool_alloc_refused"].inc(0, kind=kind)
        if self._moe_groups:
            for name in ("moe_routed_pairs_held", "moe_dropped_pairs"):
                m[name].inc(0)
            for path in ("compact", "whole"):
                m["moe_passes"].inc(0, path=path)
        for path in ("overlapped", "drained"):
            m["decode_ticks"].inc(0, path=path)

    # ------------------------------------------------------------------ #

    def add_request(self, prompt_ids, **kw):
        req = self._make_request(prompt_ids, **kw)
        n = len(req.prompt)
        if n >= self.S:
            raise ValueError(
                f"prompt length {n} >= max_seq_len {self.S}")
        # lifetime page need (capacity retirement caps a row at S tokens)
        worst = self._cost(
            self._held_by_group(min(self.S, n + req.max_new_tokens)))
        if np.any(worst > self._capacity):
            raise ValueError(
                f"request needs up to {worst} pages but the pool only has "
                f"{self.pool.pages_total}; grow num_pages or shrink the "
                "request")
        self.sched.enqueue_prefill(req)
        return req.req_id

    def _held_by_group(self, length) -> list:
        """Pages a row with `length` tokens cached holds at most, a group:
        a full group's grow with it, a window group's stop at its table's
        width."""
        m = _pages_for_prompt(length, self.ps)
        return [min(m, t.shape[1]) for t in self.group_tables]

    def _held_pages(self, length) -> int:
        return sum(self._held_by_group(length))

    def _prompt_by_group(self, n) -> list:
        """Pages the admission of an `n`-token prompt takes, a group: a
        window group only those of the prompt's last window."""
        m = _pages_for_prompt(n, self.ps)
        return [m - (g.spec.first_page(n, self.ps) if g.window else 0)
                for g in self.groups]

    def _prompt_pages(self, n) -> int:
        return sum(self._prompt_by_group(n))

    def _cost(self, counts):
        """What `counts` pages a group cost the pool, in what the scheduler
        compares with what is free: pages, of a pool of one shape; (units,
        whole blocks) of a pool of two, since a page of the larger shape
        needs its units side by side."""
        if self.pool.span == 1:
            return sum(counts)
        return np.array(
            [sum(c * g.span for c, g in zip(counts, self.groups)),
             sum(c for c, g in zip(counts, self.groups)
                 if g.span == self.pool.span)])

    def _free(self):
        """What the pool has free, as `_cost` counts."""
        if self.pool.span == 1:
            return self.pool.pages_free
        return np.array([self.pool.pages_free, self.pool.blocks_free])

    def has_work(self):
        return (self._inflight is not None or self.sched.has_waiting()
                or any(r is not None for r in self.active))

    @property
    def live_count(self) -> int:
        return sum(r is not None for r in self.active)

    # -- allocation / preemption ---------------------------------------- #

    def _alloc_or_preempt(self, requester_row=None, span=1) -> int:
        while True:
            page = self.pool.alloc(span)
            if page is not None:
                return page
            # a spill takes the row's last token: never with a tick unread
            assert self._inflight is None, "preemption with a tick in flight"
            if not self.preemption or not self._preempt_lowest(requester_row):
                raise RuntimeError(
                    "KV page pool exhausted with no preemptible request; "
                    "pool is too small for the admitted working set")

    def _preempt_lowest(self, exclude_row) -> bool:
        """Spill the lowest-priority live request (newest arrival among
        equals; never `exclude_row`, whose allocation triggered this)."""
        candidates = [i for i in range(self.B)
                      if self.active[i] is not None and i != exclude_row]
        if not candidates:
            return False
        victim = min(candidates,
                     key=lambda i: (self.active[i].priority,
                                    -self.active[i].req_id))
        self._spill_row(victim)
        return True

    def _row_pages(self, row):
        """The row's pages per group, each in logical order."""
        return [[int(p) for p in t[row] if p >= 0] for t in self.group_tables]

    def _spill_row(self, row):
        req = self.active[row]
        by_group = self._row_pages(row)
        pages = [p for group in by_group for p in group]
        with span("spill", rid=req.req_id, pages=len(pages),
                  **self._state_attrs()):
            kv_host = self.pool.read_pages(self.pool.units_of(pages))
            state_host = self.pool.read_state(row)
            keys = [self.pool.page_key(p) for p in pages]
            for p in pages:
                self.pool.release(p)
        req.preemptions += 1
        self.sched.enqueue_resume(SpilledRequest(
            req, self.lengths[row], self.last_tok[row], kv_host, keys,
            state_host, [len(g) for g in by_group], self.window_start[row],
            self._cost([len(g) for g in by_group])))
        self._clear_tables(row)
        self._vacate(row)
        m = serving_metrics()
        m["preemptions"].inc()
        m["preempted_pages"].inc(len(pages))

    def _clear_tables(self, row):
        for t in self.group_tables:
            t[row, :] = -1
        self.window_start[row] = 0

    def _state_attrs(self):
        """Span attributes of a spill or a resume that moves a slot."""
        if not self.pool.state_layers:
            return {}
        return {"state_bytes": self.pool.state_row_nbytes}

    def _release_row(self, row):
        for group in self._row_pages(row):
            for p in group:
                self.pool.release(p)
        self._clear_tables(row)
        self._vacate(row)

    # -- admission ------------------------------------------------------- #

    def _admit(self) -> int:
        """Admit what the scheduler picks; returns how many."""
        free_rows = [i for i in range(self.B) if self.active[i] is None]
        work = self.sched.pick(len(free_rows), self._free(),
                               self.B - len(free_rows))
        for item in work:
            row = free_rows.pop(0)
            if isinstance(item, SpilledRequest):
                self._resume_into(row, item)
            else:
                self._prefill_into(row, item)
        return len(work)

    def _stack_pages(self, kv_layers, n):
        """Per layer the arrays a prefill returns for it, each [1, Sp, ...]
        ((k, v), each [1, Sp, Hkv, D]; a latent layer's one [1, Sp, W]) ->
        per layer the same arrays page-stacked over the whole bucket
        ([mb, Hkv, ps, D]; [mb, ps, W]), `mb` = ceil(Sp / ps), zero behind
        the prompt's `n` tokens. ONE program a bucket, the length is data."""
        if self._stack is None:
            ps = self.ps

            def stack(kv_layers, n):
                def one(a):
                    a = a[0]
                    sp, rest = a.shape[0], a.shape[1:]
                    real = (jnp.arange(sp) < n).reshape((sp,) + (1,) * len(rest))
                    a = jnp.pad(jnp.where(real, a, 0),
                                ((0, -sp % ps),) + ((0, 0),) * len(rest))
                    # a page's token axis lies second to last
                    return jnp.moveaxis(a.reshape((-1, ps) + rest), 1, -2)

                return [tuple(one(a) for a in layer) for layer in kv_layers]

            self._stack = jax.jit(stack)
        return self._stack(kv_layers, np.int32(n))

    def _prefill_into(self, row, req):
        t0_ns = time.perf_counter_ns()
        rid, n = req.req_id, len(req.prompt)
        req._t_admit = time.perf_counter()
        bucket = _bucket(n)
        compiled = bucket not in self._prefill_programs
        with span("prefill", rid=rid, prompt_len=n, bucket=bucket,
                  compiled=compiled, **self._prefill_attrs(bucket)) as prefill:
            logits_row, new_c, n, phases = self._run_prefill(req)
        m = _pages_for_prompt(n, self.ps)
        mb = _pages_for_prompt(bucket, self.ps)
        tables, masks = [], []
        with span("pages", rid=rid, pages=self._prompt_pages(n)) as pages_sp:
            for gi, group in enumerate(self.groups):
                # a window group takes the prompt's last window only, and
                # its pages are the row's own: no key, no registry
                first = (group.spec.first_page(n, self.ps) if group.window
                         else 0)
                pages = np.zeros(mb, np.int32)
                mask = np.zeros(mb, bool)
                for j in range(first, m):
                    key = None
                    if not group.window:
                        # a second full group's page of the same prefix is
                        # another page: the group is part of its key
                        key = (prefix_page_key(req.prompt, j, self.ps)
                               + (bytes([gi]) if gi else b""))
                    page = self.pool.lookup_prefix(key)
                    if page is None:
                        page = self._alloc_or_preempt(span=group.span)
                        if key is not None:
                            self.pool.register_prefix(key, page)
                        mask[j] = True
                    pages[j] = page
                tables.append(pages[first:m])
                masks.append((pages, mask))
            written = sum(int(k.sum()) for _, k in masks)
            hits = sum(len(t) for t in tables) - written
            pages_sp.set(prefix_hits=hits)
        write_pages_s = write_state_s = 0.0
        if written:
            with span("write_pages", rid=rid, pages_written=written) as sp:
                for group, (pages, mask) in zip(self.groups, masks):
                    if not mask.any():
                        continue
                    stacked = self._stack_pages(
                        [new_c[li] for li in group.layers], n)
                    self.pool.write_prompt_pages(pages, mask, *zip(*stacked),
                                                 span=group.span)
            write_pages_s = sp.seconds
        if self.pool.state_layers:
            with span("write_state", rid=rid, row=row) as sp:
                self.pool.write_state(
                    row, [new_c[li] for li in self.pool.state_layers])
            write_state_s = sp.seconds
        for group, table, pages in zip(self.groups, self.group_tables,
                                       tables):
            table[row, :len(pages)] = pages
            if group.window:
                self.window_start[row] = group.spec.first_page(n, self.ps)
        with span("first_token", rid=rid) as first_token:
            # the host waits for the prefill here
            first = self._pick_token(logits_row, req)
        self._seat(row, req, n, first)
        self._emit(row, first)
        self._record_admission(
            "prefill", req, row, t0_ns, bucket=bucket, compiled=compiled,
            pages_written=written, prefix_hits=hits,
            queue_wait_s=req._t_admit - req._t_arrival,
            prefill_s=prefill.seconds, **phases, pages_s=pages_sp.seconds,
            write_pages_s=write_pages_s, write_state_s=write_state_s,
            first_token_s=first_token.seconds)

    def _prefill_attrs(self, bucket):
        """What a model adds to the `prefill` span (`prefill_span_attrs`:
        an expert layer's chunks)."""
        attrs = getattr(self.model, "prefill_span_attrs", None)
        return attrs(bucket) if attrs is not None else {}

    def _resume_into(self, row, sp: SpilledRequest):
        t0_ns = time.perf_counter_ns()
        pages, restore_rows, restore_pages = [], [], []
        state = self._state_attrs()
        with span("resume", rid=sp.req.req_id, **state) as resume:
            # the spilled pages' units lie one after the other in `kv_host`
            spans = [g.span for g, count in zip(self.groups, sp.group_pages)
                     for _ in range(count)]
            at = 0
            for key, units in zip(sp.keys, spans):
                page = self.pool.lookup_prefix(key)
                if page is None:
                    page = self._alloc_or_preempt(span=units)
                    if key is not None:
                        self.pool.register_prefix(key, page)
                    restore_rows.extend(range(at, at + units))
                    restore_pages.append(page)
                pages.append(page)
                at += units
            self.pool.restore_pages(self.pool.units_of(restore_pages),
                                    sp.kv_host, restore_rows)
            self.pool.write_state(row, sp.state_host)
            resume.set(pages_restored=len(restore_pages))
        at = 0
        for table, count in zip(self.group_tables, sp.group_pages):
            table[row, :count] = pages[at:at + count]
            at += count
        self.window_start[row] = sp.window_start
        self._seat(row, sp.req, sp.length, sp.last_tok)
        serving_metrics()["resumes"].inc()
        self._record_admission(
            "resume", sp.req, row, t0_ns, resume_s=resume.seconds,
            pages_restored=len(restore_pages),
            state_bytes=state.get("state_bytes", 0))

    # -- decode write-target maintenance -------------------------------- #

    def _target_plan(self, rows) -> list:
        """What `rows` need, one after the other, to write their next K and
        V in every group: (row, group, start, passed, slot, page, act) a row
        and group that needs anything, in that order. A window group first
        releases the `passed` pages the row's length has left behind and
        moves its table on (the row's window groups then start at logical
        page `start`); then the `slot`'s `page` (-1: none) is allocated
        ("alloc"), copied to a page of the row's own where it is shared
        ("copy"), or taken out of the prefix registry before its first
        divergent write ("unregister"). The refcounts are followed as the
        earlier rows' copies change them: a page two rows share is the
        second's own by its turn."""
        plan, copied = [], {}
        for row in rows:
            L = int(self.lengths[row])
            start = (self._window.first_page(L, self.ps) if self._windowed
                     else 0)
            gone = max(0, start - int(self.window_start[row]))
            for gi, table in enumerate(self.group_tables):
                slot, passed = L // self.ps, 0
                if self.groups[gi].window:   # the table moves on first
                    slot, passed = slot - start, gone
                at = slot + passed   # where the slot's page lies before
                page = int(table[row, at]) if at < table.shape[1] else -1
                act = None
                if page < 0:
                    act = "alloc"
                elif self.pool.ref[page] - copied.get(page, 0) > 1:
                    act, copied[page] = "copy", copied.get(page, 0) + 1
                elif self.pool.is_registered(page):
                    act = "unregister"
                if act or passed:
                    plan.append((row, gi, start, passed, slot, page, act))
        return plan

    def _update_page_gauges(self):
        """Live pages by kind of group, for a model whose pages are not all
        full K and V."""
        m = serving_metrics()
        for kind in self._page_kinds:
            held = [(int((t >= 0).sum()), grp.span)
                    for grp, t in zip(self.groups, self.group_tables)
                    if grp.spec.kind == kind]
            m["pages_live"].set(sum(n for n, _ in held), kind=kind)
            m["pool_bytes_live"].set(
                sum(n * span for n, span in held) * self.pool.bytes_per_page,
                kind=kind)

    def _live_grid_steps(self, live) -> dict:
        """The grid steps ONE call of each kind of paged decode kernel walks
        this tick (`decode_attention.work_list`'s count, from the live rows'
        lengths alone: their tables have no hole), for the `decode_dispatch`
        span; their share of the table's steps goes to the histogram."""
        from ...ops.pallas.decode_attention import live_step_count

        ctx = self.lengths[live].astype(np.int64) + 1
        shares = serving_metrics()["decode_live_step_share"]
        out = {}
        for tag, (kind, window) in self._grid_kinds.items():
            # a window group's table and lengths start at the row's first
            # cached page
            seen = (ctx if window is None
                    else ctx - self.window_start[live].astype(np.int64)
                    * self.ps)
            steps = live_step_count(
                seen, self.ps, self._decode_grid[tag + "pages_per_step"],
                window)
            out[tag + "live_grid_steps"] = steps
            shares.observe(steps / self._decode_grid[tag + "grid_steps"],
                           kind=kind)
        return out

    def _note_routing(self, stats):
        """One decode tick's routing counts (held_moe.STAT_NAMES, summed
        over the layers) into the serving metrics."""
        (pairs, rows_max, rows_sum, dropped, tile_rows, passes,
         whole) = (int(v) for v in stats)
        m = serving_metrics()
        m["moe_routed_pairs_held"].inc(pairs)
        m["moe_dropped_pairs"].inc(dropped)
        m["moe_passes"].inc(passes - whole, path="compact")
        m["moe_passes"].inc(whole, path="whole")
        m["moe_expert_rows_max"].observe(rows_max)
        m["moe_expert_rows_mean"].observe(rows_sum / self._moe_groups)
        m["moe_rows_live"].observe(rows_sum)
        m["moe_rows_tiled"].observe(tile_rows)

    # ------------------------------------------------------------------ #

    def _decode_program(self):
        """The ONE compiled decode program: every row advances by a token;
        tables, lengths, last tokens, temperatures and keys are data."""
        stats_kw = {"with_stats": True} if self._moe_groups else {}

        def decode(p, b, tok, offs, tables, temps, keys, caches, *starts):
            pos = offs[:, None]
            # a model with window layers is told each row's first cached
            # position of theirs; `tables` is then one table a page group
            kw = {"window_starts": starts[0]} if starts else {}
            logits, new_c, *stats = self._functional_forward(
                p, b, tok[:, None], pos, caches, offs, tables=tables,
                **stats_kw, **kw)
            last = logits[:, -1]
            # every row's token picked ON DEVICE, greedy or sampled; the
            # [B, vocab] logits stay there. A model's routing counts ride
            # beside the tokens and the keys: one host read
            return *self._choose_tokens(last, temps, keys), last, new_c, stats

        return jax.jit(decode, donate_argnums=(7,))

    def _live_rows(self) -> list:
        return [i for i in range(self.B) if self.active[i] is not None]

    def _step(self, tick):
        """One decode tick in flight (module docstring). With tick N on the
        device: the rows move past N on the host (`_advance`); unless N's
        tokens decide what comes next (`_must_drain`), N+1's write targets
        are made as that question planned them (a decision is made once,
        and carried out as made), N+1 dispatched, and THEN N read and
        emitted. A drained step reads N first and goes on as a step with
        nothing in flight: admit what `sched.plan` allows, plan and make
        the write targets, dispatch. Returns the tokens of the tick read
        ({} when none was in flight)."""
        prev, out = self._inflight, {}
        if prev is not None:
            self._advance(prev)
            if self._must_drain():
                out = self._read(prev, ahead=False)
                prev = self._inflight = None
        if prev is None:
            with span("admit") as sp:
                sp.set(picked=self._admit())
        live = self._live_rows()
        tick.set(live=len(live), waiting=self.sched.waiting_prefill)
        self.sched.update_gauges(self.engine_label, len(live))
        self.pool.update_gauges()
        if self.pool.state_layers:
            serving_metrics()["state_rows_live"].set(len(live))
        if live:
            # overlapped: nothing has moved since `_must_drain` planned
            self._write_targets(live, None if prev is None else self._targets)
            live = self._live_rows()   # a write target may have spilled
        nxt = self._dispatch(live, prev) if live else None
        if nxt is not None:
            serving_metrics()["decode_ticks"].inc(
                path="drained" if prev is None else "overlapped")
        tick.set(overlapped=prev is not None and nxt is not None)
        if prev is not None:
            out = self._read(prev, ahead=nxt is not None)
        self._inflight = nxt
        self.pool.update_gauges()
        return out

    def _advance(self, prev):
        """The host's rows move past the tick in flight before its tokens
        are read: a row whose request it ends (its budget or the row's
        capacity, known here; or an EOS read a step ago) gives its pages and
        its row back now — every later use of the pool follows that tick on
        the device — and every other row counts the token it caches."""
        for i, req in zip(prev.rows, prev.reqs):
            if req.done or any(self._limits(
                    req, len(req.generated) + 1, int(prev.lengths[i]) + 1)):
                self._release_row(i)
            else:
                self.lengths[i] += 1

    def _must_drain(self) -> bool:
        """Whether the tick in flight must be read before the next is
        dispatched: the scheduler plans an admission now (a first token is
        drawn on the host, from the prefill), or the live rows' write
        targets (`_target_plan`, kept for `_step`) need pages the pool does
        not have (a spill takes a row's last token)."""
        live = self._live_rows()
        if self.sched.plan(self.B - len(live), self._free(), len(live)):
            return True
        self._targets = self._target_plan(live)
        return not self.pool.fits([self.groups[gi].span
                                   for _, gi, *_, act in self._targets
                                   if act in ("alloc", "copy")])

    def _write_targets(self, live, plan=None):
        """Make `plan` (`_target_plan`; None: the live rows' now). A page
        the pool lacks spills the lowest-priority other row (nothing is in
        flight then), and what is left is planned again."""
        with span("write_targets") as sp:
            allocs, cows = self.pool.allocs_total, self.pool.cow_copies_total
            released = self._window_released
            plan = self._target_plan(live) if plan is None else plan
            i = 0
            while i < len(plan):
                row, gi, start, passed, slot, page, act = plan[i]
                i, table = i + 1, self.group_tables[gi]
                if passed:
                    for p in table[row, :passed]:
                        self.pool.release(int(p))
                    table[row, :-passed] = table[row, passed:]
                    table[row, -passed:] = -1
                    self._window_released += passed
                spills = self.sched.waiting_resume
                if act == "unregister":
                    self.pool.unregister_page(page)
                elif act is not None:
                    new = self._alloc_or_preempt(row, self.groups[gi].span)
                    if act == "copy":
                        self.pool.copy_page(page, new)
                        self.pool.release(page)
                    table[row, slot] = new
                if gi == len(self.groups) - 1:
                    self.window_start[row] = start
                if self.sched.waiting_resume > spills:   # a row spilled
                    plan = [t for t in self._target_plan(   # what is left
                        [r for r in self._live_rows() if r >= row])
                        if t[0] > row or t[1] > gi]
                    i = 0
            sp.set(pages_allocated=self.pool.allocs_total - allocs,
                   cow_copies=self.pool.cow_copies_total - cows)
            if self._windowed:
                gone = self._window_released - released
                sp.set(window_pages_released=gone)
                serving_metrics()["window_pages_released"].inc(gone)
            if self._page_kinds:
                self._update_page_gauges()

    def _dispatch(self, live, prev) -> _Tick:
        """Call the decode program on the live rows; their last tokens and
        keys are `prev`'s outputs on the device where a tick is in flight,
        the host's otherwise. Returns the tick, unread."""
        if self._decode_jit is None:
            self._decode_jit = self._decode_program()
        state_rows = ({"state_rows": len(live)} if self.pool.state_layers
                      else {})
        if self._windowed:
            # what the two decode kernels must read this tick, in tokens:
            # every row's context, and of it what its window still holds
            ctx = self.lengths[live].astype(np.int64) + 1
            state_rows.update(
                context_tokens=int(ctx.sum()),
                window_tokens=int(np.minimum(ctx, self._window.window).sum()))
        if self._latent:
            # what `decode_latent` must read this tick, in tokens
            state_rows.update(latent_tokens=int(
                self.lengths[live].astype(np.int64).sum() + len(live)))
        sampled = np.flatnonzero(self.temps > 0)  # live rows all: _vacate
        with span("decode_dispatch", rows=len(live),
                  sampled_rows=len(sampled), **self._decode_grid,
                  **self._live_grid_steps(live), **state_rows):
            # quantized pool: each layer's cache rides as (k, v, k_scale,
            # v_scale) so the int8 append + dequant-fused attention see
            # payload and scales together inside the one compiled program
            caches = ([kv + sc
                       for kv, sc in zip(self.pool.kv, self.pool.scales)]
                      if self.kv_quant else self.pool.kv)
            # copies: the host moves its arrays on while the tick runs
            lengths = self.lengths.copy()
            if not self._windowed:   # one group: one table, no starts
                tables, starts = jnp.asarray(self.tables.copy()), ()
            else:
                # a layer of span k reads the arrays k units a page: its
                # page's index there is handle // k (a hole stays -1)
                tables = tuple(
                    jnp.asarray(t.copy() if g.span == 1 else t // g.span)
                    for g, t in zip(self.groups, self.group_tables))
                starts = (jnp.asarray(self.window_start * self.ps),)
            temps, keys = self._sampling_inputs(sampled)
            if prev is None:
                tok = jnp.asarray(self.last_tok.copy())
            else:
                # no row was seated since `prev`: its rows' tokens and
                # streams are this tick's, where the device left them
                tok = prev.tokens
                if len(sampled):
                    keys = prev.keys
            tokens, keys, logits, new_kv, stats = self._decode_jit(
                self.params, self.buffers, tok, jnp.asarray(lengths), tables,
                temps, keys, caches, *starts)
            if self.kv_quant:
                self.pool.kv = [tuple(c[:2]) for c in new_kv]
                self.pool.scales = [tuple(c[2:]) for c in new_kv]
            else:
                self.pool.kv = [tuple(c) for c in new_kv]
        return _Tick(live, [self.active[i] for i in live], lengths, sampled,
                     tokens, keys, stats, logits)

    def _read(self, prev, ahead) -> dict:
        """Wait for `prev` and emit its tokens; `ahead`: the next tick is
        already on the device with `prev`'s rows in it, so a row whose
        request an EOS ends here keeps its pages until the next step (its
        next token is dropped there). Returns {req_id: token}."""
        with span("host_read"):  # the host waits for the decode here
            tokens, keys, stats = jax.device_get(
                (prev.tokens, prev.keys, prev.stats))
        self.last_logits = prev.logits  # device array; tests probe divergence
        if stats:
            self._note_routing(stats[0])
        out = {}
        with span("emit", rows=len(prev.rows),
                  sampled_rows=len(prev.sampled)):
            # a row still seated with the request the tick decoded for
            rows = [(i, r, self.active[i] is r)
                    for i, r in zip(prev.rows, prev.reqs)]
            if len(prev.sampled):
                drawn = [(i, s) for i, r, s in rows
                         if r.temperature > 0 and not r.done]
                with span("sample", rows=len(prev.sampled)):
                    keep = [i for i, s in drawn if s]
                    self.keys[keep] = keys[keep]
                serving_metrics()["sampled_tokens"].inc(
                    len(drawn), engine=self.engine_label)
            for i, req, s in rows:
                if req.done:
                    continue   # past its EOS: the token is dropped
                tok = int(tokens[i])
                out[req.req_id] = tok
                if s:
                    self.last_tok[i] = tok
                ended = self._take_token(req, tok, int(prev.lengths[i]) + 1)
                if ended and s and not ahead:
                    self._release_row(i)
        return out
