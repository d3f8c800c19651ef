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
"""

from __future__ import annotations

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
        return (self.sched.has_waiting()
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
        if not free_rows:
            return 0
        work = self.sched.pick(len(free_rows), self._free(),
                               self.live_count)
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

    def _ensure_write_target(self, row):
        """Guarantee this row can scatter its next K/V in every group:
        release the window pages the row's length has passed, allocate at
        page boundaries, copy-on-write off shared pages, unregister a
        private page before its first divergent write."""
        L = int(self.lengths[row])
        start = self._window.first_page(L, self.ps) if self._windowed else 0
        gone = start - int(self.window_start[row])
        for group, table in zip(self.groups, self.group_tables):
            j = L // self.ps
            if group.window:
                if gone > 0:
                    for p in table[row, :gone]:
                        self.pool.release(int(p))
                    table[row, :-gone] = table[row, gone:]
                    table[row, -gone:] = -1
                    self._window_released += gone
                j -= start
            page = int(table[row, j])
            if page < 0:
                table[row, j] = self._alloc_or_preempt(requester_row=row,
                                                       span=group.span)
            elif self.pool.is_shared(page):
                dst = self._alloc_or_preempt(requester_row=row)
                self.pool.copy_page(page, dst)
                self.pool.release(page)
                table[row, j] = dst
            elif self.pool.is_registered(page):
                self.pool.unregister_page(page)
        self.window_start[row] = start

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
        pairs, rows_max, rows_sum, dropped, tile_rows = (
            int(v) for v in stats)
        m = serving_metrics()
        m["moe_routed_pairs_held"].inc(pairs)
        m["moe_dropped_pairs"].inc(dropped)
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

    def _step(self, tick):
        """Admit (resumes then prefills), ensure every live row has a
        writable page, advance all live rows by one token with the single
        compiled paged-decode program."""
        with span("admit") as sp:
            sp.set(picked=self._admit())
        live = [i for i in range(self.B) if self.active[i] is not None]
        tick.set(live=len(live), waiting=self.sched.waiting_prefill)
        self.sched.update_gauges(self.engine_label, len(live))
        self.pool.update_gauges()
        if self.pool.state_layers:
            serving_metrics()["state_rows_live"].set(len(live))
        if not live:
            return {}
        with span("write_targets") as sp:
            allocs, cows = self.pool.allocs_total, self.pool.cow_copies_total
            released = self._window_released
            for i in live:
                if self.active[i] is not None:  # an earlier COW may have spilled i
                    self._ensure_write_target(i)
            sp.set(pages_allocated=self.pool.allocs_total - allocs,
                   cow_copies=self.pool.cow_copies_total - cows)
            if self._windowed:
                gone = self._window_released - released
                sp.set(window_pages_released=gone)
                serving_metrics()["window_pages_released"].inc(gone)
            if self._page_kinds:
                self._update_page_gauges()
        live = [i for i in range(self.B) if self.active[i] is not None]
        if not live:
            return {}
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
            if not self._windowed:   # one group: one table, no starts
                tables, starts = jnp.asarray(self.tables), ()
            else:
                # a layer of span k reads the arrays k units a page: its
                # page's index there is handle // k (a hole stays -1)
                tables = tuple(
                    jnp.asarray(t if g.span == 1 else t // g.span)
                    for g, t in zip(self.groups, self.group_tables))
                starts = (jnp.asarray(self.window_start * self.ps),)
            tokens, keys, logits, new_kv, stats = self._decode_jit(
                self.params, self.buffers, jnp.asarray(self.last_tok),
                jnp.asarray(self.lengths), tables,
                *self._sampling_inputs(sampled), caches, *starts)
            if self.kv_quant:
                self.pool.kv = [tuple(c[:2]) for c in new_kv]
                self.pool.scales = [tuple(c[2:]) for c in new_kv]
            else:
                self.pool.kv = [tuple(c) for c in new_kv]
            self.last_logits = logits  # device array; tests probe divergence
        with span("host_read"):  # the host waits for the decode here
            tokens, keys, stats = jax.device_get((tokens, keys, stats))
        if stats:
            self._note_routing(stats[0])
        out = self._emit_decoded(live, sampled, tokens, keys)
        self.pool.update_gauges()
        return out
