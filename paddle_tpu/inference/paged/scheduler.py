"""Two-queue admission scheduler for the paged serving engine.

Queues:

- **prefill** — waiting `GenerationRequest`s, held in power-of-two length
  buckets (the same buckets the prefill compile cache is keyed by, so queue
  depth per bucket reads directly against
  `serving_prefill_compiles_total{bucket=}`).
- **resume** — preempted requests whose pages were spilled to host; they
  already produced tokens, so they re-admit ahead of fresh prefills.

Admission decisions are made against a **page-budget watermark**: a request
is admitted only if, after taking its (upper-bound) page need, the pool
would still hold `watermark` free pages. The default watermark is one page
per live request — every live row can cross at most one page boundary per
`page_size` decode steps, so this reserve makes same-tick pool exhaustion
(and therefore preemption) the exception rather than the steady state.

A model with sliding-window layers beside full ones holds pages in several
page groups (`block_pool.page_layout`) out of the one free list: a request is
charged what ALL its groups need (`pages_for`: a window group only the
prompt's last window), a spilled one every page it held, and the reserve is a
page per live request and group.

A pool of pages of two shapes (`block_pool`: the larger page is `span`
adjacent units of the smaller) charges in two resources at once, units and
whole free blocks: `pages_for`, a spilled request's `n_pages`, `groups` and
`pick`'s `pages_free` are then arrays of the two, and a request fits when
it fits in both. The arithmetic below is the same on numbers and on arrays.

Admission is by rows AND pages: `pick` also takes the free decode rows, and
for a model with recurrent layers a row is a resource of its own (the row's
state slot, `block_pool.RowState`): where pages are plentiful, rows are what
runs out, and a spilled request needs a free row again before it resumes.

Ordering is strict arrival FIFO across buckets, with head-of-line blocking
when the head doesn't fit the budget. Two deliberate consequences: no
starvation (a big request is never overtaken forever by small ones), and
admission order equals the dense engine's — which keeps the sampling-key
stream identical across engines for the same workload, the property the
parity tests pin. Bucket structure is for compile management and
observability, not reordering.
"""

from __future__ import annotations

import collections

import numpy as np

from ..serving import _bucket
from ..slo import serving_metrics

__all__ = ["TwoQueueScheduler"]


def _pages_for_prompt(n_tokens: int, page_size: int) -> int:
    return -(-n_tokens // page_size)  # ceil


class TwoQueueScheduler:
    def __init__(self, page_size: int, watermark_pages: int | None = None,
                 pages_for=None, groups=1):
        """`pages_for(n)`: the pages the admission of an n-token prompt
        takes (default: ceil(n / page_size), one group of layers that keep
        everything); `groups`: the page groups a live row holds a write page
        in (a model with window layers beside full ones has several; a
        pool of two page shapes: what a page in every group costs, as
        `pages_for` counts)."""
        self.page_size = int(page_size)
        # None -> dynamic: one reserved page per live request and group
        self.watermark_pages = watermark_pages
        self.pages_for = pages_for or (
            lambda n: _pages_for_prompt(n, self.page_size))
        self.groups = groups
        self._seq = 0
        # bucket -> deque[(seq, req)]; FIFO within, arrival-merged across
        self.prefill: dict[int, collections.deque] = {}
        self.resume: collections.deque = collections.deque()

    # -- enqueue --------------------------------------------------------- #

    def enqueue_prefill(self, req):
        b = _bucket(len(req.prompt))
        self.prefill.setdefault(b, collections.deque()).append(
            (self._seq, req))
        self._seq += 1

    def enqueue_resume(self, spilled):
        self.resume.append(spilled)

    # -- introspection --------------------------------------------------- #

    @property
    def waiting_prefill(self) -> int:
        return sum(len(d) for d in self.prefill.values())

    @property
    def waiting_resume(self) -> int:
        return len(self.resume)

    def has_waiting(self) -> bool:
        return bool(self.resume) or any(self.prefill.values())

    def update_gauges(self, engine: str, live: int):
        g = serving_metrics()["queue_depth"]
        g.set(self.waiting_prefill, engine=engine, queue="prefill")
        g.set(self.waiting_resume, engine=engine, queue="resume")
        g.set(live, engine=engine, queue="decode")

    # -- admission ------------------------------------------------------- #

    def _watermark(self, live: int) -> int:
        if self.watermark_pages is not None:
            return self.watermark_pages
        return max(1, live) * self.groups

    def _head_bucket(self):
        """Bucket holding the earliest-arrived waiting request."""
        best = None
        for b, d in self.prefill.items():
            if d and (best is None or d[0][0] < self.prefill[best][0][0]):
                best = b
        return best

    def pick(self, free_rows: int, pages_free: int, live: int) -> list:
        """Admissions for this tick, in order: resumes (FIFO), then prefill
        arrivals (FIFO across buckets). Page needs are charged at their
        upper bound (prefix-sharing hits only under-run the budget). Stops
        at the first request that would dip below the watermark —
        head-of-line blocking by design (see module docstring)."""
        out = []
        budget = pages_free

        def fits(need):
            # live + 1: the reserve must cover the candidate itself once
            # admitted, or the pool runs one page short of the documented
            # one-reserved-page-per-live-request invariant
            if np.all(budget - need >= self._watermark(live + 1)):
                return True
            # idle-engine fallback: with nothing live and nothing admitted
            # yet, the head request admits whenever it fits AT ALL — a
            # request needing the whole pool must not deadlock an empty
            # engine behind its own watermark
            return live == 0 and not out and bool(np.all(budget >= need))

        while free_rows and self.resume:
            need = self.resume[0].n_pages
            if not fits(need):
                return out
            sp = self.resume.popleft()
            out.append(sp)
            free_rows -= 1
            live += 1
            budget -= need

        while free_rows:
            b = self._head_bucket()
            if b is None:
                break
            need = self.pages_for(len(self.prefill[b][0][1].prompt))
            if not fits(need):
                return out
            _, req = self.prefill[b].popleft()
            out.append(req)
            free_rows -= 1
            live += 1
            budget -= need
        return out
