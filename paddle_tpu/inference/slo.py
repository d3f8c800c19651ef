"""Serving SLO instrumentation shared by both generation engines.

Two pieces, both engine-agnostic (an `engine` label distinguishes the dense
`ContinuousBatchingEngine` from the paged `PagedServingEngine`):

- `serving_metrics()` — the serving metric families, declared through the
  PR-3 observability registry via a `HandleCache` so handles survive
  `reset_default_registry()` (tests) without re-taking the declaration lock
  on the hot scheduler path. The catalog lives in docs/OBSERVABILITY.md and
  docs/SERVING.md.
- `BoundedCompileCache` — the per-bucket prefill program cache. Prompts pad
  to power-of-two length buckets so compile count is bounded *per mix*, but
  a pathological prompt-length distribution could still grow one compiled
  program per bucket forever; the cache caps live buckets (oldest-inserted
  evicted — deliberately FIFO, not LRU: an evicted bucket that comes back
  recompiles and the counter shows it) and emits
  `serving_prefill_compiles_total{engine=,bucket=}` on every real compile so
  that growth is visible in the metrics, never silent.
"""

from __future__ import annotations

import collections

from ..observability.metrics import DEFAULT_BUCKETS, HandleCache

__all__ = ["serving_metrics", "BoundedCompileCache"]

# tokens/s per finished request: 0.5 .. 4096, x2 per bucket
_TPS_BUCKETS = tuple(0.5 * 2 ** i for i in range(14))
# rows an expert gets in a decode tick: 1 .. 1024, x2 per bucket
_ROWS_BUCKETS = tuple(float(2 ** i) for i in range(11))
# rows all held experts of all layers get in a decode tick: 64 .. 128k
_TICK_ROWS_BUCKETS = tuple(float(2 ** i) for i in range(6, 18))
# a share of a whole: twentieths
_SHARE_BUCKETS = tuple(i / 20 for i in range(1, 21))


def _build(reg):
    return {
        "ttft": reg.histogram(
            "serving_ttft_seconds",
            "Time from add_request to the request's first generated token",
            labelnames=("engine",)),
        "request_tps": reg.histogram(
            "serving_request_tokens_per_second",
            "Per finished request: generated tokens / (finish - first token)",
            labelnames=("engine",), buckets=_TPS_BUCKETS),
        "step_seconds": reg.histogram(
            "serving_step_seconds",
            "Wall time of one scheduler tick (admit + decode advance)",
            labelnames=("engine",), buckets=DEFAULT_BUCKETS),
        "tokens": reg.counter(
            "serving_tokens_total", "Generated tokens", ("engine",)),
        "sampled_tokens": reg.counter(
            "serving_sampled_tokens_total",
            "Tokens the decode program drew from a T>0 row's key stream "
            "(a request's first token, drawn at admission, is not counted)",
            ("engine",)),
        "requests": reg.counter(
            "serving_requests_total", "Finished requests", ("engine",)),
        "truncations": reg.counter(
            "serving_truncations_total",
            "Requests retired by KV-cache capacity before max_new_tokens/EOS",
            ("engine",)),
        "queue_depth": reg.gauge(
            "serving_queue_depth",
            "Requests waiting (queue=prefill|resume) or live (queue=decode)",
            ("engine", "queue")),
        "pages_free": reg.gauge(
            "serving_pages_free", "Free physical KV pages in the block pool"),
        "pages_total": reg.gauge(
            "serving_pages_total",
            "Allocatable physical KV pages (excludes the reserved null page)"),
        "kv_bytes_per_token": reg.gauge(
            "serving_kv_bytes_per_token",
            "KV-cache HBM bytes per cached token across all layers and both "
            "K/V sides (int8 payload + amortized per-page scales when the "
            "pool is quantized)"),
        "kv_quant_pages": reg.counter(
            "serving_kv_quant_pages_total",
            "KV pages written through the int8 quantized path (prefill "
            "scatters; decode appends requantize in place)"),
        "prefix_lookups": reg.counter(
            "serving_prefix_lookups_total",
            "Prompt-page hash lookups against the shared-prefix map"),
        "prefix_hits": reg.counter(
            "serving_prefix_hits_total",
            "Prompt pages served by an existing shared page (no new page)"),
        "cow_copies": reg.counter(
            "serving_cow_copies_total",
            "Copy-on-write page copies on first divergent write"),
        "preemptions": reg.counter(
            "serving_preemptions_total",
            "Requests evicted to the host spill buffer when the pool ran dry"),
        "preempted_pages": reg.counter(
            "serving_preempted_pages_total",
            "Pages released by preemption"),
        "resumes": reg.counter(
            "serving_resumes_total",
            "Spilled requests re-admitted from the host buffer"),
        "state_rows_live": reg.gauge(
            "serving_state_rows_live",
            "Decode rows whose recurrent-state slot holds a live request "
            "(models with recurrent layers only)"),
        "window_pages_released": reg.counter(
            "serving_window_pages_released_total",
            "Pages of sliding-window layers released because the row's "
            "length passed them (models with window layers only)"),
        "pages_live": reg.gauge(
            "serving_pages_live",
            "Pages held by live rows, by kind of page group: full (kept "
            "until the request ends, shareable) or window (expire)",
            ("kind",)),
        "pool_bytes_live": reg.gauge(
            "serving_pool_bytes_live",
            "HBM bytes of the pages held by live rows, by kind of page "
            "group (a pool of two page shapes: both out of one budget)",
            ("kind",)),
        "pool_alloc_refused": reg.counter(
            "serving_pool_alloc_refused_total",
            "Pages of a kind's shape refused while as many free bytes lay "
            "in the pool unpaired (units of blocks that smaller pages had "
            "broken): stranding, seen from inside",
            ("kind",)),
        "decode_live_step_share": reg.histogram(
            "serving_decode_live_step_share",
            "Per decode tick and kind of page group: the grid steps one "
            "call of the kind's paged decode kernel walks (the live ones) "
            "over the steps its block table holds (what the grid walked "
            "before it followed a work list)",
            labelnames=("kind",), buckets=_SHARE_BUCKETS),
        "moe_routed_pairs_held": reg.counter(
            "moe_routed_pairs_held",
            "(token, expert) picks of decode ticks that named an expert "
            "held here, over all layers"),
        "moe_dropped_pairs": reg.counter(
            "moe_dropped_pairs",
            "Held picks that found no row in the expert's group: must "
            "stay 0, every held pick has its row by construction"),
        "moe_expert_rows_max": reg.histogram(
            "moe_expert_rows_max",
            "Per decode tick: the most rows any held expert of any layer "
            "got", buckets=_ROWS_BUCKETS),
        "moe_expert_rows_mean": reg.histogram(
            "moe_expert_rows_mean",
            "Per decode tick: rows per held expert, mean over layers and "
            "held experts", buckets=_ROWS_BUCKETS),
        "moe_rows_live": reg.histogram(
            "moe_rows_live",
            "Per decode tick: rows of the held experts' groups, summed "
            "over layers and held experts", buckets=_TICK_ROWS_BUCKETS),
        "moe_rows_tiled": reg.histogram(
            "moe_rows_tiled",
            "Per decode tick: rows the grouped GEMM's visited row tiles "
            "cover, summed likewise: live over tiled is the share of the "
            "kernel's rows that are real", buckets=_TICK_ROWS_BUCKETS),
        "prefill_compiles": reg.counter(
            "serving_prefill_compiles_total",
            "Prefill program compiles, one per live length bucket",
            ("engine", "bucket")),
    }


_HANDLES = HandleCache(_build)


def serving_metrics() -> dict:
    """Current-registry serving metric handles (rebuilt after registry
    resets; a two-attribute read steady-state)."""
    return _HANDLES.get()


class BoundedCompileCache:
    """{bucket -> compiled program} with an explicit max and FIFO eviction.

    get_or_compile() counts every real compile in
    serving_prefill_compiles_total{engine=,bucket=} — including recompiles of
    a previously evicted bucket, which is exactly the signal that the cap is
    too small for the traffic's prompt-length mix.
    """

    def __init__(self, max_entries: int, engine: str):
        if max_entries < 1:
            raise ValueError("max_entries must be >= 1")
        self.max_entries = int(max_entries)
        self.engine = engine
        self.compiles_total = 0  # lifetime compiles (bench warmup detection)
        self._programs: collections.OrderedDict = collections.OrderedDict()

    def __len__(self):
        return len(self._programs)

    def __contains__(self, bucket):
        return bucket in self._programs

    def get_or_compile(self, bucket, compile_fn):
        prog = self._programs.get(bucket)
        if prog is not None:
            return prog
        prog = compile_fn()
        self.compiles_total += 1
        serving_metrics()["prefill_compiles"].inc(
            engine=self.engine, bucket=str(bucket))
        self._programs[bucket] = prog
        while len(self._programs) > self.max_entries:
            self._programs.popitem(last=False)  # oldest bucket out
        return prog
