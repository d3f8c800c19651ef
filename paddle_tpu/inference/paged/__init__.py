"""Paged-KV serving subsystem (docs/SERVING.md).

- `BlockPool` — fixed-size physical KV pages in the layout the Pallas
  `paged_decode_attention` kernel consumes, with free-list allocation,
  refcounted prefix sharing and copy-on-write; `quantized=True` stores int8
  payloads + per-(page, head) f32 scales for the dequant-fused kernel
  (`PagedServingEngine(kv_quant=True)`). One cache spec per layer: `PagedKV` pages for
  an attention layer, `WindowKV` pages that expire for a sliding-window one
  (both out of one free list: layers share the pool's arrays by page group),
  `LatentKV` pages for a latent-attention one (one latent and one rotated key
  a token, shared by all heads), a `RowState` slot per decode row for a
  recurrent one. A spec says what a page is; the pool and the engine ask it.
- `TwoQueueScheduler` — power-of-two prefill length buckets + decode/resume
  queues, admitting against a page-budget watermark.
- `PagedServingEngine` — the continuous-batching engine over both, with
  preemption to a host spill buffer and SLO metrics through the
  observability registry.

The dense `paddle_tpu.inference.serving.ContinuousBatchingEngine` is the
reference the tests hold this engine to, token for token; nothing selects it.
"""

from .block_pool import (BlockPool, LatentKV, PagedKV, RowState, WindowKV,
                         prefix_page_key)
from .engine import PagedServingEngine, SpilledRequest
from .scheduler import TwoQueueScheduler

__all__ = [
    "BlockPool",
    "LatentKV",
    "PagedKV",
    "RowState",
    "WindowKV",
    "PagedServingEngine",
    "SpilledRequest",
    "TwoQueueScheduler",
    "prefix_page_key",
]
