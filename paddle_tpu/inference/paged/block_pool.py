"""Block-pool KV cache manager: fixed-size physical pages, free-list
allocation, refcounted prefix sharing, copy-on-write.

The physical layout is `[n_pages, Hkv, page_size, D]` per layer — exactly
the shape `ops.pallas.decode_attention.paged_decode_attention` consumes, so
the decode program DMAs pages straight from their physical slots (the block
table is a scalar-prefetch operand resolved in the BlockSpec index_map; no
gathered copy of the cache ever materializes).

Host-side metadata (free list, refcounts, prefix map) is plain Python/numpy:
it is touched once per admission / page-boundary crossing / preemption, never
per token, and never inside a trace. Device arrays are immutable jnp values;
every mutation (`.at[...]`) swaps in a fresh array, which composes with the
engine's donated decode program.

Prefix sharing: a prompt page is keyed by the hash of the ENTIRE token
prefix through that page's end — K/V at position i depends on every token
<= i (attention mixes the prefix into the hidden state before the
projections), so two pages are interchangeable iff their full prefixes
match. Partial tail pages therefore only share between prompts with
identical full prefixes of the same length; extending a shorter prompt's
tail page in place is deliberately out of scope (vLLM's partial-block
dedup), see docs/SERVING.md. A shared page is immutable: the engine must
copy-on-write (`copy_page`) before the first divergent write, and a page
that stops being shared (refcount 1) must be unregistered before an
in-place write so a later identical prompt cannot adopt a page that now
holds generated tokens.

Layer kinds. What a layer keeps per request is a cache spec, one per layer
(`specs`; a model that is not all attention gives them through
`cache_specs()`): `PagedKV` is the layout above, with tables, prefix keys
and copy-on-write; `RowState` is a fixed-size slot per DECODE ROW, a tuple of
`[rows, ...]` arrays (a recurrent layer's conv and SSM state), indexed by
the row a request decodes in, never shared, and spilled whole. Both live in
`kv`, layer by layer, so the decode program donates and returns one list and
`pool.kv = []` releases everything. Pages are written as above; a slot is
written (admission, resume) and read (spill) by ONE jitted program each that
takes the row as data, and the writing one donates the state arrays: an
eager `.at[].set()` on a whole state array would copy all rows' state for
one row's sake.

Physical page 0 is the reserved NULL page: never allocated, never referenced
by a live block table. Parked decode rows (batch padding) route their
per-step K/V writes there, so the fixed-shape decode program needs no
conditional writes.

Quantized layout (`quantized=True`, the `kv_quant=True` serving fast
path): page payloads are int8 with one f32 dequant scale per (page, head)
stored alongside (`scales[layer] = (k_scale, v_scale)`, each
[n_pages, Hkv]); dequant is `payload * scale`, fused into the Pallas decode
kernel's page load. Prefill pages quantize with abs-max per (page, head);
decode appends keep a running abs-max per page
(`ops.pallas.decode_attention.paged_kv_write_q8`). Prefix sharing keeps the
SAME full-prefix blake2b keys: quantization is a deterministic function of
page content, so two identical prefixes produce bit-identical int8 payloads
AND scales — a shared page is interchangeable exactly as in the f32 layout,
and COW/spill/restore move payload + scales together, bit-exactly.
"""

from __future__ import annotations

import collections
import dataclasses
import hashlib
import math

import jax
import jax.numpy as jnp
import numpy as np

from ..slo import serving_metrics

__all__ = ["BlockPool", "PagedKV", "RowState", "prefix_page_key"]


@dataclasses.dataclass(frozen=True)
class PagedKV:
    """An attention layer's cache: K and V pages
    [n_pages, kv_heads, page_size, head_dim], block tables, prefix keys,
    copy-on-write; spilled page by page."""

    kv_heads: int
    head_dim: int

    def prefill_cache(self, seq, dtype):
        """The zeroed dense cache a batch-1 prefill of `seq` tokens fills."""
        return (jnp.zeros((1, seq, self.kv_heads, self.head_dim), dtype),) * 2


@dataclasses.dataclass(frozen=True)
class RowState:
    """A recurrent layer's cache: one fixed-size slot per decode row, the
    arrays `shapes` (per row); spilled and restored whole."""

    shapes: tuple

    def row_nbytes(self, dtype) -> int:
        return sum(math.prod(s) for s in self.shapes) * jnp.dtype(
            dtype).itemsize

    def prefill_cache(self, seq, dtype):
        """The zero state a batch-1 prefill starts from."""
        return tuple(jnp.zeros((1,) + tuple(s), dtype) for s in self.shapes)


def _quantize_pages(x):
    """[m, Hkv, ps, D] float pages -> (int8 payload, f32 [m, Hkv] scales):
    symmetric abs-max per (page, head), matching paged_kv_write_q8 (±127 so
    running-max rescales never overflow)."""
    from ...ops.pallas.decode_attention import KV_QMAX

    x32 = jnp.asarray(x).astype(jnp.float32)
    absmax = jnp.max(jnp.abs(x32), axis=(2, 3))
    scale = absmax / KV_QMAX
    safe = jnp.where(scale == 0.0, 1.0, scale)
    q = jnp.clip(jnp.round(x32 / safe[:, :, None, None]),
                 -KV_QMAX, KV_QMAX).astype(jnp.int8)
    return q, scale.astype(jnp.float32)


def prefix_page_key(prompt: np.ndarray, page_index: int, page_size: int):
    """Sharing key for prompt page `page_index`: hash of the full token
    prefix through the page's end (clipped to the prompt length)."""
    end = min(len(prompt), (page_index + 1) * page_size)
    return hashlib.blake2b(
        np.ascontiguousarray(prompt[:end], np.int32).tobytes(),
        digest_size=16).digest()


class BlockPool:
    """Fixed pool of physical KV pages shared by every layer's cache."""

    def __init__(self, num_layers, kv_heads, head_dim, page_size, num_pages,
                 dtype=jnp.float32, prefix_sharing=True, quantized=False,
                 specs=None, rows=0):
        """`specs`: one cache spec per layer (default: every layer
        `PagedKV(kv_heads, head_dim)`); `rows`: decode rows, the number of
        slots a `RowState` layer gets."""
        if num_pages < 2:
            raise ValueError("num_pages must be >= 2 (page 0 is reserved)")
        if page_size < 1:
            raise ValueError("page_size must be >= 1")
        self.page_size = int(page_size)
        self.num_pages = int(num_pages)
        self.num_layers = int(num_layers)
        self.kv_heads = int(kv_heads)
        self.head_dim = int(head_dim)
        self.dtype = jnp.dtype(dtype)  # unquantized payload dtype
        self.prefix_sharing = bool(prefix_sharing)
        self.quantized = bool(quantized)
        self.specs = (list(specs) if specs is not None
                      else [PagedKV(kv_heads, head_dim)] * num_layers)
        if len(self.specs) != num_layers:
            raise ValueError("one cache spec per layer")
        self.page_layers = [i for i, s in enumerate(self.specs)
                            if isinstance(s, PagedKV)]
        self.state_layers = [i for i, s in enumerate(self.specs)
                             if isinstance(s, RowState)]
        if self.state_layers and self.quantized:
            raise ValueError("an int8 pool beside recurrent state is not "
                             "supported")
        self.rows = int(rows)
        shape = (self.num_pages, kv_heads, self.page_size, head_dim)
        pay_dtype = jnp.dtype(jnp.int8) if self.quantized else self.dtype
        # immutable jnp zeros: (z,)*2 aliasing is safe, .at[] copies
        self.kv = [(jnp.zeros(shape, pay_dtype),) * 2
                   if isinstance(spec, PagedKV) else
                   tuple(jnp.zeros((self.rows,) + tuple(s), self.dtype)
                         for s in spec.shapes)
                   for spec in self.specs]
        # per-(page, head) f32 dequant scales beside the int8 payloads
        self.scales = ([(jnp.zeros((self.num_pages, kv_heads),
                                   jnp.float32),) * 2
                        for _ in range(num_layers)]
                       if self.quantized else None)
        self._write_state = self._read_state = None
        self.free: collections.deque = collections.deque(
            range(1, self.num_pages))
        self.ref = np.zeros(self.num_pages, np.int32)
        self._prefix: dict[bytes, int] = {}   # key -> page
        self._page_key: dict[int, bytes] = {}  # page -> key (registered only)
        self.allocs_total = 0  # lifetime allocations (tests/introspection)
        self.cow_copies_total = 0

    # -- accounting ------------------------------------------------------ #

    @staticmethod
    def page_nbytes(num_layers, kv_heads, head_dim, page_size,
                    dtype=jnp.float32, quantized=False) -> int:
        """HBM bytes one physical page costs across all layers and both K/V
        sides — payload plus, when quantized, the per-(page, head) f32
        scales. The unit of the equal-budget serving A/B."""
        if quantized:
            per_side = kv_heads * page_size * head_dim + kv_heads * 4
        else:
            per_side = (kv_heads * page_size * head_dim
                        * jnp.dtype(dtype).itemsize)
        return int(num_layers) * 2 * per_side

    @property
    def bytes_per_page(self) -> int:
        return self.page_nbytes(len(self.page_layers), self.kv_heads,
                                self.head_dim, self.page_size, self.dtype,
                                self.quantized)

    @property
    def state_row_nbytes(self) -> int:
        """HBM bytes one decode row's recurrent state costs, all layers."""
        return sum(self.specs[li].row_nbytes(self.dtype)
                   for li in self.state_layers)

    @property
    def bytes_per_token(self) -> float:
        """KV HBM bytes one cached token costs (all layers, K+V, amortized
        scale overhead) — the `serving_kv_bytes_per_token` series."""
        return self.bytes_per_page / self.page_size

    @property
    def pages_total(self) -> int:
        return self.num_pages - 1  # null page is not allocatable

    @property
    def pages_free(self) -> int:
        return len(self.free)

    def update_gauges(self):
        m = serving_metrics()
        m["pages_free"].set(self.pages_free)
        m["pages_total"].set(self.pages_total)
        m["kv_bytes_per_token"].set(self.bytes_per_token)

    # -- allocation / refcounts ------------------------------------------ #

    def alloc(self) -> int | None:
        """One free page with refcount 1, or None when the pool is dry."""
        if not self.free:
            return None
        page = self.free.popleft()
        self.ref[page] = 1
        self.allocs_total += 1
        return page

    def incref(self, page: int):
        assert self.ref[page] > 0, f"incref on unallocated page {page}"
        self.ref[page] += 1

    def release(self, page: int):
        """Drop one reference; a page at zero is unregistered and freed."""
        assert self.ref[page] > 0, f"release of unallocated page {page}"
        self.ref[page] -= 1
        if self.ref[page] == 0:
            self.unregister_page(page)
            self.free.append(page)

    def is_shared(self, page: int) -> bool:
        return self.ref[page] > 1

    # -- prefix sharing -------------------------------------------------- #

    def lookup_prefix(self, key: bytes | None) -> int | None:
        """Shared page for `key` (increfs on hit), else None."""
        if not self.prefix_sharing or key is None:
            return None
        m = serving_metrics()
        m["prefix_lookups"].inc()
        page = self._prefix.get(key)
        if page is None:
            return None
        self.incref(page)
        m["prefix_hits"].inc()
        return page

    def register_prefix(self, key: bytes, page: int):
        if not self.prefix_sharing or key in self._prefix:
            return
        self._prefix[key] = page
        self._page_key[page] = key

    def is_registered(self, page: int) -> bool:
        return page in self._page_key

    def page_key(self, page: int) -> bytes | None:
        return self._page_key.get(page)

    def unregister_page(self, page: int):
        """Remove a page from the prefix map (before an in-place write, or
        on free) so future lookups cannot adopt diverged content."""
        key = self._page_key.pop(page, None)
        if key is not None:
            self._prefix.pop(key, None)

    # -- device page data ------------------------------------------------ #

    def write_prompt_pages(self, pages, write_mask, k_layers, v_layers):
        """Scatter a prefilled prompt into its pages, all layers.

        pages: the request's m physical pages in logical order; write_mask[j]
        False for shared pages (content already present — identical by key
        construction, so it is never rewritten). k_layers/v_layers: per layer
        [m, Hkv, page_size, D] page-stacked prompt K/V. One batched scatter
        per layer per side. A quantized pool quantizes here (abs-max per
        (page, head)) and scatters payload + scales together."""
        idx = [j for j, w in enumerate(write_mask) if w]
        if not idx:
            return
        tgt = jnp.asarray([pages[j] for j in idx], jnp.int32)
        sel = jnp.asarray(idx, jnp.int32)
        for j, li in enumerate(self.page_layers):
            k, v = self.kv[li]
            if self.quantized:
                kq, ks = _quantize_pages(k_layers[j][sel])
                vq, vs = _quantize_pages(v_layers[j][sel])
                sk, sv = self.scales[li]
                self.kv[li] = (k.at[tgt].set(kq), v.at[tgt].set(vq))
                self.scales[li] = (sk.at[tgt].set(ks), sv.at[tgt].set(vs))
            else:
                self.kv[li] = (k.at[tgt].set(k_layers[j][sel]),
                               v.at[tgt].set(v_layers[j][sel]))
        if self.quantized:
            serving_metrics()["kv_quant_pages"].inc(len(idx))

    def copy_page(self, src: int, dst: int):
        """Copy-on-write body: duplicate src's content into dst (all
        layers; payload + scales for a quantized pool). Caller owns
        refcount/table updates."""
        for li in self.page_layers:
            k, v = self.kv[li]
            self.kv[li] = (k.at[dst].set(k[src]), v.at[dst].set(v[src]))
            if self.quantized:
                sk, sv = self.scales[li]
                self.scales[li] = (sk.at[dst].set(sk[src]),
                                   sv.at[dst].set(sv[src]))
        self.cow_copies_total += 1
        serving_metrics()["cow_copies"].inc()

    def read_pages(self, pages) -> list[tuple]:
        """Host copies of the given pages, per layer — the preemption spill
        buffer. Unquantized: [(k, v), ...] each [m, Hkv, page_size, D];
        quantized: [(k, v, k_scale, v_scale), ...] with [m, Hkv] scales
        (int8 payload + f32 scales round-trip the host bit-exactly, so a
        spilled quantized request resumes with zero extra error)."""
        idx = jnp.asarray(list(pages), jnp.int32)
        if self.quantized:
            return [(np.asarray(k[idx]), np.asarray(v[idx]),
                     np.asarray(sk[idx]), np.asarray(sv[idx]))
                    for (k, v), (sk, sv) in zip(self.kv, self.scales)]
        return [(np.asarray(k[idx]), np.asarray(v[idx]))
                for k, v in (self.kv[li] for li in self.page_layers)]

    def restore_pages(self, pages, kv_host, rows):
        """Write spilled host pages back: kv_host is read_pages() output for
        the request's full logical page list; `rows` selects which logical
        indices need restoring (prefix-shared hits don't), `pages` the
        freshly allocated physical destinations, aligned with `rows`."""
        if not pages:
            return
        tgt = jnp.asarray(list(pages), jnp.int32)
        sel = np.asarray(list(rows), np.int32)
        for j, li in enumerate(self.page_layers):
            k, v = self.kv[li]
            k_h, v_h = kv_host[j][0], kv_host[j][1]
            self.kv[li] = (k.at[tgt].set(jnp.asarray(k_h[sel])),
                           v.at[tgt].set(jnp.asarray(v_h[sel])))
            if self.quantized:
                sk, sv = self.scales[li]
                sk_h, sv_h = kv_host[j][2], kv_host[j][3]
                self.scales[li] = (sk.at[tgt].set(jnp.asarray(sk_h[sel])),
                                   sv.at[tgt].set(jnp.asarray(sv_h[sel])))

    # -- device row state ------------------------------------------------- #

    def write_state(self, row: int, values):
        """Set decode row `row`'s slot in every `RowState` layer: `values`
        holds, per such layer, the slot's arrays (a leading batch axis of 1
        is dropped). One jitted program whatever the row; it donates the
        state arrays, so only that row's bytes move."""
        if not self.state_layers:
            return
        if self._write_state is None:
            def write(states, row, values):
                return [tuple(a.at[row].set(v.reshape(a.shape[1:])
                                            .astype(a.dtype))
                              for a, v in zip(layer, vals))
                        for layer, vals in zip(states, values)]

            self._write_state = jax.jit(write, donate_argnums=(0,))
        new = self._write_state([self.kv[li] for li in self.state_layers],
                                np.int32(row), values)
        for li, layer in zip(self.state_layers, new):
            self.kv[li] = layer

    def read_state(self, row: int) -> list[tuple]:
        """Host copies of decode row `row`'s slot, per `RowState` layer: the
        state half of a preemption spill (`write_state` restores it)."""
        if not self.state_layers:
            return []
        if self._read_state is None:
            self._read_state = jax.jit(lambda states, row: [
                tuple(jax.lax.dynamic_index_in_dim(a, row, 0, keepdims=False)
                      for a in layer) for layer in states])
        got = self._read_state([self.kv[li] for li in self.state_layers],
                               np.int32(row))
        return [tuple(np.asarray(a) for a in layer) for layer in got]
