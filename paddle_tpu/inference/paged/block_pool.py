"""Block-pool KV cache manager: fixed-size physical pages, free-list
allocation, refcounted prefix sharing, copy-on-write.

The physical layout is `[n_pages, Hkv, page_size, D]` per layer — exactly
the shape `ops.pallas.decode_attention.paged_decode_attention` consumes, so
the decode program DMAs pages straight from their physical slots (the block
table is a scalar-prefetch operand resolved in the BlockSpec index_map; no
gathered copy of the cache ever materializes).

Host-side metadata (free list, refcounts, prefix map) is plain Python/numpy:
it is touched once per admission / page-boundary crossing / preemption, never
per token, and never inside a trace. Device arrays are immutable jnp values,
and every program that changes one DONATES it: the decode program, and the
pool's own two page programs, a gather (`read_pages`: a spill; the source of
a `copy_page`) and a scatter (`write_prompt_pages`, `restore_pages`,
`copy_page`), which take their page ids as data in counts bucketed to powers
of two (at most `_PAGES_PER_CALL` a call), so that a pool of any size
compiles some twenty of them and a write moves the written pages' bytes,
never the pool's.

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

Window layers and page groups. `WindowKV` is a sliding-window attention
layer's cache: the same pages, but a row keeps only those that hold one of
its last `window` positions, at most `ceil(window / page_size) + 1` of them.
They are private to the row (never in the prefix registry: a page behind
another request's window may be gone), the engine releases each as the row's
length passes it, and admission writes only the prompt's last window. So that
ONE free list serves both kinds, the paged layers are split by kind into
GROUPS of equal layer count `depth` (the greatest common divisor of the
kinds' layer counts; 2 full and 6 window layers: one full group, three
window groups, depth 2) and the pool has `depth` K/V arrays, not one per
layer: a page is a slot in all `depth` arrays, the same bytes whatever group
holds it, and it belongs to one group at a time. A request has one block
table per group. `kv` holds one entry per array (`entry_of_layer` says which
a layer reads and writes; layers of several groups share an entry, so the
decode program threads it through them in layer order); a model whose paged
layers are all of one kind has one group and `depth` = its paged layers: an
entry per layer, as before.

Latent pages. `LatentKV` is a latent-attention layer's cache: what a token
leaves behind in a layer is ONE normed latent `latent_dim` wide and ONE rotated
key `rope_dim` wide that all the heads share, not keys and values per head. To
the host it is `PagedKV` in everything (block tables as wide as a row can
grow, prefix keys over the whole prefix, copy-on-write, a spill page by page,
one group); what differs is what a page IS: one array a layer,
`[n_pages, page_size, stored_dim]`, a token's latent and its key side by side
in one row, the row padded with zeros to a whole lane tile (512 + 64 -> 640),
which is what the device would store for 576 anyway. The decode kernel
(`ops.pallas.decode_attention.latent_decode_attention`) reads each page ONCE
and uses it as key (the whole row) and as value (its first `latent_dim`).
Every spec says its own page arrays and bytes (`page_arrays`, `page_nbytes`,
`pages_per_step`); the pool and the engine ask the spec and never multiply
head counts themselves. An int8 pool of latents is refused.

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

__all__ = ["BlockPool", "PagedKV", "WindowKV", "LatentKV", "RowState",
           "PageGroup", "page_layout", "prefix_page_key"]

# pages one call of the gather or the scatter program moves at most: larger
# sets go in several calls, so the bucketed shapes end here
_PAGES_PER_CALL = 512


@dataclasses.dataclass(frozen=True)
class PagedKV:
    """An attention layer's cache: K and V pages
    [n_pages, kv_heads, page_size, head_dim], block tables, prefix keys,
    copy-on-write; spilled page by page."""

    kv_heads: int
    head_dim: int

    kind = "full"    # the `serving_pages_live` gauge's label

    def page_arrays(self, page_size):
        """The shape of one page in each array a layer keeps: K and V."""
        return ((self.kv_heads, page_size, self.head_dim),) * 2

    def page_nbytes(self, page_size, dtype, quantized=False) -> int:
        """HBM bytes one page costs in ONE layer, both sides: payload plus,
        when quantized, the per-(page, head) f32 scales."""
        values = self.kv_heads * page_size * self.head_dim
        if quantized:
            return 2 * (values + self.kv_heads * 4)
        return 2 * values * jnp.dtype(dtype).itemsize

    def pages_per_step(self, page_size, width, itemsize) -> int:
        """Pages of a row one grid step of this kind's decode kernel takes,
        for a block table `width` wide."""
        from ...ops.pallas.decode_attention import pages_per_step

        return pages_per_step(self.kv_heads, page_size, self.head_dim, width,
                              itemsize)


@dataclasses.dataclass(frozen=True)
class WindowKV(PagedKV):
    """A sliding-window attention layer's cache: K and V pages as `PagedKV`,
    of which a row keeps only those holding one of the `window` positions its
    next query sees (key j is visible to query i iff 0 <= i - j < window).
    Private to the row, released as the row's length passes them."""

    window: int

    kind = "window"

    def first_page(self, length, page_size) -> int:
        """The first logical page a row with `length` tokens cached still
        needs: the one holding position `length + 1 - window`, the oldest key
        the query at position `length` sees."""
        return max(0, length + 1 - self.window) // page_size

    def table_width(self, page_size) -> int:
        return -(-self.window // page_size) + 1


@dataclasses.dataclass(frozen=True)
class LatentKV:
    """A latent-attention layer's cache: per token ONE normed latent
    `latent_dim` wide and ONE rotated key `rope_dim` wide, shared by all the
    heads, side by side in a row of `stored_dim` values (zero behind them up
    to a whole lane tile). Pages [n_pages, page_size, stored_dim], one array
    a layer; to the host everything `PagedKV` is."""

    latent_dim: int
    rope_dim: int

    kind = "latent"

    @property
    def stored_dim(self) -> int:
        return -(-(self.latent_dim + self.rope_dim) // 128) * 128

    def page_arrays(self, page_size):
        return ((page_size, self.stored_dim),)

    def page_nbytes(self, page_size, dtype, quantized=False) -> int:
        if quantized:
            raise ValueError("an int8 pool of latent pages is not supported")
        return page_size * self.stored_dim * jnp.dtype(dtype).itemsize

    def pages_per_step(self, page_size, width, itemsize) -> int:
        from ...ops.pallas.decode_attention import latent_pages_per_step

        return latent_pages_per_step(page_size, self.stored_dim, width,
                                     itemsize)


_PAGED = (PagedKV, LatentKV)    # `WindowKV` is a `PagedKV`


@dataclasses.dataclass(frozen=True)
class PageGroup:
    """`depth` layers of one kind that share block tables: layer `layers[j]`
    keeps its K and V in the pool's page array j."""

    spec: object
    layers: tuple

    @property
    def window(self):
        return isinstance(self.spec, WindowKV)


def page_layout(specs):
    """(groups, entry_of_layer, group_of_layer) for one cache spec per layer.
    Groups: the paged layers by kind (`PagedKV` kinds first), each kind cut
    into runs of `depth` layers, `depth` the gcd of the kinds' layer counts.
    `entry_of_layer[l]`: the index in `BlockPool.kv` of what layer l reads
    and writes, entries numbered in layer order (a `RowState` layer has its
    own; paged layer j of any group has array j's). `group_of_layer[l]`: the
    group whose table layer l follows, None for a `RowState` layer."""
    kinds = {}
    for li, spec in enumerate(specs):
        if isinstance(spec, _PAGED):
            kinds.setdefault(spec, []).append(li)
    if not kinds:
        raise ValueError("no paged layer among the cache specs")
    if len({k.page_arrays(1) for k in kinds}) > 1:
        raise ValueError("paged layers of different KV head counts or sizes "
                         "in one pool are not supported")
    depth = math.gcd(*(len(v) for v in kinds.values()))
    groups = [PageGroup(spec, tuple(layers[i:i + depth]))
              for spec, layers in sorted(
                  kinds.items(), key=lambda kv: isinstance(kv[0], WindowKV))
              for i in range(0, len(layers), depth)]
    group_of, array_of = {}, {}
    for gi, group in enumerate(groups):
        for j, li in enumerate(group.layers):
            group_of[li], array_of[li] = gi, j
    entry_of_layer, array_entry = [], {}
    for li in range(len(specs)):
        if li in array_of:
            entry = array_entry.setdefault(
                array_of[li], len(set(entry_of_layer)))
        else:
            entry = len(set(entry_of_layer))
        entry_of_layer.append(entry)
    return groups, entry_of_layer, [group_of.get(li)
                                    for li in range(len(specs))]


@dataclasses.dataclass(frozen=True)
class RowState:
    """A recurrent layer's cache: one fixed-size slot per decode row, the
    arrays `shapes` (per row); spilled and restored whole."""

    shapes: tuple

    def row_nbytes(self, dtype) -> int:
        return sum(math.prod(s) for s in self.shapes) * jnp.dtype(
            dtype).itemsize


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


def _pad_rows(x, pad):
    """`x` with `pad` zero rows behind it (a host or a device array)."""
    xp = np if isinstance(x, np.ndarray) else jnp
    return xp.concatenate([x, xp.zeros((pad,) + x.shape[1:], x.dtype)])


def prefix_page_key(prompt: np.ndarray, page_index: int, page_size: int):
    """Sharing key for prompt page `page_index`: hash of the full token
    prefix through the page's end (clipped to the prompt length)."""
    end = min(len(prompt), (page_index + 1) * page_size)
    return hashlib.blake2b(
        np.ascontiguousarray(prompt[:end], np.int32).tobytes(),
        digest_size=16).digest()


class BlockPool:
    """Fixed pool of physical KV pages shared by every layer's cache."""

    def __init__(self, num_layers, kv_heads=None, head_dim=None, page_size=16,
                 num_pages=2, dtype=jnp.float32, prefix_sharing=True,
                 quantized=False, specs=None, rows=0):
        """`specs`: one cache spec per layer (default: every layer
        `PagedKV(kv_heads, head_dim)`; with `specs`, `kv_heads` and
        `head_dim` are not read: a page is what its spec says); `rows`:
        decode rows, the number of slots a `RowState` layer gets."""
        if num_pages < 2:
            raise ValueError("num_pages must be >= 2 (page 0 is reserved)")
        if page_size < 1:
            raise ValueError("page_size must be >= 1")
        self.page_size = int(page_size)
        self.num_pages = int(num_pages)
        self.num_layers = int(num_layers)
        self.dtype = jnp.dtype(dtype)  # unquantized payload dtype
        self.prefix_sharing = bool(prefix_sharing)
        self.quantized = bool(quantized)
        self.specs = (list(specs) if specs is not None
                      else [PagedKV(kv_heads, head_dim)] * num_layers)
        if len(self.specs) != num_layers:
            raise ValueError("one cache spec per layer")
        self.groups, self.entry_of_layer, self.group_of_layer = page_layout(
            self.specs)
        self.depth = len(self.groups[0].layers)
        # every group's pages are the same bytes: one spec says what a page is
        self.page_spec = self.groups[0].spec
        self.kv_heads = getattr(self.page_spec, "kv_heads", None)
        self.head_dim = getattr(self.page_spec, "head_dim", None)
        self.page_layers = [i for i, g in enumerate(self.group_of_layer)
                            if g is not None]
        self.state_layers = [i for i, s in enumerate(self.specs)
                             if isinstance(s, RowState)]
        if self.state_layers and self.quantized:
            raise ValueError("an int8 pool beside recurrent state is not "
                             "supported")
        # kv indices of the `depth` page arrays, array 0 first
        self.page_entries = [self.entry_of_layer[li]
                             for li in self.groups[0].layers]
        self.rows = int(rows)
        shapes = [(self.num_pages,) + tuple(s)
                  for s in self.page_spec.page_arrays(self.page_size)]
        self._arrays_per_layer = len(shapes)   # K and V; a latent layer's one
        # bytes first: a spec that has no int8 form refuses here
        self._bytes_per_page = self.depth * self.page_spec.page_nbytes(
            self.page_size, self.dtype, self.quantized)
        pay_dtype = jnp.dtype(jnp.int8) if self.quantized else self.dtype
        # immutable jnp zeros: (z,)*2 aliasing is safe until a donation,
        # and the page programs below take each array once
        self.kv = [None] * len(set(self.entry_of_layer))
        for li, spec in enumerate(self.specs):
            if self.kv[self.entry_of_layer[li]] is not None:
                continue   # a page array that an earlier layer's group made
            self.kv[self.entry_of_layer[li]] = (
                tuple(jnp.zeros(shape, pay_dtype) for shape in shapes)
                if isinstance(spec, _PAGED) else
                tuple(jnp.zeros((self.rows,) + tuple(s), self.dtype)
                      for s in spec.shapes))
        # per-(page, head) f32 dequant scales beside the int8 payloads
        self.scales = ([(jnp.zeros((self.num_pages, self.kv_heads),
                                   jnp.float32),
                         jnp.zeros((self.num_pages, self.kv_heads),
                                   jnp.float32)) for _ in self.kv]
                       if self.quantized else None)
        self._gather = self._scatter = None
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
        return int(num_layers) * PagedKV(kv_heads, head_dim).page_nbytes(
            page_size, dtype, quantized)

    @property
    def bytes_per_page(self) -> int:
        """HBM bytes one physical page costs, all `depth` arrays: what its
        spec says of one layer's page, times the depth."""
        return self._bytes_per_page

    @property
    def state_row_nbytes(self) -> int:
        """HBM bytes one decode row's recurrent state costs, all layers."""
        return sum(self.specs[li].row_nbytes(self.dtype)
                   for li in self.state_layers)

    @property
    def bytes_per_token(self) -> float:
        """KV HBM bytes one cached token costs (all layers, K+V, amortized
        scale overhead) while every group still holds it — the
        `serving_kv_bytes_per_token` series."""
        return self.bytes_per_page * len(self.groups) / self.page_size

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

    def _page_arrays(self):
        """Every array a page has a slot in, in a fixed order: the arrays
        (K and V; a latent layer's one) of page array 0, 1, ..., then (int8
        pool) their scales likewise."""
        flat = [a for e in self.page_entries for a in self.kv[e]]
        if self.quantized:
            flat += [a for e in self.page_entries for a in self.scales[e]]
        return flat

    def _set_page_arrays(self, flat):
        d, k = self.depth, self._arrays_per_layer
        for j, e in enumerate(self.page_entries):
            self.kv[e] = tuple(flat[k * j:k * (j + 1)])
            if self.quantized:
                self.scales[e] = (flat[2 * d + 2 * j], flat[2 * d + 2 * j + 1])

    @staticmethod
    def _calls(count):
        """(start, stop, bucket) of the calls that move `count` pages: as few
        calls of at most `_PAGES_PER_CALL` as do it, of equal size (so that a
        large set's last call is no small bucket of its own, which nothing
        would have compiled), each padded to a power of two."""
        calls = -(-count // _PAGES_PER_CALL)
        per_call = -(-count // max(calls, 1))
        for start in range(0, count, max(per_call, 1)):
            stop = min(count, start + per_call)
            yield start, stop, 1 << (stop - start - 1).bit_length()

    def _gather_pages(self, idx):
        """Device copies [len(idx), ...] of pages `idx` in every page
        array."""
        if self._gather is None:
            self._gather = jax.jit(
                lambda arrays, idx: [a[idx] for a in arrays])
        return self._gather(self._page_arrays(), idx)

    def _read(self, pages):
        """Host copies [m, ...] of `pages` in every page array."""
        pages = np.asarray(pages, np.int32)
        parts = []
        for start, stop, bucket in self._calls(len(pages)):
            idx = np.zeros(bucket, np.int32)   # the padding reads page 0
            idx[:stop - start] = pages[start:stop]
            got = jax.device_get(self._gather_pages(idx))
            parts.append([g[:stop - start] for g in got])
        return [np.concatenate(cols) for cols in zip(*parts)]

    def _write(self, pages, values):
        """Set `pages` in every page array to `values` (one [m, ...] array
        per array of `_page_arrays`, host or device). The program donates the
        arrays; the padding of a bucket writes the null page."""
        if self._scatter is None:
            self._scatter = jax.jit(
                lambda arrays, idx, values: [
                    a.at[idx].set(v.astype(a.dtype))
                    for a, v in zip(arrays, values)], donate_argnums=(0,))
        pages = np.asarray(pages, np.int32)
        for start, stop, bucket in self._calls(len(pages)):
            idx = np.zeros(bucket, np.int32)
            idx[:stop - start] = pages[start:stop]
            pad = bucket - (stop - start)
            whole = (start, stop) == (0, len(pages))
            vals = [v if whole else v[start:stop] for v in values]
            if pad:
                vals = [_pad_rows(v, pad) for v in vals]
            self._set_page_arrays(
                self._scatter(self._page_arrays(), idx, vals))

    def write_prompt_pages(self, pages, write_mask, *sides):
        """Scatter a prefilled prompt into its pages, every array of the
        pool (one group's layers). `sides`: one list per array a layer keeps
        (K's and V's, `k_layers, v_layers`; a latent layer's one), each with
        an entry per page array.

        pages: m physical pages in logical order; write_mask[j] False for a
        page that is not to be written (a shared page, whose content is
        already present and identical by key construction; a slot of the
        bucket that the prompt does not reach): it goes to the null page.
        k_layers/v_layers: per page array [m, Hkv, page_size, D] page-stacked
        prompt K/V. A quantized pool quantizes here (abs-max per (page,
        head)) and scatters payload + scales together."""
        tgt = np.where(np.asarray(write_mask, bool),
                       np.asarray(pages, np.int32), 0)
        if not tgt.any():
            return
        values = [a for layer in zip(*sides) for a in layer]
        if self.quantized:
            quant = [_quantize_pages(a) for a in values]
            values = [q for q, _ in quant] + [s for _, s in quant]
            serving_metrics()["kv_quant_pages"].inc(
                int(np.count_nonzero(tgt)))
        self._write(tgt, values)

    def copy_page(self, src: int, dst: int):
        """Copy-on-write body: duplicate src's content into dst (all
        arrays; payload + scales for a quantized pool), through the one-page
        forms of the gather and scatter programs: the page never leaves the
        device and nothing waits. Caller owns refcount/table updates."""
        self._write([dst], self._gather_pages(np.asarray([src], np.int32)))
        self.cow_copies_total += 1
        serving_metrics()["cow_copies"].inc()

    def read_pages(self, pages) -> list[tuple]:
        """Host copies of the given pages, per page array — the preemption
        spill buffer. Unquantized: [(k, v), ...] each [m, Hkv, page_size, D]
        (a latent layer's: [(latents,), ...], [m, page_size, stored_dim]);
        quantized: [(k, v, k_scale, v_scale), ...] with [m, Hkv] scales
        (int8 payload + f32 scales round-trip the host bit-exactly, so a
        spilled quantized request resumes with zero extra error)."""
        flat, d = self._read(list(pages)), self.depth
        k = self._arrays_per_layer
        return [tuple(flat[k * j:k * (j + 1)])
                + ((flat[2 * d + 2 * j], flat[2 * d + 2 * j + 1])
                   if self.quantized else ())
                for j in range(d)]

    def restore_pages(self, pages, kv_host, rows):
        """Write spilled host pages back: kv_host is read_pages() output for
        the request's full logical page list; `rows` selects which logical
        indices need restoring (prefix-shared hits don't), `pages` the
        freshly allocated physical destinations, aligned with `rows`."""
        if not len(pages):
            return
        sel = np.asarray(list(rows), np.int32)
        k = self._arrays_per_layer
        values = [h[i][sel] for h in kv_host for i in range(k)]
        if self.quantized:
            values += [h[i][sel] for h in kv_host for i in (2, 3)]
        self._write(list(pages), values)

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
        entries = [self.entry_of_layer[li] for li in self.state_layers]
        new = self._write_state([self.kv[e] for e in entries],
                                np.int32(row), values)
        for e, layer in zip(entries, new):
            self.kv[e] = layer

    def read_state(self, row: int) -> list[tuple]:
        """Host copies of decode row `row`'s slot, per `RowState` layer: the
        state half of a preemption spill (`write_state` restores it)."""
        if not self.state_layers:
            return []
        if self._read_state is None:
            self._read_state = jax.jit(lambda states, row: [
                tuple(jax.lax.dynamic_index_in_dim(a, row, 0, keepdims=False)
                      for a in layer) for layer in states])
        got = self._read_state(
            [self.kv[self.entry_of_layer[li]] for li in self.state_layers],
            np.int32(row))
        return [tuple(np.asarray(a) for a in layer) for layer in got]
