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

Pages of two shapes. Layer kinds may differ in their KV head count (a
model whose sliding layers keep 8 KV heads beside full layers of 4) while
key width, value width and page size agree: the larger page is then `span`
ADJACENT pages of the smaller, which `[units, 4, ps, W]` seen as `[units / 2,
8, ps, W]` shows to be the same memory. The pool's arrays, its budget and
its counts (`pages_total`, `pages_free`) are in UNITS of the smallest page;
a group says how many units its page spans (`PageGroup.span`), a page's
handle everywhere on the host is its FIRST unit (a multiple of its span),
and a layer of span k reads the arrays through the k-times-coarser view at
`handle // k`. One byte budget serves both shapes: the free list keeps whole
free BLOCKS of `span` aligned units apart from the free units of blocks
that a smaller page has broken into, hands a small page a unit of a broken
block first and breaks a whole one only when there is none, and joins a
block again when its last unit comes back. A large page refused while free
units lay unpaired is counted (`serving_pool_alloc_refused_total`): that is
what stranding looks like from inside. Spill, restore, copy-on-write and the
prompt's scatter move units, so the page programs know one shape.

Physical page 0 is the reserved NULL page (the first block of a pool of two
shapes: units 0 .. span - 1): never allocated, never referenced
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
           "PageGroup", "page_layout", "prefix_page_key", "stored_width"]

# pages one call of the gather or the scatter program moves at most: larger
# sets go in several calls, so the bucketed shapes end here
_PAGES_PER_CALL = 512


def stored_width(width):
    """The width a page array gives rows of `width` values: whole lane tiles
    of 128 for a row wider than one (a key 192 wide is stored as 256, zero
    behind it); a row of one tile or less as it is. The device's tiled
    layout pads such a row to whole tiles anyway, and for a minor dimension
    that is no multiple of 128 the chip's compiler picks a layout of its own
    for the pool and re-lays all of it round every kernel that reads it (a
    copy of the whole pool a layer: `tests/test_chip_compile.py`)."""
    return width if width <= 128 else -(-width // 128) * 128


@dataclasses.dataclass(frozen=True)
class PagedKV:
    """An attention layer's cache: K pages [n_pages, kv_heads, page_size,
    head_dim] and V pages [..., value_dim] (`value_dim` None: as wide as the
    keys; each width as `stored_width` stores it: the model hands over, and
    writes, rows that wide), block tables, prefix keys, copy-on-write;
    spilled page by page."""

    kv_heads: int
    head_dim: int
    value_dim: int | None = dataclasses.field(default=None, kw_only=True)

    kind = "full"    # the `serving_pages_live` gauge's label

    @property
    def value_width(self) -> int:
        return self.head_dim if self.value_dim is None else self.value_dim

    def page_arrays(self, page_size):
        """The shape of one page in each array a layer keeps: K and V."""
        return ((self.kv_heads, page_size, stored_width(self.head_dim)),
                (self.kv_heads, page_size, stored_width(self.value_width)))

    def page_nbytes(self, page_size, dtype, quantized=False) -> int:
        """HBM bytes one page costs in ONE layer, both sides, as stored:
        payload plus, when quantized, the per-(page, head) f32 scales."""
        values = self.kv_heads * page_size * (
            stored_width(self.head_dim) + stored_width(self.value_width))
        if quantized:
            return values + 2 * self.kv_heads * 4
        return values * jnp.dtype(dtype).itemsize

    def pages_per_step(self, page_size, width, itemsize) -> int:
        """Pages of a row one grid step of this kind's decode kernel takes,
        for a block table `width` wide."""
        from ...ops.pallas.decode_attention import pages_per_step

        return pages_per_step(
            self.kv_heads, page_size, stored_width(self.head_dim), width,
            itemsize, stored_width(self.value_width))


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
    keeps its K and V in the pool's page array j. `span`: the units of the
    pool's smallest page that one page of this group takes (1 in a pool of
    one page shape)."""

    spec: object
    layers: tuple
    span: int = 1

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
    spans = dict(zip(kinds, _page_spans([k.page_arrays(1) for k in kinds])))
    depth = math.gcd(*(len(v) for v in kinds.values()))
    groups = [PageGroup(spec, tuple(layers[i:i + depth]), spans[spec])
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


def _page_spans(shapes):
    """Per kind of paged layer, the units of the smallest page its page
    takes; `shapes`: each kind's `page_arrays(1)`. Kinds of one shape: all
    1. Kinds may differ in their arrays' LEADING dimension alone (the KV
    heads), by one whole factor a kind: the larger page is then that many
    adjacent smaller ones, and the pool holds two sizes, the unit and one
    multiple of it."""
    unit = min(shapes)
    spans = []
    for arrays in shapes:
        ratios = {a[0] / u[0] for a, u in zip(arrays, unit)}
        alike = (len(arrays) == len(unit) and len(ratios) == 1
                 and all(a[1:] == u[1:] for a, u in zip(arrays, unit)))
        span = ratios.pop() if alike else 0
        if span != int(span) or span < 1:
            raise ValueError(
                "paged layers whose pages differ in more than a whole "
                "multiple of the KV head count in one pool are not "
                f"supported: {arrays} beside {unit}")
        spans.append(int(span))
    if len(set(spans) - {1}) > 1:
        raise ValueError("paged layers of more than two page sizes in one "
                         f"pool are not supported: spans {sorted(set(spans))}")
    return spans


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
        # the unit of the pool is the smallest page: its spec says what the
        # arrays are (every group's, in a pool of one page shape)
        self.page_spec = min(self.groups, key=lambda g: g.span).spec
        # units of the largest page: a free BLOCK is that many aligned units
        self.span = max(g.span for g in self.groups)
        if self.span > 1 and self.quantized:
            raise ValueError("an int8 pool of two page shapes is not "
                             "supported")
        # whole blocks only: the coarser view of the arrays must divide them
        self.num_pages -= self.num_pages % self.span
        if self.num_pages < 2 * self.span:
            raise ValueError(f"num_pages must be >= {2 * self.span} (the "
                             "first block is reserved)")
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
        # whole free blocks (a pool of one page shape: free pages), and per
        # broken block the units of it that are free
        self.free: collections.deque = collections.deque(
            range(1, self.num_pages // self.span))
        self._broken: dict[int, list] = {}
        self._units_free = self.num_pages - self.span
        self.ref = np.zeros(self.num_pages, np.int32)
        self._span_of = np.ones(self.num_pages, np.int32)  # by first unit
        self._prefix: dict[bytes, int] = {}   # key -> page
        self._page_key: dict[int, bytes] = {}  # page -> key (registered only)
        self.allocs_total = 0  # lifetime allocations (tests/introspection)
        self.cow_copies_total = 0

    # -- accounting ------------------------------------------------------ #

    @staticmethod
    def page_nbytes(num_layers, kv_heads, head_dim, page_size,
                    dtype=jnp.float32, quantized=False, value_dim=None) -> int:
        """HBM bytes one physical page costs across all layers and both K/V
        sides — payload plus, when quantized, the per-(page, head) f32
        scales. The unit of the equal-budget serving A/B."""
        spec = PagedKV(kv_heads, head_dim, value_dim=value_dim)
        return int(num_layers) * spec.page_nbytes(page_size, dtype, quantized)

    @property
    def bytes_per_page(self) -> int:
        """HBM bytes one physical page (a unit, in a pool of two shapes)
        costs, all `depth` arrays: what its spec says of one layer's page,
        times the depth."""
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
        return (self.bytes_per_page * sum(g.span for g in self.groups)
                / self.page_size)

    @property
    def pages_total(self) -> int:
        """Allocatable units (the null block is not): with `pages_free` the
        share of the pool's BYTES in use, whatever shapes hold them."""
        return self.num_pages - self.span

    @property
    def pages_free(self) -> int:
        return self._units_free

    @property
    def blocks_free(self) -> int:
        """Whole free blocks: what a page of the larger shape can take."""
        return len(self.free)

    def update_gauges(self):
        m = serving_metrics()
        m["pages_free"].set(self.pages_free)
        m["pages_total"].set(self.pages_total)
        m["kv_bytes_per_token"].set(self.bytes_per_token)

    # -- allocation / refcounts ------------------------------------------ #

    def alloc(self, span=1) -> int | None:
        """One free page of `span` units with refcount 1 (its handle: its
        first unit), or None when the pool has none. A page of the pool's
        largest shape takes a whole block, from the far end of the list; a
        smaller one a unit of a broken block, and breaks the nearest whole
        block only when there is none."""
        if span == self.span:
            if not self.free:
                if self._units_free >= span:   # free bytes, unpaired
                    serving_metrics()["pool_alloc_refused"].inc(kind=next(
                        g.spec.kind for g in self.groups if g.span == span))
                return None
            page = span * (self.free.popleft() if span == 1
                           else self.free.pop())
        elif self._broken:
            block = next(reversed(self._broken))
            page = self._broken[block].pop()
            if not self._broken[block]:
                del self._broken[block]
        elif self.free:
            block = self.free.popleft()
            first = block * self.span
            page, self._broken[block] = first, list(
                range(first + self.span - 1, first, -1))
        else:
            return None
        self.ref[page], self._span_of[page] = 1, span
        self._units_free -= span
        self.allocs_total += 1
        return page

    def units_of(self, pages):
        """The units of `pages` (handles), each page's side by side."""
        pages = np.asarray(pages, np.int32).reshape(-1)
        if self.span == 1:
            return pages
        spans = self._span_of[pages]
        first = np.repeat(pages, spans)
        # 0 .. span - 1 within each page
        within = np.arange(len(first)) - np.repeat(
            np.cumsum(spans) - spans, spans)
        return (first + within).astype(np.int32)

    def incref(self, page: int):
        assert self.ref[page] > 0, f"incref on unallocated page {page}"
        self.ref[page] += 1

    def release(self, page: int):
        """Drop one reference; a page at zero is unregistered and freed."""
        assert self.ref[page] > 0, f"release of unallocated page {page}"
        self.ref[page] -= 1
        if self.ref[page] > 0:
            return
        self.unregister_page(page)
        span = int(self._span_of[page])
        self._units_free += span
        block = page // self.span
        if span == self.span:
            self.free.append(block)
            return
        units = self._broken.setdefault(block, [])
        units.append(page)
        if len(units) == self.span:   # whole again: the next to be broken
            del self._broken[block]
            self.free.appendleft(block)

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

    def write_prompt_pages(self, pages, write_mask, *sides, span=1):
        """Scatter a prefilled prompt into its pages, every array of the
        pool (one group's layers). `sides`: one list per array a layer keeps
        (K's and V's, `k_layers, v_layers`; a latent layer's one), each with
        an entry per page array. `span`: the units a page of this group
        takes; its stacked pages [m, span * Hkv, ...] are then written as
        [m * span, Hkv, ...] units, the same bytes.

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
        if span > 1:
            # an unwritten page's units all go to the null block's first
            tgt = np.where(tgt[:, None] > 0,
                           tgt[:, None] + np.arange(span), 0).reshape(-1)
            values = [a.reshape((-1, a.shape[1] // span) + a.shape[2:])
                      for a in values]
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
        self._write(self.units_of([dst]),
                    self._gather_pages(self.units_of([src])))
        self.cow_copies_total += 1
        serving_metrics()["cow_copies"].inc()

    def read_pages(self, pages) -> list[tuple]:
        """Host copies of the given pages (units, in a pool of two shapes:
        `units_of` a row's pages), per page array — the preemption
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
