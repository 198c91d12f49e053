"""KV caches for continuous batching: slot-dense and paged-with-prefix-reuse.

`models/decode.py`'s caches carry ONE `cache_len` scalar for the whole
batch — every sequence must sit at the same depth, which is exactly what a
serving mix is not. `SlotKVCache` keeps the same layer-stacked buffer
layout ([L, S, M, H, D], S = slots) but gives every slot its own length,
so requests at different decode depths share one fixed-shape batch and one
compiled program (the pjit/TPUv4 static-shapes rule: the program is
compiled once, the *data* changes).

`PagedKVCache` goes one step further: the physical buffer is a pool of
fixed-size pages ([L, pages, H, page_size, D] — heads outside the page
rows, so one head's page is a contiguous [page_size, D] tile the Pallas
decode kernel can block on the chip) and each slot owns an
ordered page table instead of a contiguous stripe. Two things fall out:

- per-request memory is sized by the request (pages allocated at
  admission), not by the engine-wide max_len;
- a page's content is position-addressed but *location-free*, so pages
  holding a shared prompt prefix can be mapped read-only into many slots
  at once. The host-side `PrefixIndex` (a radix tree over page-sized
  token chunks) remembers which pages encode which prompt prefixes;
  `PagedAllocator` matches the longest cached prefix at admission, maps
  those pages copy-on-write (refcounted — they are FULL pages and are
  never written again, so "copy" never actually happens), and releases a
  retiring request's full prompt pages back into the tree instead of
  wiping them. Prefill then runs only on the uncached suffix.

Every program stays jit-able because page tables are fixed-shape
([slots, pages_per_slot] int32, padded with a reserved trash page): the
compiled programs gather a slot's pages into the familiar contiguous
[L, 1, rows, H, D] view (or, for a family that loops over its layers, one
layer's [1, rows, H, D] at a time: `LayerwiseSlotView`), run the
unchanged family forward, and scatter the updated pages back.
Gather/scatter indices are traced data — the request mix, hit/miss
pattern, and eviction history never change a program shape, so the
engine's compile count stays flat.

Write-safety under sharing, the invariant the allocator maintains: only
FULL prompt pages ever enter the tree, and reuse is capped at
`(prompt_len - 1) // page_size` pages (the last prompt token always
prefills, producing the first output logits). Writes land at or past a
slot's current `length`, which always lies in a private page, so a
shared page is never written. The compiled write is page-granular
(`_scatter_rows`): it re-writes the OTHER rows of that private page with
their unchanged bytes, a no-op on a page that has this one writer.

Correctness invariant (why retired slots never need zeroing): a write
always lands at the slot's current `length`, and the position mask
(`cached_attention_mask`) only lets queries attend cache rows `<= position
< length`. Rows at or beyond `length` — stale K/V from a retired request,
or padding from a chunked prefill — are never attended, and are overwritten
as the slot's length advances. Admission therefore just resets `length`
(to zero, or to the reused prefix length on a paged prefix hit); the
O(L*M*H*D) cache wipe a naive design would pay per request is a single
scalar store.

Prefill chunks are padded to a fixed size so every chunk hits the same
compiled program; the padded tail can spill up to `chunk - 1` rows past the
slot's logical `max_len`, so the physical buffer allocates `max_len +
pad_slack` rows (`pad_slack` = the chunk size). `lengths` only ever
advances by *real* token counts, keeping the invariant above.
"""

from __future__ import annotations

import dataclasses
import heapq
from typing import Any, Callable

import jax
import jax.numpy as jnp
import numpy as np

from ..models.common import part
from ..models.contract import (  # noqa: F401
    CacheSpec,
    StatePool,
    WithSide,
    ring_positions,
)
from ..telemetry.trace import span


@dataclasses.dataclass(frozen=True)
class SlotKVCache:
    """Fixed-shape slot-indexed decode cache.

    k/v: [num_layers, num_slots, max_len + pad_slack, num_kv_heads,
    head_dim]; lengths: [num_slots] int32 — per-slot decode depth. The
    arrays are pytree children, so the whole cache threads through jit (and
    donates) like any other state; `max_len`/`pad_slack` are static.
    """

    k: jax.Array
    v: jax.Array
    lengths: jax.Array
    max_len: int
    pad_slack: int

    @classmethod
    def create(
        cls,
        num_layers: int,
        num_slots: int,
        max_len: int,
        num_kv_heads: int,
        head_dim: int,
        dtype: Any = jnp.bfloat16,
        pad_slack: int = 0,
    ) -> "SlotKVCache":
        shape = (num_layers, num_slots, max_len + pad_slack, num_kv_heads,
                 head_dim)
        return cls(
            k=jnp.zeros(shape, dtype),
            v=jnp.zeros(shape, dtype),
            lengths=jnp.zeros((num_slots,), jnp.int32),
            max_len=max_len,
            pad_slack=pad_slack,
        )

    @property
    def num_layers(self) -> int:
        return self.k.shape[0]

    @property
    def num_slots(self) -> int:
        return self.k.shape[1]

    @property
    def rows(self) -> int:
        """Physical rows per slot (max_len + pad_slack)."""
        return self.k.shape[2]

    def nbytes(self) -> int:
        return self.k.nbytes + self.v.nbytes


@part("cache.view")
def slot_caches(cache: SlotKVCache, slot: jax.Array):
    """One slot's caches in `models/decode.py` layout: (k [L, 1, M, H, D],
    v [L, 1, M, H, D], cache_len scalar) — exactly what a family `forward`
    expects for a batch-of-one decode. `slot` may be traced (one compiled
    program covers every slot)."""
    ks = jax.lax.dynamic_slice_in_dim(cache.k, slot, 1, axis=1)
    vs = jax.lax.dynamic_slice_in_dim(cache.v, slot, 1, axis=1)
    return ks, vs, cache.lengths[slot]


@part("cache.write")
def write_slot(cache: SlotKVCache, slot: jax.Array, new_k: jax.Array,
               new_v: jax.Array, advance: jax.Array) -> SlotKVCache:
    """Write one slot's updated [L, 1, M, H, D] buffers back and advance its
    length by `advance` REAL tokens (chunk padding is excluded by the
    caller, per the module invariant)."""
    return dataclasses.replace(
        cache,
        k=jax.lax.dynamic_update_slice_in_dim(cache.k, new_k, slot, axis=1),
        v=jax.lax.dynamic_update_slice_in_dim(cache.v, new_v, slot, axis=1),
        lengths=cache.lengths.at[slot].set(cache.lengths[slot] + advance),
    )


def reset_slot(cache: SlotKVCache, slot: jax.Array) -> SlotKVCache:
    """Admit a new request into `slot`: length back to zero. The stale K/V
    rows stay in place — the position mask hides them (see module
    docstring)."""
    return dataclasses.replace(cache,
                               lengths=cache.lengths.at[slot].set(0))


def _flatten(cache: SlotKVCache):
    return (cache.k, cache.v, cache.lengths), (cache.max_len, cache.pad_slack)


def _unflatten(aux, children):
    k, v, lengths = children
    max_len, pad_slack = aux
    return SlotKVCache(k=k, v=v, lengths=lengths, max_len=max_len,
                       pad_slack=pad_slack)


jax.tree_util.register_pytree_node(SlotKVCache, _flatten, _unflatten)


# ---------------------------------------------------------------------------
# paged pool (device side)
# ---------------------------------------------------------------------------


def _split_side(cache, k):
    """(K's part, the side row's part or None) of what stands in K's place."""
    return (k.rows, k.side) if cache.side is not None else (k, None)


@dataclasses.dataclass(frozen=True)
class PagedKVCache:
    """Paged KV pool with fixed-shape per-slot page tables.

    k/v: [num_layers, num_pages + 1, num_kv_heads, page_size, head_dim] —
    the last page is the reserved TRASH page backing padded page-table
    entries (idle lanes gather it, masked rows and dead writes land in
    it, and it is never allocated). lengths: [num_slots] int32, the
    per-slot decode depth (which STARTS at the reused prefix length on a
    prefix hit). The arrays are pytree children so the cache threads
    through jit and donates; `page_size`/`pages_per_slot`/... are static.

    QUANTIZED mode (`create(kv_dtype="int8")`): k/v hold int8 codes and
    `k_scale`/`v_scale` ([L, pages+1, H, page_size] bf16, one symmetric
    absmax scale per row per head — `ops/quant.py kv_quantize_rows`)
    ride alongside as extra pytree children. Halving the bytes per page
    doubles the pages — and therefore the concurrent users — a fixed
    HBM budget holds. All writes quantize and all dense views
    dequantize (to `compute_dtype`), so the gather/scatter programs and
    the host-side page accounting are unchanged; the Pallas
    paged-attention kernel dequantizes per page in-kernel instead of
    materializing a dense copy. Per-ROW scales keep appends independent
    (a new row never re-scales a page's existing rows), which is what
    keeps shared copy-on-write pages bit-stable.

    LATENT mode (`create(latent=True)`; `CacheSpec.kind == "latent"`):
    `k` holds the one row a token has and `v` is None. Every view then
    carries None in V's place and every write takes None for V's rows;
    int8 codes are not implemented for it.

    RING mode (`create(window=W)`; a group of `GroupedPagedCache` whose
    layers see the last W positions only): a slot's table row is a ring
    of `pages_per_slot` = ceil((W + pad_slack) / page_size) + 1 pages,
    the page of positions [p * page_size, (p + 1) * page_size) is entry
    `p % pages_per_slot`, and view row r holds the newest written
    position that is r modulo `rows`. A chunk's rows (padding included)
    overwrite positions at least W + 1 behind the chunk's first query,
    so what a query may see is never overwritten before it is read, and
    a slot holds the same pages at any length. Who reads a ring view
    masks by POSITION (`ring_positions`), so stale rows of the slot's
    last tenant, whose positions come out negative, are never seen.

    SIDE ROW (`create(side_width=w)`; `CacheSpec.side_width`): `side`
    holds a third row of w lanes a token and layer under the SAME page
    ids, so the page table, the allocator, the prefix index, a fork and a
    release carry it without knowing it. A page's `page_size` x w lanes
    are stored as whole 128-lane rows, [L, pages + 1, page_size * w / 128,
    128] (token t of a page at row `t * w // 128`, lanes from `t * w %
    128`): a [page_size, 64] page would lie padded to 128 lanes on the
    chip, twice its bytes, while this one is a whole (8, 128)(2, 1) tile
    of bf16 and a page is still indexed outside the tile. So w divides 128
    and page_size x w is a whole number of such rows, or `create` raises.
    Views carry the side rows as one head, [L, B, R, 1, w], beside K's (or
    the latent rows) in a `WithSide`; int8 codes and a ring are not
    implemented with it.

    `stats`: a family's own device counters (`family.init_serving_stats`),
    or None. They ride here because the cache is what both engine
    programs donate and return: they are accumulated on the device and
    never read on a step's path (`Engine.device_counters`).
    """

    k: jax.Array
    v: jax.Array | None
    lengths: jax.Array
    page_size: int
    pages_per_slot: int
    max_len: int
    pad_slack: int
    k_scale: jax.Array | None = None
    v_scale: jax.Array | None = None
    compute_dtype: Any = jnp.bfloat16
    stats: Any = None
    window: int | None = None
    side: jax.Array | None = None

    @classmethod
    def create(
        cls,
        num_layers: int,
        num_slots: int,
        max_len: int,
        num_kv_heads: int,
        head_dim: int,
        dtype: Any = jnp.bfloat16,
        page_size: int = 16,
        pad_slack: int = 0,
        num_pages: int | None = None,
        kv_dtype: Any = None,
        latent: bool = False,
        stats: Any = None,
        window: int | None = None,
        side_width: int = 0,
    ) -> "PagedKVCache":
        if page_size < 1:
            raise ValueError(f"page_size must be >= 1, got {page_size}")
        if kv_dtype not in (None, "int8", jnp.int8):
            raise ValueError(
                f"kv_dtype must be None (store in `dtype`) or 'int8', "
                f"got {kv_dtype!r}")
        quantized = kv_dtype is not None
        if latent and quantized:
            raise ValueError(
                "an int8 latent pool is not implemented: kv_dtype='int8' "
                "quantizes K and V rows per head, and a latent row's key "
                "and value parts would need scales of their own")
        if window is not None and (quantized or window < 1):
            raise ValueError(
                "a ring of pages (window=) holds rows in `dtype`: an int8 "
                "ring is not implemented, and a window is at least 1; got "
                f"window={window}")
        if side_width and (quantized or window is not None):
            raise ValueError(
                "a side row (side_width=) lives beside rows in `dtype` "
                "under every position: with int8 codes or inside a ring it "
                "is not implemented")
        if side_width and (128 % side_width or page_size * side_width % 128):
            raise ValueError(
                "a page's side rows are stored as whole 128-lane rows: "
                f"side_width={side_width} must divide 128 and page_size="
                f"{page_size} x side_width be a multiple of 128")
        # a slot's view must cover max_len rows plus the chunk-padding
        # spill (see SlotKVCache docstring) — round up to whole pages
        pages_per_slot = -(-(max_len + pad_slack) // page_size)
        if window is not None:
            # the ring: the window, one chunk, and a page of rounding
            pages_per_slot = min(pages_per_slot,
                                 -(-(window + pad_slack) // page_size) + 1)
        if num_pages is None:
            num_pages = num_slots * pages_per_slot
        if num_pages < pages_per_slot:
            raise ValueError(
                f"num_pages({num_pages}) < pages_per_slot({pages_per_slot}):"
                " a max-size request could never be admitted")
        shape = (num_layers, num_pages + 1, num_kv_heads, page_size, head_dim)
        scale_shape = shape[:-1]
        return cls(
            k=jnp.zeros(shape, jnp.int8 if quantized else dtype),
            v=None if latent
            else jnp.zeros(shape, jnp.int8 if quantized else dtype),
            lengths=jnp.zeros((num_slots,), jnp.int32),
            page_size=page_size,
            pages_per_slot=pages_per_slot,
            max_len=max_len,
            pad_slack=pad_slack,
            k_scale=jnp.zeros(scale_shape, jnp.bfloat16) if quantized
            else None,
            v_scale=jnp.zeros(scale_shape, jnp.bfloat16) if quantized
            else None,
            compute_dtype=dtype,
            stats=stats,
            window=window,
            side=jnp.zeros(shape[:2] + (page_size * side_width // 128, 128),
                           dtype) if side_width else None,
        )

    @property
    def side_width(self) -> int:
        """Lanes of the side row a token and layer (0 without one)."""
        if self.side is None:
            return 0
        return self.side.shape[2] * self.side.shape[3] // self.page_size

    @property
    def ring(self) -> bool:
        """A slot's table row is a ring (class docstring, RING mode)."""
        return self.window is not None

    def with_stats(self, stats) -> "PagedKVCache":
        return dataclasses.replace(self, stats=stats)

    @property
    def quantized(self) -> bool:
        return self.k_scale is not None

    @property
    def latent(self) -> bool:
        return self.v is None

    @property
    def num_layers(self) -> int:
        return self.k.shape[0]

    @property
    def num_pages(self) -> int:
        """Allocatable pages (the +1 trash page is excluded)."""
        return self.k.shape[1] - 1

    @property
    def trash_page(self) -> int:
        """Reserved page index backing padded page-table entries."""
        return self.k.shape[1] - 1

    @property
    def num_slots(self) -> int:
        return self.lengths.shape[0]

    @property
    def rows(self) -> int:
        """Rows in one slot's gathered view (pages_per_slot * page_size)."""
        return self.pages_per_slot * self.page_size

    @property
    def page_nbytes(self) -> int:
        """HBM bytes one page costs across K and V (codes + scales in
        quantized mode; the one pool in latent mode) and all layers —
        the unit behind the `serving_kv_bytes_in_use` gauge and the HBM math in
        docs/serving.md: pages a budget holds = budget / page_nbytes."""
        L, _, H, ps, D = self.k.shape
        per = L * ps * H * D * self.k.dtype.itemsize
        if self.quantized:
            per += L * ps * H * self.k_scale.dtype.itemsize
        return (per if self.latent else 2 * per) + self.side_page_nbytes

    @property
    def side_page_nbytes(self) -> int:
        """Of `page_nbytes`, what the side row takes (0 without one)."""
        if self.side is None:
            return 0
        return (self.num_layers * self.page_size * self.side_width
                * self.side.dtype.itemsize)

    def nbytes(self) -> int:
        total = self.k.nbytes + (0 if self.latent else self.v.nbytes)
        if self.quantized:
            total += self.k_scale.nbytes + self.v_scale.nbytes
        if self.side is not None:
            total += self.side.nbytes
        return total


def _both(f, k, v):
    """`f` over K's array and over V's; a latent pool has no V, and None
    stays None."""
    return f(k), (None if v is None else f(v))


def _dense_pages(codes: jax.Array, scales: jax.Array | None,
                 idx: jax.Array, dtype) -> jax.Array:
    """Gather pool pages at `idx` (any int32 index shape) and materialize
    them densely as [L, *idx, page_size, H, D]: a plain gather for a
    bf16 pool, gather + per-row dequantization for an int8 pool."""
    pages = codes[:, idx]                       # [L, *idx, H, ps, D]
    if scales is not None:
        from ..ops.quant import kv_dequantize_rows

        pages = kv_dequantize_rows(pages, scales[:, idx], dtype)
    # pool pages keep heads outside the rows; the dense views are
    # row-major ([rows, H, D], `models/decode.py` layout)
    return jnp.swapaxes(pages, -3, -2)


def _side_view(cache: PagedKVCache, idx: jax.Array, batch: int) -> jax.Array:
    """The side rows of the pages at `idx` ([batch, P] or [P] with batch
    1) as a view of one head, [L, batch, P * page_size, 1, w]."""
    return cache.side[:, idx].reshape(
        cache.num_layers, batch, -1, 1, cache.side_width)


class LayerwiseSlotView:
    """One pool buffer (K's, V's or the side row's) as ONE slot sees it,
    gathered a LAYER at a time: what stands in the place of a stacked view
    `[L, 1, R, heads, width]` (`shape`) without that array ever existing.
    `at_layer(i)` is layer i's `[1, R, heads, width]` from the slot's pages
    of that layer alone, dequantised as `_dense_pages` does. In the image
    of `ops.paged_attention.PagedKV.at_layer`: a family that loops over
    its layers holds one layer's view at a time, writes its chunk's rows
    into that temporary and returns THE ROWS, not the view
    (`paged_write_chunk` takes them).

    The `is_layerwise_view` marker lets a family tell it from arrays and
    from pools by what it is, as `is_paged_kv` does."""

    is_layerwise_view = True

    def __init__(self, cache: PagedKVCache, pool: jax.Array,
                 scales: jax.Array | None, table_row: jax.Array,
                 heads: int, width: int, side: bool = False):
        self._table_row, self._side = table_row, side
        self._pages = pool.shape[1]
        # the layer axis folded into the page axis (both lie outside a
        # page's tile: no data moves), so that one layer's pages are ONE
        # gather over one index and no slice of a layer's pool is made
        self._pool = pool.reshape((-1,) + pool.shape[2:])
        self._scales = None if scales is None else scales.reshape(
            (-1,) + scales.shape[2:])
        self.shape = (cache.num_layers, 1, cache.rows, heads, width)
        self.dtype = cache.compute_dtype

    def at_layer(self, layer: int) -> jax.Array:
        """Layer `layer`'s view [1, R, heads, width]."""
        with part("cache.view"):
            at = layer * self._pages + self._table_row
            if self._side:      # a page's rows, stored 128 lanes wide
                return self._pool[at].reshape((1,) + self.shape[2:])
            return _dense_pages(
                self._pool[None],
                None if self._scales is None else self._scales[None],
                at, self.dtype).reshape((1,) + self.shape[2:])


@part("cache.view")
def paged_slot_view(cache: PagedKVCache, table_row: jax.Array,
                    slot: jax.Array, by_layer: bool = False):
    """One slot's pages gathered into `models/decode.py` layout:
    (k [L, 1, R, H, D], v [L, 1, R, H, D], length scalar), R =
    pages_per_slot * page_size, dequantized to `compute_dtype` on an
    int8 pool. `table_row` ([pages_per_slot] int32) and `slot` are
    traced — one compiled program covers every slot and every page
    mapping. A grouped cache takes one table row a group and gives one
    view a group (a ring group's view is its ring). `by_layer`: a
    `LayerwiseSlotView` in every stacked view's place, and nothing is
    gathered until a family asks for a layer."""
    if isinstance(cache, GroupedPagedCache):
        views = [paged_slot_view(g, row, slot, by_layer)
                 for g, row in zip(cache.groups, table_row)]
        return (tuple(v[0] for v in views), tuple(v[1] for v in views),
                cache.lengths[slot])
    L, _, H, ps, D = cache.k.shape
    P = cache.pages_per_slot
    if by_layer:
        def view(kv):
            return LayerwiseSlotView(cache, *kv, table_row, H, D)
    else:
        def view(kv):
            return _dense_pages(*kv, table_row, cache.compute_dtype).reshape(
                L, 1, P * ps, H, D)
    ks, vs = _both(view, (cache.k, cache.k_scale), None if cache.latent
                   else (cache.v, cache.v_scale))
    if cache.side is not None:
        ks = WithSide(ks, LayerwiseSlotView(
            cache, cache.side, None, table_row, 1, cache.side_width,
            side=True) if by_layer else _side_view(cache, table_row, 1))
    return ks, vs, cache.lengths[slot]


def paged_decode_operands(cache: PagedKVCache):
    """(K, V) as a family forward takes the WHOLE pools under the paged
    kernels (`ops.paged_attention.PagedKV`: nothing is gathered, the kernel
    walks the page table): one pool a group under a grouped cache, a
    `WithSide` in K's place where there is a side row, None in V's for
    latent rows. The decode-side twin of `paged_slot_view`."""
    from ..ops.paged_attention import PagedKV

    if isinstance(cache, GroupedPagedCache):
        ks, vs = zip(*(paged_decode_operands(g) for g in cache.groups))
        return ks, None if cache.latent else vs
    k = PagedKV(cache.k, cache.k_scale, cache.compute_dtype)
    if cache.side is not None:
        k = WithSide(k, PagedKV(cache.side, None, cache.compute_dtype))
    return k, None if cache.latent else PagedKV(
        cache.v, cache.v_scale, cache.compute_dtype)


@part("cache.write")
def paged_write_chunk(cache: PagedKVCache, table_row: jax.Array,
                      slot: jax.Array, rows_k, rows_v,
                      advance: jax.Array) -> PagedKVCache:
    """Write the rows a prefill chunk produced ([L, 1, chunk, H, D]: view
    rows [length, length + chunk), padding included) to the pool and
    advance the slot's length by `advance` REAL tokens. `_scatter_rows`
    rewrites the `chunk // page_size + 1` pages they straddle: per-chunk
    write traffic is O(chunk), not O(max_len). Every written row is at or
    past `length`, hence in a PRIVATE page by the allocator's invariant:
    shared copy-on-write pages are never touched, and the rows of a
    private page below `length` are put back as the bytes they were
    (selected, not re-encoded: an int8 round-trip is NOT idempotent, so
    re-quantizing "the same values" would drift them)."""
    if isinstance(cache, GroupedPagedCache):
        return cache.map_groups(
            lambda g, row, rk, rv: paged_write_chunk(g, row, slot, rk, rv,
                                                     advance),
            table_row, rows_k, rows_v)
    length = cache.lengths[slot]
    chunk = jax.tree.leaves(rows_k)[0].shape[2]
    return _scatter_rows(cache, table_row[None], length[None],
                         jnp.full((1,), chunk, jnp.int32), rows_k, rows_v,
                         cache.lengths.at[slot].set(length + advance))


@part("cache.write")
def paged_write_slot(cache: PagedKVCache, table_row: jax.Array,
                     slot: jax.Array, new_k: jax.Array, new_v: jax.Array,
                     advance: jax.Array, chunk: int) -> PagedKVCache:
    """`paged_write_chunk` for a family that returns whole updated views
    ([L, 1, R, H, D]): the chunk only changes view rows [length, length +
    chunk), so exactly those `chunk` rows (a ring's modulo R) are taken
    out of the views and written. `chunk` must be a static python int."""
    if isinstance(cache, GroupedPagedCache):
        return cache.map_groups(
            lambda g, row, nk, nv: paged_write_slot(g, row, slot, nk, nv,
                                                    advance, chunk),
            table_row, new_k, new_v)
    L, _, H, ps, D = cache.k.shape
    R = cache.rows
    # rows never spill past the view: length <= max_len and pad_slack
    # covers the chunk padding (module docstring)
    rows = cache.lengths[slot] + jnp.arange(chunk, dtype=jnp.int32)
    if cache.ring:
        rows = rows % R
    new_k, new_side = _split_side(cache, new_k)
    win_k, win_v = _both(
        lambda new: jnp.take(new.reshape(L, R, H, D), rows, axis=1)[:, None],
        new_k, new_v)
    if new_side is not None:
        win_k = WithSide(win_k, jnp.take(new_side.reshape(
            L, R, 1, cache.side_width), rows, axis=1)[:, None])
    return paged_write_chunk(cache, table_row, slot, win_k, win_v, advance)


@part("cache.write")
def _scatter_rows(cache: PagedKVCache, table: jax.Array, start: jax.Array,
                  count: jax.Array, win_k: jax.Array, win_v: jax.Array,
                  new_lengths: jax.Array) -> PagedKVCache:
    """Write, for every lane n, the first `count[n]` rows of its window
    (`win_k`/`win_v` [L, N, W, H, D]) at view rows [start[n], start[n] +
    count[n]) of the lane's page-table row (`table` [N, pages_per_slot]),
    quantizing codes + per-row scales on an int8 pool. The shared tail of
    every pool write path (prefill chunks, decode appends in both engine
    attention modes, the speculative commit).

    The write is a read-modify-write of WHOLE pages: gather the pages the
    W rows can straddle, put the new rows over them with a select, and
    write the pages back with an update that indexes the page axis only.
    That axis lies outside the chip's (8, 128) tile of a page, so the
    pool keeps its layout and the donated update happens in place. (A
    scatter of single ROWS indexes `page_size`, the tile's sublane axis:
    the TPU compiler then re-lays the whole pool half out, scatters, and
    copies it back — two whole-pool copies for K and two for V in every
    decode and prefill call; PERF.md, PR 25.) Rows
    of a page that are not written keep their bytes bit for bit: they are
    selected, never re-encoded. A written row is at or past the lane's
    length, hence in a PRIVATE page with this one writer; page-table
    entries past the view and the padding of a table are the trash page,
    which takes every duplicate write."""
    ps = cache.page_size
    win_k, win_side = _split_side(cache, win_k)
    N, W = win_k.shape[1], win_k.shape[2]
    n_pages = (W + ps - 2) // ps + 1    # most that W consecutive rows touch
    lane_page = (start // ps)[:, None] + jnp.arange(n_pages, dtype=jnp.int32)
    if cache.ring:
        # n_pages consecutive entries of a ring are distinct pages: a
        # ring has a page more than a window's and a chunk's rows take
        pages = jnp.take_along_axis(
            table, lane_page % table.shape[1], axis=1).reshape(N * n_pages)
    else:
        # a last page past the table's end holds no written row: the
        # trash page
        pages = jnp.take_along_axis(
            table, lane_page, axis=1, mode="fill",
            fill_value=cache.trash_page).reshape(N * n_pages)
    # the window row that belongs at every row of those pages
    src = (lane_page[:, :, None] * ps + jnp.arange(ps, dtype=jnp.int32)
           - start[:, None, None])                         # [N, n_pages, ps]
    write = ((src >= 0) & (src < count[:, None, None])).reshape(
        N * n_pages, 1, ps)
    src = jnp.clip(src, 0, W - 1).reshape(N, n_pages * ps)

    def put(pool, win):
        # win [L, N, W, H, *tail] -> page-major [L, N * n_pages, H, ps, *tail]
        tail = win.ndim - 4
        new = jnp.take_along_axis(
            win, src.reshape((1, N, n_pages * ps, 1) + (1,) * tail), axis=2)
        new = jnp.swapaxes(
            new.reshape((win.shape[0], N * n_pages, ps) + win.shape[3:]),
            2, 3).astype(pool.dtype)
        mask = write.reshape(write.shape + (1,) * tail)
        # a side pool's page is stored as whole 128-lane rows: the select
        # runs on the page as [1, ps, w], what goes back is as stored
        old = pool[:, pages]
        return pool.at[:, pages].set(jnp.where(
            mask, new, old.reshape(new.shape)).reshape(old.shape))

    if not cache.quantized:
        k, v = _both(lambda pw: put(*pw), (cache.k, win_k),
                     None if cache.latent else (cache.v, win_v))
        side = None if win_side is None else put(cache.side, win_side)
        return dataclasses.replace(cache, k=k, v=v, side=side,
                                   lengths=new_lengths)
    from ..ops.quant import kv_quantize_rows

    ck, sk = kv_quantize_rows(win_k)
    cv, sv = kv_quantize_rows(win_v)
    return dataclasses.replace(
        cache,
        k=put(cache.k, ck), v=put(cache.v, cv),
        k_scale=put(cache.k_scale, sk), v_scale=put(cache.v_scale, sv),
        lengths=new_lengths,
    )


@part("cache.view")
def paged_batch_view(cache: PagedKVCache, table: jax.Array):
    """All slots' pages gathered into the dense decode layout:
    (k [L, S, R, H, D], v [L, S, R, H, D]), dequantized to
    `compute_dtype` on an int8 pool. `table` is the full
    [S, pages_per_slot] int32 page table (traced); one a group, and one
    view a group, for a grouped cache."""
    if isinstance(cache, GroupedPagedCache):
        views = [paged_batch_view(g, t) for g, t in zip(cache.groups, table)]
        return tuple(v[0] for v in views), tuple(v[1] for v in views)
    L, _, H, ps, D = cache.k.shape
    S = cache.num_slots
    P = cache.pages_per_slot
    ks, vs = _both(
        lambda kv: _dense_pages(*kv, table, cache.compute_dtype).reshape(
            L, S, P * ps, H, D),
        (cache.k, cache.k_scale), None if cache.latent
        else (cache.v, cache.v_scale))
    if cache.side is not None:
        ks = WithSide(ks, _side_view(cache, table, S))
    return ks, vs


@part("cache.write")
def paged_append_rows(cache: PagedKVCache, table: jax.Array,
                      row_k: jax.Array, row_v: jax.Array,
                      live: jax.Array) -> PagedKVCache:
    """Write each slot's SINGLE new row ([L, S, H, D] — the K/V of the
    token decode just produced, at view row `length`) to its page and
    advance live lanes' lengths by one. One page per slot is rewritten
    (`_scatter_rows`), so per-token write traffic is O(slots), not
    O(pool). A live slot's current-length row always lies in a PRIVATE
    page (allocator invariant), so no two live lanes collide; retired
    lanes' tables are all-trash (the engine resets them at release), so
    their dead writes land in the trash page — never in a page that may
    have been reallocated. This is the write half of BOTH decode
    attention modes: the dense gather path extracts the row from the
    returned views (`paged_append_batch`), the Pallas kernel path hands
    the rows over directly."""
    if isinstance(cache, GroupedPagedCache):
        return cache.map_groups(
            lambda g, t, rk, rv: paged_append_rows(g, t, rk, rv, live),
            table, row_k, row_v)
    # (a `WithSide` in K's place is mapped leaf by leaf)
    return _scatter_rows(cache, table, cache.lengths,
                         jnp.ones_like(cache.lengths),
                         *jax.tree.map(lambda row: row[:, :, None],
                                       (row_k, row_v)),
                         cache.lengths + live.astype(jnp.int32))


@part("cache.write")
def paged_append_batch(cache: PagedKVCache, table: jax.Array,
                       new_k: jax.Array, new_v: jax.Array,
                       live: jax.Array) -> PagedKVCache:
    """`paged_append_rows` for the dense-gather decode path, where the
    family forward returns whole updated [L, S, R, H, D] views: extract
    the one changed row per slot (view row `length`), then scatter."""
    if isinstance(cache, GroupedPagedCache):
        return cache.map_groups(
            lambda g, t, nk, nv: paged_append_batch(g, t, nk, nv, live),
            table, new_k, new_v)
    row = cache.lengths % cache.rows if cache.ring else cache.lengths
    idx = row[None, :, None, None, None]
    row_k, row_v = jax.tree.map(                               # [L, S, H, D]
        lambda new: jnp.take_along_axis(new, idx, axis=2)[:, :, 0],
        (new_k, new_v))
    return paged_append_rows(cache, table, row_k, row_v, live)


@part("cache.write")
def paged_append_window(cache: PagedKVCache, table: jax.Array,
                        win_k: jax.Array, win_v: jax.Array,
                        counts: jax.Array, live: jax.Array) -> PagedKVCache:
    """Write each slot's next `counts[s]` rows from a fixed-width window
    ([L, S, W, H, D] — view rows [length, length + W)) and advance live
    lanes' lengths by their count. The speculative-decoding commit: the
    verify program produces W candidate rows per slot but only the
    accepted prefix is real, so rows at or past a slot's count (and every
    row of a dead lane) are not written: the pages the window straddles
    keep their own bytes there — the update stays fixed-shape
    whatever the per-slot accept counts. Every written row is at or past
    `length`, hence in a PRIVATE page (allocator invariant), so shared
    copy-on-write pages are untouched — the same write-safety argument
    as `paged_append_rows`, W rows at a time."""
    counts = jnp.where(live, counts, 0)
    return _scatter_rows(cache, table, cache.lengths, counts, win_k, win_v,
                         cache.lengths + counts)


def paged_admit_slot(cache: PagedKVCache, slot: jax.Array,
                     reused_len: jax.Array) -> PagedKVCache:
    """Admit a request into `slot`: length starts at the reused prefix
    length (0 on a cold miss). Nothing is wiped — reused pages carry the
    prefix K/V, rows past `length` are masked until overwritten."""
    if isinstance(cache, GroupedPagedCache):
        return dataclasses.replace(cache, groups=tuple(
            paged_admit_slot(g, slot, reused_len) for g in cache.groups))
    return dataclasses.replace(
        cache, lengths=cache.lengths.at[slot].set(reused_len))


@part("cache.write")
def state_admit_slot(cache, slot: jax.Array, entry: jax.Array):
    """Admit a request into `slot` of a state pool: length zero, and the
    state at `entry` ZEROED in every layer, both blocks (a state is read
    whole from the first token on; nothing masks what the entry's last
    tenant left). Of a grouped cache: its state group's entry (the page
    groups' lengths are `paged_admit_slot`'s)."""
    if isinstance(cache, GroupedPagedCache):
        return dataclasses.replace(
            cache, state=state_admit_slot(cache.state, slot, entry))

    def zero(pool, axis=1):
        if pool is None:
            return None
        blank = jnp.zeros(pool.shape[:axis] + (1,) + pool.shape[axis + 1:],
                          pool.dtype)
        return jax.lax.dynamic_update_slice_in_dim(pool, blank, entry,
                                                   axis=axis)

    return dataclasses.replace(
        cache, s=zero(cache.s), z=zero(cache.z, cache.z_entry_axis),
        lengths=cache.lengths.at[slot].set(0))


def _flatten_paged(cache: PagedKVCache):
    return (cache.k, cache.v, cache.lengths, cache.k_scale, cache.v_scale,
            cache.stats, cache.side), (
        cache.page_size, cache.pages_per_slot, cache.max_len,
        cache.pad_slack, cache.compute_dtype, cache.window)


def _unflatten_paged(aux, children):
    k, v, lengths, k_scale, v_scale, stats, side = children
    (page_size, pages_per_slot, max_len, pad_slack, compute_dtype,
     window) = aux
    return PagedKVCache(k=k, v=v, lengths=lengths, page_size=page_size,
                        pages_per_slot=pages_per_slot, max_len=max_len,
                        pad_slack=pad_slack, k_scale=k_scale,
                        v_scale=v_scale, compute_dtype=compute_dtype,
                        stats=stats, window=window, side=side)


jax.tree_util.register_pytree_node(PagedKVCache, _flatten_paged,
                                   _unflatten_paged)


@dataclasses.dataclass(frozen=True)
class StateCache:
    """The pool of a family that keeps a STATE a sequence and no rows
    (`CacheSpec.kind == "state"`).

    s: [num_layers, entries + 1, heads, state_rows, width], z:
    [num_layers, entries + 1, heads, aux_rows, width] (None where the spec
    has no second block; [num_layers, heads * aux_rows, entries + 1, width]
    where it asks for `aux_entry_minor`: `z_entry_axis` says which axis
    counts entries), both in the spec's `state_dtype`; lengths:
    [num_slots] int32. What the rows MEAN is the family's (`CacheSpec`,
    `kind="state"`): this class zeroes, hands over and takes back two
    blocks of rows an entry and layer. ONE entry is one
    sequence's whole state in every layer. A slot is given its entry at
    admission, where it is zeroed (`state_admit_slot`: unlike K/V rows,
    which a position mask hides, a state left by the last tenant would be
    read), and gives it back at release. The last entry is the SPARE, the
    trash page's like: it is never allocated, an idle lane's table row
    names it, and the decode step sends there the write of every lane that
    is not live, so a live sequence's state is never touched by another
    lane.

    To the host's books an entry IS a page: `num_pages` entries, a table
    row of `pages_per_slot` = 1 page whose `page_size` is every position a
    slot may hold, `page_nbytes` an entry's bytes. The allocator, the
    scheduler, the sanitizer and the page gauges therefore work unchanged
    and say true things (a request needs one page; admission is bounded by
    free entries). What a state cannot do follows from the same fact: no
    part of an entry is a prefix of another sequence, so prefix reuse,
    forks, the host tier and page shipments need a SNAPSHOT of a state,
    which is not implemented (`serving/engine.py` `_UNPORTED["state"]`).

    The programs never gather a view of it: a family forward is handed the
    whole pool (`pool()`, a `models.contract.StatePool`) and hands it
    back updated, each layer's op writing the entries it read, in place
    under donation (`commit`).

    As the state group of a `GroupedPagedCache` (`.state`) it has one entry
    a slot, slot i's is entry i, and the host keeps no books of it: the
    pages beside it are what a request allocates."""

    s: jax.Array
    z: jax.Array
    lengths: jax.Array
    max_len: int
    pad_slack: int
    compute_dtype: Any = jnp.bfloat16
    stats: Any = None
    z_entry_axis: int = 1

    # what the engine asks of any pool and this one has none of
    quantized = latent = ring = False
    side = k_scale = v_scale = window = None
    side_width = side_page_nbytes = 0
    pages_per_slot = 1

    @classmethod
    def create(cls, spec: CacheSpec, num_slots: int, max_len: int,
               dtype: Any = jnp.bfloat16, pad_slack: int = 0,
               num_entries: int | None = None,
               stats: Any = None) -> "StateCache":
        if spec.state_rows < 1 or spec.aux_rows < 0 or spec.heads < 1:
            raise ValueError(
                "a state is `heads` blocks of `state_rows` x `width` and of "
                f"`aux_rows` x `width`; got heads {spec.heads}, state_rows "
                f"{spec.state_rows}, aux_rows {spec.aux_rows}")
        entries = num_slots if num_entries is None else num_entries
        if entries < 1:
            raise ValueError(f"a state pool of {entries} entries")
        lead = (spec.num_layers, entries + 1, spec.heads)
        # (no axis of one heads: the TPU's default layout of an array with
        # an axis of 1 before its tile is not the plain one, and a program
        # would re-lay the pool out on the way in and on the way out)
        z_shape = ((spec.num_layers, spec.heads * spec.aux_rows, entries + 1,
                    spec.width) if spec.aux_entry_minor
                   else lead + (spec.aux_rows, spec.width))
        return cls(
            s=jnp.zeros(lead + (spec.state_rows, spec.width),
                        spec.state_dtype),
            z=jnp.zeros(z_shape, spec.state_dtype) if spec.aux_rows else None,
            lengths=jnp.zeros((num_slots,), jnp.int32),
            max_len=max_len, pad_slack=pad_slack, compute_dtype=dtype,
            stats=stats, z_entry_axis=2 if spec.aux_entry_minor else 1)

    def with_stats(self, stats) -> "StateCache":
        return dataclasses.replace(self, stats=stats)

    def pool(self, kernel: bool = False):
        """The pool as a family forward takes it."""
        return StatePool(self.s, self.z, kernel)

    def commit(self, pool, lengths: jax.Array) -> "StateCache":
        """The cache after a program: the pool a forward handed back, and
        the slots' new lengths."""
        return dataclasses.replace(self, s=pool.s, z=pool.z, lengths=lengths)

    @property
    def num_layers(self) -> int:
        return self.s.shape[0]

    @property
    def num_pages(self) -> int:
        """Entries a sequence can be given (the spare is excluded)."""
        return self.s.shape[1] - 1

    @property
    def trash_page(self) -> int:
        """The spare entry."""
        return self.s.shape[1] - 1

    @property
    def num_slots(self) -> int:
        return self.lengths.shape[0]

    @property
    def page_size(self) -> int:
        """Positions an entry stands for: all a slot may hold."""
        return self.max_len + self.pad_slack

    rows = page_size

    @property
    def page_nbytes(self) -> int:
        """HBM bytes of one entry: a sequence's state in every layer."""
        return self.nbytes() // self.s.shape[1]

    def nbytes(self) -> int:
        return (self.s.size + (0 if self.z is None else self.z.size)
                ) * self.s.dtype.itemsize


jax.tree_util.register_pytree_node(
    StateCache,
    lambda c: ((c.s, c.z, c.lengths, c.stats),
               (c.max_len, c.pad_slack, c.compute_dtype, c.z_entry_axis)),
    lambda aux, ch: StateCache(s=ch[0], z=ch[1], lengths=ch[2], stats=ch[3],
                               max_len=aux[0], pad_slack=aux[1],
                               compute_dtype=aux[2], z_entry_axis=aux[3]))


@dataclasses.dataclass(frozen=True)
class GroupedPagedCache:
    """The cache of a family whose layers differ in kind: one
    `PagedKVCache` a GROUP (`CacheSpec`), each with its own stacked pool
    pair (or its one latent pool: the page groups are all K/V or all
    latent, each of its own row width), its own page shape and its
    retention rule. `groups[0]` keeps every position (`window` None) and
    may carry a side row; a further group keeps a window, as a ring of
    pages a slot. `layers[g]` are the model's layers group g
    holds, in order. Every group carries the slots' lengths (they advance
    together through the one `_scatter_rows`); the family's counters ride
    the first. What reads ONE pool's books (`num_pages`, `page_nbytes`,
    `pages_per_slot`, ...) reads the first group's: the one that grows
    with context.

    A group of ENTRIES beside the pages (`state`, `state_layers`; a spec of
    `kind="state"`, the last of the tuple): the layers that keep one state
    a sequence and no rows. It is a `StateCache` of one entry a slot, slot
    i's entry is entry i and the spare is the last, as a ring group's rings
    follow from the slots: a request's ONE allocation is its pages, and the
    host keeps no second free list. The functions that map the page groups
    (`map_groups`, views, writes) do not see it; the engine's programs hand
    its pool to the family beside the page groups' operands and take it
    back (`with_state`), and `state_admit_slot` zeroes a slot's entry."""

    groups: tuple
    layers: tuple
    state: Any = None
    state_layers: tuple = ()

    @classmethod
    def create(cls, specs, num_slots: int, max_len: int, dtype=jnp.bfloat16,
               page_size: int = 16, pad_slack: int = 0,
               num_pages: int | None = None,
               stats: Any = None) -> "GroupedPagedCache":
        """`num_pages` sizes the first group's pool; a ring group's pool
        and a state group's follow from the slots (`num_slots` rings,
        `num_slots` entries and the spare)."""
        states = [s for s in specs if s.kind == "state"]
        if states and specs[-1].kind != "state":
            raise ValueError(
                "the first group of a grouped cache keeps every position "
                "(its pages are what a request allocates): a group of state "
                "entries comes LAST")
        specs = [s for s in specs if s.kind != "state"]
        if states and (len(states) > 1 or len(specs) != 1
                       or specs[0].kind != "kv" or specs[0].side_width
                       or states[0].layers is None):
            raise ValueError(
                "a group of state entries stands beside ONE group of K/V "
                "pages that keeps every position, and names its layers: a "
                "state beside latent rows, a side row, a ring or a second "
                "state group is not implemented; got kinds "
                f"{[s.kind for s in specs] + ['state'] * len(states)}")
        if not specs or specs[0].window is not None or any(
                s.window is None for s in specs[1:]):
            raise ValueError(
                "the first group of a grouped cache keeps every position "
                "and every further one a window; got windows "
                f"{[s.window for s in specs]}")
        kinds = {s.kind for s in specs}
        if (len(kinds) != 1 or not kinds <= {"kv", "latent"}
                or any(s.layers is None for s in specs)):
            raise ValueError(
                "the page groups of a grouped cache are of ONE kind, K/V "
                "rows (kind='kv') or latent rows (kind='latent'; each group "
                "its own width), and name their layers; got kinds "
                f"{sorted(kinds)}")
        return cls(
            groups=tuple(PagedKVCache.create(
                s.num_layers, num_slots, max_len, s.heads, s.width,
                dtype=dtype, page_size=page_size, pad_slack=pad_slack,
                num_pages=num_pages if g == 0 else None,
                stats=stats if g == 0 else None, window=s.window,
                latent=s.kind == "latent", side_width=s.side_width)
                for g, s in enumerate(specs)),
            layers=tuple(tuple(s.layers) for s in specs),
            state=StateCache.create(
                states[0], num_slots, max_len, dtype=dtype,
                pad_slack=pad_slack) if states else None,
            state_layers=tuple(states[0].layers) if states else ())

    def map_groups(self, f, *per_group) -> "GroupedPagedCache":
        """`f(group, *the g-th of every argument)` in every PAGE group's
        place."""
        return dataclasses.replace(self, groups=tuple(
            f(g, *args) for g, *args in zip(self.groups, *per_group)))

    def with_stats(self, stats) -> "GroupedPagedCache":
        first, *rest = self.groups
        return dataclasses.replace(
            self, groups=(first.with_stats(stats), *rest))

    def with_state(self, pool) -> "GroupedPagedCache":
        """The cache with the state group's pool as a forward handed it
        back. (The state group's own lengths stay zero: the slots' lengths
        are the page groups'.)"""
        return dataclasses.replace(
            self, state=self.state.commit(pool, self.state.lengths))

    def nbytes(self) -> int:
        return sum(g.nbytes() for g in self.groups) + (
            0 if self.state is None else self.state.nbytes())

    # one pool's books, the lengths and the counters: the first group's
    _OF_THE_FIRST_GROUP = (
        "lengths", "stats", "num_pages", "trash_page", "num_slots",
        "page_size", "pages_per_slot", "max_len", "pad_slack", "rows",
        "page_nbytes", "compute_dtype", "quantized", "latent", "side",
        "side_width", "side_page_nbytes")

    def __getattr__(self, name):
        if name in self._OF_THE_FIRST_GROUP and "groups" in self.__dict__:
            return getattr(self.groups[0], name)
        raise AttributeError(name)


# (the page groups are the children, as they were before a state group
# could stand beside them: a cache without one flattens to the same paths,
# so its programs' text is the same; a state group is one child more)
jax.tree_util.register_pytree_node(
    GroupedPagedCache,
    lambda c: ((c.groups, c.layers) if c.state is None else (
        (*c.groups, c.state), ("state", c.layers, c.state_layers))),
    lambda aux, ch: (
        GroupedPagedCache(tuple(ch[:-1]), aux[1], ch[-1], aux[2])
        if aux[0] == "state" else GroupedPagedCache(tuple(ch), aux)))


def create_cache(spec, engine_config, pad_slack: int, stats: Any = None):
    """The cache a family's declared `spec` gets, at an `EngineConfig`'s
    sizes: a tuple of specs a `GroupedPagedCache`, `kind="state"` a
    `StateCache`, rows of either other kind a `PagedKVCache`."""
    ec = engine_config
    if isinstance(spec, tuple):
        return GroupedPagedCache.create(
            spec, ec.num_slots, ec.max_len, dtype=ec.cache_dtype,
            page_size=ec.page_size, pad_slack=pad_slack,
            num_pages=ec.num_pages, stats=stats)
    if spec.kind == "state":
        # `num_pages` counts ENTRIES (one a sequence, a spare besides)
        return StateCache.create(
            spec, ec.num_slots, ec.max_len, dtype=ec.cache_dtype,
            pad_slack=pad_slack, num_entries=ec.num_pages, stats=stats)
    return PagedKVCache.create(
        spec.num_layers, ec.num_slots, ec.max_len, spec.heads, spec.width,
        dtype=ec.cache_dtype, page_size=ec.page_size, pad_slack=pad_slack,
        num_pages=ec.num_pages, kv_dtype=ec.kv_dtype,
        latent=spec.kind == "latent", stats=stats,
        side_width=spec.side_width)


# ---------------------------------------------------------------------------
# host-side page accounting: free list + prefix radix tree + allocator
# ---------------------------------------------------------------------------


# The aligned sub-group of table entries that the K/V paged decode kernel
# copies with ONE descriptor a pool when their page ids are consecutive:
# `ops/paged_attention.py` `PAGES_PER_RUN`, which imports Pallas and so is
# not imported here (tests/test_paged_cache.py holds the two equal).
TABLE_RUN_PAGES = 8


def table_run_pages(pages, run: int = TABLE_RUN_PAGES) -> int:
    """How many of a table row's `pages` lie in a sub-group of `run`
    entries, aligned in TABLE INDEX, that holds consecutive page ids:
    what a paged decode kernel copies `run` pages at a time. The entries
    past the last whole sub-group are followed by the trash page and
    count as none."""
    whole = len(pages) // run * run
    groups = np.asarray(pages[:whole], np.int64).reshape(-1, run)
    return int((np.diff(groups, axis=1) == 1).all(axis=1).sum()) * run


class PagePool:
    """Free list over the allocatable pages (the trash page never enters).

    Pure host bookkeeping — which physical page holds which bytes is
    entirely decided here and in `PrefixIndex`; the device only ever sees
    page indices as traced data."""

    def __init__(self, num_pages: int):
        self.num_pages = num_pages
        self._free: list[int] = list(range(num_pages - 1, -1, -1))

    @property
    def free_count(self) -> int:
        return len(self._free)

    @property
    def used_count(self) -> int:
        return self.num_pages - len(self._free)

    def alloc(self, n: int) -> list[int] | None:
        """Pop `n` free pages, or None (and no change) if short. They
        come ASCENDING, whatever order they were freed in: pages a
        retired request gave back together are neighbours in the pool,
        and only an ascending table row shows that as the runs of
        consecutive ids a paged decode kernel copies with one descriptor
        (`table_run_pages`). A page is location-free, so nothing else
        reads the order."""
        if n > len(self._free):
            return None
        taken = self._free[len(self._free) - n:]
        del self._free[len(self._free) - n:]
        taken.sort()
        return taken

    def release(self, pages) -> None:
        self._free.extend(pages)


class _RadixNode:
    """One cached page: `key` is the page's token chunk (bytes of
    page_size int32 tokens), `page` its physical index. `refcount` counts
    live slots currently mapping the page; 0 means cached-but-unmapped
    (evictable once it is a leaf).

    `residency` is the hierarchical-KV state: "hbm" means `page` is a
    live pool page holding the chunk's K/V; "host" means the chunk's
    bytes were swapped out to the host tier (serving/host_tier.py) —
    `page` is -1, the node stays in the tree so the prefix still
    matches, and a later admission swaps the bytes back into a freshly
    reserved pool page. A host-resident node is always refcount-0 (a
    mapped node's page is pinned in HBM) and all of its children are
    host-resident too: eviction drains leaf-first, so residency along
    any root path is an HBM prefix followed by a host suffix."""

    __slots__ = ("key", "page", "children", "refcount", "last_used",
                 "parent", "residency")

    def __init__(self, key: bytes, page: int, parent: "_RadixNode | None"):
        self.key = key
        self.page = page
        self.children: dict[bytes, _RadixNode] = {}
        self.refcount = 0
        self.last_used = 0
        self.parent = parent
        self.residency = "hbm"


class PrefixIndex:
    """Radix tree over page-sized token chunks -> cached KV pages.

    Each edge consumes exactly `page_size` token IDs (reuse is
    page-granular: a prefix is reusable only in whole pages, which is
    also what makes the cached pages immutable — see the module
    docstring), so the tree IS the map from prompt prefixes to page
    lists. Nodes are LRU-stamped on every match/insert; eviction frees
    refcount-0 LEAVES oldest-first, which keeps every cached path
    contiguous from the root (an interior node is unevictable while any
    descendant survives, and a mapped page — refcount > 0 — is never
    evicted).

    The eviction candidates STAND between evictions: `_lru` is a
    min-heap of `(last_used, page, serial, node)` that holds, for every
    node `_evictable` says yes to, at least one entry under the node's
    current stamp and page. Nothing is removed in place. Whatever makes
    a node a candidate, or re-stamps one, pushes an entry (`_offer`);
    whatever unmakes one (an `acquire`, a child cached below it, its
    eviction) leaves its entries behind, and `evict_lru` discards an
    entry that no longer describes its node when it surfaces. So an
    eviction costs the pages it frees and the stale entries above them,
    never a walk of the tree."""

    # the heap may hold this many entries a cached page (plus a floor
    # for a small tree) before `_offer` rebuilds it from its live ones
    LRU_SLACK = 2
    LRU_FLOOR = 64

    def __init__(self, page_size: int):
        self.page_size = page_size
        self.root = _RadixNode(b"", -1, None)
        self._tick = 0
        self.cached_pages = 0   # HBM-resident nodes (pool pages in the tree)
        self.mapped_pages = 0   # nodes with refcount > 0 (always HBM)
        self.host_pages = 0     # host-resident nodes (bytes in the host tier)
        # drop_host(node): the host tier forgets `node`'s swapped-out
        # bytes. Fired when a host-resident chunk is re-homed in HBM by a
        # fresh insert (adoption) or its naming path is destructively
        # evicted. None when no host tier is attached.
        self.drop_host: Callable[[Any], None] | None = None
        self._lru: list[tuple] = []
        # entries ever pushed: the third field of an entry, so that two
        # entries of one node under one stamp never compare the nodes
        self._lru_serial = 0
        self.lru_stale = 0      # entries discarded so far (running total)

    def _touch(self, node: _RadixNode) -> None:
        self._tick += 1
        node.last_used = self._tick

    def _evictable(self, node: _RadixNode) -> bool:
        """Would an eviction take `node` next, were it the oldest: an
        attached, unmapped HBM node with no HBM child (host-resident
        children don't pin their parent). Never the root."""
        if node.refcount or node.residency != "hbm" or node.parent is None:
            return False
        for child in node.children.values():
            if child.residency == "hbm":
                return False
        return True

    def _offer(self, node: _RadixNode) -> None:
        """Enter `node` among the candidates if it is one now. Called
        wherever a node may have BECOME one or a candidate's stamp or
        page changed; an entry it makes stale is not looked for."""
        if not self._evictable(node):
            return
        self._lru_serial += 1
        heapq.heappush(self._lru, (node.last_used, node.page,
                                   self._lru_serial, node))
        self._bound_lru()

    def _live(self, entry: tuple) -> bool:
        stamp, page, _, node = entry
        return (node.last_used == stamp and node.page == page
                and self._evictable(node))

    def lru_bound(self) -> int:
        """The most entries the heap holds once a call has returned."""
        return self.LRU_SLACK * self.cached_pages + self.LRU_FLOOR

    def _bound_lru(self) -> None:
        """Keep the heap within `lru_bound()`: a history that never
        evicts (a pool that never fills) pushes an entry a touch and
        pops none. The rebuild walks the HEAP, keeps one live entry a
        node, and leaves at most `cached_pages` of them, so it is paid
        once in `cached_pages` pushes."""
        if len(self._lru) <= self.lru_bound():
            return
        kept: dict[int, tuple] = {}
        for entry in self._lru:
            if self._live(entry):
                kept.setdefault(id(entry[3]), entry)
        self.lru_stale += len(self._lru) - len(kept)
        self._lru[:] = kept.values()
        heapq.heapify(self._lru)

    def _chunk(self, prompt: np.ndarray, i: int) -> bytes:
        ps = self.page_size
        return np.ascontiguousarray(
            prompt[i * ps:(i + 1) * ps], dtype=np.int32).tobytes()

    def match(self, prompt: np.ndarray) -> list[_RadixNode]:
        """Longest cached prefix of `prompt`, as the node path from the
        root, capped at (prompt_len - 1) // page_size pages so at least
        one prompt token always prefills (the first output token's
        logits have to come from somewhere)."""
        limit = (int(prompt.shape[0]) - 1) // self.page_size
        node, path = self.root, []
        for i in range(limit):
            child = node.children.get(self._chunk(prompt, i))
            if child is None:
                break
            path.append(child)
            node = child
        for n in path:
            self._touch(n)
        self._offer_path_end(path)
        return path

    def _offer_path_end(self, path: list[_RadixNode]) -> None:
        """Offer the one node of a root path that can be a candidate:
        every node above the path's last HBM node has an HBM child, the
        next one (residency along a path is an HBM prefix, then a host
        suffix)."""
        for node in reversed(path):
            if node.residency == "hbm":
                self._offer(node)
                return

    def acquire(self, nodes: list[_RadixNode]) -> None:
        for n in nodes:
            n.refcount += 1
            if n.refcount == 1:
                self.mapped_pages += 1

    def release(self, nodes: list[_RadixNode]) -> None:
        """Unmap `nodes`, a path in root-to-leaf order (what `match`
        and `extend_path` return, which is all that is ever acquired:
        refcounts are downward-closed). Only its end can have become an
        eviction candidate."""
        for n in nodes:
            n.refcount -= 1
            if n.refcount == 0:
                self.mapped_pages -= 1
        self._offer_path_end(nodes)

    def insert(self, prompt: np.ndarray, pages: list[int],
               upto_pages: int) -> list[int]:
        """Cache prompt pages [0, upto_pages): walk/create the node path,
        adopting `pages[i]` for chunks not yet cached. Returns the pages
        NOT adopted (an equal chunk was cached concurrently by another
        request — the caller frees the duplicates)."""
        node, spare = self.root, []
        for i in range(upto_pages):
            key = self._chunk(prompt, i)
            child = node.children.get(key)
            if child is None:
                child = _RadixNode(key, pages[i], node)
                node.children[key] = child
                self.cached_pages += 1
            elif child.residency == "host":
                # the chunk was swapped out while this request prefilled
                # its own copy — adopt the fresh HBM page (value-identical
                # bytes) and let the host tier drop the stale mirror
                self._adopt_host(child, pages[i])
            elif child.page != pages[i]:
                spare.append(pages[i])
            self._touch(child)
            node = child
        # every node walked is HBM now and all but the last has the next
        # one below it: the walk's end is its one possible candidate
        self._offer(node)
        return spare

    def _adopt_host(self, node: _RadixNode, page: int) -> None:
        """Re-home a host-resident node in HBM at `page` (whose bytes
        must already hold the chunk's K/V) and drop the host mirror.
        The caller stamps the node and offers its walk's end."""
        node.page = page
        node.residency = "hbm"
        self.host_pages -= 1
        self.cached_pages += 1
        if self.drop_host is not None:
            self.drop_host(node)

    def extend_path(self, prompt: np.ndarray, pages: list[int],
                    start: int, upto: int) -> list[_RadixNode]:
        """Walk/create nodes for chunks [start, upto) of `prompt`,
        adopting `pages[i]` for chunks not yet cached — the mid-flight
        half of `insert`, used by `PagedAllocator.publish_prompt` to
        share a RUNNING request's already-prefilled prompt pages (COW
        request forking). Stops at the first chunk already cached under
        a DIFFERENT page: past that point the caller's pages can't back
        the tree path, and the pages[:len(nodes)]-are-node-pages
        invariant of `PageAllocation` must hold for the extended node
        list. The first `start` chunks must already be the caller's
        mapped (refcount > 0, hence unevictable) path. Returned nodes
        are refcount-0 until the caller acquires them."""
        node = self.root
        for i in range(start):
            node = node.children[self._chunk(prompt, i)]
        out: list[_RadixNode] = []
        for i in range(start, upto):
            key = self._chunk(prompt, i)
            child = node.children.get(key)
            if child is None:
                child = _RadixNode(key, pages[i], node)
                node.children[key] = child
                self.cached_pages += 1
            elif child.residency == "host":
                # same adoption as `insert`: the publisher's freshly
                # prefilled page re-homes the swapped-out chunk in HBM
                self._adopt_host(child, pages[i])
            elif child.page != pages[i]:
                break
            self._touch(child)
            out.append(child)
            node = child
        self._offer_path_end(out)
        return out

    def swap_in(self, nodes: list[_RadixNode], pages: list[int]) -> list:
        """Re-home the host-resident end of a matched path in HBM, one
        reserved page a node, and map it: bookkeeping only, the caller
        installs the bytes. Returns the (node, page) pairs. The nodes go
        from host-resident to mapped, so none is ever a candidate."""
        swap_ins = list(zip(nodes, pages))
        for node, page in swap_ins:
            node.page = page
            node.residency = "hbm"
            self.host_pages -= 1
            self.cached_pages += 1
        self.acquire(nodes)
        return swap_ins

    def undo_swap_in(self, swap_ins: list) -> None:
        """Turn released `swap_in` pairs back to host residency (their
        bytes never left the host tier). The node ABOVE them is the end
        of the path's HBM part again, and no `release` saw it so: it had
        an HBM child then."""
        for node, _ in swap_ins:
            node.page = -1
            node.residency = "host"
            self.host_pages += 1
            self.cached_pages -= 1
        if swap_ins:
            self._offer(swap_ins[0][0].parent)
            self._bound_lru()

    def evict_lru(self, n: int,
                  swap_out: "Callable[[Any], bool] | None" = None
                  ) -> list[int]:
        """Free exactly `n` pages, draining least-recently-used
        refcount-0 effective leaves (an effective leaf is an HBM node
        with no HBM descendant — host-resident children don't pin their
        parent, or a host tier would freeze eviction; draining one can
        turn its parent into the next candidate). Mapped pages
        (refcount > 0) are never touched. ALL-OR-NOTHING: if fewer than
        `n` pages are evictable the tree is left intact and [] returned
        — a failed admission must not cost the cache its reusable
        prefixes, and (key for a queue head that stays blocked for many
        engine steps) that case bails in O(1).

        `swap_out(node)` (the host tier's offer, while `node.page` still
        names the bytes) decides each victim's fate: True keeps the node
        in the tree as host-resident (page freed, bytes mirrored to host
        DRAM); False/None is the classic destructive eviction — the node
        detaches, and any host-resident subtree hanging under it loses
        its naming path, so those mirrors are dropped via `drop_host`.
        Either way exactly one HBM page per victim is freed.

        Why `cached - mapped` IS the evictable total: acquire/release
        always ref whole root-paths (`match` returns contiguous paths
        from the root), so refcounts are downward-closed — a refcount-0
        node can never have a mapped descendant, and every refcount-0
        subtree drains leaf-first (host-resident nodes are refcount-0 by
        construction and hold no HBM page, so they count in neither
        term). That is also why the standing heap cannot run dry below:
        while an unmapped HBM page is left, the deepest one on its path
        is a candidate, and every candidate has a live entry.

        The victims come off the standing heap (see the class): each pop
        is either a victim or a stale entry that leaves for good, so a
        call costs O((n + stale) log heap), whatever the tree's size."""
        if n <= 0 or self.cached_pages - self.mapped_pages < n:
            return []
        freed: list[int] = []
        while len(freed) < n:
            entry = heapq.heappop(self._lru)
            if not self._live(entry):
                self.lru_stale += 1
                continue
            victim = entry[3]
            parent = victim.parent
            freed.append(victim.page)
            self.cached_pages -= 1
            if swap_out is not None and swap_out(victim):
                victim.page = -1
                victim.residency = "host"
                self.host_pages += 1
            else:
                del parent.children[victim.key]
                victim.parent = None
                # every descendant of an effective leaf is host-resident;
                # their mirrors die with the path that named them
                drop_stack = list(victim.children.values())
                while drop_stack:
                    orphan = drop_stack.pop()
                    drop_stack.extend(orphan.children.values())
                    self.host_pages -= 1
                    if self.drop_host is not None:
                        self.drop_host(orphan)
            self._offer(parent)
        self._bound_lru()
        return freed

    def residency_probe(self, prompt: np.ndarray) -> tuple[int, int]:
        """(hbm_pages, host_pages) along the longest cached prefix of
        `prompt`, WITHOUT touching LRU stamps — the pod router's
        placement probe (scoring a worker must not make its cache look
        hot)."""
        limit = (int(prompt.shape[0]) - 1) // self.page_size
        node, hbm, host = self.root, 0, 0
        for i in range(limit):
            child = node.children.get(self._chunk(prompt, i))
            if child is None:
                break
            if child.residency == "hbm":
                hbm += 1
            else:
                host += 1
            node = child
        return hbm, host


@dataclasses.dataclass
class PageAllocation:
    """One admitted request's page mapping: `pages` is the ordered table
    row prefix (cached prefix pages first, then private pages); `nodes`
    are the mapped radix nodes backing pages[:len(nodes)].

    `swap_ins` lists (node, page) pairs whose chunks matched
    host-resident: the allocator already reserved `page` and re-homed
    the node, but the BYTES are still in the host tier — the engine must
    install them (jitted PageTransport install) before the slot's admit
    program runs, or the reused prefix serves garbage.

    `rings`: under a grouped cache, the pages of the slot's ring in each
    window group (one list a group), fixed from admission to release.

    `run_pages`: how many of `pages` the K/V paged decode kernel can copy
    a run at a time (`table_run_pages`)."""

    reused_len: int
    nodes: list
    pages: list[int]
    swap_ins: list | None = None
    rings: tuple = ()
    run_pages: int = 0


class PagedAllocator:
    """Admission-time page allocation with prefix reuse.

    The scheduler calls `allocate()` before admitting a queued request
    (None = not enough pages yet, the request stays queued — transient
    pressure, relieved as running slots retire) and `release()` when a
    slot retires or is cancelled. Worst-case pages are reserved at
    admission, so a running request can never hit pool pressure
    mid-flight and never needs preemption."""

    def __init__(
        self,
        page_size: int,
        num_pages: int,
        pad_slack: int = 0,
        prefix_cache: bool = True,
        on_evict: Callable[[int], None] | None = None,
        on_unmap: Callable[[int], None] | None = None,
        rings: tuple = (),
        state_entries: bool = False,
        entries_beside: bool = False,
    ):
        # a state pool's page is an ENTRY, one sequence's whole state
        # (`StateCache`): the span says so beside the page count
        self.state_entries = state_entries
        # a group of entries BESIDE the pages (`GroupedPagedCache.state`):
        # every allocation holds its slot's one entry, which needs no books
        # of its own; the span and `allocations_live` say how many are held
        self.entries_beside = entries_beside
        self.allocations_live = 0
        self.page_size = page_size
        self.pad_slack = pad_slack
        self.prefix_cache = prefix_cache
        self.pool = PagePool(num_pages)
        # the window groups of a grouped cache, as (pages a slot's ring
        # has at most, pages in the group's pool) each: a free list a
        # group. A request takes its ring at admission and keeps exactly
        # those pages until release: no page moves while it decodes
        self.ring_pages = tuple(per_slot for per_slot, _ in rings)
        self.ring_pools = tuple(PagePool(n) for _, n in rings)
        self.index = PrefixIndex(page_size)
        self.on_evict = on_evict
        self.on_unmap = on_unmap
        # admission-hold hook: hold_admission(request) -> True keeps the
        # request queued even when pages ARE available. The engine uses
        # it for COW forks: a fork child admitted before its parent's
        # prompt pages are published would cold-prefill the very prompt
        # it was forked to share — waiting the few steps until the
        # parent's prefill publishes them is what makes an n-way fan-out
        # cost ONE prefill. Same no-skip-ahead semantics as a pages-tight
        # head: the queue waits behind it.
        self.hold_admission: Callable[[Any], bool] | None = None
        # host-tier hooks (engine-wired when EngineConfig.host_tier_bytes
        # > 0, else None and eviction stays destructive):
        #   swap_out(node) -> bool — offer an eviction victim to the host
        #     tier while node.page still names its bytes; True = accepted
        #     (node goes host-resident), False = tier full, evict
        #     destructively.
        #   swap_stall(need) -> bool — True when the tier WOULD accept
        #     victims but its bounded swap-out queue can't absorb `need`
        #     more pages right now: the admission stalls (request stays
        #     queued, decode never blocks) instead of either blocking on
        #     the queue or destroying prefixes the tier has room for.
        self.swap_out: Callable[[Any], bool] | None = None
        self.swap_stall: Callable[[int], bool] | None = None
        # running totals for host-side (model-free) observability and
        # tests. The engine's registry counters are booked separately:
        # evictions reach it through on_evict, admission outcomes through
        # Engine._run_admit reading the same PageAllocation.
        self.lookups = 0
        self.hits = 0
        self.tokens_reused = 0
        self.evictions = 0

    @property
    def pages_free(self) -> int:
        return self.pool.free_count

    @property
    def pages_in_use(self) -> int:
        """Allocated to live slots OR cached in the prefix tree."""
        return self.pool.used_count

    def pages_needed(self, prompt_len: int, max_new_tokens: int,
                     group: int = 0) -> int:
        """Worst-case pages for one request in cache group `group`: every
        prompt+generated row plus the chunk-padding spill, in whole
        pages; in a window group (`group` >= 1) at most the ring."""
        rows = prompt_len + max_new_tokens + self.pad_slack
        pages = -(-rows // self.page_size)
        return pages if group == 0 else min(pages,
                                            self.ring_pages[group - 1])

    @property
    def ring_pages_in_use(self) -> tuple:
        """Pages held by live slots' rings, one count a window group."""
        return tuple(pool.used_count for pool in self.ring_pools)

    def allocate(self, request) -> PageAllocation | None:
        """Match the longest cached prefix and reserve the remaining
        private pages, evicting LRU refcount-0 pages under pressure.
        None = insufficient pages even with eviction (keep queued) — and
        in that case NOTHING was evicted (evict_lru is all-or-nothing),
        so a too-big queue head can't strip the cache while it waits."""
        with span("serving.kv.allocate") as sp:
            evictions, stale = self.evictions, self.index.lru_stale
            alloc = self._allocate(request)
            sp.set(pages=len(alloc.pages) if alloc else 0,
                   run_pages=alloc.run_pages if alloc else 0,
                   reused_len=alloc.reused_len if alloc else 0,
                   evicted=self.evictions - evictions,
                   lru_stale=self.index.lru_stale - stale,
                   lru_heap=len(self.index._lru))
            if self.state_entries:
                sp.set(state_entries=len(alloc.pages) if alloc else 0)
            if self.ring_pools:
                sp.set(full_pages=len(alloc.pages) if alloc else 0,
                       window_pages=sum(map(len, alloc.rings)) if alloc
                       else 0)
            if self.entries_beside:
                sp.set(full_pages=len(alloc.pages) if alloc else 0,
                       state_entries=1 if alloc else 0)
            if alloc is not None:
                self.allocations_live += 1
        return alloc

    def _allocate(self, request) -> PageAllocation | None:
        if self.hold_admission is not None and self.hold_admission(request):
            return None
        ring_need = [self.pages_needed(request.prompt_len,
                                       request.max_new_tokens, g + 1)
                     for g in range(len(self.ring_pools))]
        if any(pool.free_count < n
               for pool, n in zip(self.ring_pools, ring_need)):
            return None     # before anything is matched, acquired or evicted
        path = (self.index.match(request.prompt)
                if self.prefix_cache else [])
        # residency along a matched path is an HBM prefix then a host
        # suffix (leaf-first eviction — see _RadixNode); the host suffix
        # needs fresh pool pages to swap back into, reserved here with
        # the same worst-case discipline as private pages
        n_hbm = 0
        while n_hbm < len(path) and path[n_hbm].residency == "hbm":
            n_hbm += 1
        hbm_nodes, host_nodes = path[:n_hbm], path[n_hbm:]
        n_total = self.pages_needed(request.prompt_len,
                                    request.max_new_tokens)
        n_extra = n_total - n_hbm   # swap-in pages + private pages
        # acquire BEFORE evicting: matched nodes are refcount-0 until
        # mapped, and eviction must never free a page we are about to
        # use. Host nodes can't be acquired yet (mapped_pages counts HBM
        # pages) but are eviction-proof anyway: eviction only drops a
        # host subtree under a destructively evicted HBM ancestor, and
        # every HBM ancestor of `host_nodes` is in `hbm_nodes` — pinned.
        self.index.acquire(hbm_nodes)
        try:
            extra = self.pool.alloc(n_extra)
            if extra is None:
                need = n_extra - self.pool.free_count
                if self.swap_stall is not None and self.swap_stall(need):
                    self.index.release(hbm_nodes)
                    return None
                freed = self.index.evict_lru(need, swap_out=self.swap_out)
                if freed:
                    self.evictions += len(freed)
                    self.pool.release(freed)
                    if self.on_evict is not None:
                        self.on_evict(len(freed))
                extra = self.pool.alloc(n_extra)
            if extra is None:
                self.index.release(hbm_nodes)
                return None
            # re-home the host suffix: each node takes a reserved page
            # NOW (bookkeeping only — the caller installs the bytes
            # before the slot's first device program reads them)
            swap_ins = self.index.swap_in(host_nodes, extra)
        except BaseException:
            # on_evict / swap_stall are caller-supplied callbacks: if
            # one raises mid-allocate the matched nodes' refcounts must
            # not leak (they would pin their whole root paths
            # unevictable forever — the ATP201 self-lint finding this
            # handler exists for)
            self.index.release(hbm_nodes)
            raise
        private = extra[len(host_nodes):]
        self.lookups += 1
        if path:
            self.hits += 1
            self.tokens_reused += len(path) * self.page_size
        # ownership of the acquired refcounts transfers to the returned
        # allocation here (hbm prefix + re-homed host suffix == path)
        pages = [n.page for n in hbm_nodes + host_nodes] + private
        return PageAllocation(
            reused_len=len(path) * self.page_size,
            nodes=hbm_nodes + host_nodes,
            pages=pages,
            swap_ins=swap_ins or None,
            rings=tuple(pool.alloc(n)
                        for pool, n in zip(self.ring_pools, ring_need)),
            run_pages=table_run_pages(pages),
        )

    def rollback(self, alloc: PageAllocation) -> None:
        """Undo an `allocate()` whose slot attachment never happened (the
        pod router's adopt race): shared nodes drop their refcount,
        private pages return to the free list, nothing is cached. The
        inverse of allocate lives HERE so the [node pages | private]
        layout of PageAllocation.pages stays a single-module invariant.
        Pending swap-ins revert to host residency — their bytes were
        never installed, so the reserved pages return to the pool and the
        host tier keeps the mirror."""
        self.allocations_live -= 1
        self.index.release(alloc.nodes)
        self.pool.release(alloc.pages[len(alloc.nodes):])
        for pool, ring in zip(self.ring_pools, alloc.rings):
            pool.release(ring)
        swap_ins = alloc.swap_ins or []
        self.index.undo_swap_in(swap_ins)
        self.pool.release([page for _, page in swap_ins])
        self.lookups -= 1
        if alloc.nodes:
            self.hits -= 1
            self.tokens_reused -= alloc.reused_len

    def publish_prompt(self, slot) -> int:
        """Insert a RUNNING slot's already-prefilled FULL prompt pages
        into the prefix tree NOW, instead of waiting for retirement —
        the mechanism behind engine-level COW request forking: a fork of
        this request admitted later maps these pages instead of
        re-prefilling the prompt. Only pages every row of which holds
        final real-token K/V are published (prefill writes always land
        at or past the slot's current length, so a full page below
        `prompt_done` is immutable from here on — the same invariant
        retirement-inserted pages rely on). The published nodes are
        acquired into the slot's own allocation, so they are mapped
        (unevictable) for as long as the slot runs, and `release()` later
        drops them exactly like an admission-time prefix hit. Returns
        the number of prompt pages now shared. Idempotent; no-op when
        the prefix cache is off."""
        if not self.prefix_cache:
            return 0
        alloc, req = slot.alloc, slot.request
        if alloc is None:
            return 0
        full = min(slot.prompt_done, req.prompt_len) // self.page_size
        n_cached = len(alloc.nodes)
        if full <= n_cached:
            return n_cached
        with span("serving.kv.release", pages=0, published=full - n_cached):
            new_nodes = self.index.extend_path(req.prompt, alloc.pages,
                                               n_cached, full)
            self.index.acquire(new_nodes)
            alloc.nodes.extend(new_nodes)
        return len(alloc.nodes)

    def release(self, slot, finished: bool) -> None:
        """Return a retiring slot's pages: shared nodes drop a refcount
        (other sharers keep decoding untouched); on a normal finish the
        FULL prompt pages are inserted into the tree (content intact —
        this is the 'release to the tree, not wipe' half of reuse); the
        rest — generation pages, the partial last prompt page, and pages
        whose chunks a concurrent request cached first — go back to the
        free list. `finished=False` (cancel) caches nothing: a
        mid-prefill page may hold garbage.

        The insertable range is additionally capped at the slot's
        PREFILLED prompt, not the whole prompt: `finish_early` can
        retire a slot whose prefill is still mid-flight (a server-side
        stop decision), and inserting pages past `prompt_done` would
        cache never-written garbage KV that a later prefix hit serves
        as real prompt state — silent corruption, surfaced while
        building the ATP2xx/sanitizer audit and pinned model-free in
        test_paged_cache."""
        alloc, req = slot.alloc, slot.request
        self.allocations_live -= 1
        with span("serving.kv.release") as sp:
            self.index.release(alloc.nodes)
            n_cached = len(alloc.nodes)
            full = min(req.prompt_len, slot.prompt_done) // self.page_size \
                if (finished and self.prefix_cache) else n_cached
            spare = (self.index.insert(req.prompt, alloc.pages, full)
                     if full > n_cached else [])
            freed = spare + alloc.pages[full:]
            self.pool.release(freed)
            for pool, ring in zip(self.ring_pools, alloc.rings):
                pool.release(ring)
            if self.on_unmap is not None:
                self.on_unmap(slot.index)
            sp.set(pages=len(freed),
                   published=max(full - n_cached, 0) - len(spare))
