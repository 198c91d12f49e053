"""Pallas paged-attention decode kernel: walk the page table IN the kernel.

The serving engine's decode step used to gather every slot's pages into a
dense [L, S, rows, H, D] view (`serving/cache.py paged_batch_view`)
*before* the vmapped family forward — O(pool) HBM reads per token,
rebuilt outside the attention op, growing with `pages_per_slot` however
short the live sequences are. This kernel inverts that: the pool stays
in place in HBM and the kernel's work follows the LIVE pages and nothing
else. It takes the WHOLE stacked pool `[L, pages + 1, Hkv, page_size, D]`
(`memory_space=pl.ANY`) and a layer index, so nothing slices a layer out
of the pool around its call and a decode step does not know the pool's
size. One grid step is one slot; inside it a loop with a DYNAMIC trip
count walks the slot's live pages in groups of `PAGES_PER_GROUP`, copied
(every KV head of a page together) into one of two VMEM buffers while
the other is folded into the online softmax. A lane whose length the
engine masked to 0 (retired, or mid-prefill) costs one grid step and no
copy. Pages `p ... p + R - 1` of a layer are ONE block of the pool in
HBM, as a group's pages `j ... j + R - 1` are of its buffer: the kernel
reads a group's table entries `PAGES_PER_RUN` (`R`) at a time, from an
index that is a multiple of `R`, and where they hold consecutive page
ids it copies the RUN with one descriptor a pool, otherwise each page
with its own, as it did every page before (the kernel's pace was ~20 ns
a descriptor, not the bytes: PERF.md section 6, PRs 49-50). The same bytes
land in the same rows either way, so the one wait a buffer, the fold and
every output bit are what the page-at-a-time walk gives. The allocator
hands pages out ascending (`serving/cache.py` `PagePool.alloc`), so a
slot's table is mostly runs; what it is not (a run broken or unaligned
in the table, the trash page or the table's clamped end, both repeated)
is decided by what the kernel READS, a sub-group at a time, and no
caller chooses. Over a ring (`ring=True`) the run copy is taken only
where `pages_per_slot % R == 0`, so that no aligned sub-group wraps;
any other ring keeps a copy a page (`_pages_per_run`: `R` is
`gcd(PAGES_PER_RUN, group)`, a static shape's matter). The pjit/TPUv4
rule (arxiv 2204.06514) still holds: the table, lengths and layer are
traced *data*, so one compiled program covers every page mapping, request
mix, and eviction history. The call itself is one inner `jax.jit` a
variant (`_live_pages_call`; static: window, ring, group, run): a family
that loops over its layers in Python hands every layer's call the same
shapes and its layer index as data, so its decode program traces and
lowers ONE kernel body a variant and `call`s it a layer; tracing and
lowering are paid in every process before any compile cache is asked
(PERF.md section 6, PR 50).

Layout and semantics:

- pool K/V: [L, num_pages + 1, Hkv, page_size, D], the serving pool as it
  is stored; `PagedKV.layer` says which layer this call attends (the
  family forwards scan over `arange(L)`, not over the pool:
  `models/decode.scan_decode_layers`). Heads sit OUTSIDE the page rows so
  one head's page is a whole [page_size, D] tile, and a group's pages of
  one head, [G, page_size, D], read as [G * page_size, D] rows for free.
  The last page is the reserved trash page backing padded table entries.
- page table: [slots, pages_per_slot] int32; lengths: [slots] int32.
- q: one token per slot, GQA grouped as [slots, Hkv, group, D] — the
  head-group broadcast happens in-kernel (each group of pages dots every
  kv head's whole q group, padded to 8 sublanes, against that head's
  rows), so K/V are never `repeat_kv`'d.
- the NEW token's K/V (this step's, position == length) are folded into
  the online softmax as a final single-key update instead of being
  written to the pool first: the kernel never writes, the engine
  scatters the one new row per slot afterwards (`paged_append_rows`).
- arithmetic: scores, softmax state and accumulators in float32; q and K
  meet on the MXU in the pool's dtype when q has it too (bf16 x bf16 with
  a float32 result is the exact product); the probabilities are NOT
  rounded for the PV product.
- masked rows cannot reach an output: a group is copied whole, with the
  last page's slack, pages past the length and the trash page behind
  padded table entries (where retired lanes' dead writes land), so the
  masked rows of V are zeroed before the PV product (a masked key's
  probability is an exact 0, and 0 x inf is NaN).
- int8 pools (`PagedKV.scales` set) and heads whose width is no whole
  128-lane tile keep the OLDER kernel, separately (`_page_step_kernel`;
  the two share no logic): one grid step a page of ONE layer's pool,
  staged through BlockSpec index maps, given `pool[layer]` by an explicit
  index. Both are the chip compiler's refusals (compiled for a described
  v5e, PR 27): an array whose last dimension is under 128 lies padded to
  128 lanes in HBM, and a page cannot be cut out of it for a copy. For a
  64-wide head's pool: "Slice shape along dimension 4 must be aligned to
  tiling (128), but is 64"; for a page's [Hkv, page_size] scales out of
  [L, N+1, Hkv, page_size]: "Slice shape along dimension 3 must be
  aligned to tiling (128), but is 16". (The int8 CODES' page copy
  compiles. With the scales gathered outside the kernel instead,
  `scales[layer, table]`, the kernel compiles too, but the scale arrays
  lie pages-minor on the chip and XLA re-lays both out whole for the
  gather, 59 MB each at 4,096 pages, every decode step: not taken.
  What lifts it is a scale layout a page can be copied from, PERF.md
  section 7.) No benchmark cell runs either. That kernel dequantizes an
  int8 page INSIDE the kernel — per-row-per-head scales applied to the
  scores and the probabilities, which is the same product as scaling
  the codes — so the HBM stream is the int8 bytes.

Masking matches `models/decode.cached_attention_mask` exactly: a slot's
query (position == length) attends pool rows < length plus its own new
K/V; `window` applies the HF sliding-window band (key visible iff
q - key < window), and the walk starts at the first group the band
reaches. Lanes that are not live compute garbage that the engine
discards via its `live` lane mask — same contract as the dense gather
path.

On non-TPU backends the kernel runs in pallas interpret mode (slow, for
tests; decided and recorded in `ops/kernel_mode.py`) — tier-1 proves
exactness against `paged_decode_reference` and token-exactness against
the dense-gather engine path on CPU, `tests/test_chip_compile.py` that
the chip's compiler takes it at real widths, and `chip_smoke.py` that
it agrees with the reference on the chip.
"""

from __future__ import annotations

import functools
import math
from typing import Any

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from . import kernel_mode

NEG_INF = -1e30
KERNEL_NAME = "paged_decode_attention"
# the same kernel body over a RING of pages (a layer kind that keeps the
# last `window` positions only): a name of its own in the compiled program,
# because a device trace names an operation by nothing else
WINDOW_KERNEL_NAME = "paged_decode_attention_window"
_LANES = 128  # TPU vector lane width; scalar-per-group state is kept 2D
_SUBLANES = 8  # f32 sublanes per vreg; the query group pads to this

__all__ = [
    "PagedKV",
    "PagedDecodeMeta",
    "paged_decode_attention",
    "paged_decode_reference",
]


# ---------------------------------------------------------------------------
# the engine <-> family interface types
# ---------------------------------------------------------------------------


@jax.tree_util.register_pytree_node_class
class PagedKV:
    """One pool buffer (K or V) as it threads through a family forward.

    `data` is the WHOLE stacked pool [L, pages+1, Hkv, page_size, D];
    `layer` (int32 scalar, traced inside the forwards' layer scan) says
    which layer of it an attention call reads, None where the caller
    keeps its own index (`models/deepseek.py`). `scales` is the int8
    mode's [L, pages+1, Hkv, page_size] per-row-per-head scale array,
    None for a bf16 pool. `compute_dtype` is the dtype attention math
    materializes K/V rows in (and the dtype of the new-token rows handed
    back for the engine to write); None defaults to `data.dtype` (bf16
    pools) or bfloat16 (int8 pools).

    The `is_paged_kv` marker lets `models/decode.decode_attention`
    dispatch without importing this (pallas-importing) module on the
    dense path."""

    is_paged_kv = True

    def __init__(self, data, scales=None, compute_dtype=None, layer=None):
        self.data = data
        self.scales = scales
        self.compute_dtype = compute_dtype
        self.layer = layer

    @property
    def quantized(self) -> bool:
        return self.scales is not None

    @property
    def row_dtype(self):
        """The dtype K/V rows materialize in (see class docstring)."""
        if self.compute_dtype is not None:
            return self.compute_dtype
        return jnp.bfloat16 if self.quantized else self.data.dtype

    def at_layer(self, layer) -> "PagedKV":
        """The same pool, read at `layer`."""
        return PagedKV(self.data, self.scales, self.compute_dtype, layer)

    def tree_flatten(self):
        return (self.data, self.scales, self.layer), (self.compute_dtype,)

    @classmethod
    def tree_unflatten(cls, aux, children):
        data, scales, layer = children
        return cls(data, scales, compute_dtype=aux[0], layer=layer)


@jax.tree_util.register_pytree_node_class
class PagedDecodeMeta:
    """The paged decode step's per-slot addressing, riding the family
    cache tuple's third slot (where the dense path carries `cache_len`).

    `table` [slots, pages_per_slot] int32 and `lengths` [slots] int32 are
    traced data; `rows` (pages_per_slot * page_size, static) is what
    `rope_table_len` sizes the rotary tables by. Families advance the
    dense `cache_len` with `+ seq_len` when returning new caches —
    `__add__` absorbs that as a no-op: per-slot length advance is the
    engine's job (live-lane masked, in `paged_append_rows`), not the
    traced program's."""

    is_paged_meta = True

    def __init__(self, table, lengths, rows: int):
        self.table = table
        self.lengths = lengths
        self.rows = rows

    def __add__(self, other):
        return self

    def tree_flatten(self):
        return (self.table, self.lengths), (self.rows,)

    @classmethod
    def tree_unflatten(cls, aux, children):
        table, lengths = children
        return cls(table, lengths, rows=aux[0])


# ---------------------------------------------------------------------------
# the bf16 / float pool's kernel: the work follows the live pages
# ---------------------------------------------------------------------------

# Pages copied and folded at a time: swept on the chip over 8 / 16 / 32 /
# 64 in both Qwen serving cells, PERF.md section 6 (PR 27).
PAGES_PER_GROUP = 32
# Table entries tested together for a RUN (consecutive page ids), which is
# one copy a pool: swept on the chip over 4 / 8 / 16 in docqa's and jamba's
# shapes, PERF.md section 6 (PRs 49-50).
PAGES_PER_RUN = 8
# What the four group buffers (K and V, two each) may take of the chip's
# 16 MB of scoped VMEM. A Qwen page (2 kv heads x 16 x 128 bf16) is 8 KB
# and its buffers 1 MB; an MHA page of 32 heads is 128 KB, and 32 of them
# four times over do not fit.
_GROUP_BUFFER_BYTES = 8 << 20


def _pages_per_group(pages_per_slot: int, page_shape, dtype) -> int:
    """`PAGES_PER_GROUP`, clamped by the table's width and by what the
    group buffers may hold of pages shaped `page_shape` [Hkv, ps, D]; a
    clamped group stays a whole number of 128-lane score tiles where it
    can."""
    heads, ps, width = page_shape
    itemsize = jnp.dtype(dtype).itemsize
    # as a page lies in VMEM: its rows padded to the dtype's sublane tile
    tile_rows = 32 // itemsize
    page_bytes = heads * -(-ps // tile_rows) * tile_rows * width * itemsize
    fit = max(1, _GROUP_BUFFER_BYTES // (4 * page_bytes))
    if fit >= 8:
        fit -= fit % 8
    return min(PAGES_PER_GROUP, pages_per_slot, fit)


def _pages_per_run(pages_per_group: int, pages_per_slot: int,
                   ring: bool) -> int:
    """`PAGES_PER_RUN`, clamped to a divisor of the group; 1 (every page a
    copy of its own) where a ring's aligned sub-group could wrap."""
    run = math.gcd(PAGES_PER_RUN, pages_per_group)
    return 1 if ring and pages_per_slot % run else run


def _live_pages_kernel(table_ref, lengths_ref, layer_ref, q_ref, kn_ref,
                       vn_ref, k_hbm, v_hbm, o_ref, kbuf, vbuf, sem, *,
                       sm_scale: float, page_size: int, pages_per_slot: int,
                       pages_per_group: int, pages_per_run: int,
                       num_kv_heads: int, window: int | None,
                       ring: bool = False):
    """Grid [slots]: one step is one slot. A loop with a DYNAMIC trip
    count walks the slot's live pages in groups of `pages_per_group`:
    a group is copied (every kv head of a page) out of the whole stacked
    pool `k_hbm`/`v_hbm` [L, N+1, Hkv, ps, D], where it lies in HBM,
    into one of two VMEM buffers, while the other buffer is folded into
    the online softmax: `pages_per_run` table entries that hold
    consecutive page ids as ONE copy a pool, any others a page a copy.
    A slot of length 0 (a lane the engine masked out) starts no copy at
    all. With `ring`, the table row
    is a ring: the page of positions [p * ps, (p + 1) * ps) is entry `p %
    pages_per_slot`, and what an entry held `pages_per_slot` pages ago
    lies behind the window, where the mask (which goes by position) does
    not look."""
    s = pl.program_id(0)
    length = lengths_ref[s]
    layer = layer_ref[0]
    G, R, ps, P = pages_per_group, pages_per_run, page_size, pages_per_slot
    rows = G * ps
    n_groups = (length + rows - 1) // rows
    # under a sliding window the walk starts at the first group that
    # holds a visible key (position > length - window)
    first = 0 if window is None else jnp.maximum(
        length - window + 1, 0) // rows

    def start(g, slot):
        """Start the copies of every page of group `g` into buffer `slot`."""

        # the index arithmetic binds `jax.lax` primitives on int32 scalars
        # (all of them >= 0, so `rem` is `%`): an operator on a tracer is a
        # jitted `jax.numpy` function, traced again in every body, `pl.when`
        # and loop, and a body's ~600 of them were seconds of every
        # process's set-up on the chip's host, which no compile cache saves
        # (PERF.md section 6, PR 50: mellum's `decode` traced in 6.3-6.9 s
        # with operators, 4.2-4.7 with these)
        i32 = lambda x: x if isinstance(x, jax.Array) else np.int32(x)  # noqa: E731
        row = jax.lax.mul(s, i32(P))        # the slot's table row
        at = jax.lax.mul(i32(g), i32(G))    # the group's first entry

        def entry(j):
            # entries past the table's end re-read its last page: masked
            e = jax.lax.add(at, i32(j))
            e = jax.lax.rem(e, i32(P)) if ring else jax.lax.min(e, i32(P - 1))
            return table_ref[jax.lax.add(row, e)]

        def copy(pages, j):
            # `pages` of the pool's layer -> the buffer's pages from `j`
            for which, (hbm, buf) in enumerate(((k_hbm, kbuf), (v_hbm, vbuf))):
                pltpu.make_async_copy(hbm.at[layer, pages], buf.at[slot, j],
                                      sem.at[which, slot]).start()

        # R entries from an index that is a multiple of R. Consecutive page
        # ids lie side by side in the pool, as their pages do in the
        # buffer: one copy. Whatever else the entries hold (a broken or
        # descending run, the trash page or the table's clamped end, both
        # repeated) goes a page at a time. Both land the same bytes in the
        # same rows. The walk is static: rolled, its copies take their
        # buffer offsets from the loop's index, which a table of NO runs
        # paid 10-19% for (PERF.md section 6, PRs 49-50)
        for j in range(0, G, R):
            pages = [entry(j + r) for r in range(R)]

            def each(j=j, pages=pages):
                for r in range(R):
                    copy(pages[r], j + r)

            if R == 1:
                each()
                continue
            run = functools.reduce(jax.lax.bitwise_and, (
                jax.lax.eq(pages[r], jax.lax.add(pages[0], i32(r)))
                for r in range(1, R)))
            pl.when(run)(lambda j=j, pages=pages: copy(
                pl.ds(pages[0], R), pl.ds(j, R)))
            pl.when(jax.lax.bitwise_not(run))(each)

    @pl.when(first < n_groups)
    def _first():
        start(first, first % 2)

    # K and q go to the MXU as they are stored when their dtypes agree
    # (bf16 x bf16 with a float32 result is the exact product); the
    # probabilities stay float32 through the PV product
    dot_dtype = jnp.promote_types(q_ref.dtype, kbuf.dtype)
    qs = [q_ref[0, h].astype(dot_dtype) for h in range(num_kv_heads)]

    def fold(state, s_blk, pv):
        """One online-softmax update with scaled, masked scores [Gp, n];
        `pv` maps the probabilities to their value sum [Gp, D]."""
        m, l, acc = state
        m_new = jnp.maximum(m, jnp.max(s_blk, axis=-1, keepdims=True))
        # a fully masked block keeps m_new at NEG_INF, where exp(s - m)
        # would be exp(0) = 1 a masked key: zero those explicitly
        p = jnp.where(s_blk <= NEG_INF / 2, 0.0, jnp.exp(s_blk - m_new))
        alpha = jnp.exp(m - m_new)
        return (m_new, l * alpha + jnp.sum(p, axis=-1, keepdims=True),
                acc * alpha + pv(p))

    def body(g, carry):
        slot = g % 2

        @pl.when(g + 1 < n_groups)
        def _next():
            start(g + 1, 1 - slot)

        # ONE wait a buffer: a DMA semaphore counts bytes, and a group's
        # copies fill exactly the buffer this descriptor names (a wait
        # a page read 11% slower in docqa's shape, PERF.md section 6)
        for buf, which in ((kbuf, 0), (vbuf, 1)):
            pltpu.make_async_copy(buf.at[slot], buf.at[slot],
                                  sem.at[which, slot]).wait()
        def visible(shape, axis):
            """Which of the group's rows the query may attend, laid along
            `axis` of `shape`."""
            pos = g * rows + jax.lax.broadcasted_iota(jnp.int32, shape, axis)
            if window is None:
                return pos < length
            # HF sliding-window convention: key visible iff q - key <
            # window; the query sits at position == length
            return (pos < length) & (pos > length - window)

        keep = visible((1, rows), 1)
        # the same mask down the rows of V: a group is copied whole, so
        # its buffer also holds rows nobody wrote for this request (the
        # slack of the last page, pages past the length, the trash page
        # behind padded table entries). A masked key's probability is an
        # exact 0, but 0 x inf is NaN: those rows are zeroed, so whatever
        # lies there cannot reach a live slot's output
        keep_row = visible((rows, 1), 0)
        out = []
        for h in range(num_kv_heads):
            # a head's pages of the group, [G, ps, D], as rows [G*ps, D]
            k = kbuf[slot, :, h].reshape(rows, -1).astype(dot_dtype)
            v = jnp.where(keep_row, vbuf[slot, :, h].reshape(
                rows, -1).astype(jnp.float32), 0.0)
            # q @ k^T as an NT contraction (no in-kernel transpose)
            s_blk = jax.lax.dot_general(
                qs[h], k, (((1,), (1,)), ((), ())),
                preferred_element_type=jnp.float32)
            s_blk = jnp.where(keep, s_blk * sm_scale, NEG_INF)
            out.append(fold(carry[h], s_blk, lambda p, v=v: jnp.dot(
                p, v, preferred_element_type=jnp.float32)))
        return tuple(out)

    Gp, D = q_ref.shape[2], q_ref.shape[3]
    carry = tuple((jnp.full((Gp, 1), NEG_INF, jnp.float32),
                   jnp.zeros((Gp, 1), jnp.float32),
                   jnp.zeros((Gp, D), jnp.float32))
                  for _ in range(num_kv_heads))
    carry = jax.lax.fori_loop(first, n_groups, body, carry)
    # the new token's K/V (position == length, always visible: its window
    # distance is 0) folds as one more single-key update, on the VPU (a
    # one-column matmul has no legal MXU shape); then finalize. l > 0
    # always: this key contributes exp(0) when it is the running max.
    for h in range(num_kv_heads):
        q = qs[h].astype(jnp.float32)
        kn = kn_ref[0, h].astype(jnp.float32)              # [1, D]
        vn = vn_ref[0, h].astype(jnp.float32)
        s_new = jnp.sum(q * kn, axis=-1, keepdims=True) * sm_scale
        _, l, acc = fold(carry[h], s_new, lambda p, vn=vn: p * vn)
        o_ref[0, h] = (acc / l).astype(o_ref.dtype)


@functools.partial(jax.jit, static_argnames=(
    "window", "interpret", "ring", "pages_per_group", "pages_per_run"))
def _live_pages_call(q4, kn, vn, pool_k, pool_v, layer, table, lengths, *,
                     window: int | None, interpret: bool, ring: bool,
                     pages_per_group: int, pages_per_run: int):
    """q4 [S, Hkv, Gp, D], kn/vn [S, Hkv, 1, D], pools [L, N+1, Hkv, ps,
    D], layer int32 scalar -> out [S, Hkv, Gp, D]. A program of its own:
    the layers of one model hand it the same shapes and their layer index
    as DATA, so a decode program that loops over its layers in Python
    traces and lowers ONE kernel body a variant (`window`, `ring`) and
    calls it a layer; every process pays tracing and lowering before any
    compile cache is asked (PERF.md section 6, PRs 46 and 50)."""
    S, Hkv, Gp, D = q4.shape
    P = table.shape[1]
    ps = pool_k.shape[3]
    G = pages_per_group
    kernel = functools.partial(
        _live_pages_kernel, sm_scale=1.0 / math.sqrt(D), page_size=ps,
        pages_per_slot=P, pages_per_group=G, pages_per_run=pages_per_run,
        num_kv_heads=Hkv, window=window, ring=ring)
    per_slot = lambda s, *_: (s, 0, 0, 0)  # noqa: E731
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=3,
        grid=(S,),
        in_specs=[pl.BlockSpec((1, Hkv, Gp, D), per_slot),
                  pl.BlockSpec((1, Hkv, 1, D), per_slot),
                  pl.BlockSpec((1, Hkv, 1, D), per_slot),
                  pl.BlockSpec(memory_space=pl.ANY),
                  pl.BlockSpec(memory_space=pl.ANY)],
        out_specs=pl.BlockSpec((1, Hkv, Gp, D), per_slot),
        scratch_shapes=[pltpu.VMEM((2, G, Hkv, ps, D), pool_k.dtype),
                        pltpu.VMEM((2, G, Hkv, ps, D), pool_v.dtype),
                        pltpu.SemaphoreType.DMA((2, 2))],
    )
    return pl.pallas_call(
        kernel,
        out_shape=jax.ShapeDtypeStruct((S, Hkv, Gp, D), q4.dtype),
        grid_spec=grid_spec,
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary",)),
        name=WINDOW_KERNEL_NAME if ring else KERNEL_NAME,
        interpret=pltpu.InterpretParams() if interpret else False,
    )(table.reshape(-1).astype(jnp.int32), lengths.astype(jnp.int32),
      jnp.asarray(layer, jnp.int32).reshape(1), q4, kn, vn, pool_k, pool_v)


# ---------------------------------------------------------------------------
# the older kernel: one grid step a page of ONE layer's pool. Kept, as it
# was, for what the kernel above cannot take: int8 pools, and heads whose
# width is no whole 128-lane tile
# ---------------------------------------------------------------------------


def _page_step_kernel(table_ref, lengths_ref, q_ref, kn_ref, vn_ref,
                      pk_ref, pv_ref, *rest, sm_scale: float,
                      page_size: int, pages_per_slot: int,
                      num_kv_heads: int, window: int | None,
                      quantized: bool):
    """Grid [slots, pages_per_slot] (pages innermost/arbitrary): each
    step folds one page of one slot — every kv head of it, in a static
    loop — into the online softmax; the last step also folds the new
    token's K/V and finalizes. `table_ref`/`lengths_ref` are
    scalar-prefetch SMEM refs — the same values the BlockSpec index maps
    used to choose the page blocks.

    Every block's trailing two dims are whole array dims ([ps, D] page
    tiles, [Gp, D] query groups, [1, D] new rows, [Hkv, ps] scales), the
    one shape rule Mosaic holds a TPU block to; heads are indexed with
    static leading indices only."""
    if quantized:
        ks_ref, vs_ref, o_ref, m_scr, l_scr, acc_scr = rest
    else:
        (ks_ref, vs_ref), (o_ref, m_scr, l_scr, acc_scr) = (None, None), rest
    s, j = pl.program_id(0), pl.program_id(1)
    length = lengths_ref[s]

    @pl.when(j == 0)
    def _init():
        m_scr[...] = jnp.full_like(m_scr, NEG_INF)
        l_scr[...] = jnp.zeros_like(l_scr)
        acc_scr[...] = jnp.zeros_like(acc_scr)

    def update(h, s_blk, pv):
        """One online-softmax step for kv head `h`: fold pre-scaled,
        pre-masked scores s_blk [Gp, n] into the running state; `pv`
        maps the probabilities [Gp, n] to their value sum [Gp, D].
        Probabilities stay f32 through the PV product — decode is
        bandwidth-bound, not MXU-bound, and the dense reference path
        keeps f32 probabilities too."""
        m_prev = m_scr[h][:, :1]
        l_prev = l_scr[h][:, :1]
        m_new = jnp.maximum(m_prev, jnp.max(s_blk, axis=-1, keepdims=True))
        p = jnp.exp(s_blk - m_new)
        # a fully-masked block keeps m_new at NEG_INF where exp(s - m)
        # would be exp(0) = 1 per masked key — zero those explicitly
        p = jnp.where(s_blk <= NEG_INF / 2, 0.0, p)
        alpha = jnp.exp(m_prev - m_new)
        l_new = l_prev * alpha + jnp.sum(p, axis=-1, keepdims=True)
        acc_scr[h] = acc_scr[h] * alpha + pv(p)
        m_scr[h] = jnp.broadcast_to(m_new, m_scr.shape[1:])
        l_scr[h] = jnp.broadcast_to(l_new, l_scr.shape[1:])

    # a page is live iff it holds at least one row below the slot's
    # length; dead pages (allocation slack, trash padding) compute
    # nothing, and their index map re-targeted an already-fetched block
    live = j * page_size < length

    @pl.when(live)
    def _page():
        pos = j * page_size + jax.lax.broadcasted_iota(
            jnp.int32, (1, page_size), 1)
        keep = pos < length
        if window is not None:
            # HF sliding-window convention: key visible iff q - key <
            # window; the query sits at position == length
            keep = keep & (pos > length - window)
        if quantized:
            # per-row scales sit along the LANES of the [Gp, ps] score
            # block, so dequantization is applied to scores and
            # probabilities (q.(c*s) == (q.c)*s) instead of to the
            # [ps, D] codes — no lane->sublane transpose of the scales
            ks_all = ks_ref[0].astype(jnp.float32)        # [Hkv, ps]
            vs_all = vs_ref[0].astype(jnp.float32)
        for h in range(num_kv_heads):
            q = q_ref[0, h].astype(jnp.float32)           # [Gp, D]
            k = pk_ref[0, h].astype(jnp.float32)          # [ps, D]
            v = pv_ref[0, h].astype(jnp.float32)
            # q @ k^T as an NT contraction (no in-kernel transpose)
            s_blk = jax.lax.dot_general(
                q, k, (((1,), (1,)), ((), ())),
                preferred_element_type=jnp.float32)
            if quantized:
                s_blk = s_blk * ks_all[h:h + 1, :]
                vs = vs_all[h:h + 1, :]
            s_blk = jnp.where(keep, s_blk * sm_scale, NEG_INF)
            if quantized:
                update(h, s_blk, lambda p, v=v, vs=vs: jnp.dot(
                    p * vs, v, preferred_element_type=jnp.float32))
            else:
                update(h, s_blk, lambda p, v=v: jnp.dot(
                    p, v, preferred_element_type=jnp.float32))

    @pl.when(j == pages_per_slot - 1)
    def _tail():
        # the new token's K/V (position == length, always visible — its
        # window distance is 0) folds as one more single-key update, on
        # the VPU (a one-column matmul has no legal MXU shape); then
        # finalize. l > 0 always: this key contributes exp(0) when it is
        # the running max.
        for h in range(num_kv_heads):
            q = q_ref[0, h].astype(jnp.float32)
            kn = kn_ref[0, h].astype(jnp.float32)          # [1, D]
            vn = vn_ref[0, h].astype(jnp.float32)
            s_new = jnp.sum(q * kn, axis=-1, keepdims=True) * sm_scale
            update(h, s_new, lambda p, vn=vn: p * vn)
            l = l_scr[h][:, :1]
            o_ref[0, h] = (acc_scr[h] / jnp.maximum(l, 1e-30)).astype(
                o_ref.dtype)


def _page_step_call(q4, kn, vn, pool_k, pool_v, k_scales, v_scales,
                    table, lengths, window: int | None, interpret: bool):
    """q4 [S, Hkv, Gp, D], kn/vn [S, Hkv, 1, D], ONE layer's pool [N+1,
    Hkv, ps, D] (+ scales [N+1, Hkv, ps] when quantized) -> out [S, Hkv,
    Gp, D]."""
    S, Hkv, Gp, D = q4.shape
    P = table.shape[1]
    ps = pool_k.shape[2]
    quantized = k_scales is not None
    sm_scale = 1.0 / math.sqrt(D)

    def page_map(s, j, table_ref, lengths_ref):
        # dead steps (page start >= length) re-target page 0 of the
        # slot's table: consecutive dead steps then revisit one block
        # instead of streaming allocation slack / trash padding
        j_live = jnp.where(j * ps < jnp.maximum(lengths_ref[s], 1), j, 0)
        return table_ref[s * P + j_live], 0, 0, 0

    def per_slot(s, j, table_ref, lengths_ref):
        return (s, 0, 0, 0)

    in_specs = [
        pl.BlockSpec((1, Hkv, Gp, D), per_slot),
        pl.BlockSpec((1, Hkv, 1, D), per_slot),
        pl.BlockSpec((1, Hkv, 1, D), per_slot),
        pl.BlockSpec((1, Hkv, ps, D), page_map),
        pl.BlockSpec((1, Hkv, ps, D), page_map),
    ]
    operands = [q4, kn, vn, pool_k, pool_v]
    if quantized:
        scale_map = (lambda s, j, table_ref, lengths_ref:
                     page_map(s, j, table_ref, lengths_ref)[:3])
        in_specs += [pl.BlockSpec((1, Hkv, ps), scale_map),
                     pl.BlockSpec((1, Hkv, ps), scale_map)]
        operands += [k_scales, v_scales]
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,
        grid=(S, P),
        in_specs=in_specs,
        out_specs=pl.BlockSpec((1, Hkv, Gp, D), per_slot),
        scratch_shapes=[
            pltpu.VMEM((Hkv, Gp, _LANES), jnp.float32),
            pltpu.VMEM((Hkv, Gp, _LANES), jnp.float32),
            pltpu.VMEM((Hkv, Gp, D), jnp.float32),
        ],
    )
    kernel = functools.partial(
        _page_step_kernel, sm_scale=sm_scale, page_size=ps,
        pages_per_slot=P, num_kv_heads=Hkv, window=window,
        quantized=quantized)
    return pl.pallas_call(
        kernel,
        out_shape=jax.ShapeDtypeStruct((S, Hkv, Gp, D), q4.dtype),
        grid_spec=grid_spec,
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary"),
        ),
        name=KERNEL_NAME,
        interpret=interpret,
    )(table.reshape(-1), lengths, *operands)


# ---------------------------------------------------------------------------
# the op the shared decode path calls
# ---------------------------------------------------------------------------


def paged_decode_attention(
    q: jax.Array,
    k_new: jax.Array,
    v_new: jax.Array,
    pk: PagedKV,
    pv: PagedKV,
    meta: PagedDecodeMeta,
    window: int | None = None,
    interpret: bool | None = None,
    ring: bool = False,
):
    """One decode step of paged attention for every slot at once, in the
    layer `pk.layer` of the stacked pool. `ring`: the pool is a group's
    that keeps the last `window` positions, `meta.table` its rings
    (`serving/cache.py`), and the call is the kernel named
    `WINDOW_KERNEL_NAME`.

    q: [S, 1, H, D] (S slots, one token each, H = Hkv * group);
    k_new/v_new: [S, 1, Hkv, D] — this step's K/V, folded in-kernel and
    returned (cast to the pool's row dtype) for the engine to append.
    Returns (out [S, 1, H, D], (k_row, v_row) both [S, 1, Hkv, D])."""
    S, sq, H, D = q.shape
    if sq != 1:
        raise ValueError(
            f"paged decode attention is one token per slot; got S_q={sq} "
            "(chunked prefill stays on the dense-gather path)")
    Hkv = k_new.shape[2]
    if H % Hkv:
        raise ValueError(f"q heads ({H}) not a multiple of kv heads ({Hkv})")
    if meta.table.shape[0] != S:
        raise ValueError(
            f"page table covers {meta.table.shape[0]} slots, q has {S}")
    if pk.data.ndim != 5 or pk.layer is None:
        raise ValueError(
            "paged decode attention takes the whole stacked pool [L, "
            "pages + 1, Hkv, page_size, D] and a layer index; got a pool of "
            f"shape {pk.data.shape} and layer {pk.layer!r}")
    if ring:
        if window is None or pk.quantized or D % _LANES:
            raise ValueError(
                "a ring of pages is read by the live-pages kernel under a "
                "window: it takes a bf16 or float pool of 128-lane heads; "
                f"got window {window!r}, head width {D}, int8 "
                f"{pk.quantized}")
    elif window is not None and (window <= 0 or window >= meta.rows):
        window = None  # band wider than the cache reach: plain causal
    interpret = kernel_mode.resolve_interpret(
        WINDOW_KERNEL_NAME if ring else KERNEL_NAME, interpret)
    G = H // Hkv
    row_dtype = pk.row_dtype
    # the fold must see exactly the bytes the engine will write, so a
    # later step reading the row from the pool agrees with this step
    k_row = k_new.astype(row_dtype)
    v_row = v_new.astype(row_dtype)
    q4 = q[:, 0].reshape(S, Hkv, G, D)
    # the query group is the sublane dim of every score block: pad it to
    # a whole f32 sublane tile (zero rows attend uniformly and are
    # sliced off) so G in {1, 4, 6} lowers like G = 8
    Gp = -(-G // _SUBLANES) * _SUBLANES
    if Gp != G:
        q4 = jnp.pad(q4, ((0, 0), (0, 0), (0, Gp - G), (0, 0)))
    # [S, Hkv, 1, D]: a unit sublane dim makes the per-head new row a
    # whole-trailing-dims block
    kn, vn = k_row[:, 0, :, None, :], v_row[:, 0, :, None, :]
    if pk.quantized or D % _LANES:
        # the page-a-grid-step kernel, given its layer's slice of the
        # pool (module docstring: what the live-pages kernel cannot take)
        def layer_of(stacked):
            return None if stacked is None else jax.lax.dynamic_index_in_dim(
                stacked, pk.layer, keepdims=False)

        out = _page_step_call(
            q4, kn, vn, layer_of(pk.data), layer_of(pv.data),
            layer_of(pk.scales), layer_of(pv.scales), meta.table,
            meta.lengths, window, interpret)
    else:
        # the group and the run follow from static shapes, here, so that
        # the call below is keyed by them
        P = meta.table.shape[1]
        group = _pages_per_group(P, pk.data.shape[2:], pk.data.dtype)
        out = _live_pages_call(
            q4, kn, vn, pk.data, pv.data, pk.layer, meta.table,
            meta.lengths, window=window, interpret=interpret, ring=ring,
            pages_per_group=group,
            pages_per_run=_pages_per_run(group, P, ring))
    return out[:, :, :G].reshape(S, 1, H, D), (k_row, v_row)


def paged_decode_reference(
    q: jax.Array,
    k_new: jax.Array,
    v_new: jax.Array,
    pk: PagedKV,
    pv: PagedKV,
    meta: PagedDecodeMeta,
    window: int | None = None,
    ring: bool = False,
):
    """Dense-gather reference with identical semantics (and the
    executable spec of them): gather every table page of the pool's layer
    `pk.layer`, dequantize,
    overlay the new token's row at position == length, mask rows the
    query may not see, plain f32 softmax. The exactness tests pin the
    kernel to this; the serving engine's dense path is the same math
    threaded through the family forward."""
    S, _, H, D = q.shape
    Hkv = k_new.shape[2]
    G = H // Hkv
    ps = pk.data.shape[3]
    R = meta.table.shape[1] * ps
    row_dtype = pk.row_dtype

    def dense(p: PagedKV):
        pages = p.data[p.layer][meta.table]             # [S, P, Hkv, ps, D]
        full = pages.astype(jnp.float32)
        if p.quantized:
            full = full * p.scales[p.layer][meta.table].astype(
                jnp.float32)[..., None]
        return jnp.swapaxes(full, 2, 3).reshape(S, R, Hkv, D)

    k_all, v_all = dense(pk), dense(pv)
    k_row = k_new.astype(row_dtype)
    v_row = v_new.astype(row_dtype)
    rows = jnp.arange(R, dtype=jnp.int32)
    if ring:
        # row r of a ring holds the newest written position p < length
        # with p % R == r (negative: nothing of this request yet); the new
        # token's row goes where position `length` belongs
        last = meta.lengths[:, None] - 1
        pos = last - (last - rows[None, :]) % R
        pos = jnp.where(rows[None, :] == meta.lengths[:, None] % R,
                        meta.lengths[:, None], pos)
    else:
        pos = jnp.broadcast_to(rows[None, :], (S, R))
    sel = (pos == meta.lengths[:, None])[:, :, None, None]
    k_all = jnp.where(sel, k_row.astype(jnp.float32), k_all)
    v_all = jnp.where(sel, v_row.astype(jnp.float32), v_all)
    keep = (pos >= 0) & (pos <= meta.lengths[:, None])
    if window is not None and (ring or window < R):
        keep = keep & (pos > meta.lengths[:, None] - window)
    q4 = q[:, 0].reshape(S, Hkv, G, D).astype(jnp.float32)
    s = jnp.einsum("shgd,srhd->shgr", q4, k_all) / math.sqrt(D)
    s = jnp.where(keep[:, None, None, :], s, NEG_INF)
    p = jax.nn.softmax(s, axis=-1)
    out = jnp.einsum("shgr,srhd->shgd", p, v_all)
    return out.reshape(S, 1, H, D).astype(q.dtype), (k_row, v_row)
