"""Decode attention whose keys a second, learned scorer chooses: an indexer
scores every cached position of a slot, the `top_k` largest are selected
EXACTLY, and attention is a softmax over the selected positions alone
(DeepSeek-Sparse-Attention's lightning indexer, as `models/keye.py`
serves it). Three pieces, each with names of its own in a device trace:

- `indexer_paged_scores` (Pallas, `SCORES_KERNEL_NAME`): a slot's LIVE
  pages of the index-key pool (`serving/cache.py`, SIDE ROW: one key of w
  lanes a token and layer, under K's and V's page ids) -> float32 scores
  `I[s, n] = sum_j wts[s, j] * relu(q[s, j] . key[n])`, `-inf` from the
  slot's length on. One grid step a slot; a loop with a dynamic trip count
  walks the live pages in groups, each page one copy out of the whole
  stacked pool into one of two VMEM buffers while the other is scored.
  The pool stores a page's `page_size x w` lanes as whole 128-lane rows
  (`128 / w` tokens a row), so the kernel multiplies the rows by a
  BLOCK-DIAGONAL query (`128 / w` copies of q, each over its own lanes):
  every token's score comes out of one MXU product with no relayout of
  the page, in `128 / w` planes that the wrapper interleaves.
- `exact_topk_mask` (XLA, `SELECT_NAME`; a `while` in the trace) and
  `exact_topk_mask_rows` (Pallas, `ROWS_SELECT_NAME`): which
  positions are the k largest of each row, ties to the LOWER position. No
  sort: the k-th largest value is found bit by bit over the scores'
  order-preserving uint32 image (32 counting passes), then the ties at
  that value are cut at the position that fills k (a second bisection,
  over position bits, run only if some row must leave a tie out). Exact
  for any input; `approx_max_k`, or any selection that can miss a key, is
  a different model. Two forms of the one algorithm, taken by the static
  shape and the call site: a DECODE step's one row a slot (`[16, 43008]`,
  2.75 MB of keys) runs XLA's loop as written; a prefill CHUNK's hundreds
  of rows over a slot's view (`[1, 512, 43520]`, 89 MB of keys, which
  XLA streams from HBM in every pass unless it happens to find room for
  them) runs `exact_topk_mask_rows` (Pallas, `ROWS_SELECT_NAME`): a tile
  of 32 query rows is read once, only the columns below the slot's live
  length, its keys stay in vector memory through the 32 passes, the cut
  of ties runs in the kernel for the tiles that need it, and the mask is
  written once. Bit for bit the same mask; fewer rows than one tile keep
  XLA's loop.
- `sparse_paged_decode_attention` (Pallas, `ATTENTION_KERNEL_NAME`): the
  live-pages walk of `ops/paged_attention.py` over a COMPACTED table. A
  page none of whose positions was selected is NOT COPIED: the wrapper
  sorts the pages that hold a selected position to the front of a slot's
  table (one small sort a call; a softmax does not know an order) and the
  walk ends with them, every copy unconditional, one wait a buffer. The
  scores take an additive bias (0 selected, -1e30 not) from a [groups,
  rows] block in the same order, so the softmax runs over the selected
  positions alone. The new token's own K/V fold as a last single-key
  update iff the token selected itself.

Granularity, and why. The chip reads a page (`Hkv x 16 x 128` bf16, 16 KB)
in ONE copy of about 21 ns of issue (PERF.md section 6, PR 27), which is
what 16 KB take at the HBM's rate; a single bf16 row is no copy at all
(two rows share a 32-bit sublane in the pool's (8, 128)(2, 1) tiling). A
copy a selected TOKEN would therefore cost 2,048 issues a slot, layer and
pool: as much as reading 2,048 whole pages. So the kernel reads at PAGE
granularity, the pages that hold a selected position, and masks inside
them; what that saves depends on how the selection clusters (`PERF.md`
section 6, PR 33 has the reading).

While `length + 1 <= top_k` every position is selected and the result is
`paged_decode_attention`'s.

- `sparse_latent_paged_decode_attention` (Pallas,
  `LATENT_ATTENTION_KERNEL_NAME`): the same walk over a LATENT pool
  (`ops/latent_paged_attention.py`: one row a token that is key and value
  at once, H absorbed query heads over it). The index keys are then the
  latent pool's side row; `indexer_paged_scores` and `exact_topk_mask` are
  the ones above. A copied page is read ONCE for all heads and as key and
  value both, and the same reasoning holds for its granularity.
"""

from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from . import kernel_mode
from .paged_attention import (
    _SUBLANES,
    NEG_INF,
    PagedDecodeMeta,
    PagedKV,
    _pages_per_group,
)

SCORES_KERNEL_NAME = "indexer_paged_scores"
ATTENTION_KERNEL_NAME = "sparse_paged_decode_attention"
SELECT_NAME = "sparse_topk_select"
ROWS_SELECT_NAME = "sparse_topk_select_rows"
LATENT_ATTENTION_KERNEL_NAME = "sparse_latent_paged_decode_attention"
# latent pages copied and attended at a time (a page is 20 KB at 640 lanes)
LATENT_PAGES_PER_GROUP = 32
# index-pool pages copied and scored at a time (a page is 2 KB at the
# published shape: 64 of them are one [512, 128] bf16 operand)
SCORE_PAGES_PER_GROUP = 64

__all__ = [
    "indexer_scores",
    "indexer_paged_scores",
    "indexer_paged_scores_reference",
    "exact_topk_mask",
    "exact_topk_mask_rows",
    "selection_columns",
    "sparse_paged_decode_attention",
    "sparse_paged_decode_reference",
    "sparse_latent_paged_decode_attention",
    "sparse_latent_paged_decode_reference",
]


# ---------------------------------------------------------------------------
# the index score, plain
# ---------------------------------------------------------------------------


def indexer_scores(q, wts, keys):
    """`I[..., n] = sum_j wts[..., j] * relu(q[..., j, :] . keys[..., n, :])`
    in float32 from operands as they are stored. q [..., J, w], wts
    [..., J] float32, keys [..., N, w] -> [..., N]."""
    s = jnp.einsum("...jw,...nw->...jn", q, keys.astype(q.dtype),
                   preferred_element_type=jnp.float32)
    return jnp.sum(jax.nn.relu(s) * wts[..., None].astype(jnp.float32),
                   axis=-2)


# ---------------------------------------------------------------------------
# indexer_paged_scores
# ---------------------------------------------------------------------------


def _scores_kernel(table_ref, lengths_ref, layer_ref, q_ref, w_ref, pool_hbm,
                   o_ref, buf, sem, *, pages_per_slot: int,
                   pages_per_group: int, tokens_per_row: int, heads: int):
    """Grid [slots]. q_ref [1, tpr * J, lanes] (block-diagonal), w_ref
    [1, tpr * J, 1] float32, pool_hbm [L, N + 1, r2, lanes], o_ref [1,
    groups, tpr, G * r2] float32: in group g, plane b, column `j * r2 + r`
    is the token `r * tpr + b` of the group's j-th page."""
    s = pl.program_id(0)
    length = lengths_ref[s]
    layer = layer_ref[0]
    G, P, tpr, J = pages_per_group, pages_per_slot, tokens_per_row, heads
    r2 = buf.shape[2]
    rows = G * r2                       # pool rows a group
    per_group = rows * tpr              # positions a group
    n_groups = (length + per_group - 1) // per_group
    o_ref[...] = jnp.full(o_ref.shape, -jnp.inf, o_ref.dtype)

    def start(g, slot):
        for j in range(G):
            page = table_ref[s * P + jnp.minimum(g * G + j, P - 1)]
            pltpu.make_async_copy(pool_hbm.at[layer, page], buf.at[slot, j],
                                  sem.at[slot]).start()

    @pl.when(n_groups > 0)
    def _first():
        start(0, 0)

    q = q_ref[0]
    wts = w_ref[0]

    def body(g, carry):
        slot = g % 2

        @pl.when(g + 1 < n_groups)
        def _next():
            start(g + 1, 1 - slot)

        # one wait a buffer: the semaphore counts bytes, and a group's
        # copies fill exactly this buffer
        pltpu.make_async_copy(buf.at[slot], buf.at[slot],
                              sem.at[slot]).wait()
        keys = buf[slot].reshape(rows, -1).astype(q.dtype)
        sc = jax.lax.dot_general(q, keys, (((1,), (1,)), ((), ())),
                                 preferred_element_type=jnp.float32)
        sc = jnp.maximum(sc, 0.0) * wts                    # [tpr * J, rows]
        col = jax.lax.broadcasted_iota(jnp.int32, (1, rows), 1)
        # column c of the group is pool row c: page c // r2 of the group,
        # row c % r2, whose token b sits at position
        # (g * G + c // r2) * ps + (c % r2) * tpr + b = (g * rows + c) * tpr + b
        for b in range(tpr):
            plane = jnp.sum(sc[b * J:(b + 1) * J], axis=0, keepdims=True)
            pos = (g * rows + col) * tpr + b
            o_ref[0, g, pl.ds(b, 1), :] = jnp.where(pos < length, plane,
                                                    -jnp.inf)
        return carry

    jax.lax.fori_loop(0, n_groups, body, 0)


def _block_diagonal(q, tokens_per_row: int):
    """q [S, J, w] -> [S, tpr * J, tpr * w]: copy b of q over lanes
    [b * w, (b + 1) * w), zeros elsewhere."""
    S, J, w = q.shape
    eye = jnp.eye(tokens_per_row, dtype=q.dtype)
    return jnp.einsum("ab,sjw->sajbw", eye, q).reshape(
        S, tokens_per_row * J, tokens_per_row * w)


def indexer_paged_scores(q, wts, pool: PagedKV, meta: PagedDecodeMeta,
                         page_size: int, interpret: bool | None = None):
    """float32 index scores [S, R] (R = pages_per_slot x page_size) of every
    slot's one query over its cached positions, `-inf` at positions >=
    `meta.lengths`. q [S, J, w] in the pool's dtype, wts [S, J] float32,
    `pool.data` the whole stacked index pool [L, pages + 1, page_size * w
    / lanes, lanes] read at `pool.layer`."""
    S, J, w = q.shape
    data = pool.data
    r2, lanes = data.shape[2], data.shape[3]
    tpr = lanes // w
    if data.ndim != 4 or lanes % w or r2 * tpr != page_size \
            or pool.layer is None:
        raise ValueError(
            f"an index pool is [L, pages + 1, page_size * w / lanes, lanes] "
            f"with a layer index; got {data.shape} for page_size "
            f"{page_size}, w {w}, layer {pool.layer!r}")
    P = meta.table.shape[1]
    interpret = kernel_mode.resolve_interpret(SCORES_KERNEL_NAME, interpret)
    G = min(SCORE_PAGES_PER_GROUP, P)
    n_groups = -(-P // G)
    rows = G * r2
    Jp = -(-J // _SUBLANES) * _SUBLANES   # whole sublane tiles a plane
    qp = jnp.pad(q, ((0, 0), (0, Jp - J), (0, 0)))
    wp = jnp.pad(wts.astype(jnp.float32), ((0, 0), (0, Jp - J)))
    q_bd = _block_diagonal(qp.astype(data.dtype), tpr)
    w_col = jnp.tile(wp, (1, tpr))[:, :, None]
    kernel = functools.partial(
        _scores_kernel, pages_per_slot=P, pages_per_group=G,
        tokens_per_row=tpr, heads=Jp)
    per_slot = lambda s, *_: (s, 0, 0)  # noqa: E731
    out = pl.pallas_call(
        kernel,
        out_shape=jax.ShapeDtypeStruct((S, n_groups, tpr, rows),
                                       jnp.float32),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=3,
            grid=(S,),
            in_specs=[pl.BlockSpec((1, tpr * Jp, lanes), per_slot),
                      pl.BlockSpec((1, tpr * Jp, 1), per_slot),
                      pl.BlockSpec(memory_space=pl.ANY)],
            out_specs=pl.BlockSpec((1, n_groups, tpr, rows),
                                   lambda s, *_: (s, 0, 0, 0)),
            scratch_shapes=[pltpu.VMEM((2, G, r2, lanes), data.dtype),
                            pltpu.SemaphoreType.DMA((2,))]),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary",)),
        name=SCORES_KERNEL_NAME,
        interpret=pltpu.InterpretParams() if interpret else False,
    )(meta.table.reshape(-1).astype(jnp.int32),
      meta.lengths.astype(jnp.int32),
      jnp.asarray(pool.layer, jnp.int32).reshape(1), q_bd, w_col, data)
    # [S, groups, tpr, G, r2] -> position = (g * G + j) * ps + r * tpr + b
    out = out.reshape(S, n_groups, tpr, G, r2)
    out = jnp.transpose(out, (0, 1, 3, 4, 2)).reshape(S, -1)
    return out[:, :P * page_size]


def indexer_paged_scores_reference(q, wts, pool: PagedKV,
                                   meta: PagedDecodeMeta, page_size: int):
    """The same scores by a plain gather of every table page."""
    pages = pool.data[pool.layer][meta.table]             # [S, P, r2, lanes]
    keys = pages.reshape(q.shape[0], -1, q.shape[-1])            # [S, R, w]
    scores = indexer_scores(q.astype(pool.data.dtype), wts, keys)
    pos = jnp.arange(keys.shape[1], dtype=jnp.int32)[None, :]
    return jnp.where(pos < meta.lengths[:, None], scores, -jnp.inf)


# ---------------------------------------------------------------------------
# the selection
# ---------------------------------------------------------------------------


def _ordered_bits(x):
    """float32 -> uint32, monotone: a < b iff bits(a) < bits(b) (-0.0 and
    +0.0 made one value first)."""
    b = jax.lax.bitcast_convert_type(x + 0.0, jnp.uint32)
    return jnp.where(b >> 31 == 1, ~b, b | jnp.uint32(1 << 31))


def exact_topk_mask(scores, k: int):
    """[..., N] bool: the `min(k, visible)` positions of largest score in
    each row of float32 `scores` [..., N], where a position is visible iff
    its score is above `-inf`; among equal scores the LOWER positions.
    Exact for every input (scores must not be NaN)."""
    # the selection is XLA's own loop: compiled wherever it runs
    kernel_mode.resolve_interpret(SELECT_NAME, False)
    with jax.named_scope(SELECT_NAME):
        N = scores.shape[-1]
        visible = scores > -jnp.inf
        keys = _ordered_bits(scores.astype(jnp.float32))
        count = functools.partial(jnp.sum, axis=-1, keepdims=True,
                                  dtype=jnp.int32)

        def value_bit(i, prefix):
            cand = prefix | (jnp.uint32(1) << (jnp.uint32(31) - i))
            return jnp.where(count(keys >= cand) >= k, cand, prefix)

        # the largest value that at least k keys reach: the k-th largest
        # (0 where a row has fewer than k keys: everything is above it)
        kth = jax.lax.fori_loop(
            0, 32, value_bit,
            jnp.zeros(scores.shape[:-1] + (1,), jnp.uint32))
        above = (keys > kth) & visible
        tie = (keys == kth) & visible
        need = k - count(above)             # ties to take, >= 1 where any
        pos = jax.lax.broadcasted_iota(jnp.int32, scores.shape,
                                       scores.ndim - 1)

        def cut(_):
            """The largest position p with `ties at or below p <= need`."""
            def position_bit(i, prefix):
                cand = prefix | (jnp.int32(1) << (bits - 1 - i))
                fits = count(tie & (pos <= cand)) <= need
                return jnp.where(fits, cand, prefix)

            bits = max(1, (N - 1).bit_length())
            last = jax.lax.fori_loop(0, bits, position_bit,
                                     jnp.zeros_like(need))
            # position 0 is taken by every prefix: a tie there counts
            return jnp.where(count(tie & (pos <= 0)) <= need, last, -1)

        last = jax.lax.cond(jnp.all(count(tie) <= need),
                            lambda _: jnp.full_like(need, N), cut, None)
        return above | (tie & (pos <= last))


_INT_MIN = -(1 << 31)
# the signed key of -inf: a key is visible iff it is above this
_KEY_NEG_INF = (0xFF800000 ^ 0x7FFFFFFF) - (1 << 32)


def _signed_keys(x):
    """float32 -> int32, monotone in SIGNED order: `_ordered_bits` with its
    top bit flipped, which is the order the chip's vector compares know."""
    b = jax.lax.bitcast_convert_type(x, jnp.int32)
    b = jnp.where(b == _INT_MIN, 0, b)                  # -0.0 is +0.0
    return b ^ ((b >> 31) & jnp.int32(0x7FFFFFFF))


def _select_rows_kernel(live_ref, x_ref, o_ref, keys, *, k: int,
                        position_bits: int):
    """Grid [B, row tiles]. `x_ref` [n, rows, block] float32: a tile of
    query rows' scores in the n blocks of columns they were scored in,
    `o_ref` [rows, N] int8, `keys` [steps, rows, step] int32: the row
    tile's `_signed_keys`, one counting step of columns an entry, on
    which all the counting passes run; only the entries below
    `live_ref[b]` columns are written and read (a pass walks them
    `_SELECT_UNROLL` a turn: the last turn's entries past the live ones
    are filled with the least key, which no pass counts). Whatever walks
    the counting steps (the keys' fill, the passes, the mask's write) is
    a LOOP over the live ones, never a Python loop over the view's: the
    body's jaxpr and its Mosaic text, which every process traces and
    lowers before any compile cache is asked, do not grow with the view."""
    b = pl.program_id(0)
    _, rows, block = x_ref.shape
    step = keys.shape[2]
    per_block = block // step
    live = live_ref[b]
    n_live = (live + step - 1) // step      # counting steps with a live column
    unroll = _SELECT_UNROLL
    n_loops = (n_live + unroll - 1) // unroll

    def lanes(c):
        """The `step` lanes of counting step `c` in an array of whole
        steps."""
        return pl.ds(pl.multiple_of(c * step, step), step)

    def over(first, end, body):
        """`body(c)` for the counting steps [first, end): ONE traced body
        however many steps the view has."""
        def turn(c, carry):
            body(c)
            return carry
        jax.lax.fori_loop(first, end, turn, 0)

    @functools.partial(over, 0, n_live)
    def _keys(c):
        keys[c] = _signed_keys(
            x_ref[c // per_block, :, lanes(c % per_block)])

    @functools.partial(over, n_live, n_loops * unroll)
    def _fill(c):
        keys[c] = jnp.full((rows, step), _INT_MIN, jnp.int32)

    def count(pred):
        """[rows, 1] int32: how many live keys of a row `pred` holds
        for."""
        def body(t, acc):
            for u in range(unroll):
                c = t * unroll + u
                acc = acc + pred(c, keys[c]).astype(jnp.int32)
            return acc
        acc = jax.lax.fori_loop(0, n_loops, body,
                                jnp.zeros((rows, step), jnp.int32))
        return jnp.sum(acc, axis=-1, keepdims=True)

    def value_bit(i, carry):
        # `prefix` holds the bits of the UNSIGNED image; a candidate is
        # compared in the signed one
        prefix, reached = carry
        cand = prefix | (jnp.int32(1) << (31 - i))
        at_least = cand ^ _INT_MIN
        n = count(lambda c, key: key >= at_least)
        take = n >= k
        return (jnp.where(take, cand, prefix),
                jnp.where(take, n, reached))

    # the k-th largest key (the least key where a row has fewer than k)
    # and how many keys reach it
    zero = jnp.zeros((rows, 1), jnp.int32)
    kth, reached = jax.lax.fori_loop(0, 32, value_bit, (zero, zero))
    kth = kth ^ _INT_MIN
    # visible keys at or above the k-th
    floor = jnp.maximum(kth, _KEY_NEG_INF + 1)
    # a row must leave a tie out iff more than k visible keys reach a
    # visible k-th
    cuts = (reached > k) & (kth > _KEY_NEG_INF)

    @functools.partial(over, n_live, o_ref.shape[1] // step)
    def _nothing_past_the_live_steps(c):
        o_ref[:, lanes(c)] = jnp.zeros((rows, step), jnp.int8)

    def write(mask):
        @functools.partial(over, 0, n_live)
        def _step(c):
            o_ref[:, lanes(c)] = mask(c, keys[c]).astype(jnp.int8)

    any_cut = jnp.max(cuts.astype(jnp.int32)) > 0

    @pl.when(jnp.logical_not(any_cut))
    def _all_ties():
        write(lambda c, key: key >= floor)

    @pl.when(any_cut)
    def _cut_ties():
        def position(c):
            return c * step + jax.lax.broadcasted_iota(
                jnp.int32, (rows, step), 1)

        need = k - count(lambda c, key: key > floor)

        def position_bit(i, prefix):
            cand = prefix | (jnp.int32(1) << (position_bits - 1 - i))
            n = count(lambda c, key: (key == floor)
                      & (position(c) <= cand))
            return jnp.where(n <= need, cand, prefix)

        # the largest position p with `ties at or below p <= need`
        # (need >= 1 in a row that cuts: position 0 is always taken)
        last = jax.lax.fori_loop(0, position_bits, position_bit, zero)
        last = jnp.where(cuts, last, jnp.int32((1 << 31) - 1))
        write(lambda c, key: (key > floor) | (
            (key == floor) & (position(c) <= last)))


# the rows kernel's tiles: query rows a tile (an int8 mask tile's 32
# sublanes), columns a counting step (its partial counts are 16 vregs)
# and steps a turn of a pass's loop. Alone on the chip at [1, 512, 43520],
# all columns live
# (`PERF.md` section 6, PR 46): 64 rows or 256 / 1,024 columns a step
# read 10-25% slower than 32 x 512; 1 / 2 / 4 steps a turn 0.86 / 0.75 /
# 0.70 ms
_SELECT_ROWS = 32
_SELECT_STEP = 512
_SELECT_UNROLL = 4


def _live_columns(shape, live):
    """`live` as `exact_topk_mask_rows` takes it -> int32 [B], one a
    flattened leading index, inside [0, N]."""
    *lead, _, N = shape
    live = jnp.clip(jnp.asarray(N if live is None else live, jnp.int32), 0, N)
    return jnp.broadcast_to(live, tuple(lead)).reshape(-1)


def selection_columns(shape, live=None):
    """(columns `exact_topk_mask_rows` scans for scores of `shape` under
    `live`, columns they hold), each summed over the query rows: int32
    scalars for a device counter. The kernel scans whole counting steps
    below `live`; XLA's loop (fewer rows than a tile) every column."""
    *_, S, N = shape
    live = _live_columns(shape, live)
    scanned = jnp.full_like(live, N)
    if S >= _SELECT_ROWS:
        step = _SELECT_STEP
        scanned = jnp.minimum((live + step - 1) // step * step, N)
    return (S * jnp.sum(scanned, dtype=jnp.int32),
            jnp.int32(S * N * live.shape[0]))


def exact_topk_mask_rows(scores, k: int, live=None, columns=None,
                         interpret: bool | None = None):
    """`exact_topk_mask(scores, k)` for MANY query rows over one long view
    (a prefill chunk), bit for bit, as one Pallas kernel
    (`ROWS_SELECT_NAME`). `scores`: float32 [..., S, N], or, with
    `columns`, the same scores in the BLOCKS of columns a blocked scorer
    made them in, [n, ..., S, block] (`models/keye.py`
    `view_index_score_blocks`: the kernel reads them where they lie, and
    no `[S, N]` array is laid out for it), of whose n x block columns the
    first `columns` are the view's. `live`: int32, a scalar or one a
    leading index `[...]`: only the first `live` columns may hold a
    visible key (default all; the caller's contract: the columns at or
    past it hold `-inf`), and the passes do not go past them.
    -> bool [..., S, N].

    A tile of `_SELECT_ROWS` query rows is read from HBM once, turned into
    order-preserving int32 keys in vector memory, and the 32 counting
    passes, the tie cut (only in a tile where some row must leave a tie
    out) and the mask run on that resident tile; XLA's loop streams the
    `[S, N]` key array from HBM in every pass unless the compiler happens
    to find room for it. Every tile is whole: a view that the counting
    step does not divide is padded with `-inf` first (a copy). Fewer rows
    than one tile (a decode step's one row a slot, a tiny chunk) keep
    XLA's loop: padding them would count 32 rows for one."""
    if k < 1:
        raise ValueError(f"a selection takes at least one key; got k = {k}")
    rows, step = _SELECT_ROWS, _SELECT_STEP
    blocked = columns is not None
    if blocked and (scores.shape[-2] < rows or scores.shape[-1] % step):
        # blocks the kernel cannot read where they lie: side by side
        scores = jnp.moveaxis(scores, 0, -2)
        scores = scores.reshape(scores.shape[:-2] + (-1,))[..., :columns]
        blocked = False
    *lead, S, N = scores.shape[1:] if blocked else scores.shape
    shape = (*lead, S, columns if blocked else N)
    if S < rows:
        return exact_topk_mask(scores, k)
    interpret = kernel_mode.resolve_interpret(ROWS_SELECT_NAME, interpret)
    return _select_rows(scores, _live_columns(shape, live), k=k,
                        columns=columns if blocked else None,
                        interpret=interpret)


@functools.partial(jax.jit, static_argnames=("k", "columns", "interpret"))
def _select_rows(scores, live, *, k: int, columns, interpret: bool):
    """`exact_topk_mask_rows` past its choices: scores of `_SELECT_ROWS` or
    more query rows, `[..., S, N]` or (`columns`) `[n, ..., S, block]` with
    blocks of whole counting steps; `live` int32 [B]. A program of its
    own: the layers of one model hand it the same shapes, so an engine
    program traces and lowers ONE kernel body and calls it a layer."""
    rows, step = _SELECT_ROWS, _SELECT_STEP
    blocked = columns is not None
    *lead, S, N = scores.shape[1:] if blocked else scores.shape
    B = math.prod(lead)
    Sp = -(-S // rows) * rows
    if blocked:
        n, block, N = scores.shape[0], N, columns
        x = scores.astype(jnp.float32).reshape(n, B, S, block)
        if Sp != S:
            x = jnp.pad(x, ((0, 0), (0, 0), (0, Sp - S), (0, 0)),
                        constant_values=-jnp.inf)
    else:
        n, block = 1, -(-N // step) * step
        x = scores.astype(jnp.float32).reshape(1, B, S, N)
        if (Sp, block) != (S, N):
            x = jnp.pad(x, ((0, 0), (0, 0), (0, Sp - S), (0, block - N)),
                        constant_values=-jnp.inf)
    # whole counting steps of the view's own columns
    Np = -(-N // step) * step
    held = -(-Np // step // _SELECT_UNROLL) * _SELECT_UNROLL
    vmem = rows * (2 * n * block * 4 + held * step * 4 + 2 * Np) \
        + 8 * rows * step * 4
    with jax.named_scope(ROWS_SELECT_NAME):
        mask = pl.pallas_call(
            functools.partial(_select_rows_kernel, k=k,
                              position_bits=max(1, (N - 1).bit_length())),
            out_shape=jax.ShapeDtypeStruct((B, Sp, Np), jnp.int8),
            grid_spec=pltpu.PrefetchScalarGridSpec(
                num_scalar_prefetch=1,
                grid=(B, Sp // rows),
                in_specs=[pl.BlockSpec((n, None, rows, block),
                                       lambda b, i, live: (0, b, i, 0))],
                out_specs=pl.BlockSpec((None, rows, Np),
                                       lambda b, i, live: (b, i, 0)),
                scratch_shapes=[pltpu.VMEM((held, rows, step),
                                           jnp.int32)]),
            compiler_params=pltpu.CompilerParams(
                dimension_semantics=("parallel", "parallel"),
                vmem_limit_bytes=min(vmem + (8 << 20), 100 << 20)),
            name=ROWS_SELECT_NAME,
            interpret=interpret,
        )(live, x)
        return (mask[:, :S, :N] != 0).reshape(*lead, S, N)


# ---------------------------------------------------------------------------
# sparse_paged_decode_attention
# ---------------------------------------------------------------------------


def _sparse_kernel(table_ref, count_ref, layer_ref, self_ref, q_ref, kn_ref,
                   vn_ref, bias_ref, k_hbm, v_hbm, o_ref, kbuf, vbuf, sem, *,
                   sm_scale: float, page_size: int, pages_per_slot: int,
                   pages_per_group: int, num_kv_heads: int):
    """Grid [slots]; `ops/paged_attention._live_pages_kernel` over a
    COMPACTED table. `table_ref` lists a slot's pages that hold a selected
    position first (`count_ref[s]` of them, in any order: a softmax does
    not know one), and the walk ends with them; `bias_ref` [1, groups,
    G * ps], in the same order, is 0 at a selected position and NEG_INF
    elsewhere (unselected, at or past the length, never written: whatever
    a copied page holds besides, it is a pool row and finite, and meets a
    probability of exactly 0); `self_ref[s]` says whether the new token
    selected itself."""
    s = pl.program_id(0)
    layer = layer_ref[0]
    G, ps, P = pages_per_group, page_size, pages_per_slot
    rows = G * ps
    n_groups = (count_ref[s] + G - 1) // G

    def start(g, slot):
        for j in range(G):
            # entries past the table's end re-read its last page: masked
            page = table_ref[s * P + jnp.minimum(g * G + j, P - 1)]
            pltpu.make_async_copy(k_hbm.at[layer, page], kbuf.at[slot, j],
                                  sem.at[0, slot]).start()
            pltpu.make_async_copy(v_hbm.at[layer, page], vbuf.at[slot, j],
                                  sem.at[1, slot]).start()

    @pl.when(n_groups > 0)
    def _first():
        start(0, 0)

    dot_dtype = jnp.promote_types(q_ref.dtype, kbuf.dtype)
    qs = [q_ref[0, h].astype(dot_dtype) for h in range(num_kv_heads)]

    def fold(state, s_blk, pv):
        m, l, acc = state
        m_new = jnp.maximum(m, jnp.max(s_blk, axis=-1, keepdims=True))
        p = jnp.where(s_blk <= NEG_INF / 2, 0.0, jnp.exp(s_blk - m_new))
        alpha = jnp.exp(m - m_new)
        return (m_new, l * alpha + jnp.sum(p, axis=-1, keepdims=True),
                acc * alpha + pv(p))

    def body(g, carry):
        slot = g % 2

        @pl.when(g + 1 < n_groups)
        def _next():
            start(g + 1, 1 - slot)

        # one wait a buffer: the semaphore counts bytes, and a group's
        # copies fill exactly this buffer
        for buf, which in ((kbuf, 0), (vbuf, 1)):
            pltpu.make_async_copy(buf.at[slot], buf.at[slot],
                                  sem.at[which, slot]).wait()
        bias = bias_ref[0, pl.ds(g, 1), :]                      # [1, rows]
        out = []
        for h in range(num_kv_heads):
            k = kbuf[slot, :, h].reshape(rows, -1).astype(dot_dtype)
            v = vbuf[slot, :, h].reshape(rows, -1).astype(jnp.float32)
            s_blk = jax.lax.dot_general(
                qs[h], k, (((1,), (1,)), ((), ())),
                preferred_element_type=jnp.float32)
            out.append(fold(carry[h], s_blk * sm_scale + bias,
                            lambda p, v=v: jnp.dot(
                                p, v, preferred_element_type=jnp.float32)))
        return tuple(out)

    Gp, D = q_ref.shape[2], q_ref.shape[3]
    carry = tuple((jnp.full((Gp, 1), NEG_INF, jnp.float32),
                   jnp.zeros((Gp, 1), jnp.float32),
                   jnp.zeros((Gp, D), jnp.float32))
                  for _ in range(num_kv_heads))
    carry = jax.lax.fori_loop(0, n_groups, body, carry)
    self_bias = jnp.where(self_ref[s] > 0, 0.0, NEG_INF)
    for h in range(num_kv_heads):
        q = qs[h].astype(jnp.float32)
        kn = kn_ref[0, h].astype(jnp.float32)
        vn = vn_ref[0, h].astype(jnp.float32)
        s_new = (jnp.sum(q * kn, axis=-1, keepdims=True) * sm_scale
                 + self_bias)
        _, l, acc = fold(carry[h], s_new, lambda p, vn=vn: p * vn)
        o_ref[0, h] = (acc / jnp.maximum(l, 1e-30)).astype(o_ref.dtype)


def _compact_selection(selected, meta: PagedDecodeMeta, page_size: int):
    """`selected` [S, R] bool over positions (the query's own position,
    `length`, included) -> (a slot's table with the pages that hold a
    selected cached position FIRST [S, P], the bits of each listed page's
    selected rows [S, P] int32 (0 from `count` on), how many pages hold
    one [S], whether the new token selected itself [S]). ONE small sort a
    call; the order among the selected pages is the sort's."""
    S, P = meta.table.shape
    pos = jnp.arange(selected.shape[1], dtype=jnp.int32)[None, :]
    cached = selected & (pos < meta.lengths[:, None])
    own = jnp.any(selected & (pos == meta.lengths[:, None]), axis=-1)
    lane = jnp.arange(page_size, dtype=jnp.int32)
    bits = jnp.sum(cached.reshape(S, P, page_size).astype(jnp.int32) << lane,
                   axis=-1)
    _, table, bits = jax.lax.sort(
        ((bits == 0).astype(jnp.int32), meta.table.astype(jnp.int32), bits),
        dimension=1, num_keys=1)
    return table, bits, jnp.sum(bits != 0, axis=-1, dtype=jnp.int32), own


def _compacted_walk(selected, meta: PagedDecodeMeta, page_size: int,
                    pages_per_group: int):
    """What a sparse kernel prefetches and adds to its scores:
    `_compact_selection`'s table, count and self flag, and the additive
    bias [S, groups, pages_per_group x page_size] in the table's order (0
    at a selected cached position, NEG_INF elsewhere)."""
    S, P = meta.table.shape
    ps, G = page_size, pages_per_group
    n_groups = -(-P // G)
    rows = G * ps
    table, bits, count, own = _compact_selection(selected, meta, ps)
    lane = jnp.arange(ps, dtype=jnp.int32)
    bias = jnp.where((bits[:, :, None] >> lane) & 1 == 1, 0.0,
                     NEG_INF).astype(jnp.float32).reshape(S, P * ps)
    bias = jnp.pad(bias, ((0, 0), (0, n_groups * rows - P * ps)),
                   constant_values=NEG_INF).reshape(S, n_groups, rows)
    return table, count, own, bias


def sparse_paged_decode_attention(q, k_new, v_new, pk: PagedKV, pv: PagedKV,
                                  meta: PagedDecodeMeta, selected,
                                  interpret: bool | None = None):
    """One decode step of attention over SELECTED keys for every slot, in
    the layer `pk.layer` of the stacked pool. q [S, 1, H, D]; k_new / v_new
    [S, 1, Hkv, D], this step's K/V at position `length`; `selected` [S, R]
    bool over a slot's positions (R = pages_per_slot x page_size), the
    query's own position included: a cached position counts iff it is
    below the slot's length. Returns (out [S, 1, H, D], (k_row, v_row)) as
    `paged_decode_attention` does."""
    S, sq, H, D = q.shape
    Hkv = k_new.shape[2]
    if sq != 1 or H % Hkv or pk.data.ndim != 5 or pk.layer is None \
            or pk.quantized or D % 128 or pk.data.shape[3] > 31:
        raise ValueError(
            "sparse paged decode attention is one token a slot over the "
            "whole stacked bf16 or float pool [L, pages + 1, Hkv, page_size, "
            f"D] of 128-lane heads and a layer index; got q {q.shape}, pool "
            f"{pk.data.shape}, layer {pk.layer!r}, int8 {pk.quantized}")
    interpret = kernel_mode.resolve_interpret(ATTENTION_KERNEL_NAME,
                                              interpret)
    ps = pk.data.shape[3]
    P = meta.table.shape[1]
    G = _pages_per_group(P, pk.data.shape[2:], pk.data.dtype)
    n_groups = -(-P // G)
    rows = G * ps
    # the pages that hold a selected position first: the kernel's walk
    # ends where they end
    table, count, own, bias = _compacted_walk(selected, meta, ps, G)
    group = H // Hkv
    row_dtype = pk.row_dtype
    k_row, v_row = k_new.astype(row_dtype), v_new.astype(row_dtype)
    q4 = q[:, 0].reshape(S, Hkv, group, D)
    Gp = -(-group // _SUBLANES) * _SUBLANES
    if Gp != group:
        q4 = jnp.pad(q4, ((0, 0), (0, 0), (0, Gp - group), (0, 0)))
    kn, vn = k_row[:, 0, :, None, :], v_row[:, 0, :, None, :]
    kernel = functools.partial(
        _sparse_kernel, sm_scale=1.0 / math.sqrt(D), page_size=ps,
        pages_per_slot=P, pages_per_group=G, num_kv_heads=Hkv)
    per_slot = lambda s, *_: (s, 0, 0, 0)  # noqa: E731
    out = pl.pallas_call(
        kernel,
        out_shape=jax.ShapeDtypeStruct((S, Hkv, Gp, D), q4.dtype),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=4,
            grid=(S,),
            in_specs=[pl.BlockSpec((1, Hkv, Gp, D), per_slot),
                      pl.BlockSpec((1, Hkv, 1, D), per_slot),
                      pl.BlockSpec((1, Hkv, 1, D), per_slot),
                      pl.BlockSpec((1, n_groups, rows),
                                   lambda s, *_: (s, 0, 0)),
                      pl.BlockSpec(memory_space=pl.ANY),
                      pl.BlockSpec(memory_space=pl.ANY)],
            out_specs=pl.BlockSpec((1, Hkv, Gp, D), per_slot),
            scratch_shapes=[pltpu.VMEM((2, G, Hkv, ps, D), pk.data.dtype),
                            pltpu.VMEM((2, G, Hkv, ps, D), pv.data.dtype),
                            pltpu.SemaphoreType.DMA((2, 2))]),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary",)),
        name=ATTENTION_KERNEL_NAME,
        interpret=pltpu.InterpretParams() if interpret else False,
    )(table.reshape(-1), count,
      jnp.asarray(pk.layer, jnp.int32).reshape(1), own.astype(jnp.int32),
      q4, kn, vn, bias, pk.data, pv.data)
    return out[:, :, :group].reshape(S, 1, H, D), (k_row, v_row)


def sparse_paged_decode_reference(q, k_new, v_new, pk: PagedKV, pv: PagedKV,
                                  meta: PagedDecodeMeta, selected):
    """The same by a plain gather of every table page: the new token's row
    overlaid at position == length, a float32 softmax over the selected
    positions alone."""
    S, _, H, D = q.shape
    Hkv = k_new.shape[2]
    ps = pk.data.shape[3]
    R = meta.table.shape[1] * ps
    k_row, v_row = k_new.astype(pk.row_dtype), v_new.astype(pk.row_dtype)

    def dense(p: PagedKV):
        pages = p.data[p.layer][meta.table].astype(jnp.float32)
        return jnp.swapaxes(pages, 2, 3).reshape(S, R, Hkv, D)

    pos = jnp.arange(R, dtype=jnp.int32)[None, :]
    at_self = (pos == meta.lengths[:, None])[:, :, None, None]
    k_all = jnp.where(at_self, k_row.astype(jnp.float32), dense(pk))
    v_all = jnp.where(at_self, v_row.astype(jnp.float32), dense(pv))
    keep = selected & (pos <= meta.lengths[:, None])
    q4 = q[:, 0].reshape(S, Hkv, H // Hkv, D).astype(jnp.float32)
    s = jnp.einsum("shgd,srhd->shgr", q4, k_all) / math.sqrt(D)
    s = jnp.where(keep[:, None, None, :], s, NEG_INF)
    out = jnp.einsum("shgr,srhd->shgd", jax.nn.softmax(s, axis=-1), v_all)
    return out.reshape(S, 1, H, D).astype(q.dtype), (k_row, v_row)


# ---------------------------------------------------------------------------
# sparse_latent_paged_decode_attention
# ---------------------------------------------------------------------------


def _sparse_latent_kernel(table_ref, count_ref, layer_ref, self_ref, q_ref,
                          new_ref, bias_ref, pool_hbm, o_ref, buf, sem, *,
                          sm_scale: float, page_size: int,
                          pages_per_slot: int, pages_per_group: int,
                          value_width: int):
    """Grid [slots]; `_sparse_kernel`'s walk of a COMPACTED table over a
    latent pool `pool_hbm` [L, N + 1, ps, W]: q_ref [1, H, W] absorbed
    queries, new_ref [1, 1, W] the new token's row, `bias_ref` [1, groups,
    G * ps] 0 at a selected position and NEG_INF elsewhere, in the table's
    order. A copied row serves every head, as key (all W lanes) and as
    value (its first `value_width`)."""
    s = pl.program_id(0)
    layer = layer_ref[0]
    G, ps, P = pages_per_group, page_size, pages_per_slot
    n_groups = (count_ref[s] + G - 1) // G

    def start(g, slot):
        for j in range(G):
            # entries past the table's end re-read its last page: masked
            page = table_ref[s * P + jnp.minimum(g * G + j, P - 1)]
            pltpu.make_async_copy(pool_hbm.at[layer, page],
                                  buf.at[slot, pl.ds(j * ps, ps)],
                                  sem.at[slot]).start()

    @pl.when(n_groups > 0)
    def _first():
        start(0, 0)

    q = q_ref[0]                                            # [H, W]
    H = q.shape[0]

    def fold(carry, s_blk, pv):
        m, l, acc = carry
        m_new = jnp.maximum(m, jnp.max(s_blk, axis=-1, keepdims=True))
        p = jnp.where(s_blk <= NEG_INF / 2, 0.0, jnp.exp(s_blk - m_new))
        alpha = jnp.exp(m - m_new)
        return (m_new, l * alpha + jnp.sum(p, axis=-1, keepdims=True),
                acc * alpha + pv(p))

    def body(g, carry):
        slot = g % 2

        @pl.when(g + 1 < n_groups)
        def _next():
            start(g + 1, 1 - slot)

        # one wait a buffer: the semaphore counts bytes, and a group's
        # copies fill exactly this buffer
        pltpu.make_async_copy(buf.at[slot], buf.at[slot],
                              sem.at[slot]).wait()
        kv = buf[slot]                                      # [G * ps, W]
        s_blk = jax.lax.dot_general(
            q, kv, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32) * sm_scale
        s_blk = s_blk + bias_ref[0, pl.ds(g, 1), :]
        return fold(carry, s_blk, lambda p: jnp.dot(
            p.astype(kv.dtype), kv[:, :value_width],
            preferred_element_type=jnp.float32))

    carry = (jnp.full((H, 1), NEG_INF, jnp.float32),
             jnp.zeros((H, 1), jnp.float32),
             jnp.zeros((H, value_width), jnp.float32))
    carry = jax.lax.fori_loop(0, n_groups, body, carry)
    # the new token's own row, iff the token selected itself; on the VPU
    new = new_ref[0].astype(jnp.float32)                    # [1, W]
    s_new = (jnp.sum(q.astype(jnp.float32) * new, axis=-1, keepdims=True)
             * sm_scale + jnp.where(self_ref[s] > 0, 0.0, NEG_INF))
    _, l, acc = fold(carry, s_new, lambda p: p * new[:, :value_width])
    o_ref[0] = (acc / jnp.maximum(l, 1e-30)).astype(o_ref.dtype)


def sparse_latent_paged_decode_attention(q, new_row, pool, layer,
                                         meta: PagedDecodeMeta, selected, *,
                                         value_width: int, sm_scale: float,
                                         interpret: bool | None = None):
    """One decode step of absorbed latent attention over SELECTED rows for
    every slot. q [S, H, W]: each head's absorbed query, laid out like a
    pool row; new_row [S, W]: this step's latent row (in the pool's dtype;
    what the engine appends afterwards); pool [L, pages + 1, page_size,
    W], the whole stacked latent pool; layer: int32 scalar; `selected`
    [S, R] bool over a slot's positions (R = pages_per_slot x page_size),
    the query's own position included: a cached position counts iff it is
    below the slot's length. Returns o_lat [S, H, value_width] in q's
    dtype, as `latent_paged_decode_attention` does."""
    S, H, W = q.shape
    L, _, ps, Wp = pool.shape
    if Wp != W or new_row.shape != (S, W) or W % 128 or value_width % 128 \
            or value_width > W or ps > 31:
        raise ValueError(
            "sparse latent decode attention is one absorbed query a head "
            "and slot over the whole stacked latent pool [L, pages + 1, "
            "page_size, W] of whole 128-lane tiles; got q "
            f"{q.shape}, new_row {new_row.shape}, pool {pool.shape}, "
            f"value_width {value_width}")
    interpret = kernel_mode.resolve_interpret(LATENT_ATTENTION_KERNEL_NAME,
                                              interpret)
    P = meta.table.shape[1]
    G = max(1, min(LATENT_PAGES_PER_GROUP, P))
    n_groups = -(-P // G)
    rows = G * ps
    table, count, own, bias = _compacted_walk(selected, meta, ps, G)
    kernel = functools.partial(
        _sparse_latent_kernel, sm_scale=float(sm_scale), page_size=ps,
        pages_per_slot=P, pages_per_group=G, value_width=value_width)
    per_slot = lambda s, *_: (s, 0, 0)  # noqa: E731
    return pl.pallas_call(
        kernel,
        out_shape=jax.ShapeDtypeStruct((S, H, value_width), q.dtype),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=4,
            grid=(S,),
            in_specs=[pl.BlockSpec((1, H, W), per_slot),
                      pl.BlockSpec((1, 1, W), per_slot),
                      pl.BlockSpec((1, n_groups, rows), per_slot),
                      pl.BlockSpec(memory_space=pl.ANY)],
            out_specs=pl.BlockSpec((1, H, value_width), per_slot),
            scratch_shapes=[pltpu.VMEM((2, rows, W), pool.dtype),
                            pltpu.SemaphoreType.DMA((2,))]),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary",)),
        name=LATENT_ATTENTION_KERNEL_NAME,
        interpret=pltpu.InterpretParams() if interpret else False,
    )(table.reshape(-1), count, jnp.asarray(layer, jnp.int32).reshape(1),
      own.astype(jnp.int32), q.astype(pool.dtype), new_row[:, None, :], bias,
      pool)


def sparse_latent_paged_decode_reference(q, new_row, pool, layer,
                                         meta: PagedDecodeMeta, selected, *,
                                         value_width: int, sm_scale: float):
    """The same by a plain gather of every table page: the new token's row
    as one more key at position == length, a float32 softmax over the
    selected positions alone."""
    S, H, W = q.shape
    R = meta.table.shape[1] * pool.shape[2]
    rows = pool[layer][meta.table].reshape(S, R, W).astype(jnp.float32)
    pos = jnp.arange(R, dtype=jnp.int32)[None, :]
    at_self = pos == meta.lengths[:, None]
    rows = jnp.where(at_self[:, :, None],
                     new_row.astype(jnp.float32)[:, None, :], rows)
    keep = selected & (pos <= meta.lengths[:, None])
    s = jnp.einsum("shw,srw->shr", q.astype(pool.dtype).astype(jnp.float32),
                   rows) * sm_scale
    s = jnp.where(keep[:, None, :], s, NEG_INF)
    p = jax.nn.softmax(s, axis=-1)
    return jnp.einsum("shr,srw->shw", p,
                      rows[:, :, :value_width]).astype(q.dtype)
