"""A chunk of queries over a view of LATENT rows (multi-head latent
attention, EXPANDED form) in one Pallas kernel: a block of rows is
decompressed to K and V, scored, masked and folded into an online softmax
without its scores, its K or its V ever leaving vector memory.

What a latent cache holds for a token in a layer is one row `[c_kv | k_pe
| 0]` (`ops/latent_paged_attention.py`). A decode step reads it in the
absorbed form; a prefill chunk has hundreds of queries a key, and there
the expanded form is cheaper: `[k_nope_h | v_h] = c_kv W_kvb_h` a head,
`k_h = [k_nope_h | k_pe]` (the rope key ONE head shared by all), a causal
softmax of `q_h k_h^T / sqrt(nope + rope)` times `v_h`. Written in
`jax.numpy` (`latent_chunk_attention_reference`, the loop the models ran
until PR 44) XLA makes three fusions of a block with the float32 scores
`[H, S, block]` in HBM between them: written once, read twice.

The kernel. One grid step is a GROUP of heads over a TILE of rows, the
row tiles innermost: the group's queries, its slice of `W_kvb` and its
output block stay where they are while the view's rows stream past, and
`m`, `l`, `acc` live in scratch across the row tiles. In a step, once:
the masks (position, window, selection) as an additive float32 bias `[S,
tile]`, and the rows' rope lanes into the key scratch; then a head at a
time: `k_nope_h` and `v_h` from the tile's `c_kv` (two products, rounded
to the queries' dtype as the reference rounds them), the scores against
`[k_nope_h | k_pe]`, the online softmax in float32, `p` rounded to the
queries' dtype before the value product. `acc / max(l, 1e-30)` is written
when the last tile has passed. The view's rows are read once a head group.

Work follows the LIVE rows, not the view's capacity: `live = (first,
end)` row bounds (traced scalars, scalar prefetch) are turned into the
range of tiles that may hold a visible key; a tile outside it is neither
computed nor copied (its block index is clamped to the range's edge, and
a block whose index does not change is not fetched again). Every tile is
a whole one: `_row_tile` takes rows that DIVIDE the view (1,280 of
dots3's 43,520, 1,024 of joyai's 18,432), and a view nothing divides (a
ring of 1,056 rows) is padded to whole tiles first, a copy of a short
view.

Same mathematics as the reference, to the order of the float32 sums: the
queries' dtype for every product's operands, float32 accumulation, K, V
and `p` rounded where the reference rounds them, masked scores exactly
`-1e30` (a finite score plus `-1e30` IS `-1e30` in float32), a masked `p`
exactly 0 (the exponent's maximum is floored at `-1e29`, which no real
score reaches and under which `exp(-1e30 - m)` is 0 whether or not the
query has seen a key yet), `l` floored at `1e-30`: a query that sees
nothing reads 0.

Tile sizes (`_tiles`) follow from the shapes and the vector memory they
take; nothing sets them from outside. On a backend that is no TPU the
kernel runs through the Pallas interpreter (`ops/kernel_mode.py`).
"""

from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from . import kernel_mode

NEG_INF = -1e30
KERNEL_NAME = "latent_chunk_attention"
_LANES = 128
_SUBLANES = 16      # a bf16 tile's; float32's 8 divides it
# what one call's blocks, scratch and temporaries may take of a core's
# vector memory (128 MiB on a v5e; XLA keeps the call's operands there
# too), and the most rows a tile holds
_VMEM_BUDGET = 40 << 20
_MAX_TILE = 1280

__all__ = ["latent_chunk_attention", "latent_chunk_attention_reference"]


def _round_up(n: int, m: int) -> int:
    return -(-n // m) * m


def _widths(q_nope, q_pe, w_kvb):
    rank, H, both = w_kvb.shape
    nope, rope = q_nope.shape[-1], q_pe.shape[-1]
    return rank, H, nope, rope, both - nope


def latent_chunk_attention_reference(q_nope, q_pe, q_pos, rows, key_pos,
                                     w_kvb, *, select=None, window=None,
                                     live=None, block: int = 1024):
    """`latent_chunk_attention` in `jax.numpy`: a `fori_loop` over blocks
    of `block` rows, each decompressed through `w_kvb` where it is
    attended; the blocks that hold a row of `live` are visited, the others
    not read. The `[H, S, R]` scores never exist whole, a block's do."""
    rank, H, nope, rope, v = _widths(q_nope, q_pe, w_kvb)
    B, S = q_pos.shape
    R = rows.shape[1]
    dtype = q_nope.dtype
    blk = min(block, R)
    if R % blk:
        pad = blk - R % blk
        rows = jnp.pad(rows, ((0, 0), (0, pad), (0, 0)))
        key_pos = jnp.pad(key_pos, ((0, 0), (0, pad)), constant_values=-1)
        if select is not None:
            select = jnp.pad(select, ((0, 0), (0, 0), (0, pad)))
    n_blocks = rows.shape[1] // blk
    first, end = (0, R) if live is None else live
    q = jnp.concatenate([q_nope, q_pe], axis=-1)
    w_kvb = w_kvb.astype(dtype)
    scale = 1.0 / math.sqrt(nope + rope)
    at = q_pos[:, None, :, None]

    def body(i, carry):
        m, l, acc = carry
        rb = jax.lax.dynamic_slice_in_dim(rows, i * blk, blk, axis=1)
        rb = rb.astype(dtype)
        kv = jnp.einsum("brc,chd->brhd", rb[..., :rank], w_kvb,
                        preferred_element_type=jnp.float32).astype(dtype)
        k_pe = jnp.broadcast_to(rb[:, :, None, rank:rank + rope],
                                (B, blk, H, rope))
        kb = jnp.concatenate([kv[..., :nope], k_pe], axis=-1)
        pb = jax.lax.dynamic_slice_in_dim(
            key_pos, i * blk, blk, axis=1)[:, None, None, :]
        s = jnp.einsum("bshd,brhd->bhsr", q, kb,
                       preferred_element_type=jnp.float32) * scale
        see = (pb >= 0) & (pb <= at)
        if window is not None:
            see = see & (at - pb < window)
        if select is not None:
            see = see & jax.lax.dynamic_slice_in_dim(
                select, i * blk, blk, axis=2)[:, None]
        s = jnp.where(see, s, NEG_INF)
        m_new = jnp.maximum(m, jnp.max(s, axis=-1, keepdims=True))
        p = jnp.where(see, jnp.exp(s - m_new), 0.0)
        alpha = jnp.exp(m - m_new)
        pv = jnp.einsum("bhsr,brhd->bhsd", p.astype(dtype), kv[..., nope:],
                        preferred_element_type=jnp.float32)
        return (m_new, l * alpha + jnp.sum(p, axis=-1, keepdims=True),
                acc * alpha + pv)

    carry = (jnp.full((B, H, S, 1), NEG_INF, jnp.float32),
             jnp.zeros((B, H, S, 1), jnp.float32),
             jnp.zeros((B, H, S, v), jnp.float32))
    lo = jnp.asarray(first, jnp.int32) // blk
    hi = jnp.minimum(-(-jnp.asarray(end, jnp.int32) // blk), n_blocks)
    _, l, acc = jax.lax.fori_loop(lo, hi, body, carry)
    out = acc / jnp.maximum(l, 1e-30)                      # [B, H, S, v]
    return jnp.swapaxes(out, 1, 2).astype(dtype)


def _row_tile(R: int) -> int:
    """Rows a tile, in whole lane tiles, such that no tile hangs over the
    view's end: the largest power of two up to `_MAX_TILE` if it divides
    the view (a tile of 1,024 rows read 3-6% faster a row than one of
    1,152 or 1,280), else the most rows up to `_MAX_TILE` that do; a view
    nothing near that size divides is split into equal tiles and padded
    to them by its caller (a copy; none of a cell's long views)."""
    top = _MAX_TILE // _LANES * _LANES
    best = 1 << (top.bit_length() - 1)
    for tile in (best, *range(top, top // 2, -_LANES)):
        if R % tile == 0:
            return tile
    return _round_up(-(-R // -(-R // top)), _LANES)


def _tiles(S: int, R: int, H: int, rank: int, key_width: int, v: int,
           row_width: int, itemsize: int, selected: bool):
    """(heads a group, rows a tile, the bytes of vector memory they take):
    `_row_tile`'s rows, and the most heads (a divisor of H) whose blocks
    fit `_VMEM_BUDGET` beside them."""
    tile = _row_tile(R)
    lanes = lambda n: _round_up(n, _LANES)  # noqa: E731
    step = (2 * tile * lanes(row_width) * itemsize      # the rows, 2 buffers
            + (2 * S * tile if selected else 0)         # the selection, int8
            + S * tile * 4                              # the bias
            + tile * key_width * itemsize               # the key scratch
            + 3 * S * tile * 4                          # s, p and between
            + 2 * tile * lanes(key_width + v) * 4)      # a head's K, V, f32
    head = (2 * S * key_width * itemsize                # q, 2 buffers
            + 2 * S * lanes(v) * itemsize               # out, 2 buffers
            + S * lanes(v) * 4 + 2 * S * _LANES * 4     # acc, m, l
            + 2 * rank * lanes(key_width + v) * itemsize)   # W_kvb's slice
    heads = 1
    for n in range(1, H + 1):
        if H % n == 0 and step + n * head <= _VMEM_BUDGET:
            heads = n
    return heads, tile, step + heads * head


def _kernel(bounds_ref, q_ref, qpos_ref, kpos_ref, rows_ref, wuk_ref, wuv_ref,
            *rest, heads: int, rank: int, nope: int, sm_scale: float, window,
            selected: bool):
    """One grid step: a group of `heads` heads over one tile of rows.
    `q_ref` [heads, S, Dk] (`[q_nope | q_pe | 0]`), `qpos_ref` [S, 1],
    `kpos_ref` [1, tile], `rows_ref` [tile, W], `wuk_ref` [heads, rank,
    nope], `wuv_ref` [heads, rank, v], `sel_ref` [S, tile] int8 with a
    selection; `o_ref` [heads, S, v]; scratch `m`, `l` [heads, S, 1] and
    `acc` [heads, S, v] float32, `key` [tile, Dk], `bias` [S, tile]."""
    if selected:
        sel_ref, o_ref, m_scr, l_scr, acc_scr, key_scr, bias_scr = rest
    else:
        o_ref, m_scr, l_scr, acc_scr, key_scr, bias_scr = rest
    j = pl.program_id(2)
    dtype = q_ref.dtype
    Dk = key_scr.shape[1]

    @pl.when(j == 0)
    def _first():
        m_scr[...] = jnp.full_like(m_scr, NEG_INF)
        l_scr[...] = jnp.zeros_like(l_scr)
        acc_scr[...] = jnp.zeros_like(acc_scr)

    @pl.when((j >= bounds_ref[0]) & (j < bounds_ref[1]))
    def _live():
        kp, qp = kpos_ref[...], qpos_ref[...]
        see = (kp >= 0) & (kp <= qp)
        if window is not None:
            see = see & (qp - kp < window)
        if selected:
            see = see & (sel_ref[...].astype(jnp.int32) != 0)
        bias_scr[...] = jnp.where(see, 0.0, NEG_INF)
        rows = rows_ref[...].astype(dtype)
        c_kv = rows[:, :rank]
        # [k_pe | 0] under every head's k_nope: the queries' lanes past
        # nope + rope are 0
        key_scr[:, nope:] = rows[:, rank:rank + Dk - nope]

        def head(h, carry):
            key_scr[:, :nope] = jnp.dot(
                c_kv, wuk_ref[h], preferred_element_type=jnp.float32
            ).astype(dtype)
            v_h = jnp.dot(c_kv, wuv_ref[h],
                          preferred_element_type=jnp.float32).astype(dtype)
            s = jax.lax.dot_general(
                q_ref[h], key_scr[...], (((1,), (1,)), ((), ())),
                preferred_element_type=jnp.float32) * sm_scale + bias_scr[...]
            m = m_scr[h]
            m_new = jnp.maximum(m, jnp.max(s, axis=-1, keepdims=True))
            p = jnp.exp(s - jnp.maximum(m_new, 0.1 * NEG_INF))
            alpha = jnp.exp(m - m_new)
            l_scr[h] = l_scr[h] * alpha + jnp.sum(p, axis=-1, keepdims=True)
            acc_scr[h] = acc_scr[h] * alpha + jnp.dot(
                p.astype(dtype), v_h, preferred_element_type=jnp.float32)
            m_scr[h] = m_new
            return carry

        jax.lax.fori_loop(0, heads, head, 0)

    @pl.when(j == pl.num_programs(2) - 1)
    def _last():
        o_ref[...] = (acc_scr[...] / jnp.maximum(l_scr[...], 1e-30)
                      ).astype(o_ref.dtype)


def latent_chunk_attention(q_nope, q_pe, q_pos, rows, key_pos, w_kvb, *,
                           select=None, window=None, live=None,
                           interpret: bool | None = None):
    """Causal attention of a chunk of queries over a view of latent rows,
    K and V decompressed a tile of rows at a time in vector memory.

    q_nope [B, S, H, nope], q_pe [B, S, H, rope] (rotated) at positions
    `q_pos` [B, S]; `rows` [B, R, W] = `[c_kv (rank) | k_pe (rope) | 0]`,
    the row at index r at position `key_pos[b, r]` (negative: nothing
    there); `w_kvb` [rank, H, nope + v]. A query sees the rows at `0 <=
    position <= its own`, with `window` those at `its own - position <
    window`, with `select` [B, S, R] bool those it selected. `live`:
    `(first, end)` int32 scalars, rows outside `[first, end)` hold no
    visible key (default: the whole view); the tiles outside are neither
    read nor computed. What lies in a tile that IS read must be finite.
    -> [B, S, H, v] in the queries' dtype; a query that sees nothing
    reads 0."""
    rank, H, nope, rope, v = _widths(q_nope, q_pe, w_kvb)
    B, S = q_pos.shape
    R, W = rows.shape[1:]
    dtype = q_nope.dtype
    Dk = _round_up(nope + rope, _LANES)
    if W - rank < Dk - nope:
        raise ValueError(
            f"a latent row is [c_kv | k_pe | 0] in whole {_LANES}-lane "
            f"tiles: {W} lanes do not hold {rank} + {Dk - nope}")
    interpret = kernel_mode.resolve_interpret(KERNEL_NAME, interpret)
    selected = select is not None
    n_queries = S
    if S % _SUBLANES:
        # whole sublane tiles of queries; the ones added are nowhere
        pad = _SUBLANES - S % _SUBLANES
        q_nope, q_pe = (jnp.pad(a, ((0, 0), (0, pad), (0, 0), (0, 0)))
                        for a in (q_nope, q_pe))
        q_pos = jnp.pad(q_pos, ((0, 0), (0, pad)), constant_values=-1)
        if selected:
            select = jnp.pad(select, ((0, 0), (0, pad), (0, 0)))
        S += pad
    heads, tile, vmem = _tiles(S, R, H, rank, Dk, v, W, dtype.itemsize,
                               selected)
    n_tiles = -(-R // tile)
    first, end = (0, R) if live is None else live
    lo = jnp.asarray(first, jnp.int32) // tile
    hi = jnp.minimum(-(-jnp.asarray(end, jnp.int32) // tile), n_tiles)
    bounds = jnp.stack([lo, jnp.maximum(hi, lo + 1)])
    # [q_nope | q_pe | 0] a head, heads outermost; W_kvb a head
    q = jnp.concatenate(
        [q_nope, q_pe, jnp.zeros((B, S, H, Dk - nope - rope), dtype)], axis=-1)
    q = jnp.swapaxes(q, 1, 2)                              # [B, H, S, Dk]
    w = jnp.swapaxes(w_kvb.astype(dtype), 0, 1)            # [H, rank, *]
    key_pos = key_pos.astype(jnp.int32)
    if R % tile:
        # whole tiles: the rows added are nowhere, and zero (0 x NaN is
        # NaN in the value product)
        pad = n_tiles * tile - R
        rows = jnp.pad(rows, ((0, 0), (0, pad), (0, 0)))
        key_pos = jnp.pad(key_pos, ((0, 0), (0, pad)), constant_values=-1)
        if selected:
            select = jnp.pad(select, ((0, 0), (0, 0), (0, pad)))

    def at(j, bounds):
        return jnp.clip(j, bounds[0], bounds[1] - 1)

    per_group = lambda b, g, j, bounds: (b, g, 0, 0)  # noqa: E731
    weights = lambda b, g, j, bounds: (g, 0, 0)  # noqa: E731
    in_specs = [
        pl.BlockSpec((None, heads, S, Dk), per_group),
        pl.BlockSpec((None, S, 1), lambda b, g, j, bounds: (b, 0, 0)),
        pl.BlockSpec((None, 1, tile),
                     lambda b, g, j, bounds: (b, 0, at(j, bounds))),
        pl.BlockSpec((None, tile, W),
                     lambda b, g, j, bounds: (b, at(j, bounds), 0)),
        pl.BlockSpec((heads, rank, nope), weights),
        pl.BlockSpec((heads, rank, v), weights),
    ]
    operands = [q, q_pos.astype(jnp.int32)[:, :, None], key_pos[:, None, :],
                rows, w[..., :nope], w[..., nope:]]
    if selected:
        in_specs.append(pl.BlockSpec(
            (None, S, tile), lambda b, g, j, bounds: (b, 0, at(j, bounds))))
        operands.append(select.astype(jnp.int8))
    out = pl.pallas_call(
        functools.partial(
            _kernel, heads=heads, rank=rank, nope=nope,
            sm_scale=1.0 / math.sqrt(nope + rope), window=window,
            selected=selected),
        out_shape=jax.ShapeDtypeStruct((B, H, S, v), dtype),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1,
            grid=(B, H // heads, n_tiles),
            in_specs=in_specs,
            out_specs=pl.BlockSpec((None, heads, S, v), per_group),
            scratch_shapes=[pltpu.VMEM((heads, S, 1), jnp.float32),
                            pltpu.VMEM((heads, S, 1), jnp.float32),
                            pltpu.VMEM((heads, S, v), jnp.float32),
                            pltpu.VMEM((tile, Dk), dtype),
                            pltpu.VMEM((S, tile), jnp.float32)]),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary"),
            vmem_limit_bytes=min(vmem + (16 << 20), 100 << 20)),
        name=KERNEL_NAME,
        interpret=interpret,
    )(bounds, *operands)
    return jnp.swapaxes(out, 1, 2)[:, :n_queries]
