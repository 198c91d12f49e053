"""A dropless expert layer: route, sort the assignments by expert, grouped
matrix products over the sorted rows, weighted sum back per token.

`parallel/moe.py` dispatches into fixed-capacity buffers (tokens over
capacity are dropped) and `models/mixtral.py`'s "dense" form runs every
expert on every token. Neither serves a model with hundreds of small
experts: here no token is dropped at any imbalance, the products cost
`tokens x top_k` expert applications (not `tokens x num_experts`), and an
expert's weights are read once a call.

Shapes are static whatever the routing: `tokens * top_k` rows, sorted by
expert, with the group sizes as data. The grouped product over those
rows has two kernels, chosen from the static shapes by ONE rule
(`few_rows_an_expert`):

- MANY rows an expert (a prefill chunk: 4,096 rows over 64 or 256
  experts): `jax.lax.ragged_dot`, which the TPU compiler lowers to its
  own grouped-matmul kernel (`ragged-dot-none` in a device trace) and
  every other backend to a plain masked product. That kernel multiplies
  a large row tile for every group it visits, which is the right shape
  of work when an expert has tens of rows or more.
- FEW rows an expert (a decode step: 384 rows over 64 experts, 128 over
  256), on matrices of whole 128-lane tiles: the rows kernel below
  (`ragged-dot-rows`), a Pallas kernel that walks only the experts that
  HAVE rows, streams each one's matrix through VMEM once, double-
  buffered, as it is held in HBM (no copy, no concatenated array), and
  multiplies a window of `row_tile` rows. XLA's kernel cannot be told
  its tile and spends a decode step's time on masked rows (`PERF.md`
  section 6, PR 32); the two get their own code, the sort, the route
  and the combine around them stay shared.

A layer that holds a SHARE of its experts (`experts_held`) and has many
rows an expert keeps few of its sorted rows (the others belong to absent
experts): those go through the rows kernel too, a window of them a pass,
an expert's matrix in blocks of lanes (`held_rows_in_windows`,
`_held_swiglu_in_windows`).
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from . import kernel_mode
from ..models.common import part

__all__ = ["sigmoid_topk_route", "softmax_topk_route", "expert_counts",
           "grouped_swiglu_experts", "grouped_rows_matmul",
           "few_rows_an_expert", "held_rows_in_windows", "ROWS_KERNEL_NAME"]


def sigmoid_topk_route(x, router_kernel, correction_bias, top_k: int,
                       scaling_factor: float = 1.0, norm_topk: bool = True):
    """`noaux_tc` routing with one group: scores `s = sigmoid(x W_r)` in
    float32; the experts are the `top_k` of `s + correction_bias`; their
    weights are `s` at those experts WITHOUT the bias, divided by their
    sum (`norm_topk`), times `scaling_factor`. x [T, h], router_kernel
    [h, E], correction_bias [E] -> (experts [T, k] int32, weights [T, k]
    float32). Operands that are bfloat16 values multiply exactly into the
    float32 accumulator, so this is the float32 product of the published
    router on such inputs."""
    with part("moe.route"):
        scores = jax.nn.sigmoid(jnp.dot(
            x, router_kernel.astype(x.dtype),
            preferred_element_type=jnp.float32))
        _, experts = jax.lax.top_k(
            scores + correction_bias.astype(jnp.float32), top_k)
        weights = jnp.take_along_axis(scores, experts, axis=-1)
        if norm_topk:
            weights = weights / (jnp.sum(weights, axis=-1, keepdims=True)
                                 + 1e-20)
        return experts.astype(jnp.int32), weights * scaling_factor


def softmax_topk_route(x, router_kernel, top_k: int, norm_topk: bool = True):
    """Softmax routing: `p = softmax(x W_r)` over ALL experts in float32;
    the experts are the `top_k` of `p` and their weights `p` at those
    experts, divided by their sum where `norm_topk`. No bias in the
    choice and no scaling factor. x [T, h], router_kernel [h, E] ->
    (experts [T, k] int32, weights [T, k] float32)."""
    with part("moe.route"):
        probs = jax.nn.softmax(jnp.dot(
            x, router_kernel.astype(x.dtype),
            preferred_element_type=jnp.float32), axis=-1)
        weights, experts = jax.lax.top_k(probs, top_k)
        if norm_topk:
            weights = weights / jnp.sum(weights, axis=-1, keepdims=True)
        return experts.astype(jnp.int32), weights


def expert_counts(experts, num_experts: int, token_mask=None):
    """Assignments per expert [E] int32 of `experts` [T, k]; tokens whose
    `token_mask` [T] is False (padding, dead lanes) are not counted."""
    hit = experts[:, :, None] == jnp.arange(num_experts, dtype=jnp.int32)
    if token_mask is not None:
        hit = hit & token_mask[:, None, None]
    return jnp.sum(hit, axis=(0, 1), dtype=jnp.int32)


# ---------------------------------------------------------------------------
# the grouped product for FEW rows an expert
# ---------------------------------------------------------------------------

ROWS_KERNEL_NAME = "ragged-dot-rows"
_ROW_ALIGN = 16   # a bf16 sublane tile: where a window of rows may start
_LANES = 128
# Rows a product: 16, 32 and 64 read the same on the chip (`PERF.md`
# section 6, PR 32); at 32 one window holds any group of up to 17 rows
# wherever it starts.
ROW_TILE = 32


def _rows_kernel(ids_ref, offsets_ref, x_ref, w_ref, o_ref, *, row_tile,
                 rows, walk_axis=0):
    """One grid step = one expert THAT HAS ROWS: its matrix `w_ref`
    [K, N] (or, under `lane_tile`, the [K, lane_tile] block of it that
    the grid's first axis names) is fetched once, by the pipeline, while
    the step before multiplies. All rows and the result's block stay in
    VMEM; the expert's rows are covered by windows of `row_tile` rows that
    start on a sublane tile, and what a window holds of other experts'
    rows is masked out of the sums."""
    g = pl.program_id(walk_axis)

    @pl.when(g == 0)
    def _zero():
        o_ref[...] = jnp.zeros_like(o_ref)

    expert = ids_ref[g]
    start, end = offsets_ref[expert], offsets_ref[expert + 1]
    first = (start // _ROW_ALIGN) * _ROW_ALIGN
    w = w_ref[...]

    def window(i, carry):
        lo = first + i * row_tile
        # the last window is pulled back inside the rows; what it then
        # holds of the window before is masked (`row >= lo`)
        r0 = pl.multiple_of(jnp.minimum(lo, rows - row_tile), _ROW_ALIGN)
        acc = jnp.dot(x_ref[pl.ds(r0, row_tile), :], w,
                      preferred_element_type=jnp.float32)
        row = r0 + jax.lax.broadcasted_iota(jnp.int32, (row_tile, 1), 0)
        keep = (row >= jnp.maximum(start, lo)) & (row < end)
        o_ref[pl.ds(r0, row_tile), :] += jnp.where(keep, acc, 0.0)
        return carry

    jax.lax.fori_loop(0, pl.cdiv(end - first, row_tile), window, None)


def _group_walk(sizes):
    """What the rows kernel prefetches to walk the groups of `sizes` [E]:
    (ids [E] int32: the groups that HAVE rows, in order, at the front,
    and a valid id behind them; offsets [E + 1] int32: each group's first
    row; how many have rows). The compiler computes it once for the
    products of one layer (they hold the same expression of `sizes`)."""
    sizes = sizes.astype(jnp.int32)
    E = sizes.shape[0]
    offsets = jnp.concatenate([jnp.zeros((1,), jnp.int32),
                               jnp.cumsum(sizes, dtype=jnp.int32)])
    seen = jnp.cumsum(sizes > 0, dtype=jnp.int32)   # groups with rows so far
    # the j-th group with rows is the first whose `seen` passes j: a
    # compare and a sum, where a sort would be a program of its own
    ids = jnp.sum(seen[None, :] <= jnp.arange(E, dtype=jnp.int32)[:, None],
                  axis=1, dtype=jnp.int32)
    return jnp.minimum(ids, E - 1), offsets, seen[-1]


def grouped_rows_matmul(x, w, sizes, *, row_tile: int = ROW_TILE,
                        lane_tile: int | None = None,
                        interpret: bool | None = None):
    """`jax.lax.ragged_dot(x, w, sizes, preferred_element_type=float32)`
    for FEW rows a group: x [M, K] sorted by group, w [E, K, N] as it is
    held (never copied), sizes [E] int32 summing to M OR LESS (rows behind
    the last group belong to none and come back 0, where any group has
    rows) -> float32 [M, N].

    The grid walks the groups that HAVE rows (`_group_walk(sizes)`,
    scalar-prefetched; a group without rows costs no step and no read):
    every weight byte is read once, a whole matrix a step, double-
    buffered behind the products. Under `lane_tile` (whole 128-lane
    tiles, a divisor of N) a step holds a [K, lane_tile] block of the
    matrix instead, and the groups are walked once a block of N: a matrix
    too large for VMEM is still read once. Operands keep x's dtype,
    accumulation is float32."""
    M, K = x.shape
    N = w.shape[2]
    if row_tile % _ROW_ALIGN:
        raise ValueError(f"a window of {row_tile} rows is not whole "
                         f"{_ROW_ALIGN}-row sublane tiles")
    interpret = kernel_mode.resolve_interpret(ROWS_KERNEL_NAME, interpret)
    rows = max(-(-M // _ROW_ALIGN) * _ROW_ALIGN, row_tile)
    x = jnp.pad(x, ((0, rows - M), (0, 0)))
    ids, offsets, live = _group_walk(sizes)
    item = jnp.dtype(x.dtype).itemsize
    if lane_tile is None:
        n = N
        grid, walk_axis, semantics = (live,), 0, ("arbitrary",)
        in_specs = [
            pl.BlockSpec((rows, K), lambda g, ids, offsets: (0, 0)),
            pl.BlockSpec((None, K, N),
                         lambda g, ids, offsets: (ids[g], 0, 0)),
        ]
        out_specs = pl.BlockSpec((rows, N), lambda g, ids, offsets: (0, 0))
    else:
        n = lane_tile
        if n % _LANES or N % n:
            raise ValueError(f"a block of {n} lanes is not whole "
                             f"{_LANES}-lane tiles that divide {N}")
        grid, walk_axis = (N // n, live), 1
        semantics = ("arbitrary", "arbitrary")
        in_specs = [
            pl.BlockSpec((rows, K), lambda j, g, ids, offsets: (0, 0)),
            pl.BlockSpec((None, K, n),
                         lambda j, g, ids, offsets: (ids[g], 0, j)),
        ]
        out_specs = pl.BlockSpec((rows, n),
                                 lambda j, g, ids, offsets: (0, j))
    buffers = 2 * (rows * K * item + K * n * item + rows * n * 4)
    out = pl.pallas_call(
        functools.partial(_rows_kernel, row_tile=row_tile, rows=rows,
                          walk_axis=walk_axis),
        out_shape=jax.ShapeDtypeStruct((rows, N), jnp.float32),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=2, grid=grid, in_specs=in_specs,
            out_specs=out_specs),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=semantics,
            vmem_limit_bytes=buffers + (16 << 20)),
        name=ROWS_KERNEL_NAME,
        interpret=interpret,
    )(ids, offsets, x, w.astype(x.dtype))
    return out[:M]


# Under this many rows an expert (`rows // E`, static) the products take
# the rows kernel: XLA's `ragged-dot` costs a large row tile for every
# group it touches whatever the group's rows, the rows kernel a window of
# `ROW_TILE`. Both cells' decode steps lie under it (6 and 0), both
# cells' chunks over it (64 and 16); `PERF.md` section 6, PR 32 has the
# sweep, and why the chunks stay on `ragged_dot` for now.
ROWS_KERNEL_BELOW = 8
# A step holds an expert's whole matrix twice (this step's and the
# next's): the largest one the kernel takes.
_MATRIX_BYTES = 8 << 20


def few_rows_an_expert(rows: int, num_experts: int, k: int, n: int,
                       dtype=jnp.bfloat16) -> bool:
    """The one rule that picks the grouped product's kernel, from static
    shapes alone: few rows an expert, over [k, n] (and [n, k]) matrices
    of whole lane tiles that fit the rows kernel's VMEM block."""
    return (rows // num_experts < ROWS_KERNEL_BELOW
            and k % _LANES == 0 and n % _LANES == 0
            and k * n * jnp.dtype(dtype).itemsize <= _MATRIX_BYTES)


# A SHARE's chunk (`experts_held`, many rows an expert): most of the
# `tokens * top_k` sorted rows belong to absent experts and to no group, so
# the held rows are few (a chunk of 512 tokens x 8 over 32 of 256 experts:
# ~512 of 4,096) and the rows kernel takes them, this many a pass, its
# matrices in blocks of `_MATRIX_BYTES`. `ragged_dot` there pays ~55 us for
# every group it touches (a 15.7 MB matrix is read in 19), 5.4 ms a layer
# where this reads 2.7, and a chunk's time followed how many of the held
# experts the seed's router touched (`PERF.md` section 6, PR 43).
HELD_ROWS_WINDOW = 1024


def _lane_tile(k: int, n: int, dtype) -> int:
    """The widest block of whole lane tiles that divides `n` and whose
    [k, block] slice of a matrix fits the rows kernel's VMEM block."""
    fits = _MATRIX_BYTES // (k * jnp.dtype(dtype).itemsize)
    return max((t for t in range(_LANES, n + 1, _LANES)
                if n % t == 0 and t <= fits), default=_LANES)


def held_rows_in_windows(rows: int, num_experts: int, k: int, n: int,
                         dtype=jnp.bfloat16) -> bool:
    """The rule for a SHARE's products, from static shapes alone: many
    rows an expert (few take the rule above) over [k, n] (and [n, k])
    matrices of whole lane tiles, of any size."""
    return (rows // num_experts >= ROWS_KERNEL_BELOW
            and k % _LANES == 0 and n % _LANES == 0)


def _held_swiglu_in_windows(x, order, top_k: int, sizes, gate, up, down):
    """The three products over the HELD rows alone, `HELD_ROWS_WINDOW`
    sorted rows a pass and as many passes as the held rows need (one, at
    the cell's share; all `tokens * top_k` rows if every assignment lands
    here: dropless at any imbalance, and an expert's matrix is read once
    a pass that holds rows of it). x [T, h], order [T * k] (the sort),
    sizes [E] of the held experts -> float32 [T * k, h], rows behind the
    last group 0."""
    M, (_, h, f) = order.shape[0], gate.shape
    window = min(HELD_ROWS_WINDOW, M)
    passes = -(-M // window)
    order = jnp.pad(order, (0, passes * window - M))
    offsets = jnp.concatenate([jnp.zeros((1,), jnp.int32),
                               jnp.cumsum(sizes, dtype=jnp.int32)])
    tiles = _lane_tile(h, f, x.dtype), _lane_tile(f, h, x.dtype)

    def one_pass(i, out):
        lo = i * window
        with part("moe.sort"):
            rows = x[jax.lax.dynamic_slice(order, (lo,), (window,)) // top_k]
        inside = jnp.clip(offsets, lo, lo + window)
        product = functools.partial(grouped_rows_matmul,
                                    sizes=inside[1:] - inside[:-1])
        act = (jax.nn.silu(product(rows, gate, lane_tile=tiles[0]))
               * product(rows, up, lane_tile=tiles[0])).astype(x.dtype)
        return jax.lax.dynamic_update_slice(
            out, product(act, down, lane_tile=tiles[1]), (lo, 0))

    out = jax.lax.fori_loop(
        0, -(-offsets[-1] // window), one_pass,
        jnp.zeros((passes * window, h), jnp.float32))
    return out[:M]


def grouped_swiglu_experts(x, experts, weights, gate, up, down,
                           experts_held=None):
    """`y[t] = sum_k weights[t, k] * E_{experts[t, k]}(x[t])` with every
    expert `W_down(silu(W_gate x) * W_up x)`. x [T, h]; experts, weights
    [T, k]; gate, up [E, h, f]; down [E, f, h]. Products take x's dtype
    with float32 accumulation; returns float32 [T, h].

    `experts_held = (first, count)`: this layer HOLDS the experts `first
    .. first + count - 1` of those the router chose among (an expert-
    parallel layer's share: gate, up, down are `[count, ...]`). The
    router's choice and its weights stay as they are, over all experts;
    an assignment to an expert that is not held is DROPPED before the
    grouped products (it sorts behind the held ones and belongs to no
    group: no product, no weight byte), and the sum is over the held
    assignments alone: the part of `y` this share gives, which the other
    shares' parts complete. A token none of whose experts is held gets 0.
    Absent (None): every expert is held, the layer whole."""
    T, k = experts.shape
    E, h, f = gate.shape
    held = None
    if experts_held is not None:
        first, count = experts_held
        if count != E:
            raise ValueError(
                f"experts_held={experts_held} names {count} experts; gate, "
                f"up and down hold {E}")
        with part("moe.sort"):
            experts = experts - first
            held = (experts >= 0) & (experts < E)
            # an absent expert's id is E: behind every group, counted in none
            experts = jnp.where(held, experts, E)
    with part("moe.sort"):
        flat = experts.reshape(T * k)
        order = jnp.argsort(flat, stable=True)
        sizes = expert_counts(experts, E)
    if held is not None and held_rows_in_windows(T * k, E, h, f, x.dtype):
        with part("moe.experts"):
            out = _held_swiglu_in_windows(x, order, k, sizes, gate, up, down)
    else:
        with part("moe.sort"):
            rows = x[order // k]                            # [T * k, h]
        with part("moe.experts"):
            if few_rows_an_expert(T * k, E, h, f, x.dtype):
                product = functools.partial(grouped_rows_matmul, sizes=sizes)
            else:
                def product(a, w):
                    return jax.lax.ragged_dot(
                        a, w.astype(a.dtype), sizes,
                        preferred_element_type=jnp.float32)

            act = (jax.nn.silu(product(rows, gate))
                   * product(rows, up)).astype(x.dtype)
            out = product(act, down)                        # [T * k, h] f32
    with part("moe.combine"):
        back = jnp.zeros((T * k,), order.dtype).at[order].set(
            jnp.arange(T * k, dtype=order.dtype))
        out = out[back].reshape(T, k, -1)
        if held is not None:
            # rows past the last group are whatever the product left there
            out = jnp.where(held[:, :, None], out, 0.0)
        return jnp.sum(out * weights[:, :, None].astype(jnp.float32), axis=1)
