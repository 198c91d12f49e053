"""The selective scan of a state-space layer (Mamba-1: Gu & Dao,
arXiv:2312.00752) over a pool of states, for serving.

THE MATHEMATICS. A layer has `d` channels, each with a state of `n`
numbers. With `dt_t [d]` a token's step sizes (positive), `x_t [d]` its
convolved input, `B_t, C_t [n]` its input and output projections and `A [n,
d]` the (negative) rates:

    S_t = exp(dt_t * A) * S_{t-1} + (dt_t * x_t) (x) B_t          [n, d]
    y_t = sum_n S_t[n, :] * C_t[n]                                 [d]

`S_{-1} = 0`. Beside the state a sequence keeps a WINDOW: the last `taps -
1` rows of the layer's pre-convolution input `u`, which the causal
depthwise convolution of the next token reads.

THE LAYOUT. `n` lies in SUBLANES and `d` in lanes: a state is `[n, d]`
float32 (16 x 5120: two whole 8-row tiles a 128-lane column). A `B_t` or
`C_t` reaches a kernel already spread along 128 lanes (`[n, 128]`, every
lane alike), so that `[n, 1]` against `[1, d]` is a lane broadcast of a
column. The window is `taps - 1` rows of `d`, oldest first, and lies with
the ENTRIES in sublanes (`CacheSpec.aux_entry_minor`): the convolution is
plain XLA over every lane at once, and row j of all lanes is then one dense
`[lanes, d]` slice. (Entry-major, `[entries, rows, d]`, the TPU's compiler
re-laid the whole 1 GB pool out before every layer's read: 25 copies a
decode step, seen in the compiled program before any chip run.)

THE POOL (`models.contract.StatePool`; `serving/cache.py` `StateCache` owns
it): `s [L, entries + 1, 1, n, d]`, `z [L, taps - 1, entries + 1, d]`,
the last entry a SPARE. The ops take the whole pool and a layer index and
hand the whole pool back, updated in place where the caller donates it:

- `conv_step` / `conv_chunk`: the convolution, and the window moved on: a
  slice read, `taps` shifted multiply-adds and a slice update, XLA's.
- `ssm_decode_step`: one token a lane. On the chip a Pallas kernel
  (`ssm_decode_step` in a device trace), grid lane groups x 8: a lane's
  state is read once, advanced and written back where it lay; a lane that
  is not live names the SPARE entry (whose block the pipeline neither
  fetches nor writes again while the index stays) and computes nothing.
  Memory-bound: 2 x n x d x 4 bytes a live lane.
- `ssm_chunk_scan`: a chunk of `T` rows a lane folded into its state, `y`
  for every row. The recurrence is SEQUENTIAL in t; the kernel
  (`ssm_chunk_scan`) walks it with the state of a block of channels in
  registers, 8 rows of `dt`, `x`, `y` a load and store, grid lanes x row
  blocks x channel blocks, and writes the state back once, when the
  chunk's last row block is done. A row with `dt = 0` and `x = 0` leaves
  the state as it is: the caller zeroes both for a chunk's padding. (As
  `jax.lax.scan` the same loop is `T` tiny sequential bodies a layer.)

Off the chip both run as plain `jax.numpy` over gathered entries
(`pool.kernel` False) or through the Pallas interpreter (`pool.kernel`
True; `ops/kernel_mode.py`).
"""

from __future__ import annotations

import dataclasses
import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from ..models.common import part
from ..models.contract import StateMeta, StatePool
from . import kernel_mode

DECODE_KERNEL = "ssm_decode_step"
CHUNK_KERNEL = "ssm_chunk_scan"
_LANES = 128
_GROUP = 8          # lanes of the batch a decode grid row holds

__all__ = ["conv_step", "conv_chunk", "scan_reference",
           "ssm_chunk_scan", "ssm_decode_step"]


# ---------------------------------------------------------------------------
# the plain form: what the tests hold both ops to
# ---------------------------------------------------------------------------


def scan_reference(dt, x, Bm, Cm, A, S0):
    """The recurrence position by position. dt, x [T, d]; Bm, Cm [T, n]; A
    [n, d]; S0 [n, d] -> (y [T, d], S_T), float32."""
    f32 = jnp.float32

    def step(S, row):
        dt_t, x_t, b_t, c_t = row
        S = jnp.exp(dt_t[None, :] * A) * S + (dt_t * x_t)[None, :] * b_t[:, None]
        return S, jnp.sum(S * c_t[:, None], axis=0)

    S, y = jax.lax.scan(step, S0.astype(f32), (
        dt.astype(f32), x.astype(f32), Bm.astype(f32), Cm.astype(f32)))
    return y, S


# ---------------------------------------------------------------------------
# the convolution: taps shifted multiply-adds (XLA's)
# ---------------------------------------------------------------------------


def _entries(pool: StatePool, meta: StateMeta, batch: int):
    """[B] int32, each lane's entry (`meta.entries` None: lane b's is b)."""
    if meta.entries is None:
        return jnp.arange(batch, dtype=jnp.int32)
    return meta.entries.astype(jnp.int32)


@part("cache.view")
def _read_windows(pool: StatePool, layer, meta: StateMeta, batch: int):
    """[rows, B, d] float32: the lanes' windows in layer `layer`. Lane b's
    entry being entry b, they are one slice; else one slice a lane."""
    z = jax.lax.dynamic_index_in_dim(pool.z, layer, axis=0, keepdims=False)
    if meta.entries is None:
        return z[:, :batch].astype(jnp.float32)
    return jnp.concatenate([jax.lax.dynamic_slice_in_dim(
        z, meta.entries[b], 1, axis=1) for b in range(batch)],
        axis=1).astype(jnp.float32)


@part("cache.write")
def _write_windows(pool: StatePool, layer, meta: StateMeta, new):
    """The pool with `new` [rows, B, d] as the lanes' windows in layer
    `layer`: one slice update, or one a lane."""
    z = pool.z
    new = new.astype(z.dtype)[None]
    if meta.entries is None:
        z = jax.lax.dynamic_update_slice(z, new, (layer, 0, 0, 0))
    else:
        for b in range(new.shape[2]):
            z = jax.lax.dynamic_update_slice(
                z, new[:, :, b:b + 1], (layer, 0, meta.entries[b], 0))
    return dataclasses.replace(pool, z=z)


def conv_step(u, pool: StatePool, layer, meta: StateMeta, weight, bias,
              skip_oldest: int = 0):
    """One token a lane through the causal depthwise convolution, and the
    live lanes' windows moved on by it. u [B, d]; weight [taps, d] (row j
    multiplies `u_{t - taps + 1 + j}`), bias [d] -> (the pre-activation [B,
    d] float32, the pool). A lane with `meta.rows` 0 keeps its window.
    `skip_oldest`: leave that many of the oldest taps out (the benchmark's
    control of a wrong model)."""
    taps = weight.shape[0]
    win = _read_windows(pool, layer, meta, u.shape[0])
    w = weight.astype(jnp.float32)
    u = u.astype(jnp.float32)
    out = bias.astype(jnp.float32) + w[taps - 1] * u
    for j in range(skip_oldest, taps - 1):
        out = out + w[j] * win[j]
    new = jnp.where((meta.rows > 0)[None, :, None],
                    jnp.concatenate([win[1:], u[None]], axis=0), win)
    return out, _write_windows(pool, layer, meta, new)


def conv_chunk(u, pool: StatePool, layer, meta: StateMeta, weight, bias,
               skip_oldest: int = 0):
    """A chunk a lane through the convolution, and the lanes' windows
    advanced past their real rows. u [B, T, d]; `meta.rows` [B] the real
    leading rows -> (pre-activation [B, T, d] float32, the pool with layer
    `layer`'s windows of those lanes holding their last `taps - 1` real
    `u` rows: of a prompt shorter than the window, what it has, after the
    zeros or the rows that were there)."""
    B, T, d = u.shape
    taps = weight.shape[0]
    win = jnp.swapaxes(_read_windows(pool, layer, meta, B), 0, 1)
    ext = jnp.concatenate([win, u.astype(jnp.float32)], axis=1)
    w = weight.astype(jnp.float32)
    out = bias.astype(jnp.float32) + sum(
        w[j] * ext[:, j:j + T] for j in range(skip_oldest, taps))
    # ext row r holds u_{r - taps + 1}: the window after `rows` real rows
    # is ext[rows : rows + taps - 1]
    new = jax.vmap(lambda e, r: jax.lax.dynamic_slice_in_dim(
        e, r, taps - 1, axis=0))(ext, meta.rows.astype(jnp.int32))
    return out, _write_windows(pool, layer, meta, jnp.swapaxes(new, 0, 1))


# ---------------------------------------------------------------------------
# one token a lane
# ---------------------------------------------------------------------------


def _lane_block(d: int, at_most: int) -> int:
    """The widest block of channels, whole 128-lane tiles, that divides
    `d` and is at most `at_most` lanes."""
    if d % _LANES:
        raise ValueError(
            f"a state-space layer's channels lie in whole 128-lane tiles; "
            f"got {d}")
    return max(b for b in range(_LANES, min(d, at_most) + 1, _LANES)
               if d % b == 0)


def _spread(m):
    """[..., n] -> [..., n, 128] float32, every lane alike."""
    return jnp.broadcast_to(m.astype(jnp.float32)[..., None],
                            m.shape + (_LANES,))


def _decode_kernel(ent_ref, live_ref, layer_ref, dt_ref, dtx_ref, b_ref,
                   c_ref, a_ref, s_ref, s_out, y_ref, *, block: int):
    """One grid step: lane `i * 8 + j` of the batch. `dt_ref`, `dtx_ref`,
    `y_ref` [8, d] hold the lane group's rows (row j is this lane's);
    `b_ref`, `c_ref` [n, 128]; `a_ref` [n, d]; the state of the lane's
    entry in and out."""
    del ent_ref, layer_ref
    i, j = pl.program_id(0), pl.program_id(1)
    d = a_ref.shape[1]
    row = pl.ds(j, 1)

    @pl.when(live_ref[i * _GROUP + j] != 0)
    def _live():
        b_col, c_col = b_ref[:, 0:1], c_ref[:, 0:1]
        for at in range(0, d, block):
            ch = slice(at, at + block)
            new = (jnp.exp(dt_ref[row, ch] * a_ref[:, ch])
                   * s_ref[:, ch].astype(jnp.float32)
                   + dtx_ref[row, ch] * b_col)
            s_out[:, ch] = new.astype(s_out.dtype)
            y_ref[row, ch] = jnp.sum(new * c_col, axis=0, keepdims=True)

    @pl.when(live_ref[i * _GROUP + j] == 0)
    def _dead():
        y_ref[row, :] = jnp.zeros((1, d), y_ref.dtype)


@functools.partial(jax.jit, static_argnames=("interpret",))
def _decode_pallas(dt, dtx, Bm, Cm, A, s, layer, entries, live, *,
                   interpret: bool):
    """(inside one `jax.jit`, so that a program's layers share ONE lowered
    body: `layer` is data)."""
    B, d = dt.shape
    n = A.shape[0]
    Bp = -(-B // _GROUP) * _GROUP
    f32 = jnp.float32
    spare = s.shape[1] - 1

    def rows(a):
        return jnp.pad(a.astype(f32), ((0, Bp - B), (0, 0)))

    live = jnp.pad(live.astype(jnp.int32), (0, Bp - B))
    entries = jnp.where(live > 0, jnp.pad(entries, (0, Bp - B)),
                        spare).astype(jnp.int32)
    b_sp, c_sp = (jnp.pad(_spread(m), ((0, Bp - B), (0, 0), (0, 0)))
                  for m in (Bm, Cm))

    def group(i, j, *_):
        return (i, 0)

    def lane(i, j, *_):
        return (i * _GROUP + j, 0, 0)

    def entry(i, j, ent, live, layer):
        return (layer[0], ent[i * _GROUP + j], 0, 0, 0)

    rows_spec = pl.BlockSpec((_GROUP, d), group)
    col_spec = pl.BlockSpec((None, n, _LANES), lane)
    s_spec = pl.BlockSpec((None, None, None, n, d), entry)
    s, y = pl.pallas_call(
        functools.partial(_decode_kernel, block=_lane_block(d, 512)),
        out_shape=[jax.ShapeDtypeStruct(s.shape, s.dtype),
                   jax.ShapeDtypeStruct((Bp, d), f32)],
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=3,
            grid=(Bp // _GROUP, _GROUP),
            in_specs=[rows_spec, rows_spec, col_spec, col_spec,
                      pl.BlockSpec((n, d), lambda i, j, *_: (0, 0)),
                      s_spec],
            out_specs=[s_spec, rows_spec]),
        # operands count the scalar-prefetch arguments: s is the 9th
        input_output_aliases={8: 0},
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary", "arbitrary")),
        name=DECODE_KERNEL,
        interpret=interpret,
    )(entries, live, jnp.asarray(layer, jnp.int32).reshape(1), rows(dt),
      rows(dtx), b_sp, c_sp, A.astype(f32), s)
    return y[:B], s


def _gather(pool_array, layer, entries):
    return jax.lax.dynamic_index_in_dim(
        pool_array, layer, axis=0, keepdims=False)[entries, 0]


def _decode_dense(dt, dtx, Bm, Cm, A, pool: StatePool, layer, entries, live):
    """`ssm_decode_step` by a gather, one step of the recurrence in
    `jax.numpy` and a scatter: the path off the chip."""
    f32 = jnp.float32
    entries = jnp.where(live, entries, pool.spare)
    with part("cache.view"):
        S = _gather(pool.s, layer, entries).astype(f32)          # [B, n, d]
    S = (jnp.exp(dt[:, None, :] * A[None]) * S
         + dtx[:, None, :] * Bm.astype(f32)[:, :, None])
    y = jnp.where(live[:, None],
                  jnp.sum(S * Cm.astype(f32)[:, :, None], axis=1), 0.0)
    with part("cache.write"):
        # (dead lanes all name the spare: what lands there is never read)
        s = pool.s.at[layer, entries, 0].set(S.astype(pool.s.dtype))
    return y, dataclasses.replace(pool, s=s)


def ssm_decode_step(dt, x, Bm, Cm, A, pool: StatePool, layer,
                    meta: StateMeta, *, interpret: bool | None = None):
    """One token of every lane through its state. dt, x [B, d] (step sizes,
    the convolved input); Bm, Cm [B, n]; A [n, d]; `pool`, `layer` (int32
    scalar or int) and `meta` as in the module docstring. A lane with
    `meta.rows` 0 is dead: its state is neither read nor written and its
    `y` is zero. -> (y [B, d] float32, the pool with layer `layer`'s
    states of the live lanes advanced by their token)."""
    B = dt.shape[0]
    f32 = jnp.float32
    live = meta.rows > 0
    entries = _entries(pool, meta, B)
    dt = dt.astype(f32)
    dtx = dt * x.astype(f32)
    if not pool.kernel:
        return _decode_dense(dt, dtx, Bm, Cm, A.astype(f32), pool, layer,
                             entries, live)
    interpret = kernel_mode.resolve_interpret(DECODE_KERNEL, interpret)
    y, s = _decode_pallas(dt, dtx, Bm, Cm, A, pool.s, layer, entries, live,
                          interpret=interpret)
    return y, dataclasses.replace(pool, s=s)


# ---------------------------------------------------------------------------
# a chunk of rows a lane
# ---------------------------------------------------------------------------


def _chunk_kernel(ent_ref, layer_ref, dt_ref, dtx_ref, b_ref, c_ref, a_ref,
                  s_ref, s_out, y_ref, *, block: int):
    """One grid step: lane b, rows [i * Tb, (i + 1) * Tb), channels [j *
    block, (j + 1) * block). `dt_ref`, `dtx_ref`, `y_ref` [Tb, block];
    `b_ref`, `c_ref` [Tb, n, 128]; `a_ref` [n, block]; `s_ref`, `s_out` the
    lane's whole state [n, d]: `s_out` stays in vector memory over the
    lane's steps and carries the state from one row block to the next."""
    del ent_ref, layer_ref
    i, j = pl.program_id(1), pl.program_id(2)
    ch = pl.ds(pl.multiple_of(j * block, _LANES), block)
    rows = dt_ref.shape[0]

    @pl.when(i == 0)
    def _first():
        s_out[:, ch] = s_ref[:, ch]

    a = a_ref[...]

    def eight(g, S):
        at = pl.ds(pl.multiple_of(g * 8, 8), 8)
        dt8, dtx8 = dt_ref[at, :], dtx_ref[at, :]
        out = []
        for r in range(8):
            t = g * 8 + r
            S = (jnp.exp(dt8[r:r + 1] * a) * S
                 + dtx8[r:r + 1] * b_ref[t][:, 0:1])
            out.append(jnp.sum(S * c_ref[t][:, 0:1], axis=0, keepdims=True))
        y_ref[at, :] = jnp.concatenate(out, axis=0)
        return S

    s_out[:, ch] = jax.lax.fori_loop(
        0, rows // 8, eight, s_out[:, ch].astype(jnp.float32)
    ).astype(s_out.dtype)


@functools.partial(jax.jit, static_argnames=("interpret",))
def _chunk_pallas(dt, dtx, Bm, Cm, A, s, layer, entries, *, interpret: bool):
    B, T, d = dt.shape
    n = A.shape[0]
    f32 = jnp.float32
    Tb = min(64, -(-T // 8) * 8)
    Tp = -(-T // Tb) * Tb
    block = _lane_block(d, 1024)

    def rows(a):        # a padded row folds nothing: dt = 0, dt x = 0
        return jnp.pad(a.astype(f32), ((0, 0), (0, Tp - T), (0, 0)))

    b_sp, c_sp = (jnp.pad(_spread(m), ((0, 0), (0, Tp - T), (0, 0), (0, 0)))
                  for m in (Bm, Cm))

    def tile(b, i, j, *_):
        return (b, i, j)

    def cols(b, i, j, *_):
        return (b, i, 0, 0)

    def entry(b, i, j, ent, layer):
        return (layer[0], ent[b], 0, 0, 0)

    tile_spec = pl.BlockSpec((None, Tb, block), tile)
    col_spec = pl.BlockSpec((None, Tb, n, _LANES), cols)
    s_spec = pl.BlockSpec((None, None, None, n, d), entry)
    s, y = pl.pallas_call(
        functools.partial(_chunk_kernel, block=block),
        out_shape=[jax.ShapeDtypeStruct(s.shape, s.dtype),
                   jax.ShapeDtypeStruct((B, Tp, d), f32)],
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=2,
            grid=(B, Tp // Tb, d // block),
            in_specs=[tile_spec, tile_spec, col_spec, col_spec,
                      pl.BlockSpec((n, block), lambda b, i, j, *_: (0, j)),
                      s_spec],
            out_specs=[s_spec, tile_spec]),
        # operands count the scalar-prefetch arguments: s is the 8th
        input_output_aliases={7: 0},
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary", "arbitrary", "arbitrary")),
        name=CHUNK_KERNEL,
        interpret=interpret,
    )(entries.astype(jnp.int32), jnp.asarray(layer, jnp.int32).reshape(1),
      rows(dt), rows(dtx), b_sp, c_sp, A.astype(f32), s)
    return y[:, :T], s


def ssm_chunk_scan(dt, x, Bm, Cm, A, pool: StatePool, layer, meta: StateMeta,
                   *, interpret: bool | None = None):
    """A chunk of rows a lane folded into its state. dt, x [B, T, d]; Bm,
    Cm [B, T, n]; A [n, d]. Rows at or past `meta.rows[b]` of lane b (a
    last chunk's padding) do not touch the state: their `dt` counts as 0.
    Their `y` is not meaningful. -> (y [B, T, d] float32, the pool with
    layer `layer`'s states of those lanes advanced by their real rows)."""
    B, T, d = dt.shape
    f32 = jnp.float32
    real = (jnp.arange(T, dtype=jnp.int32)[None, :]
            < meta.rows.astype(jnp.int32)[:, None])[..., None]
    dt = jnp.where(real, dt.astype(f32), 0.0)
    dtx = dt * x.astype(f32)
    entries = _entries(pool, meta, B)
    if not pool.kernel:
        with part("cache.view"):
            S0 = _gather(pool.s, layer, entries)
        y, S = jax.vmap(scan_reference, in_axes=(0, 0, 0, 0, None, 0))(
            dt, jnp.where(real, x.astype(f32), 0.0), Bm, Cm, A.astype(f32),
            S0)
        with part("cache.write"):
            s = pool.s.at[layer, entries, 0].set(S.astype(pool.s.dtype))
        return y, dataclasses.replace(pool, s=s)
    interpret = kernel_mode.resolve_interpret(CHUNK_KERNEL, interpret)
    y, s = _chunk_pallas(dt, dtx, Bm, Cm, A, pool.s, layer, entries,
                         interpret=interpret)
    return y, dataclasses.replace(pool, s=s)
