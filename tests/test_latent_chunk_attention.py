"""The chunk kernel over latent rows (`ops/latent_chunk_attention.py`),
interpreted, against its `jax.numpy` reference (the loop the models ran
until PR 44) and, for the reference itself, against a plain softmax over
the whole view: with and without a selection and a window, work bounded
by `live`, a ring past its wrap, a query that sees nothing, a view that
is no whole number of tiles, several head groups.

Tiny widths (nope 16, rope 8, v 16, rank 128, rows of 256 lanes); the
tile is cut to 128 rows and the vector-memory budget to a few heads'
worth, so that a view of a few hundred rows is several tiles and 32-128
heads are several groups."""

import math

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from accelerate_tpu.ops import kernel_mode
from accelerate_tpu.ops import latent_chunk_attention as lca

NOPE, ROPE, V, RANK, W = 16, 8, 16, 128, 256
TILE = 128


@pytest.fixture(autouse=True)
def small_tiles(monkeypatch):
    monkeypatch.setattr(lca, "_MAX_TILE", TILE)
    monkeypatch.setattr(lca, "_VMEM_BUDGET", 3 << 20)


def _inputs(B, S, H, R, dtype, seed=0):
    ks = jax.random.split(jax.random.key(seed), 5)
    q_nope = jax.random.normal(ks[0], (B, S, H, NOPE), dtype)
    q_pe = jax.random.normal(ks[1], (B, S, H, ROPE), dtype)
    rows = jax.random.normal(ks[2], (B, R, W), dtype)
    rows = rows.at[..., RANK + ROPE:].set(0)
    w_kvb = (jax.random.normal(ks[3], (RANK, H, NOPE + V), jnp.float32)
             * RANK ** -0.5).astype(dtype)
    return q_nope, q_pe, rows, w_kvb, ks[4]


def _plain(q_nope, q_pe, q_pos, rows, key_pos, w_kvb, select, window):
    """Every row decompressed, one softmax over the whole view, float32."""
    f32 = jnp.float32
    rows, w_kvb = rows.astype(f32), w_kvb.astype(f32)
    kv = jnp.einsum("brc,chd->brhd", rows[..., :RANK], w_kvb,
                    precision="highest")
    s = (jnp.einsum("bshd,brhd->bhsr", q_nope.astype(f32), kv[..., :NOPE],
                    precision="highest")
         + jnp.einsum("bshd,brd->bhsr", q_pe.astype(f32),
                      rows[..., RANK:RANK + ROPE], precision="highest")
         ) / math.sqrt(NOPE + ROPE)
    kp, qp = key_pos[:, None, None, :], q_pos[:, None, :, None]
    see = (kp >= 0) & (kp <= qp)
    if window is not None:
        see = see & (qp - kp < window)
    if select is not None:
        see = see & select[:, None]
    p = jnp.where(see, jnp.exp(s - jnp.max(jnp.where(see, s, -1e30), -1,
                                           keepdims=True)), 0.0)
    p = p / jnp.maximum(p.sum(-1, keepdims=True), 1e-30)
    return jnp.einsum("bhsr,brhd->bshd", p, kv[..., NOPE:],
                      precision="highest")


# name, B, S, H, R, chunk start, selection, window, live, ring, dtype
CASES = [
    ("plain-f32", 2, 16, 4, 300, 280, False, None, False, False, "float32"),
    ("selection-f32", 2, 16, 4, 300, 280, True, None, False, False,
     "float32"),
    ("window-f32", 1, 16, 4, 300, 280, False, 9, False, False, "float32"),
    ("selection-window-live-f32", 1, 13, 4, 300, 150, True, 40, True, False,
     "float32"),
    ("live-nan-past-end-f32", 2, 16, 4, 640, 130, True, None, True, False,
     "float32"),
    ("ring-wrapped-f32", 2, 16, 4, 200, 530, False, 150, False, True,
     "float32"),
    ("ring-not-yet-wrapped-f32", 1, 16, 4, 200, 60, False, 50, True, True,
     "float32"),
    ("one-token-f32", 2, 1, 4, 300, 170, True, None, True, False,
     "float32"),
    ("whole-tiles-f32", 1, 16, 4, 384, 300, True, None, False, False,
     "float32"),
    ("32-heads-bf16", 1, 16, 32, 300, 280, True, None, False, False,
     "bfloat16"),
    ("64-heads-window-bf16", 1, 16, 64, 200, 530, False, 150, False, True,
     "bfloat16"),
    ("128-heads-bf16", 1, 32, 128, 300, 140, True, None, True, False,
     "bfloat16"),
    ("plain-bf16", 2, 16, 4, 300, 280, False, None, False, False,
     "bfloat16"),
]


@pytest.mark.parametrize(
    "B,S,H,R,start,selection,window,bounded,ring,dtype",
    [c[1:] for c in CASES], ids=[c[0] for c in CASES])
def test_the_kernel_is_its_reference(B, S, H, R, start, selection, window,
                                     bounded, ring, dtype):
    """Kernel (interpreted) against the reference: float32 to 1e-5, bf16
    to one bf16 unit of the largest output; the reference against one
    softmax over the whole view in float32. A query past the rows of its
    own position, and one masked out of everything, read 0; tiles past
    `live` hold NaN and are not read."""
    dtype = jnp.dtype(dtype)
    q_nope, q_pe, rows, w_kvb, key = _inputs(B, S, H, R, dtype)
    q_pos = jnp.broadcast_to(
        start + jnp.arange(S, dtype=jnp.int32)[None], (B, S))
    # the second sequence of a batch lags the first by three positions
    q_pos = q_pos - 3 * jnp.arange(B, dtype=jnp.int32)[:, None]
    # the last query of the first sequence is nowhere: it sees nothing
    q_pos = q_pos.at[0, S - 1].set(-1) if S > 1 else q_pos
    if ring:
        # row r holds the newest position p <= last with p % R == r
        last = start + S - 1
        r = jnp.arange(R, dtype=jnp.int32)
        key_pos = jnp.broadcast_to((last - (last - r) % R)[None], (B, R))
    else:
        key_pos = jnp.broadcast_to(jnp.arange(R, dtype=jnp.int32)[None],
                                   (B, R))
    select = None
    if selection:
        select = jax.random.uniform(key, (B, S, R)) < 0.3
        # one query selects nothing at all
        select = select.at[B - 1, 0].set(False)
    live = None
    if bounded:
        first = max(start - 3 * (B - 1) - (window or start + 1) + 1, 0)
        end = start + S
        live = (jnp.int32(first), jnp.int32(end))
        # what lies in a tile outside `live` is never read
        lo, hi = first // TILE * TILE, -(-end // TILE) * TILE
        dead = (jnp.arange(R) < lo) | (jnp.arange(R) >= hi)
        assert bool(dead.any())
        rows = jnp.where(dead[None, :, None], jnp.nan, rows)
    heads, tile, _ = lca._tiles(-(-S // 16) * 16, R, H, RANK, 128, V, W,
                                dtype.itemsize, selection)
    assert tile == TILE and -(-R // tile) > 1
    assert heads < H or H == 4, "several head groups"

    args = (q_nope, q_pe, q_pos, rows, key_pos, w_kvb)
    with jax.default_matmul_precision("highest"):
        got = jax.jit(lambda *a: lca.latent_chunk_attention(
            *a, select=select, window=window, live=live))(*args)
        ref = jax.jit(lambda *a: lca.latent_chunk_attention_reference(
            *a, select=select, window=window, live=live, block=TILE))(*args)
    assert kernel_mode.kernel_report()[lca.KERNEL_NAME] == "interpret"
    assert got.shape == ref.shape == (B, S, H, V) and got.dtype == dtype
    got, ref = np.asarray(got, np.float32), np.asarray(ref, np.float32)
    assert np.isfinite(got).all() and np.isfinite(ref).all()
    unit = 2.0 ** -8 * np.abs(ref).max()
    tol = 1e-5 if dtype == jnp.float32 else unit
    assert np.abs(got - ref).max() <= tol
    plain = np.asarray(_plain(q_nope, q_pe, q_pos, jnp.nan_to_num(rows),
                              key_pos, w_kvb, select, window))
    assert np.abs(ref - plain).max() <= (2e-5 if dtype == jnp.float32
                                         else 4 * unit)
    if S > 1:
        assert not got[0, S - 1].any()
    if selection:
        assert not got[B - 1, 0].any()
    assert np.abs(got).max() > 0.05


def test_a_row_too_narrow_for_its_rope_key_raises():
    q_nope, q_pe, rows, w_kvb, _ = _inputs(1, 16, 4, 64, jnp.float32)
    pos = jnp.arange(16, dtype=jnp.int32)[None]
    with pytest.raises(ValueError, match="whole 128-lane tiles"):
        lca.latent_chunk_attention(
            q_nope, q_pe, pos, rows[..., :RANK + ROPE],
            jnp.arange(64, dtype=jnp.int32)[None], w_kvb)


def test_the_tiles_follow_from_the_shapes(monkeypatch):
    """At the cells' shapes (bf16): rows a tile and heads a group, and
    what they take stays inside the budget. The two long views are whole
    tiles (no copy to pad them); the ring of 1,056 rows is one tile of
    1,152."""
    monkeypatch.undo()
    for name, (S, R, H, rank, v, width, selected), want in [
            ("dots3 full", (512, 43520, 128, 512, 128, 640, True), (8, 1280)),
            ("dots3 sliding", (512, 1056, 64, 1024, 128, 1152, False),
             (4, 1152)),
            ("joyai", (512, 18432, 32, 512, 128, 640, False), (8, 1024)),
            ("one token", (16, 43520, 128, 512, 128, 640, True), (32, 1280)),
    ]:
        heads, tile, vmem = lca._tiles(S, R, H, rank, 256, v, width, 2,
                                       selected)
        assert (heads, tile) == want, name
        assert vmem <= lca._VMEM_BUDGET, name
        assert R % tile == 0 or R < tile, name
