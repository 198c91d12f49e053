"""`ops/selective_scan.py` on the CPU: both Pallas kernels (interpreted)
and both plain forms against the recurrence written position by position,
the convolution against four shifted sums, and what a pool keeps of lanes
that are dead, padded or absent."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from accelerate_tpu.models.contract import StateMeta, StatePool
from accelerate_tpu.ops import selective_scan as ss

L, E, N, D, TAPS = 2, 5, 16, 256, 4


def _rand(seed, *shape):
    return jnp.asarray(np.random.default_rng(seed).standard_normal(shape),
                       jnp.float32)


def _pool(kernel, seed=0):
    """A pool full of noise: 4 entries and the spare."""
    return StatePool(_rand(seed, L, E, 1, N, D),
                     _rand(seed + 1, L, TAPS - 1, E, D), kernel)


def _rates():
    return -jnp.exp(0.3 * _rand(9, N, D))


def _step_inputs(B, seed=3):
    return (jax.nn.softplus(_rand(seed, B, D)), _rand(seed + 1, B, D),
            _rand(seed + 2, B, N), _rand(seed + 3, B, N))


def _by_hand(dt, x, Bm, Cm, A, S):
    """One position of the recurrence in NumPy, float64."""
    dt, x, Bm, Cm, A, S = (np.asarray(a, np.float64)
                           for a in (dt, x, Bm, Cm, A, S))
    S = np.exp(dt[None, :] * A) * S + (dt * x)[None, :] * Bm[:, None]
    return (S * Cm[:, None]).sum(0), S


@pytest.mark.parametrize("entries", [None, (2, 0, 3)],
                         ids=["lane-b-is-entry-b", "named-entries"])
@pytest.mark.parametrize("kernel", [False, True], ids=["dense", "kernel"])
def test_a_decode_step_advances_live_lanes_and_no_other(kernel, entries):
    """Three lanes, the middle one dead: the live lanes' states are the
    recurrence's next, the dead lane's entry, the other layer and every
    entry no lane names keep their bytes, the dead lane's `y` is zero."""
    pool, A = _pool(kernel), _rates()
    dt, x, Bm, Cm = _step_inputs(3)
    meta = StateMeta(None if entries is None else jnp.asarray(entries),
                     jnp.array([1, 0, 1], jnp.int32))
    y, new = ss.ssm_decode_step(dt, x, Bm, Cm, A, pool, 1, meta)
    at = (0, 1, 2) if entries is None else entries
    for b in (0, 2):
        want_y, want_S = _by_hand(dt[b], x[b], Bm[b], Cm[b], A,
                                  pool.s[1, at[b], 0])
        np.testing.assert_allclose(y[b], want_y, atol=1e-5)
        np.testing.assert_allclose(new.s[1, at[b], 0], want_S, atol=1e-5)
    assert float(jnp.abs(y[1]).max()) == 0.0
    untouched = [e for e in range(E - 1) if e not in (at[0], at[2])]
    np.testing.assert_array_equal(new.s[1, untouched], pool.s[1, untouched])
    np.testing.assert_array_equal(new.s[0], pool.s[0])
    np.testing.assert_array_equal(new.z, pool.z)    # the window is conv_step's


@pytest.mark.parametrize("kernel", [False, True], ids=["dense", "kernel"])
def test_a_chunk_scan_is_the_recurrence_position_by_position(kernel):
    """Two lanes of 20 rows, the second with 13 real: `y` of the real rows
    and the state after them are the recurrence's; the padding of the
    second lane leaves its state where row 12 left it."""
    pool, A = _pool(kernel), _rates()
    T = 20
    dt = jax.nn.softplus(_rand(4, 2, T, D))
    x, Bm, Cm = _rand(5, 2, T, D), _rand(6, 2, T, N), _rand(7, 2, T, N)
    meta = StateMeta(jnp.array([1, 3]), jnp.array([20, 13]))
    y, new = ss.ssm_chunk_scan(dt, x, Bm, Cm, A, pool, 0, meta)
    for b, (entry, real) in enumerate(((1, 20), (3, 13))):
        S = pool.s[0, entry, 0]
        for t in range(real):
            want, S = _by_hand(dt[b, t], x[b, t], Bm[b, t], Cm[b, t], A, S)
            np.testing.assert_allclose(y[b, t], want, rtol=2e-5, atol=2e-5)
        np.testing.assert_allclose(new.s[0, entry, 0], S, rtol=2e-5,
                                   atol=2e-5)
    np.testing.assert_array_equal(new.s[0, [0, 2, 4]], pool.s[0, [0, 2, 4]])
    np.testing.assert_array_equal(new.s[1], pool.s[1])


@pytest.mark.parametrize("kernel", [False, True], ids=["dense", "kernel"])
def test_a_scan_of_two_chunks_equals_one_of_both(kernel):
    """72 rows as one chunk (two row blocks of the kernel's 64) and as
    chunks of 40 and 32: the same `y` and the same state."""
    pool, A = _pool(kernel), _rates()
    T = 72
    dt = jax.nn.softplus(_rand(4, 1, T, D))
    x, Bm, Cm = _rand(5, 1, T, D), _rand(6, 1, T, N), _rand(7, 1, T, N)

    def scan(pool, lo, hi):
        return ss.ssm_chunk_scan(
            dt[:, lo:hi], x[:, lo:hi], Bm[:, lo:hi], Cm[:, lo:hi], A, pool,
            1, StateMeta(jnp.array([2]), jnp.array([hi - lo])))

    whole, one = scan(pool, 0, T)
    first, mid = scan(pool, 0, 40)
    second, two = scan(mid, 40, T)
    np.testing.assert_allclose(jnp.concatenate([first, second], 1), whole,
                               rtol=1e-5, atol=1e-5)
    np.testing.assert_allclose(two.s, one.s, rtol=1e-5, atol=1e-5)


def test_the_kernels_agree_with_the_plain_forms():
    """Both kernels (interpreted) against `jax.numpy` over gathered
    entries, on one pool of noise."""
    A = _rates()
    dt, x, Bm, Cm = _step_inputs(11, seed=20)    # 11 lanes: padded to 16
    rows = jnp.asarray([1, 0] * 5 + [1], jnp.int32)
    big = [StatePool(_rand(0, L, 12, 1, N, D), _rand(1, L, TAPS - 1, 12, D),
                     k) for k in (False, True)]
    (y0, p0), (y1, p1) = (ss.ssm_decode_step(
        dt, x, Bm, Cm, A, p, 0, StateMeta(None, rows)) for p in big)
    np.testing.assert_allclose(y1, y0, atol=1e-5)
    np.testing.assert_allclose(p1.s[:, :11], p0.s[:, :11], atol=1e-5)


def test_the_convolution_is_four_shifted_sums_and_moves_the_window_on():
    """A chunk over a window of noise: row t is `b + sum_j w[j] u[t - 3 +
    j]` with the window before the chunk; afterwards the window holds the
    last three REAL rows (of a prompt of one token: two old rows and it)."""
    pool = _pool(False)
    w, bias = _rand(30, TAPS, D), _rand(31, D)
    u = _rand(32, 3, 9, D)
    meta = StateMeta(jnp.array([0, 3, 1]), jnp.array([9, 5, 1]))
    out, new = ss.conv_chunk(u, pool, 1, meta, w, bias)
    for b, (entry, real) in enumerate(((0, 9), (3, 5), (1, 1))):
        ext = np.concatenate([np.asarray(pool.z[1, :, entry]),
                              np.asarray(u[b])])
        want = np.asarray(bias) + sum(
            np.asarray(w[j]) * ext[j:j + 9] for j in range(TAPS))
        np.testing.assert_allclose(out[b], want, atol=1e-5)
        np.testing.assert_array_equal(new.z[1, :, entry],
                                      ext[real:real + TAPS - 1])
    np.testing.assert_array_equal(new.z[1, :, [2, 4]], pool.z[1, :, [2, 4]])
    np.testing.assert_array_equal(new.z[0], pool.z[0])
    # the control's wrong model: the oldest tap left out
    short, _ = ss.conv_chunk(u, pool, 1, meta, w, bias, skip_oldest=1)
    ext = np.concatenate([np.asarray(pool.z[1, :, 0]), np.asarray(u[0])])
    np.testing.assert_allclose(
        out[0] - short[0], np.asarray(w[0]) * ext[0:9], atol=1e-5)


@pytest.mark.parametrize("entries", [None, (3, 1)],
                         ids=["lane-b-is-entry-b", "named-entries"])
def test_a_convolution_step_follows_a_chunk_and_skips_dead_lanes(entries):
    """One token after a chunk reads the window the chunk left; a dead
    lane's window keeps its rows."""
    pool = _pool(False)
    w, bias = _rand(30, TAPS, D), _rand(31, D)
    u = _rand(33, 2, D)
    meta = StateMeta(None if entries is None else jnp.asarray(entries),
                     jnp.array([1, 0]))
    at = (0, 1) if entries is None else entries
    out, new = ss.conv_step(u, pool, 0, meta, w, bias)
    win = np.asarray(pool.z[0, :, at[0]])
    want = np.asarray(bias) + np.asarray(w[3]) * np.asarray(u[0]) + sum(
        np.asarray(w[j]) * win[j] for j in range(3))
    np.testing.assert_allclose(out[0], want, atol=1e-5)
    np.testing.assert_array_equal(
        new.z[0, :, at[0]], np.concatenate([win[1:], np.asarray(u[:1])]))
    np.testing.assert_array_equal(new.z[0, :, at[1]], pool.z[0, :, at[1]])


def test_channels_lie_in_whole_lane_tiles():
    with pytest.raises(ValueError, match="whole 128-lane tiles"):
        ss._lane_block(200, 512)
    assert ss._lane_block(5120, 1024) == 1024
    assert ss._lane_block(5120, 512) == 512
    assert ss._lane_block(384, 1024) == 384
