"""`ops/power_retention.py` on the CPU, float32: `phi`'s layout gives the
power of the inner product; the recurrence, the chunk form and the decode
step (plain `jax.numpy` and the Pallas kernels, interpreted) all give the
`[positions, positions]` form's numbers; a padded row and a dead lane
leave a state as it was, bit for bit; the quadratic forms that stand in for
`phi` in the normaliser are `phi`'s."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from accelerate_tpu.ops import power_retention as pr

# 16-lane heads keep the interpreted kernels quick (9 rows of `phi` where
# 128 lanes have 65); one test runs both kernels at the chip's 128 lanes
D_HEAD, G, HG, T, LAYERS, ENTRIES = 16, 2, 3, 22, 2, 3


def _rows(d=D_HEAD, t=T):
    k = jax.random.split(jax.random.key(0), 4)
    return (jax.random.normal(k[0], (t, G, HG, d)),
            jax.random.normal(k[1], (t, G, d)),
            jax.random.normal(k[2], (t, G, d)),
            jax.nn.log_sigmoid(jax.random.normal(k[3], (t, G)) + 3.0))


@pytest.fixture(scope="module")
def rows():
    return _rows()


def _pool(degree, kernel, dtype=jnp.float32, fill=0.0, d=D_HEAD):
    D = pr.state_rows(d, degree)
    lead = (LAYERS, ENTRIES + 1, G)
    return pr.StatePool(
        jnp.full(lead + (D, d), fill, dtype),
        jnp.full(lead + (pr.normaliser_rows(d, degree), d), fill, dtype),
        kernel)


def _interpret(kernel):
    return True if kernel else None


@pytest.mark.parametrize("d", [2, 16, 128])
def test_phi_of_two_vectors_gives_the_square_of_their_product(d):
    a, b = jax.random.normal(jax.random.key(1), (2, 7, d))
    got = jnp.sum(pr.phi(a) * pr.phi(b), axis=-1)
    np.testing.assert_allclose(got, jnp.sum(a * b, axis=-1) ** 2, rtol=2e-5,
                               atol=1e-5)
    assert pr.phi(a).shape == (7, pr.state_rows(d))
    np.testing.assert_array_equal(pr.phi(a, degree=1), a)


def test_the_layouts_sizes():
    assert pr.feature_rows(128) == 65 and pr.state_rows(128) == 8320
    assert pr.state_rows(128) - 128 * 129 // 2 == 64      # 0.8% over 8,256
    assert pr.normaliser_rows(128) == 72 and pr.normaliser_rows(128, 1) == 8
    assert pr.state_rows(128, 1) == 128 and pr.state_rows(16) == 9 * 16
    with pytest.raises(ValueError, match="degree 1 or 2"):
        pr.state_rows(128, 3)
    with pytest.raises(ValueError, match="even number of lanes"):
        pr.state_rows(7)


@pytest.mark.parametrize("degree", [2, 1])
def test_the_recurrence_gives_the_quadratic_forms_numbers(rows, degree):
    q, k, v, gamma = rows
    want = pr.retention_quadratic(q, k, v, gamma, degree=degree)
    got, S, z = pr.retention_recurrent(q, k, v, gamma, degree=degree)
    scale = float(jnp.max(jnp.abs(want)))
    assert float(jnp.max(jnp.abs(got - want))) < 1e-4 * scale
    assert S.shape == (G, pr.state_rows(D_HEAD, degree), D_HEAD)


def _serve(rows, degree, kernel, chunk=8, prompt=14, dtype=jnp.float32):
    """The first `prompt` rows in chunks of `chunk` (the last one padded),
    the rest one token at a time as lane 1 of three (lanes 0 and 2 dead),
    all through entry 2 of layer 1. -> (outputs [T, G, HG, d], the pool)."""
    q, k, v, gamma = rows
    d, steps = q.shape[-1], q.shape[0]
    pool = _pool(degree, kernel, dtype, d=d)
    out = []

    def pad(x):
        return jnp.concatenate([x, jnp.zeros(
            (chunk - x.shape[0],) + x.shape[1:], x.dtype)])[None]

    for a in range(0, prompt, chunk):
        b = min(a + chunk, prompt)
        o, pool = pr.retention_chunk(
            pad(q[a:b]).reshape(1, chunk, G * HG, d), pad(k[a:b]),
            pad(v[a:b]), pad(gamma[a:b]), pool, 1, jnp.array([2]),
            degree=degree, interpret=_interpret(kernel))
        out.append(o[0, :b - a].reshape(b - a, G, HG, d))
    meta = pr.StateMeta(jnp.array([0, 2, 1], jnp.int32),
                        jnp.array([0, 1, 0], jnp.int32))
    for t in range(prompt, steps):
        o, pool = pr.retention_decode_step(
            jnp.stack([q[t]] * 3).reshape(3, G * HG, d),
            jnp.stack([k[t]] * 3), jnp.stack([v[t]] * 3),
            jnp.stack([gamma[t]] * 3), pool, 1, meta, degree=degree,
            interpret=_interpret(kernel))
        assert float(jnp.abs(o[0]).max()) == float(jnp.abs(o[2]).max()) == 0
        out.append(o[1].reshape(1, G, HG, d))
    return jnp.concatenate(out), pool


@pytest.fixture(scope="module")
def served(rows):
    """`_serve` of every (degree, kernel), made once."""
    return {(degree, kernel): _serve(rows, degree, kernel)
            for degree in (2, 1) for kernel in (False, True)}


@pytest.mark.parametrize("kernel", [False, True], ids=["dense", "kernel"])
@pytest.mark.parametrize("degree", [2, 1])
def test_chunks_then_steps_give_the_quadratic_forms_numbers(rows, served,
                                                            degree, kernel):
    q, k, v, gamma = rows
    want = pr.retention_quadratic(q, k, v, gamma, degree=degree)
    got, pool = served[degree, kernel]
    scale = float(jnp.max(jnp.abs(want)))
    assert float(jnp.max(jnp.abs(got - want))) < 2e-4 * scale
    # the state after the last token is the recurrence's, and nothing but
    # entry 2 of layer 1 (and the spare) was written
    _, S, z = pr.retention_recurrent(q, k, v, gamma, degree=degree)
    n_o = pr.feature_rows(D_HEAD, degree)
    np.testing.assert_allclose(pool.s[1, 2], S, rtol=1e-4,
                               atol=1e-5 * float(jnp.abs(S).max()))
    np.testing.assert_allclose(pool.z[1, 2][:, :n_o].reshape(G, -1), z,
                               rtol=1e-4, atol=1e-5 * float(jnp.abs(z).max()))
    assert float(jnp.abs(pool.s[0]).max()) == 0.0
    assert float(jnp.abs(pool.s[1, :2]).max()) == 0.0
    assert float(jnp.abs(pool.z[1, :2]).max()) == 0.0


@pytest.mark.parametrize("degree", [2, 1])
def test_the_kernels_give_the_dense_forms_numbers(served, degree):
    dense, pool_d = served[degree, False]
    kernel, pool_k = served[degree, True]
    scale = float(jnp.abs(dense).max())
    assert float(jnp.abs(dense - kernel).max()) < 1e-4 * scale
    np.testing.assert_allclose(pool_k.s[1, 2], pool_d.s[1, 2], rtol=1e-4,
                               atol=1e-5 * float(jnp.abs(pool_d.s).max()))


@pytest.mark.parametrize("kernel", [False, True], ids=["dense", "kernel"])
def test_a_chunk_of_padding_leaves_the_state_as_it_was(kernel):
    """`gamma` 0 and `k` = `v` = 0 in every row: no decay, nothing added."""
    pool = _pool(2, kernel, fill=0.5)
    C = 8
    q = jax.random.normal(jax.random.key(2), (1, C, G * HG, D_HEAD))
    zero = jnp.zeros((1, C, G, D_HEAD))
    _, after = pr.retention_chunk(q, zero, zero, jnp.zeros((1, C, G)), pool,
                                  0, jnp.array([1]),
                                  interpret=_interpret(kernel))
    np.testing.assert_array_equal(after.s[:, :ENTRIES], pool.s[:, :ENTRIES])
    np.testing.assert_array_equal(after.z[:, :ENTRIES], pool.z[:, :ENTRIES])


@pytest.mark.parametrize("kernel", [False, True], ids=["dense", "kernel"])
def test_a_dead_lane_touches_no_state_but_the_spares(rows, kernel):
    """Three lanes that all NAME entry 0; only the middle one is live: the
    entries keep their bytes except entry 0 of the layer, which the live
    lane advanced once (and the spare, which nobody reads)."""
    q, k, v, gamma = rows
    pool = _pool(2, kernel, fill=0.25)
    meta = pr.StateMeta(jnp.zeros((3,), jnp.int32),
                        jnp.array([0, 1, 0], jnp.int32))
    o, after = pr.retention_decode_step(
        q[:3].reshape(3, G * HG, D_HEAD), k[:3], v[:3], gamma[:3], pool, 1,
        meta, interpret=_interpret(kernel))
    np.testing.assert_array_equal(after.s[0, :ENTRIES], pool.s[0, :ENTRIES])
    np.testing.assert_array_equal(after.s[1, 1:ENTRIES], pool.s[1, 1:ENTRIES])
    np.testing.assert_array_equal(after.z[1, 1:ENTRIES], pool.z[1, 1:ENTRIES])
    one = pr.StateMeta(jnp.zeros((1,), jnp.int32), jnp.ones((1,), jnp.int32))
    o1, alone = pr.retention_decode_step(
        q[1:2].reshape(1, G * HG, D_HEAD), k[1:2], v[1:2], gamma[1:2], pool,
        1, one, interpret=_interpret(kernel))
    np.testing.assert_array_equal(after.s[1, 0], alone.s[1, 0])
    np.testing.assert_array_equal(o[1], o1[0])
    assert float(jnp.abs(o[0]).max()) == 0.0


def test_both_kernels_at_the_chips_128_lanes():
    """One chunk of 8 rows (2 padded) and two decode steps at 128-lane
    heads: 65 rows of `phi`, 13 a block, as the chip runs them."""
    rows = _rows(128, 8)
    want = pr.retention_quadratic(*rows)
    got, pool = _serve(rows, 2, True, chunk=8, prompt=6)
    assert pool.s.shape[-2:] == (8320, 128) and pool.z.shape[-2:] == (72, 128)
    scale = float(jnp.abs(want).max())
    assert float(jnp.abs(got - want).max()) < 2e-4 * scale


def test_a_state_kept_in_bfloat16_is_close_and_stays_bfloat16(rows):
    q, k, v, gamma = rows
    want = pr.retention_quadratic(q, k, v, gamma)
    got, pool = _serve(rows, 2, True, dtype=jnp.bfloat16)
    assert pool.s.dtype == pool.z.dtype == jnp.bfloat16
    scale = float(jnp.abs(want).max())
    assert 1e-5 * scale < float(jnp.abs(got - want).max()) < 0.05 * scale


@pytest.mark.parametrize("degree", [2, 1])
def test_the_normalisers_quadratic_forms_are_phis(degree):
    """`phi(q) . z` and `sum_s w_s phi(k_s)` without `phi`: two products of
    d x d matrices a head (p = 2) against the 8,320-wide sums."""
    B, C = 2, 5
    key = jax.random.split(jax.random.key(3), 4)
    q = jax.random.normal(key[0], (B, C, G, HG, D_HEAD))
    k = jax.random.normal(key[1], (B, C, G, D_HEAD))
    w = jax.random.uniform(key[2], (B, C, G))
    n_o = pr.feature_rows(D_HEAD, degree)
    z0 = jax.random.normal(key[3], (B, G, n_o, D_HEAD))
    den, inc = pr._normaliser(q, k, w, z0, degree)
    want_den = jnp.einsum("bcghD,bgD->bghc", pr.phi(q, degree),
                          z0.reshape(B, G, -1))
    want_inc = jnp.einsum("bcgD,bcg->bgD", pr.phi(k, degree), w)
    np.testing.assert_allclose(den, want_den, rtol=2e-4, atol=2e-3)
    np.testing.assert_allclose(inc.reshape(B, G, -1), want_inc, rtol=2e-4,
                               atol=2e-4)


def test_the_decode_kernel_wants_value_heads_as_wide_as_key_heads():
    pool = pr.StatePool(jnp.zeros((1, 2, 1, pr.state_rows(128), 64)),
                        jnp.zeros((1, 2, 1, 72, 128)), True)
    with pytest.raises(ValueError, match="as wide as key heads"):
        pr.retention_decode_step(
            jnp.zeros((1, 1, 128)), jnp.zeros((1, 1, 128)),
            jnp.zeros((1, 1, 64)), jnp.zeros((1, 1)), pool, 0,
            pr.StateMeta(jnp.zeros((1,), jnp.int32),
                         jnp.ones((1,), jnp.int32)), interpret=True)
