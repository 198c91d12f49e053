"""The grouped products of the dropless expert layer
(`ops/grouped_experts.py`): the rows kernel (`ragged-dot-rows`, through
the Pallas interpreter here) against a float32 loop over the experts and
against `jax.lax.ragged_dot`, and the one rule that picks between the
two kernels from static shapes."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from accelerate_tpu.ops import grouped_experts, kernel_mode
from accelerate_tpu.ops.grouped_experts import (
    ROWS_KERNEL_NAME,
    few_rows_an_expert,
    grouped_rows_matmul,
    grouped_swiglu_experts,
)

K, N = 256, 128


def _operands(sizes, dtype, k=K, n=N, seed=0):
    sizes = np.asarray(sizes, np.int32)
    kx, kw = jax.random.split(jax.random.key(seed))
    x = jax.random.normal(kx, (int(sizes.sum()), k), dtype)
    w = (jax.random.normal(kw, (len(sizes), k, n), jnp.float32)
         / np.sqrt(k)).astype(dtype)
    return x, w, jnp.asarray(sizes)


def _loop(x, w, sizes):
    """Each group's rows times its own matrix, in float32, one at a time."""
    x, w = np.asarray(x, np.float32), np.asarray(w, np.float32)
    out = np.zeros((x.shape[0], w.shape[2]), np.float32)
    at = 0
    for e, n in enumerate(np.asarray(sizes)):
        out[at:at + n] = x[at:at + n] @ w[e]
        at += n
    return out


def _spread(rows, experts, seed):
    """`rows` assignments over `experts` groups, some of them empty."""
    rng = np.random.default_rng(seed)
    return np.bincount(rng.integers(0, experts, rows), minlength=experts)


SIZES = {
    "rows-off-the-tile": [5, 9, 1, 22],             # 37 rows, tile 16
    "no-rows-first": [0, 0, 11, 7, 14],
    "no-rows-last": [13, 19, 0, 0],
    "runs-of-no-rows": [3, 0, 0, 0, 17, 0, 0, 12, 0],
    "one-takes-all": [0, 0, 48, 0],
    "first-takes-all": [48, 0, 0, 0],
    "one-row-each": [1] * 24,
    "one-row": [0, 1, 0],
    "windows-cross-groups": [17, 15, 33, 31, 16],   # starts off the tile
    "e4": _spread(40, 4, 1),
    "e64-few-rows": _spread(96, 64, 2),
    "e64-most-empty": _spread(12, 64, 3),
}


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16],
                         ids=["f32", "bf16"])
@pytest.mark.parametrize("case", SIZES)
def test_rows_kernel_against_the_loop_and_ragged_dot(case, dtype):
    x, w, sizes = _operands(SIZES[case], dtype)
    got = grouped_rows_matmul(x, w, sizes, row_tile=16, interpret=True)
    assert got.shape == (x.shape[0], N) and got.dtype == jnp.float32
    # bf16 operands multiply exactly into float32; only the order of the
    # sums differs between the three
    tol = 2e-5 if dtype == jnp.float32 else 1e-5
    np.testing.assert_allclose(got, _loop(x, w, sizes), atol=tol * 8, rtol=tol)
    ragged = jax.lax.ragged_dot(x, w, sizes,
                                preferred_element_type=jnp.float32)
    np.testing.assert_allclose(got, ragged, atol=tol * 8, rtol=tol)


@pytest.mark.parametrize("row_tile", [32, 64, 128])
def test_rows_kernel_windows(row_tile):
    """A window wider than a group, and than all the rows, gives the same
    product: the rows are padded up to one window and what a window holds
    beside the group is masked."""
    x, w, sizes = _operands([5, 0, 9, 1, 22, 0, 40], jnp.bfloat16, n=256)
    got = grouped_rows_matmul(x, w, sizes, row_tile=row_tile, interpret=True)
    np.testing.assert_allclose(got, _loop(x, w, sizes), atol=1e-4, rtol=1e-5)


@pytest.mark.parametrize("lane_tile", [128, 256])
def test_rows_kernel_in_blocks_of_lanes(lane_tile):
    """Under `lane_tile` a step holds a [K, lane_tile] block of an
    expert's matrix and the groups are walked once a block: the same
    product, and rows behind the last group come back 0."""
    x, w, sizes = _operands([5, 0, 9, 1, 22, 0, 40], jnp.bfloat16, n=256)
    x = jnp.concatenate([x, jnp.ones((19, x.shape[1]), x.dtype)])
    got = np.asarray(grouped_rows_matmul(x, w, sizes, row_tile=32,
                                         lane_tile=lane_tile, interpret=True))
    np.testing.assert_allclose(got[:77], _loop(x[:77], w, sizes), atol=1e-4,
                               rtol=1e-5)
    assert np.abs(got[77:]).max() == 0.0


def test_rows_kernel_refuses_a_block_off_the_lane_tile():
    x, w, sizes = _operands([4, 4], jnp.float32, n=256)
    for bad in (64, 384):
        with pytest.raises(ValueError, match="lane"):
            grouped_rows_matmul(x, w, sizes, lane_tile=bad, interpret=True)


def test_rows_kernel_masks_other_groups_rows():
    """A window holds rows of the groups beside it; they must not reach
    the sums even where they are not finite."""
    x, w, sizes = _operands([3, 4, 2], jnp.float32)
    x = x.at[3:7].set(jnp.inf)          # the middle group's rows
    got = np.asarray(grouped_rows_matmul(x, w, sizes, row_tile=16,
                                         interpret=True))
    want = _loop(x.at[3:7].set(0), w, sizes)
    np.testing.assert_allclose(got[:3], want[:3], atol=1e-4)
    np.testing.assert_allclose(got[7:], want[7:], atol=1e-4)


def test_group_walk_puts_the_groups_with_rows_first():
    ids, offsets, live = grouped_experts._group_walk(
        jnp.asarray([0, 3, 0, 0, 2, 7, 0]))
    assert int(live) == 3
    assert ids.tolist()[:3] == [1, 4, 5]
    assert all(0 <= i < 7 for i in ids.tolist())    # never walked, in range
    assert offsets.tolist() == [0, 0, 3, 3, 3, 5, 12, 12]


def test_rows_kernel_refuses_a_window_off_the_sublane_tile():
    x, w, sizes = _operands([4, 4], jnp.float32)
    with pytest.raises(ValueError, match="sublane"):
        grouped_rows_matmul(x, w, sizes, row_tile=24, interpret=True)


# ---------------------------------------------------------------------------
# the rule, and the layer around either kernel
# ---------------------------------------------------------------------------

CELL_SHAPES = {
    # (tokens, top_k, experts, hidden, expert width) -> the rows kernel?
    "mellum-decode": ((48, 8, 64, 2304, 896), True),
    "joyai-decode": ((16, 8, 256, 2048, 768), True),
    "mellum-chunk": ((512, 8, 64, 2304, 896), False),
    "joyai-chunk": ((512, 8, 256, 2048, 768), False),
}


def _kernels_of_the_layer(T, k, E, h, f):
    """Which grouped products the expert layer's traced program holds:
    (`ragged_dot` calls, calls of the rows kernel under its name)."""
    def sds(shape, dtype):
        return jax.ShapeDtypeStruct(shape, dtype)

    eqns = jax.make_jaxpr(grouped_swiglu_experts)(
        sds((T, h), jnp.bfloat16), sds((T, k), jnp.int32),
        sds((T, k), jnp.float32), sds((E, h, f), jnp.bfloat16),
        sds((E, h, f), jnp.bfloat16), sds((E, f, h), jnp.bfloat16)).eqns
    names = [e.primitive.name for e in eqns]
    kernels = [e.params["name"] for e in eqns
               if e.primitive.name == "pallas_call"]
    assert set(kernels) <= {ROWS_KERNEL_NAME}, kernels
    return sum(n.startswith("ragged_dot") for n in names), len(kernels)


@pytest.mark.parametrize("cell", CELL_SHAPES)
def test_the_rule_picks_a_kernel_from_the_cells_shapes(cell):
    (T, k, E, h, f), rows_kernel = CELL_SHAPES[cell]
    assert few_rows_an_expert(T * k, E, h, f) is rows_kernel
    # three products a layer, all of one kind
    assert _kernels_of_the_layer(T, k, E, h, f) == (
        (0, 3) if rows_kernel else (3, 0))


def test_the_rule_keeps_what_the_rows_kernel_cannot_block_on_ragged_dot():
    """The tiny test models (hidden 64, experts of 32) are off the lane
    tile and a Mixtral-sized expert (4096 x 14336) is no VMEM block: they
    stay on `ragged_dot` at any rows an expert."""
    assert not few_rows_an_expert(16, 8, 64, 32)
    assert few_rows_an_expert(16, 8, 128, 256)
    assert not few_rows_an_expert(16, 8, 4096, 14336)
    assert few_rows_an_expert(16, 8, 2048, 2048)
    assert not few_rows_an_expert(16, 8, 2048, 2048, jnp.float32)
    assert not few_rows_an_expert(
        grouped_experts.ROWS_KERNEL_BELOW * 8, 8, 128, 256)


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16],
                         ids=["f32", "bf16"])
@pytest.mark.parametrize("T,E", [(6, 4), (12, 64), (40, 4)],
                         ids=["rows-kernel-e4", "rows-kernel-e64",
                              "ragged-dot-e4"])
def test_expert_layer_against_the_masked_combine(T, E, dtype):
    """The layer through whichever kernel the rule picks (the rows kernel
    for the first two shapes, `ragged_dot` at 20 rows an expert) against
    every expert on every token, masked: same operands, float32 sums."""
    k, h, f = 2, 128, 128
    assert few_rows_an_expert(T * k, E, h, f, dtype) == (T * k // E < 8)
    keys = jax.random.split(jax.random.key(7), 6)
    x = jax.random.normal(keys[0], (T, h), dtype)
    gate, up = (jax.random.normal(kk, (E, h, f), dtype) / np.sqrt(h)
                for kk in keys[1:3])
    down = jax.random.normal(keys[3], (E, f, h), dtype) / np.sqrt(f)
    experts = jnp.stack([jax.random.permutation(kk, E)[:k] for kk in
                         jax.random.split(keys[4], T)]).astype(jnp.int32)
    weights = jax.nn.softmax(jax.random.normal(keys[5], (T, k)), axis=-1)
    before = dict(kernel_mode.kernel_report())
    got = grouped_swiglu_experts(x, experts, weights, gate, up, down)
    if T * k // E < 8:
        assert kernel_mode.kernel_report()[ROWS_KERNEL_NAME] == "interpret"
    else:
        assert kernel_mode.kernel_report() == before

    def dot(a, b):
        return jnp.einsum("th,ehf->etf", a, b,
                          preferred_element_type=jnp.float32)

    act = (jax.nn.silu(dot(x, gate)) * dot(x, up)).astype(dtype)
    every = jnp.einsum("etf,efh->eth", act, down,
                       preferred_element_type=jnp.float32)      # [E, T, h]
    hit = (experts[None] == jnp.arange(E)[:, None, None])        # [E, T, k]
    want = jnp.einsum("eth,etk,tk->th", every, hit.astype(jnp.float32),
                      weights)
    # in bf16 the activation is rounded between the products, and a
    # rounding flips with the order of the float32 sums before it
    tol = 2e-4 if dtype == jnp.float32 else 3e-2
    np.testing.assert_allclose(got, want, atol=tol, rtol=tol)
