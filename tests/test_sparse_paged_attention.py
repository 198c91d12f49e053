"""`ops/sparse_paged_attention.py` on the CPU (kernels interpreted): the
exact selection against a sort, ties and `-inf` included; the rows kernel
of the same selection (a prefill chunk's) bit for bit against it, under
every live bound; the indexer-score
kernel and the sparse attention kernel against plain gathers; the sparse
kernel equal to the dense live-pages kernel while nothing is left out; and
a page that holds no selected key is never copied."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from accelerate_tpu.ops import kernel_mode
from accelerate_tpu.ops import sparse_paged_attention as sparse
from accelerate_tpu.ops.paged_attention import (
    PagedDecodeMeta,
    PagedKV,
    paged_decode_attention,
)


def _oracle(scores: np.ndarray, k: int) -> np.ndarray:
    """Row by row: the visible positions sorted by (score down, position
    up), the first k."""
    out = np.zeros(scores.shape, bool)
    for r, row in enumerate(scores):
        visible = np.nonzero(row > -np.inf)[0]
        order = sorted(visible, key=lambda i: (-row[i], i))[:k]
        out[r, order] = True
    return out


def _score_rows():
    rng = np.random.default_rng(0)
    x = rng.normal(size=(7, 300)).astype(np.float32)
    x[0, :50] = 0.0                      # a run of equal scores (ReLU zeros)
    x[1, :] = 1.0                        # every score equal
    x[2, 100:] = -np.inf                 # 100 visible
    x[3, :] = -np.inf
    x[3, :7] = 2.0                       # 7 visible, all equal
    x[4, ::3] = 0.5                      # ties spread over the row
    x[5, :] = -np.inf                    # nothing visible
    x[6, 150] = -0.0                     # the two zeros are one value
    x[6, 10] = 0.0
    return x


@pytest.mark.parametrize("k", [1, 7, 50, 64, 100, 299, 300, 400])
def test_exact_topk_mask_is_the_sorted_selection(k):
    x = _score_rows()
    got = np.asarray(sparse.exact_topk_mask(jnp.asarray(x), k))
    np.testing.assert_array_equal(got, _oracle(x, k))
    visible = (x > -np.inf).sum(-1)
    np.testing.assert_array_equal(got.sum(-1), np.minimum(visible, k))


def test_exact_topk_mask_takes_leading_axes_and_the_lower_of_equal_zeros():
    x = _score_rows().reshape(1, 7, 300)
    got = np.asarray(sparse.exact_topk_mask(jnp.asarray(x), 30))[0]
    np.testing.assert_array_equal(got, _oracle(x[0], 30))
    # row 6: -0.0 at 150 and +0.0 at 10 tie; whichever is kept last, the
    # lower position goes first
    row = np.array([[0.0, -0.0, -1.0, -0.0]], np.float32)
    got = np.asarray(sparse.exact_topk_mask(jnp.asarray(row), 2))
    np.testing.assert_array_equal(got, [[True, True, False, False]])


def _chunk_scores(case: str):
    """-> (float32 scores, k, live or None) for one case of the rows
    kernel: several row tiles and counting steps of tiny scores, the
    columns at or past `live` at `-inf` as the kernel's callers hold
    them."""
    rng = np.random.default_rng(7)
    x = rng.normal(size=(2, 40, 1300)).astype(np.float32)
    live, k = None, 50
    if case == "k-equals-visible":
        live = k = 200
    elif case == "k-above-visible":
        live, k = 200, 900
    elif case == "k-above-every-column":
        k = 5000
    elif case == "ties-that-must-be-cut":
        x = np.round(x * 2) / 2
    elif case == "every-score-equal":
        x[:], k = 1.0, 77
    elif case == "both-zeros-are-one-value":
        x = np.where(x > 0.3, 1.0, np.where(x > 0, 0.0, np.where(
            x > -0.3, -0.0, -1.0))).astype(np.float32)
        k = 700
    elif case == "rows-of-all-minus-inf":
        x[:, ::3] = -np.inf
        x[1, 1, 5] = 3.0
    elif case == "live-bound-0":
        live = 0
    elif case == "live-bound-inside-a-step":
        live = 700
    elif case == "live-bound-equals-n":
        live = 1300
    elif case == "whole-tiles-no-padding":
        x = np.round(rng.normal(size=(1, 64, 1024)) * 8).astype(np.float32)
        live = 1024
    elif case == "leading-axes-a-bound-each":
        x = np.round(rng.normal(size=(2, 3, 33, 600)) * 4).astype(np.float32)
        live, k = np.array([[600, 0, 17], [512, 513, 300]], np.int32), 24
    elif case == "a-bound-a-batch-row":
        live = np.array([700, 1300], np.int32)
    elif case == "fewer-rows-than-a-tile":
        x, live = x[:, :8], 900
    elif case in ("blocks-as-they-were-scored", "blocks-under-a-live-bound"):
        # 1,300 columns scored in three blocks of 512: 236 past the view
        x = np.round(rng.normal(size=(2, 40, 1536)) * 2).astype(np.float32) / 2
        x[:, :, 1300:] = -np.inf
        live, k = (None, 300) if case.endswith("scored") else (700, 50)
    elif case == "blocks-no-step-divides":
        x = rng.normal(size=(2, 40, 1344)).astype(np.float32)  # 21 x 64
        x[:, :, 1300:] = -np.inf
    else:
        assert case == "k-below-visible", case
    if live is not None:
        col = np.arange(x.shape[-1])
        x = np.where(col >= np.asarray(live)[..., None, None], -np.inf,
                     x).astype(np.float32)
    return x, k, live


@pytest.mark.parametrize("case", [
    "k-below-visible", "k-equals-visible", "k-above-visible",
    "k-above-every-column", "ties-that-must-be-cut", "every-score-equal",
    "both-zeros-are-one-value", "rows-of-all-minus-inf", "live-bound-0",
    "live-bound-inside-a-step", "live-bound-equals-n",
    "whole-tiles-no-padding", "leading-axes-a-bound-each",
    "a-bound-a-batch-row", "fewer-rows-than-a-tile",
    "blocks-as-they-were-scored", "blocks-under-a-live-bound",
    "blocks-no-step-divides"])
def test_rows_kernel_selects_what_exact_topk_mask_selects(case):
    """`exact_topk_mask_rows` (interpreted) against `exact_topk_mask`, bit
    for bit: k below, at and above the visible count, ties at the k-th
    value that must be cut (the lower positions win), `-0.0 == +0.0`,
    rows that see nothing, every kind of live bound, a view the counting
    step does not divide, leading axes, and scores handed over in the
    blocks of columns they were scored in (read where they lie when a
    block is whole counting steps, laid side by side first when not)."""
    x, k, live = _chunk_scores(case)
    select = jax.jit(sparse.exact_topk_mask_rows, static_argnums=(1, 3))
    bound = x.shape[-1] if live is None else live
    if case.startswith("blocks"):
        block = 64 if case == "blocks-no-step-divides" else 512
        blocks = np.moveaxis(x.reshape(2, 40, -1, block), 2, 0)
        x = x[:, :, :1300]
        got = np.asarray(select(blocks, k, bound, 1300))
    else:
        # jitted: the cases of one shape and k share a compile
        got = np.asarray(select(x, k, bound, None))
    # the reference is handed +0.0 for -0.0: jitted on the CPU XLA folds
    # its `x + 0.0` away and it would order the two zeros
    want = np.asarray(jax.jit(sparse.exact_topk_mask, static_argnums=1)(
        x + np.float32(0.0), k))
    assert got.dtype == want.dtype == np.bool_ and got.shape == x.shape
    np.testing.assert_array_equal(got, want)
    visible = (x > -np.inf).sum(-1)
    np.testing.assert_array_equal(got.sum(-1), np.minimum(visible, k))
    if case == "ties-that-must-be-cut":
        np.testing.assert_array_equal(got[1], _oracle(x[1], k))
        assert (got.sum(-1) < (x >= np.where(got, x, np.inf).min(
            -1, keepdims=True)).sum(-1)).any()      # some tie was left out
    scanned, total = sparse.selection_columns(x.shape, live)
    assert int(total) == x.size
    if case == "live-bound-inside-a-step":
        assert int(scanned) == 2 * 40 * 1024        # two whole steps a row
    elif case == "leading-axes-a-bound-each":
        assert int(scanned) == 33 * (600 + 0 + 512 + 512 + 600 + 512)
    elif case == "fewer-rows-than-a-tile" or live is None:
        assert int(scanned) == int(total)           # XLA's loop, or no bound


def _equations(jaxpr):
    """Equations of a traced program, those of what it calls, loops over
    and hands a kernel included."""
    return sum(1 + sum(_equations(inner)
                       for inner in jax.core.jaxprs_in_params(eqn.params))
               for eqn in jaxpr.eqns)


@pytest.mark.parametrize("blocked", [False, True],
                         ids=["row-major", "blocks-as-scored"])
def test_what_the_rows_kernel_costs_to_lower_does_not_grow_with_the_view(
        monkeypatch, blocked):
    """Tracing and lowering run in every process before any compile cache
    is asked, so the kernel's counting steps are LOOPS in its body, not
    ladders of conditionals a step: lowered for the chip over a view of
    4,096 columns (8 counting steps) and of 43,520 (85), the program has
    the same number of equations and a text of the same size (the ladders
    read 25 KB against 109 KB)."""
    monkeypatch.setattr(kernel_mode, "resolve_interpret",
                        lambda name, interpret=None: False)
    sizes = []
    for columns in (4096, 43520):
        shape = (-(-columns // 1024), 1, 512, 1024) if blocked else (
            1, 512, columns)
        traced = jax.jit(
            lambda x, live, columns=columns: sparse.exact_topk_mask_rows(
                x, 2048, live, columns=columns if blocked else None)
        ).trace(jax.ShapeDtypeStruct(shape, jnp.float32),
                jax.ShapeDtypeStruct((), jnp.int32))
        text = traced.lower(lowering_platforms=("tpu",)).as_text()
        assert text.count("tpu_custom_call") == 1
        sizes.append((_equations(traced.jaxpr.jaxpr), len(text)))
    (few, small), (many, large) = sizes
    assert few == many and few > 100, sizes
    assert large < 1.5 * small and large < 40_000, sizes


def _index_pool(rng, layers, pages, page_size, w):
    shape = (layers, pages + 1, page_size * w // 128, 128)
    return jnp.asarray(rng.normal(size=shape).astype(np.float32))


@pytest.mark.parametrize("w,page_size", [(64, 16), (32, 8), (128, 4)],
                         ids=["two-a-row", "four-a-row", "a-row-each"])
def test_indexer_paged_scores_match_a_plain_gather(w, page_size):
    rng = np.random.default_rng(1)
    L, pages, S, P, J = 2, 40, 4, 9, 5
    side = _index_pool(rng, L, pages, page_size, w)
    table = jnp.asarray(rng.permutation(pages)[:S * P].reshape(S, P)
                        .astype(np.int32))
    R = P * page_size
    lengths = jnp.asarray([0, 1, R // 2 + 3, R - 1], jnp.int32)
    meta = PagedDecodeMeta(table, lengths, rows=R)
    q = jnp.asarray(rng.normal(size=(S, J, w)).astype(np.float32))
    wts = jnp.asarray(rng.normal(size=(S, J)).astype(np.float32))
    pool = PagedKV(side, None, jnp.float32, jnp.int32(1))
    got = np.asarray(sparse.indexer_paged_scores(q, wts, pool, meta,
                                                 page_size))
    want = np.asarray(sparse.indexer_paged_scores_reference(
        q, wts, pool, meta, page_size))
    assert got.shape == (S, R)
    np.testing.assert_allclose(got, want, rtol=1e-5, atol=1e-5)
    # by hand, one entry: slot 2, position 5
    page, off = int(table[2, 5 // page_size]), 5 % page_size
    key = np.asarray(side[1, page]).reshape(page_size, w)[off]
    by_hand = (np.maximum(np.asarray(q[2]) @ key, 0) * np.asarray(wts[2])).sum()
    assert got[2, 5] == pytest.approx(by_hand, rel=1e-4, abs=1e-5)
    assert np.isneginf(got[0]).all() and np.isneginf(got[1, 1:]).all()


def _kv_setup(seed=2, S=3, P=8, ps=16, Hkv=2, G=4, D=128, L=2, pages=40):
    rng = np.random.default_rng(seed)
    pool = lambda: jnp.asarray(rng.normal(  # noqa: E731
        size=(L, pages + 1, Hkv, ps, D)).astype(np.float32))
    pk = PagedKV(pool(), None, jnp.float32, jnp.int32(1))
    pv = PagedKV(pool(), None, jnp.float32, jnp.int32(1))
    table = jnp.asarray(rng.permutation(pages)[:S * P].reshape(S, P)
                        .astype(np.int32))
    lengths = jnp.asarray([0, 37, P * ps - 8][:S], jnp.int32)
    meta = PagedDecodeMeta(table, lengths, rows=P * ps)
    q = jnp.asarray(rng.normal(size=(S, 1, Hkv * G, D)).astype(np.float32))
    kn, vn = (jnp.asarray(rng.normal(size=(S, 1, Hkv, D)).astype(np.float32))
              for _ in range(2))
    scores = jnp.where(
        jnp.arange(P * ps)[None, :] <= lengths[:, None],
        jnp.asarray(rng.normal(size=(S, P * ps)).astype(np.float32)),
        -jnp.inf)
    return q, kn, vn, pk, pv, meta, scores


@pytest.mark.parametrize("k", [1, 5, 30, 100])
def test_sparse_attention_kernel_matches_a_plain_gather(k):
    q, kn, vn, pk, pv, meta, scores = _kv_setup()
    select = sparse.exact_topk_mask(scores, k)
    got, (k_row, v_row) = sparse.sparse_paged_decode_attention(
        q, kn, vn, pk, pv, meta, select)
    want, _ = sparse.sparse_paged_decode_reference(q, kn, vn, pk, pv, meta,
                                                   select)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), rtol=2e-5,
                               atol=2e-5)
    np.testing.assert_array_equal(np.asarray(k_row), np.asarray(kn))
    np.testing.assert_array_equal(np.asarray(v_row), np.asarray(vn))


def test_sparse_attention_with_tied_scores_takes_the_lower_positions():
    q, kn, vn, pk, pv, meta, scores = _kv_setup(seed=3)
    # every visible score equal: the selection is positions 0..k-1, and the
    # new token (the highest position) is left out
    tied = jnp.where(scores > -jnp.inf, 1.0, -jnp.inf)
    select = sparse.exact_topk_mask(tied, 20)
    lengths = np.asarray(meta.lengths)
    want_mask = np.arange(select.shape[1])[None, :] < np.minimum(
        lengths + 1, 20)[:, None]
    np.testing.assert_array_equal(np.asarray(select), want_mask)
    got, _ = sparse.sparse_paged_decode_attention(q, kn, vn, pk, pv, meta,
                                                  select)
    want, _ = sparse.sparse_paged_decode_reference(q, kn, vn, pk, pv, meta,
                                                   select)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), rtol=2e-5,
                               atol=2e-5)


def test_with_nothing_left_out_it_is_the_dense_kernel():
    """`length + 1 <= topk`: every position selected."""
    q, kn, vn, pk, pv, meta, scores = _kv_setup(seed=4)
    select = sparse.exact_topk_mask(scores, int(meta.rows))
    got, _ = sparse.sparse_paged_decode_attention(q, kn, vn, pk, pv, meta,
                                                  select)
    dense, _ = paged_decode_attention(q, kn, vn, pk, pv, meta)
    np.testing.assert_allclose(np.asarray(got), np.asarray(dense), rtol=1e-6,
                               atol=1e-6)


def test_the_walk_ends_with_the_pages_that_hold_a_selected_key():
    """80 pages a slot, 6 keys selected: the kernel copies ONE group of 32
    pages (the selected ones first) of the 80. NaN in every other page of
    the pools: were one of them copied, its rows would meet a probability
    of 0, and 0 x NaN is NaN."""
    q, kn, vn, pk, pv, meta, scores = _kv_setup(seed=5, P=80, pages=300)
    ps = pk.data.shape[3]
    select = sparse.exact_topk_mask(scores, 6)
    table, bits, count, _ = sparse._compact_selection(select, meta, ps)
    count = np.asarray(count)
    assert (count <= 6).all() and count.max() > 1
    assert (np.asarray(bits)[:, 6:] == 0).all()
    walked = np.asarray(table)[:, :32][count > 0]
    poison = np.ones(pk.data.shape[1], bool)
    poison[walked] = False
    assert poison[np.asarray(meta.table)].sum() >= 2 * 48
    bad = [PagedKV(jnp.where(jnp.asarray(poison)[None, :, None, None, None],
                             jnp.nan, p.data), None, jnp.float32, p.layer)
           for p in (pk, pv)]
    got, _ = sparse.sparse_paged_decode_attention(q, kn, vn, *bad, meta,
                                                  select)
    want, _ = sparse.sparse_paged_decode_reference(q, kn, vn, pk, pv, meta,
                                                   select)
    assert np.isfinite(np.asarray(got)).all()
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), rtol=2e-5,
                               atol=2e-5)


def test_the_kernels_refuse_what_they_do_not_take():
    q, kn, vn, pk, pv, meta, scores = _kv_setup()
    select = sparse.exact_topk_mask(scores, 4)
    with pytest.raises(ValueError, match="one token a slot"):
        sparse.sparse_paged_decode_attention(
            q, kn, vn, PagedKV(pk.data, None, jnp.float32, None), pv, meta,
            select)
    side = PagedKV(jnp.zeros((2, 41, 8, 128)), None, jnp.float32, None)
    with pytest.raises(ValueError, match="an index pool is"):
        sparse.indexer_paged_scores(jnp.zeros((3, 4, 64)), jnp.zeros((3, 4)),
                                    side, meta, 16)
