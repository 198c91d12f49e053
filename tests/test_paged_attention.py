"""Pallas paged-attention decode kernel (ops/paged_attention.py).

Interpret-mode exactness vs the dense-gather reference across the page
geometry the serving engine actually produces — page-boundary lengths,
mid-page lengths, GQA head groups, trash-padded table rows, sliding
windows, reused (stale-content) pages — plus the int8-pool in-kernel
dequantization and the `PagedKV`/`PagedDecodeMeta` plumbing types the
family forwards thread."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from accelerate_tpu.ops.paged_attention import (
    PagedDecodeMeta,
    PagedKV,
    paged_decode_attention,
    paged_decode_reference,
)
from accelerate_tpu.ops.quant import kv_dequantize_rows, kv_quantize_rows


def _setup(seed=0, S=3, P=4, ps=8, Hkv=2, G=3, D=16, num_pages=12,
           quantized=False, dtype=jnp.float32):
    """A pool + table geometry exercising the engine's corner cases:
    slot 0 mid-page length, slot 1 exactly at a page boundary, slot 2
    nearly empty with a trash-padded table row."""
    rng = np.random.default_rng(seed)
    shape = (num_pages + 1, Hkv, ps, D)
    pool_k = jnp.asarray(rng.normal(size=shape), dtype)
    pool_v = jnp.asarray(rng.normal(size=shape), dtype)
    table = np.full((S, P), num_pages, np.int32)  # trash-padded
    fills = ([0, 1, 2], [3, 4], [5])
    for s in range(S):
        f = fills[s % 3][:P]
        table[s, :len(f)] = f
    lengths = jnp.asarray([min(ps + 5, P * ps - 1), min(2 * ps, P * ps),
                           2][:S], jnp.int32)
    q = jnp.asarray(rng.normal(size=(S, 1, Hkv * G, D)), jnp.float32)
    kn = jnp.asarray(rng.normal(size=(S, 1, Hkv, D)), jnp.float32)
    vn = jnp.asarray(rng.normal(size=(S, 1, Hkv, D)), jnp.float32)
    if quantized:
        ck, sk = kv_quantize_rows(pool_k)
        cv, sv = kv_quantize_rows(pool_v)
        pk = PagedKV(ck, sk, compute_dtype=dtype)
        pv = PagedKV(cv, sv, compute_dtype=dtype)
    else:
        pk, pv = PagedKV(pool_k), PagedKV(pool_v)
    meta = PagedDecodeMeta(jnp.asarray(table), lengths, rows=P * ps)
    return q, kn, vn, pk, pv, meta


def _assert_close(out, ref, tol=2e-5):
    err = float(jnp.max(jnp.abs(out.astype(jnp.float32)
                                - ref.astype(jnp.float32))))
    assert err < tol, f"max err {err}"


@pytest.mark.parametrize("window", [None, 5, 1000])
def test_kernel_matches_reference_geometry_matrix(window):
    """Mid-page / page-boundary / trash-padded slots, GQA groups, and
    sliding windows (incl. one wider than the cache = plain causal) all
    match the dense reference."""
    q, kn, vn, pk, pv, meta = _setup()
    out, (k_row, v_row) = paged_decode_attention(q, kn, vn, pk, pv, meta,
                                                 window=window)
    ref, (rk, rv) = paged_decode_reference(q, kn, vn, pk, pv, meta,
                                           window=window)
    _assert_close(out, ref)
    # the rows handed back for the engine to scatter are identical too
    # (same cast — the fold and the write must see the same bytes)
    assert jnp.array_equal(k_row, rk) and jnp.array_equal(v_row, rv)


def test_kernel_matches_reference_single_page_and_single_head():
    """Degenerate geometry: one page per slot, MHA (G=1)."""
    q, kn, vn, pk, pv, meta = _setup(S=2, P=1, ps=4, Hkv=3, G=1, D=8,
                                     num_pages=4)
    meta = PagedDecodeMeta(meta.table[:2, :1],
                           jnp.asarray([3, 0], jnp.int32), rows=4)
    out, _ = paged_decode_attention(q, kn, vn, pk, pv, meta)
    ref, _ = paged_decode_reference(q, kn, vn, pk, pv, meta)
    _assert_close(out, ref)


def test_kernel_length_zero_slot_attends_only_new_token():
    """A fresh slot (length 0, all-trash table) attends exactly its own
    new K/V — the output is vn, not trash-page garbage."""
    q, kn, vn, pk, pv, meta = _setup()
    meta = PagedDecodeMeta(meta.table,
                           jnp.zeros_like(meta.lengths), rows=meta.rows)
    out, _ = paged_decode_attention(q, kn, vn, pk, pv, meta)
    S, _, H, D = q.shape
    G = H // vn.shape[2]
    expect = jnp.repeat(vn[:, 0], G, axis=1).reshape(S, 1, H, D)
    _assert_close(out, expect)


def test_kernel_ignores_stale_rows_in_reused_pages():
    """Rows at or past `length` — stale K/V from a previous tenant of
    the page (slot reuse), or allocation slack — never leak into the
    output: poisoning them with huge values changes nothing."""
    q, kn, vn, pk, pv, meta = _setup()
    out0, _ = paged_decode_attention(q, kn, vn, pk, pv, meta)
    ps = pk.data.shape[2]
    poisoned_k, poisoned_v = np.asarray(pk.data).copy(), np.asarray(
        pv.data).copy()
    table, lengths = np.asarray(meta.table), np.asarray(meta.lengths)
    for s in range(table.shape[0]):
        for j, page in enumerate(table[s]):
            for r in range(ps):
                if j * ps + r >= lengths[s]:
                    poisoned_k[page, :, r] = 900.0
                    poisoned_v[page, :, r] = -900.0
    out1, _ = paged_decode_attention(
        q, kn, vn, PagedKV(jnp.asarray(poisoned_k)),
        PagedKV(jnp.asarray(poisoned_v)), meta)
    _assert_close(out1, out0, tol=1e-6)


def test_kernel_int8_pool_dequantizes_in_kernel():
    """int8 pool: the kernel's in-VMEM dequantization matches the dense
    reference's gather-then-dequantize bit for bit (same math)."""
    q, kn, vn, pk, pv, meta = _setup(quantized=True)
    assert pk.data.dtype == jnp.int8
    out, (k_row, v_row) = paged_decode_attention(q, kn, vn, pk, pv, meta)
    ref, _ = paged_decode_reference(q, kn, vn, pk, pv, meta)
    _assert_close(out, ref)
    # rows come back in the pool's compute dtype, ready to quantize+append
    assert k_row.dtype == pk.row_dtype


def test_kernel_under_jit_and_vmap_free_batching():
    """The op is jit-compatible with traced tables/lengths (how the
    engine's decode program calls it)."""
    q, kn, vn, pk, pv, meta = _setup()

    @jax.jit
    def run(q, kn, vn, pk, pv, table, lengths):
        m = PagedDecodeMeta(table, lengths, rows=meta.rows)
        return paged_decode_attention(q, kn, vn, pk, pv, m)[0]

    out = run(q, kn, vn, pk, pv, meta.table, meta.lengths)
    ref, _ = paged_decode_reference(q, kn, vn, pk, pv, meta)
    _assert_close(out, ref)


def test_kernel_rejects_multi_token_and_mismatched_heads():
    q, kn, vn, pk, pv, meta = _setup()
    with pytest.raises(ValueError, match="one token per slot"):
        paged_decode_attention(jnp.concatenate([q, q], axis=1), kn, vn,
                               pk, pv, meta)
    with pytest.raises(ValueError, match="not a multiple"):
        paged_decode_attention(q[:, :, :5], kn, vn, pk, pv, meta)


def test_paged_types_are_pytrees_and_meta_add_is_noop():
    """PagedKV/PagedDecodeMeta flatten/unflatten (they ride lax.scan in
    the family forwards), and the dense-path `cache_len + S` convention
    is absorbed as a no-op (length advance is the engine's live-masked
    job)."""
    q, kn, vn, pk, pv, meta = _setup(quantized=True)
    leaves, treedef = jax.tree_util.tree_flatten((pk, pv, meta))
    rebuilt = jax.tree_util.tree_unflatten(treedef, leaves)
    assert rebuilt[0].quantized and rebuilt[2].rows == meta.rows
    assert (meta + 1) is meta
    assert getattr(pk, "is_paged_kv") and getattr(meta, "is_paged_meta")
    # bf16 pool: scales child is None, flattening still round-trips
    bf = PagedKV(pk.data.astype(jnp.bfloat16))
    leaves, treedef = jax.tree_util.tree_flatten(bf)
    assert not jax.tree_util.tree_unflatten(treedef, leaves).quantized


def test_kv_quantize_roundtrip_error_bound():
    """Per-row symmetric int8: round-trip error bounded by ~scale/2 per
    element (relative to the row's absmax)."""
    rng = np.random.default_rng(3)
    x = jnp.asarray(rng.normal(size=(5, 7, 16)), jnp.float32)
    codes, scales = kv_quantize_rows(x)
    assert codes.dtype == jnp.int8 and scales.shape == (5, 7)
    back = kv_dequantize_rows(codes, scales, jnp.float32)
    absmax = np.abs(np.asarray(x)).max(-1, keepdims=True)
    # bf16 scale storage adds up to 2^-8 relative on top of the 1/254 step
    bound = absmax * (1 / 254 + 2 ** -8) + 1e-6
    assert np.all(np.abs(np.asarray(back - x)) <= bound)


def test_decode_attention_dispatches_paged_vs_dense():
    """models/decode.decode_attention routes a paged cache through the
    kernel and a dense tuple through the classic path, with matching
    numerics on equivalent state."""
    from accelerate_tpu.models.decode import decode_attention

    q, kn, vn, pk, pv, meta = _setup(G=2)
    out_paged, (k_row, v_row, m2) = decode_attention(
        q, kn, vn, (pk, pv, meta), positions=meta.lengths[:, None],
        n_rep=2)
    assert m2 is meta
    ref, _ = paged_decode_reference(q, kn, vn, pk, pv, meta)
    _assert_close(out_paged, ref)
    with pytest.raises(ValueError, match="paged decode path"):
        decode_attention(q, kn, vn, (pk, pv, meta),
                         positions=meta.lengths[:, None],
                         mask=jnp.ones((3, 1), bool))
