"""Pallas paged-attention decode kernels (ops/paged_attention.py).

Interpret-mode exactness vs the dense-gather reference across the page
geometry the serving engine actually produces — page-boundary lengths,
mid-page lengths, GQA head groups, trash-padded table rows, sliding
windows, reused (stale-content) pages — on a STACKED pool whose layers
all differ, read at a layer other than 0, plus the int8-pool in-kernel
dequantization and the `PagedKV`/`PagedDecodeMeta` plumbing types the
family forwards thread.

`D = 128` is the live-pages kernel (one grid step a slot, grouped page
copies out of the whole pool); `D = 16` and int8 pools are the older
page-a-grid-step kernel, given its layer's slice."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from accelerate_tpu.ops import paged_attention as pa
from accelerate_tpu.ops.paged_attention import (
    PagedDecodeMeta,
    PagedKV,
    paged_decode_reference,
)
from accelerate_tpu.ops.quant import kv_dequantize_rows, kv_quantize_rows


def paged_decode_attention(*args, **kwargs):
    """The op, WAITED for. The live-pages kernel's CPU interpreter runs jax
    operations of its own inside the program's callbacks; a test that goes
    on to dispatch (the reference, a second call) while the kernel is still
    in flight can deadlock the CPU client (seen under six workers' load: a
    worker of the suite stood still in one of the run copy's layouts, PR
    50). The engine reads a step's results before it dispatches again."""
    return jax.block_until_ready(pa.paged_decode_attention(*args, **kwargs))


LIVE, OLDER = 128, 16   # head widths: which kernel a pool is given to


def _setup(seed=0, S=3, P=4, ps=8, Hkv=2, G=3, D=LIVE, num_pages=12,
           quantized=False, dtype=jnp.float32, layers=3, layer=2):
    """A pool + table geometry exercising the engine's corner cases:
    slot 0 mid-page length, slot 1 exactly at a page boundary, slot 2
    nearly empty with a trash-padded table row. The pool is stacked,
    every layer of it different, and read at `layer`."""
    rng = np.random.default_rng(seed)
    shape = (layers, num_pages + 1, Hkv, ps, D)
    layer = jnp.int32(layer)
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
        pk = PagedKV(ck, sk, compute_dtype=dtype, layer=layer)
        pv = PagedKV(cv, sv, compute_dtype=dtype, layer=layer)
    else:
        pk, pv = PagedKV(pool_k, layer=layer), PagedKV(pool_v, layer=layer)
    meta = PagedDecodeMeta(jnp.asarray(table), lengths, rows=P * ps)
    return q, kn, vn, pk, pv, meta


def _assert_close(out, ref, tol=2e-5):
    err = float(jnp.max(jnp.abs(out.astype(jnp.float32)
                                - ref.astype(jnp.float32))))
    assert err < tol, f"max err {err}"


@pytest.fixture
def pages_per_group(monkeypatch):
    """Set the live-pages kernel's group (the module's one constant)."""
    return lambda n: monkeypatch.setattr(pa, "PAGES_PER_GROUP", n)


@pytest.mark.parametrize("D", [LIVE, OLDER], ids=["live", "older"])
@pytest.mark.parametrize("window", [None, 5, 1000])
def test_kernel_matches_reference_geometry_matrix(window, D):
    """Mid-page / page-boundary / trash-padded slots, GQA groups, and
    sliding windows (incl. one wider than the cache = plain causal) all
    match the dense reference, in the pool's layer 2 of 3."""
    q, kn, vn, pk, pv, meta = _setup(D=D)
    out, (k_row, v_row) = paged_decode_attention(q, kn, vn, pk, pv, meta,
                                                 window=window)
    ref, (rk, rv) = paged_decode_reference(q, kn, vn, pk, pv, meta,
                                           window=window)
    _assert_close(out, ref)
    # the rows handed back for the engine to scatter are identical too
    # (same cast — the fold and the write must see the same bytes)
    assert jnp.array_equal(k_row, rk) and jnp.array_equal(v_row, rv)


@pytest.mark.parametrize("D", [LIVE, OLDER], ids=["live", "older"])
def test_kernel_reads_the_layer_it_is_given(D):
    """Every layer of the stacked pool differs: the same call at another
    layer index gives another answer, each its own reference's. A kernel
    that ignored `layer` would fail at all but one."""
    q, kn, vn, pk, pv, meta = _setup(D=D)
    outs = []
    for layer in range(pk.data.shape[0]):
        at = jnp.int32(layer)
        out, _ = paged_decode_attention(q, kn, vn, pk.at_layer(at),
                                        pv.at_layer(at), meta)
        ref, _ = paged_decode_reference(q, kn, vn, pk.at_layer(at),
                                        pv.at_layer(at), meta)
        _assert_close(out, ref)
        outs.append(out)
    assert float(jnp.max(jnp.abs(outs[0] - outs[1]))) > 1e-2
    assert float(jnp.max(jnp.abs(outs[1] - outs[2]))) > 1e-2
    with pytest.raises(ValueError, match="layer index"):
        paged_decode_attention(q, kn, vn, PagedKV(pk.data), PagedKV(pv.data),
                               meta)
    with pytest.raises(ValueError, match="layer index"):  # a layer's slice
        paged_decode_attention(q, kn, vn, PagedKV(pk.data[0], layer=at),
                               PagedKV(pv.data[0], layer=at), meta)


@pytest.mark.parametrize("group,lengths,window", [
    (2, (13, 16, 31), None),     # lengths that are no multiple of a group
    (2, (17, 24, 1), None),      # one row into a group; a group's end
    (3, (25, 31, 8), None),      # the last group runs past the table's end
    (64, (13, 16, 31), None),    # a group larger than pages_per_slot
    (2, (0, 0, 0), None),        # length 0: no group at all
    (2, (29, 18, 31), 6),        # the window's band crosses a group boundary
    (1, (29, 18, 31), 11),       # ... and starts some groups into the table
], ids=["ragged", "edges", "past-table", "group-gt-table", "empty",
        "window-crossing", "window-late-start"])
def test_live_pages_kernel_group_geometry(pages_per_group, group, lengths,
                                          window):
    """The live-pages kernel's own geometry: groups of `group` pages of 8
    rows over a table of 4 pages a slot."""
    pages_per_group(group)
    q, kn, vn, pk, pv, meta = _setup()
    meta = PagedDecodeMeta(
        jnp.asarray([[0, 1, 2, 6], [3, 4, 7, 8], [5, 9, 10, 11]], jnp.int32),
        jnp.asarray(lengths, jnp.int32), rows=meta.rows)
    out, _ = paged_decode_attention(q, kn, vn, pk, pv, meta, window=window)
    ref, _ = paged_decode_reference(q, kn, vn, pk, pv, meta, window=window)
    _assert_close(out, ref)


def test_live_pages_kernel_masked_lanes_with_stale_tables(pages_per_group):
    """What the engine hands the kernel: lengths masked to 0 on lanes that
    are not live, whose table rows still point at another tenant's pages.
    Such a lane attends its own new token only (whatever the pages hold),
    and the live lanes are untouched by it."""
    pages_per_group(2)
    q, kn, vn, pk, pv, meta = _setup()
    live = jnp.asarray([True, False, True])
    masked = PagedDecodeMeta(meta.table, jnp.where(live, meta.lengths, 0),
                             rows=meta.rows)
    out, _ = paged_decode_attention(q, kn, vn, pk, pv, masked)
    full, _ = paged_decode_attention(q, kn, vn, pk, pv, meta)
    _assert_close(out[0], full[0], tol=1e-6)
    _assert_close(out[2], full[2], tol=1e-6)
    S, _, H, D = q.shape
    own = jnp.repeat(vn[:, 0], H // vn.shape[2], axis=1).reshape(S, 1, H, D)
    _assert_close(out[1], own[1])


# table layouts for the run copy: groups of 8 pages of 8 rows, tested for
# runs 4 entries at a time, in a pool of 64 pages (the trash page is 64).
# name -> (rows of the table (padded with the trash page to `P`), lengths,
# P, run sub-groups a slot the kernel must find, window, ring, Hkv, G)
_ASC = list(range(16, 36))
_RUN_LAYOUTS = {
    "all-runs": ([_ASC, list(range(40, 60))], (157, 96), 20, (5, 5),
                 None, False, 2, 3),
    "shuffled": ([[9, 3, 27, 14, 40, 8, 61, 22, 5, 50, 33, 2, 19, 44, 11,
                   30, 58, 1, 36, 25]], (155,), 20, (0,), None, False, 2, 3),
    "broken-inside": ([[16, 17, 5, 19, 20, 21, 22, 23, 24, 25, 27, 26]],
                      (95,), 12, (1,), None, False, 2, 3),
    "unaligned-start": ([[50, 7, 8, 9, 10, 11, 12, 13, 14, 15, 16, 17]],
                        (90,), 12, (2,), None, False, 2, 3),
    "tail-past-length": ([_ASC[:16]], (83,), 16, (4,), None, False, 2, 3),
    # 20 entries in groups of 8: the last group's entries 20-23 are the
    # table's clamped end, entry 19 four times
    "past-table": ([_ASC], (159,), 20, (5,), None, False, 2, 3),
    # padded entries repeat the trash page; slot 1's last real page is the
    # one below it, so [61, 62, 63, 64] is a run THROUGH the trash page
    "padded-trash": ([[4, 5, 6, 7, 8], [60, 61, 62, 63]], (37, 30), 12,
                     (1, 1), None, False, 2, 3),
    "window": ([_ASC], (150,), 20, (5,), 45, False, 2, 3),
    # a ring of 12 pages (12 % 4 == 0: no aligned sub-group wraps), walked
    # twice round, and one of 10 (every page a copy of its own)
    "ring-whole-runs": ([list(range(8, 20))], (203,), 12, (3,), 40, True,
                        2, 3),
    "ring-no-runs-taken": ([list(range(8, 18))], (171,), 10, (0,), 40, True,
                           2, 3),
    "mqa-20-heads": ([_ASC], (149,), 20, (5,), None, False, 1, 20),
    "gqa-2": ([_ASC], (149,), 20, (5,), None, False, 2, 6),
    "gqa-4": ([_ASC], (149,), 20, (5,), None, False, 4, 8),
}


def _run_sub_groups(table, P, G, R, ring):
    """Sub-groups a slot that the kernel's rule takes as ONE copy, by the
    allocator's own count (`table_run_pages`) over the entries the kernel
    reads: whole groups, entries past the table's end clamped to its last
    (once round a ring, whose sub-groups do not wrap or are not looked
    for)."""
    from accelerate_tpu.serving.cache import table_run_pages

    if ring and P % R:
        return tuple(0 for _ in table)
    read = range(P if ring else -(-P // G) * G)
    return tuple(
        table_run_pages([int(row[min(i, P - 1)]) for i in read], R) // R
        for row in np.asarray(table))


@pytest.mark.parametrize("layout", list(_RUN_LAYOUTS))
def test_live_pages_kernel_run_copy_is_bitwise_the_per_page_walk(
        monkeypatch, layout):
    """A sub-group of consecutive page ids is ONE copy a pool, anything
    else a page at a time, and both land the same bytes: the output is
    BITWISE that of the same call on a pool whose pages were permuted (the
    table with them) so that no sub-group is a run, and within the
    tolerance of the dense reference."""
    rows, lengths, P, runs, window, ring, Hkv, G = _RUN_LAYOUTS[layout]
    monkeypatch.setattr(pa, "PAGES_PER_GROUP", 8)
    monkeypatch.setattr(pa, "PAGES_PER_RUN", 4)
    N, ps, S = 64, 8, len(rows)
    q, kn, vn, pk, pv, _ = _setup(S=S, P=P, ps=ps, Hkv=Hkv, G=G,
                                  num_pages=N, seed=7)
    table = np.full((S, P), N, np.int32)
    for s, row in enumerate(rows):
        table[s, :len(row)] = row
    group = pa._pages_per_group(P, pk.data.shape[2:], pk.data.dtype)
    assert group == 8
    per_run = pa._pages_per_run(group, P, ring)
    assert per_run == (1 if layout == "ring-no-runs-taken" else 4)
    assert _run_sub_groups(table, P, group, 4, ring) == runs
    meta = PagedDecodeMeta(jnp.asarray(table),
                           jnp.asarray(lengths, jnp.int32), rows=P * ps)
    out, _ = paged_decode_attention(q, kn, vn, pk, pv, meta, window=window,
                                    ring=ring)
    ref, _ = paged_decode_reference(q, kn, vn, pk, pv, meta, window=window,
                                    ring=ring)
    _assert_close(out, ref)
    # the same bytes under other page ids: neighbours 37 apart, the trash
    # page where it was
    perm = np.append((np.arange(N) * 37 + 11) % N, N)
    assert _run_sub_groups(perm[table], P, group, 4, ring) == (0,) * S
    moved = [PagedKV(jnp.zeros_like(p.data).at[:, perm].set(p.data),
                     layer=p.layer) for p in (pk, pv)]
    walked, _ = paged_decode_attention(
        q, kn, vn, *moved, PagedDecodeMeta(
            jnp.asarray(perm[table]), meta.lengths, rows=meta.rows),
        window=window, ring=ring)
    assert jnp.array_equal(out, walked)


@pytest.mark.parametrize("D", [LIVE, 8], ids=["live", "older"])
def test_kernel_matches_reference_single_page_and_single_head(D):
    """Degenerate geometry: one page per slot, MHA (G=1)."""
    q, kn, vn, pk, pv, meta = _setup(S=2, P=1, ps=4, Hkv=3, G=1, D=D,
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


@pytest.mark.parametrize("D,slack", [
    (LIVE, np.inf), (LIVE, np.nan), (OLDER, 900.0)],
    ids=["live-inf", "live-nan", "older"])
def test_kernel_ignores_stale_rows_in_reused_pages(D, slack):
    """Rows at or past `length` — stale K/V from a previous tenant of
    the page (slot reuse), or allocation slack — never leak into the
    output, and neither do the pages behind them: every page a table
    names past its slot's live pages, and the trash page that backs the
    padded entries (where retired lanes' dead writes land, never
    cleaned), hold inf / NaN here and change nothing. The live-pages
    kernel copies such pages with their group, so it must zero what it
    masks (0 x inf is NaN); the older kernel never fetches a dead page,
    and is held to huge finite values in a live page's slack as it
    always was."""
    q, kn, vn, pk, pv, meta = _setup(D=D)
    out0, _ = paged_decode_attention(q, kn, vn, pk, pv, meta)
    ps = pk.data.shape[3]
    poisoned_k, poisoned_v = np.asarray(pk.data).copy(), np.asarray(
        pv.data).copy()
    table, lengths = np.asarray(meta.table), np.asarray(meta.lengths)
    live_pages = set()
    for s in range(table.shape[0]):
        for j, page in enumerate(table[s]):
            if j * ps < lengths[s]:
                live_pages.add(int(page))
            for r in range(ps):
                if j * ps <= lengths[s] - 1 < (j + 1) * ps \
                        and j * ps + r >= lengths[s]:
                    poisoned_k[:, page, :, r] = slack
                    poisoned_v[:, page, :, r] = -slack
    for page in range(pk.data.shape[1]):      # the trash page included
        if page not in live_pages:
            poisoned_k[:, page] = np.nan
            poisoned_v[:, page] = -np.inf
    assert table.max() == pk.data.shape[1] - 1 not in live_pages
    out1, _ = paged_decode_attention(
        q, kn, vn, PagedKV(jnp.asarray(poisoned_k), layer=pk.layer),
        PagedKV(jnp.asarray(poisoned_v), layer=pk.layer), meta)
    _assert_close(out1, out0, tol=1e-6)


@pytest.mark.parametrize("D", [LIVE, OLDER])
def test_kernel_int8_pool_dequantizes_in_kernel(D):
    """int8 pool: the kernel's in-VMEM dequantization matches the dense
    reference's gather-then-dequantize bit for bit (same math)."""
    q, kn, vn, pk, pv, meta = _setup(quantized=True, D=D)
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
    # the layer index is a child (it is traced inside the layer scan)
    at = jax.tree_util.tree_unflatten(*reversed(
        jax.tree_util.tree_flatten(bf.at_layer(jnp.int32(1)))))
    assert int(at.layer) == 1 and at.data is bf.data and bf.layer is None


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


def _tiny_engine_decode(family_name):
    """(a tiny engine of the family with 128-wide heads over abstract
    weights, the layers of it that attend by kind)."""
    from accelerate_tpu.serving import Engine, EngineConfig

    kwargs = dict(num_slots=2, max_len=64, prefill_chunk=8, page_size=8,
                  cache_dtype=jnp.float32, paged_attention=True)
    if family_name == "mellum":
        from accelerate_tpu.models import mellum as family

        cfg = family.MellumConfig.tiny()
        kinds = {"full": len(cfg.layers_of(family.FULL)),
                 "ring": len(cfg.layers_of(family.SLIDING))}
        kwargs.update(prefix_cache=False)
    elif family_name == "jamba":
        from accelerate_tpu.models import jamba as family

        cfg = family.JambaConfig.tiny()     # layers 1 and 3 attend
        kinds = {"full": 2}
        kwargs.update(prefix_cache=False, page_size=16)
    else:
        from accelerate_tpu.models import llama as family

        cfg = family.LlamaConfig.tiny(hidden_size=512, num_attention_heads=4,
                                      num_key_value_heads=2)
        kinds = {"scan": 1}                 # one body inside the layer scan
    params = jax.eval_shape(
        lambda: family.init_params(cfg, jax.random.key(0), jnp.float32))
    return Engine(family, cfg, params, EngineConfig(**kwargs)), kinds


@pytest.mark.parametrize("family_name", ["mellum", "jamba", "llama"])
def test_a_decode_program_holds_the_live_kernel_once_a_variant(
        family_name, monkeypatch):
    """The engine's `decode` lowered for the chip: a family that loops over
    its layers in Python holds the live-pages kernel's body ONCE a variant
    (full layers; a ring under a window), in a function of its own that
    the layers call with their layer index as data, so what every process
    pays to trace and lower it does not grow with the model's depth; under
    the families' shared layer scan it is one body and one call."""
    from accelerate_tpu.ops import kernel_mode

    monkeypatch.setattr(kernel_mode, "resolve_interpret",
                        lambda name, interpret=None: False)
    eng, kinds = _tiny_engine_decode(family_name)
    text = eng._decode_p.trace(
        eng.params, eng.cache, eng._tokens, eng._slot_keys, eng._temps,
        np.ones((2,), bool), eng._tables()).lower(
            lowering_platforms=("tpu",)).as_text()
    assert text.count("func.func private @_live_pages_call") == len(kinds)
    assert text.count("call @_live_pages_call") == sum(kinds.values())
    assert text.count(f'kernel_name = "{pa.KERNEL_NAME}"') == (
        len(kinds) - ("ring" in kinds))
    assert text.count(f'kernel_name = "{pa.WINDOW_KERNEL_NAME}"') == (
        "ring" in kinds)
