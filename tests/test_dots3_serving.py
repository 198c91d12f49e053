"""The family whose layers are latent attention of two kinds over a share
of its experts (`models/dots3.py`: full layers read the keys a learned
indexer selects on a latent row, sliding layers a window of a second,
wider latent row, 4 of 8 routed experts held) on the CPU at a small size,
seeded weights: against the benchmark's plain reference
(`chipbench/references/dots3_note.py`: K and V decompressed, the index
scores against every position and an exact top-k, a position mask for the
window, every held expert by a masked combine) through every cache form
the engine uses; both new decode kernels, interpreted, against
`jax.numpy`; the counters and gauges; what raises.

Size: 4 layers (a dense full layer, an expert full layer, two sliding
ones), `index_topk` 24 and a window of 9, so every context here crosses
both; rows of 256 and 384 lanes in pages of 16. Logits are compared, not
tokens. float32 weights and caches under
`jax.default_matmul_precision("highest")`: program and reference sum in
different orders, which reads 5e-7 on logits of order 0.5."""

import dataclasses
import importlib.util
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from accelerate_tpu.models import dots3
from accelerate_tpu.ops import kernel_mode
from accelerate_tpu.ops import latent_paged_attention as latent
from accelerate_tpu.ops import sparse_paged_attention as sparse
from accelerate_tpu.ops.paged_attention import PagedDecodeMeta
from accelerate_tpu.serving import Engine, EngineConfig
from accelerate_tpu.serving.cache import WithSide

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
TOL = 2e-5


def _reference():
    spec = importlib.util.spec_from_file_location(
        "dots3_note_reference", os.path.join(
            ROOT, "chipbench", "references", "dots3_note.py"))
    module = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(module)
    return module


REF = _reference()
CFG = dots3.Dots3Config.tiny(experts_held=(2, 4))
TOPK, WINDOW = CFG.index_topk, CFG.sliding_window_size


def _ref_cfg(config):
    """The configuration FILE's keys for a program config: the experts'
    key counts the held ones, the router's width stands beside it."""
    out = {f.name: getattr(config, f.name)
           for f in dataclasses.fields(config)}
    out.update(layer_types=list(config.layer_types),
               router_experts=config.n_routed_experts,
               n_routed_experts=config.experts_here,
               experts_held=list(config.experts_held
                                 or (0, config.n_routed_experts)))
    return out


REF_CFG = _ref_cfg(CFG)
PAD = 128  # every reference pass runs at this one length (one compile)


@jax.jit
def _ref_logits(params, row):
    with jax.default_matmul_precision("highest"):
        return REF.logits(REF_CFG, params, row)


def _padded(seq):
    out = np.zeros((PAD,), np.int32)
    out[:len(seq)] = seq
    return jnp.asarray(out)


@pytest.fixture(scope="module")
def params():
    return REF.make_params(REF_CFG, REF.seed_words(5), jnp.float32)


@pytest.fixture(scope="module")
def ids():
    return np.asarray(jax.random.randint(
        jax.random.key(3), (2, 120), 0, CFG.vocab_size))


@pytest.fixture(scope="module")
def ref_logits(params, ids):
    return np.stack([np.asarray(_ref_logits(params, _padded(row)))[:120]
                     for row in ids])


def _forward(config, params, ids, **kw):
    with jax.default_matmul_precision("highest"):
        return np.asarray(jax.jit(
            lambda p, i: dots3.forward(config, p, i, **kw))(params, ids))


# ---------------------------------------------------------------------------
# the model against the reference
# ---------------------------------------------------------------------------


def test_the_trees_of_program_and_reference_are_one(params):
    mine = jax.eval_shape(lambda: dots3.init_params(
        CFG, jax.random.key(0), jnp.float32))
    assert jax.tree.structure(mine) == jax.tree.structure(params)
    assert jax.tree.map(lambda a: a.shape, mine) == jax.tree.map(
        lambda a: a.shape, params)
    assert REF.param_count(REF_CFG) == sum(
        int(np.prod(a.shape)) for a in jax.tree.leaves(mine))
    # the router keeps its width, the expert arrays hold the share
    moe = params["layers"][1]["moe"]
    assert moe["router"]["kernel"].shape == (64, 8)
    assert moe["experts"]["gate_proj"].shape == (4, 64, 32)


def test_full_forward_agrees_with_the_reference(params, ids, ref_logits):
    """120 positions: five times `index_topk`, thirteen windows."""
    assert np.abs(_forward(CFG, params, ids) - ref_logits).max() < TOL


def test_selection_off_is_another_model(params, ids, ref_logits):
    with jax.default_matmul_precision("highest"):
        dense = np.asarray(jax.jit(lambda p, row: REF.logits(
            REF_CFG, p, row, use_selection=False))(params, _padded(ids[0])))
    assert np.abs(dense[:120] - ref_logits[0]).max() > 0.05
    # up to `index_topk` positions nothing is left out: the same model
    assert np.abs(dense[:TOPK] - ref_logits[0, :TOPK]).max() < TOL


@pytest.mark.parametrize("what,changed", [
    ("a query selects 25 keys", dict(index_topk=TOPK + 1)),
    ("a query selects 23 keys", dict(index_topk=TOPK - 1)),
    ("every key is attended", dict(index_topk=4096)),
    ("the window is one key wider", dict(sliding_window_size=WINDOW + 1)),
    ("the window is one key narrower", dict(sliding_window_size=WINDOW - 1)),
    ("the latents are not rescaled", dict(apply_mla_qkv_lora_rescale=False)),
    ("another share of the experts", dict(experts_held=(0, 4))),
])
def test_a_one_off_model_fails_the_comparison(params, ids, ref_logits, what,
                                              changed):
    wrong = dataclasses.replace(CFG, **changed)
    assert np.abs(_forward(wrong, params, ids[:1])
                  - ref_logits[:1]).max() > 50 * TOL, what


def test_chunked_prefill_then_decode_through_views(params, ids, ref_logits):
    """Chunks of 16 and then single tokens through the views `generate`
    uses: a group's rows (and the full group's index keys) are written
    where each layer attends, and every query selects over all of them."""
    with jax.default_matmul_precision("highest"):
        caches = dots3.init_kv_caches(CFG, 2, 128, jnp.float32)
        assert isinstance(caches[0][0], WithSide) and caches[1] == (None, None)
        assert caches[0][0].rows.shape == (2, 2, 128, 1, 256)
        assert caches[0][1].shape == (2, 2, 128, 1, 384)
        step = jax.jit(lambda p, i, c: dots3.forward(CFG, p, i, kv_caches=c))
        got = []
        for start in range(0, 96, 16):
            out, caches = step(params, ids[:, start:start + 16], caches)
            got.append(np.asarray(out))
        for at in range(96, 104):
            out, caches = step(params, ids[:, at:at + 1], caches)
            got.append(np.asarray(out))
    assert np.abs(np.concatenate(got, axis=1)
                  - ref_logits[:, :104]).max() < TOL


@pytest.mark.parametrize("form", ["no cache", "views"])
def test_every_layer_attends_in_one_chunk_kernel(params, ids, form):
    """Both forms that attend latent ROWS (the paged step reads pages) are
    one `latent_chunk_attention` call a layer, full and sliding alike, and
    what every test above compared was that kernel, interpreted."""
    caches = (None if form == "no cache"
              else dots3.init_kv_caches(CFG, 2, 128, jnp.float32))
    text = str(jax.make_jaxpr(lambda p, i: dots3.forward(
        CFG, p, i, kv_caches=caches))(params, ids[:, :16]))
    assert text.count("name=latent_chunk_attention") == CFG.num_hidden_layers
    assert kernel_mode.kernel_report()["latent_chunk_attention"] == "interpret"


# ---------------------------------------------------------------------------
# the two decode kernels, interpreted, against jax.numpy
# ---------------------------------------------------------------------------


def _latent_pool(key, layers, pages, width, slots, pages_per_slot):
    k = jax.random.split(key, 4)
    pool = jax.random.normal(k[0], (layers, pages + 1, 16, width)) * 3.0
    table = jax.random.permutation(k[1], pages)[:slots * pages_per_slot]
    return pool, table.reshape(slots, pages_per_slot).astype(jnp.int32), k


def test_the_window_latent_kernel_reads_a_ring(params):
    """64-lane-tile rows in a ring of 4 pages, window 21: lengths under
    the window, past it, past the ring's wrap, and a dead lane (length
    0); stale rows are LARGE and must not be seen."""
    S, H, W, V, window = 5, 4, 384, 256, 21
    pool, table, k = _latent_pool(jax.random.key(0), 3, 40, W, S, 4)
    q = jax.random.normal(k[2], (S, H, W))
    new = jax.random.normal(k[3], (S, W))
    lengths = jnp.asarray([0, 7, 20, 21, 150], jnp.int32)
    for layer in (0, 2):
        got = latent.latent_paged_decode_attention(
            q, new, pool, layer, table, lengths, value_width=V,
            sm_scale=0.2, window=window, interpret=True)
        want = latent.latent_paged_decode_reference(
            q, new, pool, layer, table, lengths, value_width=V, sm_scale=0.2,
            window=window)
        assert np.abs(np.asarray(got) - np.asarray(want)).max() < 1e-4
        # one key more or less is another answer: the mask is exact
        off = latent.latent_paged_decode_reference(
            q, new, pool, layer, table, lengths, value_width=V, sm_scale=0.2,
            window=window + 1)
        assert np.abs(np.asarray(off)[3:] - np.asarray(want)[3:]).max() > 1e-2
        # a dead lane attends its own new row alone
        np.testing.assert_allclose(np.asarray(got)[0],
                                   np.tile(np.asarray(new)[0, :V], (H, 1)),
                                   rtol=1e-5, atol=1e-5)
    assert latent.WINDOW_KERNEL_NAME in kernel_mode.kernel_report()


def test_the_sparse_latent_kernel_attends_the_selected_rows_alone(params):
    """5 slots over a 6-page table, a random selection a slot: a slot
    under `k` (everything selected), slots whose selection leaves whole
    pages out, one that did not select itself, a dead lane."""
    S, H, W, V = 5, 4, 256, 128
    pool, table, k = _latent_pool(jax.random.key(1), 2, 50, W, S, 6)
    q = jax.random.normal(k[2], (S, H, W))
    new = jax.random.normal(k[3], (S, W))
    lengths = jnp.asarray([0, 9, 40, 77, 95], jnp.int32)
    meta = PagedDecodeMeta(table, lengths, rows=96)
    scores = jax.random.normal(jax.random.key(2), (S, 96))
    col = jnp.arange(96)[None, :]
    scores = jnp.where(col <= lengths[:, None], scores, -jnp.inf)
    # slot 3 does not select itself: its own score is the least
    scores = scores.at[3, 77].set(-1e9)
    selected = sparse.exact_topk_mask(scores, 12)
    assert not bool(selected[3, 77]) and bool(selected[1, :10].all())
    got = sparse.sparse_latent_paged_decode_attention(
        q, new, pool, 1, meta, selected, value_width=V, sm_scale=0.3,
        interpret=True)
    want = sparse.sparse_latent_paged_decode_reference(
        q, new, pool, 1, meta, selected, value_width=V, sm_scale=0.3)
    assert np.abs(np.asarray(got) - np.asarray(want)).max() < 1e-4
    # the selection matters: every key attended is another answer
    dense = latent.latent_paged_decode_reference(
        q, new, pool, 1, table, lengths, value_width=V, sm_scale=0.3)
    assert np.abs(np.asarray(dense)[2:] - np.asarray(want)[2:]).max() > 1e-2
    assert np.abs(np.asarray(dense)[:2] - np.asarray(want)[:2]).max() < 1e-4
    assert sparse.LATENT_ATTENTION_KERNEL_NAME in kernel_mode.kernel_report()
    with pytest.raises(ValueError, match="whole 128-lane tiles"):
        sparse.sparse_latent_paged_decode_attention(
            q[..., :200], new[..., :200], pool[..., :200], 1, meta, selected,
            value_width=V, sm_scale=0.3, interpret=True)


# ---------------------------------------------------------------------------
# the engine
# ---------------------------------------------------------------------------


def _engine(params, config=CFG, **kw):
    args = dict(num_slots=3, max_len=128, prefill_chunk=16, page_size=16,
                cache_dtype=jnp.float32, prefix_cache=False,
                paged_attention=False)
    args.update(kw)
    return Engine(dots3, config, params, EngineConfig(**args))


def _teacher_forced(params, prompt, tokens):
    seq = np.concatenate([np.asarray(prompt), tokens])
    out = np.asarray(_ref_logits(params, _padded(seq)))
    return out[len(prompt) - 1:len(prompt) - 1 + len(tokens)]


def _agrees_with_the_reference(params, prompt, req, tol=1e-4):
    ref = _teacher_forced(params, prompt, np.asarray(req.tokens))
    at = np.arange(len(req.tokens))
    lp = np.asarray(ref - jax.nn.logsumexp(ref, axis=-1, keepdims=True))
    assert np.abs(ref.max(-1) - ref[at, req.tokens]).max() < tol
    assert np.abs(lp[at, req.tokens] - np.asarray(req.logprobs)).max() < tol


@pytest.mark.parametrize("kernel,shapes", [
    # contexts on both sides of `index_topk` (24) and of the window (9);
    # last chunks that are padded (70, 5, 33, 37) and one that is whole
    (False, [(70, 30), (5, 50), (96, 20), (33, 30), (37, 60)]),
    # the kernels interpreted: keep the decode short
    (True, [(75, 5), (5, 6), (60, 7), (30, 4)]),
], ids=["dense", "kernel"])
def test_engine_serves_chunks_then_decode_through_the_paged_groups(
        params, ids, kernel, shapes):
    """Chunked prefill through the slot's gathered views, a layer at a
    time, then paged decode (the dense gather, and the Pallas kernels
    interpreted: scores, selection and the sparse latent kernel on the
    full layers, the ring kernel on the sliding ones), requests of
    different lengths in one batch, against the reference's full pass:
    every served token is the reference's first choice by its own logits,
    and the engine's log-probability of it is the reference's."""
    with jax.default_matmul_precision("highest"):
        eng = _engine(params, paged_attention=kernel)
        full, ring = eng.cache.groups
        assert full.side.shape == (2, 3 * 9 + 1, 16, 128) and ring.ring
        assert (full.k.shape[-1], ring.k.shape[-1]) == (256, 384)
        assert ring.pages_per_slot == 3       # ceil((9 + 16) / 16) + 1
        prompts = [ids[i % 2, i:i + n] for i, (n, _) in enumerate(shapes)]
        reqs = [eng.submit(p, max_new_tokens=m, temperature=0.0)
                for p, (_, m) in zip(prompts, shapes)]
        eng.run_until_idle()
    assert eng._use_paged_kernel is kernel
    for prompt, req in zip(prompts, reqs):
        assert req.status.value == "finished"
        _agrees_with_the_reference(params, prompt, req)
    assert eng.compile_stats() == {"admit": 1, "prefill": 1, "decode": 1}
    assert eng.allocator.pages_in_use == 0
    assert eng.allocator.ring_pages_in_use == (0,)
    # the device counters: the keys seen and selected on the TWO full
    # layers, and the share of the router's assignments that landed here
    got = eng.device_counters()
    assert int(got["prefill"]["calls"]) == eng.metrics.prefill_chunks
    assert int(got["decode"]["calls"]) == eng.metrics.decode_steps

    def keys(positions):
        positions = np.asarray(positions)
        return (2 * int((positions + 1).sum()),
                2 * int(np.minimum(positions + 1, TOPK).sum()))

    prefill = np.concatenate([np.arange(n) for n, _ in shapes])
    decode = np.concatenate([n + np.arange(m - 1) for n, m in shapes])
    for program, positions in (("prefill", prefill), ("decode", decode)):
        stats = got[program]
        assert (dots3.wide_count(stats["keys_visible"]),
                dots3.wide_count(stats["keys_selected"])) == keys(positions)
        # 3 expert layers, 2 experts a real token; experts 2-5 held
        routed = [dots3.wide_count(r) for r in stats["assignments_routed"]]
        held = [dots3.wide_count(r) for r in stats["assignments_held"]]
        assert routed == [2 * len(positions)] * 3
        assert held == stats["assignments"][:, 2:6].sum(-1).tolist()
        assert all(0 < h < r for h, r in zip(held, routed))
        assert stats["assignments"].sum(-1).tolist() == routed


def test_a_chunk_of_whole_row_tiles_selects_in_the_rows_kernel(params, ids):
    """A chunk of 32 query rows is one tile of `sparse_topk_select_rows`
    (interpreted here) and a full layer's view of 1,056 rows three of its
    counting steps: the engine serves the reference's logits with the
    kernel on the path of both full layers, and the prefill program's
    counters say what the live bound let it skip (every chunk ends below
    position 512: ONE step scanned of a view of 1,056)."""
    with jax.default_matmul_precision("highest"):
        eng = _engine(params, max_len=1024, prefill_chunk=32)
        prompts = [ids[0, :75], ids[1, 3:43]]
        reqs = [eng.submit(p, max_new_tokens=3, temperature=0.0)
                for p in prompts]
        eng.run_until_idle()
    for prompt, req in zip(prompts, reqs):
        assert req.status.value == "finished"
        _agrees_with_the_reference(params, prompt, req)
    assert eng.compile_stats() == {"admit": 1, "prefill": 1, "decode": 1}
    got = eng.device_counters()
    chunks, view = eng.metrics.prefill_chunks, 1024 + 32
    assert chunks == 3 + 2
    stats = got["prefill"]
    assert (dots3.wide_count(stats["select_columns_scanned"]),
            dots3.wide_count(stats["select_columns_total"])) == (
                chunks * 2 * 32 * 512, chunks * 2 * 32 * view)
    # `decode` is not handed them: its program is what it was without
    assert eng.metrics.decode_steps > 0
    assert not set(dots3.CHUNK_COUNTERS) & set(got["decode"])
    assert set(dots3.SELECTION_COUNTERS) <= set(got["decode"])


def test_a_program_holds_the_rows_kernel_once_and_decode_none_of_it(
        monkeypatch):
    """The engine's programs lowered for the chip, the indexer's scores in
    blocks of whole counting steps: `prefill` holds the rows kernel's body
    ONCE, in a function of its own that both full layers call (beside the
    four layers' chunk kernels); `decode` holds nothing of it, and none
    of the chunks' counters is among its arguments."""
    monkeypatch.setattr(kernel_mode, "resolve_interpret",
                        lambda name, interpret=None: False)
    cfg = dataclasses.replace(CFG, kv_block=512)
    abstract = jax.eval_shape(
        lambda: dots3.init_params(cfg, jax.random.key(0), jnp.float32))
    eng = _engine(abstract, cfg, max_len=1024, prefill_chunk=32,
                  paged_attention=True)
    state = (eng.params, eng.cache, eng._tokens, eng._slot_keys, eng._temps)
    prefill = eng._prefill_p.trace(
        *state, jnp.int32(0), eng._tables(0), np.zeros((32,), np.int32),
        jnp.int32(32), eng._chunk_stats).lower(
            lowering_platforms=("tpu",)).as_text()
    assert prefill.count("func.func private @_select_rows(") == 1
    assert prefill.count("call @_select_rows(") == 2
    assert prefill.count("tpu_custom_call") == 4 + 1
    decode = eng._decode_p.trace(
        *state, np.ones((3,), bool), eng._tables()).lower(
            lowering_platforms=("tpu",)).as_text()
    assert sparse.ROWS_SELECT_NAME not in decode
    assert "_select_rows" not in decode
    handed = {path[-1].key for path, _ in jax.tree_util.tree_leaves_with_path(
        eng.cache.stats) if hasattr(path[-1], "key")}
    assert {"keys_visible", "keys_selected"} <= handed
    assert not set(dots3.CHUNK_COUNTERS) & handed
    assert set(dots3.CHUNK_COUNTERS) == set(eng._chunk_stats)


def test_a_reused_slot_serves_the_logits_of_a_cold_request(params, ids):
    """ONE slot: the second request takes the first's slot, ring and (by
    the free list's order) pages, with the first's rows and index keys
    still lying in them past its length; it serves what a cold engine
    serves, bit for bit."""
    first, second = ids[0, :75], ids[1, 10:51]
    with jax.default_matmul_precision("highest"):
        eng = _engine(params, num_slots=1)
        a = eng.submit(first, max_new_tokens=9, temperature=0.0)
        eng.run_until_idle()
        b = eng.submit(second, max_new_tokens=12, temperature=0.0)
        eng.run_until_idle()
        cold_engine = _engine(params, num_slots=1)
        cold = cold_engine.submit(second, max_new_tokens=12, temperature=0.0)
        cold_engine.run_until_idle()
    assert a.status.value == b.status.value == "finished"
    assert b.tokens == cold.tokens and b.logprobs == cold.logprobs
    _agrees_with_the_reference(params, second, b)


def test_the_gauges_and_the_allocation_span_say_both_groups(params, ids):
    from accelerate_tpu.telemetry.trace import (
        configure_tracing,
        flight_recorder,
    )

    configure_tracing(True)
    try:
        with jax.default_matmul_precision("highest"):
            eng = _engine(params)
            eng.submit(ids[0, :40], max_new_tokens=30, temperature=0.0)
            for _ in range(6):
                eng.step()
        spans = [s for s in flight_recorder()
                 if s["name"] == "serving.kv.allocate"]
    finally:
        configure_tracing(False)
    # 40 + 30 + 16 rows = 6 pages of the full group, the ring's 3
    assert spans[-1]["attrs"]["full_pages"] == 6
    assert spans[-1]["attrs"]["window_pages"] == 3
    summary = eng.metrics.summary()
    assert summary["pages_in_use.full"] == 6
    assert summary["pages_in_use.window9"] == 3
    assert eng.cache.side_page_nbytes == 2 * 16 * 128 * 4
    assert eng.metrics_summary()["kv_side_bytes_in_use"] == (
        6 * eng.cache.side_page_nbytes)
    groups = eng.debug_pages()["groups"]
    assert [g["group"] for g in groups] == ["full", "window9"]
    assert [g["layers"] for g in groups] == [[0, 1], [2, 3]]
    eng.run_until_idle()
    assert eng.metrics.summary()["pages_in_use.window9"] == 0.0
    eng.close()


def test_the_sanitizer_joins_both_latent_groups_books(params, ids):
    from accelerate_tpu.serving.sanitizer import (
        SanitizerViolation,
        check_engine,
    )

    with jax.default_matmul_precision("highest"):
        eng = _engine(params, sanitize=False)
        eng.submit(ids[0, :40], max_new_tokens=4, temperature=0.0)
        eng.step()
        check_engine(eng)
        slot = eng.scheduler.slots[0]
        # a ring page that is also on the window group's free list
        eng.allocator.ring_pools[0]._free.append(slot.alloc.rings[0][0])
        with pytest.raises(SanitizerViolation, match="ring page"):
            check_engine(eng)
        eng.allocator.ring_pools[0]._free.pop()
        eng.run_until_idle()
        check_engine(eng)


# ---------------------------------------------------------------------------
# what raises
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("option,match", [
    (dict(prefix_cache=True), "differ in kind.*a retention rule of their own"),
    (dict(kv_dtype="int8"), "caches one latent row a token.*int8 latent"),
    (dict(host_tier_bytes=1 << 20),
     "one latent row a token.*a K and a V half"),
    (dict(mesh="two-devices"), "a sharded latent pool"),
    (dict(speculative="draft"), "multi-token latent attention"),
])
def test_unported_combinations_raise_at_construction(params, option, match):
    if option.get("mesh"):
        option = dict(mesh=jax.sharding.Mesh(
            np.array(jax.devices()[:2]), ("model",)))
    if option.get("speculative"):
        option = dict(speculative=(dots3, CFG, params))
    option = dict(dict(prefix_cache=False), **option)
    with pytest.raises(ValueError, match=match) as err:
        Engine(dots3, CFG, params, EngineConfig(
            num_slots=2, max_len=64, prefill_chunk=8, **option))
    assert "Nothing falls back to" in str(err.value)


def test_each_trait_of_the_cache_refuses_its_own_options(params):
    """Latent + grouped + side compose: with the latent trait's options
    left alone, the grouped trait's and the side trait's still raise."""
    from accelerate_tpu.serving import engine as engine_module

    groups = dots3.cache_spec(CFG)
    with pytest.raises(ValueError, match="int8 latent pages"):
        engine_module._refuse_unported(
            EngineConfig(prefix_cache=False, kv_dtype="int8"), groups[0],
            groups)
    saved = dict(engine_module._UNPORTED)
    try:
        engine_module._UNPORTED["latent"] = ("a K/V pool", {})
        with pytest.raises(ValueError, match="an int8 ring"):
            engine_module._refuse_unported(
                EngineConfig(prefix_cache=False, kv_dtype="int8"),
                groups[0], groups)
        engine_module._UNPORTED["grouped"] = ("a one-kind pool", {})
        with pytest.raises(ValueError, match="side row's codes and scales"):
            engine_module._refuse_unported(
                EngineConfig(prefix_cache=False, kv_dtype="int8"),
                groups[0], groups)
    finally:
        engine_module._UNPORTED.update(saved)
    # a side row INSIDE a ring group is what is still not there
    ring_side = (groups[0], dataclasses.replace(groups[1], side_width=128))
    with pytest.raises(ValueError, match="INSIDE a ring group"):
        engine_module._refuse_unported(EngineConfig(prefix_cache=False),
                                       groups[0], ring_side)


def test_page_shipments_and_forks_of_latent_groups_raise(params):
    from accelerate_tpu.serving.pod.transfer import PageTransport

    eng = _engine(params)
    with pytest.raises(ValueError, match="one group a layer kind"):
        PageTransport(eng)


@pytest.mark.parametrize("changed,match", [
    (dict(layer_types=["full_attention"] * 3 + ["chunked_attention"]),
     "unknown kinds"),
    (dict(layer_types=["full_attention"] * 3), "got 3 entries"),
    (dict(topk_method="greedy"), "noaux_tc"),
    (dict(attention_gate_type="elementwise"), "headwise"),
    (dict(num_key_value_heads=2), "num_key_value_heads equals"),
    (dict(tie_word_embeddings=True), "tie_word_embeddings=False"),
    (dict(rope_scaling={"rope_type": "yarn"}), "rope_scaling=None"),
    (dict(index_topk=0), "index_topk"),
    (dict(scoring_func="softmax"), "scoring_func='sigmoid'"),
])
def test_the_config_refuses_what_is_not_implemented(changed, match):
    with pytest.raises(ValueError, match=match):
        dots3.Dots3Config.tiny(**changed)


def test_the_config_is_hashable_as_published():
    """`layer_types` and `experts_held` come as lists; the published
    pattern is the default; the cache's groups follow the kinds."""
    a = dots3.Dots3Config.tiny(layer_types=list(CFG.layer_types),
                               experts_held=[2, 4])
    assert a == CFG and hash(a) == hash(CFG)
    published = dots3.Dots3Config()
    assert published.layer_types[:6] == (
        dots3.FULL, dots3.FULL, dots3.SLIDING, dots3.SLIDING, dots3.SLIDING,
        dots3.FULL)
    assert (len(published.layers_of(dots3.FULL)),
            len(published.layers_of(dots3.SLIDING))) == (13, 33)
    full, ring = dots3.cache_spec(published)
    assert (full.kind, full.width, full.side_width, full.window,
            full.num_layers) == ("latent", 640, 128, None, 13)
    assert (ring.kind, ring.width, ring.side_width, ring.window,
            ring.num_layers) == ("latent", 1152, 0, 513, 33)
    assert published.mla(dots3.SLIDING).qk_head_dim == 256
    with pytest.raises(ValueError, match="no full_attention layer"):
        dots3.cache_spec(dots3.Dots3Config.tiny(
            layer_types=[dots3.SLIDING] * 4))
