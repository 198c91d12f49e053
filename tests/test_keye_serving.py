"""The family whose attention chooses its keys (`models/keye.py`: a learned
indexer selects `topk` keys a query, M-RoPE, a softmax-routed expert layer)
on the CPU at a small size, seeded weights: against the benchmark's plain
reference (`chipbench/references/keye_vl2.py`: the index scores, the
selection and the attention scores a block of query rows against every
position, every expert by a masked combine) through every cache form the
engine uses; the index-key pool that lives in K's and V's pages, through
a prefix hit and a fork; the counters; what raises.

Size: 2 layers, 2 KV heads of 128 so that the sparse kernel runs, a 4 x 64
indexer, `topk` 24, 8 experts top-2. Every context here is LONGER than
`topk`, so the selection bites: with it off the logits move by 0.4
(`test_selection_off_is_another_model`). Logits are compared, not tokens.
Tolerance as in `tests/test_mixed_window_serving.py`: float32 weights and
caches under `jax.default_matmul_precision("highest")`; program and
reference sum in different orders, which reads 4e-7 on logits of order
0.5."""

import dataclasses
import importlib.util
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from accelerate_tpu.models import keye
from accelerate_tpu.serving import Engine, EngineConfig
from accelerate_tpu.serving.cache import PagedKVCache, WithSide

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
TOL = 2e-5


def _reference():
    spec = importlib.util.spec_from_file_location(
        "keye_vl2_reference", os.path.join(
            ROOT, "chipbench", "references", "keye_vl2.py"))
    module = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(module)
    return module


REF = _reference()
CFG = keye.KeyeConfig.tiny()
TOPK = CFG.topk


def _ref_cfg(config):
    out = {f: getattr(config, f) for f in config.__dataclass_fields__}
    out["sa_config"] = config.indexer
    out["rope_scaling"] = dict(config.rope_scaling)
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
            lambda p, i: keye.forward(config, p, i, **kw))(params, ids))


# ---------------------------------------------------------------------------
# the model against the reference
# ---------------------------------------------------------------------------


def test_the_trees_of_program_and_reference_are_one(params):
    mine = jax.eval_shape(lambda: keye.init_params(
        CFG, jax.random.key(0), jnp.float32))
    assert jax.tree.structure(mine) == jax.tree.structure(params)
    assert jax.tree.map(lambda a: a.shape, mine) == jax.tree.map(
        lambda a: a.shape, params)
    assert REF.param_count(REF_CFG) == sum(
        int(np.prod(a.shape)) for a in jax.tree.leaves(mine))


def test_full_forward_agrees_with_the_reference(params, ids, ref_logits):
    """120 positions, five times `topk`."""
    assert np.abs(_forward(CFG, params, ids) - ref_logits).max() < TOL


def test_selection_off_is_another_model(params, ids, ref_logits):
    with jax.default_matmul_precision("highest"):
        dense = np.asarray(jax.jit(lambda p, row: REF.logits(
            REF_CFG, p, row, selection=False))(params, _padded(ids[0])))
    assert np.abs(dense[:120] - ref_logits[0]).max() > 0.1
    # up to `topk` positions nothing is left out: the same model
    assert np.abs(dense[:TOPK] - ref_logits[0, :TOPK]).max() < TOL


@pytest.mark.parametrize("what,changed", [
    ("a query selects 25 keys", dict(topk=TOPK + 1)),
    ("a query selects 23 keys", dict(topk=TOPK - 1)),
    ("every key is attended", dict(topk=4096)),
])
def test_a_one_off_selection_fails_the_comparison(params, ids, ref_logits,
                                                  what, changed):
    wrong = keye.KeyeConfig.tiny(sa_config=dict(CFG.indexer, **changed))
    assert np.abs(_forward(wrong, params, ids[:1])
                  - ref_logits[:1]).max() > 50 * TOL, what


def test_mrope_with_three_different_position_rows(params, ids):
    """A temporal, a height and a width id that differ (an image's patches
    would give such): program and reference rotate pair i by the row of
    its section. The temporal row stays increasing: it is causality's."""
    at = np.arange(120)
    pos = np.stack([at, at // 3, at % 7 + at // 2])
    with jax.default_matmul_precision("highest"):
        want = np.asarray(jax.jit(lambda p, row: REF.logits(
            REF_CFG, p, row, positions=pos))(params, jnp.asarray(ids[0])))
    got = _forward(CFG, params, ids[:1],
                   positions=jnp.asarray(pos)[:, None, :])[0]
    assert np.abs(got - want).max() < TOL
    # and the three rows matter: text positions give other logits
    assert np.abs(_forward(CFG, params, ids[:1])[0] - want).max() > 50 * TOL


def test_apply_mrope_is_apply_rope_on_equal_rows():
    from accelerate_tpu.models.common import (
        apply_mrope,
        apply_rope,
        rope_frequencies,
    )

    x = jax.random.normal(jax.random.key(0), (2, 9, 3, 128))
    cos, sin = rope_frequencies(128, 64, 10000.0)
    pos = jnp.asarray(np.random.default_rng(0).integers(0, 64, (2, 9)))
    same = apply_mrope(x, cos, sin, jnp.stack([pos] * 3), (16, 24, 24))
    np.testing.assert_allclose(np.asarray(same),
                               np.asarray(apply_rope(x, cos, sin, pos)),
                               rtol=1e-6, atol=1e-6)
    with pytest.raises(ValueError, match="must sum to head_dim / 2"):
        apply_mrope(x, cos, sin, jnp.stack([pos] * 3), (16, 24, 25))


def test_chunked_prefill_then_decode_through_views(params, ids, ref_logits):
    """Chunks of 16 and then single tokens through the views `generate`
    uses: each call's index keys are written beside K and V and every
    query selects over all of them."""
    with jax.default_matmul_precision("highest"):
        caches = keye.init_kv_caches(CFG, 2, 128, jnp.float32)
        assert isinstance(caches[0], WithSide)
        step = jax.jit(lambda p, i, c: keye.forward(CFG, p, i, kv_caches=c))
        got = []
        for start in range(0, 96, 16):
            out, caches = step(params, ids[:, start:start + 16], caches)
            got.append(np.asarray(out))
        for at in range(96, 104):
            out, caches = step(params, ids[:, at:at + 1], caches)
            got.append(np.asarray(out))
    assert np.abs(np.concatenate(got, axis=1)
                  - ref_logits[:, :104]).max() < TOL


# ---------------------------------------------------------------------------
# the cache
# ---------------------------------------------------------------------------


def test_the_side_row_lies_in_whole_lane_rows():
    """A page of 16 tokens x 64 lanes is stored as [8, 128]: ONE layout; a
    shape that is no whole number of 128-lane rows raises."""
    cache = PagedKVCache.create(6, 4, 64, 4, 128, page_size=16, num_pages=20,
                                side_width=64)
    assert cache.side.shape == (6, 21, 8, 128)
    assert cache.side_page_nbytes == 6 * 16 * 64 * 2
    assert cache.page_nbytes == 16 * (6 * 2 * 4 * 128 * 2 + 6 * 64 * 2)
    assert cache.page_nbytes == 16 * 13_056         # the issue's arithmetic
    assert cache.nbytes() == cache.k.nbytes * 2 + cache.side.nbytes
    for odd in (dict(page_size=4, side_width=24),    # 24 does not divide 128
                dict(page_size=1, side_width=64)):   # half a row a page
        with pytest.raises(ValueError, match="whole 128-lane rows"):
            PagedKVCache.create(2, 2, 16, 2, 8, num_pages=8, **odd)
    for bad in (dict(kv_dtype="int8"), dict(window=8)):
        with pytest.raises(ValueError, match="side row"):
            PagedKVCache.create(2, 2, 16, 2, 8, page_size=16, side_width=8,
                                **bad)


def _engine(params, config=CFG, **kw):
    args = dict(num_slots=3, max_len=128, prefill_chunk=16, page_size=16,
                cache_dtype=jnp.float32, prefix_cache=True,
                paged_attention=False)
    args.update(kw)
    return Engine(keye, config, params, EngineConfig(**args))


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
    (False, [(70, 30), (5, 50), (100, 20), (33, 30), (37, 60)]),
    # the kernels interpreted: keep the decode short; contexts past `topk`
    (True, [(75, 5), (40, 6), (60, 7), (30, 4)]),
], ids=["dense", "kernel"])
def test_engine_serves_chunks_then_decode_through_the_paged_cache(
        params, ids, kernel, shapes):
    """Chunked prefill through the gathered views, then paged decode (the
    dense gather, and the two Pallas kernels interpreted with the
    selection between them), against the reference's full pass: every
    served token is the reference's first choice by its own logits, and
    the engine's log-probability of it is the reference's."""
    with jax.default_matmul_precision("highest"):
        eng = _engine(params, paged_attention=kernel, prefix_cache=False)
        assert eng.cache.side.shape == (2, 3 * 9 + 1, 8, 128)
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
    # the device counters: the experts', and the keys seen and selected
    got = eng.device_counters()
    layers = CFG.num_hidden_layers
    assert int(got["prefill"]["calls"]) == eng.metrics.prefill_chunks
    assert int(got["decode"]["calls"]) == eng.metrics.decode_steps
    # a prompt token at position p sees p + 1 keys and selects min(p + 1,
    # topk); a decode step's token sits at prompt + generated - 1
    def keys(positions):
        positions = np.asarray(positions)
        return (layers * int((positions + 1).sum()),
                layers * int(np.minimum(positions + 1, TOPK).sum()))

    prefill = np.concatenate([np.arange(n) for n, _ in shapes])
    decode = np.concatenate([n + np.arange(m - 1) for n, m in shapes])
    for program, positions in (("prefill", prefill), ("decode", decode)):
        assert (keye.wide_count(got[program]["keys_visible"]),
                keye.wide_count(got[program]["keys_selected"])) == keys(
                    positions), program


@pytest.mark.parametrize("kv_block", [16, 512],
                         ids=["scores-laid-out", "blocks-as-scored"])
def test_a_chunk_of_whole_row_tiles_selects_in_the_rows_kernel(
        params, ids, kv_block):
    """A chunk of 32 query rows is one tile of `sparse_topk_select_rows`
    (interpreted here), and a view of 1,056 rows is three of its counting
    steps: the engine serves the reference's logits with the kernel on
    the path, and the prefill program's counters say what the live bound
    let it skip: every chunk ends below position 512, so the selection
    scanned ONE step of a view it would else scan whole. The decode steps
    (one row a slot over the same views) keep XLA's loop, and their
    program neither counts columns nor is handed the chunks' counters.
    The indexer's blocks of 16 columns are laid side by side for the
    kernel; blocks of whole counting steps (as the cells score them) it
    reads where they lie."""
    with jax.default_matmul_precision("highest"):
        eng = _engine(params, dataclasses.replace(CFG, kv_block=kv_block),
                      max_len=1024, prefill_chunk=32, prefix_cache=False)
        prompts = [ids[0, :75], ids[1, 3:43]]
        reqs = [eng.submit(p, max_new_tokens=3, temperature=0.0)
                for p in prompts]
        eng.run_until_idle()
    for prompt, req in zip(prompts, reqs):
        assert req.status.value == "finished"
        _agrees_with_the_reference(params, prompt, req)
    assert eng.compile_stats() == {"admit": 1, "prefill": 1, "decode": 1}
    got = eng.device_counters()
    layers, view = CFG.num_hidden_layers, 1024 + 32
    chunks, steps = eng.metrics.prefill_chunks, eng.metrics.decode_steps
    assert chunks == 3 + 2
    prefill = {name: keye.wide_count(got["prefill"][name])
               for name in keye.CHUNK_COUNTERS}
    assert prefill == {"select_columns_scanned": chunks * layers * 32 * 512,
                       "select_columns_total": chunks * layers * 32 * view}
    # `decode` is not handed them: its program is what it was without
    assert steps > 0 and not set(keye.CHUNK_COUNTERS) & set(got["decode"])
    assert set(keye.SELECTION_COUNTERS) <= set(got["decode"])


def test_a_program_holds_the_rows_kernel_once_and_decode_none_of_it(
        monkeypatch):
    """Six layers, the engine's programs lowered for the chip: `prefill`
    holds the rows kernel's body ONCE (one Mosaic payload, in a function
    of its own that the six layers call), so what a process pays to trace
    and lower it does not grow with the model's depth; `decode` (one row a
    slot: XLA's loop) holds nothing of it, and none of the chunks'
    counters is among its arguments."""
    from accelerate_tpu.ops import kernel_mode
    from accelerate_tpu.ops.sparse_paged_attention import ROWS_SELECT_NAME

    monkeypatch.setattr(kernel_mode, "resolve_interpret",
                        lambda name, interpret=None: False)
    cfg = keye.KeyeConfig.tiny(num_hidden_layers=6, kv_block=512)
    abstract = jax.eval_shape(
        lambda: keye.init_params(cfg, jax.random.key(0), jnp.float32))
    eng = _engine(abstract, cfg, max_len=1024, prefill_chunk=32,
                  paged_attention=True)
    state = (eng.params, eng.cache, eng._tokens, eng._slot_keys, eng._temps)
    prefill = eng._prefill_p.trace(
        *state, jnp.int32(0), eng._tables(0), np.zeros((32,), np.int32),
        jnp.int32(32), eng._chunk_stats).lower(
            lowering_platforms=("tpu",)).as_text()
    assert prefill.count("tpu_custom_call") == 1
    assert prefill.count("func.func private @_select_rows(") == 1
    assert prefill.count("call @_select_rows(") == 6
    decode = eng._decode_p.trace(
        *state, np.ones((3,), bool), eng._tables()).lower(
            lowering_platforms=("tpu",))
    assert decode.as_text().count("tpu_custom_call") == 2 * 6
    assert ROWS_SELECT_NAME not in decode.as_text()
    assert "_select_rows" not in decode.as_text()
    handed = {path[-1].key for path, _ in jax.tree_util.tree_leaves_with_path(
        eng.cache.stats) if hasattr(path[-1], "key")}
    assert {"keys_visible", "keys_selected"} <= handed
    assert not set(keye.CHUNK_COUNTERS) & handed
    assert set(keye.CHUNK_COUNTERS) == set(eng._chunk_stats)


def test_a_prefix_hit_serves_the_logits_of_a_cold_request(params, ids):
    """The index keys came with the pages: a request whose first 64
    positions are a cached document selects among THOSE index keys, never
    recomputed, and serves what a cold engine serves."""
    doc, q1, q2 = ids[0, :64], ids[1, :9], ids[1, 20:31]
    with jax.default_matmul_precision("highest"):
        warm = _engine(params)
        first = warm.submit(np.concatenate([doc, q1]), max_new_tokens=4,
                            temperature=0.0)
        warm.run_until_idle()
        chunks = warm.metrics.prefill_chunks
        hit = warm.submit(np.concatenate([doc, q2]), max_new_tokens=12,
                          temperature=0.0)
        warm.run_until_idle()
        cold_engine = _engine(params, prefix_cache=False)
        cold = cold_engine.submit(np.concatenate([doc, q2]),
                                  max_new_tokens=12, temperature=0.0)
        cold_engine.run_until_idle()
    assert first.status.value == hit.status.value == "finished"
    assert warm.metrics.prefix_tokens_reused == 64
    assert warm.metrics.prefill_chunks - chunks == 1    # the question alone
    assert hit.tokens == cold.tokens
    np.testing.assert_allclose(hit.logprobs, cold.logprobs, rtol=0,
                               atol=1e-5)
    _agrees_with_the_reference(params, np.concatenate([doc, q2]), hit)
    # the gauge of the index pool's bytes: what the held pages' index keys
    # weigh, a part of serving_kv_bytes_in_use
    summary = warm.metrics_summary()
    assert summary["kv_side_bytes_in_use"] == (
        warm.allocator.pages_in_use * warm.cache.side_page_nbytes)
    assert 0 < summary["kv_side_bytes_in_use"] < summary["kv_bytes_in_use"]


def test_a_fork_shares_the_parents_index_keys(params, ids):
    """A fork maps its parent's prompt pages copy-on-write: K, V and the
    index keys under one page id. Greedy forks serve the parent's tokens."""
    prompt = ids[0, :70]
    with jax.default_matmul_precision("highest"):
        eng = _engine(params, num_slots=3)
        parent = eng.submit(prompt, max_new_tokens=6, temperature=0.0)
        forks = [eng.fork(parent) for _ in range(2)]
        eng.run_until_idle()
    # 5 cold chunks of 16, and one catch-up chunk a fork (6 tokens past the
    # 4 shared pages)
    assert eng.metrics.prefill_chunks == 5 + 2
    for req in forks:
        assert req.tokens == parent.tokens
    _agrees_with_the_reference(params, prompt, parent)
    assert eng.allocator.pages_in_use == eng.allocator.index.cached_pages


# ---------------------------------------------------------------------------
# what raises
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("option,match", [
    (dict(kv_dtype="int8"), "the side row's codes and scales"),
    (dict(host_tier_bytes=1 << 20), "no side row"),
    (dict(mesh="two-devices"), "a sharded index pool"),
    (dict(speculative="draft"), "multi-token selection"),
])
def test_unported_combinations_raise_at_construction(params, option, match):
    if option.get("mesh"):
        option = dict(mesh=jax.sharding.Mesh(
            np.array(jax.devices()[:2]), ("model",)))
    if option.get("speculative"):
        option = dict(speculative=(keye, CFG, params))
    with pytest.raises(ValueError, match=match) as err:
        Engine(keye, CFG, params, EngineConfig(
            num_slots=2, max_len=64, prefill_chunk=8, **option))
    assert "Nothing falls back to attention over every key" in str(err.value)


def test_page_shipments_of_a_pool_with_a_side_row_raise(params):
    from accelerate_tpu.serving.pod.transfer import PageTransport

    with pytest.raises(ValueError, match="an indexer's keys too"):
        PageTransport(_engine(params))


@pytest.mark.parametrize("changed,match", [
    (dict(sa_config=dict(CFG.indexer, indexer_num_kv_heads=2)),
     "ONE indexer key a token"),
    (dict(sa_config=dict(CFG.indexer, topk=0)), "topk >= 1"),
    (dict(rope_scaling={"mrope_section": [16, 24, 25]}), "mrope_section"),
    (dict(rope_scaling={"rope_type": "yarn", "mrope_section": [16, 24, 24]}),
     "rope_type 'default'"),
    (dict(decoder_sparse_step=2), "an expert layer in every block"),
    (dict(mlp_only_layers=[0]), "an expert layer in every block"),
    (dict(norm_topk_prob=False), "norm_topk_prob=False"),
    (dict(tie_word_embeddings=True), "tie_word_embeddings=False"),
])
def test_the_config_refuses_what_is_not_implemented(changed, match):
    with pytest.raises(ValueError, match=match):
        keye.KeyeConfig.tiny(**changed)


def test_the_config_is_hashable_as_published():
    as_published = dict(
        num_hidden_layers=6, mlp_only_layers=[],
        rope_scaling={"mrope_section": [16, 24, 24], "rope_type": "default",
                      "type": "default"},
        sa_config={"indexer_head_dim": 64, "indexer_num_heads": 16,
                   "indexer_num_kv_heads": 1, "kv_chunk_size": 512,
                   "q_chunk_size": 512, "topk": 2048})
    published = keye.KeyeConfig(**as_published)
    assert hash(published) == hash(keye.KeyeConfig(**as_published))
    assert keye.KeyeConfig(num_hidden_layers=6).indexer == published.indexer
    assert published.topk == 2048 and published.mrope_section == (16, 24, 24)
    spec = keye.cache_spec(published)
    assert (spec.num_layers, spec.heads, spec.width, spec.side_width,
            spec.kind) == (6, 4, 128, 64, "kv")
