"""The family whose layers differ in kind (`models/mellum.py`: sliding-window
and full attention mixed by layer, two rotary tables, a softmax-routed
expert layer) on the CPU at a small size, seeded weights: against the
benchmark's plain reference (`chipbench/references/mellum2.py`: a full
`[positions, positions]` mask a layer kind, every expert by a masked
combine) through every cache form the engine uses; the cache with one
group a layer kind (a ring of pages a slot for the sliding layers), its
allocator, gauges and sanitizer; what raises.

Size: 8 layers in the published pattern (s s s f s s s f), 4 KV heads of
128 so that the LIVE-pages kernel runs, window 32, 8 experts top-2, YaRN
with an original length of 64 so that its ramp is crossed. Logits are
compared, not tokens. Tolerances: float32 weights and caches under
`jax.default_matmul_precision("highest")`; the program and the reference
sum in different orders (blocks of keys against one softmax, grouped
products against a masked combine), which reads 4e-7 on logits of order
0.5; 2e-5 leaves room for other seeds and is two orders below what any of
the one-off errors at the foot of this file reads."""

import importlib.util
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from accelerate_tpu.models import mellum
from accelerate_tpu.models.common import rope_frequencies, softmax_moe_layer
from accelerate_tpu.ops.grouped_experts import softmax_topk_route
from accelerate_tpu.serving import Engine, EngineConfig
from accelerate_tpu.serving.sanitizer import SanitizerViolation, check_engine

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
TOL = 2e-5


def _reference():
    spec = importlib.util.spec_from_file_location(
        "mellum2_reference", os.path.join(
            ROOT, "chipbench", "references", "mellum2.py"))
    module = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(module)
    return module


REF = _reference()
CFG = mellum.MellumConfig.tiny()
WINDOW = CFG.sliding_window


def _ref_cfg(config):
    """The plain dict with the published keys that the reference reads;
    `attention_factor` written out as the published file has it."""
    out = {f: getattr(config, f) for f in config.__dataclass_fields__}
    out["layer_types"] = list(config.layer_types)
    out["rope_parameters"] = {k: config.rope_of(k) for k in (
        mellum.FULL, mellum.SLIDING)}
    yarn = out["rope_parameters"][mellum.FULL]
    if yarn["rope_type"] == "yarn":
        yarn.setdefault("attention_factor",
                        0.1 * np.log(yarn["factor"]) + 1.0)
    return out


REF_CFG = _ref_cfg(CFG)
PAD = 160  # every reference pass runs at this one length (one compile)


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
        jax.random.key(3), (2, 150), 0, CFG.vocab_size))


@pytest.fixture(scope="module")
def ref_logits(params, ids):
    return np.stack([np.asarray(_ref_logits(params, _padded(row)))[:150]
                     for row in ids])


def _forward(config, params, ids):
    with jax.default_matmul_precision("highest"):
        return np.asarray(jax.jit(
            lambda p, i: mellum.forward(config, p, i))(params, ids))


# ---------------------------------------------------------------------------
# the model against the reference
# ---------------------------------------------------------------------------


def test_the_trees_of_program_and_reference_are_one(params):
    mine = jax.eval_shape(lambda: mellum.init_params(
        CFG, jax.random.key(0), jnp.float32))
    assert jax.tree.structure(mine) == jax.tree.structure(params)
    assert jax.tree.map(lambda a: a.shape, mine) == jax.tree.map(
        lambda a: a.shape, params)
    assert REF.param_count(REF_CFG) == sum(
        int(np.prod(a.shape)) for a in jax.tree.leaves(mine))
    assert CFG.layer_types == (mellum.SLIDING,) * 3 + (mellum.FULL,) + (
        mellum.SLIDING,) * 3 + (mellum.FULL,)


def test_full_forward_agrees_with_the_reference(params, ids, ref_logits):
    """150 positions: past the window (32) and past YaRN's original
    length (64)."""
    assert np.abs(_forward(CFG, params, ids) - ref_logits).max() < TOL


@pytest.mark.parametrize("what,changed", [
    ("a sliding layer sees 33 keys", dict(sliding_window=WINDOW + 1)),
    ("a sliding layer sees 31 keys", dict(sliding_window=WINDOW - 1)),
    ("a full layer rotates by the plain table", dict(rope_parameters={
        mellum.FULL: {"rope_type": "default", "rope_theta": 10000.0},
        mellum.SLIDING: {"rope_type": "default", "rope_theta": 10000.0}})),
    ("q and k are not normalised", dict(qk_norm=False)),
])
def test_a_one_off_error_fails_the_comparison(params, ids, ref_logits, what,
                                              changed):
    """The comparison above is tight enough to tell each of these from the
    model: by a hundred tolerances and more."""
    wrong = mellum.MellumConfig.tiny(**changed)
    tree = params
    if not wrong.qk_norm:
        tree = dict(params, layers=[dict(lay, attn={
            k: v for k, v in lay["attn"].items() if "norm" not in k})
            for lay in params["layers"]])
    assert np.abs(_forward(wrong, tree, ids) - ref_logits).max() > 100 * TOL, \
        what


def test_chunked_prefill_then_decode_through_views(params, ids, ref_logits):
    """`generate`'s caches: chunks of 16 then one token at a time, every
    group's view written at `position % rows`, on logits, to position 80:
    past the window."""
    caches = mellum.init_kv_caches(CFG, 2, 96, jnp.float32)
    assert [v.shape for v in caches[0]] == [(2, 2, 96, 4, 128),
                                            (6, 2, 96, 4, 128)]
    step = jax.jit(lambda p, tokens, caches: mellum.forward(
        CFG, p, tokens, kv_caches=caches))
    at = 0
    with jax.default_matmul_precision("highest"):
        for size in (16,) * 4 + (1,) * 16:
            logits, caches = step(params, ids[:, at:at + size], caches)
            assert np.abs(np.asarray(logits)
                          - ref_logits[:, at:at + size]).max() < TOL, at
            at += size
    assert int(caches[2]) == 80


def test_rope_frequencies_yarn_against_the_formula():
    """`f_i = theta^(-2i/d)`, `c(r) = d ln(original / (2 pi r)) / (2 ln
    theta)`, `low = max(floor(c(beta_fast)), 0)`, `high = min(ceil(c(
    beta_slow)), d - 1)`, `ramp_i = clip((i - low) / (high - low), 0, 1)`,
    `inv_freq_i = (f_i / factor) ramp_i + f_i (1 - ramp_i)`, cos and sin
    times `attention_factor`: at the published numbers and at the tiny
    ones, whose ramp the 64 pairs cross."""
    for d, theta, factor, original, fast, slow, amp in (
            (128, 500000.0, 16.0, 8192, 32, 1, 1.2772588722239782),
            (128, 10000.0, 4.0, 64, 32, 1, None)):
        i = np.arange(d // 2)
        f = theta ** (-2.0 * i / d)

        def c(r):
            return d * np.log(original / (2 * np.pi * r)) / (2 * np.log(theta))

        low = max(np.floor(c(fast)), 0)
        high = min(np.ceil(c(slow)), d - 1)
        ramp = np.clip((i - low) / (high - low), 0, 1)
        assert 0 < ramp.min() + ramp.max() and ramp.max() == 1  # crossed
        inv = f / factor * ramp + f * (1 - ramp)
        a = amp if amp is not None else 0.1 * np.log(factor) + 1
        t = np.arange(300)[:, None] * inv[None, :]
        scaling = {"rope_type": "yarn", "factor": factor, "rope_theta": theta,
                   "original_max_position_embeddings": original,
                   "beta_fast": fast, "beta_slow": slow}
        if amp is not None:
            scaling["attention_factor"] = amp
        cos, sin = rope_frequencies(d, 300, theta, scaling=scaling)
        assert np.abs(np.asarray(cos) - a * np.cos(t)).max() < 1e-6
        assert np.abs(np.asarray(sin) - a * np.sin(t)).max() < 1e-6
        plain, _ = rope_frequencies(d, 300, theta)
        assert np.abs(np.asarray(cos) - np.asarray(plain)).max() > 0.1
        want = REF.rotary_table(dict(
            head_dim=d, rope_parameters={"k": dict(scaling,
                                                   attention_factor=a)}),
            "k", 300)
        assert np.abs(np.asarray(cos) - np.asarray(want[0])).max() < 1e-6


@pytest.mark.parametrize("case", ["all-on-two", "seeded"])
def test_router_and_expert_layer_against_the_masked_combine(params, case):
    """`all-on-two`: a zero router gives every expert the same probability
    and the top-2 of a tie are experts 0 and 1 for EVERY token (a capacity
    dispatch would drop most of them; nothing is dropped here)."""
    E, k = CFG.num_experts, CFG.num_experts_per_tok
    x = jax.random.normal(jax.random.key(9), (2, 12, CFG.hidden_size))
    flat = x.reshape(24, -1)
    m = dict(params["layers"][1]["moe"])
    if case == "all-on-two":
        m["router"] = {"kernel": jnp.zeros((CFG.hidden_size, E))}
    with jax.default_matmul_precision("highest"):
        want = np.asarray(REF.moe(REF_CFG, m, flat))
        experts, weights = REF.route(REF_CFG, m, flat)
        got, counts = softmax_moe_layer(CFG, m, x)
        mine, mine_w = softmax_topk_route(flat, m["router"]["kernel"], k)
        _, raw = softmax_topk_route(flat, m["router"]["kernel"], k,
                                    norm_topk=False)
    assert np.abs(np.asarray(got).reshape(24, -1) - want).max() < TOL
    assert np.array_equal(np.asarray(mine), np.asarray(experts))
    assert np.abs(np.asarray(mine_w) - np.asarray(weights)).max() < 1e-6
    assert np.allclose(np.asarray(mine_w).sum(-1), 1.0, atol=1e-6)
    # weights that are not renormalised are another router: a quarter of
    # the mass (2 of 8 experts) at a tie, and far outside the tolerance
    assert np.abs(np.asarray(raw) - np.asarray(weights)).max() > 0.05
    assert np.array_equal(np.asarray(counts), np.bincount(
        np.asarray(experts).ravel(), minlength=E))
    if case == "all-on-two":
        assert counts[0] == counts[1] == 24 and int(counts.sum()) == 48
        assert np.allclose(np.asarray(weights), 0.5)


# ---------------------------------------------------------------------------
# the ring kernel alone
# ---------------------------------------------------------------------------


def test_window_kernel_over_a_ring_against_the_dense_reference():
    """The live-pages kernel over rings of 5 pages of 8 rows under a
    window of 24: slots that have not filled the ring, one that has
    wrapped it twice, a dead lane of length 0; the ring's stale rows hold
    LARGE values, so a row seen that should not be shows."""
    from accelerate_tpu.ops import kernel_mode
    from accelerate_tpu.ops.paged_attention import (
        WINDOW_KERNEL_NAME,
        PagedDecodeMeta,
        PagedKV,
        paged_decode_attention,
        paged_decode_reference,
    )

    L, S, P, ps, Hkv, G, D, window = 2, 4, 5, 8, 4, 2, 128, 24
    k = jax.random.split(jax.random.key(0), 5)
    pool_k = jax.random.normal(k[0], (L, S * P + 1, Hkv, ps, D))
    pool_v = 30.0 * jax.random.normal(k[1], (L, S * P + 1, Hkv, ps, D))
    table = jnp.arange(S * P, dtype=jnp.int32).reshape(S, P)
    lengths = jnp.asarray([0, 13, 40, 97], jnp.int32)
    q = jax.random.normal(k[2], (S, 1, Hkv * G, D))
    kn = jax.random.normal(k[3], (S, 1, Hkv, D))
    vn = jax.random.normal(k[4], (S, 1, Hkv, D))
    meta = PagedDecodeMeta(table, lengths, rows=P * ps)
    for layer in range(L):
        pk = PagedKV(pool_k, None, jnp.float32, jnp.int32(layer))
        pv = PagedKV(pool_v, None, jnp.float32, jnp.int32(layer))
        got, _ = paged_decode_attention(q, kn, vn, pk, pv, meta,
                                        window=window, ring=True)
        want, _ = paged_decode_reference(q, kn, vn, pk, pv, meta,
                                         window=window, ring=True)
        # (outputs of order 50: the stale rows are LARGE on purpose)
        assert np.abs(np.asarray(got) - np.asarray(want)).max() < 1e-3
        # one key more or less is another answer: the mask is exact
        off, _ = paged_decode_reference(q, kn, vn, pk, pv, meta,
                                        window=window + 1, ring=True)
        assert np.abs(np.asarray(off)[2:] - np.asarray(want)[2:]).max() > 1e-2
    assert WINDOW_KERNEL_NAME in kernel_mode.kernel_report()
    with pytest.raises(ValueError, match="ring of pages"):
        paged_decode_attention(q, kn, vn, pk, pv, meta, window=None,
                               ring=True)


# ---------------------------------------------------------------------------
# the engine and its cache with one group a layer kind
# ---------------------------------------------------------------------------


def _engine(params, **engine):
    kwargs = dict(num_slots=2, max_len=256, prefill_chunk=16, page_size=8,
                  cache_dtype=jnp.float32, prefix_cache=False)
    kwargs.update(engine)
    return Engine(mellum, CFG, params, EngineConfig(**kwargs))


def _teacher_forced(params, prompt, tokens):
    seq = np.concatenate([np.asarray(prompt), tokens])
    out = np.asarray(_ref_logits(params, _padded(seq)))
    return out[len(prompt) - 1:len(prompt) - 1 + len(tokens)]


@pytest.mark.parametrize("kernel,shapes", [
    # (prompt, new tokens): a ring is 7 pages of 8 = 56 rows. Dense: five
    # requests over two slots, sessions of 110 and 95 positions (the ring
    # wraps in prefill and again in decode), short ones reusing the slots
    (False, [(70, 40), (5, 90), (100, 20), (33, 30), (17, 60)]),
    # the kernels interpreted (~1 s a step): the ring wraps in prefill and
    # is read wrapped from the first decode step; slot reuse too
    (True, [(75, 5), (9, 6), (60, 7), (30, 4), (58, 3)]),
], ids=["dense", "kernel"])
def test_engine_serves_through_the_mixed_cache(params, ids, kernel, shapes):
    """Chunked prefill through the gathered views, then paged decode (the
    dense gather, and both Pallas kernels interpreted), against the
    reference's full pass: every served token is the reference's first
    choice by its own logits, and the engine's log-probability of it is
    the reference's. A ring's stale rows (the last tenant's) are not
    seen: the requests that reuse a slot agree like the first."""
    with jax.default_matmul_precision("highest"):
        eng = _engine(params, paged_attention=kernel)
        full, ring = eng.cache.groups
        assert (full.window, ring.window) == (None, WINDOW)
        assert ring.pages_per_slot == (WINDOW + 16) // 8 + 1 == 7
        assert ring.num_pages == 2 * 7 and full.pages_per_slot == 34
        assert eng.cache.layers == ((3, 7), (0, 1, 2, 4, 5, 6))
        prompts = [ids[i % 2, i:i + n] for i, (n, _) in enumerate(shapes)]
        reqs = [eng.submit(p, max_new_tokens=m, temperature=0.0)
                for p, (_, m) in zip(prompts, shapes)]
        eng.run_until_idle()
    assert eng._use_paged_kernel is kernel
    for prompt, req in zip(prompts, reqs):
        assert req.status.value == "finished"
        ref = _teacher_forced(params, prompt, np.asarray(req.tokens))
        at = np.arange(len(req.tokens))
        lp = ref - jax.nn.logsumexp(ref, axis=-1, keepdims=True)
        assert np.abs(ref.max(-1) - ref[at, req.tokens]).max() < 1e-4
        assert np.abs(np.asarray(lp)[at, req.tokens]
                      - np.asarray(req.logprobs)).max() < 1e-4
    assert eng.compile_stats() == {"admit": 1, "prefill": 1, "decode": 1}
    assert eng.allocator.pages_in_use == 0
    assert eng.allocator.ring_pages_in_use == (0,)
    # the new family's device counters, as the latent family's
    got = eng.device_counters()
    k, layers = CFG.num_experts_per_tok, CFG.num_hidden_layers
    assert got["prefill"]["assignments"].shape == (layers, CFG.num_experts)
    assert int(got["prefill"]["calls"]) == eng.metrics.prefill_chunks
    assert int(got["decode"]["calls"]) == eng.metrics.decode_steps
    assert got["prefill"]["assignments"].sum(-1).tolist() == [
        sum(n for n, _ in shapes) * k] * layers
    assert got["decode"]["assignments"].sum(-1).tolist() == [
        sum(m - 1 for _, m in shapes) * k] * layers


def test_a_sliding_group_holds_its_ring_at_any_length(params, ids):
    """A slot decodes 10 x the window: the sliding group holds the ring it
    took at admission, the same pages, to the end (nothing on a step's
    path walks or moves them); the full group's pages follow the request.
    The group gauges and `debug_pages` show both."""
    from accelerate_tpu.telemetry.trace import flight_recorder
    from accelerate_tpu.telemetry.trace import configure_tracing

    configure_tracing(True)
    try:
        eng = _engine(params, num_slots=1, max_len=400, paged_attention=False)
        bound = eng.cache.groups[1].pages_per_slot
        assert bound == 7  # window + chunk + a page of rounding, in pages
        req = eng.submit(ids[0, :20], max_new_tokens=10 * WINDOW)
        slot = eng.scheduler.slots[0]
        ring = list(slot.alloc.rings[0])
        rings = slot.alloc.rings
        full_pages = len(slot.alloc.pages)
        assert full_pages == -(-(20 + 10 * WINDOW + 16) // 8) == 45
        seen = set()
        while eng.step():
            if slot.alloc is not None:
                assert slot.alloc.rings is rings and rings[0] == ring
                assert eng.allocator.ring_pages_in_use == (bound,)
                seen.add(eng.metrics.summary()["pages_in_use.window32"])
        assert req.status.value == "finished" and len(req.tokens) == 320
        assert seen == {float(bound)}
        assert eng.metrics.summary()["pages_in_use.window32"] == 0.0
        assert eng.metrics.summary()["pages_in_use.full"] == 0.0
        spans = [s for s in flight_recorder()
                 if s["name"] == "serving.kv.allocate"]
        assert spans[-1]["attrs"]["full_pages"] == full_pages
        assert spans[-1]["attrs"]["window_pages"] == bound
    finally:
        configure_tracing(False)
    groups = eng.debug_pages()["groups"]
    assert [g["group"] for g in groups] == ["full", "window32"]
    assert groups[1]["layers"] == [0, 1, 2, 4, 5, 6]
    assert groups[1]["pages_per_slot"] == bound
    # a short request takes what it needs of a ring, not the whole of it
    short = eng.submit(ids[1, :5], max_new_tokens=3)
    assert len(eng.scheduler.slots[0].alloc.rings[0]) == 3  # 5 + 3 + 16 rows
    eng.run_until_idle()
    assert short.status.value == "finished"


def test_the_allocator_reserves_a_ring_a_window_group():
    """Model-free: a request takes `min(its pages, the ring)` pages of the
    window group's pool at admission; a group's pool that is short keeps
    the request queued with NOTHING taken from any pool; `rollback` and
    `release` give the ring back."""
    import types

    from accelerate_tpu.serving.cache import PagedAllocator
    from accelerate_tpu.serving.scheduler import Request

    alloc = PagedAllocator(page_size=8, num_pages=40, pad_slack=16,
                           prefix_cache=False, rings=((7, 10),))

    def request(prompt_len, new):
        return Request(prompt=np.zeros((prompt_len,), np.int32),
                       max_new_tokens=new)

    assert alloc.pages_needed(100, 20) == 17
    assert alloc.pages_needed(100, 20, group=1) == 7
    assert alloc.pages_needed(5, 3, group=1) == 3
    long = alloc.allocate(request(100, 20))
    assert len(long.pages) == 17 and [len(r) for r in long.rings] == [7]
    short = alloc.allocate(request(5, 3))
    assert len(short.pages) == 3 and [len(r) for r in short.rings] == [3]
    assert alloc.ring_pages_in_use == (10,) and alloc.pages_in_use == 20
    # the full group has 20 pages left, the window group none
    assert alloc.allocate(request(5, 3)) is None
    assert alloc.ring_pages_in_use == (10,) and alloc.pages_in_use == 20
    alloc.rollback(short)
    assert alloc.ring_pages_in_use == (7,) and alloc.pages_in_use == 17
    slot = types.SimpleNamespace(alloc=long, request=request(100, 20),
                                 prompt_done=100, index=0)
    alloc.release(slot, finished=True)
    assert alloc.ring_pages_in_use == (0,) and alloc.pages_in_use == 0
    assert sorted(alloc.ring_pools[0]._free) == list(range(10))


def test_the_sanitizer_joins_both_groups_books(params, ids):
    eng = _engine(params, paged_attention=False, sanitize=False)
    eng.submit(ids[0, :40], max_new_tokens=4)
    eng.step()
    check_engine(eng)
    slot = eng.scheduler.slots[0]
    # a ring page that is also on the group's free list
    eng.allocator.ring_pools[0]._free.append(slot.alloc.rings[0][0])
    with pytest.raises(SanitizerViolation, match="ring page"):
        check_engine(eng)
    eng.allocator.ring_pools[0]._free.pop()
    check_engine(eng)
    # a ring table row that disagrees with the slot's ring
    was = eng._ring_tables[0][0, 1]
    eng._ring_tables[0][0, 1] = eng.cache.groups[1].trash_page
    with pytest.raises(SanitizerViolation, match="ring table row"):
        check_engine(eng)
    eng._ring_tables[0][0, 1] = was
    # a page lost from the window group's pool
    lost = eng.allocator.ring_pools[0]._free.pop()
    with pytest.raises(SanitizerViolation, match="lost or double-counted"):
        check_engine(eng)
    eng.allocator.ring_pools[0]._free.append(lost)
    eng.run_until_idle()
    check_engine(eng)


@pytest.mark.parametrize("option,match", [
    (dict(prefix_cache=True), "a ring has overwritten"),
    (dict(kv_dtype="int8"), "int8 ring"),
    (dict(host_tier_bytes=1 << 20), "one pool's pages"),
    (dict(mesh="two-devices"), "sharded groups"),
    (dict(speculative="draft"), "multi-token window attention"),
])
def test_unported_combinations_raise_at_construction(params, option, match):
    if option.get("mesh"):
        option = dict(mesh=jax.sharding.Mesh(
            np.array(jax.devices()[:2]), ("model",)))
    if option.get("speculative"):
        option = dict(speculative=(mellum, CFG, params))
    option = dict(dict(prefix_cache=False), **option)
    with pytest.raises(ValueError, match=match):
        Engine(mellum, CFG, params, EngineConfig(
            num_slots=2, max_len=64, prefill_chunk=8, **option))


def test_page_shipments_of_a_grouped_cache_raise(params):
    from accelerate_tpu.serving.pod.transfer import PageTransport

    eng = _engine(params)
    with pytest.raises(ValueError, match="one group a layer kind"):
        PageTransport(eng)


@pytest.mark.parametrize("changed,match", [
    (dict(layer_types=["sliding_attention"] * 7 + ["chunked_attention"]),
     "unknown kinds"),
    (dict(layer_types=["full_attention"] * 3), "got 3 entries"),
    (dict(mlp_layer_types=["sparse"] * 7 + ["dense"]), "no dense layer"),
    (dict(rope_parameters={mellum.FULL: {"rope_type": "longrope",
                                         "rope_theta": 1e4}}), "longrope"),
    (dict(norm_topk_prob=False), "norm_topk_prob"),
    (dict(attention_bias=True), "attention_bias"),
])
def test_the_config_refuses_what_is_not_implemented(changed, match):
    with pytest.raises(ValueError, match=match):
        mellum.MellumConfig.tiny(**changed)


def test_the_config_is_hashable_as_published():
    """`layer_types` and `rope_parameters` come as lists and dicts."""
    a = mellum.MellumConfig.tiny(layer_types=list(CFG.layer_types))
    assert a == CFG and hash(a) == hash(CFG)
    assert CFG.rope_of(mellum.FULL)["rope_type"] == "yarn"
    spec = mellum.cache_spec(CFG)
    assert [(s.label, s.num_layers, s.window) for s in spec] == [
        ("full", 2, None), ("window32", 6, WINDOW)]
    with pytest.raises(ValueError, match="no full_attention layer"):
        mellum.cache_spec(mellum.MellumConfig.tiny(
            layer_types=[mellum.SLIDING] * 8))
