"""The DeepSeek-V3 / JoyAI-LLM-Flash family (`models/deepseek.py`) on the
CPU at small sizes, seeded weights: the family against the benchmark's
plain reference (`chipbench/references/joyai_llm_flash.py`: K and V
decompressed, every expert by a masked combine), through every cache form
the engine uses; the dropless expert layer under forced routings; the
latent pool's prefix reuse; the device counters; what raises."""

import importlib.util
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from accelerate_tpu.models import deepseek
from accelerate_tpu.serving import Engine, EngineConfig

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _reference():
    spec = importlib.util.spec_from_file_location(
        "joyai_llm_flash_reference", os.path.join(
            ROOT, "chipbench", "references", "joyai_llm_flash.py"))
    module = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(module)
    return module


REF = _reference()
CFG = deepseek.DeepseekConfig.tiny()
# the reference reads a plain dict with the published keys
REF_CFG = {f.name: getattr(CFG, f.name)
           for f in CFG.__dataclass_fields__.values()}
PAD = 48  # every reference pass runs at this one length (one compile)


@jax.jit
def _ref_logits(params, row):
    """The reference's logits of token ids [PAD]; later positions never
    reach earlier ones (causal), so a padded tail changes nothing."""
    with jax.default_matmul_precision("highest"):
        return REF.logits(REF_CFG, params, row)


def _padded(seq):
    out = np.zeros((PAD,), np.int32)
    out[:len(seq)] = seq
    return jnp.asarray(out)


_forward = jax.jit(lambda params, ids: deepseek.forward(CFG, params, ids))


@pytest.fixture(scope="module")
def params():
    """The benchmark's own initialiser builds the program's tree: the
    correction bias is seeded and not zero."""
    p = REF.make_params(REF_CFG, REF.seed_words(5), jnp.float32)
    assert float(jnp.abs(
        p["layers"][1]["moe"]["router"]["e_score_correction_bias"]).max()) > 0
    return p


@pytest.fixture(scope="module")
def ids():
    return jax.random.randint(jax.random.key(3), (2, 40), 0, CFG.vocab_size)


@pytest.fixture(scope="module")
def ref_logits(params, ids):
    return np.stack([np.asarray(_ref_logits(params, _padded(row)))[:40]
                     for row in np.asarray(ids)])


def test_the_trees_of_program_and_reference_are_one(params):
    mine = jax.eval_shape(lambda: deepseek.init_params(
        CFG, jax.random.key(0), jnp.float32))
    assert jax.tree.structure(mine) == jax.tree.structure(params)
    assert jax.tree.map(lambda a: a.shape, mine) == jax.tree.map(
        lambda a: a.shape, params)
    assert REF.param_count(REF_CFG) == sum(
        int(np.prod(a.shape)) for a in jax.tree.leaves(mine))


def test_full_forward_agrees_with_the_reference(params, ids, ref_logits):
    got = np.asarray(_forward(params, ids))
    assert np.abs(got - ref_logits).max() < 2e-5


def test_chunked_prefill_then_decode_through_a_dense_latent_cache(
        params, ids, ref_logits):
    """Chunks of 16 (decompressed attention over the cached rows) then
    one token at a time (absorbed), on logits."""
    caches = deepseek.init_kv_caches(CFG, 2, 48, jnp.float32)
    assert caches[0].shape == (3, 2, 48, 1, CFG.latent_row_width) \
        and caches[1] is None
    step = jax.jit(lambda p, tokens, caches: deepseek.forward(
        CFG, p, tokens, kv_caches=caches))
    at = 0
    for size in (16, 16, 1, 1, 1, 1, 1, 1, 1, 1):
        logits, caches = step(params, ids[:, at:at + size], caches)
        assert np.abs(np.asarray(logits)
                      - ref_logits[:, at:at + size]).max() < 2e-5, at
        at += size
    assert int(caches[2]) == 40


@pytest.mark.parametrize("queries", [1, 5])
def test_absorbed_and_decompressed_attention_are_one_mathematics(params,
                                                                 queries):
    """The same queries over the same latent rows through both forms: the
    scores through `W_UK` folded into the query, the values through `W_UV`
    after the sum, against K and V expanded from every row."""
    a = params["layers"][1]["attn"]
    k = jax.random.split(jax.random.key(11), 3)
    B, R, H = 2, 40, CFG.num_attention_heads
    q_nope = jax.random.normal(k[0], (B, queries, H, CFG.qk_nope_head_dim))
    q_pe = jax.random.normal(k[1], (B, queries, H, CFG.qk_rope_head_dim))
    view = jax.random.normal(k[2], (B, R, CFG.latent_row_width))
    positions = jnp.asarray([[17], [33]]) + jnp.arange(queries)[None, :]
    absorbed = deepseek._absorbed_attention(CFG, a, q_nope, q_pe, view,
                                            positions)
    expanded = deepseek._decompressed_attention(CFG, a, q_nope, q_pe, view,
                                                positions)
    assert absorbed.shape == (B, queries, H, CFG.v_head_dim)
    assert np.abs(np.asarray(absorbed) - np.asarray(expanded)).max() < 1e-4


def _serve(params, prompts, new_tokens=6, **engine):
    eng = Engine(deepseek, CFG, params, EngineConfig(
        num_slots=3, max_len=64, prefill_chunk=8, page_size=8,
        cache_dtype=jnp.float32, **engine))
    reqs = [eng.submit(np.asarray(p), max_new_tokens=new_tokens,
                       temperature=0.0) for p in prompts]
    eng.run_until_idle()
    return eng, reqs


def _teacher_forced(params, prompt, tokens):
    """The reference's logits at the served positions."""
    seq = np.concatenate([np.asarray(prompt), tokens])
    out = np.asarray(_ref_logits(params, _padded(seq)))
    return out[len(prompt) - 1:len(prompt) - 1 + len(tokens)]


@pytest.mark.parametrize("kernel", [True, False], ids=["kernel", "dense"])
def test_engine_serves_through_the_latent_paged_cache(params, ids, kernel):
    """Chunked prefill through the gathered latent view, then paged decode
    (the Pallas kernel interpreted, and the dense gather), against the
    reference's full pass: every served token is the reference's first
    choice by its own logits, and the engine's log-probability of it is
    the reference's."""
    prompts = [ids[0, :13], ids[1, :29], ids[0, 5:24]]
    eng, reqs = _serve(params, prompts, paged_attention=kernel)
    assert eng.cache.latent and eng.cache.v is None
    assert eng.cache.k.shape[2:] == (1, 8, CFG.latent_row_width)
    assert eng._use_paged_kernel is kernel
    for prompt, req in zip(prompts, reqs):
        ref = _teacher_forced(params, prompt, np.asarray(req.tokens))
        lp = ref - jax.nn.logsumexp(ref, axis=-1, keepdims=True)
        took = np.asarray(lp)[np.arange(len(req.tokens)), req.tokens]
        assert np.abs(ref.max(-1) - ref[np.arange(len(req.tokens)),
                                        req.tokens]).max() < 1e-4
        assert np.abs(took - np.asarray(req.logprobs)).max() < 1e-4
    assert eng.compile_stats() == {"admit": 1, "prefill": 1, "decode": 1}


def test_a_chunk_attends_every_layer_in_one_chunk_kernel(params, ids):
    """The cache-free forward and a chunk over a dense view are one
    `latent_chunk_attention` call a layer (a single token over a view
    stays absorbed), interpreted here."""
    from accelerate_tpu.ops import kernel_mode

    caches = deepseek.init_kv_caches(CFG, 2, 64, jnp.float32)
    for kv_caches, tokens, calls in ((None, 16, CFG.num_hidden_layers),
                                     (caches, 16, CFG.num_hidden_layers),
                                     (caches, 1, 0)):
        text = str(jax.make_jaxpr(lambda p, i: deepseek.forward(
            CFG, p, i, kv_caches=kv_caches))(params, ids[:, :tokens]))
        assert text.count("name=latent_chunk_attention") == calls
    assert kernel_mode.kernel_report()["latent_chunk_attention"] == "interpret"


def test_prefix_cache_reuses_latent_pages(params, ids):
    """A second request over the same 32-token document maps its pages
    instead of prefilling them, and serves what a cold engine serves."""
    doc = np.asarray(ids[0, :32])
    first = np.concatenate([doc, np.asarray(ids[1, :5])])
    second = np.concatenate([doc, np.asarray(ids[1, 7:13])])
    eng, (a,) = _serve(params, [first], paged_attention=False)
    b = eng.submit(second, max_new_tokens=6, temperature=0.0)
    eng.run_until_idle()
    assert eng.metrics.prefix_tokens_reused == 32
    cold, (c,) = _serve(params, [second], paged_attention=False,
                        prefix_cache=False)
    assert cold.metrics.prefix_tokens_reused == 0
    assert b.tokens == c.tokens
    assert np.abs(np.asarray(b.logprobs) - np.asarray(c.logprobs)).max() < 1e-5


def _moe_params(params, **router):
    m = dict(params["layers"][1]["moe"])
    m["router"] = dict(m["router"], **router)
    return m


@pytest.mark.parametrize("case", ["pile-on-two", "bias-moves-the-choice",
                                  "seeded"])
def test_expert_layer_against_the_masked_combine(params, case):
    """`pile-on-two`: a zero router and a bias that sends EVERY token to
    experts 2 and 5 (capacity dispatch would drop most of them; nothing is
    dropped here). `bias-moves-the-choice`: a bias large enough to change
    which experts are chosen, and the weights are still the scores
    without it."""
    E = CFG.n_routed_experts
    x = jax.random.normal(jax.random.key(9), (2, 12, CFG.hidden_size))
    flat = x.reshape(24, -1)
    if case == "pile-on-two":
        m = _moe_params(
            params, kernel=jnp.zeros((CFG.hidden_size, E)),
            e_score_correction_bias=jnp.zeros((E,)).at[
                jnp.array([2, 5])].set(4.0))
    elif case == "bias-moves-the-choice":
        m = _moe_params(params, e_score_correction_bias=jnp.zeros((E,)).at[
            jnp.array([0, 7])].set(0.6))
    else:
        m = _moe_params(params)
    with jax.default_matmul_precision("highest"):
        want = np.asarray(REF.moe(REF_CFG, m, flat))
        experts, weights = REF.route(REF_CFG, m, flat)
        free, _ = REF.route(REF_CFG, _moe_params(
            params, kernel=m["router"]["kernel"],
            e_score_correction_bias=jnp.zeros((E,))), flat)
    got, counts = deepseek.moe_layer(CFG, m, x)
    assert np.abs(np.asarray(got).reshape(24, -1) - want).max() < 2e-5
    assert np.array_equal(np.asarray(counts),
                          np.bincount(np.asarray(experts).ravel(),
                                      minlength=E))
    if case == "pile-on-two":
        assert counts[2] == counts[5] == 24 and int(counts.sum()) == 48
        assert np.allclose(np.asarray(weights),
                           CFG.routed_scaling_factor / 2)
    if case == "bias-moves-the-choice":
        # the choice moved, and the weights carry no bias: they are the
        # plain scores at the chosen experts, normalised and scaled
        assert not np.array_equal(np.sort(np.asarray(experts)),
                                  np.sort(np.asarray(free)))
        scores = np.asarray(jax.nn.sigmoid(flat @ m["router"]["kernel"]))
        picked = np.take_along_axis(scores, np.asarray(experts), axis=-1)
        assert np.allclose(
            np.asarray(weights), CFG.routed_scaling_factor * picked
            / picked.sum(-1, keepdims=True), atol=1e-6)


def test_a_token_mask_keeps_padding_out_of_the_counts(params):
    x = jax.random.normal(jax.random.key(2), (1, 8, CFG.hidden_size))
    m = params["layers"][2]["moe"]
    mask = jnp.arange(8)[None, :] < 5
    _, all_counts = deepseek.moe_layer(CFG, m, x)
    _, real = deepseek.moe_layer(CFG, m, x, mask)
    _, head = deepseek.moe_layer(CFG, m, x[:, :5])
    assert int(all_counts.sum()) == 16 and int(real.sum()) == 10
    assert np.array_equal(np.asarray(real), np.asarray(head))


@pytest.mark.parametrize("kernel", [True, False], ids=["kernel", "dense"])
def test_device_counters_against_a_numpy_count(params, ids, kernel):
    """Assignments per expert per layer, distinct experts a call and calls,
    accumulated in the two programs: against the engine's own step counts
    and a NumPy count of the reference's routing over what was served."""
    prompts = [ids[0, :13], ids[1, :21]]
    eng, reqs = _serve(params, prompts, paged_attention=kernel)
    got = eng.device_counters()
    assert set(got) == {"prefill", "decode"}
    k, layers = CFG.num_experts_per_tok, 2
    pre, dec = got["prefill"], got["decode"]
    assert pre["assignments"].shape == (layers, CFG.n_routed_experts)
    assert int(pre["calls"]) == eng.metrics.prefill_chunks == 2 + 3
    assert int(dec["calls"]) == eng.metrics.decode_steps
    # every prompt token once a layer (padding and dead lanes left out);
    # a request's last served token is never fed back
    assert pre["assignments"].sum(-1).tolist() == [(13 + 21) * k] * layers
    assert dec["assignments"].sum(-1).tolist() == [2 * 5 * k] * layers
    assert (pre["distinct_experts"] <= np.minimum(
        pre["assignments"].sum(-1), int(pre["calls"]) * 8)).all()
    assert (dec["distinct_experts"] >= int(dec["calls"]) * k).all()
    # the first expert layer sees the same inputs in program and
    # reference: its per-expert counts are the reference's routing
    @jax.jit
    def first_expert_layer_choice(seq):
        with jax.default_matmul_precision("highest"):
            x = REF._f32(params["embed_tokens"]["embedding"][seq])
            lay = params["layers"][0]
            x = x + REF._attention(REF_CFG, lay["attn"], REF._rms_norm(
                x, lay["input_layernorm"]["scale"], 1e-6))
            x = x + REF._swiglu(
                REF._rms_norm(x, lay["post_attention_layernorm"]["scale"],
                              1e-6), *(lay["mlp"][n]["kernel"] for n in (
                                  "gate_proj", "up_proj", "down_proj")))
            lay = params["layers"][1]
            x = x + REF._attention(REF_CFG, lay["attn"], REF._rms_norm(
                x, lay["input_layernorm"]["scale"], 1e-6))
            return REF.route(REF_CFG, lay["moe"], REF._rms_norm(
                x, lay["post_attention_layernorm"]["scale"], 1e-6))[0]

    want = np.zeros(CFG.n_routed_experts, np.int64)
    for prompt, req in zip(prompts, reqs):
        fed = np.concatenate([np.asarray(prompt), req.tokens[:-1]])
        experts = np.asarray(first_expert_layer_choice(_padded(fed)))
        want += np.bincount(experts[:len(fed)].ravel(),
                            minlength=CFG.n_routed_experts)
    assert np.array_equal(pre["assignments"][0] + dec["assignments"][0], want)
    # a family without counters has none
    from accelerate_tpu.models import llama

    lcfg = llama.LlamaConfig.tiny()
    plain = Engine(llama, lcfg, llama.init_params(lcfg, jax.random.key(0)),
                   EngineConfig(num_slots=2, max_len=32, prefill_chunk=8))
    assert plain.device_counters() == {} and plain.cache.stats is None


@pytest.mark.parametrize("option,match", [
    (dict(kv_dtype="int8"), "int8 latent pages"),
    (dict(host_tier_bytes=1 << 20), "host tier"),
    (dict(mesh="two-devices"), "sharded latent pool"),
    (dict(speculative="draft"), "multi-token latent attention"),
])
def test_unported_combinations_raise_at_construction(params, option, match):
    if option.get("mesh"):
        option = dict(mesh=jax.sharding.Mesh(
            np.array(jax.devices()[:2]), ("model",)))
    if option.get("speculative"):
        option = dict(speculative=(deepseek, CFG, params))
    with pytest.raises(ValueError, match=match):
        Engine(deepseek, CFG, params, EngineConfig(
            num_slots=2, max_len=32, prefill_chunk=8, **option))


def test_page_shipments_of_a_latent_pool_raise(params):
    from accelerate_tpu.serving.pod.transfer import PageTransport

    eng = Engine(deepseek, CFG, params, EngineConfig(
        num_slots=2, max_len=32, prefill_chunk=8))
    with pytest.raises(ValueError, match="latent pool"):
        PageTransport(eng)


@pytest.mark.parametrize("bad", [dict(scoring_func="softmax"),
                                 dict(n_group=8, topk_group=4),
                                 dict(rope_interleave=False)])
def test_config_refuses_what_is_not_implemented(bad):
    with pytest.raises(ValueError):
        deepseek.DeepseekConfig.tiny(**bad)


def test_generate_through_the_dense_latent_cache(params, ids):
    """`generate` (prefill + fused decode scan) is greedy-exact against
    the cache-free forward."""
    out = np.asarray(deepseek.generate(CFG, params, ids[:1, :10],
                                       max_new_tokens=4))
    assert out.shape == (1, 14) and np.array_equal(out[:, :10], ids[:1, :10])
    best = np.asarray(jnp.argmax(_forward(params, jnp.asarray(out)), -1))
    assert np.array_equal(out[0, 10:], best[0, 9:13])
