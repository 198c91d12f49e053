"""`models/brumby.py` through `serving.Engine` on the CPU, float32, seeded
weights: a family that keeps ONE recurrent state a sequence and no K/V
rows (`CacheSpec.kind == "state"`, `serving/cache.py` `StateCache`). Chunks
then decode through the engine against one full forward, with a padded last
chunk; requests of different lengths in one batch; a reused slot serves a
cold request's numbers (its entry is zeroed at admission); a lane that
finishes on an EOS the host could not count ahead rides its dead step
without touching a live state; the pool's books answer in entries; the
counters; what raises. `dense` is the plain `jax.numpy` forms, `kernel`
the two Pallas kernels interpreted."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from accelerate_tpu.models import brumby
from accelerate_tpu.models.common import wide_count
from accelerate_tpu.serving import Engine, EngineConfig
from accelerate_tpu.serving.cache import CacheSpec, StateCache
from accelerate_tpu.telemetry.trace import configure_tracing, flight_recorder

# 16-lane heads keep both forms quick on the CPU (9 rows of `phi` where 128
# lanes have 65); one test serves through both kernels at the chip's 128
CFG = brumby.BrumbyConfig.tiny(head_dim=16)
CFG128 = brumby.BrumbyConfig.tiny()
CHUNK = 8


@pytest.fixture(scope="module")
def params():
    return brumby.init_params(CFG, jax.random.key(0))


def _engine(params, kernel=False, slots=3, entries=None, cfg=CFG, **kw):
    kw.setdefault("sanitize", True)
    return Engine(brumby, cfg, params, EngineConfig(
        num_slots=slots, max_len=64, prefill_chunk=CHUNK,
        num_pages=entries, cache_dtype=jnp.float32, prefix_cache=False,
        paged_attention=kernel, **kw))


def _prompts(*lengths, seed=0):
    rng = np.random.default_rng(seed)
    return [rng.integers(0, CFG.vocab_size, (n,)).astype(np.int32)
            for n in lengths]


def _full_forward(params, prompt, tokens, cfg=CFG):
    """(the tokens one full forward puts first after the prompt and after
    each served token, the served tokens' log-probabilities under it)."""
    ids = jnp.asarray(np.concatenate([prompt, np.asarray(tokens, np.int32)]))
    logits = brumby.forward(cfg, params, ids[None])[0, len(prompt) - 1:-1]
    lp = jax.nn.log_softmax(logits)
    return (np.asarray(jnp.argmax(logits, -1)), np.asarray(
        jnp.take_along_axis(lp, jnp.asarray(tokens)[:, None], 1))[:, 0])


@pytest.mark.parametrize("kernel", [False, True], ids=["dense", "kernel"])
def test_chunks_then_decode_agree_with_one_full_forward(params, kernel):
    """Prompts of 13, 5, 21 and 9 tokens (chunk 8: every last chunk is
    padded) over 3 slots, so the fourth waits for a slot and an entry:
    every served token is the full forward's and its log-probability is."""
    eng = _engine(params, kernel)
    prompts = _prompts(13, 5, 21, 9)
    reqs = [eng.submit(p, max_new_tokens=6, temperature=0.0) for p in prompts]
    eng.run_until_idle()
    for p, r in zip(prompts, reqs):
        assert r.status.value == "finished" and len(r.tokens) == 6
        first, lps = _full_forward(params, p, r.tokens)
        assert list(r.tokens) == list(first)
        np.testing.assert_allclose(r.logprobs, lps, atol=2e-5)
    assert eng.compile_stats() == {"admit": 1, "prefill": 1, "decode": 1}
    assert eng.allocator.pages_in_use == 0


def test_both_kernels_serve_at_the_chips_128_lanes():
    """One request of two chunks (the second padded) and three decode
    steps through both kernels at 128-lane heads."""
    params = brumby.init_params(CFG128, jax.random.key(0))
    eng = _engine(params, kernel=True, slots=1, cfg=CFG128)
    prompt, = _prompts(11, seed=7)
    req = eng.submit(prompt, max_new_tokens=4, temperature=0.0)
    eng.run_until_idle()
    first, lps = _full_forward(params, prompt, req.tokens, CFG128)
    assert list(req.tokens) == list(first)
    np.testing.assert_allclose(req.logprobs, lps, atol=2e-5)


def test_the_programs_forward_agrees_with_the_quadratic_form(params):
    """The family forward (chunk form from a zero state) against the same
    layer written with `retention_quadratic`."""
    from accelerate_tpu.models.common import dense, rms_norm
    from accelerate_tpu.ops.power_retention import retention_quadratic

    ids = jnp.asarray(_prompts(19)[0])
    got = brumby.forward(CFG, params, ids[None])[0]
    c = CFG
    x = params["embed_tokens"]["embedding"][ids][None]
    pos = jnp.arange(19)[None]
    for layer in params["layers"]:
        a = layer["attn"]
        y = rms_norm(x, layer["input_layernorm"]["scale"], c.rms_norm_eps)
        q = brumby._rotate(rms_norm(dense(y, a["q_proj"]["kernel"]).reshape(
            1, 19, 4, c.head_dim), a["q_norm"]["scale"], c.rms_norm_eps), pos,
            c.rope_theta)
        k = brumby._rotate(rms_norm(dense(y, a["k_proj"]["kernel"]).reshape(
            1, 19, 2, c.head_dim), a["k_norm"]["scale"], c.rms_norm_eps), pos,
            c.rope_theta)
        v = dense(y, a["v_proj"]["kernel"]).reshape(19, 2, c.head_dim)
        gamma = jax.nn.log_sigmoid(y[0] @ a["gate_proj"]["kernel"]
                                   + a["gate_proj"]["bias"])
        o = retention_quadratic(q[0].reshape(19, 2, 2, c.head_dim), k[0], v, gamma)
        x = x + dense(o.reshape(1, 19, 4 * c.head_dim), a["o_proj"]["kernel"])
        y = rms_norm(x, layer["post_attention_layernorm"]["scale"],
                     c.rms_norm_eps)
        m = layer["mlp"]
        x = x + dense(jax.nn.silu(dense(y, m["gate_proj"]["kernel"]))
                      * dense(y, m["up_proj"]["kernel"]),
                      m["down_proj"]["kernel"])
    want = dense(rms_norm(x, params["norm"]["scale"], c.rms_norm_eps),
                 params["lm_head"]["kernel"])[0]
    np.testing.assert_allclose(got, want, atol=2e-4, rtol=2e-4)


@pytest.mark.parametrize("kernel", [False, True], ids=["dense", "kernel"])
def test_a_reused_slot_serves_a_cold_requests_logits(params, kernel):
    """ONE slot and ONE entry: the second request takes the entry the first
    left full. Its tokens and log-probabilities are those of an engine that
    never saw the first (the entry is zeroed at admission)."""
    first, second = _prompts(17, 11, seed=1)
    eng = _engine(params, kernel, slots=1, entries=1)
    a = eng.submit(first, max_new_tokens=5, temperature=0.0)
    eng.run_until_idle()
    assert float(jnp.abs(eng.cache.s[:, 0]).max()) > 0  # left as it was
    b = eng.submit(second, max_new_tokens=5, temperature=0.0)
    assert eng.scheduler.slots[0].alloc.pages == [0]
    eng.run_until_idle()
    cold = _engine(params, kernel, slots=1, entries=1)
    c = cold.submit(second, max_new_tokens=5, temperature=0.0)
    cold.run_until_idle()
    assert a.status.value == b.status.value == "finished"
    assert list(b.tokens) == list(c.tokens)
    np.testing.assert_array_equal(b.logprobs, c.logprobs)
    counters = eng.device_counters()
    assert int(counters["prefill"]["states_zeroed"]) == 2
    assert int(counters["decode"]["states_zeroed"]) == 0


@pytest.mark.parametrize("kernel", [False, True], ids=["dense", "kernel"])
def test_a_lane_that_finished_rides_its_dead_step_past_a_live_state(
        params, kernel):
    """The short request ends on an EOS, which the host cannot count ahead:
    the step after it was already dispatched with the lane live, and the
    steps after that carry the lane dead. The long request beside it is
    served what it is served alone, and a third request then admitted into
    the freed entry is served cold."""
    short, long_, third = _prompts(9, 14, 7, seed=2)
    alone = _engine(params, kernel)
    probe = alone.submit(short, max_new_tokens=8, temperature=0.0)
    want_long = alone.submit(long_, max_new_tokens=12, temperature=0.0)
    want_third = alone.submit(third, max_new_tokens=4, temperature=0.0)
    alone.run_until_idle()
    eos = probe.tokens[2]
    stops_at = list(probe.tokens).index(eos) + 1
    eng = _engine(params, kernel, slots=2, entries=2)
    a = eng.submit(short, max_new_tokens=8, temperature=0.0,
                   eos_token_id=int(eos))
    b = eng.submit(long_, max_new_tokens=12, temperature=0.0)
    c = eng.submit(third, max_new_tokens=4, temperature=0.0)
    eng.run_until_idle()
    assert list(a.tokens) == list(probe.tokens[:stops_at])
    assert list(b.tokens) == list(want_long.tokens)
    np.testing.assert_allclose(b.logprobs, want_long.logprobs, atol=1e-6)
    assert list(c.tokens) == list(want_third.tokens)
    np.testing.assert_allclose(c.logprobs, want_third.logprobs, atol=1e-6)


def test_two_requests_of_different_lengths_share_a_batch(params):
    """A lane mid-prefill is not live in the decode steps between its
    chunks: its state is what its own chunks made of it."""
    p_short, p_long = _prompts(4, 37, seed=3)
    eng = _engine(params, slots=2)
    a = eng.submit(p_short, max_new_tokens=16, temperature=0.0)
    b = eng.submit(p_long, max_new_tokens=3, temperature=0.0)
    eng.run_until_idle()
    for p, r in ((p_short, a), (p_long, b)):
        first, lps = _full_forward(params, p, r.tokens)
        assert list(r.tokens) == list(first)
        np.testing.assert_allclose(r.logprobs, lps, atol=2e-5)
    # decode steps ran between the long prompt's five chunks
    assert eng.metrics.decode_steps >= 15 and eng.metrics.prefill_chunks == 6


def test_the_pools_books_are_entries(params):
    """An entry is the pool's page: one a request, bounded by free entries,
    bytes = a sequence's state in every layer; the spare is no page."""
    eng = _engine(params, slots=3, entries=2)
    cache = eng.cache
    assert isinstance(cache, StateCache)
    assert cache.s.shape == (2, 3, 2, 9 * 16, 16)
    assert cache.z.shape == (2, 3, 2, 16, 16)
    assert (cache.num_pages, cache.trash_page, cache.pages_per_slot) == (
        2, 2, 1)
    assert cache.page_size == cache.rows == 64 + CHUNK
    assert cache.page_nbytes == 2 * 2 * (9 * 16 + 16) * 16 * 4
    assert cache.nbytes() == 3 * cache.page_nbytes
    assert eng.allocator.pages_needed(50, 14) == 1
    configure_tracing(True)
    try:
        reqs = [eng.submit(p, max_new_tokens=4, temperature=0.0)
                for p in _prompts(6, 7, 8, seed=4)]
        eng.step()
        # three slots, two entries: the third request waits for an ENTRY
        assert eng.allocator.pages_in_use == 2
        assert eng.scheduler.queue_depth == 1
        assert sorted(int(x) for x in eng._table[:, 0]) == [0, 1, 2]
        summary = eng.metrics_summary()
        assert summary["state_bytes_in_use"] == 2 * cache.page_nbytes
        assert summary["kv_bytes_in_use"] == summary["state_bytes_in_use"]
        eng.run_until_idle()
        spans = [s for s in flight_recorder()
                 if s["name"] == "serving.kv.allocate"]
        assert spans[-1]["attrs"]["state_entries"] == 1
        assert spans[-1]["attrs"]["pages"] == 1
    finally:
        configure_tracing(False)
    assert all(r.status.value == "finished" for r in reqs)
    assert eng.metrics_summary()["state_bytes_in_use"] == 0.0
    assert (eng._table == cache.trash_page).all()
    assert eng.allocator.index.cached_pages == 0   # nothing is ever published


def test_the_device_counters_count_tokens_folded_and_states_zeroed(params):
    eng = _engine(params)
    for p in _prompts(13, 5, seed=5):
        eng.submit(p, max_new_tokens=4, temperature=0.0)
    eng.run_until_idle()
    got = eng.device_counters()
    layers = CFG.num_hidden_layers
    # every prompt token is folded by a chunk; of a request's 4 tokens the
    # first comes from the last chunk and the last is never fed back
    assert wide_count(got["prefill"]["tokens_folded"]) == 18 * layers
    assert wide_count(got["decode"]["tokens_folded"]) == 2 * 3 * layers
    assert int(got["prefill"]["states_zeroed"]) == 2


def test_generate_runs_over_states(params):
    prompt = jnp.asarray(np.stack(_prompts(6, 6, seed=6)))
    out = brumby.generate(CFG, params, prompt, max_new_tokens=4)
    assert out.shape == (2, 10)
    for row in np.asarray(out):
        first, _ = _full_forward(params, row[:6], row[6:])
        assert list(row[6:]) == list(first)


# ---------------------------------------------------------------------------
# what raises
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("option,match", [
    (dict(prefix_cache=True), "a snapshot published at a boundary"),
    (dict(kv_dtype="int8"), "int8 codes of a state"),
    (dict(host_tier_bytes=1 << 20), "a snapshot of an entry"),
    (dict(mesh="two-devices"), "sharded over KV heads"),
    (dict(speculative="draft"), "cannot be cut off a state"),
])
def test_unported_options_raise_at_construction(params, option, match):
    if option.get("mesh"):
        option = dict(mesh=jax.sharding.Mesh(
            np.array(jax.devices()[:2]), ("model",)))
    if option.get("speculative"):
        option = dict(speculative=(brumby, CFG, params))
    option = dict(dict(prefix_cache=False), **option)
    with pytest.raises(ValueError, match=match) as err:
        Engine(brumby, CFG, params, EngineConfig(
            num_slots=2, max_len=64, prefill_chunk=8, **option))
    assert "CacheSpec.kind='state'" in str(err.value)
    assert "Nothing falls back to K/V rows" in str(err.value)


def test_the_default_engine_config_raises_for_its_prefix_cache(params):
    with pytest.raises(ValueError, match="prefix_cache=True"):
        Engine(brumby, CFG, params, EngineConfig(num_slots=2, max_len=64))


def test_a_fork_raises(params):
    eng = _engine(params)
    parent = eng.submit(_prompts(9)[0], max_new_tokens=2, temperature=0.0)
    with pytest.raises(ValueError, match="a snapshot of the parent's state"):
        eng.fork(parent)
    eng.run_until_idle()
    assert parent.status.value == "finished"


def test_page_shipments_of_a_state_pool_raise(params):
    from accelerate_tpu.serving.pod.transfer import PageTransport

    with pytest.raises(ValueError, match="a snapshot of an entry"):
        PageTransport(_engine(params))


@pytest.mark.parametrize("changed,match", [
    (dict(retention_degree=3), "degree 1 or 2"),
    (dict(head_dim=7), "even number of lanes"),
    (dict(tie_word_embeddings=True), "tie_word_embeddings=False"),
    (dict(attention_bias=True), "attention_bias=False"),
    (dict(num_key_value_heads=3), "multiple of KV heads"),
])
def test_the_config_refuses_what_is_not_implemented(changed, match):
    with pytest.raises(ValueError, match=match):
        brumby.BrumbyConfig.tiny(**changed)


def test_a_state_pool_takes_the_familys_rows():
    """The pool asks nothing of what the rows mean (a state's shape is the
    family's): rows that are no whole number of `width` are laid out as
    declared; no entry at all is refused."""
    pool = StateCache.create(
        CacheSpec(2, 2, 128, kind="state", state_rows=100), 2, 64)
    assert pool.s.shape == (2, 3, 2, 100, 128) and pool.z is None
    with pytest.raises(ValueError, match="0 entries"):
        StateCache.create(CacheSpec(2, 2, 128, kind="state", state_rows=128),
                          2, 64, num_entries=0)


def test_brumby_declares_the_pool_it_had_and_compiles_to_the_programs_it_had(
        params):
    """Since the state pool's shape is the family's (`CacheSpec.aux_rows`),
    brumby DECLARES the normaliser's rows the pool used to reckon for it.
    A pool made by hand the old way (`state_rows / width` rows, rounded up
    to whole 8-row tiles) has the declared pool's shapes and pytree, and
    the engine's three programs lower to the same text over either."""
    from accelerate_tpu.ops import power_retention as pr

    eng = _engine(params, slots=2, entries=2)
    spec = brumby.cache_spec(CFG)
    assert (spec.kind, spec.state_rows, spec.aux_rows, spec.aux_entry_minor
            ) == ("state", 9 * 16, 16, False)
    z_rows = -(-spec.state_rows // spec.width // 8) * 8
    lead = (spec.num_layers, 3, spec.heads)
    by_hand = StateCache(
        s=jnp.zeros(lead + (spec.state_rows, spec.width), jnp.float32),
        z=jnp.zeros(lead + (z_rows, spec.width), jnp.float32),
        lengths=jnp.zeros((2,), jnp.int32), max_len=64, pad_slack=CHUNK,
        compute_dtype=jnp.float32, stats=eng.cache.stats)
    assert jax.tree.structure(by_hand) == jax.tree.structure(eng.cache)
    assert [x.shape for x in jax.tree.leaves(by_hand)] == [
        x.shape for x in jax.tree.leaves(eng.cache)]
    assert isinstance(eng.cache.pool(), pr.StatePool)
    regs = (eng._tokens, eng._slot_keys, eng._temps)
    for program, args in (
            (eng._admit_p, lambda c: (c, *regs[1:], jnp.int32(0),
                                      eng._slot_keys[0], jnp.float32(0.0),
                                      jnp.int32(1))),
            (eng._prefill_p, lambda c: (eng.params, c, *regs, jnp.int32(0),
                                        eng._tables(0),
                                        np.zeros((CHUNK,), np.int32),
                                        jnp.int32(CHUNK))),
            (eng._decode_p, lambda c: (eng.params, c, *regs,
                                       np.ones((2,), bool), eng._tables()))):
        assert (program.lower(*args(eng.cache)).as_text()
                == program.lower(*args(by_hand)).as_text())
