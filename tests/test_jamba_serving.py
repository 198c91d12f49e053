"""`models/jamba.py` through `serving.Engine` on the CPU, float32, seeded
weights: a family whose Mamba layers keep one recurrent state and a
convolution window a sequence while its attention layers keep K/V rows (a
group of state ENTRIES beside a group of PAGES, `GroupedPagedCache.state`).
Chunks then decode through the engine against one full forward, with a
padded last chunk and a prompt shorter than the convolution window;
requests of different lengths in one batch; a reused slot serves a cold
request's numbers (entry and window zeroed at admission); a dead lane's
step changes no live entry; the page group's books; the counters; what
raises. `dense` is the plain `jax.numpy` forms, `kernel` both
selective-scan kernels and the live-pages attention kernel interpreted."""

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from accelerate_tpu.models import jamba
from accelerate_tpu.models.common import wide_count
from accelerate_tpu.serving import Engine, EngineConfig
from accelerate_tpu.serving.cache import GroupedPagedCache, StateCache
from accelerate_tpu.telemetry.trace import configure_tracing, flight_recorder

CFG = jamba.JambaConfig.tiny()      # layers 0, 2 scan; layers 1, 3 attend
CHUNK = 8


@pytest.fixture(scope="module")
def params():
    return jamba.init_params(CFG, jax.random.key(0))


def _engine(params, kernel=False, slots=3, pages=None, cfg=CFG, **kw):
    kw.setdefault("sanitize", True)
    return Engine(jamba, cfg, params, EngineConfig(
        num_slots=slots, max_len=64, prefill_chunk=CHUNK, page_size=16,
        num_pages=pages, cache_dtype=jnp.float32, prefix_cache=False,
        paged_attention=kernel, **kw))


def _prompts(*lengths, seed=0):
    rng = np.random.default_rng(seed)
    return [rng.integers(0, CFG.vocab_size, (n,)).astype(np.int32)
            for n in lengths]


def _full_forward(params, prompt, tokens, cfg=CFG):
    """(the tokens one full forward puts first after the prompt and after
    each served token, the served tokens' log-probabilities under it)."""
    ids = jnp.asarray(np.concatenate([prompt, np.asarray(tokens, np.int32)]))
    logits = jamba.forward(cfg, params, ids[None])[0, len(prompt) - 1:-1]
    lp = jax.nn.log_softmax(logits)
    return (np.asarray(jnp.argmax(logits, -1)), np.asarray(
        jnp.take_along_axis(lp, jnp.asarray(tokens)[:, None], 1))[:, 0])


def _assert_served_as_one_forward(params, prompt, req, atol=3e-5):
    first, lps = _full_forward(params, prompt, req.tokens)
    assert list(req.tokens) == list(first)
    np.testing.assert_allclose(req.logprobs, lps, atol=atol)


@pytest.mark.parametrize("kernel", [False, True], ids=["dense", "kernel"])
def test_chunks_then_decode_agree_with_one_full_forward(params, kernel):
    """Prompts of 13, 2, 21 and 9 tokens (chunk 8: every last chunk is
    padded; 2 tokens are fewer than the convolution's window) over 3 slots,
    so the fourth waits for a slot: every served token is the full
    forward's and its log-probability is."""
    eng = _engine(params, kernel)
    prompts = _prompts(13, 2, 21, 9)
    reqs = [eng.submit(p, max_new_tokens=5, temperature=0.0) for p in prompts]
    eng.run_until_idle()
    for p, r in zip(prompts, reqs):
        assert r.status.value == "finished" and len(r.tokens) == 5
        _assert_served_as_one_forward(params, p, r)
    assert eng.compile_stats() == {"admit": 1, "prefill": 1, "decode": 1}
    assert eng.allocator.pages_in_use == 0


def test_the_programs_forward_is_the_layers_written_out(params):
    """The family forward (no cache) against the same stack written here
    with the recurrence position by position (`scan_reference`) and a
    `[positions, positions]` softmax."""
    from accelerate_tpu.models.common import dense, rms_norm
    from accelerate_tpu.ops.selective_scan import scan_reference

    T = 19
    ids = jnp.asarray(_prompts(T)[0])
    got = jamba.forward(CFG, params, ids[None])[0]
    c, eps = CFG, CFG.rms_norm_eps
    d, n, r = c.d_inner, c.mamba_d_state, c.mamba_dt_rank
    x = params["embed_tokens"]["embedding"][ids]
    for i, layer in enumerate(params["layers"]):
        y = rms_norm(x, layer["input_layernorm"]["scale"], eps)
        if i in c.attention_layers:
            a = layer["attn"]
            q = (y @ a["q_proj"]["kernel"]).reshape(T, 2, 128)
            k, v = y @ a["k_proj"]["kernel"], y @ a["v_proj"]["kernel"]
            s = jnp.einsum("qhd,td->hqt", q, k) / np.sqrt(128)
            s = jnp.where(np.tril(np.ones((T, T), bool))[None], s, -jnp.inf)
            mixed = jnp.einsum("hqt,td->qhd", jax.nn.softmax(s, -1),
                               v).reshape(T, 256) @ a["o_proj"]["kernel"]
        else:
            m = layer["mamba"]
            uz = y @ m["in_proj"]["kernel"]
            u, z = uz[:, :d], uz[:, d:]
            padded = jnp.concatenate([jnp.zeros((3, d)), u])
            act = jax.nn.silu(m["conv"]["bias"] + sum(
                m["conv"]["kernel"][j] * padded[j:j + T] for j in range(4)))
            proj = act @ m["x_proj"]["kernel"]
            rt = rms_norm(proj[:, :r], m["dt_norm"]["scale"], eps)
            Bm = rms_norm(proj[:, r:r + n], m["b_norm"]["scale"], eps)
            Cm = rms_norm(proj[:, r + n:], m["c_norm"]["scale"], eps)
            dt = jax.nn.softplus(rt @ m["dt_proj"]["kernel"]
                                 + m["dt_proj"]["bias"])
            out, _ = scan_reference(dt, act, Bm, Cm, -jnp.exp(m["A_log"]),
                                    jnp.zeros((n, d)))
            mixed = ((out + m["D"] * act) * jax.nn.silu(z)
                     ) @ m["out_proj"]["kernel"]
        x = x + mixed
        y = rms_norm(x, layer["pre_ff_layernorm"]["scale"], eps)
        f = layer["mlp"]
        x = x + dense(jax.nn.silu(dense(y, f["gate_proj"]["kernel"]))
                      * dense(y, f["up_proj"]["kernel"]),
                      f["down_proj"]["kernel"])
    want = rms_norm(x, params["norm"]["scale"], eps) @ params[
        "embed_tokens"]["embedding"].T
    np.testing.assert_allclose(got, want, atol=2e-4, rtol=2e-4)


@pytest.mark.parametrize("kernel", [False, True], ids=["dense", "kernel"])
def test_a_reused_slot_serves_a_cold_requests_logits(params, kernel):
    """ONE slot: the second request takes the entry the first left full,
    state and window. Its tokens and log-probabilities are those of an
    engine that never saw the first (both are zeroed at admission)."""
    first, second = _prompts(17, 11, seed=1)
    eng = _engine(params, kernel, slots=1)
    a = eng.submit(first, max_new_tokens=5, temperature=0.0)
    eng.run_until_idle()
    state = eng.cache.state
    assert float(jnp.abs(state.s[:, 0]).max()) > 0      # left as it was
    assert float(jnp.abs(state.z[:, :, 0]).max()) > 0
    b = eng.submit(second, max_new_tokens=5, temperature=0.0)
    eng.run_until_idle()
    cold = _engine(params, kernel, slots=1)
    c = cold.submit(second, max_new_tokens=5, temperature=0.0)
    cold.run_until_idle()
    assert a.status.value == b.status.value == "finished"
    assert list(b.tokens) == list(c.tokens)
    np.testing.assert_array_equal(b.logprobs, c.logprobs)
    counters = eng.device_counters()
    assert int(counters["prefill"]["states_zeroed"]) == 2
    assert int(counters["decode"]["states_zeroed"]) == 0


@pytest.mark.parametrize("kernel", [False, True], ids=["dense", "kernel"])
def test_a_dead_lanes_step_changes_no_live_entry(params, kernel):
    """The short request ends on an EOS, which the host cannot count ahead:
    the step after it was already dispatched with the lane live, and the
    steps after that carry the lane dead, its write on the spare entry and
    the trash page. The long request beside it is served what it is served
    alone, and a third then admitted into the freed slot is served cold."""
    short, long_, third = _prompts(9, 14, 7, seed=2)
    alone = _engine(params, kernel)
    probe = alone.submit(short, max_new_tokens=8, temperature=0.0)
    want_long = alone.submit(long_, max_new_tokens=12, temperature=0.0)
    want_third = alone.submit(third, max_new_tokens=4, temperature=0.0)
    alone.run_until_idle()
    # (the tiny model repeats itself: stop at the first token's value)
    eos, stops_at = probe.tokens[0], 1
    eng = _engine(params, kernel, slots=2)
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
    chunks: its state and window are what its own chunks made of them."""
    p_short, p_long = _prompts(4, 37, seed=3)
    eng = _engine(params, slots=2)
    a = eng.submit(p_short, max_new_tokens=16, temperature=0.0)
    b = eng.submit(p_long, max_new_tokens=3, temperature=0.0)
    eng.run_until_idle()
    for p, r in ((p_short, a), (p_long, b)):
        _assert_served_as_one_forward(params, p, r)
    # decode steps ran between the long prompt's five chunks
    assert eng.metrics.decode_steps >= 15 and eng.metrics.prefill_chunks == 6


def test_the_books_are_the_page_groups_and_the_entry_follows_the_slot(params):
    """`engine.cache.num_pages`, `.page_nbytes` and `engine.allocator` mean
    the attention layers' pages, allocated and released as a first group's
    are; the state group has one entry a slot and no books; the gauges say
    both groups."""
    eng = _engine(params, slots=3, pages=12)
    cache = eng.cache
    assert isinstance(cache, GroupedPagedCache)
    assert isinstance(cache.state, StateCache)
    assert cache.layers == ((1, 3),) and cache.state_layers == (0, 2)
    assert cache.groups[0].k.shape == (2, 13, 1, 16, 128)
    assert cache.state.s.shape == (2, 4, 1, 16, 512)
    assert cache.state.z.shape == (2, 3, 4, 512)
    assert (cache.num_pages, cache.trash_page, cache.page_size) == (12, 12, 16)
    assert cache.page_nbytes == 2 * 2 * 16 * 128 * 4
    entry = 2 * (16 + 3) * 512 * 4
    assert cache.state.page_nbytes == entry
    assert eng.allocator.pages_needed(20, 4) == 2      # 32 rows with slack
    assert eng._ring_tables == [] and eng.allocator.ring_pools == ()
    configure_tracing(True)
    try:
        reqs = [eng.submit(p, max_new_tokens=4, temperature=0.0)
                for p in _prompts(20, 7, 30, seed=4)]
        eng.step()
        assert eng.allocator.pages_in_use == 2 + 2 + 3
        summary = eng.metrics_summary()
        assert summary["state_bytes_in_use"] == 3 * entry
        assert summary["kv_bytes_in_use"] == 7 * cache.page_nbytes
        gauges = {g: eng.registry.gauge("serving_group_pages_in_use",
                                        group=g).value
                  for g in ("full", "state")}
        assert gauges == {"full": 7, "state": 3}
        eng.run_until_idle()
        spans = [s for s in flight_recorder()
                 if s["name"] == "serving.kv.allocate"]
        assert spans[-1]["attrs"]["state_entries"] == 1
        assert spans[-1]["attrs"]["full_pages"] == spans[-1]["attrs"]["pages"]
    finally:
        configure_tracing(False)
    assert all(r.status.value == "finished" for r in reqs)
    assert eng.metrics_summary()["state_bytes_in_use"] == 0.0
    assert eng.allocator.pages_in_use == 0
    assert (eng._table == cache.trash_page).all()


def test_the_device_counters_count_tokens_scanned_and_states_zeroed(params):
    eng = _engine(params)
    for p in _prompts(13, 5, seed=5):
        eng.submit(p, max_new_tokens=4, temperature=0.0)
    eng.run_until_idle()
    got = eng.device_counters()
    scans = len(CFG.mamba_layers)
    # every prompt token is scanned by a chunk; of a request's 4 tokens the
    # first comes from the last chunk and the last is never fed back
    assert wide_count(got["prefill"]["tokens_scanned"]) == 18 * scans
    assert wide_count(got["decode"]["tokens_scanned"]) == 2 * 3 * scans
    assert int(got["prefill"]["states_zeroed"]) == 2


def test_generate_runs_over_views_and_states(params):
    prompt = jnp.asarray(np.stack(_prompts(6, 6, seed=6)))
    out = jamba.generate(CFG, params, prompt, max_new_tokens=4)
    assert out.shape == (2, 10)
    for row in np.asarray(out):
        first, _ = _full_forward(params, row[:6], row[6:])
        assert list(row[6:]) == list(first)


def test_the_controls_are_other_models(params):
    """What `probe.py --set cell.program_config_extra...` serves is not
    this model: without the inner norms, or with the oldest tap left out,
    the logits move by far more than any rounding."""
    ids = jnp.asarray(_prompts(24, seed=8)[0])[None]
    want = jamba.forward(CFG, params, ids)
    for other in (dict(use_inner_norms=False), dict(conv_taps_skipped=1)):
        got = jamba.forward(dataclasses.replace(CFG, **other), params, ids)
        assert float(jnp.abs(got - want).max()) > 0.05, other


def test_the_family_is_not_imported_with_the_package():
    import subprocess
    import sys

    code = ("import sys, accelerate_tpu, accelerate_tpu.serving; "
            "assert 'accelerate_tpu.models.jamba' not in sys.modules; "
            "assert 'accelerate_tpu.ops.selective_scan' not in sys.modules")
    from accelerate_tpu.test_utils import checkout_child_env

    subprocess.run([sys.executable, "-c", code], check=True,
                   env=checkout_child_env())


# ---------------------------------------------------------------------------
# what raises
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("option,match", [
    (dict(prefix_cache=True), "a snapshot published at a boundary"),
    (dict(kv_dtype="int8"), "int8 codes of a state"),
    (dict(host_tier_bytes=1 << 20), "a snapshot of an entry"),
    (dict(mesh="two-devices"), "its kernels under\n? ?GSPMD|under GSPMD"),
    (dict(speculative="draft"), "cannot be cut off a state"),
])
def test_unported_options_raise_with_both_traits_named(params, option, match):
    if option.get("mesh"):
        option = dict(mesh=jax.sharding.Mesh(
            np.array(jax.devices()[:2]), ("model",)))
    if option.get("speculative"):
        option = dict(speculative=(jamba, CFG, params))
    option = dict(dict(prefix_cache=False), **option)
    with pytest.raises(ValueError, match=match) as err:
        Engine(jamba, CFG, params, EngineConfig(
            num_slots=2, max_len=64, prefill_chunk=8, **option))
    said = str(err.value)
    assert "cache_spec gives 2 groups: ['full', 'state']" in said
    assert "BESIDE the other layers' K/V rows" in said
    assert "Nothing falls back to a one-kind pool" in said
    assert "Nothing falls back to K/V rows" in said


def test_the_default_engine_config_raises_for_its_prefix_cache(params):
    with pytest.raises(ValueError, match="prefix_cache=True"):
        Engine(jamba, CFG, params, EngineConfig(num_slots=2, max_len=64))


def test_a_fork_raises_with_both_traits_named(params):
    eng = _engine(params)
    parent = eng.submit(_prompts(9)[0], max_new_tokens=2, temperature=0.0)
    with pytest.raises(ValueError,
                       match="a snapshot of the parent's state") as err:
        eng.fork(parent)
    assert "BESIDE its groups of K/V pages" in str(err.value)
    eng.run_until_idle()
    assert parent.status.value == "finished"


@pytest.mark.parametrize("changed,match", [
    (dict(num_experts=16), "an expert block inside"),
    (dict(sliding_window=4096), "sliding window"),
    (dict(mamba_proj_bias=True), "mamba_proj_bias=False"),
    (dict(mamba_conv_bias=False), "mamba_conv_bias=True"),
    (dict(tie_word_embeddings=False), "tie_word_embeddings=True"),
    (dict(num_key_value_heads=3, num_attention_heads=4), "KV head"),
    (dict(mamba_d_conv=1), "2 taps or more"),
    (dict(attn_layer_period=1, attn_layer_offset=0), "no layer of one kind"),
])
def test_the_config_refuses_what_is_not_implemented(changed, match):
    with pytest.raises(ValueError, match=match):
        jamba.JambaConfig.tiny(**changed)


def test_the_layer_kinds_follow_offset_and_period():
    c = jamba.JambaConfig()
    assert c.attention_layers == (7, 21) and len(c.mamba_layers) == 26
    assert (c.head_dim, c.d_inner) == (128, 5120)
    pages, state = jamba.cache_spec(c)
    assert (pages.kind, pages.layers, pages.heads, pages.width) == (
        "kv", (7, 21), 1, 128)
    assert (state.kind, state.num_layers, state.state_rows, state.aux_rows,
            state.width, state.aux_entry_minor) == (
        "state", 26, 16, 3, 5120, True)
    assert state.layers == c.mamba_layers and state.label == "state"
