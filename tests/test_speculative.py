"""Speculative decoding + COW request forking + real logprobs (ISSUE 12).

CPU contracts for the draft/verify/accept engine mode and engine-level
request forking: greedy output is byte-identical to the non-speculative
engine whatever the draft (exact-match accept), sampled output follows
the TARGET distribution exactly (rejection sampling — pinned against a
known closed-form distribution, with a deliberately skewed draft), the
compile count stays flat across the speculative x int8 config matrix,
strict="error" audits the five programs clean, an n-way fork fan-out
pays ONE prompt prefill (pinned by chunk count) with full COW isolation
under cancel/retire, and per-token logprobs match a hand computation
from the family forward."""

import dataclasses
from types import SimpleNamespace

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from accelerate_tpu.models import gpt2
from accelerate_tpu.serving import Engine, EngineConfig, RequestStatus


@pytest.fixture(scope="module", autouse=True)
def _persistent_compile_cache(tmp_path_factory):
    """Every Engine() here compiles the same tiny programs; the repo's
    persistent compilation cache turns the repeats into deserializes."""
    import os

    from accelerate_tpu.utils.environment import configure_compilation_cache

    prev = os.environ.get("ACCELERATE_TPU_COMPILATION_CACHE_MIN_COMPILE_SECS")
    os.environ.setdefault(
        "ACCELERATE_TPU_COMPILATION_CACHE_MIN_COMPILE_SECS", "0")
    configure_compilation_cache(
        str(tmp_path_factory.mktemp("xla_cache")), force=True)
    yield
    # scoped: hand the process back with caching OFF — a later module that
    # re-traces an AOT-compiled train step would deserialize a threshold-0
    # entry from this dir and segfault jaxlib (ISSUE 16 hit this the moment
    # an engine module sorted before test_launched_scripts)
    if prev is None:
        os.environ.pop(
            "ACCELERATE_TPU_COMPILATION_CACHE_MIN_COMPILE_SECS", None)
    configure_compilation_cache("off", force=True)


@pytest.fixture(scope="module")
def gpt2_setup():
    cfg = gpt2.GPT2Config.tiny()
    params = gpt2.init_params(cfg, jax.random.key(0))
    return cfg, params


@pytest.fixture(scope="module")
def draft_setup(gpt2_setup):
    """A DISAGREEING draft: same tiny architecture, different random
    init — its argmax/softmax differ from the target's, so exact-match
    accepts fail and the rejection/correction paths actually run."""
    cfg, _ = gpt2_setup
    return cfg, gpt2.init_params(cfg, jax.random.key(99))


def _engine(cfg, params, family=gpt2, **overrides):
    defaults = dict(num_slots=3, max_len=64, prefill_chunk=8,
                    cache_dtype=jnp.float32)
    defaults.update(overrides)
    return Engine(family, cfg, params, EngineConfig(**defaults))


def _prompt(rng, n, vocab):
    return rng.integers(0, vocab, (n,)).astype(np.int32)


def _run_wave(eng, prompts, temps, budget=7, keys=None):
    reqs = [eng.submit(p, max_new_tokens=budget, temperature=t,
                       key=None if keys is None else keys[i])
            for i, (p, t) in enumerate(zip(prompts, temps))]
    eng.run_until_idle()
    assert all(r.status is RequestStatus.FINISHED for r in reqs)
    return reqs


SPEC_PROGRAMS = {"admit": 1, "prefill": 1, "draft_prefill": 1,
                 "draft": 1, "verify": 1}


# ---------------------------------------------------------------------------
# the acceptance contract: greedy byte-identical, whatever the draft
# ---------------------------------------------------------------------------


def test_speculative_greedy_byte_identical_disagreeing_draft(
        gpt2_setup, draft_setup):
    """Exact-match accept means greedy output CANNOT depend on the draft:
    a disagreeing draft (different random init) only lowers the accept
    rate — the committed chain is the target's argmax chain, byte for
    byte, through staggered multi-slot traffic and prefix-reuse hits."""
    cfg, params = gpt2_setup
    _, dparams = draft_setup
    rng = np.random.default_rng(0)
    shared = _prompt(rng, 18, cfg.vocab_size)
    prompts = [_prompt(rng, n, cfg.vocab_size) for n in (5, 13, 9)]
    prompts += [np.concatenate([shared, _prompt(rng, n, cfg.vocab_size)])
                for n in (3, 4)]
    temps = (0.0,) * len(prompts)

    def run(eng):
        # second shared-prefix prompt arrives in a second wave, so it
        # admits as a prefix HIT (target reuses pages; the draft runs
        # its catch-up chunks)
        out = [r.tokens for r in _run_wave(eng, prompts[:4], temps[:4])]
        return out + [r.tokens for r in _run_wave(eng, prompts[4:],
                                                  temps[4:])]

    plain = run(_engine(cfg, params, num_slots=2, page_size=8))
    eng = _engine(cfg, params, num_slots=2, page_size=8,
                  speculative=(gpt2, cfg, dparams), draft_k=4)
    spec = run(eng)
    assert spec == plain
    assert eng.compile_stats() == SPEC_PROGRAMS
    assert eng.metrics.prefix_hits >= 1
    m = eng.metrics_summary()
    # the disagreeing draft must actually disagree — otherwise the
    # rejection/correction path was never exercised
    assert 0.0 < m["spec_accept_rate"] < 1.0, m["spec_accept_rate"]
    assert m["spec_drafted_tokens"] > m["spec_accepted_tokens"]


def test_speculative_self_draft_hits_tokens_per_step_bar(gpt2_setup):
    """A perfectly-agreeing draft (the target drafts for itself) commits
    draft_k + 1-adjacent tokens per verify step: accept rate 1.0 and
    tokens-per-decode-step > 1.5 — the ISSUE 12 acceptance bar — while
    staying byte-identical to the plain engine."""
    cfg, params = gpt2_setup
    rng = np.random.default_rng(1)
    prompts = [_prompt(rng, n, cfg.vocab_size) for n in (5, 12)]
    temps = (0.0, 0.0)
    # engine shapes deliberately MATCH the disagreeing-draft test's
    # (slots 2, page 8): the module's compile cache turns this test's
    # plain-engine programs into deserializes (tier-1 budget satellite)
    plain = [r.tokens for r in _run_wave(
        _engine(cfg, params, num_slots=2, page_size=8), prompts, temps,
        budget=10)]
    eng = _engine(cfg, params, num_slots=2, page_size=8,
                  speculative=(gpt2, cfg, params), draft_k=3)
    spec = [r.tokens for r in _run_wave(eng, prompts, temps, budget=10)]
    assert spec == plain
    m = eng.metrics_summary()
    assert m["spec_accept_rate"] == 1.0
    assert m["tokens_per_decode_step"] > 1.5, m["tokens_per_decode_step"]


# ---------------------------------------------------------------------------
# distribution preservation under sampling (the rejection-sampling pin)
# ---------------------------------------------------------------------------


def _const_logits_forward(bias):
    """A family forward whose logits are CONSTANT (independent of input)
    — the KV mechanics are gpt2's, so cache plumbing stays real, but
    every sampled token is an i.i.d. draw from softmax(bias). That makes
    the committed-token distribution checkable in closed form."""
    bias = jnp.asarray(bias, jnp.float32)

    def fwd(config, params, input_ids, positions=None, kv_caches=None):
        logits, caches = gpt2.forward(config, params, input_ids,
                                      positions=positions,
                                      kv_caches=kv_caches)
        return jnp.broadcast_to(bias, logits.shape), caches

    return fwd


def test_speculative_sampling_preserves_target_distribution(gpt2_setup):
    """The rejection-sampling correctness pin: with a KNOWN constant
    target distribution and a draft deliberately skewed toward a token
    the target (almost) never emits, the committed tokens must still
    follow the TARGET distribution — accepted proposals plus residual
    corrections reproduce it exactly. A broken accept rule (e.g.
    committing draft proposals unconditionally) floods token 0 and fails
    by a wide margin."""
    cfg, params = gpt2_setup
    V = cfg.vocab_size
    target_p = np.full((V,), 1e-12)
    target_p[1:5] = [0.4, 0.3, 0.2, 0.1]
    target_bias = np.log(target_p / target_p.sum())
    draft_p = np.full((V,), 1e-12)
    draft_p[0] = 0.5                       # the poison proposal
    draft_p[1:5] = 0.125
    draft_bias = np.log(draft_p / draft_p.sum())

    # 4 waves x budget 12 instead of 6 x 8: the same 192 samples, but a
    # third fewer admission/prefill cycles drive the eager host-side
    # wave loop (tier-1 budget satellite — batched deeper, same
    # closed-form statistics)
    eng = Engine(
        _const_logits_forward(target_bias), cfg, params,
        EngineConfig(num_slots=4, max_len=32, prefill_chunk=8,
                     cache_dtype=jnp.float32,
                     speculative=(_const_logits_forward(draft_bias),
                                  cfg, params),
                     draft_k=4))
    rng = np.random.default_rng(2)
    samples: list[int] = []
    for wave in range(4):
        prompts = [_prompt(rng, 4, V) for _ in range(4)]
        keys = [np.array([wave, i], np.uint32) for i in range(4)]
        reqs = _run_wave(eng, prompts, temps=(1.0,) * 4, budget=12,
                         keys=keys)
        for r in reqs:
            samples.extend(r.tokens)
    counts = np.bincount(samples, minlength=V)
    freq = counts / counts.sum()
    # token 0 is (essentially) impossible under the target: any real
    # mass here means draft proposals leaked through the accept rule
    assert freq[0] < 0.04, freq[:6]
    for tok, p in ((1, 0.4), (2, 0.3), (3, 0.2), (4, 0.1)):
        assert abs(freq[tok] - p) < 0.12, (tok, freq[tok], p)
    assert counts[5:].sum() == 0  # nothing outside the support
    # the skewed draft really was skewed: most proposals were rejected
    m = eng.metrics_summary()
    assert m["spec_accept_rate"] < 0.8, m["spec_accept_rate"]


# ---------------------------------------------------------------------------
# compile-count flatness + config validation + strict audit
# ---------------------------------------------------------------------------


def test_compile_flat_across_speculative_int8_and_k_mixes(gpt2_setup):
    """The compile-count guard over the new axes: a speculative engine
    per kv_dtype (bf16/int8 pools — the kernel axis is invalid with
    speculation, pinned in config validation below), driven through
    waves of different prompt lengths / budgets / temperatures / prefix
    hits — five programs, each compiled exactly once. draft_k=3 differs
    from the other suites' k=4 so two k values compile-flat overall."""
    cfg, params = gpt2_setup
    rng = np.random.default_rng(3)
    shared = _prompt(rng, 18, cfg.vocab_size)
    for kvd in (None, "int8"):
        eng = _engine(cfg, params, num_slots=2, max_len=48,
                      page_size=8, cache_dtype=jnp.bfloat16,
                      kv_dtype=kvd,
                      speculative=(gpt2, cfg, params), draft_k=3)
        for plen, mnt, temp in ((3, 2, 0.0), (13, 1, 1.0),
                                ("shared", 2, 0.5)):
            if plen == "shared":
                prompts = [np.concatenate(
                    [shared, _prompt(rng, 2 + i, cfg.vocab_size)])
                    for i in range(2)]
            else:
                prompts = [_prompt(rng, plen, cfg.vocab_size)
                           for _ in range(2)]
            reqs = [eng.submit(p, max_new_tokens=mnt, temperature=temp)
                    for p in prompts]
            eng.run_until_idle()
            assert all(r.status is RequestStatus.FINISHED
                       for r in reqs)
            assert eng.compile_stats() == SPEC_PROGRAMS, kvd


def test_speculative_config_validation(gpt2_setup):
    """Bad speculative configs fail LOUDLY at construction: k < 1,
    vocab mismatch, a non-triple, the Pallas kernel (single-token op vs
    K-token verify), and a meshed engine."""
    cfg, params = gpt2_setup
    spec = (gpt2, cfg, params)
    with pytest.raises(ValueError, match="draft_k"):
        _engine(cfg, params, speculative=spec, draft_k=0)
    with pytest.raises(ValueError, match="triple"):
        _engine(cfg, params, speculative=gpt2)
    bad_cfg = dataclasses.replace(cfg, vocab_size=cfg.vocab_size + 1)
    with pytest.raises(ValueError, match="vocab_size"):
        _engine(cfg, params, speculative=(gpt2, bad_cfg, params))
    with pytest.raises(ValueError, match="paged_attention"):
        _engine(cfg, params, speculative=spec, paged_attention=True)
    with pytest.raises(ValueError, match="meshed"):
        _engine(cfg, params, speculative=spec,
                mesh=SimpleNamespace(size=2))
    # "auto" resolves to the dense verify path instead of erroring
    eng = _engine(cfg, params, speculative=spec, paged_attention="auto")
    assert not eng._use_paged_kernel


def test_speculative_strict_error_audits_clean(gpt2_setup):
    """strict="error" audits all five speculative programs (the
    exhaustive no-collectives contract names each) with no findings on
    a greedy + sampled wave; the contract factory exposes the five
    names."""
    from accelerate_tpu.analysis.contracts import serving_program_contracts

    contracts = serving_program_contracts(speculative=True)
    assert set(contracts) == set(SPEC_PROGRAMS)
    assert contracts["verify"].name == "serving.verify"

    cfg, params = gpt2_setup
    # shapes match the self-draft test's spec engine (slots 2, page 8,
    # k=3): the audit reads the lowering, the executables deserialize
    eng = _engine(cfg, params, num_slots=2, page_size=8,
                  speculative=(gpt2, cfg, params), draft_k=3,
                  strict="error")
    rng = np.random.default_rng(4)
    prompts = [_prompt(rng, n, cfg.vocab_size) for n in (5, 11)]
    _run_wave(eng, prompts, temps=(0.0, 0.9), budget=5)  # no raise = clean


def test_pod_router_strips_speculation(gpt2_setup):
    """PodEngine workers must not half-adopt speculation (the install
    path drives the classic admit program): the router strips the
    setting and the pod still serves the trace byte-identically to a
    plain single engine."""
    from accelerate_tpu.serving.pod import PodConfig, PodEngine

    cfg, params = gpt2_setup
    ec = EngineConfig(num_slots=3, max_len=64, prefill_chunk=8,
                      cache_dtype=jnp.float32,
                      speculative=(gpt2, cfg, params), draft_k=4)
    pod = PodEngine(gpt2, cfg, params, ec,
                    PodConfig(prefill_workers=1, decode_workers=1))
    for handle in pod.workers.values():
        assert handle.local.engine.engine_config.speculative is None
    rng = np.random.default_rng(5)
    p = _prompt(rng, 9, cfg.vocab_size)
    ref_eng = _engine(cfg, params)
    ref = ref_eng.submit(p, max_new_tokens=5)
    ref_eng.run_until_idle()
    req = pod.submit(p, max_new_tokens=5)
    pod.run_until_idle()
    assert req.status is RequestStatus.FINISHED
    assert req.tokens == ref.tokens


# ---------------------------------------------------------------------------
# COW request forking
# ---------------------------------------------------------------------------


def test_fork_fan_out_pays_one_prefill_pinned(gpt2_setup):
    """The ISSUE 12 fan-out bar at the engine level: 1 submit + 7 forks
    of an 80-token prompt (page_size 16, chunk 16) cost exactly ONE full
    prompt prefill (5 chunks) plus one catch-up chunk per fork (the
    final partial page — reuse is capped one token short, so the last
    token always prefills to produce first-token logits): 12 chunks,
    not the 40 of eight independent prefills."""
    cfg, params = gpt2_setup
    eng = _engine(cfg, params, num_slots=4, max_len=128, prefill_chunk=16,
                  page_size=16)
    rng = np.random.default_rng(6)
    prompt = _prompt(rng, 80, cfg.vocab_size)
    parent = eng.submit(prompt, max_new_tokens=5, temperature=0.8,
                        key=np.array([1, 0], np.uint32))
    forks = [eng.fork(parent, key=np.array([1, i + 1], np.uint32))
             for i in range(7)]
    eng.run_until_idle()
    assert all(r.status is RequestStatus.FINISHED
               for r in [parent] + forks)
    assert eng.metrics.prefill_chunks == 5 + 7, eng.metrics.prefill_chunks
    # distinct keys -> decorrelated sibling streams
    assert len({tuple(r.tokens) for r in forks}) > 1
    for f in forks:
        assert f.parent_id == parent.request_id


def test_fork_greedy_matches_parent_and_fresh_engine(gpt2_setup):
    """Greedy forks share the parent's argmax chain: reused prompt pages
    hold exactly the K/V a cold prefill would produce (COW rewrite is
    byte-identical), so parent, forks, and a fresh-engine submission all
    emit the same tokens AND the same per-token logprobs."""
    cfg, params = gpt2_setup
    eng = _engine(cfg, params, page_size=8)
    rng = np.random.default_rng(7)
    prompt = _prompt(rng, 21, cfg.vocab_size)
    parent = eng.submit(prompt, max_new_tokens=6)
    forks = [eng.fork(parent) for _ in range(2)]
    eng.run_until_idle()
    ref_eng = _engine(cfg, params, page_size=8)
    ref = ref_eng.submit(prompt, max_new_tokens=6)
    ref_eng.run_until_idle()
    assert parent.tokens == forks[0].tokens == forks[1].tokens
    assert parent.tokens == ref.tokens
    assert parent.logprobs == pytest.approx(ref.logprobs, abs=1e-5)
    assert forks[0].logprobs == pytest.approx(ref.logprobs, abs=1e-5)


def test_fork_cow_isolation_under_cancel_and_retire(gpt2_setup):
    """COW isolation: cancelling the PARENT mid-decode leaves every
    fork's stream untouched (shared pages are refcounted, not owned),
    cancelling one FORK leaves its siblings untouched, and after all
    requests retire no page is still mapped."""
    cfg, params = gpt2_setup
    rng = np.random.default_rng(8)
    prompt = _prompt(rng, 40, cfg.vocab_size)
    keys = [np.array([9, i], np.uint32) for i in range(4)]

    # baseline: same (prompt, key) requests on a fresh engine — fork
    # streams are schedule-independent, so these are the ground truth
    base_eng = _engine(cfg, params, num_slots=2, page_size=8, max_len=96)
    base = [base_eng.submit(prompt, max_new_tokens=6, temperature=0.7,
                            key=k) for k in keys]
    base_eng.run_until_idle()

    eng = _engine(cfg, params, num_slots=2, page_size=8, max_len=96)
    parent = eng.submit(prompt, max_new_tokens=6, temperature=0.7,
                        key=keys[0])
    forks = [eng.fork(parent, key=keys[i]) for i in (1, 2, 3)]
    # run until the parent has a couple of tokens, then kill it
    while len(parent.tokens) < 2:
        eng.step()
    assert eng.cancel(parent)
    # kill one fork as soon as it produces a token
    while len(forks[0].tokens) < 1:
        eng.step()
    assert eng.cancel(forks[0])
    eng.run_until_idle()
    for i, f in zip((2, 3), forks[1:]):
        assert f.status is RequestStatus.FINISHED
        assert f.tokens == base[i].tokens, i
    assert parent.status is RequestStatus.CANCELLED
    assert eng.allocator.index.mapped_pages == 0
    assert eng.scheduler.live_slots == 0


def test_fork_of_finished_parent_and_no_prefix_cache(gpt2_setup):
    """A fork of a FINISHED parent maps the retirement-cached pages (one
    catch-up chunk only); with prefix_cache=False the fork still runs
    correctly — it just re-prefills (sharing needs the radix tree)."""
    cfg, params = gpt2_setup
    eng = _engine(cfg, params, num_slots=2, prefill_chunk=8, page_size=8,
                  max_len=96)
    rng = np.random.default_rng(10)
    prompt = _prompt(rng, 32, cfg.vocab_size)
    parent = eng.submit(prompt, max_new_tokens=4)
    eng.run_until_idle()
    chunks_before = eng.metrics.prefill_chunks
    assert chunks_before == 4
    fork = eng.fork(parent)
    eng.run_until_idle()
    assert fork.status is RequestStatus.FINISHED
    assert fork.tokens == parent.tokens
    assert eng.metrics.prefill_chunks == chunks_before + 1

    cold = _engine(cfg, params, num_slots=2, prefill_chunk=8, page_size=8,
                   max_len=96, prefix_cache=False)
    p2 = cold.submit(prompt, max_new_tokens=4)
    f2 = cold.fork(p2)
    cold.run_until_idle()
    assert f2.tokens == p2.tokens == parent.tokens
    assert cold.metrics.prefill_chunks == 8  # two full prefills


def test_fork_parentage_visible_in_debug_views(gpt2_setup):
    """The satellite's introspection clause: /debug/requests entries
    carry forked_from on forks and fork_parent on the shared parent."""
    cfg, params = gpt2_setup
    eng = _engine(cfg, params, num_slots=2, page_size=8, max_len=96)
    rng = np.random.default_rng(11)
    prompt = _prompt(rng, 24, cfg.vocab_size)
    parent = eng.submit(prompt, max_new_tokens=8)
    fork = eng.fork(parent)
    eng.step()
    dbg = eng.debug_requests()
    entries = dbg["running"] + dbg["queued"]
    by_id = {e["request_id"]: e for e in entries}
    assert by_id[parent.request_id].get("fork_parent") is True
    assert by_id[fork.request_id]["forked_from"] == parent.request_id
    eng.run_until_idle()


def test_fork_through_speculative_engine(gpt2_setup):
    """Forking composes with speculation: the verify commit's window
    scatter writes only PRIVATE pages (shared COW pages stay
    bit-stable), so greedy forks through a speculative engine match the
    plain engine's fork streams byte for byte."""
    cfg, params = gpt2_setup

    def run(eng):
        rng = np.random.default_rng(12)
        prompt = _prompt(rng, 24, cfg.vocab_size)
        parent = eng.submit(prompt, max_new_tokens=6)
        forks = [eng.fork(parent) for _ in range(2)]
        eng.run_until_idle()
        return [r.tokens for r in [parent] + forks]

    plain = run(_engine(cfg, params, num_slots=2, page_size=8, max_len=96))
    spec = run(_engine(cfg, params, num_slots=2, page_size=8, max_len=96,
                       speculative=(gpt2, cfg, params), draft_k=3))
    assert spec == plain


# ---------------------------------------------------------------------------
# real logprobs
# ---------------------------------------------------------------------------


def test_logprobs_match_hand_computed(gpt2_setup):
    """The engine's per-token logprobs equal log_softmax of the family
    forward's raw logits at the emitted token — recomputed here from
    one full-context forward, greedy AND sampled (the logprob is
    temperature-free, so both arms check against the same numbers)."""
    cfg, params = gpt2_setup
    rng = np.random.default_rng(13)
    prompt = _prompt(rng, 9, cfg.vocab_size)
    # ONE engine serves both arms concurrently (mixed temperatures are
    # one program), and the reference forward is jitted once — the two
    # full-context calls share a shape, so it compiles once (tier-1
    # budget satellite: was two engines + two eager op-by-op forwards)
    eng = _engine(cfg, params)
    reqs = {temp: eng.submit(prompt, max_new_tokens=6, temperature=temp,
                             key=np.array([3, 1], np.uint32))
            for temp in (0.0, 0.9)}
    eng.run_until_idle()
    ref_forward = jax.jit(lambda ids: gpt2.forward(cfg, params, ids))
    for temp, req in reqs.items():
        assert len(req.logprobs) == len(req.tokens) == 6
        full = np.concatenate([prompt, np.asarray(req.tokens, np.int32)])
        logits = ref_forward(jnp.asarray(full[None, :-1]))
        lsm = jax.nn.log_softmax(np.asarray(logits[0], np.float32), axis=-1)
        want = [float(lsm[len(prompt) - 1 + i, tok])
                for i, tok in enumerate(req.tokens)]
        assert req.logprobs == pytest.approx(want, abs=2e-3), temp
        assert req.cumulative_logprob == pytest.approx(sum(want), abs=1e-2)


def test_speculative_logprobs_match_plain_engine(gpt2_setup):
    """Speculative greedy emits the same tokens AND the same per-token
    logprobs as the plain engine (both are log-softmax of the target's
    raw logits at the committed token)."""
    cfg, params = gpt2_setup
    rng = np.random.default_rng(14)
    prompt = _prompt(rng, 7, cfg.vocab_size)
    # shapes match the disagreeing-draft test's engines (slots 2, page 8,
    # draft_k 4) so every program here deserializes from the module cache
    plain_eng = _engine(cfg, params, num_slots=2, page_size=8)
    plain = plain_eng.submit(prompt, max_new_tokens=6)
    plain_eng.run_until_idle()
    spec_eng = _engine(cfg, params, num_slots=2, page_size=8,
                       speculative=(gpt2, cfg, params), draft_k=4)
    spec = spec_eng.submit(prompt, max_new_tokens=6)
    spec_eng.run_until_idle()
    assert spec.tokens == plain.tokens
    assert spec.logprobs == pytest.approx(plain.logprobs, abs=2e-3)


def test_best_of_rank_uses_cumulative_logprob():
    """The server's best_of ranking (HttpFrontDoor._rank) orders by true
    cumulative logprob — hand-built candidates with known logprobs:
    highest sum wins, ties break to the lower index, a candidate with no
    logprobs ranks last. The documented length heuristic is gone."""
    from accelerate_tpu.server.http import HttpFrontDoor

    def cand(lps, n_tokens=None):
        r = SimpleNamespace(logprobs=list(lps),
                            tokens=[0] * (n_tokens if n_tokens is not None
                                          else len(lps)))
        r.cumulative_logprob = (sum(lps) if lps else None)
        return r

    # candidate 2 has the best (least negative) sum but the SHORTEST
    # completion — the old heuristic would rank it last, logprobs rank
    # it first
    reqs = [cand([-2.0, -2.0, -2.0, -2.0]),      # sum -8, longest
            cand([-1.0, -1.5]),                  # sum -2.5
            cand([-0.5]),                        # sum -0.5, shortest
            cand([])]                            # shed: no logprobs
    params = SimpleNamespace(best_of=4, n=3)
    ranked = HttpFrontDoor._rank(None, params, reqs)
    assert [r.cumulative_logprob for r in ranked] == [-0.5, -2.5, -8.0]
    # ties break to the lower candidate index
    tied = [cand([-1.0]), cand([-0.5, -0.5])]
    ranked = HttpFrontDoor._rank(None, SimpleNamespace(best_of=2, n=1),
                                 tied)
    assert ranked[0] is tied[0]


def test_catch_up_draft_length_survives_interleaved_decode(gpt2_setup):
    """Regression (review finding): a speculative decode step for OTHER
    slots must not clobber a mid-catch-up slot's draft length with the
    target's reused length — the draft rebuilds a prefix hit from zero,
    and a clobbered length shifts every later catch-up write onto wrong
    rows/positions (silent draft-state corruption: outputs stay correct
    because the accept rule reads target logits, but acceptance decays
    to draft-vs-garbage). Pinned white-box: while a slot prefills, its
    draft device length IS its host-tracked draft_done."""
    cfg, params = gpt2_setup
    eng = _engine(cfg, params, num_slots=2, max_len=96, page_size=8,
                  speculative=(gpt2, cfg, params), draft_k=4)
    rng = np.random.default_rng(15)
    shared = _prompt(rng, 24, cfg.vocab_size)
    r1 = eng.submit(np.concatenate([shared, _prompt(rng, 4, cfg.vocab_size)]),
                    max_new_tokens=4)
    eng.run_until_idle()           # retires -> shared prefix pages cached
    r2 = eng.submit(_prompt(rng, 5, cfg.vocab_size), max_new_tokens=40)
    for _ in range(6):
        eng.step()                 # r2 decoding when the hit arrives
    assert not r2.done
    r3 = eng.submit(np.concatenate([shared, _prompt(rng, 6, cfg.vocab_size)]),
                    max_new_tokens=12)
    slot3 = next(s for s in eng.scheduler.slots if s.request is r3)
    assert slot3.alloc.reused_len > 0  # the scenario needs a prefix HIT
    checked = 0
    while slot3.request is r3 and slot3.prompt_done < r3.prompt_len:
        eng.step()                 # alternates r3 catch-up / r2 decode
        if slot3.request is r3 and slot3.draft_done < slot3.prompt_done:
            assert int(np.asarray(eng._draft_cache.lengths)[slot3.index]) \
                == slot3.draft_done
            checked += 1
    assert checked > 0             # the interleave actually happened
    eng.run_until_idle()
    assert r3.status is RequestStatus.FINISHED
    # self-draft over uncorrupted state accepts everything
    assert eng.metrics_summary()["spec_accept_rate"] == 1.0
