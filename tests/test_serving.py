"""Continuous-batching serving engine (accelerate_tpu.serving).

CPU contracts for the request-lifecycle layer: batched greedy decode is
token-exact vs sequential `generate()`, slots are reused after retirement,
chunked prefill interleaves with decode instead of stalling it, admission
control rejects/sheds instead of OOMing, and the engine's compiled-program
count stays flat however the request mix changes (the fixed-shape design's
whole point)."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from accelerate_tpu.models import gpt2, llama
from accelerate_tpu.models.decode import sample_token
from accelerate_tpu.serving import (
    Engine,
    EngineConfig,
    Request,
    RequestStatus,
    Scheduler,
    SlotKVCache,
    SlotState,
)


@pytest.fixture(scope="module", autouse=True)
def _persistent_compile_cache(tmp_path_factory):
    """Every Engine() compiles the same three tiny programs; the repo's
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


def _engine(cfg, params, family=gpt2, **overrides):
    defaults = dict(num_slots=3, max_len=64, prefill_chunk=8,
                    cache_dtype=jnp.float32)
    defaults.update(overrides)
    return Engine(family, cfg, params, EngineConfig(**defaults))


def _prompt(rng, n, vocab):
    return rng.integers(0, vocab, (n,)).astype(np.int32)


# ---------------------------------------------------------------------------
# the acceptance contract: staggered concurrent == sequential, one compile
# ---------------------------------------------------------------------------


def test_staggered_requests_match_sequential_generate(gpt2_setup):
    """3 requests submitted at different times (so their decode depths
    never align) produce token-identical greedy output vs 3 sequential
    `generate()` calls — through exactly ONE decode-program compilation."""
    cfg, params = gpt2_setup
    eng = _engine(cfg, params)
    rng = np.random.default_rng(0)
    prompts = [_prompt(rng, n, cfg.vocab_size) for n in (5, 11, 3)]

    reqs = [eng.submit(prompts[0], max_new_tokens=8)]
    for _ in range(3):  # r0 mid-prefill/decode before r1 even arrives
        eng.step()
    reqs.append(eng.submit(prompts[1], max_new_tokens=8))
    for _ in range(2):
        eng.step()
    reqs.append(eng.submit(prompts[2], max_new_tokens=8))
    eng.run_until_idle()

    for p, r in zip(prompts, reqs):
        assert r.status is RequestStatus.FINISHED
        ref = gpt2.generate(cfg, params, jnp.asarray(p)[None, :],
                            max_new_tokens=8)
        assert r.tokens == np.asarray(ref)[0, len(p):].tolist()
    assert eng.compile_stats()["decode"] == 1, eng.compile_stats()


def test_chunked_prefill_is_token_exact(gpt2_setup):
    """A prompt much longer than the chunk prefills in pieces and still
    decodes exactly like one-shot generate (writes advance by real tokens
    only; padded rows are never attended)."""
    cfg, params = gpt2_setup
    eng = _engine(cfg, params, prefill_chunk=4)
    rng = np.random.default_rng(1)
    p = _prompt(rng, 19, cfg.vocab_size)
    r = eng.submit(p, max_new_tokens=6)
    eng.run_until_idle()
    ref = gpt2.generate(cfg, params, jnp.asarray(p)[None, :],
                        max_new_tokens=6)
    assert r.tokens == np.asarray(ref)[0, len(p):].tolist()


def test_gqa_family_llama_matches_sequential():
    """The engine is family-agnostic: llama's GQA cache dims ride the same
    programs."""
    cfg = llama.LlamaConfig.tiny()
    params = llama.init_params(cfg, jax.random.key(0))
    eng = _engine(cfg, params, family=llama, num_slots=2)
    rng = np.random.default_rng(2)
    prompts = [_prompt(rng, n, cfg.vocab_size) for n in (6, 9)]
    reqs = [eng.submit(p, max_new_tokens=5) for p in prompts]
    eng.run_until_idle()
    for p, r in zip(prompts, reqs):
        ref = llama.generate(cfg, params, jnp.asarray(p)[None, :],
                             max_new_tokens=5)
        assert r.tokens == np.asarray(ref)[0, len(p):].tolist()


# ---------------------------------------------------------------------------
# recompile guard
# ---------------------------------------------------------------------------


def test_compiled_program_count_flat_across_request_mix(gpt2_setup):
    """Waves of requests with different prompt lengths, token budgets, and
    temperatures never add a compiled program: the request mix is data,
    not shape. Extended for the paged cache (ISSUE 5): a wave of
    shared-prefix prompts (prefix-cache HITS — reused lengths and remapped
    page tables are traced data too) rides the same three programs."""
    cfg, params = gpt2_setup
    eng = _engine(cfg, params, num_slots=2, max_len=48)
    rng = np.random.default_rng(3)
    shared = _prompt(rng, 18, cfg.vocab_size)
    waves = [(3, 4, 0.0), (13, 2, 1.0), (7, 6, 0.5), (1, 3, 0.0),
             ("shared", 3, 0.0), ("shared", 3, 1.0)]
    for wave, (plen, mnt, temp) in enumerate(waves):
        if plen == "shared":
            prompts = [np.concatenate(
                [shared, _prompt(rng, 2 + i, cfg.vocab_size)])
                for i in range(3)]
        else:
            prompts = [_prompt(rng, plen, cfg.vocab_size) for _ in range(3)]
        reqs = [eng.submit(p, max_new_tokens=mnt, temperature=temp)
                for p in prompts]
        eng.run_until_idle()
        assert all(r.status is RequestStatus.FINISHED for r in reqs)
        counts = eng.compile_stats()
        assert counts == {"admit": 1, "prefill": 1, "decode": 1}, (
            f"wave {wave} recompiled: {counts}")
    assert eng.metrics.prefix_hits >= 2  # the shared waves actually hit


# ---------------------------------------------------------------------------
# slot lifecycle
# ---------------------------------------------------------------------------


def test_slot_reuse_after_retirement(gpt2_setup):
    """More requests than slots: retired slots re-admit from the queue, and
    a reused slot's stale cache never leaks into the next request's output
    (length reset + position mask — no cache wipe)."""
    cfg, params = gpt2_setup
    eng = _engine(cfg, params, num_slots=2)
    rng = np.random.default_rng(4)
    # equal-length prompts: slot reuse doesn't depend on length variety
    # (the staggered test covers that), and one BATCHED reference
    # generate replaces five per-length compiles (tier-1 budget)
    prompts = [_prompt(rng, 6, cfg.vocab_size) for _ in range(5)]
    reqs = [eng.submit(p, max_new_tokens=4) for p in prompts]
    assert eng.scheduler.queue_depth == 3  # only 2 slots
    eng.run_until_idle()
    refs = np.asarray(gpt2.generate(
        cfg, params, jnp.asarray(np.stack(prompts)), max_new_tokens=4))
    for i, (p, r) in enumerate(zip(prompts, reqs)):
        assert r.status is RequestStatus.FINISHED
        assert r.tokens == refs[i, len(p):].tolist()
    # all 5 ran through 2 slots
    assert eng.metrics.finished == 5


def test_prefill_decode_interleave_ordering(gpt2_setup):
    """A long prompt arriving while another request decodes must NOT stall
    it: prefill chunks and decode steps strictly alternate, so between any
    two consecutive prefill chunks there is a decode step."""
    cfg, params = gpt2_setup
    eng = _engine(cfg, params, prefill_chunk=4)
    actions = []
    orig_prefill, orig_decode = eng._run_prefill_chunk, eng._run_decode
    eng._run_prefill_chunk = lambda s: (actions.append("p"), orig_prefill(s))[1]
    eng._run_decode = lambda s: (actions.append("d"), orig_decode(s))[1]

    rng = np.random.default_rng(5)
    r0 = eng.submit(_prompt(rng, 4, cfg.vocab_size), max_new_tokens=16)
    for _ in range(4):  # r0 prefilled and decoding
        eng.step()
    del actions[:]
    eng.submit(_prompt(rng, 20, cfg.vocab_size), max_new_tokens=2)
    eng.run_until_idle()
    first_burst = actions[:9]  # while both kinds of work existed
    assert "p" in first_burst and "d" in first_burst
    assert "pp" not in "".join(first_burst), (
        f"prefill monopolized the engine: {actions}")
    assert r0.status is RequestStatus.FINISHED


def test_cancel_queued_and_running(gpt2_setup):
    cfg, params = gpt2_setup
    eng = _engine(cfg, params, num_slots=1)
    rng = np.random.default_rng(6)
    running = eng.submit(_prompt(rng, 4, cfg.vocab_size), max_new_tokens=32)
    head = eng.submit(_prompt(rng, 4, cfg.vocab_size), max_new_tokens=4)
    queued = eng.submit(_prompt(rng, 4, cfg.vocab_size), max_new_tokens=32)
    for _ in range(3):
        eng.step()
    assert running.status is RequestStatus.RUNNING
    # `queued` sits BEHIND `head`: removal must not compare numpy prompts
    # against other queued requests (Request compares by identity)
    assert eng.cancel(queued) and queued.status is RequestStatus.CANCELLED
    assert head.status is RequestStatus.QUEUED  # untouched by the removal
    assert eng.cancel(running) and running.status is RequestStatus.CANCELLED
    assert not eng.cancel(running)  # idempotent on terminal requests
    eng.run_until_idle()
    assert head.status is RequestStatus.FINISHED
    assert eng.scheduler.live_slots == 0


# ---------------------------------------------------------------------------
# admission control
# ---------------------------------------------------------------------------


def test_admission_rejects_when_queue_full(gpt2_setup):
    cfg, params = gpt2_setup
    eng = _engine(cfg, params, num_slots=1, max_queue=2)
    rng = np.random.default_rng(7)
    ok = [eng.submit(_prompt(rng, 4, cfg.vocab_size), max_new_tokens=4)
          for _ in range(3)]  # 1 would-be slot + 2 queued... all accepted
    assert all(not r.done for r in ok)
    shed = eng.submit(_prompt(rng, 4, cfg.vocab_size), max_new_tokens=4)
    assert shed.status is RequestStatus.REJECTED
    assert "queue full" in shed.reject_reason
    assert shed.tokens == []
    eng.run_until_idle()  # the accepted ones still finish
    assert all(r.status is RequestStatus.FINISHED for r in ok)
    assert eng.metrics.rejected == 1


def test_submit_drains_freed_slot_before_queue_full_check(gpt2_setup):
    """A slot freed since the last step must make room BEFORE a new submit
    is judged against max_queue — the bound covers genuinely *waiting*
    requests only. Regression: submit used to capacity-check first, so a
    full queue plus a just-freed slot spuriously REJECTED."""
    cfg, params = gpt2_setup
    eng = _engine(cfg, params, num_slots=1, max_queue=1)
    rng = np.random.default_rng(16)
    a = eng.submit(_prompt(rng, 4, cfg.vocab_size), max_new_tokens=1)
    b = eng.submit(_prompt(rng, 4, cfg.vocab_size), max_new_tokens=1)
    eng.step()  # a's prefill chunk computes its only token...
    assert a.status is RequestStatus.RUNNING and a.tokens == []
    eng.step()  # ...which the next step commits -> slot freed
    assert a.status is RequestStatus.FINISHED
    assert eng.scheduler.queue_depth == 1  # b still holds the queue position
    c = eng.submit(_prompt(rng, 4, cfg.vocab_size), max_new_tokens=1)
    assert c.status is not RequestStatus.REJECTED
    eng.run_until_idle()
    assert b.status is RequestStatus.FINISHED
    assert c.status is RequestStatus.FINISHED


def test_admission_rejects_overlong_request(gpt2_setup):
    cfg, params = gpt2_setup
    eng = _engine(cfg, params, max_len=16)
    r = eng.submit(np.arange(10, dtype=np.int32), max_new_tokens=10)
    assert r.status is RequestStatus.REJECTED
    assert "max_len" in r.reject_reason


def test_deadline_shedding_reports_expired(gpt2_setup):
    """A queued request whose deadline lapses before a slot frees is shed
    with EXPIRED — fake clock, no sleeping."""
    cfg, params = gpt2_setup
    now = [0.0]
    eng = Engine(gpt2, cfg, params,
                 EngineConfig(num_slots=1, max_len=64, prefill_chunk=8,
                              cache_dtype=jnp.float32),
                 clock=lambda: now[0])
    rng = np.random.default_rng(8)
    hog = eng.submit(_prompt(rng, 4, cfg.vocab_size), max_new_tokens=32)
    patient = eng.submit(_prompt(rng, 4, cfg.vocab_size), max_new_tokens=4)
    hurried = eng.submit(_prompt(rng, 4, cfg.vocab_size), max_new_tokens=4,
                         deadline_s=5.0)
    for _ in range(3):
        eng.step()
        now[0] += 1.0
    assert hurried.status is RequestStatus.QUEUED
    now[0] += 10.0  # deadline lapses while still queued, behind `patient`
    eng.step()  # shedding a non-head request must not crash on numpy __eq__
    assert hurried.status is RequestStatus.EXPIRED
    assert "deadline" in hurried.reject_reason
    assert patient.status is not RequestStatus.EXPIRED
    eng.run_until_idle()
    assert hog.status is RequestStatus.FINISHED
    assert patient.status is RequestStatus.FINISHED
    assert eng.metrics.expired == 1


# ---------------------------------------------------------------------------
# streaming + sampling
# ---------------------------------------------------------------------------


def test_stream_yields_tokens_and_matches_handle(gpt2_setup):
    cfg, params = gpt2_setup
    eng = _engine(cfg, params)
    rng = np.random.default_rng(9)
    r = eng.submit(_prompt(rng, 5, cfg.vocab_size), max_new_tokens=7)
    streamed = list(eng.stream(r))
    assert streamed == r.tokens and len(streamed) == 7
    assert r.status is RequestStatus.FINISHED


def test_astream_interleaves_concurrent_requests(gpt2_setup):
    import asyncio

    cfg, params = gpt2_setup
    eng = _engine(cfg, params, num_slots=2)
    rng = np.random.default_rng(10)

    async def consume(req):
        return [tok async for tok in eng.astream(req)]

    async def main():
        r1 = eng.submit(_prompt(rng, 4, cfg.vocab_size), max_new_tokens=5)
        r2 = eng.submit(_prompt(rng, 6, cfg.vocab_size), max_new_tokens=5)
        return await asyncio.gather(consume(r1), consume(r2)), (r1, r2)

    (t1, t2), (r1, r2) = asyncio.run(main())
    assert t1 == r1.tokens and t2 == r2.tokens
    assert len(t1) == len(t2) == 5


def test_eos_token_finishes_early(gpt2_setup):
    """EOS is checked host-side per token; pick the greedy first token as
    the 'EOS' so the request finishes after exactly one token."""
    cfg, params = gpt2_setup
    rng = np.random.default_rng(11)
    p = _prompt(rng, 5, cfg.vocab_size)
    ref = gpt2.generate(cfg, params, jnp.asarray(p)[None, :], max_new_tokens=2)
    eos = int(np.asarray(ref)[0, len(p)])
    eng = _engine(cfg, params)
    r = eng.submit(p, max_new_tokens=16, eos_token_id=eos)
    eng.run_until_idle()
    assert r.tokens == [eos]
    assert r.status is RequestStatus.FINISHED


def test_finish_mid_prefill_never_poisons_the_prefix_cache(gpt2_setup):
    """ISSUE 13 lifecycle-audit regression: `Engine.finish` on a request
    whose prefill is still mid-flight retires it FINISHED — but only the
    pages its prefill actually completed may enter the prefix tree.
    Pre-fix, the full prompt range was inserted and a later identical
    prompt reused never-written garbage KV; pinned by token-exactness
    against a fresh engine."""
    cfg, params = gpt2_setup
    rng = np.random.default_rng(23)
    p = _prompt(rng, 30, cfg.vocab_size)
    eng = _engine(cfg, params, prefill_chunk=8, page_size=8, max_len=96)
    r1 = eng.submit(p, max_new_tokens=4)
    eng.step()                      # one chunk: 8 of 30 prompt tokens
    slot = next(s for s in eng.scheduler.slots if s.request is r1)
    assert 0 < slot.prompt_done < r1.prompt_len
    assert eng.finish(r1)           # server-side early finish
    assert r1.status is RequestStatus.FINISHED
    # the same prompt again: whatever it reuses must be REAL prefilled
    # state, so its tokens match a fresh engine's cold run exactly
    r2 = eng.submit(p, max_new_tokens=6)
    eng.run_until_idle()
    fresh = _engine(cfg, params, prefill_chunk=8, page_size=8, max_len=96)
    ref = fresh.submit(p, max_new_tokens=6)
    fresh.run_until_idle()
    assert r2.tokens == ref.tokens
    # and the reuse really was capped at the completed pages
    assert eng.allocator.tokens_reused <= 8


def test_per_slot_sampling_decorrelates_streams(gpt2_setup):
    """Two identical prompts at temperature>0 in different slots draw from
    different PRNG streams (the sample_token batched-keys satellite, wired
    through the engine's per-slot request keys)."""
    cfg, params = gpt2_setup
    eng = _engine(cfg, params, num_slots=2)
    rng = np.random.default_rng(12)
    p = _prompt(rng, 5, cfg.vocab_size)
    a = eng.submit(p, max_new_tokens=12, temperature=1.0)
    b = eng.submit(p, max_new_tokens=12, temperature=1.0)
    eng.run_until_idle()
    assert a.tokens != b.tokens


def test_sampling_deterministic_per_key_and_schedule_independent(gpt2_setup):
    """The same request key yields the same sampled stream even when the
    engine's interleave differs (a competing request changes scheduling):
    step keys derive from (request key, position), not from step order."""
    cfg, params = gpt2_setup
    rng = np.random.default_rng(13)
    p = _prompt(rng, 5, cfg.vocab_size)
    key = jax.random.key(42)

    eng1 = _engine(cfg, params, num_slots=2)
    alone = eng1.submit(p, max_new_tokens=8, temperature=0.7, key=key)
    eng1.run_until_idle()

    eng2 = _engine(cfg, params, num_slots=2)
    crowded = eng2.submit(p, max_new_tokens=8, temperature=0.7, key=key)
    eng2.step()
    eng2.submit(_prompt(rng, 17, cfg.vocab_size), max_new_tokens=8)
    eng2.run_until_idle()

    assert alone.tokens == crowded.tokens


def test_sample_token_accepts_batched_keys():
    """models/decode.py satellite: a [B]-batch of typed keys (or [B, 2]
    raw) samples each row from its own stream, matching per-row calls."""
    logits = jax.random.normal(jax.random.key(0), (3, 1, 64))
    keys = jax.random.split(jax.random.key(1), 3)
    batched = sample_token(logits, keys, 1.0)
    assert batched.shape == (3,)
    per_row = [int(sample_token(logits[i:i + 1], keys[i], 1.0)[0])
               for i in range(3)]
    assert batched.tolist() == per_row
    raw = jax.random.key_data(keys)
    assert sample_token(logits, raw, 1.0).tolist() == per_row
    # single key still broadcasts one stream across the batch
    single = sample_token(logits, jax.random.key(1), 1.0)
    assert single.shape == (3,)
    # greedy path ignores keys entirely
    assert sample_token(logits, None, 0.0).shape == (3,)


# ---------------------------------------------------------------------------
# metrics
# ---------------------------------------------------------------------------


def test_metrics_summary_reports_serving_stats(gpt2_setup):
    cfg, params = gpt2_setup
    eng = _engine(cfg, params)
    rng = np.random.default_rng(14)
    reqs = [eng.submit(_prompt(rng, 4, cfg.vocab_size), max_new_tokens=4)
            for _ in range(4)]
    eng.run_until_idle()
    s = eng.metrics_summary()
    assert s["requests_finished"] == 4
    assert s["tokens_out"] == 16
    assert s["ttft_p50_ms"] > 0 and s["ttft_p99_ms"] >= s["ttft_p50_ms"]
    assert s["per_token_p50_ms"] > 0
    assert 0 < s["slot_occupancy_mean"] <= 1
    assert s["tokens_per_sec"] > 0
    assert s["compiles_decode"] == 1
    for r in reqs:
        assert r.ttft_s is not None and r.ttft_s >= 0


def test_metrics_flow_into_tracker(gpt2_setup, tmp_path):
    """Engine metrics ride the existing tracking layer (JSONLTracker)."""
    import json

    from accelerate_tpu.tracking import JSONLTracker

    cfg, params = gpt2_setup
    tracker = JSONLTracker("serve_run", logging_dir=str(tmp_path))
    eng = Engine(gpt2, cfg, params,
                 EngineConfig(num_slots=2, max_len=64, prefill_chunk=8,
                              cache_dtype=jnp.float32),
                 tracker=tracker, log_every=2)
    rng = np.random.default_rng(15)
    for _ in range(2):
        eng.submit(_prompt(rng, 4, cfg.vocab_size), max_new_tokens=6)
    eng.run_until_idle()
    tracker.finish()
    lines = [json.loads(ln) for ln in
             (tmp_path / "serve_run" / "metrics.jsonl").read_text().splitlines()]
    logged = [ln for ln in lines if ln.get("event") == "log"]
    assert logged and any("tokens_out" in ln for ln in logged)


def test_engine_prometheus_endpoint_serves_serving_series(gpt2_setup):
    """Acceptance (ISSUE 3): an engine with the exporter enabled serves a
    Prometheus exposition containing TTFT / queue-depth / tokens-per-sec
    series. Port 0 = ephemeral, so tier-1 never collides on ports."""
    import urllib.request

    cfg, params = gpt2_setup
    eng = _engine(cfg, params, metrics_port=0)
    try:
        assert eng.metrics_server is not None
        rng = np.random.default_rng(21)
        for _ in range(3):
            eng.submit(_prompt(rng, 6, cfg.vocab_size), max_new_tokens=4)
        eng.run_until_idle()
        url = f"http://127.0.0.1:{eng.metrics_server.port}/metrics"
        body = urllib.request.urlopen(url, timeout=5).read().decode()
        for series in ("serving_ttft_seconds", "serving_queue_depth",
                       "serving_tokens_per_sec",
                       "serving_tokens_out_total",
                       "serving_step_dispatch_seconds"):
            assert series in body, f"{series} missing from exposition"
        # counters carry the finished run's values, not just zeros
        assert "serving_requests_finished_total 3.0" in body
        assert "serving_tokens_out_total 12.0" in body
    finally:
        eng.close()


def test_engine_step_ticks_watchdog(gpt2_setup):
    """The serving loop arms the stall watchdog: every step() heartbeats,
    so a live engine never fires; the report machinery is exercised by a
    manual check after silence (fake silence via a huge negative tick)."""
    cfg, params = gpt2_setup
    eng = _engine(cfg, params, watchdog_timeout_s=3600.0)
    try:
        assert eng.watchdog is not None
        rng = np.random.default_rng(22)
        eng.submit(_prompt(rng, 4, cfg.vocab_size), max_new_tokens=3)
        eng.run_until_idle()
        assert eng.watchdog.check() is None  # just ticked: silent
        eng.watchdog._last_tick -= 7200.0    # simulate 2h of silence
        report = eng.watchdog.check()
        assert report is not None and report["stall_count"] == 1
    finally:
        eng.close()


def test_engine_reset_metrics_keeps_registry_and_exporter_live(gpt2_setup):
    cfg, params = gpt2_setup
    eng = _engine(cfg, params)
    rng = np.random.default_rng(23)
    eng.submit(_prompt(rng, 4, cfg.vocab_size), max_new_tokens=3)
    eng.run_until_idle()
    registry = eng.registry
    assert eng.metrics.tokens_out == 3
    eng.reset_metrics()
    assert eng.registry is registry          # same registry object
    assert eng.metrics.tokens_out == 0       # zeroed in place
    eng.submit(_prompt(rng, 4, cfg.vocab_size), max_new_tokens=2)
    eng.run_until_idle()
    assert eng.metrics.tokens_out == 2       # fresh window accumulates


# ---------------------------------------------------------------------------
# scheduler unit coverage (no model)
# ---------------------------------------------------------------------------


def _req(n=4, **kw):
    kw.setdefault("max_new_tokens", 4)
    return Request(prompt=np.zeros((n,), np.int32), **kw)


def test_scheduler_fifo_admission_and_alternation():
    now = [0.0]
    sched = Scheduler(num_slots=2, max_len=32, max_queue=8,
                      clock=lambda: now[0])
    a, b, c = _req(), _req(), _req()
    for r in (a, b, c):
        sched.submit(r)
    admitted = sched.admissions()
    assert [r.request_id for _, r in admitted] == [a.request_id, b.request_id]
    assert sched.queue_depth == 1
    # both slots prefilling: prefill then (still) prefill — no decode yet
    kind, slot = sched.next_action()
    assert kind == "prefill"
    assert not sched.note_prefill_chunk(slot, 2)   # 2 of 4 prompt tokens
    assert sched.note_prefill_chunk(slot, 2)       # prompt done -> DECODE
    assert slot.state is SlotState.DECODE
    # now one prefilling + one decoding: strict alternation
    kinds = []
    for _ in range(2):
        k, payload = sched.next_action()
        kinds.append(k)
        if k == "prefill":
            sched.note_prefill_chunk(payload, 4)
    assert sorted(kinds) == ["decode", "prefill"]


def test_prefill_is_fifo_not_slot_indexed():
    """A long prompt mid-prefill in a high-index slot must keep making
    progress while short arrivals churn through lower-index slots: prefill
    picks the earliest-admitted request, not the lowest slot (starvation
    regression — an accepted request must not see unbounded TTFT)."""
    now = [0.0]
    sched = Scheduler(num_slots=2, max_len=512, max_queue=8,
                      clock=lambda: now[0])
    early = _req(n=400, max_new_tokens=1)
    sched.submit(early)
    sched.admissions()           # early -> slot 0
    now[0] = 1.0
    late = _req(n=4, max_new_tokens=1)
    sched.submit(late)
    sched.admissions()           # late -> slot 1 (higher index, newer)
    kind, slot = sched.next_action()
    assert kind == "prefill" and slot.request is early
    # and with the order reversed (newer request in the LOWER slot) the
    # older one still wins
    sched2 = Scheduler(num_slots=2, max_len=512, max_queue=8,
                       clock=lambda: now[0])
    a, b = _req(n=400, max_new_tokens=1), _req(n=4, max_new_tokens=1)
    now[0] = 0.0
    sched2.submit(a)
    sched2.submit(b)
    sched2.admissions()          # a -> slot 0, b -> slot 1, same tick
    ((s0, _), (s1, _)) = [(s, s.request) for s in sched2.slots]
    s0.free()                    # a finishes hypothetically; slot 0 frees
    now[0] = 2.0
    c = _req(n=4, max_new_tokens=1)
    sched2.submit(c)
    sched2.admissions()          # c -> slot 0, admitted later than b
    kind, slot = sched2.next_action()
    assert kind == "prefill" and slot.request is b


def test_scheduler_cancel_and_shed_non_head_queued():
    """Removing a request from BEHIND other queued requests must not
    element-compare numpy prompts (Request is eq=False: identity only).
    Regression — the generated dataclass __eq__ raised 'truth value of an
    array is ambiguous' at any queue depth > 1."""
    now = [0.0]
    sched = Scheduler(num_slots=0, max_len=32, max_queue=8,
                      clock=lambda: now[0])
    head, mid, tail = _req(), _req(deadline_s=1.0), _req()
    for r in (head, mid, tail):
        sched.submit(r)
    assert sched.cancel(tail) and tail.status is RequestStatus.CANCELLED
    now[0] = 5.0
    shed = sched.shed_expired()
    assert shed == [mid] and mid.status is RequestStatus.EXPIRED
    assert head.status is RequestStatus.QUEUED
    assert sched.queue_depth == 1
    # equal-field requests are still distinct handles
    assert _req() != _req()


def test_scheduler_retire_frees_slot_for_queue():
    sched = Scheduler(num_slots=1, max_len=32, max_queue=8)
    first, second = _req(max_new_tokens=1), _req()
    sched.submit(first)
    sched.submit(second)
    ((slot, _),) = sched.admissions()
    sched.note_prefill_chunk(slot, 4)
    assert sched.note_token(slot, 7)   # budget 1 -> retired
    assert first.status is RequestStatus.FINISHED
    assert slot.state is SlotState.IDLE
    ((slot2, r2),) = sched.admissions()
    assert r2 is second and slot2 is slot


def test_slot_cache_shapes_and_reset():
    cache = SlotKVCache.create(num_layers=2, num_slots=3, max_len=16,
                               num_kv_heads=4, head_dim=8,
                               dtype=jnp.float32, pad_slack=4)
    assert cache.k.shape == (2, 3, 20, 4, 8)
    assert cache.rows == 20 and cache.max_len == 16
    from accelerate_tpu.serving.cache import reset_slot

    cache = cache.__class__(k=cache.k, v=cache.v,
                            lengths=cache.lengths.at[1].set(9),
                            max_len=cache.max_len, pad_slack=cache.pad_slack)
    cache = reset_slot(cache, jnp.int32(1))
    assert int(cache.lengths[1]) == 0
    # pytree round-trip (jit/donation compatibility)
    leaves, treedef = jax.tree_util.tree_flatten(cache)
    assert len(leaves) == 3
    rebuilt = jax.tree_util.tree_unflatten(treedef, leaves)
    assert rebuilt.max_len == 16 and rebuilt.pad_slack == 4


# ---------------------------------------------------------------------------
# paged-attention kernel + int8 KV pages (ISSUE 10)
# ---------------------------------------------------------------------------


def _run_trace(eng, prompts, temps, budget=6):
    reqs = [eng.submit(p, max_new_tokens=budget, temperature=t)
            for p, t in zip(prompts, temps)]
    eng.run_until_idle()
    assert all(r.status is RequestStatus.FINISHED for r in reqs)
    return [r.tokens for r in reqs]


def _kernel_setup(kernel, monkeypatch):
    """A tiny model for each of the two paged decode kernels, chosen by
    what the op sees: `older` is 16-wide heads (the page-a-grid-step
    kernel), `live` is 128-wide heads over 2 KV heads of 2 query heads
    each (the live-pages kernel the Qwen cells serve with), its groups
    cut to 2 pages so that a slot of these lengths walks several."""
    if kernel == "older":
        return llama.LlamaConfig.tiny()
    from accelerate_tpu.ops import paged_attention

    monkeypatch.setattr(paged_attention, "PAGES_PER_GROUP", 2)
    cfg = llama.LlamaConfig.tiny(hidden_size=512, num_attention_heads=4,
                                 num_key_value_heads=2)
    assert cfg.head_dim == 128
    return cfg


@pytest.mark.parametrize("kernel", ["older-gpt2", "older", "live"])
def test_paged_kernel_decode_token_exact_vs_dense(gpt2_setup, kernel,
                                                  monkeypatch):
    """The acceptance bar: decode with paged_attention=True (the Pallas
    kernel, interpret mode on CPU) is token-exact vs the dense-gather
    reference path on the same seeded trace — greedy AND sampled lanes —
    with compile counts still admit/prefill/decode = 1/1/1."""
    if kernel == "older-gpt2":
        family, (cfg, params) = gpt2, gpt2_setup
    else:
        family, cfg = llama, _kernel_setup(kernel, monkeypatch)
        params = llama.init_params(cfg, jax.random.key(0))
    rng = np.random.default_rng(7)
    prompts = [_prompt(rng, n, cfg.vocab_size) for n in (5, 17, 3)]
    # two shared-prefix prompts ride along so the kernel path is also
    # proven on prefix-cache HITS (reused pages, non-zero start lengths)
    shared = _prompt(rng, 16, cfg.vocab_size)
    prompts += [np.concatenate([shared, _prompt(rng, n, cfg.vocab_size)])
                for n in (3, 5)]
    temps = (0.0, 0.8, 0.0, 0.0, 0.6)

    def run(eng):
        # two waves: the second shared-prefix prompt arrives after the
        # first retired, so its prompt pages are cached and it admits as
        # a prefix HIT
        out = _run_trace(eng, prompts[:4], temps[:4])
        return out + _run_trace(eng, prompts[4:], temps[4:])

    dense = run(_engine(cfg, params, family=family, page_size=8,
                        paged_attention=False))
    eng = _engine(cfg, params, family=family, page_size=8,
                  paged_attention=True)
    kernel = run(eng)
    assert kernel == dense
    assert eng.metrics.prefix_hits >= 1
    assert eng.compile_stats() == {"admit": 1, "prefill": 1, "decode": 1}
    # the path counter says the kernel actually served the steps
    ctr = eng.registry.counter("serving_decode_path_total", path="kernel")
    assert ctr.value > 0


@pytest.mark.parametrize("kernel", ["older", "live"])
def test_paged_kernel_gqa_and_slot_reuse_token_exact(kernel, monkeypatch):
    """llama's GQA head groups broadcast in-kernel, and reused slots
    (more requests than slots — stale pool rows under fresh tables)
    stay exact; the older kernel under strict=error, so the
    kernel-backed decode program passes the full analysis audit with no
    findings (the live-pages kernel's interpreter, which has to act out
    copies and semaphores, calls back into Python from the program: on
    the CPU the audit would find those calls, and no kernel)."""
    cfg = _kernel_setup(kernel, monkeypatch)
    audit = {"strict": "error"} if kernel == "older" else {}
    params = llama.init_params(cfg, jax.random.key(0))
    rng = np.random.default_rng(8)
    prompts = [_prompt(rng, n, cfg.vocab_size) for n in (6, 13, 9, 4, 11)]
    temps = (0.0, 0.6, 0.0, 0.9, 0.0)
    dense = _run_trace(_engine(cfg, params, family=llama, num_slots=2,
                               page_size=8, paged_attention=False),
                       prompts, temps)
    kernel = _run_trace(_engine(cfg, params, family=llama, num_slots=2,
                                page_size=8, paged_attention=True,
                                **audit), prompts, temps)
    assert kernel == dense


@pytest.mark.parametrize("window", [None, 12], ids=["causal", "window"])
def test_live_pages_kernel_token_exact_with_dead_lanes(window):
    """128-wide heads take the live-pages kernel (one grid step a slot,
    grouped page copies out of the whole stacked pool, a layer index from
    the families' shared scan). The engine hands it lengths masked by the
    decode call's `live` lanes: this trace holds decode calls with a
    RETIRED lane (a short answer done early, its length stale) and with a
    MID-PREFILL lane (a long prompt, chunks alternating with decode
    steps, its length the rows prefilled so far), and stays token-exact
    against the dense-gather path, greedy and sampled lanes alike."""
    from accelerate_tpu.serving.scheduler import SlotState

    cfg = llama.LlamaConfig.tiny(hidden_size=256, num_attention_heads=2,
                                 num_key_value_heads=1,
                                 sliding_window=window)
    assert cfg.head_dim == 128
    params = llama.init_params(cfg, jax.random.key(1))
    rng = np.random.default_rng(12)
    first = [(_prompt(rng, 6, cfg.vocab_size), 2, 0.0),     # retires early
             (_prompt(rng, 11, cfg.vocab_size), 12, 0.7)]
    late = (_prompt(rng, 29, cfg.vocab_size), 4, 0.0)       # 4 chunks of 8

    def run(eng):
        dead = set()
        run_decode = eng._run_decode

        def watched(slots):
            lengths = np.asarray(eng.cache.lengths)
            for s in eng.scheduler.slots:
                if s not in slots and lengths[s.index] > 0:
                    # a DECODE lane is dead only while its last token
                    # waits to be committed (read one step late)
                    assert (s.state is not SlotState.DECODE
                            or s.budget_dispatched)
                    dead.add(s.state)
            run_decode(slots)

        eng._run_decode = watched
        reqs = [eng.submit(p, max_new_tokens=n, temperature=t)
                for p, n, t in first]
        for _ in range(5):
            eng.step()
        reqs.append(eng.submit(late[0], max_new_tokens=late[1],
                               temperature=late[2]))
        eng.run_until_idle()
        assert all(r.status is RequestStatus.FINISHED for r in reqs)
        return [r.tokens for r in reqs], dead

    dense, _ = run(_engine(cfg, params, family=llama, page_size=8,
                           paged_attention=False))
    eng = _engine(cfg, params, family=llama, page_size=8,
                  paged_attention=True)
    kernel, dead = run(eng)
    assert kernel == dense
    assert dead - {SlotState.DECODE} == {SlotState.IDLE, SlotState.PREFILL}
    assert eng.compile_stats() == {"admit": 1, "prefill": 1, "decode": 1}


def test_compile_flat_across_kernel_and_int8_mixes(gpt2_setup):
    """The compile-count guard extended to the new config axes: for each
    (paged_attention, kv_dtype) combination, waves of different prompt
    lengths / budgets / temperatures / prefix hits stay at exactly three
    compiled programs."""
    cfg, params = gpt2_setup
    rng = np.random.default_rng(9)
    shared = _prompt(rng, 18, cfg.vocab_size)
    # budgets/wave sizes are deliberately minimal: the guard is about
    # SHAPE variety (lengths, temps, prefix hits), and every extra
    # decode token costs real time on the interpret-mode kernel arms
    # (ISSUE 12's tier-1 budget trim: 9.3s -> measured below)
    for pa in (False, True):
        for kvd in (None, "int8"):
            eng = _engine(cfg, params, num_slots=2, max_len=48,
                          page_size=8, paged_attention=pa, kv_dtype=kvd)
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
                assert all(r.status is RequestStatus.FINISHED for r in reqs)
                assert eng.compile_stats() == {
                    "admit": 1, "prefill": 1, "decode": 1}, (pa, kvd)


def test_int8_kv_halves_bytes_gauge(gpt2_setup):
    """kv_dtype="int8" halves the per-page code bytes for the same
    num_pages: the serving_kv_bytes_in_use gauge reports (codes +
    scales), so the ratio is (D+2)/2D — exactly 0.5 on the code bytes,
    plus the documented 2/D scale overhead."""
    cfg, params = gpt2_setup
    rng = np.random.default_rng(10)
    prompts = [_prompt(rng, 9, cfg.vocab_size)]
    seen = {}
    for kvd in (None, "int8"):
        eng = _engine(cfg, params, page_size=8, kv_dtype=kvd,
                      cache_dtype=jnp.bfloat16)
        reqs = [eng.submit(p, max_new_tokens=4) for p in prompts]
        for _ in range(3):
            eng.step()  # mid-flight: pages held, gauge live
        s = eng.metrics_summary()
        assert s["pages_in_use"] > 0
        seen[kvd] = (s["kv_bytes_in_use"], s["pages_in_use"],
                     eng.cache.page_nbytes)
        eng.run_until_idle()
    (b16, p16, pb16), (b8, p8, pb8) = seen[None], seen["int8"]
    assert p16 == p8  # same trace -> same pages
    D = cfg.head_dim
    assert pb8 / pb16 == pytest.approx((D + 2) / (2 * D))
    assert b8 / b16 == pytest.approx((D + 2) / (2 * D))
    assert b16 == p16 * pb16


def test_int8_kv_logit_error_bound_and_greedy_agreement(gpt2_setup):
    """The int8 quality gate. (1) model-level logit bound: one decode
    step over an int8-round-tripped KV history stays within a small
    logit error of the bf16 history, argmax unchanged. (2) engine-level:
    a greedy trace through the int8 engine agrees with the bf16 engine
    on (at least) the vast majority of tokens."""
    from accelerate_tpu.ops.quant import kv_dequantize_rows, kv_quantize_rows

    cfg, params = gpt2_setup
    rng = np.random.default_rng(11)
    prompt = _prompt(rng, 24, cfg.vocab_size)
    caches = gpt2.init_kv_caches(cfg, 1, 32, dtype=jnp.float32)
    logits, caches = gpt2.forward(cfg, params,
                                  jnp.asarray(prompt)[None, :],
                                  kv_caches=caches)
    ck, cv, cl = caches
    ck8 = kv_dequantize_rows(*kv_quantize_rows(ck), jnp.float32)
    cv8 = kv_dequantize_rows(*kv_quantize_rows(cv), jnp.float32)
    tok = jnp.argmax(logits[:, -1], axis=-1).astype(jnp.int32)[:, None]
    pos = jnp.asarray([[len(prompt)]], jnp.int32)
    l_bf, _ = gpt2.forward(cfg, params, tok, positions=pos,
                           kv_caches=(ck, cv, cl))
    l_i8, _ = gpt2.forward(cfg, params, tok, positions=pos,
                           kv_caches=(ck8, cv8, cl))
    err = float(jnp.max(jnp.abs(l_bf - l_i8)))
    assert err < 0.5, f"int8 KV logit error {err}"
    assert int(jnp.argmax(l_bf[0, 0])) == int(jnp.argmax(l_i8[0, 0]))

    prompts = [_prompt(rng, n, cfg.vocab_size) for n in (5, 12, 8)]
    temps = (0.0, 0.0, 0.0)
    bf16 = _run_trace(_engine(cfg, params, page_size=8,
                              cache_dtype=jnp.bfloat16), prompts, temps,
                      budget=8)
    i8 = _run_trace(_engine(cfg, params, page_size=8,
                            cache_dtype=jnp.bfloat16, kv_dtype="int8"),
                    prompts, temps, budget=8)
    total = sum(len(t) for t in bf16)
    agree = sum(a == b for ta, tb in zip(bf16, i8)
                for a, b in zip(ta, tb))
    assert agree / total >= 0.9, f"greedy agreement {agree}/{total}"


def test_paged_attention_true_on_mesh_raises(gpt2_setup):
    """Explicit paged_attention=True on a meshed engine is a config
    error (the kernel is opaque to GSPMD), reported BEFORE any port or
    watchdog side effects; 'auto' quietly keeps the dense path there."""
    import jax as _jax
    from jax.sharding import Mesh

    cfg, params = gpt2_setup
    mesh = Mesh(np.array(_jax.devices()[:1]), ("model",))
    # a 1-device mesh normalizes away -> kernel fine
    eng = _engine(cfg, params, mesh=mesh, paged_attention=True)
    assert eng._use_paged_kernel
    eng.close()

    class Fake:
        size = 2

    with pytest.raises(ValueError, match="meshed engine"):
        from accelerate_tpu.serving.engine import _resolve_paged_attention

        _resolve_paged_attention(True, Fake())
    assert _resolve_paged_attention("auto", Fake()) is False
