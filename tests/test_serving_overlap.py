"""The engine reads a step's results one step late (serving/engine.py
`_Unread`, `settle`): `step()` dispatches the next program before it
fetches and commits the last one's tokens.

CPU contracts: what is served is bit-equal to the synchronous order (the
same engine with `settle()` after every `step()`) and to the family's
cache-free `generate`; an EOS finish, which the host cannot count ahead,
costs one dead lane and corrupts nothing; whatever acts on a request from
outside `step()` sees settled books; a program in flight keeps the page
table it was given; the two read counters add up."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from accelerate_tpu.models import gpt2, llama
from accelerate_tpu.serving import (
    Engine,
    EngineConfig,
    RequestStatus,
    SlotState,
)
from accelerate_tpu.serving.sanitizer import check_engine
from accelerate_tpu.telemetry.export import render_prometheus
from accelerate_tpu.telemetry.trace import (
    clear_flight_recorder,
    configure_tracing,
    flight_recorder,
)


@pytest.fixture(scope="module", autouse=True)
def _persistent_compile_cache(tmp_path_factory):
    """Every Engine() compiles the same tiny programs (see test_serving)."""
    import os

    from accelerate_tpu.utils.environment import configure_compilation_cache

    prev = os.environ.get("ACCELERATE_TPU_COMPILATION_CACHE_MIN_COMPILE_SECS")
    os.environ.setdefault(
        "ACCELERATE_TPU_COMPILATION_CACHE_MIN_COMPILE_SECS", "0")
    configure_compilation_cache(
        str(tmp_path_factory.mktemp("xla_cache")), force=True)
    yield
    if prev is None:
        os.environ.pop(
            "ACCELERATE_TPU_COMPILATION_CACHE_MIN_COMPILE_SECS", None)
    configure_compilation_cache("off", force=True)


@pytest.fixture(scope="module")
def setup():
    cfg = gpt2.GPT2Config.tiny()
    return cfg, gpt2.init_params(cfg, jax.random.key(0))


def _engine(cfg, params, family=gpt2, **overrides):
    defaults = dict(num_slots=3, max_len=64, prefill_chunk=8, page_size=8,
                    cache_dtype=jnp.float32, sanitize=True)
    defaults.update(overrides)
    return Engine(family, cfg, params, EngineConfig(**defaults))


def _prompt(rng, n, vocab):
    return rng.integers(0, vocab, (n,)).astype(np.int32)


def _reference(cfg, params, prompt, n, family=gpt2):
    out = family.generate(cfg, params, jnp.asarray(prompt)[None, :],
                          max_new_tokens=n)
    return np.asarray(out)[0, len(prompt):].tolist()


def _drive(eng, synchronous=False, after_step=None):
    while eng.step():
        if synchronous:
            eng.settle()
        if after_step is not None:
            after_step()


def _step_until_unread(eng, program=None):
    """Step until a dispatched program is unread (of that kind)."""
    for _ in range(200):
        assert eng.step()
        if eng._unread is not None and program in (None,
                                                   eng._unread.program):
            return eng._unread
    raise AssertionError("no program was left unread")


# ---------------------------------------------------------------------------
# (a) bit-equal to the synchronous order and to generate
# ---------------------------------------------------------------------------


def _mixed_trace(cfg, seed):
    """Shared-prefix and unshared prompts, greedy and sampled, prompts of
    one chunk and of several, more requests than slots, arriving between
    steps: (steps to run first, prompt, max_new_tokens, temperature)."""
    rng = np.random.default_rng(seed)
    shared = _prompt(rng, 19, cfg.vocab_size)
    trace = []
    for i in range(9):
        tail = _prompt(rng, int(rng.integers(1, 14)), cfg.vocab_size)
        prompt = np.concatenate([shared, tail]) if i % 3 == 0 else tail
        trace.append((int(rng.integers(0, 4)), prompt,
                      int(rng.integers(1, 9)), 0.8 if i % 2 else 0.0))
    return trace


def _serve(eng, trace, synchronous):
    reqs = []
    for i, (steps, prompt, n, temp) in enumerate(trace):
        for _ in range(steps):
            eng.step()
            if synchronous:
                eng.settle()
        reqs.append(eng.submit(prompt, max_new_tokens=n, temperature=temp,
                               key=jax.random.key(100 + i)))
    _drive(eng, synchronous)
    return reqs


@pytest.mark.parametrize("path", ["dense", "kernel"])
def test_mixed_trace_is_bit_equal_to_the_synchronous_order(setup, path):
    cfg, params = setup
    trace = _mixed_trace(cfg, seed=3)
    served = {}
    for order in ("late", "synchronous"):
        eng = _engine(cfg, params, paged_attention=path == "kernel")
        served[order] = _serve(eng, trace, order == "synchronous"), eng
    (late, eng), (sync, sync_eng) = served["late"], served["synchronous"]
    for (_, prompt, n, temp), a, b in zip(trace, late, sync):
        assert a.status is b.status is RequestStatus.FINISHED
        assert a.tokens == b.tokens and len(a.tokens) == n
        assert a.logprobs == b.logprobs          # bit-equal, not close
        if temp == 0.0:
            assert a.tokens == _reference(cfg, params, prompt, n)
    # the same programs ran, one compile each; only the reads moved
    assert eng.compile_stats() == sync_eng.compile_stats() == {
        "admit": 1, "prefill": 1, "decode": 1}
    assert eng.metrics.prefill_chunks == sync_eng.metrics.prefill_chunks
    assert eng.metrics.reads_overlapped > 0 == sync_eng.metrics.reads_overlapped
    assert eng._unread is None and sync_eng._unread is None
    assert eng.metrics.prefix_hits > 0           # the shared prefix was reused


def test_a_token_reaches_the_request_one_step_late(setup):
    """`Engine.step()`'s contract: the step that computes a token
    dispatches its program; the NEXT step commits it, after dispatching its
    own. `run_until_idle` and `stream` end with every token committed."""
    cfg, params = setup
    eng = _engine(cfg, params)
    prompt = _prompt(np.random.default_rng(0), 5, cfg.vocab_size)
    r = eng.submit(prompt, max_new_tokens=3)
    assert eng.step()                            # the prompt's only chunk
    assert r.tokens == [] and eng._unread.program == "prefill"
    assert eng.metrics.prefill_chunks == 1
    assert eng.step()                            # decode 1; commits token 0
    assert len(r.tokens) == 1 and eng._unread.program == "decode"
    assert eng.metrics.decode_steps == 1
    assert eng.step()                            # decode 2; commits token 1
    assert len(r.tokens) == 2 and not r.done
    # the budget's last token is on its way: nothing left to dispatch, the
    # step commits it and still reports work
    assert eng.step()
    assert len(r.tokens) == 3 and r.done and eng._unread is None
    assert eng.metrics.decode_steps == 2
    assert not eng.step()
    assert r.tokens == _reference(cfg, params, prompt, 3)

    again = eng.submit(prompt, max_new_tokens=4)
    assert list(eng.stream(again)) == again.tokens == _reference(
        cfg, params, prompt, 4)
    assert eng._unread is None


# ---------------------------------------------------------------------------
# (b) an EOS finish: the one the host cannot count ahead
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("eos_at", [0, 2], ids=["first-token", "third-token"])
def test_eos_finish_rides_one_dead_lane_and_corrupts_nothing(setup, eos_at):
    cfg, params = setup
    rng = np.random.default_rng(7)
    prompt = _prompt(rng, 11, cfg.vocab_size)
    # a sampled stream (greedy repeats itself at this size), served in the
    # synchronous order without an EOS: what the tokens must be
    sampled = dict(temperature=0.9, key=jax.random.key(21))
    whole = _engine(cfg, params)
    full = whole.submit(prompt, max_new_tokens=8, **sampled)
    _drive(whole, synchronous=True)
    full = full.tokens
    eos = full[eos_at]
    assert eos not in full[:eos_at]
    # a pool with no page to spare: the next owner must take the freed ones
    eng = _engine(cfg, params, num_slots=2, max_len=32, num_pages=5,
                  prefix_cache=False)
    a = eng.submit(prompt, max_new_tokens=8, eos_token_id=eos, **sampled)
    committed = []
    note = eng.scheduler.note_token

    def counting(slot, token, **kw):
        committed.append((slot.request, token))
        return note(slot, token, **kw)

    eng.scheduler.note_token = counting
    while not a.done:
        assert eng.step()
        check_engine(eng)
    assert a.status is RequestStatus.FINISHED
    assert a.tokens == full[:eos_at + 1] and len(a.logprobs) == eos_at + 1
    # the step that committed the EOS had already dispatched one more
    # decode step with the lane live: it is owed to nobody now
    dead = eng._unread
    assert dead is not None and dead.program == "decode"
    (slot, owner, _), = dead.lanes
    assert owner is a and slot.request is None and slot.unread == 0
    assert slot.state is SlotState.IDLE
    freed = set(eng.allocator.pool._free)
    # the freed pages' next owner reads what IT wrote
    other = _prompt(rng, 13, cfg.vocab_size)
    b = eng.submit(other, max_new_tokens=6)
    assert b.admitted_at is not None
    b_slot = next(s for s in eng.scheduler.slots if s.request is b)
    assert set(b_slot.alloc.pages) & freed
    _drive(eng, after_step=lambda: check_engine(eng))
    assert b.tokens == _reference(cfg, params, other, 6)
    # the dead lane's token was discarded: every commit went to a live owner
    assert [r for r, _ in committed].count(a) == eos_at + 1
    assert len(committed) == len(a.tokens) + len(b.tokens)
    assert a.tokens == full[:eos_at + 1]


# ---------------------------------------------------------------------------
# (c) the shortest budgets
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("budget", [1, 2])
def test_shortest_budgets_dispatch_no_step_too_many(setup, budget):
    """The host counts a request's tokens ahead: a lane whose last token
    is on its way rides no further decode step."""
    cfg, params = setup
    eng = _engine(cfg, params)
    rng = np.random.default_rng(budget)
    prompts = [_prompt(rng, n, cfg.vocab_size) for n in (4, 12, 9, 3)]
    reqs = [eng.submit(p, max_new_tokens=budget) for p in prompts]
    _drive(eng)
    for p, r in zip(prompts, reqs):
        assert r.status is RequestStatus.FINISHED
        assert r.tokens == _reference(cfg, params, p, budget)
    lanes = sum(len(r.tokens) - 1 for r in reqs)
    assert eng.metrics.decode_steps <= lanes     # 0 when the budget is 1
    assert eng.metrics.tokens_out == budget * len(reqs)


# ---------------------------------------------------------------------------
# (d) callers outside step() see settled books
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("how", ["cancel", "finish"])
def test_cancel_and_finish_settle_the_unread_program_first(setup, how):
    cfg, params = setup
    eng = _engine(cfg, params)
    rng = np.random.default_rng(11)
    pa, pb = _prompt(rng, 6, cfg.vocab_size), _prompt(rng, 10, cfg.vocab_size)
    a = eng.submit(pa, max_new_tokens=12)
    b = eng.submit(pb, max_new_tokens=7)
    for _ in range(4):
        _step_until_unread(eng, "decode")
    held = len(a.tokens)
    assert any(s.request is a and s.unread == 1 for s in eng.scheduler.slots)
    assert getattr(eng, how)(a)
    # the token that was on its way was committed before the request ended
    assert eng._unread is None and len(a.tokens) == held + 1
    assert a.status is (RequestStatus.CANCELLED if how == "cancel"
                        else RequestStatus.FINISHED)
    assert a.tokens == _reference(cfg, params, pa, 12)[:held + 1]
    assert len(a.logprobs) == len(a.token_times) == held + 1
    check_engine(eng)
    _drive(eng)
    assert b.tokens == _reference(cfg, params, pb, 7)
    assert len(a.tokens) == held + 1


def test_a_token_on_its_way_finishes_the_request_before_a_cancel(setup):
    cfg, params = setup
    eng = _engine(cfg, params)
    prompt = _prompt(np.random.default_rng(5), 4, cfg.vocab_size)
    r = eng.submit(prompt, max_new_tokens=1)
    assert eng.step() and r.tokens == [] and not r.done
    assert not eng.cancel(r)                     # it had finished already
    assert r.status is RequestStatus.FINISHED
    assert r.tokens == _reference(cfg, params, prompt, 1)


def test_counter_reads_and_metric_resets_settle_first(setup):
    cfg, params = setup
    eng = _engine(cfg, params)
    r = eng.submit(_prompt(np.random.default_rng(2), 5, cfg.vocab_size),
                   max_new_tokens=6)
    _step_until_unread(eng, "decode")
    held = len(r.tokens)
    eng.reset_metrics()
    assert eng._unread is None and len(r.tokens) == held + 1
    assert eng.metrics.reads_overlapped == eng.metrics.reads_settled == 0
    _drive(eng)
    assert r.done and len(r.tokens) == 6


# ---------------------------------------------------------------------------
# (e) a program in flight keeps the table it was given
# ---------------------------------------------------------------------------


def test_in_flight_program_does_not_see_a_later_table_write(setup):
    cfg, params = setup
    eng = _engine(cfg, params)
    rng = np.random.default_rng(13)
    prompts = [_prompt(rng, n, cfg.vocab_size) for n in (17, 6)]
    given = []
    for name in ("_decode_p", "_prefill_p"):
        program = getattr(eng, name)

        def spy(*args, _program=program, _name=name):
            given.append((_name, args[6]))
            return _program(*args)

        setattr(eng, name, spy)
    reqs = [eng.submit(p, max_new_tokens=6) for p in prompts]
    trash = eng.cache.trash_page
    while eng.step():
        # right after the dispatch: what an admission or a release would do
        kept = eng._table.copy()
        eng._table[:] = trash
        if eng._unread is not None:
            jax.block_until_ready(eng._unread.out)
        eng._table[:] = kept
    for p, r in zip(prompts, reqs):
        assert r.tokens == _reference(cfg, params, p, 6)
    assert {n for n, _ in given} == {"_decode_p", "_prefill_p"}
    assert not any(np.shares_memory(t, eng._table) for _, t in given)


# ---------------------------------------------------------------------------
# (f) the two counters and the span attribute
# ---------------------------------------------------------------------------


def test_read_counters_add_up_to_the_dispatches_that_owe_a_token(setup):
    cfg, params = setup
    eng = _engine(cfg, params)
    for _ in range(1000):                        # an idle engine records nothing
        assert not eng.step()
    assert eng.metrics.reads_overlapped == eng.metrics.reads_settled == 0
    assert eng.metrics.occupancy.count == 0
    configure_tracing(enabled=True, annotate=False)
    clear_flight_recorder()
    try:
        reqs = _serve(eng, _mixed_trace(cfg, seed=5), synchronous=False)
        reads = [e for e in flight_recorder()
                 if e["name"] == "serving.host_read"]
    finally:
        configure_tracing(enabled=False)
        clear_flight_recorder()
    m = eng.metrics
    # every decode step and every prompt's LAST chunk owes the host tokens
    assert m.reads_overlapped + m.reads_settled == m.decode_steps + len(reqs)
    assert m.reads_overlapped > 4 * m.reads_settled
    summary = eng.metrics_summary()
    assert summary["reads_overlapped"] == m.reads_overlapped
    assert summary["reads_settled"] == m.reads_settled
    behind = [e["attrs"]["behind"] for e in reads]
    assert set(behind) <= {"decode", "prefill", "none"}
    assert len(behind) - behind.count("none") == m.reads_overlapped
    assert behind.count("none") == m.reads_settled
    assert {e["attrs"]["program"] for e in reads} == {"decode", "prefill"}
    text = render_prometheus(eng.registry)
    assert "serving_reads_overlapped_total" in text
    assert "serving_reads_settled_total" in text


# ---------------------------------------------------------------------------
# (g) the speculative engine keeps the synchronous order
# ---------------------------------------------------------------------------


def test_speculative_engine_reads_every_result_in_its_own_step(setup):
    cfg, params = setup
    draft = gpt2.init_params(cfg, jax.random.key(9))
    eng = _engine(cfg, params, speculative=(gpt2, cfg, draft), draft_k=3)
    rng = np.random.default_rng(17)
    prompts = [_prompt(rng, n, cfg.vocab_size) for n in (5, 13)]
    reqs = [eng.submit(p, max_new_tokens=7) for p in prompts]
    while eng.step():
        assert eng._unread is None
        assert all(s.unread == 0 for s in eng.scheduler.slots)
    for p, r in zip(prompts, reqs):
        assert r.tokens == _reference(cfg, params, p, 7)
    assert eng.metrics.reads_overlapped == 0
    assert eng.metrics.reads_settled == len(reqs)  # the first tokens


def test_llama_kernel_engine_is_bit_equal_late_and_synchronous():
    """The other family and the live-pages kernel's shapes (128-wide
    heads), sampled lanes included."""
    cfg = llama.LlamaConfig.tiny(hidden_size=256, num_attention_heads=2,
                                 num_key_value_heads=1)
    params = llama.init_params(cfg, jax.random.key(1))
    trace = _mixed_trace(cfg, seed=9)[:5]
    out = []
    for synchronous in (False, True):
        eng = _engine(cfg, params, family=llama, paged_attention=True)
        out.append(_serve(eng, trace, synchronous))
    for a, b in zip(*out):
        assert a.tokens == b.tokens and a.logprobs == b.logprobs
        assert a.status is b.status is RequestStatus.FINISHED
