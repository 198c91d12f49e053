"""End-to-end request tracing (ISSUE 8 tentpole a): one trace per
request linking queue-wait -> admit -> prefill chunk(s) -> decode
lifetime under a shared trace id, machine-readable shed reasons on every
terminal path, engine-level live introspection, and the disabled-path
guarantees (compile counts flat, no spans when sampling says no).

Scheduler-side shed-code tests are model-free; the engine section drives
a tiny gpt2 engine on CPU (same shapes as tests/test_server.py so the
in-process jit cache is shared)."""

import numpy as np
import pytest

from accelerate_tpu.serving.scheduler import (
    Request,
    RequestStatus,
    Scheduler,
    TenantSpec,
)
from accelerate_tpu.telemetry import (
    clear_flight_recorder,
    configure_tracing,
    export_chrome_trace,
    flight_recorder,
    trace_events,
)


def _req(n=4, tenant="default", max_new=4, slo=None, **kw):
    return Request(prompt=np.arange(1, n + 1, dtype=np.int32),
                   max_new_tokens=max_new, tenant=tenant,
                   slo_ttft_s=slo, **kw)


@pytest.fixture(autouse=True)
def _tracing_reset():
    configure_tracing(enabled=False, sample_rates={},
                      default_sample_rate=1.0)
    clear_flight_recorder()
    yield
    configure_tracing(enabled=False, sample_rates={},
                      default_sample_rate=1.0)
    clear_flight_recorder()


# ---------------------------------------------------------------------------
# machine-readable shed reasons (model-free)
# ---------------------------------------------------------------------------


class TestShedCodes:
    def test_too_long_and_queue_full(self):
        s = Scheduler(1, 16, max_queue=1)
        long = s.submit(_req(n=20, max_new=20))
        assert long.shed_code == "too_long"
        s.submit(_req())
        bounced = s.submit(_req())
        assert bounced.status is RequestStatus.REJECTED
        assert bounced.shed_code == "queue_full"

    def test_tenant_queue_full(self):
        s = Scheduler(1, 64, max_queue=100,
                      tenants=[TenantSpec("small", max_queue=1)])
        s.submit(_req(tenant="small"))
        r = s.submit(_req(tenant="small"))
        assert r.shed_code == "tenant_queue_full"

    def test_deadline_and_certain_miss(self):
        clock = [0.0]
        s = Scheduler(1, 64, clock=lambda: clock[0],
                      tenants=[TenantSpec("t", ttft_slo_s=0.5)])
        s.note_step_time(0.1)
        dl = s.submit(_req(tenant="t", deadline_s=0.1, slo=100.0))
        miss = s.submit(_req(32, tenant="t"))
        clock[0] = 1.0
        shed = s.shed_expired()
        assert set(shed) == {dl, miss}
        assert dl.shed_code == "deadline"
        assert miss.shed_code == "certain_miss"

    def test_pressure_victim(self):
        clock = [0.0]
        s = Scheduler(1, 64, max_queue=2, clock=lambda: clock[0],
                      tenants=[TenantSpec("t", ttft_slo_s=0.2)])
        s.note_step_time(0.05)
        r1 = s.submit(_req(32, tenant="t", max_new=16))
        r2 = s.submit(_req(32, tenant="t", max_new=16))
        s.submit(_req(2, tenant="t", max_new=2))
        victim = r1 if r1.status is RequestStatus.EXPIRED else r2
        assert victim.shed_code == "pressure_victim"

    def test_displaced_by_tier(self):
        s = Scheduler(1, 64, max_queue=2,
                      tenants=[TenantSpec("gold", priority=0),
                               TenantSpec("bronze", priority=1)])
        s.submit(_req(tenant="bronze"))
        b2 = s.submit(_req(tenant="bronze"))
        s.submit(_req(tenant="gold"))
        assert b2.shed_code == "displaced_by_tier"

    def test_debug_state_shape(self):
        s = Scheduler(2, 64, tenants=[TenantSpec("gold", priority=0,
                                                 weight=4, ttft_slo_s=0.5)])
        s.submit(_req(tenant="gold"))
        s.note_step_time(0.01)
        state = s.debug_state()
        assert state["queue_depth"] == 1
        assert state["step_time_ema_s"] == pytest.approx(0.01)
        gold = state["tenants"]["gold"]
        assert gold["priority"] == 0 and gold["weight"] == 4
        assert gold["queue_depth"] == 1
        assert "drr_deficit" in gold
        assert "gold" in state["tiers"]["0"]
        import json

        json.dumps(state)  # must be JSON-safe as-is


# ---------------------------------------------------------------------------
# engine-level request traces (tiny gpt2, CPU)
# ---------------------------------------------------------------------------


@pytest.fixture(scope="module")
def gpt2_setup():
    import jax

    from accelerate_tpu.models import gpt2

    cfg = gpt2.GPT2Config.tiny()
    params = gpt2.init_params(cfg, jax.random.key(0))
    return gpt2, cfg, params


def _make_engine(gpt2_setup, **overrides):
    import jax.numpy as jnp

    from accelerate_tpu.serving import Engine, EngineConfig

    family, cfg, params = gpt2_setup
    defaults = dict(num_slots=2, max_len=64, prefill_chunk=8,
                    cache_dtype=jnp.float32)
    defaults.update(overrides)
    return Engine(family, cfg, params, EngineConfig(**defaults))


class TestEngineRequestTrace:
    def test_full_span_chain_shares_the_trace(self, gpt2_setup):
        """Acceptance: one request yields linked queue-wait -> admit ->
        prefill-chunk(s) -> decode spans under ONE trace id, all
        parented on the request's root span, exported to chrome trace."""
        configure_tracing(enabled=True, annotate=False)
        eng = _make_engine(gpt2_setup)
        r = eng.submit(np.arange(1, 12, dtype=np.int32), max_new_tokens=4)
        assert r.trace_sampled and len(r.trace_id) == 32
        toks = list(eng.stream(r))
        assert len(toks) == 4
        events = trace_events(r.trace_id)
        names = [e["name"] for e in events]
        assert "serving.queue_wait" in names
        assert "serving.admit" in names
        assert names.count("serving.prefill") == 2  # 11 tokens / chunk 8
        assert "serving.decode_lifetime" in names
        assert "serving.request" in names
        root = next(e for e in events if e["name"] == "serving.request")
        assert root["span_id"] == r.span_id
        assert root["attrs"]["status"] == "finished"
        assert root["attrs"]["tokens"] == 4
        children = [e for e in events if e["name"] != "serving.request"]
        assert all(e["trace_id"] == r.trace_id for e in events)
        assert all(e["parent_id"] == r.span_id for e in children)
        doc = export_chrome_trace(trace_id=r.trace_id)
        assert {e["name"] for e in doc["traceEvents"]} == set(names)
        # the shared decode-step spans LINK this request's trace
        decode_steps = [e for e in flight_recorder()
                        if e["name"] == "serving.decode"]
        assert any(r.trace_id in e.get("links", []) for e in decode_steps)

    def test_compile_counts_flat_with_tracing_on(self, gpt2_setup):
        configure_tracing(enabled=True, annotate=False)
        eng = _make_engine(gpt2_setup)
        for n in (3, 11, 7):
            r = eng.submit(np.arange(1, n + 1, dtype=np.int32),
                           max_new_tokens=3, trace_id=None)
            list(eng.stream(r))
        assert eng.compile_stats() == {"admit": 1, "prefill": 1,
                                       "decode": 1}

    def test_cancelled_request_closes_its_span_with_reason(self, gpt2_setup):
        """Satellite: a cancelled request still closes its root span,
        carrying the terminal status."""
        configure_tracing(enabled=True, annotate=False)
        eng = _make_engine(gpt2_setup)
        r = eng.submit(np.arange(1, 10, dtype=np.int32), max_new_tokens=16)
        eng.step()
        assert eng.cancel(r)
        root = next(e for e in trace_events(r.trace_id)
                    if e["name"] == "serving.request")
        assert root["attrs"]["status"] == "cancelled"

    def test_shed_request_closes_its_span_with_shed_code(self, gpt2_setup):
        """Satellite: a deadline-shed queued request's trace closes with
        the machine-readable shed reason."""
        configure_tracing(enabled=True, annotate=False)
        eng = _make_engine(gpt2_setup, num_slots=1)
        blocker = eng.submit(np.arange(1, 10, dtype=np.int32),
                             max_new_tokens=32)
        doomed = eng.submit(np.arange(1, 6, dtype=np.int32),
                            max_new_tokens=4, deadline_s=0.0)
        eng.step()  # shed_expired runs: the queued request's deadline lapsed
        assert doomed.status is RequestStatus.EXPIRED
        root = next(e for e in trace_events(doomed.trace_id)
                    if e["name"] == "serving.request")
        assert root["attrs"]["status"] == "expired"
        assert root["attrs"]["shed_code"] == "deadline"
        assert "reason" in root["attrs"]
        eng.cancel(blocker)

    def test_sampling_zero_records_no_spans_but_keeps_the_id(self,
                                                            gpt2_setup):
        """Satellite: rate 0 -> zero spans, but a supplied trace id (the
        x-request-id the server already returned) is preserved."""
        configure_tracing(enabled=True, annotate=False,
                          default_sample_rate=0.0)
        eng = _make_engine(gpt2_setup)
        r = eng.submit(np.arange(1, 6, dtype=np.int32), max_new_tokens=2,
                       trace_id="ee" * 16)
        list(eng.stream(r))
        assert r.trace_id == "ee" * 16 and not r.trace_sampled
        assert trace_events("ee" * 16) == []

    def test_sampling_zero_still_mints_an_engine_id(self, gpt2_setup):
        """Review regression: the id is minted whenever tracing is ON —
        sampling only gates spans. A rate-0 direct engine caller still
        sees its request id in /debug views and exemplars."""
        configure_tracing(enabled=True, annotate=False,
                          default_sample_rate=0.0)
        eng = _make_engine(gpt2_setup)
        r = eng.submit(np.arange(1, 6, dtype=np.int32), max_new_tokens=2)
        list(eng.stream(r))
        assert r.trace_id is not None and len(r.trace_id) == 32
        assert not r.trace_sampled
        assert trace_events(r.trace_id) == []

    def test_tracing_disabled_requests_carry_no_trace(self, gpt2_setup):
        eng = _make_engine(gpt2_setup)
        r = eng.submit(np.arange(1, 6, dtype=np.int32), max_new_tokens=2)
        list(eng.stream(r))
        assert r.trace_id is None and not r.trace_sampled
        assert flight_recorder() == []

    def test_ttft_exemplar_carries_the_trace_id(self, gpt2_setup):
        configure_tracing(enabled=True, annotate=False)
        eng = _make_engine(gpt2_setup)
        r = eng.submit(np.arange(1, 6, dtype=np.int32), max_new_tokens=2)
        list(eng.stream(r))
        exemplars = eng.metrics.ttft_s.exemplars()
        assert any(label == str(r.trace_id)
                   for _, label, _ in exemplars.values())


# ---------------------------------------------------------------------------
# the engine's host pass in phase spans (ISSUE 24): structure, not durations
# ---------------------------------------------------------------------------

DECODE_STEP = ["serving.schedule", "serving.stage_inputs", "serving.decode",
               "serving.host_read", "serving.commit", "serving.bookkeeping"]


def _by_start(events=None):
    events = flight_recorder() if events is None else events
    return sorted(events, key=lambda e: (e["start_ns"], -e["dur_ns"]))


def _inside(inner, outer):
    return (outer["start_ns"] <= inner["start_ns"]
            and inner["start_ns"] + inner["dur_ns"]
            <= outer["start_ns"] + outer["dur_ns"])


class TestEnginePhaseSpans:
    def test_every_decode_step_is_tiled_by_its_phases_in_order(
            self, gpt2_setup):
        configure_tracing(enabled=True, annotate=False)
        eng = _make_engine(gpt2_setup)
        r = eng.submit(np.arange(1, 6, dtype=np.int32), max_new_tokens=5)
        list(eng.stream(r))
        # the engine's own pass: live spans outside every request's trace,
        # top level only (the allocator's spans nest inside a phase)
        phases = [e for e in _by_start()
                  if e["name"] in DECODE_STEP + ["serving.prefill"]]
        starts = [i for i, e in enumerate(phases)
                  if e["name"] == "serving.schedule"
                  and e["attrs"]["action"] == "decode"]
        assert len(starts) == 4  # the first token comes from the prefill
        for i in starts:
            step = phases[i:i + len(DECODE_STEP)]
            assert [e["name"] for e in step] == DECODE_STEP
            assert len({e["thread"] for e in step}) == 1
            for a, b in zip(step, step[1:]):
                assert a["start_ns"] + a["dur_ns"] <= b["start_ns"]
        assert [e["name"] for e in phases].count("serving.decode") == 4
        host_reads = [e for e in phases if e["name"] == "serving.host_read"]
        assert [e["attrs"]["program"] for e in host_reads] == (
            ["prefill"] + ["decode"] * 4)
        commits = [e for e in phases if e["name"] == "serving.commit"]
        assert sum(e["attrs"]["tokens"] for e in commits) == 5
        assert sum(e["attrs"]["finished"] for e in commits) == 1
        # a phase belongs to no trace: nothing of it in the per-trace index
        assert all(e["trace_id"] == 0 for e in phases
                   if e["name"] not in ("serving.decode", "serving.prefill"))

    def test_admit_pending_contains_the_allocation_and_the_admit_dispatch(
            self, gpt2_setup):
        configure_tracing(enabled=True, annotate=False)
        eng = _make_engine(gpt2_setup, num_slots=1)
        first = eng.submit(np.arange(1, 6, dtype=np.int32), max_new_tokens=2)
        second = eng.submit(np.arange(2, 9, dtype=np.int32), max_new_tokens=2)
        eng.run_until_idle()
        events = _by_start()
        pendings = [e for e in events
                    if e["name"] == "serving.admit_pending"
                    and e["attrs"]["admitted"] == 1]
        assert len(pendings) == 2
        submit_of_first = next(e for e in events
                               if e["name"] == "serving.submit"
                               and e["trace_id"] == first.trace_id)
        # the first is admitted inside its own submit, the second by a step
        assert _inside(pendings[0], submit_of_first)
        assert submit_of_first["attrs"] == {"prompt_len": 5, "admitted": True,
                                            "shed": 0}
        submit_of_second = next(e for e in events
                                if e["name"] == "serving.submit"
                                and e["trace_id"] == second.trace_id)
        assert submit_of_second["attrs"]["admitted"] is False
        assert not _inside(pendings[1], submit_of_second)
        for pending, req in zip(pendings, (first, second)):
            inside = [e for e in events
                      if e is not pending and _inside(e, pending)]
            allocate = [e for e in inside
                        if e["name"] == "serving.kv.allocate"]
            admit = [e for e in inside if e["name"] == "serving.admit"]
            assert len(allocate) == 1 and len(admit) == 1
            assert admit[0]["trace_id"] == req.trace_id
            assert allocate[0]["parent_id"] == pending["span_id"]
            assert allocate[0]["attrs"]["pages"] > 0
            assert allocate[0]["attrs"]["evicted"] == 0
            assert pending["trace_id"] == 0 and allocate[0]["trace_id"] == 0
        release = [e for e in events if e["name"] == "serving.kv.release"]
        commits = [e for e in events if e["name"] == "serving.commit"]
        assert len(release) == 2
        assert all(any(_inside(r, c) for c in commits) for r in release)

    def test_submit_joins_the_requests_trace_only_when_sampled(
            self, gpt2_setup):
        configure_tracing(enabled=True, annotate=False,
                          sample_rates={"quiet": 0.0})
        eng = _make_engine(gpt2_setup)
        loud = eng.submit(np.arange(1, 6, dtype=np.int32), max_new_tokens=2)
        quiet = eng.submit(np.arange(1, 6, dtype=np.int32), max_new_tokens=2,
                           tenant="quiet", trace_id="ab" * 16)
        eng.run_until_idle()
        mine = [e for e in trace_events(loud.trace_id)
                if e["name"] == "serving.submit"]
        assert len(mine) == 1 and mine[0]["parent_id"] == loud.span_id
        assert trace_events("ab" * 16) == []
        submits = [e for e in flight_recorder()
                   if e["name"] == "serving.submit"]
        assert len(submits) == 2
        other = next(e for e in submits if e is not mine[0])
        assert other["trace_id"] not in ("ab" * 16, loud.trace_id)
        assert not quiet.trace_sampled and other["parent_id"] == 0

    def test_speculative_reads_lie_under_host_read(self, gpt2_setup):
        configure_tracing(enabled=True, annotate=False)
        family, cfg, params = gpt2_setup
        eng = _make_engine(gpt2_setup, speculative=(family, cfg, params),
                           draft_k=3)
        r = eng.submit(np.arange(1, 6, dtype=np.int32), max_new_tokens=6)
        list(eng.stream(r))
        events = _by_start()
        names = [e["name"] for e in events]
        verifies = [e for e in events if e["name"] == "serving.verify"]
        assert verifies and names.count("serving.draft") == len(verifies)
        reads = [e for e in events if e["name"] == "serving.host_read"
                 and e["attrs"]["program"] == "verify"]
        assert len(reads) == len(verifies)
        for verify, read in zip(verifies, reads):
            assert verify["start_ns"] + verify["dur_ns"] <= read["start_ns"]
        commits = [e for e in events if e["name"] == "serving.commit"]
        assert sum(e["attrs"]["tokens"] for e in commits) == len(r.tokens)

    def test_an_idle_engine_records_nothing(self, gpt2_setup):
        """The benchmark (and any server loop) polls `step()` every half
        millisecond: an idle engine must not flood the recorder."""
        configure_tracing(enabled=True, annotate=False)
        eng = _make_engine(gpt2_setup)
        r = eng.submit(np.arange(1, 6, dtype=np.int32), max_new_tokens=2)
        list(eng.stream(r))
        before = len(flight_recorder())
        assert before > 0
        for _ in range(1000):
            assert eng.step() is False
        assert len(flight_recorder()) == before


class TestEngineIntrospection:
    def test_debug_views_reflect_live_state(self, gpt2_setup):
        eng = _make_engine(gpt2_setup, num_slots=1)
        running = eng.submit(np.arange(1, 10, dtype=np.int32),
                             max_new_tokens=32)
        queued = eng.submit(np.arange(1, 5, dtype=np.int32),
                            max_new_tokens=4)
        eng.step()
        dbg = eng.debug_requests()
        assert [q["request_id"] for q in dbg["queued"]] == [
            queued.request_id]
        assert [q["request_id"] for q in dbg["running"]] == [
            running.request_id]
        assert dbg["running"][0]["tenant"] == "default"
        assert dbg["running"][0]["age_s"] >= 0
        slots = eng.debug_slots()
        assert slots[0]["request_id"] == running.request_id
        assert slots[0]["state"] in ("prefill", "decode")
        assert slots[0]["pages"] > 0
        pages = eng.debug_pages()
        assert pages["pages_in_use"] > 0
        assert pages["page_size"] == eng.engine_config.page_size
        sched = eng.debug_scheduler()
        assert sched["queue_depth"] == 1 and sched["live_slots"] == 1
        import json

        json.dumps({"r": dbg, "s": slots, "p": pages, "c": sched})
        eng.cancel(running)
        eng.cancel(queued)
        eng.run_until_idle()
        dbg = eng.debug_requests()
        assert dbg["queued"] == [] and dbg["running"] == []

    def test_incident_dumps_bundle_everything(self, gpt2_setup):
        eng = _make_engine(gpt2_setup)
        dumps = eng.incident_dumps()
        assert set(dumps) == {"requests", "slots", "pages", "scheduler",
                              "compile_stats", "cost_table"}

    def test_watchdog_stall_writes_engine_bundle(self, gpt2_setup,
                                                 tmp_path):
        """Acceptance: an induced stall on a live engine writes a bundle
        carrying the engine's scheduler/page dumps and metrics."""
        import json
        import os

        from accelerate_tpu.telemetry.watchdog import StallWatchdog

        eng = _make_engine(gpt2_setup)
        r = eng.submit(np.arange(1, 6, dtype=np.int32), max_new_tokens=2)
        list(eng.stream(r))
        now = [0.0]
        wd = StallWatchdog(5.0, clock=lambda: now[0],
                           incident_dir=str(tmp_path),
                           registry=eng.registry, dumps=eng.incident_dumps)
        now[0] = 6.0
        report = wd.check()
        path = report["bundle_path"]
        files = set(os.listdir(path))
        assert {"manifest.json", "report.json", "stacks.txt", "trace.json",
                "metrics.json", "metrics.prom", "scheduler.json",
                "pages.json", "requests.json", "slots.json",
                "compile_stats.json"} <= files
        metrics = json.load(open(os.path.join(path, "metrics.json")))
        key = "serving_requests_finished_total"
        assert metrics["counters"][key] == 1.0
        compiles = json.load(
            open(os.path.join(path, "compile_stats.json")))
        assert compiles == {"admit": 1, "prefill": 1, "decode": 1}
