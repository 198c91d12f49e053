"""Pod-scale serving (serving/pod): SPMD mesh sharding + MPMD
disaggregation.

CPU contracts on the virtual mesh: the mesh-sharded engine and the
disaggregated prefill->decode pod are byte-identical to the
single-device engine on the same seeded trace; per-role compile counts
stay flat (incl. the extract/install shipping programs); backpressure
stalls the router, never a prefill worker; the HTTP front door runs
unchanged over a pod engine; and the forced-host-device subprocess
harness proves the same exactness with the WHOLE backend at N=2 and N=4
devices (the ISSUE 9 acceptance shape)."""

import dataclasses
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from accelerate_tpu.analysis.contracts import (
    pod_program_contracts,
    serving_program_contracts,
)
from accelerate_tpu.models import gpt2, llama
from accelerate_tpu.serving import Engine, EngineConfig, RequestStatus
from accelerate_tpu.serving.pod import (
    KVPageShipment,
    PodConfig,
    PodEngine,
    cache_state_shardings,
    shard_params,
    sharded_engine,
    tensor_mesh,
)


@pytest.fixture(scope="module", autouse=True)
def _persistent_compile_cache(tmp_path_factory):
    """Every engine/pod here compiles the same tiny programs; the
    persistent compilation cache turns the repeats into deserializes
    (same fixture as tests/test_serving.py — fresh tmp dir, so the
    sub-second-entry segfault documented in conftest.py can't poison
    later runs)."""
    from accelerate_tpu.utils.environment import configure_compilation_cache

    cache_dir = str(tmp_path_factory.mktemp("xla_cache"))
    prev = {k: os.environ.get(k)
            for k in ("ACCELERATE_TPU_COMPILATION_CACHE_MIN_COMPILE_SECS",
                      "ACCELERATE_TPU_COMPILATION_CACHE")}
    os.environ.setdefault(
        "ACCELERATE_TPU_COMPILATION_CACHE_MIN_COMPILE_SECS", "0")
    # exported: the forced-device children (pod_exactness_script at N=2
    # then N=4) opt in via configure_compilation_cache() and share this
    # dir — the single-device reference programs compile once across
    # both runs instead of once per child (tier-1 budget)
    os.environ["ACCELERATE_TPU_COMPILATION_CACHE"] = cache_dir
    configure_compilation_cache(cache_dir, force=True)
    yield
    # scoped: hand the process back with caching OFF — a later module that
    # re-traces an AOT-compiled train step would deserialize a threshold-0
    # entry from this dir and segfault jaxlib (ISSUE 16 hit this the moment
    # an engine module sorted before test_launched_scripts)
    for k, v in prev.items():
        if v is None:
            os.environ.pop(k, None)
        else:
            os.environ[k] = v
    configure_compilation_cache("off", force=True)


@pytest.fixture(scope="module")
def gpt2_setup():
    cfg = gpt2.GPT2Config.tiny()
    params = gpt2.init_params(cfg, jax.random.key(0))
    return cfg, params


def _ec(**overrides):
    defaults = dict(num_slots=3, max_len=64, prefill_chunk=8,
                    cache_dtype=jnp.float32)
    defaults.update(overrides)
    return EngineConfig(**defaults)


def _run_trace(engine, cfg, budgets=(6, 6, 4, 4), temps=(0.0, 0.7, 0.0, 1.1)):
    """Seeded staggered mix, identical for every engine flavor."""
    rng = np.random.default_rng(7)
    prompts = [rng.integers(0, cfg.vocab_size, (n,)).astype(np.int32)
               for n in (5, 11, 3, 17)]
    reqs = [engine.submit(prompts[0], max_new_tokens=budgets[0],
                          temperature=temps[0])]
    for _ in range(3):
        engine.step()
    for p, b, t in zip(prompts[1:], budgets[1:], temps[1:]):
        reqs.append(engine.submit(p, max_new_tokens=b, temperature=t))
    engine.run_until_idle()
    return reqs


# ---------------------------------------------------------------------------
# contracts + config units (model-free)
# ---------------------------------------------------------------------------


def test_pod_config_validates_roles():
    with pytest.raises(ValueError, match="at least one worker"):
        PodConfig(prefill_workers=0)
    with pytest.raises(ValueError, match="at least one worker"):
        PodConfig(decode_workers=0)
    with pytest.raises(ValueError, match="tensor_parallel"):
        PodConfig(tensor_parallel=0)


def test_pod_program_contracts_pin_the_new_collectives():
    """The sharded programs must REQUIRE communication where the
    single-device contract forbade it — the 'no collectives' promise is
    explicitly not carried over (ISSUE 9 satellite)."""
    pod = pod_program_contracts(num_layers=2)
    single = serving_program_contracts()
    assert set(pod) == {"admit", "prefill", "decode", "extract", "install"}
    # admit stays collective-free even sharded (per-slot scalars)
    assert pod["admit"].exhaustive and "all-reduce" in pod["admit"].forbid
    for name in ("prefill", "decode"):
        c = pod[name]
        assert ("all-reduce", "reduce-scatter") in c.require
        assert dict(c.at_least)["all-reduce"] == 2
        assert "all-to-all" in c.forbid
        # a program satisfying the single-device contract (no
        # collectives at all) VIOLATES the pod contract, and vice versa
        assert single[name].check("add(f32[] a, f32[] b)") == []
        assert c.check("add(f32[] a, f32[] b)") != []
    for name in ("extract", "install"):
        assert "all-reduce" in pod[name].forbid


def test_shipment_page_bytes_counts_prompt_pages_only():
    ship = KVPageShipment(
        prompt=np.arange(20, dtype=np.int32), first_token=1,
        n_prompt_pages=2,
        k_pages=np.zeros((1, 5, 8, 2, 4), np.float32),
        v_pages=np.zeros((1, 5, 8, 2, 4), np.float32),
        key_raw=np.zeros((2,), np.uint32), temperature=0.0,
        max_new_tokens=4, eos_token_id=None)
    per_page = 2 * 1 * 8 * 2 * 4 * 4  # k+v, L*ps*H*D * itemsize
    assert ship.page_bytes == 2 * per_page


# ---------------------------------------------------------------------------
# layer 1: mesh-sharded engine
# ---------------------------------------------------------------------------


def test_sharded_engine_token_exact_and_compile_flat(gpt2_setup):
    """The N=2 mesh engine reproduces the single-device token streams
    byte for byte — greedy AND sampled — through exactly one compile per
    program, with strict="error" proving the pod contract audit passes
    on every sharded lowering."""
    cfg, params = gpt2_setup
    ref = [r.tokens for r in _run_trace(Engine(gpt2, cfg, params, _ec()),
                                        cfg)]
    eng = sharded_engine(gpt2, cfg, params, _ec(strict="error"),
                         mesh=tensor_mesh(2))
    got = [r.tokens for r in _run_trace(eng, cfg)]
    assert got == ref
    assert eng.compile_stats() == {"admit": 1, "prefill": 1, "decode": 1}


def test_sharded_engine_nondividing_heads_stays_compile_flat():
    """GQA regression: llama-tiny has 2 KV heads — on a 4-device mesh the
    pool can't shard over heads and replicates. Without the engine's
    out_shardings pin GSPMD never converged on an output layout and the
    decode compile count crept per step (measured: 13 compiles for one
    short trace); the pin holds it at one."""
    cfg = llama.LlamaConfig.tiny()
    params = llama.init_params(cfg, jax.random.key(0))
    ref_eng = Engine(llama, cfg, params, _ec(num_slots=2))
    ref = [r.tokens for r in _run_trace(ref_eng, cfg, budgets=(5, 5, 3, 3))]
    eng = sharded_engine(llama, cfg, params, _ec(num_slots=2),
                         mesh=tensor_mesh(4))
    got = [r.tokens for r in _run_trace(eng, cfg, budgets=(5, 5, 3, 3))]
    assert got == ref
    assert eng.compile_stats() == {"admit": 1, "prefill": 1, "decode": 1}


def test_sharded_engine_one_device_mesh_degrades_to_single(gpt2_setup):
    """A 1-device 'mesh' IS single-device serving: sharded_engine with
    tensor_parallel=1 (a single-chip host) must serve under
    strict='error' instead of tripping the meshed audit, which demands
    sharded args and TP reductions a lone chip can never have (review
    find: this crashed with ATP101 before the normalization)."""
    cfg, params = gpt2_setup
    ref = Engine(gpt2, cfg, params, _ec())
    rng = np.random.default_rng(31)
    p = rng.integers(0, cfg.vocab_size, (7,)).astype(np.int32)
    r0 = ref.submit(p, max_new_tokens=5)
    ref.run_until_idle()
    eng = sharded_engine(gpt2, cfg, params, _ec(strict="error"),
                         tensor_parallel=1)
    assert eng.engine_config.mesh is None  # normalized away
    r1 = eng.submit(p, max_new_tokens=5)
    eng.run_until_idle()
    assert r1.tokens == r0.tokens
    assert eng.compile_stats() == {"admit": 1, "prefill": 1, "decode": 1}


def test_allocator_rollback_inverts_allocate(gpt2_setup):
    """PagedAllocator.rollback: the adopt-race path's inverse-of-allocate
    must restore the pool and the prefix books exactly (no leak, no
    double-free, counters unwound)."""
    from accelerate_tpu.serving import PagedAllocator
    from accelerate_tpu.serving.scheduler import Request

    alloc = PagedAllocator(page_size=4, num_pages=16)
    req = Request(prompt=np.arange(10, dtype=np.int32), max_new_tokens=4)
    before = (alloc.pages_free, alloc.lookups, alloc.hits,
              alloc.tokens_reused, alloc.index.mapped_pages)
    a = alloc.allocate(req)
    assert a is not None and alloc.pages_free < before[0]
    alloc.rollback(a)
    assert (alloc.pages_free, alloc.lookups, alloc.hits,
            alloc.tokens_reused, alloc.index.mapped_pages) == before


def test_cache_state_shardings_spec_shapes(gpt2_setup):
    cfg, params = gpt2_setup
    eng = Engine(gpt2, cfg, params, _ec())
    mesh = tensor_mesh(2)
    cache_sh, rep = cache_state_shardings(eng.cache, mesh)
    assert cache_sh.k.spec == jax.sharding.PartitionSpec(
        None, None, "model")
    assert rep.spec == jax.sharding.PartitionSpec()
    # non-dividing heads (gpt2-tiny has 4): a 3-device mesh replicates
    cache_sh3, _ = cache_state_shardings(eng.cache, tensor_mesh(3))
    assert cache_sh3.k.spec == jax.sharding.PartitionSpec()


def test_single_engine_strict_still_rejects_leaked_mesh_params(gpt2_setup):
    """The ATP101 placement check kept its old teeth: params on a mesh
    WITHOUT EngineConfig(mesh=...) is still a strict-mode violation."""
    from accelerate_tpu.analysis import AnalysisViolation

    cfg, params = gpt2_setup
    placed = shard_params(params, tensor_mesh(2))
    eng = Engine(gpt2, cfg, placed, _ec(strict="error"))
    with pytest.raises(AnalysisViolation, match="ATP101"):
        _run_trace(eng, cfg)


# ---------------------------------------------------------------------------
# layer 2: disaggregated pod
# ---------------------------------------------------------------------------


def test_pod_token_exact_vs_single_engine(gpt2_setup):
    """2 prefill + 2 decode workers shipping KV pages reproduce the
    single engine's streams byte for byte on the same seeded trace —
    including sampled temperatures (the router mirrors the engine's
    key-derivation) — with per-role compile counts flat at one."""
    cfg, params = gpt2_setup
    ref = [r.tokens for r in _run_trace(Engine(gpt2, cfg, params, _ec()),
                                        cfg)]
    pod = PodEngine(gpt2, cfg, params, _ec(),
                    PodConfig(prefill_workers=2, decode_workers=2))
    reqs = _run_trace(pod, cfg)
    assert [r.tokens for r in reqs] == ref
    assert all(r.status is RequestStatus.FINISHED for r in reqs)
    assert pod.compile_stats() == {"admit": 1, "prefill": 1, "decode": 1,
                                   "extract": 1, "install": 1}
    s = pod.metrics_summary()
    assert s["pod_shipments"] == 4.0
    assert s["pod_pages_shipped"] >= 4.0
    assert s["requests_finished"] == 4.0


def test_pod_budget_one_and_eos_finish_at_prefill(gpt2_setup):
    """A request done at its first token (budget 1, or EOS immediately)
    finishes at the prefill worker — nothing ships."""
    cfg, params = gpt2_setup
    ref_eng = Engine(gpt2, cfg, params, _ec())
    rng = np.random.default_rng(3)
    p = rng.integers(0, cfg.vocab_size, (9,)).astype(np.int32)
    r_ref = ref_eng.submit(p, max_new_tokens=1)
    ref_eng.run_until_idle()

    pod = PodEngine(gpt2, cfg, params, _ec())
    r = pod.submit(p, max_new_tokens=1)
    pod.run_until_idle()
    assert r.status is RequestStatus.FINISHED
    assert r.tokens == r_ref.tokens
    assert pod.metrics_summary()["pod_shipments"] == 0.0

    # EOS on the first token: same short-circuit, same exact token
    r2 = pod.submit(p, max_new_tokens=8, eos_token_id=r_ref.tokens[0])
    pod.run_until_idle()
    assert r2.status is RequestStatus.FINISHED
    assert r2.tokens == r_ref.tokens
    assert pod.metrics_summary()["pod_shipments"] == 0.0


def test_pod_worker_drop_carries_shed_code(gpt2_setup):
    """ATP212 regression (ISSUE 13 self-lint finding): when a prefill
    worker drops an internal (the defensive wedge path), the user's
    EXPIRED terminal must carry the machine-readable shed_code and a
    retry hint — this path previously shipped prose only, invisible to
    shed accounting."""
    from accelerate_tpu.serving.scheduler import SHED_WORKER_DROP

    cfg, params = gpt2_setup
    # max_attempts=1: a drop the router may not replay is a shed (with
    # attempts left it re-prefills instead — test_pod_distributed.py)
    pod = PodEngine(gpt2, cfg, params, _ec(prefill_chunk=4),
                    PodConfig(max_attempts=1))
    rng = np.random.default_rng(11)
    p = rng.integers(0, cfg.vocab_size, (17,)).astype(np.int32)
    user = pod.submit(p, max_new_tokens=6)
    flight = pod._by_user[id(user)]
    assert flight.phase == "prefill"
    pod.step()   # the worker takes the submit and prefills one chunk
    # simulate a worker-side wedge: the internal dies mid-prefill (the
    # worker's harvest must also clean up the admit-hook page snapshot
    # — its step-end sanitizer validates that)
    worker = pod.workers[flight.worker].local
    assert worker.engine.cancel(worker._jobs[flight.flight_id].internal)
    pod.step()   # the worker reports the drop ...
    pod.step()   # ... and the router, out of attempts, sheds
    assert user.status is RequestStatus.EXPIRED
    assert user.shed_code == SHED_WORKER_DROP
    assert user.retry_after_s is not None
    assert pod.metrics_summary()["requests_expired"] == 1.0
    # the flight is gone and the pod keeps serving
    assert id(user) not in pod._by_user
    r2 = pod.submit(p, max_new_tokens=3)
    pod.run_until_idle()
    assert r2.status is RequestStatus.FINISHED


def test_pod_backpressure_stalls_router_not_prefill(gpt2_setup):
    """With a single decode slot and a shipment buffer of one, a burst
    of prompts must (a) still finish token-exact, (b) record
    backpressure stalls, and (c) keep the prefill side working ahead —
    the stall parks shipments at the router; it never wedges."""
    cfg, params = gpt2_setup
    ec = _ec(num_slots=1, max_queue=16)
    ref_eng = Engine(gpt2, cfg, params, dataclasses.replace(ec, num_slots=3))
    rng = np.random.default_rng(5)
    prompts = [rng.integers(0, cfg.vocab_size, (n,)).astype(np.int32)
               for n in (6, 9, 4, 11)]
    ref = []
    for p in prompts:
        r = ref_eng.submit(p, max_new_tokens=5)
        ref_eng.run_until_idle()
        ref.append(r.tokens)

    pod = PodEngine(gpt2, cfg, params, ec,
                    PodConfig(prefill_workers=1, decode_workers=1,
                              prefill_slots=3, max_pending_shipments=1))
    reqs = [pod.submit(p, max_new_tokens=5) for p in prompts]
    pod.run_until_idle()
    assert [r.tokens for r in reqs] == ref
    assert pod.metrics_summary()["pod_backpressure_stalls"] > 0
    assert pod.metrics_summary()["pod_shipments"] == 4.0


def test_pod_cancel_everywhere(gpt2_setup):
    """Cancel is honored in every flight phase: front-queued, decoding,
    and the handle reports CANCELLED with pages freed."""
    cfg, params = gpt2_setup
    ec = _ec(num_slots=1, max_queue=8)
    pod = PodEngine(gpt2, cfg, params, ec,
                    PodConfig(prefill_workers=1, decode_workers=1))
    rng = np.random.default_rng(9)
    prompts = [rng.integers(0, cfg.vocab_size, (n,)).astype(np.int32)
               for n in (6, 7, 8)]
    a = pod.submit(prompts[0], max_new_tokens=16)
    b = pod.submit(prompts[1], max_new_tokens=16)
    c = pod.submit(prompts[2], max_new_tokens=16)
    # drive until a is decoding
    for _ in range(40):
        pod.step()
        if a.tokens:
            break
    assert a.tokens, "a never reached decode"
    assert pod.cancel(c)          # still queued/parked
    assert pod.cancel(a)          # mid-decode
    assert not pod.cancel(a)      # idempotent
    pod.run_until_idle()
    assert a.status is RequestStatus.CANCELLED
    assert c.status is RequestStatus.CANCELLED
    assert b.status is RequestStatus.FINISHED and len(b.tokens) == 16
    # every worker drained: all pages back except prefix-tree cached ones
    for handle in pod.workers.values():
        assert handle.local.engine.scheduler.live_slots == 0
    s = pod.metrics_summary()
    assert s["requests_cancelled"] == 2.0
    assert s["requests_finished"] == 1.0


def test_pod_finish_early_is_finished(gpt2_setup):
    """The server's stop-sequence path: finish() retires a decoding
    request as FINISHED with the tokens delivered so far."""
    cfg, params = gpt2_setup
    pod = PodEngine(gpt2, cfg, params, _ec())
    rng = np.random.default_rng(11)
    p = rng.integers(0, cfg.vocab_size, (6,)).astype(np.int32)
    r = pod.submit(p, max_new_tokens=24)
    for _ in range(60):
        pod.step()
        if len(r.tokens) >= 3:
            break
    assert len(r.tokens) >= 3
    assert pod.finish(r)
    assert r.status is RequestStatus.FINISHED
    assert pod.metrics_summary()["requests_finished"] == 1.0
    pod.run_until_idle()


def test_pod_stream_matches_handle(gpt2_setup):
    cfg, params = gpt2_setup
    ref_eng = Engine(gpt2, cfg, params, _ec())
    rng = np.random.default_rng(13)
    p = rng.integers(0, cfg.vocab_size, (7,)).astype(np.int32)
    r_ref = ref_eng.submit(p, max_new_tokens=6)
    ref_eng.run_until_idle()

    pod = PodEngine(gpt2, cfg, params, _ec())
    r = pod.submit(p, max_new_tokens=6)
    streamed = list(pod.stream(r))
    assert streamed == r.tokens == r_ref.tokens


def test_pod_rejects_and_sheds_like_an_engine(gpt2_setup):
    """Admission control stays at the front door: over-long requests
    REJECT with the engine's shed vocabulary, and queue overflow carries
    retry_after_s — no pod internals leak into the failure surface."""
    cfg, params = gpt2_setup
    ec = _ec(max_queue=1, num_slots=1)
    pod = PodEngine(gpt2, cfg, params, ec,
                    PodConfig(prefill_workers=1, decode_workers=1,
                              prefill_slots=1, max_pending_shipments=1))
    too_long = pod.submit(np.arange(60, dtype=np.int32) % cfg.vocab_size,
                          max_new_tokens=32)
    assert too_long.status is RequestStatus.REJECTED
    assert too_long.shed_code == "too_long"
    rng = np.random.default_rng(17)
    keep = []
    rejected = []
    for _ in range(8):
        r = pod.submit(rng.integers(0, cfg.vocab_size, (6,)).astype(np.int32),
                       max_new_tokens=8)
        (rejected if r.status is RequestStatus.REJECTED else keep).append(r)
    assert rejected, "queue bound never bit"
    assert all(r.shed_code == "queue_full" for r in rejected)
    assert all(r.retry_after_s is not None for r in rejected)
    pod.run_until_idle()
    assert all(r.status is RequestStatus.FINISHED for r in keep)


def test_pod_debug_views(gpt2_setup):
    cfg, params = gpt2_setup
    pod = PodEngine(gpt2, cfg, params, _ec(),
                    PodConfig(prefill_workers=1, decode_workers=2))
    rng = np.random.default_rng(19)
    for n in (5, 8):
        pod.submit(rng.integers(0, cfg.vocab_size, (n,)).astype(np.int32),
                   max_new_tokens=4)
    pod.run_until_idle()
    dp = pod.debug_pod()
    # worker ids are pod-wide: the prefill worker is 0
    assert [w["worker"] for w in dp["roles"]["decode"]] == [1, 2]
    assert dp["shipments_total"] == 2
    assert dp["pages_shipped_total"] >= 2
    assert dp["in_flight"] == {}
    slots = pod.debug_slots()
    assert {e["role"] for e in slots} == {"prefill", "decode"}
    pages = pod.debug_pages()
    assert pages["pages_shipped"] >= 2
    assert len(pages["workers"]) == 3
    sched = pod.debug_scheduler()
    assert sched["pod"]["in_flight"] == 0
    import json

    json.dumps({"pod": dp, "slots": slots, "pages": pages, "sched": sched})


def test_pod_page_transfer_span_joins_request_trace(gpt2_setup):
    """The shipping hop is visible in the request's trace: a
    serving.page_transfer span parented on the request root, carrying
    the page count (ISSUE 9 telemetry satellite)."""
    from accelerate_tpu.telemetry.trace import configure_tracing, trace_events

    cfg, params = gpt2_setup
    configure_tracing(enabled=True, annotate=False)
    try:
        pod = PodEngine(gpt2, cfg, params, _ec())
        rng = np.random.default_rng(23)
        p = rng.integers(0, cfg.vocab_size, (9,)).astype(np.int32)
        r = pod.submit(p, max_new_tokens=4)
        pod.run_until_idle()
        assert r.trace_id is not None
        events = trace_events(r.trace_id)
        names = [e["name"] for e in events]
        assert "serving.page_transfer" in names
        assert "serving.queue_wait" in names
        assert "serving.request" in names
        hop = next(e for e in events if e["name"] == "serving.page_transfer")
        root = next(e for e in events if e["name"] == "serving.request")
        assert hop["attrs"]["pages"] >= 1
        assert hop["parent_id"] == root["span_id"]
    finally:
        configure_tracing(enabled=False, sample_rates={},
                          default_sample_rate=1.0)


def test_pod_role_metrics_exported(gpt2_setup):
    """The pod registry carries the satellite series: shipment counters
    and per-role occupancy gauges, visible to any exporter."""
    cfg, params = gpt2_setup
    pod = PodEngine(gpt2, cfg, params, _ec())
    rng = np.random.default_rng(29)
    pod.submit(rng.integers(0, cfg.vocab_size, (6,)).astype(np.int32),
               max_new_tokens=4)
    pod.run_until_idle()
    series = {(name, dict(labels).get("role"))
              for kind, name, labels, _ in pod.registry.items()}
    assert ("serving_pod_pages_shipped_total", None) in series
    assert ("serving_pod_role_occupancy", "prefill") in series
    assert ("serving_pod_role_occupancy", "decode") in series
    assert ("serving_pod_pending_shipments", None) in series


# ---------------------------------------------------------------------------
# the HTTP front door runs unchanged over a pod
# ---------------------------------------------------------------------------


def test_http_front_door_over_pod_engine(gpt2_setup):
    """The PR 6 server stack — protocol, SSE streaming, debug gating —
    drives a PodEngine exactly like a single engine: one streaming
    completion returns the pod's byte stream, /debug/pod serves router
    state when gated on, and 404s for EVERY method when off."""
    import asyncio
    import json

    from accelerate_tpu.server.config import ServerConfig
    from accelerate_tpu.server.http import HttpFrontDoor
    from accelerate_tpu.server.service import InferenceService
    from accelerate_tpu.server.tokenizer import get_tokenizer

    cfg, params = gpt2_setup
    ref_eng = Engine(gpt2, cfg, params, _ec())
    prompt = list(range(1, 8))
    r_ref = ref_eng.submit(np.asarray(prompt, np.int32), max_new_tokens=5)
    ref_eng.run_until_idle()

    pod = PodEngine(gpt2, cfg, params, _ec())
    scfg = ServerConfig(port=0, model_id="pod-test", tokenizer="numeric",
                        debug_endpoints=True)
    service = InferenceService(
        pod, get_tokenizer("numeric", cfg.vocab_size), scfg)
    door = HttpFrontDoor(service, scfg)

    async def req(port, verb, path, body=None):
        reader, writer = await asyncio.open_connection("127.0.0.1", port)
        payload = json.dumps(body).encode() if body is not None else b""
        writer.write(
            f"{verb} {path} HTTP/1.1\r\nHost: t\r\n"
            f"Content-Length: {len(payload)}\r\n\r\n".encode() + payload)
        await writer.drain()
        head = await reader.readuntil(b"\r\n\r\n")
        status = int(head.split(b" ")[1])
        data = await reader.read()
        writer.close()
        return status, data

    async def drive():
        await door.start()
        port = door.port
        status, data = await req(
            port, "POST", "/v1/completions",
            {"prompt": prompt, "max_tokens": 5, "temperature": 0,
             "stream": True})
        assert status == 200
        ids = []
        for frame in data.split(b"\n\n"):
            if frame.startswith(b"data: ") and b"[DONE]" not in frame:
                row = json.loads(frame[len(b"data: "):])
                ids += row["choices"][0].get("token_ids", [])
        status, body = await req(port, "GET", "/debug/pod")
        assert status == 200
        dbg = json.loads(body.partition(b"\r\n\r\n")[0] or body)
        await door.stop()
        return ids, dbg

    ids, dbg = asyncio.run(drive())
    assert ids == r_ref.tokens
    assert dbg["shipments_total"] >= 1
    assert "roles" in dbg

    # gate off: 404 for every method, pod or not (fingerprint-proof)
    scfg_off = ServerConfig(port=0, model_id="pod-test", tokenizer="numeric",
                            debug_endpoints=False)
    service2 = InferenceService(
        pod, get_tokenizer("numeric", cfg.vocab_size), scfg_off)
    door2 = HttpFrontDoor(service2, scfg_off)

    async def gate():
        await door2.start()
        out = [(await req(door2.port, verb, "/debug/pod"))[0]
               for verb in ("GET", "POST", "HEAD")]
        await door2.stop()
        return out

    assert asyncio.run(gate()) == [404, 404, 404]
    pod.close()


# ---------------------------------------------------------------------------
# forced-host-device acceptance (subprocess, N=2 and N=4)
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("n_devices", [2, 4])
def test_pod_exactness_under_forced_devices(forced_device_run, n_devices):
    """The ISSUE 9 acceptance: in a process whose ENTIRE backend is N
    forced host devices, the mesh-sharded engine (strict audit on) and
    the disaggregated TP-N pod both reproduce the single-device token
    streams byte for byte, compile-flat (see pod_exactness_script.py)."""
    script = os.path.join(os.path.dirname(__file__),
                          "pod_exactness_script.py")
    out = forced_device_run(script, n_devices, args=(n_devices,),
                            timeout=420)
    assert "POD_EXACTNESS_OK" in out


# ---------------------------------------------------------------------------
# ISSUE 10: int8 KV shipments + page-dim pool sharding
# ---------------------------------------------------------------------------


def test_pod_int8_shipments_byte_identical_to_single_engine(gpt2_setup):
    """kv_dtype="int8" through the pod: every worker's pool quantizes
    and shipments carry codes + scales verbatim (no dequant/requant
    round-trip that would drift the codes) — pod output matches the
    single int8 engine byte for byte, with the kernel-backed decode
    worker variant too."""
    cfg, params = gpt2_setup
    ref = [r.tokens for r in _run_trace(
        Engine(gpt2, cfg, params, _ec(kv_dtype="int8")), cfg)]
    for pa in (False, True):
        pod = PodEngine(gpt2, cfg, params,
                        _ec(kv_dtype="int8", paged_attention=pa),
                        PodConfig(prefill_workers=1, decode_workers=1))
        reqs = _run_trace(pod, cfg)
        assert [r.tokens for r in reqs] == ref, f"paged_attention={pa}"
        assert pod.metrics_summary()["pod_shipments"] == 4.0


def test_shipment_page_bytes_halve_under_int8():
    """The wire-bytes claim: an int8 shipment's page_bytes are the code
    bytes (half of bf16) plus the scale blocks — (D+2)/2D of the bf16
    payload for the same page geometry."""
    L, P, ps, H, D = 1, 5, 8, 2, 4
    common = dict(prompt=np.arange(20, dtype=np.int32), first_token=1,
                  n_prompt_pages=2, key_raw=np.zeros((2,), np.uint32),
                  temperature=0.0, max_new_tokens=4, eos_token_id=None)
    bf16 = KVPageShipment(
        k_pages=np.zeros((L, P, ps, H, D), np.dtype("bfloat16")
                         if hasattr(np, "bfloat16") else np.float16),
        v_pages=np.zeros((L, P, ps, H, D), np.float16), **common)
    i8 = KVPageShipment(
        k_pages=np.zeros((L, P, ps, H, D), np.int8),
        v_pages=np.zeros((L, P, ps, H, D), np.int8),
        k_scales=np.zeros((L, P, ps, H), np.float16),
        v_scales=np.zeros((L, P, ps, H), np.float16), **common)
    assert i8.page_bytes / bf16.page_bytes == (D + 2) / (2 * D)


def test_pool_page_dim_sharding_when_heads_dont_divide():
    """ISSUE 10 satellite (pod GQA follow-up from PR 9): llama-tiny's 2
    KV heads don't divide a 4-wide mesh — the pool used to fully
    replicate per chip. With a page count the mesh divides (pages+1 %
    n == 0) it now shards over the PAGE dim instead, stays token-exact,
    and holds the compile count; when neither dim divides it still
    falls back to replication (the old behavior, pinned by
    test_sharded_engine_nondividing_heads_stays_compile_flat)."""
    cfg = llama.LlamaConfig.tiny()
    params = llama.init_params(cfg, jax.random.key(0))
    ref_eng = Engine(llama, cfg, params, _ec(num_slots=2, num_pages=11))
    ref = [r.tokens for r in _run_trace(ref_eng, cfg, budgets=(5, 5, 3, 3))]
    # pages+1 = 12 divides the 4-wide mesh -> page-dim sharded pool
    eng = sharded_engine(llama, cfg, params,
                         _ec(num_slots=2, num_pages=11),
                         mesh=tensor_mesh(4))
    assert tuple(eng.cache.k.sharding.spec) == (None, "model")
    got = [r.tokens for r in _run_trace(eng, cfg, budgets=(5, 5, 3, 3))]
    assert got == ref
    assert eng.compile_stats() == {"admit": 1, "prefill": 1, "decode": 1}
    # replicate fallback: neither heads (2) nor pages+1 (11) divide 4
    fallback = cache_state_shardings(
        Engine(llama, cfg, params, _ec(num_slots=2, num_pages=10)).cache,
        tensor_mesh(4))[0]
    assert fallback.k.is_fully_replicated


def test_contract_factories_name_paged_kernel_variant():
    """ISSUE 10: both contract factories gain the kernel-backed decode
    variant — same clauses (a pallas custom call is chip-local, not a
    collective), distinct name so audit reports say which decode flavor
    they checked. A kernel-backed engine under strict mode resolves to
    the variant automatically (pinned by
    test_paged_kernel_gqa_and_slot_reuse_token_exact's strict=error)."""
    plain = serving_program_contracts()
    kern = serving_program_contracts(paged_kernel=True)
    assert kern["decode"].name == "serving.decode.paged-kernel"
    assert plain["decode"].name == "serving.decode"
    assert kern["decode"].forbid == plain["decode"].forbid
    assert kern["decode"].exhaustive
    pod_kern = pod_program_contracts(num_layers=2, paged_kernel=True)
    assert pod_kern["decode"].name == "serving.pod.decode.paged-kernel"
    assert pod_kern["decode"].require == pod_program_contracts(
        num_layers=2)["decode"].require


def test_pod_logprobs_ride_shipments(gpt2_setup):
    """ISSUE 12: per-token logprobs survive disaggregation — the first
    token's logprob rides the KVPageShipment, later ones mirror from the
    decode worker, so the pod's user-facing handle carries the same
    logprobs (index-aligned with its tokens) as the single engine."""
    cfg, params = gpt2_setup
    ref_eng = Engine(gpt2, cfg, params, _ec())
    ref = _run_trace(ref_eng, cfg)
    pod = PodEngine(gpt2, cfg, params, _ec(),
                    PodConfig(prefill_workers=1, decode_workers=1))
    reqs = _run_trace(pod, cfg)
    for r_ref, r_pod in zip(ref, reqs):
        assert r_pod.tokens == r_ref.tokens
        assert len(r_pod.logprobs) == len(r_pod.tokens)
        assert r_pod.logprobs == pytest.approx(r_ref.logprobs, abs=1e-5)
