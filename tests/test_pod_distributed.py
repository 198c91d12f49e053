"""True multi-host pod (serving/pod/distributed): wire transport,
worker heartbeats + failure recovery, elastic rebalancing.

CPU contracts, all deterministic: the frame codec round-trips shipments
byte-identically (incl. int8 codes+scales) and rejects malformed frames
without executing anything; the in-process distributed pod (LocalChannel
pairs through the real codec, fake clock) is byte-identical to the
single engine on the seeded greedy+sampled trace with compile counts
flat; every injected failure — dropped shipments, duplicated frames,
killed decode worker mid-stream, killed prefill worker mid-prefill, a
hung (heartbeat-silent) worker, random flake storms — recovers every
in-flight request by re-prefill-from-prompt with NO lost or duplicated
tokens; rebalancing converts at most one role per window; worker
registry snapshots merge into the router's exposition; and the
cross-process sanitizer invariants catch corrupted router books. The
two-OS-process socket smoke (pod_distributed_script.py) proves the same
exactness + kill-recovery across real process boundaries."""

import os
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from accelerate_tpu.models import gpt2
from accelerate_tpu.serving import (
    Engine,
    EngineConfig,
    Request,
    RequestStatus,
)
from accelerate_tpu.serving.pod import KVPageShipment
from accelerate_tpu.serving.pod.distributed import (
    DistributedPodConfig,
    FlakyTransport,
    LocalChannel,
    Message,
    SocketChannel,
    build_local_distributed_pod,
    decode_message,
    encode_message,
    shipment_from_message,
    shipment_to_message,
)
from accelerate_tpu.serving.pod.distributed.transport import ChannelListener
from accelerate_tpu.serving.pod.distributed.wire import MAGIC, WireError
from accelerate_tpu.serving.sanitizer import (
    SanitizerViolation,
    check_distributed_router,
)


@pytest.fixture(scope="module", autouse=True)
def _persistent_compile_cache(tmp_path_factory):
    """Same engine programs compile over and over across pods here; the
    persistent cache turns repeats into deserializes (see test_pod.py
    for the threshold/segfault caveats this fixture handles). The dir is
    ALSO exported so the two-process smoke's children — the script and
    its spawned pod-workers, three processes compiling the same spec —
    compile once and deserialize twice (tier-1 budget)."""
    from accelerate_tpu.utils.environment import configure_compilation_cache

    cache_dir = str(tmp_path_factory.mktemp("xla_cache"))
    prev = {k: os.environ.get(k)
            for k in ("ACCELERATE_TPU_COMPILATION_CACHE_MIN_COMPILE_SECS",
                      "ACCELERATE_TPU_COMPILATION_CACHE")}
    os.environ.setdefault(
        "ACCELERATE_TPU_COMPILATION_CACHE_MIN_COMPILE_SECS", "0")
    os.environ["ACCELERATE_TPU_COMPILATION_CACHE"] = cache_dir
    configure_compilation_cache(cache_dir, force=True)
    yield
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
    defaults = dict(num_slots=3, max_len=64, prefill_chunk=8, page_size=8,
                    cache_dtype=jnp.float32)
    defaults.update(overrides)
    return EngineConfig(**defaults)


def _traffic(cfg):
    rng = np.random.default_rng(7)
    prompts = [rng.integers(1, cfg.vocab_size, size=n).tolist()
               for n in (5, 11, 3, 17)]
    return prompts, (6, 6, 4, 4), (0.0, 0.7, 0.0, 1.1)


@pytest.fixture(scope="module")
def ref_outputs(gpt2_setup):
    """Single-engine tokens AND logprobs for the seeded trace; sampling
    keys fold in the request id, so any pod that submits the same trace
    in the same order must reproduce these byte for byte."""
    cfg, params = gpt2_setup
    engine = Engine(gpt2, cfg, params, _ec())
    prompts, budgets, temps = _traffic(cfg)
    reqs = [engine.submit(p, max_new_tokens=b, temperature=t)
            for p, b, t in zip(prompts, budgets, temps)]
    engine.run_until_idle()
    assert all(r.status.value == "finished" for r in reqs)
    return ([list(r.tokens) for r in reqs],
            [list(r.logprobs) for r in reqs])


def _fake_clock():
    t = [0.0]

    def clock():
        t[0] += 0.01
        return t[0]

    return clock


def _build_pod(cfg, params, pf=1, dec=1, wrap=None, **pc_kwargs):
    pc_kwargs.setdefault("heartbeat_interval_s", 0.0)
    pc_kwargs.setdefault("rebalance", False)
    return build_local_distributed_pod(
        gpt2, cfg, params, engine_config=_ec(),
        pod_config=DistributedPodConfig(
            prefill_workers=pf, decode_workers=dec, **pc_kwargs),
        clock=_fake_clock(), channel_wrap=wrap)


def _drive(router, reqs, max_steps=5000):
    for _ in range(max_steps):
        router.step()
        if all(r.done for r in reqs):
            return
    raise AssertionError(f"pod wedged: {router.debug_pod()}")


def _submit_traffic(router, cfg):
    prompts, budgets, temps = _traffic(cfg)
    return [router.submit(p, max_new_tokens=b, temperature=t)
            for p, b, t in zip(prompts, budgets, temps)]


# ---------------------------------------------------------------------------
# wire format
# ---------------------------------------------------------------------------


def test_message_frame_roundtrip_byte_identical():
    msg = Message("tokens",
                  {"flight_id": 3, "attempt": 2, "nested": {"a": [1, 2]}},
                  [np.arange(12, dtype=np.float32).reshape(3, 4),
                   np.array([7, 8, 9], dtype=np.int32),
                   np.array([1, 2], dtype=np.uint32)])
    got = decode_message(encode_message(msg))
    assert got.kind == msg.kind and got.meta == msg.meta
    assert len(got.buffers) == 3
    for a, b in zip(got.buffers, msg.buffers):
        assert a.dtype == b.dtype and a.shape == b.shape
        assert a.tobytes() == b.tobytes()
    # re-encoding the decode is bit-stable (no lossy hop anywhere)
    assert encode_message(got) == encode_message(msg)


def test_message_frame_roundtrip_extension_dtype():
    """bfloat16 is an ml_dtypes extension type whose `.str` is an opaque
    void tag ("<V2") — the codec must ship the registered NAME instead,
    or every bf16 KV pool decodes as void and the install jit rejects
    it. Regression for the serve_bench socket arm (cache_dtype=bf16)."""
    import ml_dtypes

    a = (np.arange(12, dtype=np.float32) / 7.0).astype(
        ml_dtypes.bfloat16).reshape(3, 4)
    got = decode_message(encode_message(Message("x", {}, [a])))
    assert got.buffers[0].dtype == a.dtype
    assert got.buffers[0].tobytes() == a.tobytes()


def _mk_shipment(quantized):
    L, pages, ps, H, D = 2, 5, 8, 2, 4
    rng = np.random.default_rng(3)
    kw = dict(
        prompt=np.arange(20, dtype=np.int32),
        first_token=17, n_prompt_pages=3,
        key_raw=np.array([123, 456], np.uint32),
        temperature=0.7, max_new_tokens=9, eos_token_id=None,
        src_worker=2, extracted_at=1.25, first_logprob=-0.5)
    if quantized:
        kw["k_pages"] = rng.integers(-128, 128, (L, pages, ps, H, D)
                                     ).astype(np.int8)
        kw["v_pages"] = rng.integers(-128, 128, (L, pages, ps, H, D)
                                     ).astype(np.int8)
        kw["k_scales"] = rng.random((L, pages, ps, H)).astype(np.float32)
        kw["v_scales"] = rng.random((L, pages, ps, H)).astype(np.float32)
    else:
        kw["k_pages"] = rng.random((L, pages, ps, H, D)).astype(np.float32)
        kw["v_pages"] = rng.random((L, pages, ps, H, D)).astype(np.float32)
    return KVPageShipment(**kw)


@pytest.mark.parametrize("quantized", [False, True])
def test_shipment_wire_roundtrip_byte_identical(quantized):
    """The hot path contract: a codes+scales shipment crosses the frame
    format with every tensor byte intact and every scalar field exact —
    int8 pools ship codes verbatim (no dequant/requant drift)."""
    ship = _mk_shipment(quantized)
    msg = decode_message(encode_message(
        shipment_to_message(ship, flight_id=5, attempt=1, worker_id=2)))
    assert msg.meta["flight_id"] == 5 and msg.meta["attempt"] == 1
    got = shipment_from_message(msg)
    for name in ("k_pages", "v_pages"):
        a, b = getattr(got, name), getattr(ship, name)
        assert a.dtype == b.dtype and a.shape == b.shape
        assert a.tobytes() == b.tobytes()
    if quantized:
        for name in ("k_scales", "v_scales"):
            assert getattr(got, name).tobytes() == \
                getattr(ship, name).tobytes()
    else:
        assert got.k_scales is None and got.v_scales is None
    assert got.prompt.tolist() == ship.prompt.tolist()
    assert got.key_raw.tolist() == ship.key_raw.tolist()
    for name in ("first_token", "n_prompt_pages", "temperature",
                 "max_new_tokens", "eos_token_id", "src_worker",
                 "extracted_at", "first_logprob"):
        assert getattr(got, name) == getattr(ship, name), name
    assert got.page_bytes == ship.page_bytes


def test_malformed_frames_raise_wire_error():
    frame = encode_message(Message("x", {"a": 1},
                                   [np.arange(4, dtype=np.float32)]))
    # truncation at every boundary class
    with pytest.raises(WireError):
        decode_message(frame[:8])
    with pytest.raises(WireError):
        decode_message(frame[:-3])
    # trailing junk: body longer than the descriptors account for
    with pytest.raises(WireError):
        decode_message(frame + b"JUNK")
    # bad magic
    with pytest.raises(WireError):
        decode_message(b"NOPE" + frame[4:])
    # header that is not JSON
    broken = bytearray(frame)
    broken[16] ^= 0xFF
    with pytest.raises(WireError):
        decode_message(bytes(broken))
    # descriptor that overruns the body it claims to describe
    big = Message("x", {}, [np.arange(100, dtype=np.float32)])
    small = encode_message(Message("x", {}, [np.arange(2, dtype=np.float32)]))
    header = encode_message(big)[:16]
    with pytest.raises(WireError):
        decode_message(header + small[16:])
    # a shipment frame with the wrong buffer count
    ship_msg = shipment_to_message(_mk_shipment(True))
    ship_msg.buffers = ship_msg.buffers[:3]
    with pytest.raises(WireError):
        shipment_from_message(ship_msg)


# ---------------------------------------------------------------------------
# channels
# ---------------------------------------------------------------------------


def test_local_channel_pair_crosses_the_codec():
    a, b = LocalChannel.pair()
    a.send(Message("ping", {"n": 1}, [np.arange(3, dtype=np.int32)]))
    b.send(Message("pong", {"n": 2}))
    got_b = b.poll()
    got_a = a.poll()
    assert [m.kind for m in got_b] == ["ping"]
    assert got_b[0].buffers[0].tolist() == [0, 1, 2]
    assert [m.kind for m in got_a] == ["pong"]
    assert a.bytes_sent > 0 and b.bytes_received == a.bytes_sent
    b.close()
    assert a.closed and b.closed
    with pytest.raises(ConnectionError):
        a.send(Message("ping", {}))


def test_socket_channel_roundtrip_and_close_detection():
    listener = ChannelListener("127.0.0.1", 0)
    try:
        client = SocketChannel.connect("127.0.0.1", listener.port)
        server = None
        deadline = 200
        while server is None and deadline:
            got = listener.accept_all()
            server = got[0] if got else None
            deadline -= 1
        assert server is not None
        client.send(Message("hello", {"worker_id": 7},
                            [np.arange(5, dtype=np.uint32)]))
        msgs = []
        for _ in range(500):
            msgs = server.poll()
            if msgs:
                break
            import time
            time.sleep(0.01)
        assert msgs and msgs[0].kind == "hello"
        assert msgs[0].meta["worker_id"] == 7
        assert msgs[0].buffers[0].tolist() == [0, 1, 2, 3, 4]
        # peer death is visible as `.closed`, and sends then raise
        server.close()
        for _ in range(500):
            if client.closed:
                break
            import time
            time.sleep(0.01)
        assert client.closed
        with pytest.raises(ConnectionError):
            client.send(Message("x", {}))
    finally:
        listener.close()


def test_socket_send_queue_bounded_backpressure():
    """The backpressure semantic: with the writer stalled and the
    bounded queue full, `send` BLOCKS (the router's forwarding step is
    the thing that waits) until space frees — then completes."""
    import socket as socketlib
    import threading
    import time

    listener = ChannelListener("127.0.0.1", 0)
    try:
        raw = socketlib.create_connection(("127.0.0.1", listener.port))
        ch = SocketChannel(raw, send_queue_depth=1)
        # stop the writer thread deterministically, then fill the queue
        ch._sendq.put(None)
        ch._writer.join(timeout=5)
        ch._sendq.put(b"filler")
        done = threading.Event()

        def sender():
            ch.send(Message("shipment", {"flight_id": 1}))
            done.set()

        t = threading.Thread(target=sender, daemon=True)
        t.start()
        time.sleep(0.25)
        assert not done.is_set(), "send returned despite a full queue"
        assert ch._sendq.get() == b"filler"   # drain one slot
        assert done.wait(timeout=5), "send never unblocked"
        ch.close()
        with pytest.raises(ConnectionError):
            ch.send(Message("x", {}))
    finally:
        listener.close()


def test_socket_close_joins_io_threads():
    """ATP305 regression: `close()` must reap the reader/writer threads,
    not just mark the channel closed — a leaked IO thread pins its
    socket and races interpreter teardown. The reader also closes the
    channel from its OWN thread on peer death, so the join has to guard
    against self-join instead of deadlocking."""
    import time

    listener = ChannelListener("127.0.0.1", 0)
    try:
        client = SocketChannel.connect("127.0.0.1", listener.port)
        server = None
        for _ in range(200):
            got = listener.accept_all()
            if got:
                server = got[0]
                break
            time.sleep(0.01)
        assert server is not None
        assert client._reader.is_alive() and client._writer.is_alive()
        client.close()
        assert not client._reader.is_alive(), "reader leaked past close()"
        assert not client._writer.is_alive(), "writer leaked past close()"
        # peer death path: server's reader notices and closes from inside
        # the reader thread itself — must finish, not self-join-wedge
        for _ in range(500):
            if server.closed:
                break
            time.sleep(0.01)
        assert server.closed
        server.close()
        for _ in range(500):
            if not (server._reader.is_alive() or server._writer.is_alive()):
                break
            time.sleep(0.01)
        assert not server._reader.is_alive()
        assert not server._writer.is_alive()
    finally:
        listener.close()


def test_step_never_sleeps_on_the_callers_thread(gpt2_setup, monkeypatch):
    """ATP303 regression: `step()` runs inline on the asyncio drive loop
    (astream), so a sleep inside it parks every coroutine on the loop.
    Pacing belongs to the sync callers, keyed off `last_step_worked` —
    step itself must never block, idle or busy."""
    import threading
    import time as time_mod

    import accelerate_tpu.serving.pod.distributed.droute as droute_mod

    cfg, params = gpt2_setup
    router = _build_pod(cfg, params)
    main = threading.current_thread()
    slept = []
    real_sleep = time_mod.sleep

    def spy(seconds):
        if threading.current_thread() is main:
            slept.append(seconds)
        real_sleep(seconds)

    monkeypatch.setattr(droute_mod.time, "sleep", spy)
    for _ in range(20):
        router.step()                  # idle pod: nothing to do
    assert router.last_step_worked is False
    assert slept == [], "idle step() slept on the caller's thread"
    reqs = _submit_traffic(router, cfg)
    _drive(router, reqs)
    assert all(r.done for r in reqs)
    assert slept == [], "busy step() slept on the caller's thread"
    router.close()


def test_flaky_transport_is_deterministic_and_injects_all_faults():
    def run_once():
        a, b = LocalChannel.pair()
        flaky = FlakyTransport(a, flake_rate=0.5, seed=42, delay_ticks=1)
        for i in range(20):
            b.send(Message("m", {"i": i}))
            flaky.send(Message("r", {"i": i}))
        seen = [m.meta["i"] for m in flaky.poll()]
        for _ in range(5):   # tick held delay entries out
            seen += [m.meta["i"] for m in flaky.poll()]
        return seen, dict(flaky.faults), [m.meta["i"] for m in b.poll()]

    first, second = run_once(), run_once()
    assert first == second, "seeded fault plan must replay identically"
    seen, faults, _ = first
    assert faults, "flake_rate=0.5 over 40 messages injected nothing"
    assert len(seen) != 20 or seen != list(range(20)), \
        "faults must be observable (drops/dups/reorders)"
    # scripted rules hit exactly the messages they name
    log = []
    a, b = LocalChannel.pair()
    flaky = FlakyTransport(
        a, rules=lambda d, kind, seq: {1: "drop", 2: "dup"}.get(seq, "ok"))
    for i in range(4):
        b.send(Message("m", {"i": i}))
    log = [m.meta["i"] for m in flaky.poll()]
    assert log == [0, 2, 2, 3]   # 1 dropped, 2 duplicated
    assert flaky.faults == {"recv:drop": 1, "recv:dup": 1}


def test_flaky_transport_hang_and_kill():
    a, b = LocalChannel.pair()
    flaky = FlakyTransport(a)
    flaky.hang()
    flaky.send(Message("m", {}))
    assert b.poll() == []            # swallowed silently
    b.send(Message("m", {}))
    assert flaky.poll() == []        # drained, never delivered
    assert not flaky.closed          # a hung link still LOOKS open
    flaky.kill()
    assert flaky.closed
    with pytest.raises(ConnectionError):
        flaky.send(Message("m", {}))


# ---------------------------------------------------------------------------
# in-process distributed pod: exactness
# ---------------------------------------------------------------------------


def test_distributed_pod_byte_identical_to_single_engine(
        gpt2_setup, ref_outputs):
    """The layer-3 exactness bar: greedy AND sampled requests routed
    through submit -> wire -> prefill worker -> shipment frame -> decode
    worker -> token sync reproduce the single engine's tokens and
    logprobs byte for byte, with every worker's compile count flat."""
    cfg, params = gpt2_setup
    router = _build_pod(cfg, params, pf=1, dec=2)
    reqs = _submit_traffic(router, cfg)
    _drive(router, reqs)
    ref_tokens, ref_logprobs = ref_outputs
    assert [list(r.tokens) for r in reqs] == ref_tokens
    assert [list(r.logprobs) for r in reqs] == ref_logprobs
    assert router.compile_stats() == {
        "admit": 1, "prefill": 1, "decode": 1, "extract": 1, "install": 1}
    ms = router.metrics_summary()
    assert ms["pod_shipments"] == 4.0
    assert ms["pod_workers_lost"] == 0.0
    assert ms["pod_requests_replayed"] == 0.0
    # the streaming surface matches the terminal token lists
    router.close()


def test_distributed_pod_stream_iterates_tokens(gpt2_setup, ref_outputs):
    cfg, params = gpt2_setup
    router = _build_pod(cfg, params, pf=1, dec=1)
    prompts, budgets, temps = _traffic(cfg)
    req = router.submit(prompts[0], max_new_tokens=budgets[0],
                        temperature=temps[0])
    got = list(router.stream(req))
    assert got == ref_outputs[0][0]
    router.close()


# ---------------------------------------------------------------------------
# failure recovery — every path byte-exact, nothing lost or duplicated
# ---------------------------------------------------------------------------


def _wrap_capture(flaky_by_wid, **flaky_kwargs):
    def wrap(wid, role, ch):
        flaky_by_wid[wid] = FlakyTransport(ch, **flaky_kwargs)
        return flaky_by_wid[wid]

    return wrap


def test_dropped_shipment_recovers_via_stalled_replay(
        gpt2_setup, ref_outputs):
    """Losing a KV shipment frame strands its flight in `prefill`; the
    flight watchdog replays it from the prompt — tokens still exact."""
    cfg, params = gpt2_setup
    state = {"dropped": 0}

    def rules(direction, kind, seq):
        if direction == "recv" and kind == "shipment" \
                and state["dropped"] == 0:
            state["dropped"] += 1
            return "drop"
        return "ok"

    def wrap(wid, role, ch):
        return FlakyTransport(ch, rules=rules) if role == "prefill" else ch

    router = _build_pod(cfg, params, pf=1, dec=1, wrap=wrap,
                           flight_timeout_s=1.0)
    reqs = _submit_traffic(router, cfg)
    _drive(router, reqs)
    assert [list(r.tokens) for r in reqs] == ref_outputs[0]
    assert state["dropped"] == 1
    ms = router.metrics_summary()
    assert ms["pod_requests_replayed"] >= 1.0
    assert ms["pod_workers_lost"] == 0.0   # the worker was fine
    assert any(e["recovery_reason"] == "stalled"
               for e in router.recovery_log)
    router.close()


def test_duplicated_shipment_is_dropped_as_stale(gpt2_setup, ref_outputs):
    """At-least-once delivery: a duplicated shipment frame must land as
    a stale no-op (the flight already advanced), never as a second
    install — tokens exact, stale counter ticks."""
    cfg, params = gpt2_setup

    def rules(direction, kind, seq):
        return "dup" if direction == "recv" and kind == "shipment" else "ok"

    def wrap(wid, role, ch):
        return FlakyTransport(ch, rules=rules) if role == "prefill" else ch

    router = _build_pod(cfg, params, pf=1, dec=1, wrap=wrap)
    reqs = _submit_traffic(router, cfg)
    _drive(router, reqs)
    assert [list(r.tokens) for r in reqs] == ref_outputs[0]
    ms = router.metrics_summary()
    assert ms["pod_stale_messages"] >= 1.0
    assert ms["pod_requests_replayed"] == 0.0
    router.close()


def test_killed_decode_worker_recovers_all_flights_exactly(
        gpt2_setup, ref_outputs):
    """THE acceptance: kill the decode worker that holds live streams
    mid-decode; every in-flight request is replayed by re-prefilling
    prompt+delivered-tokens elsewhere and finishes byte-identical —
    no lost tokens, no duplicated tokens."""
    cfg, params = gpt2_setup
    flaky = {}
    router = _build_pod(cfg, params, pf=1, dec=2,
                           wrap=_wrap_capture(flaky))
    reqs = _submit_traffic(router, cfg)
    for _ in range(6):
        router.step()
    victims = {f.worker for f in router._flights.values()
               if f.phase == "decode"}
    assert victims, "no decode flight landed in 6 steps"
    victim = victims.pop()
    mid_stream = [len(f.user.tokens) for f in router._flights.values()
                  if f.phase == "decode" and f.worker == victim]
    assert any(0 < n for n in mid_stream), "kill happened before streaming"
    flaky[victim].kill()
    _drive(router, reqs)
    assert [list(r.tokens) for r in reqs] == ref_outputs[0]
    # the replayed token's logprob is recomputed by the chunked prefill
    # program instead of the original decode step — same math, different
    # reduction order, so allow a float32 ulp on it
    for got_lp, ref_lp in zip((list(r.logprobs) for r in reqs),
                              ref_outputs[1]):
        assert np.allclose(got_lp, ref_lp, rtol=0, atol=1e-5)
    ms = router.metrics_summary()
    assert ms["pod_workers_lost"] == 1.0
    assert ms["pod_requests_replayed"] >= 1.0
    assert all(e["recovery_reason"] == "channel_drop"
               for e in router.recovery_log)
    assert not router.workers[victim].alive
    router.close()


def test_killed_prefill_worker_requeues_flights(gpt2_setup, ref_outputs):
    """Prefill death mid-prefill: queued/prefilling flights re-queue and
    land on the survivor (soft roles: with the prefill pool empty, the
    decode worker serves prefill too) — tokens exact."""
    cfg, params = gpt2_setup
    flaky = {}
    router = _build_pod(cfg, params, pf=1, dec=1,
                           wrap=_wrap_capture(flaky))
    reqs = _submit_traffic(router, cfg)
    router.step()
    assert any(f.phase == "prefill" for f in router._flights.values())
    flaky[0].kill()   # wid 0 is the prefill worker
    _drive(router, reqs)
    assert [list(r.tokens) for r in reqs] == ref_outputs[0]
    ms = router.metrics_summary()
    assert ms["pod_workers_lost"] == 1.0
    assert any(e["recovery_reason"] == "channel_drop"
               for e in router.recovery_log)
    router.close()


def test_hung_worker_detected_by_heartbeat_timeout(gpt2_setup, ref_outputs):
    """A hung link (open at the transport layer, silent both ways — the
    worker LOOKS alive) is only catchable by missed heartbeats; flights
    replay on the survivor, byte-exact."""
    cfg, params = gpt2_setup
    flaky = {}
    # busy_heartbeat_timeout_s: the victim's last delivered heartbeat may
    # announce busy=True (pre-compile), which legitimately defers the
    # heartbeat verdict — bound that deferral so the fake clock reaches it
    router = _build_pod(cfg, params, pf=1, dec=2,
                           wrap=_wrap_capture(flaky),
                           heartbeat_timeout_s=1.0, flight_timeout_s=30.0,
                           busy_heartbeat_timeout_s=1.0)
    reqs = _submit_traffic(router, cfg)
    for _ in range(6):
        router.step()
    victims = {f.worker for f in router._flights.values()
               if f.phase == "decode"}
    assert victims
    victim = victims.pop()
    flaky[victim].hang()
    _drive(router, reqs)
    assert [list(r.tokens) for r in reqs] == ref_outputs[0]
    ms = router.metrics_summary()
    assert ms["pod_workers_lost"] == 1.0
    assert any(e["recovery_reason"] == "heartbeat_timeout"
               for e in router.recovery_log)
    router.close()


def test_no_lost_requests_under_flake_storm(gpt2_setup, ref_outputs):
    """Seeded random drop/dup/delay/reorder on EVERY link: recovery may
    replay as often as it needs, but every request must finish with the
    exact single-engine tokens — nothing lost, nothing doubled."""
    cfg, params = gpt2_setup
    flaky = {}
    router = _build_pod(
        cfg, params, pf=1, dec=2,
        wrap=_wrap_capture(flaky, flake_rate=0.05, seed=11, delay_ticks=2),
        flight_timeout_s=1.0, max_attempts=10)
    reqs = _submit_traffic(router, cfg)
    _drive(router, reqs, max_steps=20000)
    assert all(r.status.value == "finished" for r in reqs), \
        [(r.status.value, r.reject_reason) for r in reqs]
    assert [list(r.tokens) for r in reqs] == ref_outputs[0]
    assert sum(f.faults.total() for f in flaky.values()) > 0, \
        "storm injected nothing — test is vacuous"
    router.close()


# ---------------------------------------------------------------------------
# elastic rebalancing
# ---------------------------------------------------------------------------


def test_rebalance_converts_idle_prefill_to_decode_once_per_window(
        gpt2_setup):
    """2 prefill + 1 decode with decode saturated: the router converts
    ONE idle prefill worker to decode (hysteresis band + one conversion
    per window — the second spare stays put), and the converted pod
    still finishes everything."""
    cfg, params = gpt2_setup
    router = _build_pod(cfg, params, pf=2, dec=1, rebalance=True,
                           rebalance_window_s=0.2,
                           occupancy_high=0.5, occupancy_low=0.1)
    prompts, _, _ = _traffic(cfg)
    reqs = [router.submit(p, max_new_tokens=8)
            for p in prompts + prompts[:2]]
    _drive(router, reqs)
    ptd = router._c_conversions["prefill_to_decode"].value
    dtp = router._c_conversions["decode_to_prefill"].value
    assert ptd == 1.0, (ptd, router.debug_pod())
    assert dtp == 0.0
    roles = sorted(w.role for w in router.workers.values())
    assert roles == ["decode", "decode", "prefill"]
    assert all(r.status.value == "finished" for r in reqs)
    router.close()


def test_rebalance_window_blocks_flapping(gpt2_setup):
    """No conversion fires before the warm-up window elapses, no matter
    the queue pressure at startup (the first-step-flip regression)."""
    cfg, params = gpt2_setup
    router = _build_pod(cfg, params, pf=1, dec=2, rebalance=True,
                           rebalance_window_s=1e9)
    prompts, _, _ = _traffic(cfg)
    reqs = [router.submit(p, max_new_tokens=6) for p in prompts]
    _drive(router, reqs)
    assert router._c_conversions["prefill_to_decode"].value == 0.0
    assert router._c_conversions["decode_to_prefill"].value == 0.0
    assert sorted(w.role for w in router.workers.values()) == [
        "decode", "decode", "prefill"]
    router.close()


# ---------------------------------------------------------------------------
# telemetry merge + sanitizer
# ---------------------------------------------------------------------------


def test_worker_snapshots_merge_into_router_exposition(gpt2_setup):
    """Heartbeats carry each worker's registry snapshot; the /metrics
    registry holds the router's own series PLUS the transport-backed
    cross-worker merge (no jax process group) under origin=workers."""
    cfg, params = gpt2_setup
    router = _build_pod(cfg, params, pf=1, dec=1)
    reqs = _submit_traffic(router, cfg)
    _drive(router, reqs)
    assert all(w.snapshot for w in router.workers.values()), \
        "heartbeats never delivered a registry snapshot"
    reg = router.exposition_registry()
    rows = {(kind, name, labels): metric
            for kind, name, labels, metric in reg.items()}
    # the router's own series, unlabelled
    assert any(name == "serving_pod_shipments_total" and not labels
               for (_k, name, labels) in rows)
    # worker counters merged as sums under origin=workers
    merged = [(name, labels, m) for (kind, name, labels), m in rows.items()
              if kind == "counter" and dict(labels).get("origin") == "workers"]
    assert merged, "no worker-origin series in the exposition"
    tokens = [m.value for (name, labels, m) in merged
              if name == "serving_tokens_out_total"]
    assert tokens and tokens[0] > 0
    # histogram sketches merged + the straggler signal derived from them
    assert any(name.endswith("__slowest_host_mean")
               for (_k, name, _l) in rows), rows.keys()
    router.close()


def test_sanitizer_catches_corrupted_router_books(gpt2_setup):
    """check_distributed_router: the joins only the router can see —
    corrupt each one and watch it fail loudly. (The one join that lives
    on a worker, its admit-hook snapshots, is
    test_sanitizer.py::test_router_fires_on_stale_admit_snapshot.)"""
    cfg, params = gpt2_setup
    router = _build_pod(cfg, params, pf=1, dec=1)
    reqs = _submit_traffic(router, cfg)
    for _ in range(4):
        router.step()
    check_distributed_router(router)   # healthy mid-run state passes
    flight = next(iter(router._flights.values()))

    # unknown phase
    orig_phase = flight.phase
    flight.phase = "teleporting"
    with pytest.raises(SanitizerViolation):
        check_distributed_router(router)
    flight.phase = orig_phase

    # the no-zombie rule: a flight riding a dead worker
    handle = router.workers[flight.worker] if flight.worker >= 0 else None
    if handle is not None:
        handle.alive, handle.lost = False, True
        with pytest.raises(SanitizerViolation):
            check_distributed_router(router)
        handle.alive, handle.lost = True, False

    # pending deque referencing a flight that is not pending
    router._pending.append(flight.flight_id)
    with pytest.raises(SanitizerViolation):
        check_distributed_router(router)
    router._pending.pop()

    # user-index desync
    key, val = next(iter(router._by_user.items()))
    del router._by_user[key]
    with pytest.raises(SanitizerViolation):
        check_distributed_router(router)
    router._by_user[key] = val

    # a terminal request that still has a live flight
    orig_status = flight.user.status
    flight.user.status = RequestStatus.CANCELLED
    with pytest.raises(SanitizerViolation, match="terminal request"):
        check_distributed_router(router)
    flight.user.status = orig_status

    # more parked shipments than the backpressure bound plus every
    # in-flight prefill could have produced
    orig_bound = router._max_pending
    router._max_pending = -1 - sum(h.slots for h in router.workers.values())
    with pytest.raises(SanitizerViolation, match="backpressure bound"):
        check_distributed_router(router)
    router._max_pending = orig_bound

    # a front-queued request that is not QUEUED
    queued = Request(prompt=np.arange(1, 5, dtype=np.int32),
                     max_new_tokens=2)
    router.scheduler.submit(queued)
    queued.status = RequestStatus.RUNNING
    with pytest.raises(SanitizerViolation, match="front-queued"):
        check_distributed_router(router)
    queued.status = RequestStatus.QUEUED
    assert router.scheduler.cancel(queued)

    check_distributed_router(router)   # restored state passes again
    _drive(router, reqs)
    router.close()


# ---------------------------------------------------------------------------
# distributed tracing, clock alignment, fleet incident bundles (ISSUE 18)
# ---------------------------------------------------------------------------


@pytest.fixture
def _traced():
    """Head-sample every request so plain submits are traced; clean the
    global recorder afterwards (this module has no autouse tracing
    reset)."""
    from accelerate_tpu.telemetry import (clear_flight_recorder,
                                          configure_tracing)

    configure_tracing(enabled=True, annotate=False, default_sample_rate=1.0)
    yield
    configure_tracing(enabled=False, default_sample_rate=0.0)
    clear_flight_recorder()


def test_tracing_staleness_and_fleet_bundle_acceptance(
        gpt2_setup, ref_outputs, _traced, tmp_path, capsys):
    """The ISSUE-18 tentpole on one pod and one kill (tier-1 budget:
    these contracts share the engines and the traffic drive):

    1. propagation — every request's spans from router (dispatch,
       page_transfer), prefill worker (pod.prefill) and decode worker
       (pod.install) land in ONE trace, monotonically ordered, and
       tracing changes no tokens;
    2. replay forensics — the killed flights record `serving.replay`
       linked to the failed attempt's dispatch span, tagged
       recovery_reason=channel_drop;
    3. staleness-honest /metrics — the lost worker's frozen snapshot
       merges under stale="true", its snapshot-age gauge keeps
       counting, and a configured horizon drops it entirely;
    4. fleet incident bundle — worker loss writes ONE bundle (router
       dumps, per-worker stanzas with an honest worker_error hole for
       the dead one, clock offsets, merged chrome traces of in-flight
       requests) and `accelerate-tpu incident show` renders it.
    """
    import json as _json

    from accelerate_tpu.commands.incident import _run_show
    from accelerate_tpu.telemetry import trace_events

    cfg, params = gpt2_setup
    flaky = {}
    router = build_local_distributed_pod(
        gpt2, cfg, params,
        engine_config=_ec(incident_dir=str(tmp_path)),
        pod_config=DistributedPodConfig(
            prefill_workers=1, decode_workers=2, rebalance=False,
            heartbeat_interval_s=0.0, fleet_bundle_min_interval_s=0.0),
        # REAL clock: worker spans are rebased by the NTP offset estimate,
        # and a +0.01/call fake clock ticks hundreds of times between a
        # heartbeat's stamping and its ingestion — the bogus offset would
        # shove rebased spans seconds out of timeline order
        channel_wrap=_wrap_capture(flaky))
    reqs = _submit_traffic(router, cfg)
    for _ in range(6):
        router.step()
    victims = {f.worker for f in router._flights.values()
               if f.phase == "decode"}
    assert victims, "no decode flight landed in 6 steps"
    victim = victims.pop()
    flaky[victim].kill()
    _drive(router, reqs)
    assert [list(r.tokens) for r in reqs] == ref_outputs[0]
    assert router.workers[victim].lost

    # 1. propagation: one ordered timeline per request, across roles
    for r in reqs:
        assert r.trace_sampled and isinstance(r.trace_id, str)
        by_name = {}
        for e in trace_events(r.trace_id):
            by_name.setdefault(e["name"], []).append(e)
        for name in ("serving.pod.dispatch", "serving.pod.prefill",
                     "serving.page_transfer", "serving.pod.install"):
            assert name in by_name, (r.trace_id, sorted(by_name))
        # the acceptance ordering: prefill end <= shipment arrival <=
        # install end, PER ATTEMPT — a replay whose re-prefill already
        # yields the final token finishes at shipment and never grows a
        # transfer/install leg, so attempts can't be compared to each
        # other's legs
        legs = ("serving.pod.prefill", "serving.page_transfer",
                "serving.pod.install")
        ends: dict = {}
        for name in legs:
            for e in by_name[name]:
                a = e["attrs"]["attempt"]
                by = ends.setdefault(a, {})
                by[name] = max(by.get(name, 0),
                               e["start_ns"] + e["dur_ns"])
        full = [by for by in ends.values() if len(by) == len(legs)]
        assert full, ends
        for by in full:
            assert by[legs[0]] <= by[legs[1]] <= by[legs[2]], ends
        # worker-side spans carry the worker attribute for the fleet view
        assert all("worker" in e.get("attrs", {})
                   for e in by_name["serving.pod.install"])

    # 2. replay forensics: linked to the failed dispatch, reason tagged
    replayed = [e["request_id"] for e in router.recovery_log
                if e["recovery_reason"] == "channel_drop"]
    assert replayed
    checked = 0
    for r in reqs:
        if r.request_id not in replayed:
            continue
        events = trace_events(r.trace_id)
        replays = [e for e in events if e["name"] == "serving.replay"]
        assert replays, [e["name"] for e in events]
        dispatch_ids = {e["span_id"] for e in events
                        if e["name"] == "serving.pod.dispatch"}
        for ev in replays:
            assert ev["attrs"]["recovery_reason"] == "channel_drop"
            assert ev.get("links"), "replay span lost its link"
            assert set(ev["links"]) & dispatch_ids, \
                "replay link does not point at a dispatch span"
        checked += 1
    assert checked

    # 3. staleness-honest scrape: kill-then-scrape
    rows = [(name, dict(labels))
            for _k, name, labels, _m in router.exposition_registry().items()]
    age_workers = {l["worker"] for n, l in rows
                   if n == "serving_pod_worker_snapshot_age_seconds"}
    assert str(victim) in age_workers and len(age_workers) >= 2
    assert any(l.get("stale") == "true" for _n, l in rows), \
        "lost worker's series lost their stale label"
    assert any(l.get("origin") == "workers" and l.get("stale") is None
               for _n, l in rows), "survivors' series vanished"
    # past the horizon the dead worker's numbers drop entirely
    import dataclasses as _dc

    router.pod_config = _dc.replace(router.pod_config,
                                    snapshot_stale_after_s=0.0)
    rows2 = [(name, dict(labels))
             for _k, name, labels, _m in router.exposition_registry().items()]
    assert not any(l.get("stale") == "true" for _n, l in rows2)
    assert any(l.get("origin") == "workers" for _n, l in rows2)

    # 4. the fleet bundle + its CLI rendering
    bundles = [p for p in tmp_path.iterdir()
               if p.name.startswith("incident-")]
    fleet = [p for p in bundles if f"fleet-loss-w{victim}" in p.name]
    assert fleet, [p.name for p in bundles]
    bundle = fleet[0]
    report = _json.loads((bundle / "report.json").read_text())
    assert report["kind"] == "fleet_incident"
    assert report["reason"] == "channel_drop"
    offsets = _json.loads((bundle / "clock_offsets.json").read_text())
    assert str(victim) in offsets and offsets[str(victim)]["lost"]
    dead = _json.loads((bundle / f"worker_{victim}.json").read_text())
    assert "worker_error" in dead        # the honest hole
    survivors = [p for p in bundle.glob("worker_*.json")
                 if p.name != f"worker_{victim}.json"]
    assert survivors
    alive = _json.loads(survivors[0].read_text())
    assert "jobs" in alive and "engine" in alive
    traces = _json.loads((bundle / "flights_trace.json").read_text())
    assert traces, "no in-flight trace captured at loss time"
    assert any((doc.get("traceEvents") or []) for doc in traces.values())
    rc = _run_show(str(tmp_path), bundle.name, "text")
    out = capsys.readouterr().out
    assert rc == 0
    assert "fleet clock offsets" in out
    assert f"worker {victim}: UNREACHABLE" in out
    assert "in-flight traces" in out
    router.close()


def test_clock_sync_span_ingest_and_busy_deferral(gpt2_setup, _traced):
    """The heartbeat-side mechanics on one idle pod (no traffic — these
    poke the router's handlers directly):

    - NTP clock estimate: one-way fallback on first contact, round-trip
      correction with EWMA smoothing, negative rtt discarded, one-way
      samples never regress a round-trip estimate, per-worker gauge;
    - span ingest: a heartbeat's batch lands rebased into router time
      exactly once (same `span_seq` = duplicated heartbeat = no-op);
    - busy deferral (the phantom-loss fix): an announced long block
      gets busy_heartbeat_timeout_s of silence, a quiet non-busy
      worker is lost at the tight timeout, and busy is a rope, not
      immortality.
    """
    from accelerate_tpu.telemetry import trace_events

    cfg, params = gpt2_setup
    now = [0.0]
    router = build_local_distributed_pod(
        gpt2, cfg, params, engine_config=_ec(),
        pod_config=DistributedPodConfig(
            prefill_workers=1, decode_workers=1, rebalance=False,
            heartbeat_interval_s=1e9, heartbeat_timeout_s=0.5,
            busy_heartbeat_timeout_s=5.0),
        clock=lambda: now[0])
    handle = next(iter(router.workers.values()))

    # -- NTP estimate -------------------------------------------------------
    # in-process handles short-circuit to offset 0 (shared clock) — mask
    # `local` so the estimator treats this handle as a remote worker
    handle.local = None
    handle.clock_offset_s = handle.clock_rtt_s = None
    # first contact: no echo yet -> one-way T4 - T3
    router._sync_worker_clock(handle, {"t": 95.0}, 100.0)
    assert handle.clock_offset_s == pytest.approx(5.0)
    assert handle.clock_rtt_s is None
    # completed round trip: T1=100.5 T2=95.6 T3=96.0 T4=101.0
    router._sync_worker_clock(
        handle, {"t": 96.0, "ack": {"router_t": 100.5,
                                    "worker_recv_t": 95.6}}, 101.0)
    assert handle.clock_rtt_s == pytest.approx(0.1)
    # sample ((100.5-95.6)+(101-96))/2 = 4.95, EWMA 0.75*5 + 0.25*4.95
    assert handle.clock_offset_s == pytest.approx(4.9875)
    # a clock stepped mid-round (rtt < 0): the sample is discarded
    router._sync_worker_clock(
        handle, {"t": 200.0, "ack": {"router_t": 100.9,
                                     "worker_recv_t": 95.9}}, 101.0)
    assert handle.clock_offset_s == pytest.approx(4.9875)
    # a later echo-less heartbeat must not regress to the one-way guess
    router._sync_worker_clock(handle, {"t": 90.0}, 102.0)
    assert handle.clock_offset_s == pytest.approx(4.9875)
    gauges = {labels: m.value
              for kind, name, labels, m in router.registry.items()
              if name == "serving_pod_worker_clock_offset_seconds"}
    assert gauges[(("worker", str(handle.worker_id)),)] \
        == pytest.approx(4.9875)

    # -- span ingest + dedup ------------------------------------------------
    handle.clock_offset_s = 2.0
    before = router._c_spans.value
    ev = {"name": "w-side", "trace_id": "req-dedup",
          "start_ns": 1_000, "dur_ns": 5}
    router._ingest_worker_spans(handle, {"spans": [ev], "span_seq": 5}, 1.0)
    got = trace_events("req-dedup")
    assert len(got) == 1
    assert got[0]["start_ns"] == 1_000 + int(2.0 * 1e9)   # rebased
    # the duplicated heartbeat: same high-water mark, no double ingest
    router._ingest_worker_spans(handle, {"spans": [ev], "span_seq": 5}, 2.0)
    assert len(trace_events("req-dedup")) == 1
    # a genuinely new batch advances
    router._ingest_worker_spans(
        handle, {"spans": [dict(ev, span_id=9)], "span_seq": 6}, 3.0)
    assert len(trace_events("req-dedup")) == 2
    assert router._c_spans.value == before + 2

    # -- busy deferral of heartbeat_timeout ---------------------------------
    for h in router.workers.values():      # registered, not yet stepped:
        h.alive, h.last_heartbeat, h.busy = True, 0.0, True
    handle, other = list(router.workers.values())[:2]
    now[0] = 2.0                       # 4x the plain timeout, but busy
    router._detect_failures()
    assert not handle.lost and not other.lost, \
        "busy-not-dead became a phantom loss"
    handle.busy = False                # same silence, no busy announce
    router._detect_failures()
    assert handle.lost and not other.lost
    # and busy is a rope, not immortality
    now[0] = 6.0
    router._detect_failures()
    assert other.lost
    router.close()


# ---------------------------------------------------------------------------
# the two-OS-process socket smoke (the acceptance harness)
# ---------------------------------------------------------------------------


def test_socket_pod_two_process_smoke():
    """Real `pod-worker` OS processes dialing a ChannelListener over
    TCP: byte-exactness across the process boundary (greedy + sampled,
    compile-flat) AND SIGKILL-a-decode-worker recovery — see
    pod_distributed_script.py for the full contract."""
    from accelerate_tpu.test_utils import execute_subprocess

    script = os.path.join(os.path.dirname(__file__),
                          "pod_distributed_script.py")
    out = execute_subprocess(
        [sys.executable, script], env={"JAX_PLATFORMS": "cpu"}, timeout=420)
    assert "PHASE1_EXACT_OK" in out
    assert "PHASE2_RECOVERY_OK" in out
    assert "PHASE2_TRACE_OK" in out
    assert "POD_DIST_OK" in out
