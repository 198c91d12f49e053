"""True multi-host pod acceptance script (run as a subprocess).

Launched by tests/test_pod_distributed.py through `execute_subprocess`:
this process is the ROUTER; it binds a `ChannelListener` and spawns real
`accelerate-tpu pod-worker` OS processes (via `spawn_socket_workers`)
that dial back over TCP. Proves, across genuine process boundaries:

- phase 1 (exactness): greedy AND sampled requests routed prefill ->
  shipment -> decode over the socket wire produce byte-identical tokens
  and logprobs to a single in-process Engine built from the same spec,
  with worker compile counts flat at admit/prefill/decode/extract/
  install = 1;
- phase 2 (recovery): SIGKILLing the decode worker's PROCESS mid-stream
  recovers every in-flight request by re-prefill-from-prompt on the
  survivor (soft roles: the prefill worker serves decode once the
  decode pool is empty), byte-identical, nothing lost or duplicated;
- tracing (ISSUE 18): the whole run samples every request
  (`ACCELERATE_TPU_TRACE=1` inherited by the worker processes), so the
  SIGKILL also proves the observability tentpole: the killed flight's
  fleet incident bundle holds ONE merged chrome trace with spans from
  BOTH worker processes rebased into router time and monotonically
  ordered (prefill end <= shipment <= install), the replay span is
  linked to the failed dispatch with recovery_reason=channel_drop, and
  `accelerate-tpu incident show` renders the bundle.

Prints POD_DIST_OK on success; any mismatch asserts (the parent test
surfaces the child's output).
"""

import os
import sys
import tempfile
import time

os.environ.setdefault("JAX_PLATFORMS", "cpu")
os.environ.setdefault("ACCELERATE_TPU_SANITIZE", "1")
# tracing + incident capture on for THIS process and (via env
# inheritance through spawn_socket_workers) every pod-worker process
os.environ.setdefault("ACCELERATE_TPU_TRACE", "1")
_INCIDENT_DIR = os.environ.setdefault(
    "ACCELERATE_TPU_INCIDENT_DIR",
    tempfile.mkdtemp(prefix="pod_incidents_"))

import jax  # noqa: E402

import numpy as np  # noqa: E402

# opt in to the parent fixture's exported compilation cache (no-op when
# the env var is unset): the router's reference engine reuses compiles
# already paid by earlier in-process tests in the same module
from accelerate_tpu.utils.environment import (  # noqa: E402
    configure_compilation_cache)

configure_compilation_cache()

from accelerate_tpu.commands.pod import spawn_socket_workers  # noqa: E402
from accelerate_tpu.serving.pod.distributed import (  # noqa: E402
    ChannelListener,
    DistributedPodConfig,
    DistributedPodRouter,
)
from accelerate_tpu.serving.pod.distributed.worker import (  # noqa: E402
    build_worker_engine,
    engine_config_from_spec,
)
from accelerate_tpu.telemetry import (  # noqa: E402
    configure_tracing,
    trace_events,
)

# the env var enabled recording at import; head-sample 100% so every
# plain submit below is a traced request
configure_tracing(enabled=True, annotate=False, default_sample_rate=1.0)

SPEC = {"family": "gpt2", "seed": 0, "num_slots": 3, "max_len": 64,
        "prefill_chunk": 8, "page_size": 8, "cache_dtype": "float32"}


def traffic(rng_seed=7):
    rng = np.random.default_rng(rng_seed)
    prompts = [rng.integers(1, 256, size=n).tolist() for n in (5, 11, 3, 9)]
    budgets = [8, 8, 6, 6]
    temps = [0.0, 0.7, 0.0, 1.1]   # greedy AND sampled, same trace
    return prompts, budgets, temps


class _EarlyTokensOnly:
    """The victim's channel as the router reads it in phase 2: `tokens`
    frames past the third token never arrive, as if they were still in
    flight when the connection died. The router's picture of every
    stream on the victim then stops mid-stream however fast the worker
    really decodes, so the SIGKILL below cannot come too late: the kill
    window does not depend on who gets the CPU."""

    def __init__(self, inner):
        self._inner = inner

    def poll(self):
        return [m for m in self._inner.poll()
                if m.kind != "tokens" or len(m.meta["tokens"]) <= 3]

    def __getattr__(self, name):
        return getattr(self._inner, name)


def drive(router, reqs, deadline_s=120.0):
    deadline = time.monotonic() + deadline_s
    while not all(r.done for r in reqs):
        router.step()
        assert time.monotonic() < deadline, (
            "pod wedged: " + repr(router.debug_pod()))
        time.sleep(0.002)


def main() -> None:
    # spawn the workers FIRST: their engine builds (the wall-clock
    # dominator) overlap the parent's reference build below
    listener = ChannelListener("127.0.0.1", 0)
    procs = spawn_socket_workers(
        listener.port, SPEC, ["prefill", "decode"],
        heartbeat_interval_s=0.05, env=dict(os.environ),
        stderr=sys.stderr)

    # the single-process reference: same spec -> same params bytes
    _family, _cfg, _params, ref_engine = build_worker_engine(SPEC)
    prompts, budgets, temps = traffic()
    # phase 2 streams longer than the three tokens `_EarlyTokensOnly`
    # lets the router see, so every stream is cut mid-decode
    budgets2 = [24, 24, 16, 16]
    # the trace runs TWICE (phase 1 exactness, phase 2 recovery) and
    # sampling keys fold in the request id, so the reference must burn
    # the same ids: batch one gets ids 1..4, batch two ids 5..8
    ref_batches = []
    for bs in (budgets, budgets2):
        ref_reqs = [ref_engine.submit(np.asarray(p, np.int32),
                                      max_new_tokens=b, temperature=t)
                    for p, b, t in zip(prompts, bs, temps)]
        ref_engine.run_until_idle()
        ref_batches.append(([list(r.tokens) for r in ref_reqs],
                            [list(r.logprobs) for r in ref_reqs]))
    (ref_tokens, ref_logprobs), (ref_tokens2, ref_logprobs2) = ref_batches
    router = DistributedPodRouter(
        engine_config=engine_config_from_spec(SPEC),
        pod_config=DistributedPodConfig(
            prefill_workers=1, decode_workers=1,
            # a worker handling its FIRST prefill is compiling and can't
            # heartbeat — the timeout must dwarf a loaded-box compile
            # (phase 2's SIGKILL is caught instantly via channel_drop,
            # which doesn't wait on this)
            heartbeat_interval_s=0.05, heartbeat_timeout_s=120.0,
            # generous: on a loaded box the first prefill includes the
            # compile, and a spurious "stalled" replay would break the
            # phase-1 logprob EXACTNESS bar (replayed logprob = one ulp)
            flight_timeout_s=300.0, rebalance=False),
        listener=listener)
    try:
        deadline = time.monotonic() + 180.0
        while sum(1 for w in router.workers.values() if w.alive) < 2:
            router.step()
            assert all(p.poll() is None for p in procs), \
                [p.returncode for p in procs]
            assert time.monotonic() < deadline, "workers never joined"
            time.sleep(0.05)

        # phase 1: byte-exactness across the process boundary
        reqs = [router.submit(p, max_new_tokens=b, temperature=t)
                for p, b, t in zip(prompts, budgets, temps)]
        drive(router, reqs)
        got = [list(r.tokens) for r in reqs]
        assert got == ref_tokens, f"{got} != {ref_tokens}"
        lps = [list(r.logprobs) for r in reqs]
        assert lps == ref_logprobs, "logprobs diverged"
        # give the post-completion heartbeats a beat to land, then check
        # the fleet-wide compile envelope stayed flat
        hb_deadline = time.monotonic() + 10.0
        while time.monotonic() < hb_deadline:
            router.step()
            if router.compile_stats() == {
                    "admit": 1, "prefill": 1, "decode": 1,
                    "extract": 1, "install": 1}:
                break
            time.sleep(0.05)
        stats = router.compile_stats()
        assert stats == {"admit": 1, "prefill": 1, "decode": 1,
                         "extract": 1, "install": 1}, stats
        print("PHASE1_EXACT_OK", flush=True)

        # phase 2: SIGKILL the decode worker process mid-stream
        victim = next(w for w in router.workers.values()
                      if w.role == "decode")
        victim.channel = _EarlyTokensOnly(victim.channel)
        reqs = [router.submit(p, max_new_tokens=b, temperature=t)
                for p, b, t in zip(prompts, budgets2, temps)]
        # wait for a decode flight with decoded tokens delivered AND for
        # both workers' spans of its trace (prefill from worker A,
        # install from worker B) to ride a heartbeat into the router's
        # recorder — the fleet bundle below must contain the whole
        # cross-process timeline. The flight cannot finish meanwhile:
        # the router never sees its fourth token.
        deadline = time.monotonic() + 120.0
        candidates = {}
        while not candidates:
            router.step()
            candidates = {
                f.user.request_id: f.user.trace_id
                for f in router._flights.values()
                if f.phase == "decode" and f.worker == victim.worker_id
                and len(f.user.tokens) >= 2
                and {"serving.pod.prefill", "serving.pod.install"}
                <= {e["name"] for e in trace_events(f.user.trace_id)}}
            assert time.monotonic() < deadline, \
                "no traced decode flight landed"
            time.sleep(0.002)
        worker_pids = {w.pid for w in router.workers.values() if w.pid}
        procs[victim.worker_id].kill()
        drive(router, reqs)
        got = [list(r.tokens) for r in reqs]
        assert got == ref_tokens2, (
            f"recovery diverged: {got} != {ref_tokens2}")
        # tokens are byte-exact; the REPLAYED token's logprob is
        # recomputed by the chunked prefill program instead of the
        # original decode step — same math, different reduction order,
        # so it can differ by a float32 ulp
        for a, b in zip((list(r.logprobs) for r in reqs), ref_logprobs2):
            assert np.allclose(a, b, rtol=0, atol=1e-5), (a, b)
        ms = router.metrics_summary()
        assert ms["pod_workers_lost"] == 1.0, ms
        assert ms["pod_requests_replayed"] >= 1.0, ms
        reasons = {e["recovery_reason"] for e in router.recovery_log}
        assert reasons <= {"channel_drop", "heartbeat_timeout"}, reasons
        print("PHASE2_RECOVERY_OK", flush=True)

        # the observability tentpole, across real process boundaries:
        # 1) the replay span lives in the killed request's own trace,
        #    linked to the failed attempt's dispatch span
        replayed = {e["request_id"] for e in router.recovery_log
                    if e["recovery_reason"] == "channel_drop"}
        hit = [tid for rid, tid in candidates.items() if rid in replayed]
        assert hit, (candidates, list(router.recovery_log))
        killed_tid = hit[0]
        events = trace_events(killed_tid)
        replays = [e for e in events if e["name"] == "serving.replay"]
        assert replays, sorted({e["name"] for e in events})
        dispatch_ids = {e["span_id"] for e in events
                        if e["name"] == "serving.pod.dispatch"}
        assert any(e["attrs"]["recovery_reason"] == "channel_drop"
                   and set(e.get("links", ())) & dispatch_ids
                   for e in replays), replays
        # 2) the worker loss wrote ONE fleet bundle holding the killed
        #    flight's merged chrome trace: spans from BOTH worker
        #    processes rebased into router time, monotonically ordered
        import json

        bundles = sorted(d for d in os.listdir(_INCIDENT_DIR)
                         if f"fleet-loss-w{victim.worker_id}" in d)
        assert bundles, os.listdir(_INCIDENT_DIR)
        bundle = os.path.join(_INCIDENT_DIR, bundles[-1])
        with open(os.path.join(bundle, "flights_trace.json")) as f:
            traces = json.load(f)
        doc = traces.get(str(killed_tid))
        assert doc, (sorted(traces), killed_tid)
        tes = doc["traceEvents"]
        pids = {e["pid"] for e in tes}
        assert worker_pids <= pids, (worker_pids, pids)
        end = {}
        for e in tes:
            end[e["name"]] = max(end.get(e["name"], float("-inf")),
                                 e["ts"] + e["dur"])
        with open(os.path.join(bundle, "clock_offsets.json")) as f:
            offsets = json.load(f)
        # clock-alignment error bound: the estimator is honest about its
        # own precision (+-rtt/2 per worker, EWMA-lagged) — on a loaded
        # single-core box "rtt" includes whole engine steps, so the
        # bound must come from the measured rtt, not a localhost guess
        tol_us = (0.1 + sum(w.get("rtt_s") or 0.0
                            for w in offsets.values())) * 1e6
        assert end["serving.pod.prefill"] \
            <= end["serving.page_transfer"] + tol_us \
            <= end["serving.pod.install"] + 2 * tol_us, (end, offsets)
        assert offsets[str(victim.worker_id)]["lost"], offsets
        with open(os.path.join(bundle,
                               f"worker_{victim.worker_id}.json")) as f:
            dead = json.load(f)
        assert "worker_error" in dead, dead   # the honest hole
        survivor = next(w for w in router.workers.values()
                        if w.worker_id != victim.worker_id)
        with open(os.path.join(bundle,
                               f"worker_{survivor.worker_id}.json")) as f:
            alive = json.load(f)
        assert "jobs" in alive and "engine" in alive, sorted(alive)
        # 3) the CLI renders the fleet view of that bundle
        import contextlib
        import io

        from accelerate_tpu.commands.incident import _run_show

        buf = io.StringIO()
        with contextlib.redirect_stdout(buf):
            rc = _run_show(_INCIDENT_DIR, os.path.basename(bundle),
                           "text")
        shown = buf.getvalue()
        assert rc == 0, shown
        assert "fleet clock offsets" in shown, shown
        assert f"worker {victim.worker_id}: UNREACHABLE" in shown, shown
        assert "in-flight traces" in shown, shown
        print("PHASE2_TRACE_OK", flush=True)
    finally:
        router.close()
        for p in procs:
            if p.poll() is None:
                p.terminate()
        for p in procs:
            try:
                p.wait(timeout=15)
            except Exception:
                p.kill()

    print("POD_DIST_OK")


if __name__ == "__main__":
    main()
