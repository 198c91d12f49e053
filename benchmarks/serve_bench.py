"""Offered-load serving benchmark: engine-direct and through the HTTP door.

Open-loop harness: request arrivals are a seeded Poisson process (the
offered load), prompts/token budgets draw from seeded ranges, and the
engine is stepped continuously — arrivals land whenever the wall clock
passes their timestamp, exactly like traffic hitting a server that is
already busy. Closed-loop driving (submit, drain, repeat) would hide
queueing: TTFT under load IS the queue, so the clock must keep running
while the engine works.

Emits ONE JSON line:

  {"metric": "serving_tokens_per_sec", "value": ..., "unit": "tokens/s",
   "extra": {"ttft_p50_ms": ..., "ttft_p99_ms": ...,
             "per_token_p50_ms": ..., "per_token_p99_ms": ...,
             "requests_finished": ..., "requests_rejected": ...,
             "requests_expired": ..., "slot_occupancy_mean": ...,
             "prefix_hit_rate": ..., "cached_token_fraction": ...,
             "decode_mfu": ..., "decode_mxu_idle_fraction": ...,
             "decode_device_time_mean_ms": ..., "goodput": ...,
             "compiles_decode": 1, ...}}

The roofline fields (decode MFU / HBM-bandwidth utilization / MXU-idle
fraction, measured device-time percentiles) and `goodput` come from the
engine's cost table (ISSUE 11, telemetry/cost.py) — sampled fence-pair
device timing against the per-program FLOPs/bytes cost table, nominal
peaks off TPU. Gate a run against a previous one with
`accelerate-tpu bench-diff old.json new.json`.

`--prefix-pool N --prefix-len L` switches the prompt generator to
shared-prefix traffic (each prompt = one of N fixed L-token prefixes + a
unique suffix) — the workload the paged KV cache's radix-tree prefix
reuse is built for; `--no-prefix-cache` is the A/B baseline on the same
trace.

`--pod-roles prefill=N,decode=M` drives the same offered load through a
DISAGGREGATED pod (`serving.pod.PodEngine`): N prefill workers produce
KV pages that ship to M decode workers owning the slots; `--pod-tp K`
additionally mesh-shards every worker over K devices. The summary then
carries the pod counters (`pod_shipments`, `pod_pages_shipped`,
`pod_backpressure_stalls`) next to the usual latency percentiles.
Both transports run the one router (`DistributedPodRouter`), so the
summary also carries the recovery counters (`pod_requests_replayed`,
`pod_workers_lost`, `pod_recovery_latency_*`). `--pod-transport socket`
is the A/B arm for the TRUE multi-host pod: the same roles run as real
`pod-worker` OS processes dialing the router over TCP, so the delta
against the default `local` transport (workers in this process) is the
wire + process-boundary cost.

`--tenants` switches to the MULTI-TENANT HTTP harness (`run_http_load`):
the real `accelerate_tpu.server` front door is stood up in-process on an
ephemeral port and per-tenant client fleets drive it over actual HTTP —
open-loop (Poisson or bursty arrivals at each tenant's `rate`),
closed-loop (`concurrency` workers per tenant in submit-wait-repeat),
or `--trace FILE` replay of a recorded arrival schedule. Per-tier
TTFT/per-token percentiles and SLO attainment come from the server's
OWN Prometheus /metrics route (the same series a production scrape
would read), next to client-observed TTFT and 429/shed counts::

  --tenants 'gold:priority=0,weight=4,slo=0.3,rate=10;bronze:rate=40'

`python benchmarks/serve_bench.py --help` for knobs; the defaults are a
CPU-safe tiny-llama smoke. `run_offered_load`/`run_http_load` are
importable — the tier-1 bench-contract tests drive miniature loads
through them in-process, and bench.py's serving/server rows reuse them
for the one-line JSON contract.
"""

from __future__ import annotations

import argparse
import json
import time


def _tiny_family(family_name: str):
    """(family module, tiny config) of the named family."""
    if family_name == "llama":
        from accelerate_tpu.models import llama as family

        return family, family.LlamaConfig.tiny()
    if family_name == "gpt2":
        from accelerate_tpu.models import gpt2 as family

        return family, family.GPT2Config.tiny()
    raise ValueError(f"unknown family {family_name!r}")


def build_tiny_engine(family_name: str = "llama", num_slots: int = 4,
                      max_len: int = 128, prefill_chunk: int = 16,
                      max_queue: int = 64, seed: int = 0,
                      metrics_port: int | None = None,
                      page_size: int = 16, prefix_cache: bool = True,
                      tenants=None, kv_dtype=None,
                      paged_attention="auto", speculative: bool = False,
                      draft_k: int = 4, num_pages: int | None = None,
                      host_tier_bytes: int = 0):
    """A small engine on the named family (tiny config, fresh params).
    `metrics_port` turns on the engine's Prometheus endpoint (0 binds an
    ephemeral port, reported on `engine.metrics_server.port`);
    `prefix_cache=False` keeps the paged cache but disables cross-request
    prefix reuse (the A/B baseline for the shared-prefix workload);
    `kv_dtype="int8"` quantizes the KV pool and `paged_attention`
    selects the decode attention op (True = Pallas kernel, False =
    dense-gather reference, "auto" = kernel on single-device TPU) — the
    A/B axes of the paged-attention bench. `speculative=True` turns on
    draft-model speculative decoding with a SELF-DRAFT (the same tiny
    model drafts for itself): with random-init benchmark weights only an
    identical draft agrees with the target, so the self-draft is the
    honest way to measure the MECHANISM — verify-batching efficiency,
    tokens-per-decode-step at accept rate ~1.0, compile-count flatness.
    Production deployments pass a real distilled family pair through
    `EngineConfig(speculative=(family, config, params))` instead."""
    import jax
    import jax.numpy as jnp

    from accelerate_tpu.serving import Engine, EngineConfig

    family, cfg = _tiny_family(family_name)
    params = family.init_params(cfg, jax.random.key(seed))
    ec = EngineConfig(num_slots=num_slots, max_len=max_len,
                      prefill_chunk=prefill_chunk, max_queue=max_queue,
                      cache_dtype=jnp.bfloat16, seed=seed,
                      page_size=page_size, prefix_cache=prefix_cache,
                      metrics_port=metrics_port, tenants=tenants,
                      kv_dtype=kv_dtype, paged_attention=paged_attention,
                      speculative=((family, cfg, params) if speculative
                                   else None),
                      draft_k=draft_k, num_pages=num_pages,
                      host_tier_bytes=host_tier_bytes)
    return Engine(family, cfg, params, ec), cfg


def parse_pod_roles(arg: str) -> tuple[int, int]:
    """'prefill=N,decode=M' -> (N, M). Order-insensitive; both required."""
    roles = {}
    for part in arg.split(","):
        name, _, val = part.strip().partition("=")
        if name not in ("prefill", "decode") or not val.isdigit():
            raise ValueError(
                f"bad --pod-roles entry {part!r} (want prefill=N,decode=M)")
        if name in roles:
            raise ValueError(
                f"--pod-roles names {name!r} twice — a typo'd duplicate "
                "would silently run the wrong worker split")
        roles[name] = int(val)
    if set(roles) != {"prefill", "decode"}:
        raise ValueError(
            f"--pod-roles needs BOTH roles, got {sorted(roles)}")
    return roles["prefill"], roles["decode"]


def build_tiny_pod(family_name: str = "llama", pod_roles=(1, 1),
                   transport: str = "local", tensor_parallel: int = 1,
                   num_slots: int = 4, max_len: int = 128,
                   prefill_chunk: int = 16, max_queue: int = 64,
                   seed: int = 0, page_size: int = 16,
                   prefix_cache: bool = True, kv_dtype=None,
                   metrics_port: int | None = None,
                   worker_wait_s: float = 180.0, trace: bool = False,
                   **local_engine):
    """A disaggregated pod on the named family, behind the one router
    (`DistributedPodRouter`): `pod_roles=(N, M)` prefill/decode workers.
    Same submit/step surface as the single engine, so `run_offered_load`
    drives it unchanged. `kv_dtype="int8"` quantizes every worker's pool
    AND the page shipments between them (half the wire bytes).

    `transport="local"` keeps the workers in this process
    (`serving.pod.PodEngine`), optionally `tensor_parallel` chips per
    worker; `local_engine` are `EngineConfig` fields only such a pod can
    take (tenants, paged_attention, num_pages, host_tier_bytes).
    `transport="socket"` is the TRUE multi-host pod: N+M real
    `pod-worker` OS processes dialing this process's listener over TCP,
    each building its engine from the JSON spec — the A/B against
    `local` prices the wire + process boundary.

    Returns (router, cfg, procs): `procs` are the socket workers (empty
    for `local`); the caller owns `router.close()` and reaping them."""
    import os
    import sys as _sys
    import time as _time

    import jax

    import accelerate_tpu
    from accelerate_tpu.commands.pod import spawn_socket_workers
    from accelerate_tpu.serving.pod import PodConfig, PodEngine, PodRouter
    from accelerate_tpu.serving.pod.distributed import ChannelListener
    from accelerate_tpu.serving.pod.distributed.worker import (
        engine_config_from_spec)

    family, cfg = _tiny_family(family_name)
    spec = {"family": family_name, "seed": seed, "num_slots": num_slots,
            "max_len": max_len, "prefill_chunk": prefill_chunk,
            "page_size": page_size, "max_queue": max_queue,
            "cache_dtype": "bfloat16", "kv_dtype": kv_dtype,
            "prefix_cache": prefix_cache}
    ec = engine_config_from_spec(spec, metrics_port=metrics_port,
                                 **local_engine)
    pc = PodConfig(
        prefill_workers=pod_roles[0], decode_workers=pod_roles[1],
        tensor_parallel=tensor_parallel,
        # first-request compiles stall worker heartbeats; generous
        # timeouts keep a loaded box from counting phantom losses
        heartbeat_timeout_s=120.0, flight_timeout_s=300.0)
    if transport == "local":
        params = family.init_params(cfg, jax.random.key(seed))
        return PodEngine(family, cfg, params, ec, pc), cfg, []
    if local_engine or tensor_parallel != 1:
        raise ValueError(
            "a socket pod-worker builds its engine from the JSON spec: "
            f"{sorted(local_engine) + ['tensor_parallel']} reach only "
            "in-process workers")
    roles = ["prefill"] * pod_roles[0] + ["decode"] * pod_roles[1]
    if jax.devices()[0].platform == "tpu":
        # a chip belongs to one process at a time: this process holds the
        # TPU already (it drives the load and builds engines), and every
        # worker it would start builds an engine on the default device
        # too — they would fail at start-up or hang. Nothing here pins a
        # worker to a chip of its own, so however many chips the host
        # has, N+M+1 processes cannot share them.
        raise RuntimeError(
            f"the socket pod starts {len(roles)} worker processes that "
            "each need the accelerator while this process holds it "
            f"({len(jax.devices())} TPU chip(s), one process per chip): not "
            "runnable on one TPU host from one launcher. Run it on the CPU "
            "(JAX_PLATFORMS=cpu), or start router and workers on hosts of "
            "their own with `accelerate-tpu pod-router --no-spawn` / "
            "`pod-worker`.")
    listener = ChannelListener("127.0.0.1", 0)
    # workers must import accelerate_tpu from this checkout even when it
    # is not pip-installed
    env = dict(os.environ)
    pkg_root = os.path.dirname(os.path.dirname(
        os.path.abspath(accelerate_tpu.__file__)))
    env["PYTHONPATH"] = os.pathsep.join(
        p for p in (pkg_root, env.get("PYTHONPATH", "")) if p)
    if trace:
        # distributed tracing A/B arm: workers record + export spans
        # (env flag is read at their import), router samples every
        # request so the ingest path is fully exercised
        from accelerate_tpu.telemetry.trace import configure_tracing

        env["ACCELERATE_TPU_TRACE"] = "1"
        configure_tracing(enabled=True, default_sample_rate=1.0)
    procs = spawn_socket_workers(listener.port, spec, roles, env=env,
                                 stderr=_sys.stderr)
    router = PodRouter(engine_config=ec, pod_config=pc, listener=listener)
    deadline = _time.monotonic() + worker_wait_s
    while sum(1 for w in router.workers.values() if w.alive) < len(roles):
        router.step()
        dead = [p.returncode for p in procs if p.poll() is not None]
        if dead:
            raise RuntimeError(f"pod worker died before hello (rc={dead})")
        if _time.monotonic() > deadline:
            raise RuntimeError(
                f"only {sum(1 for w in router.workers.values() if w.alive)}"
                f"/{len(roles)} pod workers joined within {worker_wait_s}s")
        _time.sleep(0.05)
    return router, cfg, procs


def run_offered_load(
    engine,
    vocab_size: int,
    num_requests: int = 16,
    rate_hz: float = 50.0,
    prompt_len: tuple[int, int] = (4, 24),
    max_new_tokens: tuple[int, int] = (4, 16),
    temperature: float = 0.0,
    deadline_s: float | None = None,
    seed: int = 0,
    warmup_requests: int = 1,
    prefix_pool: int = 0,
    prefix_len: int = 0,
) -> dict:
    """Drive `num_requests` Poisson arrivals at `rate_hz` through the
    engine; returns the flat metrics summary plus load parameters.

    `warmup_requests` run to completion first (compile + first dispatch)
    and are excluded from the reported distributions.

    With `prefix_pool`/`prefix_len` set, prompts model shared-prefix
    traffic (system prompts, few-shot headers): each prompt is a prefix
    sampled from a pool of `prefix_pool` fixed `prefix_len`-token
    prefixes, plus a unique suffix drawn from `prompt_len`. The summary
    then carries `prefix_hit_rate` and `cached_token_fraction` from the
    engine's prefix-cache counters.
    """
    import numpy as np

    rng = np.random.default_rng(seed)
    prefixes = [rng.integers(0, vocab_size, (prefix_len,)).astype(np.int32)
                for _ in range(prefix_pool)] if prefix_pool and prefix_len \
        else []

    def make_prompt():
        n = int(rng.integers(prompt_len[0], prompt_len[1] + 1))
        suffix = rng.integers(0, vocab_size, (n,)).astype(np.int32)
        if not prefixes:
            return suffix
        return np.concatenate(
            [prefixes[int(rng.integers(len(prefixes)))], suffix])

    def budget():
        return int(rng.integers(max_new_tokens[0], max_new_tokens[1] + 1))

    for _ in range(warmup_requests):
        engine.submit(make_prompt(), max_new_tokens=budget(),
                      temperature=temperature)
    engine.run_until_idle()
    engine.reset_metrics()  # drop warmup samples; programs stay compiled

    gaps = rng.exponential(1.0 / rate_hz, size=num_requests)
    start = time.perf_counter()
    arrivals = start + np.cumsum(gaps)
    submitted = 0
    while submitted < num_requests or engine.scheduler.has_work():
        now = time.perf_counter()
        while submitted < num_requests and arrivals[submitted] <= now:
            engine.submit(make_prompt(), max_new_tokens=budget(),
                          temperature=temperature, deadline_s=deadline_s)
            submitted += 1
        if not engine.step() and submitted < num_requests:
            # idle before the next arrival: sleep to it (open loop)
            time.sleep(max(0.0, arrivals[submitted] - time.perf_counter()))

    out = engine.metrics_summary()
    out.update({
        "offered_rate_hz": rate_hz,
        "num_requests": float(num_requests),
        "wall_s": round(time.perf_counter() - start, 3),
    })
    if prefixes:
        out.update({"prefix_pool": float(prefix_pool),
                    "prefix_len": float(prefix_len)})
    return out


# ---------------------------------------------------------------------------
# multi-tenant HTTP harness
# ---------------------------------------------------------------------------


def parse_prometheus(text: str) -> dict:
    """Prometheus text exposition -> {(name, (('k','v'),...)): value}.
    Minimal on purpose (counters/gauges/summary quantiles as flat
    samples) — exactly what the attainment report needs."""
    out = {}
    for line in text.splitlines():
        line = line.strip()
        if not line or line.startswith("#"):
            continue
        metric, _, raw = line.rpartition(" ")
        name, _, inner = metric.partition("{")
        labels = ()
        if inner:
            pairs = []
            for part in inner.rstrip("}").split(","):
                k, _, v = part.partition("=")
                pairs.append((k.strip(), v.strip().strip('"')))
            labels = tuple(sorted(pairs))
        try:
            out[(name, labels)] = float(raw)
        except ValueError:
            continue
    return out


def _prom_tenant(series: dict, name: str, tenant: str,
                 quantile: str | None = None) -> float | None:
    want = {("tenant", tenant)}
    if quantile is not None:
        want.add(("quantile", quantile))
    for (n, labels), v in series.items():
        if n == name and want <= set(labels):
            return v
    return None


def parse_tenant_load_arg(arg: str):
    """The harness grammar: TenantSpec fields + per-tenant load fields
    (`rate` arrivals/s for open loop, `concurrency` workers for closed
    loop). Returns (specs, {tenant: {"rate":…, "concurrency":…}})."""
    from accelerate_tpu.server.config import parse_tenants_arg

    return parse_tenants_arg(
        arg, extra_keys={"rate": float, "concurrency": int})


def load_trace(path: str) -> list[dict]:
    """Arrival-trace replay: JSONL of {"t": offset_s, "tenant": name,
    "prompt_len": N | "prompt": [ids], "max_new_tokens": M} sorted by t.
    Recorded once, replayed identically against any scheduler build —
    the apples-to-apples input for policy A/Bs."""
    rows = []
    with open(path) as f:
        for line in f:
            line = line.strip()
            if line:
                rows.append(json.loads(line))
    return sorted(rows, key=lambda r: float(r.get("t", 0.0)))


def _arrival_offsets(mode: str, rate_hz: float, n: int, rng) -> list[float]:
    """Open-loop arrival schedule: seeded Poisson, or bursty (the same
    mean rate delivered as geometric bursts — the overload shape that
    separates an SLO-aware queue from a FIFO)."""
    if mode == "poisson":
        return list(rng.exponential(1.0 / rate_hz, size=n).cumsum())
    if mode == "burst":
        out, t, i = [], 0.0, 0
        while i < n:
            size = min(int(rng.geometric(0.25)), n - i)
            out.extend([t] * size)
            i += size
            t += size / rate_hz  # mean rate preserved
        return out
    raise ValueError(f"unknown arrival mode {mode!r} (poisson|burst)")


def run_http_load(
    engine,
    vocab_size: int,
    tenant_specs,
    tenant_load: dict,
    num_requests: int = 24,
    mode: str = "open",
    arrival: str = "poisson",
    prompt_len: tuple[int, int] = (4, 24),
    max_new_tokens: tuple[int, int] = (4, 16),
    temperature: float = 0.0,
    seed: int = 0,
    trace: list[dict] | None = None,
    model_id: str = "serve-bench",
) -> dict:
    """Stand up the real HTTP front door over `engine` (ephemeral port)
    and drive it with per-tenant client fleets; returns the flat summary
    with one `tenants.<name>.*` block per tenant, percentiles and SLO
    attainment sourced from the server's Prometheus /metrics route.

    `mode="open"`: each tenant fires `rate` arrivals/s (`arrival` =
    poisson|burst) until its share of `num_requests` is sent — queueing
    delay lands in TTFT, exactly like production. `mode="closed"`:
    `concurrency` workers per tenant in submit-wait-repeat — the
    saturation throughput view. `trace` overrides both with a recorded
    schedule."""
    import asyncio

    import numpy as np

    from accelerate_tpu.server.config import ServerConfig
    from accelerate_tpu.server.http import HttpFrontDoor
    from accelerate_tpu.server.service import InferenceService
    from accelerate_tpu.server.tokenizer import get_tokenizer

    rng = np.random.default_rng(seed)
    tenant_names = [t.name for t in tenant_specs] or ["default"]

    # compile the three programs OUTSIDE the measured window, then drop
    # the warmup samples (and the compile-poisoned step-time EMA the SLO
    # estimates would otherwise inherit)
    warm = engine.submit(np.arange(1, 5, dtype=np.int32) % vocab_size,
                         max_new_tokens=2)
    engine.run_until_idle()
    assert warm.status.value == "finished", warm.status
    engine.reset_metrics()

    def make_prompt_ids():
        n = int(rng.integers(prompt_len[0], prompt_len[1] + 1))
        return rng.integers(0, vocab_size, (n,)).astype(int).tolist()

    def budget():
        return int(rng.integers(max_new_tokens[0], max_new_tokens[1] + 1))

    cfg = ServerConfig(port=0, model_id=model_id, tokenizer="numeric",
                       tenants=tuple(tenant_specs))
    service = InferenceService(engine, get_tokenizer("numeric", vocab_size),
                               cfg)
    door = HttpFrontDoor(service, cfg)
    # client-side books, per tenant
    obs = {t: {"sent": 0, "ok": 0, "shed_429": 0, "shed_stream": 0,
               "errors": 0, "client_ttft_s": [], "tokens": 0}
           for t in tenant_names}

    def _book(tenant: str) -> dict:
        # trace rows may name tenants outside --tenants (incl. the
        # implicit "default"); give them books instead of a KeyError
        return obs.setdefault(
            tenant, {"sent": 0, "ok": 0, "shed_429": 0, "shed_stream": 0,
                     "errors": 0, "client_ttft_s": [], "tokens": 0})

    async def one_request(port: int, tenant: str, body: dict) -> None:
        book = _book(tenant)
        book["sent"] += 1
        t0 = time.perf_counter()
        try:
            reader, writer = await asyncio.open_connection("127.0.0.1", port)
            payload = json.dumps(body).encode()
            writer.write(
                b"POST /v1/completions HTTP/1.1\r\nHost: bench\r\n"
                + f"X-Tenant: {tenant}\r\n".encode()
                + f"Content-Length: {len(payload)}\r\n\r\n".encode()
                + payload)
            await writer.drain()
            head = await reader.readuntil(b"\r\n\r\n")
            status = int(head.split(b" ")[1])
            if status == 429:
                book["shed_429"] += 1
                writer.close()
                return
            if status != 200:
                book["errors"] += 1
                writer.close()
                return
            # SSE: first data frame carrying tokens = client TTFT
            first_at = None
            ntok = 0
            finish = None
            while True:
                frame = await reader.readuntil(b"\n\n")
                if frame.startswith(b"data: [DONE]"):
                    break
                row = json.loads(frame[len(b"data: "):])
                choice = row["choices"][0]
                ids = (choice.get("token_ids")
                       or choice.get("delta", {}).get("token_ids") or [])
                ntok += len(ids)
                if ids and first_at is None:
                    first_at = time.perf_counter()
                finish = choice.get("finish_reason") or finish
            if first_at is not None:
                book["client_ttft_s"].append(first_at - t0)
            book["tokens"] += ntok
            if finish == "overloaded":
                # admitted, then shed mid-wait: the stream closed with an
                # overload verdict instead of tokens
                book["shed_stream"] += 1
            else:
                book["ok"] += 1
            writer.close()
        except (ConnectionError, asyncio.IncompleteReadError, OSError):
            book["errors"] += 1

    def body_for(tenant: str, prompt=None, max_toks=None) -> dict:
        return {"prompt": prompt or make_prompt_ids(),
                "max_tokens": max_toks or budget(),
                "temperature": temperature, "stream": True}

    async def open_loop(port: int) -> None:
        tasks = []
        if trace is not None:
            start = time.perf_counter()
            for row in trace:
                due = start + float(row.get("t", 0.0))
                await asyncio.sleep(max(0.0, due - time.perf_counter()))
                tenant = row.get("tenant", "default")
                prompt = row.get("prompt") or (
                    rng.integers(0, vocab_size,
                                 (int(row.get("prompt_len", 8)),))
                    .astype(int).tolist())
                tasks.append(asyncio.ensure_future(one_request(
                    port, tenant,
                    body_for(tenant, prompt, row.get("max_new_tokens")))))
        else:
            share = max(1, num_requests // max(1, len(tenant_names)))

            async def fleet(tenant: str) -> None:
                rate = tenant_load.get(tenant, {}).get("rate", 20.0)
                # zlib, not hash(): str hashing is salted per process and
                # would unseed the arrival schedule between runs
                import zlib

                offs = _arrival_offsets(
                    arrival, rate, share,
                    np.random.default_rng(
                        seed + zlib.adler32(tenant.encode()) % 10000))
                start = time.perf_counter()
                for off in offs:
                    await asyncio.sleep(
                        max(0.0, start + off - time.perf_counter()))
                    tasks.append(asyncio.ensure_future(
                        one_request(port, tenant, body_for(tenant))))

            await asyncio.gather(*(fleet(t) for t in tenant_names))
        if tasks:
            await asyncio.gather(*tasks)

    async def closed_loop(port: int) -> None:
        share = max(1, num_requests // max(1, len(tenant_names)))

        async def worker(tenant: str, n: int) -> None:
            for _ in range(n):
                await one_request(port, tenant, body_for(tenant))

        jobs = []
        for t in tenant_names:
            conc = max(1, tenant_load.get(t, {}).get("concurrency", 2))
            per = max(1, share // conc)
            jobs.extend(worker(t, per) for _ in range(conc))
        await asyncio.gather(*jobs)

    async def run() -> dict:
        await door.start()
        port = door.port
        t0 = time.perf_counter()
        if trace is not None or mode == "open":
            await open_loop(port)
        else:
            await closed_loop(port)
        # let in-flight engine work settle before the scrape
        while engine.scheduler.has_work():
            await asyncio.sleep(0.01)
        wall = time.perf_counter() - t0
        reader, writer = await asyncio.open_connection("127.0.0.1", port)
        writer.write(b"GET /metrics HTTP/1.1\r\nHost: bench\r\n"
                     b"Content-Length: 0\r\n\r\n")
        await writer.drain()
        raw = await reader.read()
        writer.close()
        prom = parse_prometheus(
            raw.partition(b"\r\n\r\n")[2].decode())
        await door.stop()
        return {"wall_s": wall, "prom": prom}

    res = asyncio.run(run())
    prom = res.pop("prom")
    out = engine.metrics_summary()
    out["wall_s"] = round(res["wall_s"], 3)
    out["mode"] = mode if trace is None else "trace"
    for t, book in sorted(obs.items()):
        row: dict = {
            "sent": book["sent"], "ok": book["ok"],
            "shed_429": book["shed_429"],
            "shed_stream": book["shed_stream"], "errors": book["errors"],
        }
        if book["client_ttft_s"]:
            arr = np.asarray(book["client_ttft_s"])
            row["client_ttft_p50_ms"] = float(np.percentile(arr, 50)) * 1e3
            row["client_ttft_p99_ms"] = float(np.percentile(arr, 99)) * 1e3
        # the Prometheus-sourced view: the same series a scrape reads
        for q, label in ((0.5, "p50"), (0.99, "p99")):
            v = _prom_tenant(prom, "serving_ttft_seconds", t, str(q))
            if v is not None and v == v:
                row[f"ttft_{label}_ms"] = v * 1e3
        slo_total = _prom_tenant(prom, "serving_slo_total", t)
        slo_met = _prom_tenant(prom, "serving_slo_met_total", t)
        if slo_total:
            row["slo_total"] = slo_total
            row["slo_attainment"] = (slo_met or 0.0) / slo_total
        for name, key in (("serving_requests_finished_total", "finished"),
                          ("serving_requests_expired_total", "expired")):
            v = _prom_tenant(prom, name, t)
            if v is not None:
                row[key] = v
        for k, v in row.items():
            out[f"tenants.{t}.{k}"] = round(v, 4) if isinstance(v, float) \
                else v
    return out


def main() -> None:
    # script invocation puts benchmarks/ (not the repo root) on sys.path;
    # the lazy accelerate_tpu imports below need the root
    import os
    import sys

    root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    if root not in sys.path:
        sys.path.insert(0, root)

    p = argparse.ArgumentParser(description=__doc__)
    p.add_argument("--family", default="llama", choices=("llama", "gpt2"))
    p.add_argument("--num-requests", type=int, default=16)
    p.add_argument("--rate-hz", type=float, default=50.0)
    p.add_argument("--slots", type=int, default=4)
    p.add_argument("--max-len", type=int, default=128)
    p.add_argument("--prefill-chunk", type=int, default=16)
    p.add_argument("--prompt-len", type=int, nargs=2, default=(4, 24))
    p.add_argument("--max-new-tokens", type=int, nargs=2, default=(4, 16))
    p.add_argument("--temperature", type=float, default=0.0)
    p.add_argument("--deadline-s", type=float, default=None)
    p.add_argument("--seed", type=int, default=0)
    p.add_argument("--prefix-pool", type=int, default=0,
                   help="shared-prefix workload: number of distinct "
                        "prefixes prompts draw from (0 = off)")
    p.add_argument("--prefix-len", type=int, default=0,
                   help="tokens per shared prefix; prompts become "
                        "prefix + unique suffix")
    p.add_argument("--page-size", type=int, default=16,
                   help="KV pool page size (prefix reuse is page-granular)")
    p.add_argument("--num-pages", type=int, default=None,
                   help="HBM page-pool size (default num_slots * "
                        "pages_per_slot). Shrink it under --prefix-pool "
                        "for the CHURN workload: a prefix pool bigger "
                        "than the HBM budget thrashes destructively "
                        "without a host tier and keeps hitting with one")
    p.add_argument("--host-tier-bytes", type=int, default=0,
                   help="host-DRAM overflow tier budget for evicted KV "
                        "pages (hierarchical KV): evictions swap out "
                        "instead of destroying, radix hits on "
                        "host-resident prefixes swap back in. 0 = off "
                        "(the A/B baseline on the same seeded trace)")
    p.add_argument("--no-prefix-cache", action="store_true",
                   help="disable cross-request prefix reuse (paged cache "
                        "kept) — the A/B baseline")
    p.add_argument("--kv-dtype", default="bf16", choices=("bf16", "int8"),
                   help="KV pool storage: int8 stores codes + per-row "
                        "scales — half the bytes per page, 2x the pages "
                        "a fixed HBM budget holds (summary reports "
                        "kv_bytes_in_use and pages_capacity)")
    p.add_argument("--no-paged-attention", action="store_true",
                   help="force the dense-gather decode path (the Pallas "
                        "paged-attention kernel's A/B baseline; default "
                        "'auto' uses the kernel on single-device TPU)")
    p.add_argument("--speculative", action="store_true",
                   help="draft-model speculative decoding with a "
                        "self-draft (identical tiny model — accept rate "
                        "~1.0; random-init weights make any other pair "
                        "disagree, so this measures the mechanism: "
                        "tokens/decode-step, verify batching, MXU idle). "
                        "A/B against the same run without the flag.")
    p.add_argument("--draft-k", type=int, default=4,
                   help="draft tokens proposed per speculative step "
                        "(committed tokens per step range [1, draft_k])")
    p.add_argument("--metrics-port", type=int, default=None,
                   help="serve Prometheus /metrics while the load runs "
                        "(0 = ephemeral port, printed to stderr)")
    p.add_argument("--pod-roles", default=None, metavar="prefill=N,decode=M",
                   help="disaggregated-pod mode: drive the offered load "
                        "through serving.pod.PodEngine with N prefill and "
                        "M decode workers (KV pages ship between them)")
    p.add_argument("--pod-tp", type=int, default=1,
                   help="with --pod-roles: tensor-parallel width per "
                        "worker (mesh-sharded layer 1 under the pod)")
    p.add_argument("--pod-transport", default="local",
                   choices=("local", "socket"),
                   help="with --pod-roles: 'local' = workers in this "
                        "process (default), 'socket' = real pod-worker OS "
                        "processes over TCP — same router, the A/B "
                        "prices the wire + process boundary")
    p.add_argument("--tenants", default=None,
                   help="multi-tenant HTTP harness: semicolon-separated "
                        "specs, e.g. 'gold:priority=0,weight=4,slo=0.3,"
                        "rate=10;bronze:rate=40' (rate = open-loop "
                        "arrivals/s, concurrency = closed-loop workers)")
    p.add_argument("--mode", default="open", choices=("open", "closed"),
                   help="HTTP harness loop shape (open = offered load, "
                        "closed = saturation)")
    p.add_argument("--arrival", default="poisson",
                   choices=("poisson", "burst"),
                   help="open-loop arrival process")
    p.add_argument("--trace", default=None, metavar="FILE",
                   help="replay a recorded JSONL arrival trace through "
                        "the HTTP harness instead of generating arrivals")
    p.add_argument("--pod-trace", action="store_true",
                   help="with --pod-transport socket: re-run the same "
                        "load with distributed tracing ON (100%% head "
                        "sampling, worker span export over heartbeats) "
                        "and report pod_trace_overhead_pct + span-export "
                        "lag — prices the tracing path itself")
    args = p.parse_args()

    if args.speculative and args.pod_roles:
        p.error("--speculative is not supported with --pod-roles "
                "(the pod's extract/install protocol drives the classic "
                "admit program; pod + speculation is a future arc)")
    if args.pod_transport == "socket" and not args.pod_roles:
        p.error("--pod-transport socket requires --pod-roles")
    if args.pod_transport == "socket" and args.pod_tp > 1:
        p.error("--pod-transport socket does not compose with --pod-tp "
                "(each worker process owns its whole backend)")
    if args.pod_trace and args.pod_transport != "socket":
        p.error("--pod-trace requires --pod-transport socket (the span "
                "export + clock alignment under test only exist across "
                "a real process boundary)")
    if args.tenants or args.trace:
        specs, loads = parse_tenant_load_arg(args.tenants or "")
        engine, cfg = build_tiny_engine(
            args.family, num_slots=args.slots, max_len=args.max_len,
            prefill_chunk=args.prefill_chunk, seed=args.seed,
            page_size=args.page_size,
            prefix_cache=not args.no_prefix_cache, tenants=specs,
            kv_dtype=None if args.kv_dtype == "bf16" else args.kv_dtype,
            paged_attention=False if args.no_paged_attention else "auto",
            speculative=args.speculative, draft_k=args.draft_k)
        summary = run_http_load(
            engine, cfg.vocab_size, specs, loads,
            num_requests=args.num_requests, mode=args.mode,
            arrival=args.arrival, prompt_len=tuple(args.prompt_len),
            max_new_tokens=tuple(args.max_new_tokens),
            temperature=args.temperature, seed=args.seed,
            trace=load_trace(args.trace) if args.trace else None)
        print(json.dumps({
            "metric": "serving_tokens_per_sec",
            "value": round(summary.get("tokens_per_sec", 0.0), 2),
            "unit": "tokens/s",
            "extra": {k: (round(v, 3) if isinstance(v, float) else v)
                      for k, v in summary.items()},
        }))
        return

    # a shared-prefix workload must fit prefix + suffix + budget in a
    # slot; grow max_len rather than silently rejecting every request
    max_len = args.max_len
    if args.prefix_pool and args.prefix_len:
        max_len = max(max_len, args.prefix_len + args.prompt_len[1]
                      + args.max_new_tokens[1])
    pod_procs = []
    if args.pod_roles:
        local = args.pod_transport == "local"
        engine, cfg, pod_procs = build_tiny_pod(
            args.family, pod_roles=parse_pod_roles(args.pod_roles),
            transport=args.pod_transport, tensor_parallel=args.pod_tp,
            num_slots=args.slots, max_len=max_len,
            prefill_chunk=args.prefill_chunk, seed=args.seed,
            page_size=args.page_size,
            prefix_cache=not args.no_prefix_cache,
            metrics_port=args.metrics_port,
            kv_dtype=None if args.kv_dtype == "bf16" else args.kv_dtype,
            **(dict(paged_attention=(False if args.no_paged_attention
                                     else "auto"),
                    num_pages=args.num_pages,
                    host_tier_bytes=args.host_tier_bytes)
               if local else {}))
    else:
        engine, cfg = build_tiny_engine(
            args.family, num_slots=args.slots, max_len=max_len,
            prefill_chunk=args.prefill_chunk, seed=args.seed,
            page_size=args.page_size, prefix_cache=not args.no_prefix_cache,
            metrics_port=args.metrics_port,
            kv_dtype=None if args.kv_dtype == "bf16" else args.kv_dtype,
            paged_attention=False if args.no_paged_attention else "auto",
            speculative=args.speculative, draft_k=args.draft_k,
            num_pages=args.num_pages,
            host_tier_bytes=args.host_tier_bytes)
    if engine.metrics_server is not None:
        import sys

        print(f"serving Prometheus metrics on "
              f":{engine.metrics_server.port}/metrics", file=sys.stderr)
    try:
        summary = run_offered_load(
            engine, cfg.vocab_size, num_requests=args.num_requests,
            rate_hz=args.rate_hz, prompt_len=tuple(args.prompt_len),
            max_new_tokens=tuple(args.max_new_tokens),
            temperature=args.temperature, deadline_s=args.deadline_s,
            seed=args.seed, prefix_pool=args.prefix_pool,
            prefix_len=args.prefix_len)
    finally:
        if pod_procs:
            engine.close()   # drains the workers, closes every channel
            for proc in pod_procs:
                if proc.poll() is None:
                    proc.terminate()
            for proc in pod_procs:
                try:
                    proc.wait(timeout=15)
                except Exception:
                    proc.kill()
    if args.pod_roles:
        summary["pod_transport"] = args.pod_transport
    if args.pod_trace and pod_procs:
        # second arm: identical load, tracing ON. The baseline pod is
        # already closed, so the two arms never share a port or a worker
        engine2, _, procs2 = build_tiny_pod(
            args.family, pod_roles=parse_pod_roles(args.pod_roles),
            transport="socket", num_slots=args.slots, max_len=max_len,
            prefill_chunk=args.prefill_chunk, seed=args.seed,
            page_size=args.page_size,
            prefix_cache=not args.no_prefix_cache,
            kv_dtype=None if args.kv_dtype == "bf16" else args.kv_dtype,
            trace=True)
        try:
            traced = run_offered_load(
                engine2, cfg.vocab_size, num_requests=args.num_requests,
                rate_hz=args.rate_hz, prompt_len=tuple(args.prompt_len),
                max_new_tokens=tuple(args.max_new_tokens),
                temperature=args.temperature, deadline_s=args.deadline_s,
                seed=args.seed, prefix_pool=args.prefix_pool,
                prefix_len=args.prefix_len)
        finally:
            engine2.close()
            for proc in procs2:
                if proc.poll() is None:
                    proc.terminate()
            for proc in procs2:
                try:
                    proc.wait(timeout=15)
                except Exception:
                    proc.kill()
            from accelerate_tpu.telemetry.trace import configure_tracing

            configure_tracing(enabled=False)
        base_tps = summary.get("tokens_per_sec", 0.0)
        traced_tps = traced.get("tokens_per_sec", 0.0)
        summary["pod_traced_tokens_per_sec"] = traced_tps
        if base_tps:
            summary["pod_trace_overhead_pct"] = \
                (1.0 - traced_tps / base_tps) * 100.0
        summary["pod_spans_ingested"] = traced.get("pod_spans_ingested", 0.0)
        if "pod_span_export_lag_s" in traced:
            summary["pod_span_export_lag_s"] = traced["pod_span_export_lag_s"]
    print(json.dumps({
        "metric": "serving_tokens_per_sec",
        "value": round(summary.get("tokens_per_sec", 0.0), 2),
        "unit": "tokens/s",
        "extra": {k: (round(v, 3) if isinstance(v, float) else v)
                  for k, v in summary.items()},
    }))


if __name__ == "__main__":
    main()
