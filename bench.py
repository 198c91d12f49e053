"""Benchmark: flagship Llama train step, tokens/sec/chip + MFU.

Prints ONE JSON line:
  {"schema_version": 3, "metric": ..., "value": N, "unit": ...,
   "vs_baseline": N, "device": {"platform", "kind", "count"}}

It measures the chip or it fails. A run that finds no TPU, a train child
that crashes or hangs, a TPU whose `device_kind` has no entry in the peak
table, or a phase row that failed, all end in a NON-ZERO exit code; the
line is still printed (with "error", and no value under the TPU metric's
name) so the cause can be read. There is no CPU fallback and no retry: a
number from another backend under this metric's name would be worse than
no number.

`JAX_PLATFORMS=cpu python bench.py --rehearse` is the one CPU mode: it
drives the same code at a toy size to check control flow, every row names
the device it ran on, and nothing it prints carries a value under a
device metric's name (the headline is `"skipped"`, rates and times are
left out). It exits 0 only if every phase ran.

Schema row contract: the top-level line AND every phase row under
extra.{serving,serving_prefix,server,...} carries a non-null "metric" and
"unit", plus exactly ONE non-null of "value" / "error" / "skipped". Phase
rows wrap their stats dict under "value"; a failed phase carries the
failure under "error" (and fails the run).

One process per chip: the parent never imports JAX; the train step and
each phase run as children, one after the other, each owning the chip for
its lifetime (`_spawn_child`). The `pod_dist` phase starts worker
processes of its own that each need a device while the router process
holds one too, so it cannot run on one chip: it is part of `--rehearse`
only, and fails loudly on a TPU (`serve_bench.build_tiny_pod`).

The reference publishes no training-throughput numbers (BASELINE.md); the
target from BASELINE.json is >=40% MFU on the causal-LM training loop, so
`vs_baseline` reports measured_MFU / 0.40.
"""

from __future__ import annotations

import json
import os
import subprocess
import sys
import time

# wall-clock ceiling for the train child (init + compile + timed windows)
_TPU_TIMEOUT = int(os.environ.get("BENCH_TPU_TIMEOUT", "900"))
# per-phase ceiling for the extra rows: each phase is its OWN child (one
# process holds the chip at a time), so a phase that wedges costs that
# phase's row — and the run's exit code — not the other rows
_PHASE_TIMEOUT = int(os.environ.get("BENCH_PHASE_TIMEOUT", "300"))

# bumped whenever the one-line JSON contract changes shape; v2 = the
# per-row metric/unit + exactly-one-of-value/error/skipped guarantee,
# v3 = "device" on every line, non-zero exit on any failure, no CPU rows
_SCHEMA_VERSION = 3

_HEADLINE = ("llama_train_tokens_per_sec_per_chip", "tokens/s/chip")
# the phases of a chip run, in order. `pod_dist` is NOT among them: it
# needs more processes-with-a-device than one chip allows (see the module
# docstring); `--rehearse` appends it.
_CHIP_PHASES = ("serving", "serving_prefix", "server", "pod",
                "serving_spec", "serving_host_tier")

_PHASE_METRICS = {
    "serving": ("serving_offered_load", "summary"),
    "serving_prefix": ("serving_prefix_reuse", "summary"),
    "server": ("server_http_load", "summary"),
    "pod": ("serving_pod_offered_load", "summary"),
    "pod_dist": ("serving_pod_distributed", "summary"),
    "serving_spec": ("serving_speculative_ab", "summary"),
    "serving_host_tier": ("serving_host_tier_ab", "summary"),
}


def _normalize_row(row: dict, metric: str, unit: str) -> dict:
    """Enforce the schema row contract in ONE place: non-null
    metric/unit, and exactly one non-null of value/error/skipped (a row
    that produced none of them is itself an error — silence must parse
    as failure, not as success with no number)."""
    if row.get("metric") is None:
        row["metric"] = metric
    if row.get("unit") is None:
        row["unit"] = unit
    populated = [k for k in ("error", "skipped", "value")
                 if row.get(k) is not None]
    if not populated:
        row["error"] = "degraded run: no value produced"
    else:
        # precedence error > skipped > value: a value produced alongside
        # a failure (or a pin) is suspect and must not parse as a result
        for k in populated[1:]:
            row[k] = None
    return row


def _phase_row(phase: str, payload: dict) -> dict:
    """Wrap one phase child's output as a schema row: the stats dict
    rides under "value", a failure under "error"."""
    metric, unit = _PHASE_METRICS.get(phase, (f"bench_{phase}", "summary"))
    if payload.get("error") is not None:
        return _normalize_row({"error": payload["error"]}, metric, unit)
    return _normalize_row({"value": payload}, metric, unit)


class NoChip(RuntimeError):
    """The measurement path found no TPU."""


def _device_row() -> dict:
    import jax

    d0 = jax.devices()[0]
    return {"platform": d0.platform, "kind": d0.device_kind,
            "count": len(jax.devices())}


def run_bench(rehearse: bool = False) -> dict:
    """Build and time the train step on the TPU. Raises `NoChip` when
    there is none (the child then exits 3). `rehearse` is the explicit
    CPU control-flow rehearsal: toy sizes, no device metric in the row."""
    import jax
    import numpy as np
    import optax

    from accelerate_tpu import TrainState
    from accelerate_tpu.accelerator import Accelerator
    from accelerate_tpu.models import llama
    from accelerate_tpu.models.common import count_params
    from accelerate_tpu.profiler import StepTimer
    from accelerate_tpu.utils.constants import tpu_peak_flops

    device = _device_row()
    on_tpu = device["platform"] == "tpu"
    if not on_tpu and not rehearse:
        raise NoChip(f"no tpu visible: jax reports {device}")
    if on_tpu:
        from accelerate_tpu.ops.kernel_mode import require_compiled

        # an unknown device kind raises here, before any work: there is
        # no assumed peak to divide by
        peak = tpu_peak_flops(device["kind"])
        require_compiled()  # an interpreted kernel is no measurement
        # ~400M params: fp32 master + adam moments + grads fit one v5e chip
        cfg = llama.LlamaConfig(
            vocab_size=32000, hidden_size=1536, intermediate_size=4096,
            num_hidden_layers=12, num_attention_heads=12, num_key_value_heads=4,
            max_position_embeddings=2048, remat=True, remat_policy="dots",
        )
        batch, seq, steps = 8, 2048, 20
    else:  # --rehearse: control flow only
        cfg = llama.LlamaConfig.tiny()
        batch, seq, steps = 4, 64, 3

    acc = Accelerator(mixed_precision="bf16", gradient_clipping=1.0)
    params = llama.init_params(cfg, jax.random.key(0))
    ts = acc.prepare(TrainState.create(apply_fn=None, params=params, tx=optax.adamw(3e-4)))
    n_params = count_params(ts.params)

    rng = np.random.default_rng(0)
    ids = rng.integers(0, cfg.vocab_size, (batch, seq + 1)).astype(np.int32)
    loader = acc.prepare([{"input_ids": ids}])
    (batch_arrays,) = list(loader)

    step = acc.train_step(lambda p, b: llama.causal_lm_loss(cfg, p, b))
    ts, m = step(ts, batch_arrays)  # compile + warmup
    jax.block_until_ready(m["loss"])
    # best-of-3 windows (a one-chip machine shares its host's CPU cores)
    best = float("inf")
    for _ in range(3):
        t0 = time.perf_counter()
        for _ in range(steps):
            ts, m = step(ts, batch_arrays)
        float(m["loss"])  # forces real completion on the device
        best = min(best, time.perf_counter() - t0)
    dt = best

    # per-step HOST dispatch cost (the python step() call returns once XLA
    # execution is enqueued): isolates the framework's steady-state overhead
    # from the compiled program's runtime. Cached dispatch should keep this
    # in single-digit microseconds per state leaf. Same meter as
    # profile_step.py and the serving engine (StepTimer), so the numbers
    # stay comparable across tools; warmup_steps=0 because the program is
    # already compiled and dispatch-cached by the timed windows above.
    timer = StepTimer(warmup_steps=0)
    for _ in range(steps):
        with timer.dispatch():
            ts, m = step(ts, batch_arrays)
    float(m["loss"])
    host_dispatch_us = timer.host_dispatch_us

    # per-step-synchronized window for the tail-latency telemetry row:
    # tick() blocks on each step's loss, so the histogram sees true
    # step times (the throughput windows above stay free-running)
    tail_timer = StepTimer(warmup_steps=0)
    tail_timer.tick()
    for _ in range(steps):
        ts, m = step(ts, batch_arrays)
        tail_timer.tick(m["loss"])
    tail_summary = tail_timer.summary()

    # goodput + measured roofline (ISSUE 11): training goodput is useful
    # step-time / wall-time from the synchronized window; the accelerator's
    # cost table carries the compiled step's FLOPs and the fence-sampled
    # device times accumulated by every dispatch above
    # only a MEASURED goodput lands in the row: defaulting a missing
    # reading to 1.0 would hand bench-diff a fabricated best-case
    # baseline that flags every later honest reading as a regression
    goodput_row = {}
    if "goodput" in tail_summary:
        goodput_row["training"] = round(tail_summary["goodput"], 4)
    train_sheet = acc.cost_table.roofline("train_step") or {}
    if "device_time_mean_s" in train_sheet:
        goodput_row["train_device_time_sampled_ms"] = round(
            train_sheet["device_time_mean_s"] * 1e3, 4)
    if "mfu" in train_sheet:
        goodput_row["train_mfu_measured"] = round(train_sheet["mfu"], 5)

    # resilient-loop smoke (ISSUE 20): the SAME compiled step through
    # run_resilient with periodic step-overlapped saves — goodput with the
    # loop on, what draining the async writer actually cost, and proof the
    # resilience plumbing recompiles nothing.
    goodput_row.update(_resilience_smoke(acc, step, ts, batch_arrays, steps))

    n_chips = jax.device_count()
    result = {"metric": _HEADLINE[0], "unit": _HEADLINE[1], "device": device}
    if not on_tpu:
        # the rehearsal proved the control flow; it carries counts only —
        # no rate, time or utilization from a CPU under any name
        result["metric"] = "bench_rehearsal"
        result["unit"] = "none"
        result["skipped"] = (f"rehearsal on {device['platform']}: control "
                             "flow only, no device metric")
        result["extra"] = {
            "params": n_params, "batch": batch, "seq": seq, "steps": steps,
            "n_chips": n_chips,
            "resilience": {k: v for k, v in goodput_row.items()
                           if k in ("resumes", "saves", "resumed_from_step",
                                    "train_pin_computations",
                                    "train_aot_compiles")},
        }
        return result
    tokens_per_step = batch * seq
    tokens_per_sec_per_chip = tokens_per_step * steps / dt / n_chips
    # 6ND causal-LM train FLOPs (fwd+bwd), + attention term
    attn_flops = 12 * cfg.num_hidden_layers * cfg.hidden_size * seq  # per token
    flops_per_token = 6 * n_params + attn_flops
    mfu = flops_per_token * tokens_per_sec_per_chip / peak
    result["extra"] = {
        "mfu": round(mfu, 4),
        "params": n_params,
        "batch": batch,
        "seq": seq,
        "steps": steps,
        "wall_s": round(dt, 2),
        "n_chips": n_chips,
        "host_dispatch_us": round(host_dispatch_us, 1),
        "goodput": goodput_row,
        # telemetry row (ISSUE 3): step-time tail latency from the shared
        # streaming-histogram meter, not just means
        "telemetry": {
            "step_time_p50_s": round(tail_summary["step_time_p50_s"], 6),
            "step_time_p99_s": round(tail_summary["step_time_p99_s"], 6),
            "step_time_mean_s": round(tail_summary["mean_step_time_s"], 6),
            "host_dispatch_us_mean": round(host_dispatch_us, 1),
        },
    }
    # (the serving rows are attached by the PARENT as separate phase
    # children with their own timeouts — see _emit)
    result["value"] = round(tokens_per_sec_per_chip, 1)
    result["vs_baseline"] = round(mfu / 0.40, 3)
    return result


def _resilience_smoke(acc, step, ts, batch_arrays, steps) -> dict:
    """Fold the resilience loop into the bench (ISSUE 20): run the SAME
    compiled step through `run_resilient` with periodic async saves.
    Quotes the loop's goodput, the drain/stage costs from the telemetry
    histograms, the resume latency when an earlier attempt's commit was
    picked up, and the compile-counter deltas (must be 0 — the loop adds
    no retraces)."""
    import tempfile

    from accelerate_tpu import checkpointing as ckpt
    from accelerate_tpu.profiler import StepTimer
    from accelerate_tpu.telemetry import get_registry
    from accelerate_tpu.training import run_resilient

    ckpt_dir = tempfile.mkdtemp(prefix="bench_resilient_")
    # one-time writer setup (orbax construction, torch import) happens
    # OUTSIDE the goodput window, as a real long run would have it
    ckpt.warm_async_checkpointer()
    pins0 = getattr(step, "_pin_computations", 0)
    aot0 = getattr(step, "_aot_compiles", 0)
    timer = StepTimer(warmup_steps=1, name="bench_resilient")
    num = max(6, steps)
    rep = run_resilient(
        acc, ts, step, lambda i: batch_arrays, num, ckpt_dir,
        save_every=max(2, num // 3), keep_last_n=2, timer=timer)
    row = {
        "resilient": round(rep.goodput, 4),
        "resumes": rep.resumes,
        "saves": rep.saves,
        "resumed_from_step": rep.start_step,
        "train_pin_computations": getattr(step, "_pin_computations", 0) - pins0,
        "train_aot_compiles": getattr(step, "_aot_compiles", 0) - aot0,
    }
    drain = get_registry().histogram("checkpoint_drain_seconds").summary()
    if drain.get("count"):
        row["checkpoint_drain_p99_s"] = round(drain["p99"], 4)
        row["checkpoint_drain_mean_s"] = round(drain["mean"], 4)
    stage = get_registry().histogram("checkpoint_stage_seconds").summary()
    if stage.get("count"):
        row["checkpoint_stage_mean_s"] = round(stage["mean"], 4)
    resume = get_registry().histogram("resume_latency_seconds").summary()
    if resume.get("count"):
        row["resume_latency_s"] = round(resume["mean"], 4)
    return row


def _load_serve_bench():
    import importlib.util

    path = os.path.join(os.path.dirname(os.path.abspath(__file__)),
                        "benchmarks", "serve_bench.py")
    spec = importlib.util.spec_from_file_location("serve_bench", path)
    sb = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(sb)
    return sb


def _serving_row() -> dict:
    """Offered-load smoke through the continuous-batching engine
    (benchmarks/serve_bench.py): tokens/sec + TTFT/per-token percentiles.
    The row names which decode attention op and KV dtype produced the
    numbers (paged_attention resolves per platform: Pallas kernel on a
    single-device TPU, dense gather in the CPU rehearsal) so lines stay
    comparable across configs."""
    sb = _load_serve_bench()
    engine, cfg = sb.build_tiny_engine("llama", num_slots=4, max_len=128,
                                       prefill_chunk=16)
    s = sb.run_offered_load(engine, cfg.vocab_size, num_requests=12,
                            rate_hz=200.0)
    keep = ("tokens_per_sec", "ttft_p50_ms", "ttft_p99_ms",
            "per_token_p50_ms", "per_token_p99_ms", "slot_occupancy_mean",
            "requests_finished", "requests_rejected", "kv_bytes_in_use",
            "pages_capacity",
            # roofline + goodput (ISSUE 11): what the device was doing,
            # from the engine's cost table and fence-sampled device times
            "decode_mfu", "decode_mxu_idle_fraction", "decode_hbm_bw_util",
            "decode_device_time_mean_ms", "goodput")
    row = {k: round(float(s[k]), 2) for k in keep if k in s}
    row["paged_attention"] = ("kernel" if engine._use_paged_kernel
                              else "dense")
    row["kv_dtype"] = ("int8" if engine.cache.quantized
                       else str(engine.cache.k.dtype))
    return row


def _serving_prefix_row(num_requests: int = 12, prefix_pool: int = 4,
                        prefix_len: int = 32, page_size: int = 8) -> dict:
    """Shared-prefix offered-load smoke: the paged KV cache's radix-tree
    prefix reuse under the traffic it targets — reports the hit rate and
    cached-token fraction next to the latency percentiles, so a reuse
    regression (hit rate -> 0, prefill chunks up) is visible in the same
    one-line JSON as the training row."""
    sb = _load_serve_bench()
    engine, cfg = sb.build_tiny_engine(
        "llama", num_slots=4, max_len=prefix_len + 48, prefill_chunk=16,
        page_size=page_size)
    s = sb.run_offered_load(
        engine, cfg.vocab_size, num_requests=num_requests, rate_hz=200.0,
        prompt_len=(4, 16), max_new_tokens=(4, 8),
        prefix_pool=prefix_pool, prefix_len=prefix_len)
    keep = ("tokens_per_sec", "ttft_p50_ms", "ttft_p99_ms",
            "prefill_chunks", "prefix_hits", "prefix_hit_rate",
            "cached_token_fraction", "page_evictions", "requests_finished",
            "goodput")
    return {k: round(float(s[k]), 3) for k in keep if k in s}


def _server_row(num_requests: int = 12) -> dict:
    """Two-tenant offered-load smoke through the REAL HTTP front door
    (accelerate_tpu.server over the engine): per-tier TTFT p99 and SLO
    attainment sourced from the server's own Prometheus route, plus the
    shed (429) counts — the bench line now proves the user-facing layer,
    not just the Python engine."""
    sb = _load_serve_bench()
    specs, loads = sb.parse_tenant_load_arg(
        "gold:priority=0,weight=4,slo=0.5,rate=100;"
        "bronze:priority=1,slo=2.0,rate=100")
    engine, cfg = sb.build_tiny_engine(
        "llama", num_slots=4, max_len=128, prefill_chunk=16, tenants=specs)
    s = sb.run_http_load(
        engine, cfg.vocab_size, specs, loads, num_requests=num_requests,
        prompt_len=(4, 16), max_new_tokens=(4, 8))
    keep = ("tokens_per_sec", "requests_finished", "wall_s",
            "compiles_decode")
    row = {k: round(float(s[k]), 3) for k in keep if k in s}
    for k, v in s.items():
        if k.startswith("tenants.") and isinstance(v, (int, float)):
            row[k] = round(float(v), 4)
    return row


def _serving_spec_row(num_requests: int = 10, draft_k: int = 4) -> dict:
    """Speculative-decoding A/B smoke (ISSUE 12): the SAME seeded
    offered-load trace through the engine with speculation off
    (baseline) and on (self-draft, accept rate ~1.0) — the row quotes
    tokens-per-decode-step, the accept rate, and the before/after
    `decode_mxu_idle_fraction` (PR 11's measured number this feature
    exists to lower), plus a greedy byte-exactness verdict between the
    two arms (committed tokens must be identical under greedy)."""
    sb = _load_serve_bench()
    keep = ("tokens_per_sec", "tokens_per_decode_step", "decode_steps",
            "spec_accept_rate", "spec_drafted_tokens",
            "spec_accepted_tokens", "decode_mxu_idle_fraction",
            "decode_mfu", "decode_device_time_mean_ms", "ttft_p50_ms",
            "requests_finished")
    row: dict = {"draft_k": draft_k}
    tokens = {}
    for arm, spec in (("baseline", False), ("speculative", True)):
        engine, cfg = sb.build_tiny_engine(
            "llama", num_slots=4, max_len=128, prefill_chunk=16,
            speculative=spec, draft_k=draft_k)
        # lower the fence-sampling cadence so the short smoke actually
        # measures device time (default 16 samples ~2 windows here)
        engine.cost.sample_every = 4
        s = sb.run_offered_load(engine, cfg.vocab_size,
                                num_requests=num_requests, rate_hz=200.0,
                                prompt_len=(4, 16), max_new_tokens=(6, 12))
        row[arm] = {k: round(float(s[k]), 4) for k in keep if k in s}
        tokens[arm] = [
            list(r) for r in _collect_greedy_tokens(sb, spec, draft_k)]
    row["greedy_byte_identical"] = tokens["baseline"] == tokens["speculative"]
    return row


def _collect_greedy_tokens(sb, speculative: bool, draft_k: int):
    """A tiny fixed greedy trace through a fresh engine — the byte-
    exactness probe backing the A/B row's verdict field."""
    import numpy as np

    engine, cfg = sb.build_tiny_engine(
        "llama", num_slots=2, max_len=96, prefill_chunk=16,
        speculative=speculative, draft_k=draft_k)
    rng = np.random.default_rng(7)
    prompts = [rng.integers(0, cfg.vocab_size, (n,)).astype(np.int32)
               for n in (5, 12, 9)]
    reqs = [engine.submit(p, max_new_tokens=8) for p in prompts]
    engine.run_until_idle()
    return [r.tokens for r in reqs]


def _serving_host_tier_row(num_requests: int = 24) -> dict:
    """Hierarchical-KV A/B smoke (ISSUE 16): the SAME seeded churn
    trace — a prefix pool bigger than the HBM page pool, so hot prefixes
    cycle through eviction — with the host tier off (baseline: eviction
    destroys, hits re-prefill) and on (eviction swaps out, hits swap
    back in). The row quotes prefill chunks per arm and their ratio
    (the acceptance bar is >= 2x fewer with the tier on), the swap and
    host-hit counters, plus a greedy exactness verdict: a prefix that
    round-tripped through host DRAM must continue byte-identically, in
    bf16 and int8 pools both."""
    sb = _load_serve_bench()
    keep = ("tokens_per_sec", "prefill_chunks", "prefix_hit_rate",
            "prefix_hits_hbm", "prefix_hits_host", "swap_out_pages",
            "swap_in_pages", "swap_in_p50_ms", "host_tier_pages_in_use",
            "requests_finished", "compiles_decode")
    row: dict = {}
    for arm, budget in (("baseline", 0), ("host_tier", 1 << 28)):
        engine, cfg = sb.build_tiny_engine(
            "llama", num_slots=2, max_len=160, prefill_chunk=16,
            page_size=4, num_pages=96, host_tier_bytes=budget)
        s = sb.run_offered_load(engine, cfg.vocab_size,
                                num_requests=num_requests, rate_hz=200.0,
                                prompt_len=(4, 16),
                                max_new_tokens=(4, 8),
                                prefix_pool=6, prefix_len=112, seed=0)
        row[arm] = {k: round(float(s[k]), 4) for k in keep if k in s}
    base_chunks = row["baseline"].get("prefill_chunks", 0.0)
    tier_chunks = row["host_tier"].get("prefill_chunks", 0.0)
    if tier_chunks:
        row["prefill_chunk_ratio"] = round(base_chunks / tier_chunks, 3)
    row["greedy_byte_identical"] = all(
        _host_tier_round_trip_exact(sb, kv) for kv in (None, "int8"))
    return row


def _host_tier_round_trip_exact(sb, kv_dtype) -> bool:
    """Greedy exactness probe: decode a prompt cold, churn its pages out
    to the host tier, decode it again through the swap-in path — the
    tokens must match, and a swap-in must actually have happened (a
    probe that silently skipped the round trip proves nothing)."""
    import numpy as np

    engine, _cfg = sb.build_tiny_engine(
        "llama", num_slots=2, max_len=64, prefill_chunk=8, page_size=4,
        num_pages=18, host_tier_bytes=1 << 28, kv_dtype=kv_dtype)
    rng = np.random.default_rng(11)
    pA, pB, pC = (rng.integers(0, _cfg.vocab_size, (33,)).astype(np.int32)
                  for _ in range(3))
    cold = engine.submit(pA, max_new_tokens=6)
    engine.run_until_idle()
    for p in (pB, pC):                      # churn A's pages to the tier
        engine.submit(p, max_new_tokens=6)
        engine.run_until_idle()
    warm = engine.submit(pA, max_new_tokens=6)
    engine.run_until_idle()
    swapped = engine.metrics.swap_in_pages > 0
    engine.close()
    return swapped and list(cold.tokens) == list(warm.tokens)


def _pod_row(num_requests: int = 10) -> dict:
    """Disaggregated-pod offered-load smoke (ISSUE 9): one prefill + one
    decode worker with KV pages shipping between them, behind the same
    submit/stream surface — reports the shipment counters and the
    per-role compile counts next to the latency percentiles, so a pod
    regression (shipments -> 0, compiles creeping) is visible in the
    same one-line JSON as the training row."""
    sb = _load_serve_bench()
    engine, cfg, _ = sb.build_tiny_pod(
        "llama", pod_roles=(1, 1), num_slots=4, max_len=128,
        prefill_chunk=16)
    s = sb.run_offered_load(engine, cfg.vocab_size,
                            num_requests=num_requests, rate_hz=200.0,
                            prompt_len=(4, 16), max_new_tokens=(4, 8))
    keep = ("tokens_per_sec", "ttft_p50_ms", "ttft_p99_ms",
            "per_token_p50_ms", "requests_finished", "pod_shipments",
            "pod_pages_shipped", "pod_backpressure_stalls",
            "compiles_decode", "compiles_install", "compiles_extract")
    return {k: round(float(s[k]), 3) for k in keep if k in s}


def _pod_dist_row(num_requests: int = 8) -> dict:
    """TRUE multi-host pod offered-load smoke (ISSUE 17): the same
    offered-load trace as the in-process pod row, but through
    `DistributedPodRouter` with one prefill + one decode worker as REAL
    OS processes shipping KV pages over TCP — the A/B against the "pod"
    row prices the wire + process boundary. Reports the shipment and
    recovery counters (workers_lost / requests_replayed must be 0 on a
    healthy run) next to the latency percentiles.

    NOT part of the one-chip path: the router process builds an engine on
    the default device and so does every worker process, and a chip
    belongs to one process at a time. Only `--rehearse` (CPU) runs it;
    on a TPU `build_tiny_pod` raises instead of hanging."""
    sb = _load_serve_bench()
    engine, cfg, procs = sb.build_tiny_pod(
        "llama", pod_roles=(1, 1), transport="socket", num_slots=4,
        max_len=128, prefill_chunk=16)
    try:
        s = sb.run_offered_load(engine, cfg.vocab_size,
                                num_requests=num_requests, rate_hz=200.0,
                                prompt_len=(4, 16), max_new_tokens=(4, 8))
    finally:
        engine.close()
        for proc in procs:
            if proc.poll() is None:
                proc.terminate()
        for proc in procs:
            try:
                proc.wait(timeout=15)
            except Exception:
                proc.kill()
    keep = ("tokens_per_sec", "ttft_p50_ms", "ttft_p99_ms",
            "per_token_p50_ms", "requests_finished", "pod_shipments",
            "pod_pages_shipped", "pod_backpressure_stalls",
            "pod_workers_lost", "pod_workers_recovered",
            "pod_requests_replayed", "pod_stale_messages",
            "pod_role_conversions", "pod_recovery_latency_p50_ms",
            "pod_recovery_latency_p99_ms",
            "compiles_decode", "compiles_install", "compiles_extract")
    row = {k: round(float(s[k]), 3) for k in keep if k in s}
    row["transport"] = "socket"
    return row


_PHASE_FNS = {
    "serving": _serving_row,
    "serving_prefix": _serving_prefix_row,
    "server": _server_row,
    "pod": _pod_row,
    "pod_dist": _pod_dist_row,
    "serving_spec": _serving_spec_row,
    "serving_host_tier": _serving_host_tier_row,
}


# substrings of row keys that name a rate, a time or a utilization: the
# CPU rehearsal drops them, so that nothing it prints can be read as a
# device metric
_DEVICE_METRIC_MARKS = ("per_sec", "_ms", "mfu", "util", "idle", "goodput",
                        "latency", "ttft", "per_token", "wall")


def _counts_only(row: dict) -> dict:
    out = {}
    for k, v in row.items():
        if isinstance(v, dict):
            out[k] = _counts_only(v)
        elif not (k.endswith("_s")
                  or any(m in k for m in _DEVICE_METRIC_MARKS)):
            out[k] = v
    return out


def _child_main() -> None:
    """Runs inside a bench child process (BENCH_CHILD=1). BENCH_PHASE
    selects which phase this child IS: "train" (default, the full
    training bench) or one of the serving rows — each phase child owns
    exactly one backend init, the chip for its lifetime, and one failure
    domain. A child that finds no TPU exits 3 (unless this is the
    explicit CPU rehearsal): no row ever reports another backend's
    numbers under a TPU headline."""
    phase = os.environ.get("BENCH_PHASE", "train") or "train"
    rehearse = os.environ.get("BENCH_REHEARSE") == "1"
    if phase == "train":
        try:
            print(json.dumps(run_bench(rehearse=rehearse)))
        except NoChip as e:
            print(str(e), file=sys.stderr)
            sys.exit(3)
        return
    device = _device_row()
    if device["platform"] != "tpu" and not rehearse:
        print(f"no tpu visible: jax reports {device}", file=sys.stderr)
        sys.exit(3)
    row = _PHASE_FNS[phase]()
    if rehearse:
        row = _counts_only(row)
    row["device"] = device
    print(json.dumps(row))


def _last_json_line(text: str) -> str | None:
    return next(
        (ln for ln in reversed(text.splitlines()) if ln.startswith("{")),
        None,
    )


def _spawn_child(phase: str, timeout: int, **env_overrides):
    """Run bench.py as a BENCH_CHILD subprocess — one phase, one backend
    init, one failure domain. The single place that knows the child
    protocol (env assembly, JSON-line extraction, error-tail capture).
    Returns (returncode, last JSON line or None, one-line error tail);
    TimeoutExpired propagates — each caller owns its hang message."""
    env = {**os.environ, "BENCH_CHILD": "1", "BENCH_PHASE": phase,
           **env_overrides}
    out = subprocess.run([sys.executable, __file__], env=env,
                         capture_output=True, text=True, timeout=timeout)
    tail = (out.stderr or out.stdout).strip().splitlines()
    return (out.returncode, _last_json_line(out.stdout),
            tail[-1][:300] if tail else "no output")


def _run_phase(phase: str, rehearse: bool) -> dict:
    """One extra-row phase in its own child with its own timeout: a
    wedged device (or a crash) yields a row with "error" populated —
    which fails the run — never a hang or a poisoned line."""
    try:
        rc, line, tail = _spawn_child(
            phase, _PHASE_TIMEOUT, BENCH_REHEARSE="1" if rehearse else "")
        if rc == 0 and line:
            return json.loads(line)
        if rc == 3:
            return {"error": f"{phase} bench: no tpu visible"}
        return {"error": f"{phase} bench failed: {tail}"}
    except subprocess.TimeoutExpired:
        return {"error": f"{phase} bench hung >{_PHASE_TIMEOUT}s"}


def _emit(payload: dict, rehearse: bool) -> bool:
    """Attach the serving phase rows (each its own timed child), enforce
    the schema row contract on every row, and print the one contract
    line. Returns True when the headline and every row are free of
    errors."""
    ok = payload.get("error") is None
    if ok and os.environ.get("BENCH_SERVING", "1") == "1":
        extra = payload.setdefault("extra", {})
        phases = _CHIP_PHASES + (("pod_dist",) if rehearse else ())
        for phase in phases:
            extra[phase] = _phase_row(phase, _run_phase(phase, rehearse))
            ok = ok and extra[phase].get("error") is None
    _normalize_row(payload, *_HEADLINE)
    payload["schema_version"] = _SCHEMA_VERSION
    print(json.dumps(payload))
    return ok


def _failure(error: str) -> dict:
    """The contract line of a run that measured nothing: the cause under
    "error", and NO value under the TPU metric's name."""
    return {"metric": _HEADLINE[0], "unit": _HEADLINE[1], "value": None,
            "vs_baseline": None, "device": None, "error": error}


def main(argv=None) -> int:
    if os.environ.get("BENCH_CHILD") == "1":
        _child_main()
        return 0
    argv = sys.argv[1:] if argv is None else argv
    rehearse = "--rehearse" in argv
    # The parent never initializes JAX: a parent that has touched JAX
    # holds the chip, and the children that need it would fail or hang.
    if rehearse and os.environ.get("JAX_PLATFORMS") != "cpu":
        print("bench.py --rehearse is the CPU control-flow rehearsal: run "
              "it with JAX_PLATFORMS=cpu", file=sys.stderr)
        return 2
    try:
        rc, line, tail = _spawn_child(
            "train", _TPU_TIMEOUT, BENCH_REHEARSE="1" if rehearse else "")
        if rc == 0 and line:
            payload = json.loads(line)
        elif rc == 3:
            payload = _failure(tail)
        else:
            payload = _failure(f"tpu bench failed (rc {rc}): {tail}")
    except subprocess.TimeoutExpired:
        payload = _failure(f"tpu bench hung >{_TPU_TIMEOUT}s")
    return 0 if _emit(payload, rehearse) else 1


if __name__ == "__main__":
    sys.exit(main())
