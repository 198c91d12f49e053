"""Runner for the serving kinds (`open_loop`, `closed_loop`): one
`serving.Engine` in this process, driven by a load generator on the same
thread (submit what is due, one `engine.step()`, repeat).

Order of a run: bf16 weights from the seed in one jitted call -> the engine
with the cell's shape -> set-up traffic (every document asked once where
the mix has documents; then the plan's first `fill_seconds`, which compile
the three programs and fill the slots) -> the window -> drain -> memory
peak read, engine and pool freed -> the plain float32 reference runs over
a seeded sample of the finished requests, the longest among them -> the
result line.

Times: the engine is given `time.perf_counter` as its clock, so
`Request.token_times`, `admitted_at` and the generator's due times are on
one clock. TTFT counts from the time a request was DUE (open loop) or sent
(closed loop); how late the generator ran is reported beside it.
"""

from __future__ import annotations

import faulthandler
import gc
import sys
import threading
import time

import numpy as np

from chipbench.harness import trace_reduce, traffic
from chipbench.harness.context import (
    Check,
    Context,
    Run,
    log,
    percentile,
    result_line,
)
from chipbench.harness.device import (
    machine_counters,
    memory_peak_bytes,
    peaks,
)

COUNTERS = ("prefill_chunks", "decode_steps", "prefix_tokens_reused",
            "prompt_tokens", "prefix_lookups", "prefix_hits", "tokens_out",
            "page_evictions")


def build_engine(ctx: Context, params):
    import jax.numpy as jnp

    from accelerate_tpu.serving import Engine, EngineConfig

    family, pcfg = ctx.cell.program_config()
    kwargs = dict(ctx.cell.shape["engine"])
    kwargs["cache_dtype"] = jnp.dtype(kwargs.get("cache_dtype", "bfloat16"))
    kwargs.setdefault("cost_sample_every", 0)
    kwargs.setdefault("seed", 0)
    return Engine(family, pcfg, params, EngineConfig(**kwargs),
                  clock=time.perf_counter)


class StallWatch:
    """What a stalled loop iteration was made of, so that a run that loses
    seconds in one go says where: the garbage collector's pauses (its own
    callbacks), the main thread's CPU time (the caller reads
    `time.thread_time`), and the longest silence of a side thread that
    only sleeps 20 ms at a time. A side thread that kept its beat while the
    main thread stood still means the main thread waited (on the device, a
    transfer, a lock); one that fell silent too means the process was not
    run, or the main thread waited inside a call that keeps the
    interpreter's lock. The side thread also writes every thread's Python
    stack to `dump_to`, once, when an iteration that `tick` announced has
    lasted a second: where the main thread waited."""

    def __init__(self, dump_to=None):
        self.gc_pauses: list[tuple[float, float]] = []  # (start, seconds)
        self._gc_started = 0.0
        self.beats: list[float] = []
        self._iteration_at: float | None = None
        self._dump_to = dump_to if dump_to is not None else sys.__stdout__
        self._stop = threading.Event()
        self._thread = threading.Thread(target=self._beat, daemon=True,
                                        name="chipbench-heartbeat")

    def tick(self, now: float | None) -> None:
        """The main loop starts an iteration at `now` (None: stop looking)."""
        self._iteration_at = now

    def _on_gc(self, phase, info):
        now = time.perf_counter()
        if phase == "start":
            self._gc_started = now
        elif info.get("generation") == 2 or now - self._gc_started > 0.01:
            self.gc_pauses.append((self._gc_started, now - self._gc_started))

    def _beat(self):
        while not self._stop.wait(0.02):
            now = time.perf_counter()
            self.beats.append(now)
            started = self._iteration_at
            if started is not None and now - started > 1.0:
                self._iteration_at = None
                log(f"an iteration has lasted {now - started:.2f} s; every "
                    f"thread's stack:")
                faulthandler.dump_traceback(file=self._dump_to,
                                            all_threads=True)

    def __enter__(self):
        gc.callbacks.append(self._on_gc)
        self._thread.start()
        return self

    def __exit__(self, *exc):
        gc.callbacks.remove(self._on_gc)
        self._stop.set()
        self._thread.join()

    def during(self, start: float, end: float) -> dict:
        """Of the interval [start, end]: seconds the collector took, and
        the side thread's longest silence."""
        gc_s = sum(d for t, d in self.gc_pauses if start <= t <= end)
        inside = [start] + [b for b in self.beats if start <= b <= end] + [end]
        silence = max(b - a for a, b in zip(inside, inside[1:]))
        return {"gc_s": round(gc_s, 3), "side_thread_silent_s":
                round(silence, 3)}


class Flight:
    """One planned request in flight: the plan entry, when it was due (open
    loop) or sent (closed loop), and the engine's handle."""

    __slots__ = ("plan", "due", "req", "measured")

    def __init__(self, plan, due, req, measured=False):
        self.plan, self.due, self.req, self.measured = plan, due, req, measured


def pages_held(engine) -> tuple[int, int]:
    """(pages of the pool held at all, pages held by live slots): the
    difference is what the prefix cache keeps of finished requests and
    would give up under pressure."""
    alloc = engine.allocator
    evictable = alloc.index.cached_pages - alloc.index.mapped_pages
    return alloc.pages_in_use, alloc.pages_in_use - evictable


def run(ctx: Context, break_engine=None, with_control: bool = False) -> dict:
    import jax
    import jax.numpy as jnp

    cell, tr = ctx.cell, ctx.cell.traffic
    cfg, ref = cell.config, cell.reference()
    closed_loop = tr["kind"] == "closed_loop"
    annotate = jax.profiler.TraceAnnotation

    words = ref.seed_words(ctx.seed)
    params = jax.jit(lambda w: ref.make_params(cfg, w, jnp.bfloat16))(words)
    engine = build_engine(ctx, params)
    if break_engine is not None:  # tests only: the timed path broken underneath
        break_engine(engine)
    if ctx.require_chip and not engine._use_paged_kernel:
        raise RuntimeError("paged_attention did not resolve to the kernel")
    if ctx.trace:
        from accelerate_tpu.telemetry.trace import configure_tracing

        configure_tracing(True)
    ec = engine.engine_config
    log(f"engine: {ec.num_slots} slots x max_len {ec.max_len}, chunk "
        f"{ec.prefill_chunk}, page {ec.page_size}, pool "
        f"{engine.cache.num_pages} pages "
        f"({engine.cache.num_pages * engine.cache.page_nbytes / 1e9:.2f} GB), "
        f"prefix cache {ec.prefix_cache}, paged kernel "
        f"{engine._use_paged_kernel}")

    fill = float(tr["fill_seconds"])
    n = (tr["cycle"] if closed_loop
         else traffic.open_loop_count(tr, ctx.seconds))
    plan, docs = traffic.serve_plan(tr, cfg["vocab_size"], ctx.seed, n)

    def submit(p, due):
        req = engine.submit(p.prompt, max_new_tokens=p.max_new_tokens,
                            temperature=0.0)
        return Flight(p, due, req)

    # -- set-up traffic: each document once, so the prefix cache holds it --
    if docs and tr.get("prime_documents", False):
        rng = np.random.default_rng(ctx.seed + 1)
        for doc in docs:
            q = rng.integers(0, cfg["vocab_size"], (16,)).astype(np.int32)
            engine.submit(np.concatenate([doc, q]), max_new_tokens=4,
                          temperature=0.0)
            engine.run_until_idle()
    else:
        engine.submit(plan[0].prompt[:ec.prefill_chunk + 1],
                      max_new_tokens=2, temperature=0.0)
        engine.run_until_idle()

    flights: list[Flight] = []
    clients: list = [None] * (tr.get("clients", 0) if closed_loop else 0)
    decode_lengths: list[list[int]] = []
    occupancy: list[float] = []
    late_s: list[float] = []
    slow: list = []  # stalled iterations: when, how long, what they held
    pages_live_max = 0
    watch = StallWatch()
    nxt = 0
    t0 = time.perf_counter()
    t_open, t_close = t0 + fill, t0 + fill + ctx.seconds
    window_open = False
    at_open: dict = {}
    tracer = trace_reduce.Capture(cell.work_dir(), ctx.trace,
                                  cell.shape.get("trace_seconds", 4.0))
    setup_s = 0.0
    compiles_open = (0, 0, {})
    queue_at = {}
    drain_deadline = t_close + float(tr.get("drain_seconds", 60.0))

    def feed(now):
        """Submit everything that is due at `now`."""
        nonlocal nxt
        if now >= t_close:
            return
        if closed_loop:
            for c, fl in enumerate(clients):
                if fl is None or fl.req.done:
                    # the cycle is sized never to wrap in a run; if a far
                    # faster system does wrap, it repeats
                    f = submit(plan[nxt % len(plan)], now)
                    f.measured = now >= t_open
                    clients[c] = f
                    flights.append(f)
                    nxt += 1
        else:
            while nxt < len(plan) and t0 + plan[nxt].due_s <= now:
                due = t0 + plan[nxt].due_s
                f = submit(plan[nxt], due)
                f.measured = due >= t_open
                if f.measured:
                    late_s.append(now - due)
                flights.append(f)
                nxt += 1

    with watch:
        while True:
            now, cpu = time.perf_counter(), time.thread_time()
            if not window_open and now >= t_open:
                # the window opens: counters snapshot, no fence (the engine's
                # own per-step host read keeps host and device in step)
                window_open = True
                t_open = now
                t_close = now + ctx.seconds
                drain_deadline = t_close + float(tr.get("drain_seconds", 60.0))
                at_open = {k: getattr(engine.metrics, k) for k in COUNTERS}
                compiles_open = (ctx.compiles.compiles,
                                 ctx.compiles.cache_requests,
                                 dict(engine.compile_stats()))
                queue_at["open"] = engine.scheduler.queue_depth
                machine_open = machine_counters()
                setup_s = ctx.setup_seconds(now)
                log(f"window opens after {setup_s:.1f} s of set-up "
                    f"({ctx.compiles.compiles} compiles, "
                    f"{ctx.compiles.cache_hits} of "
                    f"{ctx.compiles.cache_requests} cache requests hit); "
                    f"{engine.scheduler.live_slots} slots live, queue "
                    f"{queue_at['open']}")
            if window_open and "close" not in queue_at and now >= t_close:
                queue_at["close"] = engine.scheduler.queue_depth
                machine_close = machine_counters()
                held_at_close, live_at_close = pages_held(engine)
                at_close = {k: getattr(engine.metrics, k) for k in COUNTERS}
                compiles_close = (ctx.compiles.compiles,
                                  ctx.compiles.cache_requests,
                                  dict(engine.compile_stats()))
                tracer.stop()
            if window_open:
                tracer.poll(now, t_close)
                watch.tick(now if now < t_close else None)
            with annotate("chipbench.submit"):
                feed(now)
            if now >= t_close and not any(
                    f.measured and not f.req.done for f in flights):
                break
            if now >= drain_deadline:
                log("drain deadline passed with measured requests unfinished")
                break
            before = engine.metrics.decode_steps
            with annotate("chipbench.engine_step"):
                worked = engine.step()
            took = time.perf_counter() - now
            if window_open and now < t_close and took > 0.5:  # stalled: say so
                slow.append(dict(
                    watch.during(now, now + took),
                    at_s=round(now - t_open, 3), took_s=round(took, 3),
                    main_thread_cpu_s=round(time.thread_time() - cpu, 3),
                    action=("decode" if engine.metrics.decode_steps > before
                            else "prefill")))
            if window_open and now < t_close:
                occupancy.append(engine.scheduler.live_slots / ec.num_slots)
                pages_live_max = max(pages_live_max, pages_held(engine)[1])
                if tracer.running and engine.metrics.decode_steps > before:
                    decode_lengths.append([
                        s.request.prompt_len + len(s.request.tokens) - 1
                        for s in engine.scheduler.slots
                        if s.request is not None
                        and s.state.value == "decode"])
            if not worked:
                with annotate("chipbench.wait_arrival"):
                    time.sleep(0.0005)

    window_s = t_close - t_open
    peak = memory_peak_bytes(cell.chips)
    measured = [f for f in flights if f.measured]
    finished = [f for f in measured if f.req.status.value == "finished"]
    failed = len(measured) - len(finished)
    t_end = time.perf_counter()

    # -- end-to-end numbers ----------------------------------------------------
    tokens_in_window = sum(
        1 for f in flights for t in f.req.token_times if t_open <= t < t_close)
    ttft = [((f.req.first_token_at if f.req.first_token_at is not None
              else t_end) - f.due) for f in measured]
    itl = [b - a for f in measured
           for a, b in zip(f.req.token_times, f.req.token_times[1:])]
    queue_wait = [f.req.admitted_at - f.due for f in measured
                  if f.req.admitted_at is not None]
    if tracer.stopped_at is not None:
        # a traced run: the profiler's stop held the loop for seconds right
        # after the window; what had not happened by then is left out of
        # the per-layer samples (a traced run reports no end-to-end metric)
        ttft = [f.req.first_token_at - f.due for f in measured
                if (f.req.first_token_at or t_end) < tracer.stopped_at]
        queue_wait = [f.req.admitted_at - f.due for f in measured
                      if (f.req.admitted_at or t_end) < tracer.stopped_at]
    delta = {k: at_close[k] - at_open[k] for k in COUNTERS}
    recompiles = (compiles_close[0] - compiles_open[0]
                  + compiles_close[1] - compiles_open[1]
                  + sum(compiles_close[2].values())
                  - sum(compiles_open[2].values()))
    log(f"window: {window_s:.3f} s, {len(measured)} requests measured "
        f"({failed} failed), {tokens_in_window} output tokens, "
        f"{delta['decode_steps']} decode steps, {delta['prefill_chunks']} "
        f"prefill chunks, queue {queue_at['open']} -> {queue_at['close']}, "
        f"recompiles {recompiles}, device peak {peak / 1e9:.2f} GB")
    pool = engine.cache.num_pages
    log(f"KV pool at the window's close: {held_at_close} of {pool} pages "
        f"held ({live_at_close} by live slots, the rest by the prefix "
        f"cache and evictable); most held by live slots in the window "
        f"{pages_live_max}; {delta['page_evictions']} pages evicted")
    in_window = [d for t, d in watch.gc_pauses if t_open <= t < t_close]
    log(f"garbage collector in the window: {len(in_window)} full or long "
        f"collections, {sum(in_window):.3f} s, longest "
        f"{max(in_window, default=0.0):.3f} s")
    if slow:
        log(f"loop iterations over 0.5 s: {slow[:8]}")
        log("the machine over the window (seconds): " + str({
            k: round(machine_close[k] - machine_open[k], 3)
            for k in machine_close if k in machine_open}))
    if late_s:
        log(f"generator lateness (sent - due): median "
            f"{1e3 * percentile(late_s, 50):.2f} ms, p99 "
            f"{1e3 * percentile(late_s, 99):.2f} ms over {len(late_s)}")
    summary = tracer.reduce(cell.chips)
    from accelerate_tpu.ops.kernel_mode import kernel_report

    kernels = kernel_report()
    stats = engine.compile_stats()
    log(f"kernels traced: {kernels}; compile_stats {stats}")

    # -- correctness: free the engine, then the plain reference ---------------
    served = [(f.plan.prompt, list(f.req.tokens), list(f.req.logprobs))
              for f in finished]
    engine.close()
    engine.cache = None
    del engine
    jax.clear_caches()
    gc.collect()
    check = Check()
    limits = cell.shape["check"]["limits"]
    got, control, n_tokens = served_gap(
        ctx, ref, cfg, params, served, cell.shape["check"], ec.max_len,
        with_control)
    log(f"reference: {n_tokens} served tokens compared in "
        f"{time.perf_counter() - t_end:.1f} s after the window")
    for name in ("served_token_gap_max", "served_logprob_gap_max"):
        check.compare(name, got[name], limits[name])
    control_check = Check()
    if control is not None:
        log("control (fp8 matmul operands), same comparisons:")
        for name in control:
            control_check.compare(name, control[name], limits[name])
    check.compare("recompiles_in_window", recompiles, 0)
    for name in cell.shape["check"].get("kernels_compiled", []):
        check.compare(f"kernel_{name}_compiled",
                      float(kernels.get(name) == "compiled"
                            or not ctx.require_chip), 1, at_least=True)

    hd = cfg["hidden_size"] // cfg["num_attention_heads"]
    run_ = Run(
        cell=cell, device=ctx.device, peaks=peaks(ctx.device["kind"]),
        window_s=window_s, setup_s=setup_s,
        counters=dict(delta, requests=len(measured), failed=failed,
                      tokens_in_window=tokens_in_window,
                      recompiles=recompiles, memory_peak_bytes=peak,
                      queue_at_open=queue_at["open"],
                      queue_at_close=queue_at["close"],
                      pages_held_at_close=held_at_close,
                      pages_live_at_close=live_at_close,
                      pages_live_max=pages_live_max, num_pages=pool,
                      num_slots=ec.num_slots, head_dim=hd),
        samples={"ttft_s": ttft, "itl_s": itl, "queue_wait_s": queue_wait,
                 "occupancy": occupancy, "late_s": late_s,
                 "decode_lengths": decode_lengths},
        trace=summary,
        end_to_end={
            "setup_s": setup_s,
            "serve_out_tokens_per_s": tokens_in_window / window_s,
            "ttft_p90_ms": 1e3 * (percentile(ttft, 90) or 0.0),
            "itl_p95_ms": 1e3 * (percentile(itl, 95) or 0.0)})
    out = result_line(ctx, run_, check, attempted=len(measured),
                      failed=failed,
                      breakdown=summary.breakdown() if summary else None)
    if late_s:
        out["generator_late_p99_ms"] = 1e3 * percentile(late_s, 99)
    out["queue_depth"] = [queue_at["open"], queue_at["close"]]
    if control is not None:
        out["control_correct"] = control_check.correct
    return out


def served_gap(ctx: Context, ref, cfg: dict, params, served, check: dict,
               max_len: int, with_control: bool = False):
    """Over a sample of the finished requests drawn from the seed, the
    longest among them: the widest gap by which a served token's logit lies
    below the float32 reference's best logit at its position, and the
    widest gap between the log-probability the engine reported for a served
    token and the reference's. Each request is one teacher-forced forward
    over its prompt and its served tokens, padded to the engine's `max_len`
    (one compile a cell). With `with_control`, the same two numbers for an
    fp8 forward in the program's place: the gap of the token IT puts first,
    and its log-probability of the served token."""
    import jax
    import jax.numpy as jnp

    names = ("served_token_gap_max", "served_logprob_gap_max")
    if not served:
        return dict.fromkeys(names, float("inf")), None, 0
    rng = np.random.default_rng(ctx.seed + 2)
    longest = max(range(len(served)),
                  key=lambda i: len(served[i][0]) + len(served[i][1]))
    others = [i for i in range(len(served)) if i != longest]
    k = min(check["sample_requests"], len(served)) - 1
    sample = [longest] + [int(i) for i in rng.choice(others, size=k,
                                                     replace=False)]
    width = max(check.get("max_output", 1), max(len(t) for _, t, _ in served))

    gaps_of = jax.jit(lambda p, ids, first, toks: ref.position_gaps(
        cfg, p, ids, first, toks, dtype=jnp.float32))
    if with_control:
        low = ctx.cell.control()
        lower = jax.jit(lambda p, ids, first, toks: low.position_gaps(
            cfg, p, ids, first, toks, dtype=jnp.float32))
    got = dict.fromkeys(names, 0.0)
    control = dict.fromkeys(names, 0.0) if with_control else None
    n_tokens = 0
    with jax.default_matmul_precision("highest"):
        for i in sample:
            prompt, toks, lps = served[i]
            n = len(toks)
            ids = np.zeros((max_len,), np.int32)
            ids[:len(prompt)] = prompt
            ids[len(prompt):len(prompt) + n] = toks
            cand = np.zeros((width,), np.int32)
            cand[:n] = toks
            args = (params, jnp.asarray(ids), jnp.int32(len(prompt)))
            gaps, _, ref_lp = gaps_of(*args, jnp.asarray(cand))
            ref_lp = np.asarray(ref_lp)[:n]
            got[names[0]] = max(got[names[0]],
                                float(np.asarray(gaps)[:n].max()))
            got[names[1]] = max(got[names[1]], float(
                np.abs(np.asarray(lps, np.float64) - ref_lp).max()))
            n_tokens += n
            if with_control:
                _, first_choice, low_lp = lower(*args, jnp.asarray(cand))
                cgaps, _, _ = gaps_of(*args, first_choice)
                control[names[0]] = max(control[names[0]], float(
                    np.asarray(cgaps)[:n].max()))
                control[names[1]] = max(control[names[1]], float(
                    np.abs(np.asarray(low_lp)[:n] - ref_lp).max()))
    return got, control, n_tokens
