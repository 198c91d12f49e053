"""Runner for `kind: train_batches`: `Accelerator.train_step` driven back
to back over batches from the repo's own token loader.

Order of a run: corpus from the seed -> the program's state from the seed ->
ONE compiled step object takes the first three steps through the window's
own call and feed (losses, first gradient's norms, parameters' change are
read) -> a few warm steps -> the window, with that same object -> the
memory peak is read and the program's state freed -> the plain float32
reference takes the same three steps from the same seed (it needs the whole
chip) -> every number is compared -> the result line.
"""

from __future__ import annotations

import gc
import os
import time

import numpy as np

from chipbench.harness import flops, trace_reduce, traffic
from chipbench.harness.context import Check, Context, Run, log, result_line
from chipbench.harness.device import memory_peak_bytes, peaks

REFERENCE_STEPS = 3


def _reference_steps(ctx: Context, ref, cfg: dict, opt: dict, batches):
    """The first steps in plain jax.numpy float32: losses, per-leaf norms
    of the first (clipped) gradient, per-leaf norms of the parameters'
    change. Given the cell's `control()` as `ref`, this is the control put
    in the program's place."""
    import jax
    import jax.numpy as jnp

    words = ref.seed_words(ctx.seed)
    make = jax.jit(lambda w: ref.make_params(cfg, w))
    step = jax.jit(lambda p, m, v, c, ids: ref.adamw_step(
        cfg, opt, p, m, v, c, ids), donate_argnums=(0, 1, 2))
    zeros = jax.jit(lambda p: jax.tree_util.tree_map(jnp.zeros_like, p))
    diff = jax.jit(lambda a, b: ref.leaf_norms_of_difference(cfg, a, b))
    with jax.default_matmul_precision("highest"):
        params = make(words)
        m, v = zeros(params), zeros(params)
        losses, grad_norms = [], None
        for i, ids in enumerate(batches):
            params, m, v, loss, norms, probes = step(
                params, m, v, jnp.int32(i), jnp.asarray(ids))
            losses.append(float(loss))
            if i == 0:
                grad_norms = np.asarray(norms, np.float64)
                grad_probes = np.asarray(probes, np.float64)
        del m, v
        delta = np.asarray(diff(params, make(words)), np.float64)
    del params
    return {"losses": losses, "grad_norms": grad_norms,
            "grad_probes": grad_probes, "delta_norms": delta}


def worst_leaf_gap(program, reference) -> float:
    """The widest gap between the program's and the reference's norm of a
    leaf, against the reference's norm of that leaf or of the median leaf,
    whichever is larger (some leaves' gradients are all but zero)."""
    program = np.asarray(program, np.float64)
    reference = np.asarray(reference, np.float64)
    scale = np.maximum(reference, np.median(reference))
    return float(np.max(np.abs(program - reference) / scale))


def worst_leaf_probe_gap(program, reference) -> float:
    """The widest distance between the program's and the reference's probe
    vector of a leaf ([leaves, probes] each), against the length of the
    reference's probe vector of that leaf or of the median leaf, whichever
    is larger: an estimate of the relative norm of the DIFFERENCE of the
    two gradients, by the worst leaf."""
    program = np.asarray(program, np.float64)
    reference = np.asarray(reference, np.float64)
    size = np.linalg.norm(reference, axis=1)
    scale = np.maximum(size, np.median(size))
    return float(np.max(np.linalg.norm(program - reference, axis=1) / scale))


def compare(check: Check, limits: dict, program: dict, reference: dict):
    """Every number compared, each beside its limit."""
    for i, (a, b) in enumerate(zip(program["losses"], reference["losses"])):
        check.compare(f"loss_step{i}_rel_gap", abs(a - b) / abs(b),
                      limits["loss_rel_gap"][i])
    check.compare("first_grad_norm_worst_leaf_gap",
                  worst_leaf_gap(program["grad_norms"],
                                 reference["grad_norms"]),
                  limits["grad_norm_worst_leaf_gap"])
    check.compare("first_grad_probe_worst_leaf_gap",
                  worst_leaf_probe_gap(program["grad_probes"],
                                       reference["grad_probes"]),
                  limits["grad_probe_worst_leaf_gap"])
    check.compare("param_change_norm_worst_leaf_gap",
                  worst_leaf_gap(program["delta_norms"],
                                 reference["delta_norms"]),
                  limits["param_change_worst_leaf_gap"])


def _adam_mu(opt_state):
    """The first-moment tree of an optax Adam state, wherever it nests."""
    if hasattr(opt_state, "mu"):
        return opt_state.mu
    if isinstance(opt_state, (tuple, list)):
        for part in opt_state:
            found = _adam_mu(part)
            if found is not None:
                return found
    return None


def build_program(ctx: Context, ref, cfg: dict, trainer: dict):
    """The system under test: Accelerator, prepared TrainState from the
    benchmark's own weights, prepared loader, ONE compiled step."""
    import jax
    import optax

    from accelerate_tpu import TrainState
    from accelerate_tpu.accelerator import Accelerator

    family, pcfg = ctx.cell.program_config()
    opt = trainer["optimizer"]
    acc = Accelerator(mixed_precision=trainer["mixed_precision"],
                      gradient_clipping=trainer["clip_global_norm"],
                      cost_sample_every=0)
    params = jax.jit(lambda w: ref.make_params(cfg, w))(
        ref.seed_words(ctx.seed))
    tx = optax.adamw(opt["learning_rate"], b1=opt["b1"], b2=opt["b2"],
                     eps=opt["eps"], weight_decay=opt["weight_decay"])
    extra = {}
    if trainer["mixed_precision"] == "fp8":
        # the program's own lower precision (a control's, never a cell's):
        # its scaled fp8 matmuls want their scaling state beside the weights
        extra["fp8_state"] = family.init_fp8_state(pcfg)
    state = acc.prepare(TrainState.create(apply_fn=None, params=params,
                                           tx=tx, **extra))
    del params
    step = acc.train_step(
        lambda p, b, **kw: family.causal_lm_loss(pcfg, p, b, **kw))
    return acc, pcfg, state, step


def run(ctx: Context, broken_step=None, with_control: bool = False) -> dict:
    import jax

    cell, tr = ctx.cell, ctx.cell.traffic
    cfg, trainer = cell.config, cell.shape["trainer"]
    ref = cell.reference()
    opt = dict(trainer["optimizer"],
               clip_global_norm=trainer["clip_global_norm"])
    chips = cell.chips
    batch, seq = tr["batch"], tr["seq_len"]

    corpus = traffic.train_corpus(tr, cfg["vocab_size"], ctx.seed)
    first = [corpus[i * batch:(i + 1) * batch] for i in range(REFERENCE_STEPS)]

    # -- the program -----------------------------------------------------------
    acc, pcfg, state, step = build_program(ctx, ref, cfg, trainer)
    if broken_step is not None:  # tests only: the timed path broken underneath
        step = broken_step(step)
    from accelerate_tpu.native import TokenCorpusLoader, write_token_file

    path = write_token_file(os.path.join(cell.work_dir(), "corpus.bin"),
                            corpus)
    raw = TokenCorpusLoader(path, sample_len=seq + 1, batch_size=batch, shuffle=False,
                 seed=0)
    loader = acc.prepare(raw)
    log(f"program: {ref.param_count(cfg) / 1e6:.1f} M parameters, batch "
        f"{batch} x {seq}, loader {raw.implementation}, attention "
        f"{pcfg.attention_backend!r}")

    dispatch_s: list[float] = []
    stall_s: list[float] = []
    losses: list[tuple[int, float]] = []
    done = {"steps": 0}
    every = tr["log_loss_every"]

    def feed():
        while True:  # epochs; one covers a run at any sane speed
            yield from loader

    batches = feed()

    box = {"state": state}
    del state

    def drive(until):
        """The window's own call and feed: next batch, one step, the loss
        read every `every`-th step as a user's logging would read it."""
        while not until():
            t0 = time.perf_counter()
            b = next(batches)
            t1 = time.perf_counter()
            with jax.profiler.TraceAnnotation("chipbench.train_step"):
                box["state"], m = step(box["state"], b)
            t2 = time.perf_counter()
            stall_s.append(t1 - t0)
            dispatch_s.append(t2 - t1)
            done["steps"] += 1
            if done["steps"] % every == 0 or done["steps"] <= REFERENCE_STEPS:
                with jax.profiler.TraceAnnotation("chipbench.read_loss"):
                    losses.append((done["steps"], float(m["loss"])))

    def fence():
        jax.block_until_ready(box["state"])

    # first steps, compared with the reference
    read = jax.jit(lambda a: (ref.leaf_norms(cfg, a),
                              ref.leaf_probes(cfg, a)))
    drive(lambda: done["steps"] >= 1)
    mu = _adam_mu(box["state"].opt_state)
    mu_norms, mu_probes = read(mu)
    program = {
        "grad_norms": np.asarray(mu_norms, np.float64) / (1 - opt["b1"]),
        "grad_probes": np.asarray(mu_probes, np.float64) / (1 - opt["b1"])}
    del mu
    drive(lambda: done["steps"] >= REFERENCE_STEPS)
    # the seeded parameters are made again INSIDE the program that takes
    # the norms, leaf by leaf, so that no second copy of them is ever whole
    # on the device and the memory peak stays the train step's
    change = jax.jit(lambda p, w: ref.leaf_norms_of_difference(
        cfg, p, ref.make_params(cfg, w)))
    program["delta_norms"] = np.asarray(
        change(box["state"].params, ref.seed_words(ctx.seed)), np.float64)
    program["losses"] = [x for _, x in losses[:REFERENCE_STEPS]]

    # warm steps: the loader's prefetch and the dispatch path reach their
    # steady state; then fence and open the window
    drive(lambda: done["steps"] >= tr["warm_steps"])
    fence()
    compiles_at_open = (ctx.compiles.compiles, ctx.compiles.cache_requests,
                        step._aot_compiles + step._cache_size())
    steps_at_open, losses_at_open = done["steps"], len(losses)
    del dispatch_s[:], stall_s[:]
    tracer = trace_reduce.Capture(cell.work_dir(), ctx.trace,
                                  cell.shape.get("trace_seconds", 4.0))
    t_open = time.perf_counter()
    setup_s = ctx.setup_seconds(t_open)
    log(f"window opens after {setup_s:.1f} s of set-up "
        f"({ctx.compiles.compiles} compiles, {ctx.compiles.cache_hits} of "
        f"{ctx.compiles.cache_requests} cache requests hit)")

    def closed():
        now = time.perf_counter()
        tracer.poll(now, t_open + ctx.seconds)
        return now - t_open >= ctx.seconds

    drive(closed)
    fence()
    t_close = time.perf_counter()
    tracer.stop()
    window_s = t_close - t_open
    steps = done["steps"] - steps_at_open
    recompiles = (ctx.compiles.compiles - compiles_at_open[0]
                  + ctx.compiles.cache_requests - compiles_at_open[1]
                  + step._aot_compiles + step._cache_size()
                  - compiles_at_open[2])
    peak = memory_peak_bytes(chips)
    tokens = steps * batch * seq
    rate = tokens / window_s / chips
    window_losses = [x for _, x in losses[losses_at_open:]]
    log(f"window: {steps} steps, {tokens} tokens in {window_s:.3f} s; "
        f"losses first {program['losses'][0]:.4f} -> last "
        f"{window_losses[-1] if window_losses else float('nan'):.4f}; "
        f"recompiles {recompiles}; device peak {peak / 1e9:.2f} GB")
    from accelerate_tpu.ops.kernel_mode import kernel_report

    kernels = kernel_report()
    log(f"kernels traced: {kernels}")
    summary = tracer.reduce(chips)

    # -- the plain reference, once the program's state is freed --------------
    raw.close()
    del box["state"], loader, batches, step
    acc.free_memory()
    from accelerate_tpu.state import PartialState

    PartialState._reset_state()
    jax.clear_caches()
    gc.collect()
    t_ref = time.perf_counter()
    reference = _reference_steps(ctx, ref, cfg, opt, first)
    log(f"reference: {REFERENCE_STEPS} float32 'highest' steps in "
        f"{time.perf_counter() - t_ref:.1f} s after the window, losses "
        f"{[round(x, 4) for x in reference['losses']]}")
    check = Check()
    compare(check, cell.shape["check"]["limits"], program, reference)
    if with_control:
        # the control in the program's place: the same three steps with
        # every matmul operand rounded to float8; its readings are logged
        # beside the program's and decide nothing in this run
        gc.collect()
        control = _reference_steps(ctx, cell.control(), cfg, opt, first)
        log("control (fp8 matmul operands), same comparisons:")
        control_check = Check()
        compare(control_check, cell.shape["check"]["limits"], control,
                reference)

    check.compare("recompiles_in_window", recompiles, 0)
    check.compare("loss_finite", float(np.all(np.isfinite(window_losses))
                                       and len(window_losses) > 0), 1,
                  at_least=True)
    tail = float(np.mean(window_losses[-3:])) if window_losses else np.inf
    check.compare("loss_fell", program["losses"][0] - tail,
                  cell.shape["check"]["limits"]["loss_fall_at_least"],
                  at_least=True)
    for name in cell.shape["check"].get("kernels_compiled", []):
        check.compare(f"kernel_{name}_compiled",
                      float(kernels.get(name) == "compiled"
                            or not ctx.require_chip), 1, at_least=True)

    run_ = Run(
        cell=cell, device=ctx.device, peaks=peaks(ctx.device["kind"]), window_s=window_s,
        setup_s=setup_s,
        counters={"steps": steps, "tokens": tokens, "recompiles": recompiles,
                  "batch": batch, "seq_len": seq, "chips": chips,
                  "memory_peak_bytes": peak,
                  "flops_per_token": flops.train_flops_per_token(cfg, seq)},
        samples={"dispatch_s": dispatch_s, "stall_s": stall_s},
        trace=summary,
        end_to_end={"setup_s": setup_s,
                    "train_tokens_per_s_per_chip": rate})
    out = result_line(ctx, run_, check, attempted=steps,
                      failed=int(not np.all(np.isfinite(window_losses))),
                      breakdown=summary.breakdown() if summary else None)
    if with_control:
        out["control_correct"] = control_check.correct
    return out
