"""Decompose the bench step: fwd-only vs fwd+bwd vs full train step MFU,
plus (--host-overhead) the per-step host-side costs the device never sees —
dispatch microseconds and input-stall time.

    python benchmarks/profile_step.py                  # MFU decomposition
    python benchmarks/profile_step.py --host-overhead  # JSON host metrics

The host-overhead mode is CPU-runnable (JAX_PLATFORMS=cpu uses a tiny
model), with one caveat: the CPU backend executes the step mostly
synchronously, so `host_dispatch_us_mean` there absorbs device compute and
is an upper bound, not the pure enqueue cost (single-digit microseconds per
leaf only shows on an async backend like TPU). The host-only proof that the
cached dispatch path works is `pin_tree_computations` (1 for a fixed state
structure) plus `input_stall_us_mean`; the JSON carries
`dispatch_includes_device_time` so tooling can tell the two regimes apart.
"""

from __future__ import annotations

import argparse
import json
import time

import jax
import jax.numpy as jnp
import numpy as np
import optax

import os
import sys

# run as `python benchmarks/<this>.py`: the package is not pip-installed,
# so put the checkout root (not benchmarks/) on sys.path
sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

from accelerate_tpu import TrainState
from accelerate_tpu.accelerator import Accelerator
from accelerate_tpu.models import llama
from accelerate_tpu.models.common import count_params
from accelerate_tpu.utils.constants import tpu_peak_flops
from accelerate_tpu.profiler import StepTimer
from accelerate_tpu.training import cast_floating

BATCH, SEQ, STEPS = 8, 2048, 20


def _on_tpu() -> bool:
    dev0 = jax.devices()[0]
    return "tpu" in (dev0.platform + getattr(dev0, "device_kind", "")).lower()


def _flagship_cfg():
    return llama.LlamaConfig(
        vocab_size=32000, hidden_size=1536, intermediate_size=4096,
        num_hidden_layers=12, num_attention_heads=12, num_key_value_heads=4,
        max_position_embeddings=SEQ, remat=True, remat_policy="dots",
    )


def mfu_decomposition() -> None:
    cfg = _flagship_cfg()
    acc = Accelerator(mixed_precision="bf16", gradient_clipping=1.0)
    params = llama.init_params(cfg, jax.random.key(0))
    ts = acc.prepare(TrainState.create(apply_fn=None, params=params, tx=optax.adamw(3e-4)))
    n_params = count_params(ts.params)
    rng = np.random.default_rng(0)
    ids = rng.integers(0, cfg.vocab_size, (BATCH, SEQ + 1)).astype(np.int32)
    loader = acc.prepare([{"input_ids": ids}])
    (batch_arrays,) = list(loader)

    # an unknown device kind raises: no assumed peak, no made-up MFU
    peak = tpu_peak_flops(jax.devices()[0].device_kind)
    attn_flops = 12 * cfg.num_hidden_layers * cfg.hidden_size * SEQ
    fwd_flops_tok = 2 * n_params + attn_flops // 3
    tot_flops_tok = 6 * n_params + attn_flops

    def timeit(name, fn, *args, flops_per_token):
        out = fn(*args)
        jax.block_until_ready(out)
        best = float("inf")
        for _ in range(3):
            t0 = time.perf_counter()
            for _ in range(STEPS):
                out = fn(*args)
            jax.block_until_ready(out)
            best = min(best, time.perf_counter() - t0)
        tok_s = BATCH * SEQ * STEPS / best
        mfu = flops_per_token * tok_s / peak
        print(f"{name:24s}: {best/STEPS*1000:8.1f} ms/step  "
              f"eq-mfu={mfu:.4f}", flush=True)
        return best / STEPS

    loss_fn = lambda p, b: llama.causal_lm_loss(cfg, p, b)  # noqa: E731

    fwd = jax.jit(lambda p, b: loss_fn(cast_floating(p, jnp.bfloat16), b))
    timeit("fwd only", fwd, ts.params, batch_arrays, flops_per_token=fwd_flops_tok)

    grad = jax.jit(jax.grad(lambda p, b: loss_fn(cast_floating(p, jnp.bfloat16), b)))
    timeit("fwd+bwd", grad, ts.params, batch_arrays, flops_per_token=tot_flops_tok)

    # train_step donates its input state, so the timing loop must keep
    # rebinding the returned state rather than restarting from a donated one
    step = acc.train_step(loss_fn)
    ts, m = step(ts, batch_arrays)
    float(m["loss"])
    best = float("inf")
    for _ in range(3):
        t0 = time.perf_counter()
        for _ in range(STEPS):
            ts, m = step(ts, batch_arrays)
        float(m["loss"])  # forces completion on the device
        best = min(best, time.perf_counter() - t0)
    tok_s = BATCH * SEQ * STEPS / best
    print(f"{'full train step':24s}: {best/STEPS*1000:8.1f} ms/step  "
          f"eq-mfu={tot_flops_tok * tok_s / peak:.4f}", flush=True)


def host_overhead(steps: int = 30) -> dict:
    """Measure per-step host dispatch and input-stall time through the real
    prepare()d pipeline (device prefetch + cached dispatch) and print ONE
    JSON line. The model is tiny off-TPU: these are host-side costs."""
    on_tpu = _on_tpu()
    if on_tpu:
        cfg, batch, seq = _flagship_cfg(), BATCH, SEQ
    else:
        cfg, batch, seq = llama.LlamaConfig.tiny(), 4, 64
    acc = Accelerator(mixed_precision="bf16", gradient_clipping=1.0)
    params = llama.init_params(cfg, jax.random.key(0))
    ts = acc.prepare(TrainState.create(apply_fn=None, params=params,
                                       tx=optax.adamw(3e-4)))
    rng = np.random.default_rng(0)
    batches = [
        {"input_ids": rng.integers(0, cfg.vocab_size, (batch, seq + 1)).astype(np.int32)}
        for _ in range(steps)
    ]
    loader = acc.prepare(batches)
    step = acc.train_step(lambda p, b: llama.causal_lm_loss(cfg, p, b))

    # AOT warmup outside the loop: the first in-loop step pays dispatch only
    it = iter(loader)
    first = next(it)
    step.warmup(ts, first)

    timer = StepTimer(warmup_steps=2)
    current = first
    while current is not None:
        with timer.dispatch():
            ts, m = step(ts, current)
        timer.tick(m["loss"])
        with timer.input_stall():
            current = next(it, None)
    summary = timer.summary()
    out = {
        "metric": "train_step_host_overhead",
        "host_dispatch_us_mean": round(timer.host_dispatch_us, 1),
        "input_stall_us_mean": round(timer.input_stall_us, 1),
        "mean_step_time_s": round(timer.mean_step_time, 6),
        # tail latency from the shared streaming histogram — a p99 far from
        # the mean means jittery steps (input stalls, recompiles, noisy
        # neighbors), which a mean-only meter hides
        "step_time_p50_s": round(summary.get("step_time_p50_s", float("nan")), 6),
        "step_time_p99_s": round(summary.get("step_time_p99_s", float("nan")), 6),
        "steps_recorded": timer.steps_recorded,
        "pin_tree_computations": step._pin_computations,
        "device": getattr(jax.devices()[0], "device_kind", "cpu").lower(),
        "n_chips": jax.device_count(),
        "on_tpu": on_tpu,
        # CPU executes the step largely synchronously inside the step()
        # call — there the dispatch reading bounds (host + device), it is
        # not the pure async enqueue cost
        "dispatch_includes_device_time": not on_tpu,
    }
    print(json.dumps(out), flush=True)
    return out


def main() -> None:
    parser = argparse.ArgumentParser(description=__doc__)
    parser.add_argument(
        "--host-overhead", action="store_true",
        help="print per-step host dispatch + input stall metrics as JSON",
    )
    parser.add_argument("--steps", type=int, default=30,
                        help="steps for --host-overhead")
    args = parser.parse_args()
    if args.host_overhead:
        host_overhead(args.steps)
    else:
        mfu_decomposition()


if __name__ == "__main__":
    main()
