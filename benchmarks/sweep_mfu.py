"""MFU sweep for the single-chip Llama bench (bench.py's config).

Tries attention backend x remat policy x batch and prints one line per
config; used to pick bench.py's settings (VERDICT r1 item 1).
"""

from __future__ import annotations

import sys
import time

import jax
import numpy as np
import optax

import os

# run as `python benchmarks/<this>.py`: the package is not pip-installed,
# so put the checkout root (not benchmarks/) on sys.path
sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

from accelerate_tpu import TrainState
from accelerate_tpu.accelerator import Accelerator
from accelerate_tpu.models import llama
from accelerate_tpu.models.common import count_params
from accelerate_tpu.utils.constants import tpu_peak_flops


def run(backend: str, remat: bool, policy: str, batch: int, seq: int = 2048,
        steps: int = 20) -> None:
    cfg = llama.LlamaConfig(
        vocab_size=32000, hidden_size=1536, intermediate_size=4096,
        num_hidden_layers=12, num_attention_heads=12, num_key_value_heads=4,
        max_position_embeddings=seq, remat=remat, remat_policy=policy,
        attention_backend=backend,
    )
    acc = Accelerator(mixed_precision="bf16", gradient_clipping=1.0)
    params = llama.init_params(cfg, jax.random.key(0))
    ts = acc.prepare(TrainState.create(apply_fn=None, params=params,
                                       tx=optax.adamw(3e-4)))
    n_params = count_params(ts.params)
    rng = np.random.default_rng(0)
    ids = rng.integers(0, cfg.vocab_size, (batch, seq + 1)).astype(np.int32)
    loader = acc.prepare([{"input_ids": ids}])
    (batch_arrays,) = list(loader)
    step = acc.train_step(lambda p, b: llama.causal_lm_loss(cfg, p, b))
    try:
        ts, m = step(ts, batch_arrays)
        jax.block_until_ready(m["loss"])
    except Exception as e:  # noqa: BLE001
        print(f"{backend:7s} remat={remat!s:5s}/{policy:4s} b={batch:3d}: "
              f"FAILED {type(e).__name__}: {str(e)[:120]}", flush=True)
        return
    best = float("inf")
    for _ in range(3):
        t0 = time.perf_counter()
        for _ in range(steps):
            ts, m = step(ts, batch_arrays)
        float(m["loss"])
        best = min(best, time.perf_counter() - t0)
    tok_s = batch * seq * steps / best
    attn_flops = 12 * cfg.num_hidden_layers * cfg.hidden_size * seq
    flops_per_token = 6 * n_params + attn_flops
    # an unknown device kind raises: no assumed peak, no made-up MFU
    peak = tpu_peak_flops(jax.devices()[0].device_kind)
    mfu = flops_per_token * tok_s / peak
    print(f"{backend:7s} remat={remat!s:5s}/{policy:4s} b={batch:3d}: "
          f"{tok_s:9.1f} tok/s  mfu={mfu:.4f}", flush=True)


if __name__ == "__main__":
    configs = [
        ("einsum", True, "full", 16),   # round-1 baseline
        ("einsum", True, "dots", 16),
        ("flash", True, "full", 16),
        ("flash", True, "dots", 16),
        ("flash", False, "full", 16),
        ("flash", True, "dots", 32),
    ]
    if len(sys.argv) > 1:  # e.g. "flash,True,dots,16"
        b, r, p, bs = sys.argv[1].split(",")
        configs = [(b, r == "True", p, int(bs))]
    for c in configs:
        run(*c)
