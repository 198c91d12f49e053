"""fp8 vs bf16 train-step throughput on one chip (VERDICT r4 #8).

The fp8 path is correctness-tested everywhere (tests/test_quant_fp8.py);
this measures whether it is also *fast* on the present hardware. The
expectation, stated in docs/fp8.md: v5-lite has no fp8 MXU, XLA upcasts
the float8 operands, so fp8 should be AT BEST neutral vs bf16 there —
the win appears on fp8-capable parts (v5p+/trillium). Whichever way it
comes out, the measured row replaces the guess.

Run: python benchmarks/fp8_vs_bf16.py
Prints one JSON line per precision:
  {"metric": "fp8_vs_bf16_tokens_per_sec", "precision": ..., ...}
"""

from __future__ import annotations

import json
import time

import jax
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
from accelerate_tpu.state import PartialState


def run(precision: str, steps: int = 15) -> dict:
    PartialState._reset_state()
    on_tpu = jax.devices()[0].platform == "tpu"
    if on_tpu:
        cfg = llama.LlamaConfig(
            vocab_size=32000, hidden_size=1536, intermediate_size=4096,
            num_hidden_layers=12, num_attention_heads=12,
            num_key_value_heads=4, max_position_embeddings=2048,
            remat=True, remat_policy="dots",
        )
        batch, seq = 8, 2048
    else:  # smoke config so the script is runnable in CI
        cfg = llama.LlamaConfig.tiny()
        batch, seq, steps = 4, 64, 3

    acc = Accelerator(mixed_precision=precision, gradient_clipping=1.0)
    params = llama.init_params(cfg, jax.random.key(0))
    fp8_state = llama.init_fp8_state(cfg) if precision == "fp8" else None
    ts = acc.prepare(TrainState.create(
        apply_fn=None, params=params, tx=optax.adamw(3e-4),
        fp8_state=fp8_state,
    ))
    n_params = count_params(ts.params)
    ids = np.random.default_rng(0).integers(
        0, cfg.vocab_size, (batch, seq + 1)).astype(np.int32)
    loader = acc.prepare([{"input_ids": ids}])
    (batch_arrays,) = list(loader)
    step = acc.train_step(lambda p, b, **kw: llama.causal_lm_loss(cfg, p, b, **kw))
    ts, m = step(ts, batch_arrays)
    jax.block_until_ready(m["loss"])
    best = float("inf")
    for _ in range(3):
        t0 = time.perf_counter()
        for _ in range(steps):
            ts, m = step(ts, batch_arrays)
        float(m["loss"])
        best = min(best, time.perf_counter() - t0)
    tps = batch * seq * steps / best / jax.device_count()
    return {
        "metric": "fp8_vs_bf16_tokens_per_sec",
        "precision": precision,
        "value": round(tps, 1),
        "unit": "tokens/s/chip",
        "extra": {
            "params": n_params, "batch": batch, "seq": seq, "steps": steps,
            "device": getattr(jax.devices()[0], "device_kind", "cpu").lower(),
        },
    }


def main() -> None:
    rows = [run("bf16"), run("fp8")]
    for r in rows:
        print(json.dumps(r))
    if rows[0]["value"] and rows[1]["value"]:
        ratio = rows[1]["value"] / rows[0]["value"]
        print(json.dumps({
            "metric": "fp8_over_bf16_speedup", "value": round(ratio, 3),
            "unit": "x",
        }))


if __name__ == "__main__":
    main()
