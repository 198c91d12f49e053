"""Mixtral 8-expert training throughput: dense vs sparse dispatch.

The 8-expert benchmark config for the MoE dispatch work: measures a full
train step (fwd+bwd+adamw) tokens/s on the current chip.
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
from accelerate_tpu.models import mixtral


def run(moe_impl: str, batch: int = 8, seq: int = 1024, steps: int = 20) -> float:
    cfg = mixtral.MixtralConfig(
        vocab_size=32000, hidden_size=512, intermediate_size=1408,
        num_hidden_layers=4, num_attention_heads=8, num_key_value_heads=4,
        num_local_experts=8, num_experts_per_tok=2,
        max_position_embeddings=seq, moe_impl=moe_impl,
    )
    acc = Accelerator(mixed_precision="bf16", gradient_clipping=1.0)
    params = mixtral.init_params(cfg, jax.random.key(0))
    ts = acc.prepare(TrainState.create(apply_fn=None, params=params,
                                       tx=optax.adamw(3e-4)))
    rng = np.random.default_rng(0)
    ids = rng.integers(0, cfg.vocab_size, (batch, seq + 1)).astype(np.int32)
    loader = acc.prepare([{"input_ids": ids}])
    (b,) = list(loader)
    step = acc.train_step(lambda p, bb: mixtral.causal_lm_loss(cfg, p, bb))
    ts, m = step(ts, b)
    float(m["loss"])
    best = float("inf")
    for _ in range(3):
        t0 = time.perf_counter()
        for _ in range(steps):
            ts, m = step(ts, b)
        float(m["loss"])
        best = min(best, time.perf_counter() - t0)
    tok_s = batch * seq * steps / best
    print(f"moe_impl={moe_impl:7s}: {tok_s:9.1f} tok/s "
          f"({best/steps*1000:.1f} ms/step)", flush=True)
    return tok_s


if __name__ == "__main__":
    # "a2a" is the token-sharded EP dispatch; on one chip it falls back to
    # the single-device sort path, so this row mainly proves no regression —
    # the 8-way all_to_all itself is exercised by tests + the dryrun
    impls = sys.argv[1].split(",") if len(sys.argv) > 1 else ["dense", "sparse", "a2a"]
    for impl in impls:
        run(impl)
