"""MFU table: the single-chip Llama bench at increasing model scale.

BASELINE.md phrases the target as Llama-3-8B on v5p-64; one v5e chip
(16 GB) can't hold that, so this table quantifies how MFU trends as the
proxy grows toward it — larger hidden sizes make bigger MXU matmuls, so
per-chip MFU at 8B/v5p should sit at or above the largest row here.

Run: python benchmarks/mfu_table.py [name ...]
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

CONFIGS = {
    # name: (hidden, ffn, layers, heads, kv_heads, batch, seq, remat_policy,
    #        moments) — the 16 GB chip fits the larger rows only by shrinking
    #        the Adam moments: 'f32' -> plain adamw, 'bf16' -> mu_dtype
    #        downcast, 'int8' -> accelerate_tpu.optimizers.adamw_8bit
    #        (~2.06 bytes/param of optimizer state instead of 8 — what lets
    #        the 1.5B/2B rows train on one chip at all)
    "400M": (1536, 4096, 12, 12, 4, 8, 2048, "dots", "f32"),
    "700M": (2048, 5504, 12, 16, 8, 4, 2048, "dots", "bf16"),
    "1B": (2048, 5504, 20, 16, 8, 4, 2048, "full", "bf16"),
    "1.5B": (2560, 6912, 20, 20, 4, 4, 2048, "full", "int8"),
    "2B": (2560, 6912, 26, 20, 4, 2, 2048, "full", "int8"),
    "2B-s4k": (2560, 6912, 26, 20, 4, 1, 4096, "full", "int8"),
}


def run(name: str, steps: int = 15) -> None:
    import jax.numpy as jnp

    from accelerate_tpu.optimizers import adamw_8bit

    h, f, L, nh, nkv, batch, seq, policy, moments = CONFIGS[name]
    cfg = llama.LlamaConfig(
        vocab_size=32000, hidden_size=h, intermediate_size=f,
        num_hidden_layers=L, num_attention_heads=nh, num_key_value_heads=nkv,
        max_position_embeddings=seq, remat=True, remat_policy=policy,
    )
    acc = Accelerator(mixed_precision="bf16", gradient_clipping=1.0)
    if moments == "int8":
        # the single-chip multi-billion recipe: bf16 weights (grads then
        # materialize bf16 straight out of autodiff) + int8 Adam moments
        # ≈ 6 bytes/param of resident state — 2B params ≈ 11.7 GB, which is
        # what fits a 16 GB chip; f32 masters + f32 grads would need ~20 GB
        params = llama.init_params(cfg, jax.random.key(0), dtype=jnp.bfloat16)
        tx = adamw_8bit(3e-4)
    else:
        params = llama.init_params(cfg, jax.random.key(0))
        tx = optax.adamw(
            3e-4, mu_dtype=jnp.bfloat16 if moments == "bf16" else None
        )
    ts = acc.prepare(TrainState.create(apply_fn=None, params=params, tx=tx))
    n_params = count_params(ts.params)
    rng = np.random.default_rng(0)
    ids = rng.integers(0, cfg.vocab_size, (batch, seq + 1)).astype(np.int32)
    loader = acc.prepare([{"input_ids": ids}])
    (b,) = list(loader)
    step = acc.train_step(lambda p, bb: llama.causal_lm_loss(cfg, p, bb))
    try:
        ts, m = step(ts, b)
        float(m["loss"])
    except Exception as e:  # noqa: BLE001
        print(f"{name:5s}: FAILED {type(e).__name__}: {str(e)[:100]}", flush=True)
        return
    best = float("inf")
    for _ in range(3):
        t0 = time.perf_counter()
        for _ in range(steps):
            ts, m = step(ts, b)
        float(m["loss"])
        best = min(best, time.perf_counter() - t0)
    tok_s = batch * seq * steps / best
    attn = 12 * L * h * seq
    flops_tok = 6 * n_params + attn
    # an unknown device kind raises: no assumed peak, no made-up MFU
    peak = tpu_peak_flops(jax.devices()[0].device_kind)
    mfu = flops_tok * tok_s / peak
    print(f"{name:5s}: {n_params/1e6:7.1f}M params  b={batch} s={seq}  "
          f"{tok_s:9.1f} tok/s  mfu={mfu:.4f}", flush=True)


if __name__ == "__main__":
    names = sys.argv[1:] or list(CONFIGS)
    for n in names:
        run(n)
