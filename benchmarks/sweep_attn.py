"""Micro-bench: attention op alone, einsum vs flash block configs.

Times fwd+bwd of the attention op on the bench shape; used to tune
flash_attention block sizes and the llama 'auto' backend threshold.
"""

from __future__ import annotations

import sys
import time

import jax
import jax.numpy as jnp

import os

# run as `python benchmarks/<this>.py`: the package is not pip-installed,
# so put the checkout root (not benchmarks/) on sys.path
sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

from accelerate_tpu.models.common import dot_product_attention
from accelerate_tpu.ops.flash_attention import flash_attention

B, H, D = 8, 12, 128
SEQ = int(sys.argv[1]) if len(sys.argv) > 1 else 2048
STEPS = 30

key = jax.random.key(0)
q = jax.random.normal(key, (B, SEQ, H, D), jnp.bfloat16)
k = jax.random.normal(jax.random.fold_in(key, 1), (B, SEQ, H, D), jnp.bfloat16)
v = jax.random.normal(jax.random.fold_in(key, 2), (B, SEQ, H, D), jnp.bfloat16)

# causal attention flops (fwd): 2 matmuls, half the S^2 positions live
flops_fwd = 2 * (2 * B * H * SEQ * SEQ * D) / 2
flops_tot = 3 * flops_fwd


def bench(name, fn):
    f = jax.jit(jax.grad(lambda q: jnp.sum(fn(q).astype(jnp.float32))))
    try:
        out = f(q)
        float(jnp.ravel(out)[0])
    except Exception as e:  # noqa: BLE001
        print(f"{name:28s}: FAILED {type(e).__name__}: {str(e)[:100]}", flush=True)
        return
    best = float("inf")
    for _ in range(3):
        t0 = time.perf_counter()
        for _ in range(STEPS):
            out = f(q)
        float(jnp.ravel(out)[0])
        best = min(best, time.perf_counter() - t0)
    ms = best / STEPS * 1000
    tflops = flops_tot / (best / STEPS) / 1e12
    print(f"{name:28s}: {ms:7.2f} ms  {tflops:7.1f} TF/s", flush=True)


bench("einsum", lambda q: dot_product_attention(q, k, v, causal=True))
for bq, bk in [(128, 128), (256, 256), (256, 512), (512, 512), (512, 1024),
               (1024, 1024)]:
    bench(f"flash bq={bq} bk={bk}",
          lambda q, bq=bq, bk=bk: flash_attention(
              q, k, v, causal=True, block_q=bq, block_k=bk))
