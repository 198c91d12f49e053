"""Big-model inference benchmark: checkpoint load time + per-token decode,
on the REAL reference model families.

Mirror of ref benchmarks/big_model_inference.py (the reference's ONLY
published benchmark — benchmarks/README.md:25-36):

    model         | ref hardware      | ref load | ref s/token
    GPT-J-6B      | 2x Titan RTX fp16 |   8.7 s  | 0.05
    GPT-J-6B      | cpu-offload fp32  |  57  s   | 1.04
    GPT-NeoX-20B  | cpu-offload fp16  |  ~12 s   | 14.5
    T0pp (11B)    | 2x Titan RTX fp16 |  29  s   | 0.05-0.12
    OPT-30B       | cpu-offload fp16  |  ~12 s   | 10+

Zero-egress: a synthetic safetensors checkpoint with the model's EXACT
architecture (the real GPTJConfig/GPTNeoXConfig/OPTConfig/T5Config defaults
ARE the 6B/20B/30B/11B published sizes) is written once, then timed through
the real load path (init_empty_weights -> device-map plan -> streamed
safetensors load -> dispatch) and the family's KV-cache greedy decode:
- models that fit the chip (gptj-6b, and t0pp's decoder half) decode
  on-device at HBM rate;
- models larger than device memory (gpt-neox-20b, opt-30b) use
  `streamed_generate`: weights stream host->device double-buffered per
  layer, per token — the analogue of the reference's cpu-offload rows.
  `extra.streamed_gb_per_token` reports the traffic so s/token can be
  scaled to the host link it runs on (not measured on the current code).

Run: python benchmarks/big_model_inference.py --preset gptj-6b
     (presets: tiny-<family> for smoke, <family>-XXb for the real rows)
Prints one JSON line per phase.
"""

from __future__ import annotations

import argparse
import json
import os
import sys
import tempfile
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))


def _configs():
    from accelerate_tpu.models import gpt_neox, gptj, opt, t5

    # default config == the published size for each family
    return {
        "gptj-6b": ("gptj", gptj.GPTJConfig()),
        "gpt-neox-20b": ("gpt_neox", gpt_neox.GPTNeoXConfig()),
        "opt-30b": ("opt", opt.OPTConfig()),
        "t0pp": ("t5", t5.T5Config()),
        "tiny-gptj": ("gptj", gptj.GPTJConfig.tiny()),
        "tiny-gpt-neox": ("gpt_neox", gpt_neox.GPTNeoXConfig.tiny()),
        "tiny-opt": ("opt", opt.OPTConfig.tiny()),
        "tiny-t5": ("t5", t5.T5Config.tiny()),
    }


def main() -> None:
    parser = argparse.ArgumentParser()
    parser.add_argument("--preset", default="tiny-gptj",
                        choices=sorted(_configs()))
    parser.add_argument("--offload", action="store_true",
                        help="force host RAM placement + streamed decode "
                             "even if the model would fit")
    parser.add_argument("--new_tokens", type=int, default=None,
                        help="default: 32 on-chip, 3 streamed")
    parser.add_argument("--prompt_len", type=int, default=32)
    parser.add_argument("--checkpoint", default=None,
                        help="existing checkpoint dir (else synthesized)")
    args = parser.parse_args()

    import importlib

    if os.environ.get("JAX_PLATFORMS", "") == "cpu":
        # honor the caller's CPU request before any backend initializes
        from accelerate_tpu.utils.environment import force_cpu_platform

        force_cpu_platform()
    import jax
    import jax.numpy as jnp
    import numpy as np

    from accelerate_tpu import init_empty_weights, load_checkpoint_and_dispatch
    from accelerate_tpu.checkpointing import save_model
    from accelerate_tpu.models.common import count_params

    family, cfg = _configs()[args.preset]
    mod = importlib.import_module(f"accelerate_tpu.models.{family}")
    tiny = args.preset.startswith("tiny")
    dtype = jnp.float32 if tiny else jnp.bfloat16

    shapes = jax.eval_shape(
        lambda: mod.init_params(cfg, jax.random.key(0), dtype=dtype)
    )
    n_bytes = sum(
        int(np.prod(l.shape)) * l.dtype.itemsize
        for l in jax.tree_util.tree_leaves(shapes)
    )
    dev_mem = getattr(jax.devices()[0], "memory_stats", lambda: None)() or {}
    hbm = dev_mem.get("bytes_limit", 16 * 2**30)
    # resident decode needs weights + caches + programs; 80% HBM is the
    # practical ceiling (same margin utils/modeling.get_balanced_memory uses)
    streamed = args.offload or n_bytes > 0.8 * hbm

    ckpt = args.checkpoint
    tmp = None
    if ckpt is None:
        tmp = tempfile.mkdtemp(dir=os.environ.get("BENCH_TMPDIR"))
        ckpt = os.path.join(tmp, "model")
        # synthesize HOST-side (numpy from eval_shape): initializing on the
        # device and pulling the weights back would time the host link,
        # not the load path this benchmark measures. zeros: timing is
        # value-independent (decode FLOPs/bytes identical) and writing GBs of
        # zeros is instant vs sampling billions of normals
        params = jax.tree_util.tree_map(
            lambda l: np.zeros(l.shape, l.dtype), shapes
        )
        save_model(params, ckpt, max_shard_size="2GB")
        del params

    # --- timed load: abstract init -> plan -> streamed safetensors -> place
    t0 = time.perf_counter()
    shapes = init_empty_weights(mod.init_params, cfg, jax.random.key(0),
                                dtype=dtype)
    if streamed:
        # layers stay in host RAM for the streaming decode; small resident
        # modules (embeddings, norms, head) go to the device. For t5 the
        # DECODER half is placed on device at load time (it runs every
        # token; load-time placement matches the reference's accounting,
        # where `load` puts weights wherever they will execute) — only the
        # run-once encoder streams per prompt.
        stacked = "encoder" if family == "t5" else "layers"
        device_map = {
            name: ("cpu" if name == stacked else 0) for name in shapes
        }
    else:
        device_map = "auto"
    params = load_checkpoint_and_dispatch(shapes, ckpt, device_map=device_map)
    load_s = time.perf_counter() - t0
    n_params = count_params(params)
    print(json.dumps({
        "metric": "big_model_load_seconds",
        "value": round(load_s, 2),
        "unit": "s",
        "extra": {"preset": args.preset, "params": n_params,
                  "bytes": n_bytes, "streamed": streamed},
    }), flush=True)

    # --- timed decode (greedy, KV cache)
    new_tokens = args.new_tokens or (3 if streamed and not tiny else 32)
    vocab = getattr(cfg, "vocab_size")
    ids = np.random.default_rng(0).integers(
        4, vocab, (1, args.prompt_len)).astype(np.int32)

    if streamed:
        gen = lambda: mod.streamed_generate(  # noqa: E731
            cfg, params, ids, max_new_tokens=new_tokens, dtype=dtype)
    else:
        gen = lambda: mod.generate(  # noqa: E731
            cfg, params, ids, max_new_tokens=new_tokens)

    t0 = time.perf_counter()
    out = gen()
    jax.block_until_ready(out)
    first = time.perf_counter() - t0  # includes compile
    t0 = time.perf_counter()
    out = gen()
    jax.block_until_ready(out)
    decode_s = time.perf_counter() - t0
    extra = {
        "preset": args.preset, "new_tokens": new_tokens,
        "first_call_with_compile_s": round(first, 2),
        "mode": "streamed-offload" if streamed else "on-device",
    }
    if streamed:
        # per generated token, every stacked layer's weights cross the
        # host->device link once; for t5 the decoder is resident and only
        # the run-once encoder streams, PER PROMPT not per token
        if family == "t5":
            enc_bytes = sum(
                int(np.prod(l.shape)) * l.dtype.itemsize
                for l in jax.tree_util.tree_leaves(shapes["encoder"])
            )
            extra["streamed_gb_per_prompt"] = round(enc_bytes / 2**30, 2)
        else:
            stacked_bytes = sum(
                int(np.prod(l.shape)) * l.dtype.itemsize
                for l in jax.tree_util.tree_leaves(shapes["layers"])
            )
            extra["streamed_gb_per_token"] = round(stacked_bytes / 2**30, 2)
    print(json.dumps({
        "metric": "big_model_seconds_per_token",
        "value": round(decode_s / new_tokens, 4),
        "unit": "s/token",
        "extra": extra,
    }), flush=True)
    if tmp:
        import shutil

        shutil.rmtree(tmp, ignore_errors=True)


if __name__ == "__main__":
    main()
