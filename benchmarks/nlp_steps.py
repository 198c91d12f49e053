"""BASELINE.md row 1: `examples/nlp_example.py` steps/sec/chip.

The reference publishes no number for its nlp_example (BASELINE.md:36 —
"to be measured"); this captures ours on whatever chip is visible:
BERT-base (or --tiny) on the example's synthetic MRPC batches, the same
fused train_step the example runs, steps/sec over a timed window after a
compile warmup. Prints ONE JSON line; appended to
`bench_results/nlp_steps.jsonl` by the Makefile-style invocation in
docs/benchmarking.md.

Run: python benchmarks/nlp_steps.py [--tiny] [--batch 32] [--steps 30]
"""

from __future__ import annotations

import argparse
import json
import os
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
sys.path.insert(0, os.path.join(
    os.path.dirname(os.path.dirname(os.path.abspath(__file__))), "examples"))


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--tiny", action="store_true")
    ap.add_argument("--batch", type=int, default=32)
    ap.add_argument("--steps", type=int, default=30)
    ap.add_argument("--mixed_precision", default="bf16")
    args = ap.parse_args()

    if os.environ.get("JAX_PLATFORMS", "") == "cpu":
        from accelerate_tpu.utils.environment import force_cpu_platform

        force_cpu_platform()
    import jax
    import optax

    from accelerate_tpu import TrainState
    from accelerate_tpu.accelerator import Accelerator
    from accelerate_tpu.models import bert
    from nlp_example import get_dataloaders

    acc = Accelerator(mixed_precision=args.mixed_precision,
                      gradient_clipping=1.0)
    cfg = bert.BertConfig.tiny() if args.tiny else bert.BertConfig.base()
    train_loader, _ = get_dataloaders(acc, args.batch, cfg)
    params = bert.init_params(cfg, jax.random.key(0))
    ts = acc.prepare(TrainState.create(
        apply_fn=None, params=params, tx=optax.adamw(2e-5)))
    step = acc.train_step(
        lambda p, b: bert.classification_loss(cfg, p, b)
    )
    batches = list(train_loader)
    ts, m = step(ts, batches[0])  # compile + warmup
    float(m["loss"])

    done = 0
    t0 = time.perf_counter()
    while done < args.steps:
        for b in batches:
            ts, m = step(ts, b)
            done += 1
            if done >= args.steps:
                break
    float(m["loss"])  # block
    dt = time.perf_counter() - t0
    n_chips = jax.device_count()
    dev = jax.devices()[0]
    print(json.dumps({
        "metric": "nlp_example_steps_per_sec_per_chip",
        "value": round(args.steps / dt / n_chips, 3),
        "unit": "steps/s/chip",
        "extra": {
            "model": "bert-tiny" if args.tiny else "bert-base",
            "batch": args.batch, "steps": args.steps,
            "wall_s": round(dt, 2), "n_chips": n_chips,
            "device": getattr(dev, "device_kind", dev.platform),
            "mixed_precision": args.mixed_precision,
        },
    }))


if __name__ == "__main__":
    main()
