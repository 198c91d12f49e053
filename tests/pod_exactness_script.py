"""Pod-scale serving acceptance script (run under forced host devices).

Launched by tests/test_pod.py through the `forced_device_run` fixture
with `XLA_FLAGS=--xla_force_host_platform_device_count=N`: proves, in a
process whose WHOLE backend is the N-device mesh, that

- the mesh-sharded engine (serving.pod.sharded_engine over all N
  devices, strict="error" so every sharded program passes the
  pod_program_contracts audit) produces byte-identical token streams to
  the single-device engine on the same seeded trace, with compile
  counts flat at admit/prefill/decode = 1;
- the disaggregated prefill->decode pod (1+1 workers, each
  tensor-parallel over the same N devices — layer 1 composed under
  layer 2) produces the same byte-identical streams, with the
  extract/install programs also compiling exactly once.

Prints POD_EXACTNESS_OK on success; any mismatch asserts (the parent
test surfaces the child's output).
"""

import os
import sys

os.environ.setdefault("JAX_PLATFORMS", "cpu")

import jax  # noqa: E402

import dataclasses  # noqa: E402

import jax.numpy as jnp  # noqa: E402
import numpy as np  # noqa: E402

# opt in to the parent fixture's exported compilation cache (no-op when
# the env var is unset): the N=2 and N=4 children share the
# single-device reference compiles instead of each paying them
from accelerate_tpu.utils.environment import (  # noqa: E402
    configure_compilation_cache)

configure_compilation_cache()

from accelerate_tpu.models import gpt2  # noqa: E402
from accelerate_tpu.serving import Engine, EngineConfig  # noqa: E402
from accelerate_tpu.serving.pod import (  # noqa: E402
    PodConfig,
    PodEngine,
    sharded_engine,
)


def run_trace(engine, cfg):
    """Seeded multi-request mix: staggered arrivals, greedy + sampled
    temperatures, a budget-1 request, and an interleaved long prompt."""
    rng = np.random.default_rng(7)
    prompts = [rng.integers(0, cfg.vocab_size, (n,)).astype(np.int32)
               for n in (5, 11, 3, 17, 6)]
    reqs = [engine.submit(prompts[0], max_new_tokens=6)]
    for _ in range(3):
        engine.step()
    reqs.append(engine.submit(prompts[1], max_new_tokens=6, temperature=0.7))
    reqs.append(engine.submit(prompts[2], max_new_tokens=4))
    reqs.append(engine.submit(prompts[3], max_new_tokens=4, temperature=1.1))
    reqs.append(engine.submit(prompts[4], max_new_tokens=1))
    engine.run_until_idle()
    assert all(r.status.value == "finished" for r in reqs), \
        [(r.status.value, r.reject_reason) for r in reqs]
    return [r.tokens for r in reqs]


def main() -> None:
    n = int(sys.argv[1])
    assert jax.device_count() == n, (
        f"expected {n} forced host devices, got {jax.devices()}")

    cfg = gpt2.GPT2Config.tiny()
    params = gpt2.init_params(cfg, jax.random.key(0))
    ec = EngineConfig(num_slots=3, max_len=64, prefill_chunk=8,
                      cache_dtype=jnp.float32)

    ref = run_trace(Engine(gpt2, cfg, params, ec), cfg)

    # layer 1: one engine sharded over the full N-device mesh, strict
    sh = sharded_engine(gpt2, cfg, params,
                        dataclasses.replace(ec, strict="error"),
                        tensor_parallel=n)
    got = run_trace(sh, cfg)
    assert got == ref, f"sharded N={n} diverged: {got} != {ref}"
    stats = sh.compile_stats()
    assert stats == {"admit": 1, "prefill": 1, "decode": 1}, stats

    # layer 2 (composed with layer 1): disaggregated pod, TP-N workers,
    # strict audit on — every sharded program incl. extract/install must
    # satisfy the pod contracts
    pod = PodEngine(gpt2, cfg, params, dataclasses.replace(ec, strict="error"),
                    PodConfig(prefill_workers=1, decode_workers=1,
                              tensor_parallel=n))
    got = run_trace(pod, cfg)
    assert got == ref, f"pod N={n} diverged: {got} != {ref}"
    stats = pod.compile_stats()
    assert stats == {"admit": 1, "prefill": 1, "decode": 1,
                     "extract": 1, "install": 1}, stats
    assert pod.metrics_summary()["pod_shipments"] >= 3

    print("POD_EXACTNESS_OK")


if __name__ == "__main__":
    main()
