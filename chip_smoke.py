"""chip_smoke.py: prove that the trainer and the serving engine run on the chip.

    python chip_smoke.py               # one TPU chip: train phase + serve phase
    python chip_smoke.py --multichip   # four chips: the sharded train step only

One process (a chip belongs to one process), started from the root of a
plain copy of the tree: no install, no git, no network. Every line but the
last is free-form evidence (sizes, the depth cut, losses, compile seconds,
which attention path ran, the compile-cache directory). The LAST line of
stdout is the contract line:

    {"ok": true, "device": {"platform": "tpu", "kind": "...", "count": N}}

Any phase that fails prints `"ok": false` there and the exit code is 1. No
TPU means failure at once: nothing here carries on on the CPU, in Pallas
interpret mode (`ops.kernel_mode.require_compiled`) or on a reference path.

The model is Qwen2-1.5B at its published widths (hidden 1536, MLP 8960,
12 heads / 2 KV heads x 128, vocab 151936, tied embeddings, q/k/v biases,
rope theta 1e6) through `models/llama.py`, with seeded random weights.
Widths are never cut. Depth is cut for TRAINING only, as far as 16 GB
forces (fp32 master weights + AdamW moments + grads = 16 B a parameter);
serving runs all 28 layers in bf16.

The phase functions take a `Sizes`, so `tests/test_chip_smoke.py` can
rehearse the control flow on the CPU at `TINY` sizes; `main()` itself only
ever runs `REAL` sizes and only on a TPU.
"""

from __future__ import annotations

import argparse
import asyncio
import dataclasses
import json
import os
import shutil
import sys
import time
import traceback

ROOT = os.path.dirname(os.path.abspath(__file__))


@dataclasses.dataclass(frozen=True)
class Sizes:
    """Everything a phase sizes itself by. `REAL` is what the chip runs."""

    # model widths (Qwen2-1.5B, config.json of Qwen/Qwen2-1.5B)
    vocab_size: int = 151936
    hidden_size: int = 1536
    intermediate_size: int = 8960
    num_attention_heads: int = 12
    num_key_value_heads: int = 2
    full_layers: int = 28
    max_position_embeddings: int = 32768
    # train phase: depth cut so that 16 B/param of fp32 weights, AdamW
    # moments and grads (233M embedding + 46.8M a layer) plus activations
    # fit 16 GB: 8 layers = 608M params = 9.7 GB of state
    train_layers: int = 8
    train_batch: int = 2
    train_seq: int = 2048
    train_steps: int = 6
    # serve phase
    serve_slots: int = 8
    serve_max_len: int = 512
    serve_prefill_chunk: int = 64
    serve_prompt_lens: tuple = (5, 23, 64, 150, 37, 96)
    serve_new_tokens: int = 12
    # the op-level kernel check (pool geometry at the serving widths)
    op_slots: int = 8
    op_pages_per_slot: int = 24
    op_num_pages: int = 256
    # the selection check: a prefill chunk's scores at the sparse cells'
    # shape (512 queries over a view of 43,520 rows, 2,048 keys a query)
    select_rows: int = 512
    select_columns: int = 43520
    select_block: int = 1024
    select_k: int = 2048
    # --multichip
    multi_layers: int = 4
    multi_batch: int = 8
    multi_steps: int = 4


REAL = Sizes()
# the CPU rehearsal of the control flow (tests only; never used by main())
TINY = Sizes(
    vocab_size=512, hidden_size=64, intermediate_size=128,
    num_attention_heads=4, num_key_value_heads=2, full_layers=2,
    max_position_embeddings=256, train_layers=2, train_batch=2,
    train_seq=32, train_steps=4, serve_slots=2, serve_max_len=64,
    serve_prefill_chunk=8, serve_prompt_lens=(3, 9, 17), serve_new_tokens=4,
    op_slots=2, op_pages_per_slot=3, op_num_pages=8, select_rows=40,
    select_columns=1300, select_block=512, select_k=50, multi_layers=2,
    multi_batch=8, multi_steps=3)

# --- tolerances, each with its reason ---------------------------------------
# kernel vs paged_decode_reference, bf16 outputs of magnitude <~ 2: one bf16
# ulp there is 2^-7 = 0.0078, and the two sides round once each
OP_TOL = 0.02
# per-token logprob, kernel engine vs dense-gather engine: same bf16 model,
# only the decode attention op differs (f32 online softmax in VMEM vs bf16
# einsum over the gathered view); 28 layers of bf16 residual carry that to
# a few 1e-2 at logits of magnitude ~4 (first chip run: 0.049)
ENGINE_LOGPROB_TOL = 0.1
# per-token logprob, bf16 engine vs float32 "highest" full forward of the
# same (bf16-valued) weights: bf16 activations through 28 layers and a
# 1536-wide contraction into 151936 logits (first chip run: 0.057)
F32_LOGPROB_TOL = 0.2
# a greedy token may differ between two paths only where the float32
# reference itself ranks the two candidates within this many nats
NEAR_TIE_NATS = 2 * F32_LOGPROB_TOL
# sharded vs one-device train loss: same bf16 math, different reduction
# order across shards (and per-shard flash blocks); relative
MULTI_LOSS_RTOL = 2e-2


def log(msg: str) -> None:
    print(f"[chip_smoke] {msg}", flush=True)


_CACHE_EVENTS = {"requests": 0, "hits": 0, "misses": 0}


def _count_cache_events() -> None:
    """Count jax's own persistent-compile-cache events, so that a run can
    SAY whether its compiles were served from the cache instead of leaving
    it to be guessed from seconds."""
    import jax

    names = {"/jax/compilation_cache/compile_requests_use_cache": "requests",
             "/jax/compilation_cache/cache_hits": "hits",
             "/jax/compilation_cache/cache_misses": "misses"}

    def on_event(event: str, **_):
        key = names.get(event)
        if key:
            _CACHE_EVENTS[key] += 1

    jax.monitoring.register_event_listener(on_event)


def log_cache_events(phase: str) -> None:
    log(f"{phase}: persistent compile cache so far: "
        f"{_CACHE_EVENTS['requests']} compiles asked it, "
        f"{_CACHE_EVENTS['hits']} hits, {_CACHE_EVENTS['misses']} new entries "
        f"written")


def model_config(s: Sizes, layers: int, **overrides):
    from accelerate_tpu.models import llama

    return llama.LlamaConfig(
        vocab_size=s.vocab_size, hidden_size=s.hidden_size,
        intermediate_size=s.intermediate_size, num_hidden_layers=layers,
        num_attention_heads=s.num_attention_heads,
        num_key_value_heads=s.num_key_value_heads,
        max_position_embeddings=s.max_position_embeddings,
        rope_theta=1e6, rms_norm_eps=1e-6, attention_bias=True,
        tie_word_embeddings=True, attention_backend="auto", **overrides)


def _attention_path(cfg, seq_len: int) -> str:
    """What `attention_backend="auto"` resolves to for this process, by the
    model's own resolver (the same call `models/llama.py` makes)."""
    import jax

    from accelerate_tpu.models import llama

    return llama.select_attention_backend(
        cfg.attention_backend, on_tpu=jax.devices()[0].platform == "tpu",
        decoding=False, seq_len=seq_len)


def _xor_bits(leaves):
    import jax
    import jax.numpy as jnp

    def one(x):
        bits = jax.lax.bitcast_convert_type(
            x.astype(jnp.float32) if jnp.issubdtype(x.dtype, jnp.floating)
            else x.astype(jnp.int32), jnp.uint32)
        return jnp.bitwise_xor.reduce(bits.reshape(-1)) if bits.size else \
            jnp.uint32(0)

    return [one(x) for x in leaves]


def _state_checksums(state):
    """One uint32 per array leaf (xor of its bits), on the device."""
    import jax

    leaves = [x for x in jax.tree_util.tree_leaves(state)
              if hasattr(x, "dtype") and hasattr(x, "shape")]
    return [int(v) for v in jax.jit(_xor_bits)(leaves)]


# ---------------------------------------------------------------------------
# train phase
# ---------------------------------------------------------------------------


def _token_batches(s: Sizes, batch: int, n_batches: int, work_dir: str,
                   seed: int):
    """A seeded token corpus on disk, read back through the repo's own
    `TokenCorpusLoader` (the C++ loader built from the tracked source, or
    its NumPy twin). The corpus has few distinct sequences, so a handful of
    steps can already lower the loss on it."""
    import numpy as np

    from accelerate_tpu.native import TokenCorpusLoader, write_token_file

    rng = np.random.default_rng(seed)
    sample_len = s.train_seq + 1
    base = rng.integers(0, s.vocab_size, (batch, sample_len)).astype(np.int32)
    corpus = np.tile(base, (n_batches, 1))
    path = write_token_file(os.path.join(work_dir, "corpus.bin"), corpus)
    loader = TokenCorpusLoader(path, sample_len=sample_len, batch_size=batch,
                               shuffle=False, seed=seed)
    return loader


def train_phase(s: Sizes, work_dir: str, expect_chip: bool, seed: int = 0):
    """`Accelerator` -> `prepare(TrainState)` -> `prepare(batches)` ->
    `train_step(causal_lm_loss)`: the calls of `bench.py`'s train phase."""
    import jax
    import numpy as np
    import optax

    from accelerate_tpu import TrainState
    from accelerate_tpu.accelerator import Accelerator
    from accelerate_tpu.models import llama
    from accelerate_tpu.models.common import count_params
    from accelerate_tpu.ops.kernel_mode import kernel_report
    from accelerate_tpu.state import PartialState

    cfg = model_config(s, s.train_layers, remat=True, remat_policy="dots")
    log(f"train: Qwen2-1.5B widths hidden={s.hidden_size} mlp="
        f"{s.intermediate_size} heads={s.num_attention_heads}/"
        f"{s.num_key_value_heads}x{cfg.head_dim} vocab={s.vocab_size}; "
        f"depth cut {s.full_layers} -> {s.train_layers} layers (fp32 master "
        f"weights + AdamW state at 16 B/param must fit 16 GB)")
    path = _attention_path(cfg, s.train_seq)
    log(f"train: attention_backend='auto' resolves to '{path}' at seq "
        f"{s.train_seq}")
    if expect_chip and path != "flash":
        raise AssertionError(
            f"auto attention resolved to {path!r}, not the flash kernel")

    acc = Accelerator(mixed_precision="bf16", gradient_clipping=1.0)
    log(f"train: compile cache dir = {acc.state.partial_state.compilation_cache_dir}")
    params = llama.init_params(cfg, jax.random.key(seed))
    ts = acc.prepare(TrainState.create(apply_fn=None, params=params,
                                       tx=optax.adamw(3e-4)))
    del params
    n_params = count_params(ts.params)
    log(f"train: {n_params / 1e6:.1f}M parameters, batch {s.train_batch} x "
        f"seq {s.train_seq}")

    corpus = _token_batches(s, s.train_batch, s.train_steps, work_dir, seed)
    log(f"train: token loader implementation = {corpus.implementation}")
    loader = acc.prepare(corpus)
    step = acc.train_step(lambda p, b: llama.causal_lm_loss(cfg, p, b))

    losses, compile_s = [], None
    compiles_after_warmup = None
    for i, batch in enumerate(loader):
        t0 = time.perf_counter()
        ts, m = step(ts, batch)
        loss = float(m["loss"])
        dt = time.perf_counter() - t0
        losses.append(loss)
        if i == 0:
            compile_s = dt
            compiles_after_warmup = step._aot_compiles + step._cache_size()
        log(f"train: step {i} loss {loss:.4f} ({dt:.2f}s"
            f"{', includes compile' if i == 0 else ''})")
    recompiles = (step._aot_compiles + step._cache_size()
                  - compiles_after_warmup)
    log(f"train: first step (trace + compile + run) {compile_s:.1f}s; "
        f"recompiles after warm-up = {recompiles}; pin computations = "
        f"{step._pin_computations}")
    if len(losses) != s.train_steps:
        raise AssertionError(f"loader gave {len(losses)} batches")
    if not all(np.isfinite(losses)):
        raise AssertionError(f"non-finite loss: {losses}")
    if not losses[-1] < losses[0]:
        raise AssertionError(f"loss did not fall: {losses}")
    if recompiles != 0:
        raise AssertionError(f"{recompiles} recompiles after warm-up")
    if expect_chip and kernel_report().get("flash_attention") != "compiled":
        raise AssertionError(
            f"flash kernel did not run compiled: {kernel_report()}")
    log(f"train: kernels traced so far: {kernel_report()}")

    # one save_state / load_state round trip, compared bit for bit
    before = _state_checksums((ts.params, ts.opt_state))
    ckpt = acc.save_state(os.path.join(work_dir, "ckpt"), state=ts)
    restored = acc.load_state(ckpt, state=ts)["train_states"][0]
    after = _state_checksums((restored.params, restored.opt_state))
    if before != after:
        bad = sum(a != b for a, b in zip(before, after))
        raise AssertionError(f"checkpoint round trip changed {bad} leaves")
    log(f"train: save_state/load_state round trip bit-identical over "
        f"{len(before)} leaves")

    # hand the device back before the serve phase builds its 28 layers
    corpus.close()
    del ts, restored, step, loader, m, batch
    acc.free_memory()
    PartialState._reset_state()
    if expect_chip:
        jax.clear_caches()  # free the executables' device memory
    return {"losses": losses, "compile_s": compile_s, "params": n_params}


# ---------------------------------------------------------------------------
# serve phase
# ---------------------------------------------------------------------------


def kernel_op_check(s: Sizes, seed: int = 0) -> None:
    """`paged_decode_attention` against `paged_decode_reference` on seeded
    pools and tables at the serving widths, bf16 and int8 pools: a
    stacked pool of two layers, read at the second."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from accelerate_tpu.ops.paged_attention import (
        PagedDecodeMeta,
        PagedKV,
        paged_decode_attention,
        paged_decode_reference,
    )
    from accelerate_tpu.ops.quant import kv_quantize_rows

    cfg = model_config(s, 1)
    S, P, N, ps = s.op_slots, s.op_pages_per_slot, s.op_num_pages, 16
    Hkv, H, D = s.num_key_value_heads, s.num_attention_heads, cfg.head_dim
    rng = np.random.default_rng(seed)
    shape = (2, N + 1, Hkv, ps, D)
    layer = jnp.int32(1)
    pool_k = jnp.asarray(rng.normal(size=shape), jnp.bfloat16)
    pool_v = jnp.asarray(rng.normal(size=shape), jnp.bfloat16)
    # each slot owns a random set of pages; lengths cover empty, mid-page,
    # page-boundary and full slots; unused table entries are the trash page
    lengths = np.array([0, 1, ps, ps + 3, 5 * ps, P * ps - 1, 7, 2 * ps + 9]
                       * (S // 8 + 1))[:S].clip(0, P * ps - 1)
    table = np.full((S, P), N, np.int32)
    pages = rng.permutation(N)
    at = 0
    for i in range(S):
        need = -(-int(lengths[i] + 1) // ps)
        table[i, :need] = pages[at:at + need]
        at += need
    meta = PagedDecodeMeta(jnp.asarray(table),
                           jnp.asarray(lengths, jnp.int32), rows=P * ps)
    q = jnp.asarray(rng.normal(size=(S, 1, H, D)), jnp.bfloat16)
    kn = jnp.asarray(rng.normal(size=(S, 1, Hkv, D)), jnp.bfloat16)
    vn = jnp.asarray(rng.normal(size=(S, 1, Hkv, D)), jnp.bfloat16)
    ck, sk = kv_quantize_rows(pool_k)
    cv, sv = kv_quantize_rows(pool_v)
    for name, pk, pv in (
            ("bf16", PagedKV(pool_k, layer=layer),
             PagedKV(pool_v, layer=layer)),
            ("int8", PagedKV(ck, sk, jnp.bfloat16, layer),
             PagedKV(cv, sv, jnp.bfloat16, layer))):
        out, _ = jax.jit(paged_decode_attention)(q, kn, vn, pk, pv, meta)
        with jax.default_matmul_precision("highest"):
            ref, _ = jax.jit(paged_decode_reference)(q, kn, vn, pk, pv, meta)
        out, ref = np.asarray(out, np.float32), np.asarray(ref, np.float32)
        err = float(np.max(np.abs(out - ref)))
        log(f"serve: paged_decode_attention vs reference ({name} pool, "
            f"S={S} Hkv={Hkv} G={H // Hkv} D={D} page {ps}): max abs err "
            f"{err:.5f} (tolerance {OP_TOL})")
        if not np.all(np.isfinite(out)) or err > OP_TOL:
            raise AssertionError(f"kernel disagrees with reference: {err}")


def selection_op_check(s: Sizes, seed: int = 0) -> None:
    """`exact_topk_mask_rows` (the rows kernel of a prefill chunk's
    selection) against `exact_topk_mask` (XLA's loop), bit for bit, on
    seeded scores: distinct values under live bounds inside and at the
    view's end, and values quantized until the k-th is a tie that must be
    cut; each as one row-major array and in the blocks of columns an
    indexer's loop leaves its scores in."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from accelerate_tpu.ops.sparse_paged_attention import (
        exact_topk_mask,
        exact_topk_mask_rows,
    )

    S, N, k = s.select_rows, s.select_columns, s.select_k
    block = s.select_block
    n = -(-N // block)
    rng = np.random.default_rng(seed)
    x = rng.normal(size=(1, S, N)).astype(np.float32)
    col = np.arange(N)
    kernel = jax.jit(exact_topk_mask_rows, static_argnums=(1, 3))
    reference = jax.jit(exact_topk_mask, static_argnums=1)
    for name, scores, live in (
            ("distinct scores, three quarters live", x, 3 * N // 4 + 1),
            ("distinct scores, all live", x, N),
            ("scores in steps of 1/4, half live", np.round(x * 4) / 4,
             N // 2)):
        scores = jnp.asarray(np.where(col < live, scores, -np.inf),
                             jnp.float32)
        blocks = jnp.moveaxis(jnp.pad(
            scores, ((0, 0), (0, 0), (0, n * block - N)),
            constant_values=-jnp.inf).reshape(1, S, n, block), 2, 0)
        got = np.asarray(kernel(scores, k, jnp.int32(live), None))
        want = np.asarray(reference(scores, k))
        wrong = int((got != want).sum()) + int((np.asarray(kernel(
            blocks, k, jnp.int32(live), N)) != want).sum())
        log(f"serve: exact_topk_mask_rows vs exact_topk_mask ([{S}, {N}], "
            f"k={k}, {name}): {int(got.sum())} selected, {wrong} differ")
        if wrong or int(got.sum()) != S * min(k, live):
            raise AssertionError(
                f"the rows selection kernel disagrees with XLA's loop: "
                f"{wrong} positions ({name})")


def _prompts(s: Sizes, seed: int):
    import numpy as np

    rng = np.random.default_rng(seed + 1)
    return [rng.integers(0, s.vocab_size, (n,)).astype(np.int32)
            for n in s.serve_prompt_lens]


def _f32_reference_logprobs(cfg, params, prompts, generated):
    """Float32 full forward ("highest" matmul precision) of each prompt +
    the tokens the kernel engine generated: per generated token its
    logprob, and the full log-softmax rows (to judge near ties)."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from accelerate_tpu.models import llama

    T = max(len(p) + len(g) for p, g in zip(prompts, generated))
    ids = np.zeros((len(prompts), T), np.int32)
    for i, (p, g) in enumerate(zip(prompts, generated)):
        ids[i, :len(p)] = p
        ids[i, len(p):len(p) + len(g)] = g
    gen_len = max(len(g) for g in generated)
    starts = np.array([len(p) - 1 for p in prompts], np.int32)

    @jax.jit
    def run(params, ids, starts):
        p32 = jax.tree_util.tree_map(lambda x: x.astype(jnp.float32), params)
        logits = llama.forward(cfg, p32, ids).astype(jnp.float32)
        rows = jax.vmap(lambda l, st: jax.lax.dynamic_slice_in_dim(
            l, st, gen_len, axis=0))(logits, starts)
        return jax.nn.log_softmax(rows, axis=-1)

    with jax.default_matmul_precision("highest"):
        rows = np.asarray(run(params, jnp.asarray(ids), jnp.asarray(starts)))
    lps = [[float(rows[i, j, t]) for j, t in enumerate(g)]
           for i, g in enumerate(generated)]
    return lps, rows


def _compare_streams(name, a_tokens, a_lps, b_tokens, b_lps, ref_rows, tol):
    """Two engines on the same submits: logprobs within `tol` up to the
    first token disagreement of each request; a disagreement is allowed
    only at a near tie of the float32 reference. Returns (max logprob gap,
    disagreements)."""
    worst, disagreements = 0.0, 0
    for i, (ta, la, tb, lb) in enumerate(zip(a_tokens, a_lps, b_tokens,
                                             b_lps)):
        for j, (x, y) in enumerate(zip(ta, tb)):
            if x != y:
                disagreements += 1
                gap = abs(float(ref_rows[i, j, x]) - float(ref_rows[i, j, y]))
                log(f"serve: {name}: request {i} token {j} differs "
                    f"({x} vs {y}); float32 reference ranks them "
                    f"{gap:.4f} nats apart")
                if gap > NEAR_TIE_NATS:
                    raise AssertionError(
                        f"{name}: tokens differ away from a tie "
                        f"({gap:.4f} > {NEAR_TIE_NATS} nats)")
                break  # the streams legitimately diverge from here on
            worst = max(worst, abs(la[j] - lb[j]))
    if worst > tol:
        raise AssertionError(
            f"{name}: logprobs differ by {worst:.4f} > {tol}")
    return worst, disagreements


async def _http_completion(port: int, body: dict):
    reader, writer = await asyncio.open_connection("127.0.0.1", port)
    payload = json.dumps(body).encode()
    writer.write(b"POST /v1/completions HTTP/1.1\r\nHost: smoke\r\n"
                 b"Content-Length: %d\r\n\r\n%s" % (len(payload), payload))
    await writer.drain()
    raw = await reader.read()
    writer.close()
    head, _, body_out = raw.partition(b"\r\n\r\n")
    return int(head.split(b" ")[1]), body_out


def serve_phase(s: Sizes, expect_chip: bool, seed: int = 0):
    """`serving.Engine` + the service stack `accelerate-tpu serve` builds
    (`InferenceService` + `HttpFrontDoor`), in this process."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from accelerate_tpu.models import llama
    from accelerate_tpu.models.common import count_params
    from accelerate_tpu.ops.kernel_mode import kernel_report
    from accelerate_tpu.server.config import ServerConfig
    from accelerate_tpu.server.http import HttpFrontDoor
    from accelerate_tpu.server.service import InferenceService
    from accelerate_tpu.server.tokenizer import get_tokenizer
    from accelerate_tpu.serving import Engine, EngineConfig
    from accelerate_tpu.utils.environment import configure_compilation_cache

    # engines build without PartialState: opt into the cache as the pod
    # workers do (a no-op when the train phase already configured it)
    log(f"serve: compile cache dir = {configure_compilation_cache()}")
    kernel_op_check(s, seed)
    selection_op_check(s, seed)

    cfg = model_config(s, s.full_layers)
    params = llama.init_params(cfg, jax.random.key(seed), dtype=jnp.bfloat16)
    log(f"serve: Qwen2-1.5B widths, all {s.full_layers} layers, bf16, "
        f"{count_params(params) / 1e6:.1f}M parameters; slots "
        f"{s.serve_slots}, max_len {s.serve_max_len}, prefill chunk "
        f"{s.serve_prefill_chunk}")

    def engine(paged_attention):
        return Engine(llama, cfg, params, EngineConfig(
            num_slots=s.serve_slots, max_len=s.serve_max_len,
            prefill_chunk=s.serve_prefill_chunk, cache_dtype=jnp.bfloat16,
            seed=seed, paged_attention=paged_attention))

    # on the chip "auto" must pick the kernel by itself; the CPU rehearsal
    # has to ask for it (there "auto" means the dense path)
    kern = engine("auto" if expect_chip else True)
    if not kern._use_paged_kernel:
        raise AssertionError("paged_attention='auto' did not pick the kernel")
    log("serve: paged_attention='auto' resolved to the Pallas kernel"
        if expect_chip else "serve: rehearsal asked for the kernel")

    prompts = _prompts(s, seed)

    def drive(eng):
        t0 = time.perf_counter()
        reqs = [eng.submit(p, max_new_tokens=s.serve_new_tokens)
                for p in prompts[:-1]]
        # the last request streams while the others are in flight
        last = eng.submit(prompts[-1], max_new_tokens=s.serve_new_tokens)
        streamed = list(eng.stream(last))
        eng.run_until_idle()
        reqs.append(last)
        if streamed != list(last.tokens):
            raise AssertionError("stream() and the handle disagree")
        for r in reqs:
            if r.status.value != "finished":
                raise AssertionError(f"request ended {r.status}: "
                                     f"{getattr(r, 'reject_reason', None)}")
        return ([list(r.tokens) for r in reqs],
                [list(r.logprobs) for r in reqs], time.perf_counter() - t0)

    k_tokens, k_lps, k_s = drive(kern)
    log(f"serve: kernel engine answered {len(prompts)} requests (prompt "
        f"lengths {list(s.serve_prompt_lens)}, {s.serve_new_tokens} new "
        f"tokens each) in {k_s:.1f}s including compiles")
    log(f"serve: first request tokens {k_tokens[0]}")
    for lps in k_lps:
        if len(lps) != s.serve_new_tokens or not np.all(np.isfinite(lps)):
            raise AssertionError(f"bad logprobs {lps}")
    if expect_chip and kernel_report().get(
            "paged_decode_attention") != "compiled":
        raise AssertionError(f"paged kernel not compiled: {kernel_report()}")
    log(f"serve: kernels traced so far: {kernel_report()}")

    # the same submits through the dense-gather engine
    dense = engine(False)
    d_tokens, d_lps, d_s = drive(dense)
    if dense.compile_stats() != {"admit": 1, "prefill": 1, "decode": 1}:
        raise AssertionError(f"dense engine {dense.compile_stats()}")
    dense.close()
    del dense
    ref_lps, ref_rows = _f32_reference_logprobs(cfg, params, prompts,
                                                k_tokens)
    worst, n_dis = _compare_streams(
        "kernel vs dense engine", k_tokens, k_lps, d_tokens, d_lps,
        ref_rows, ENGINE_LOGPROB_TOL)
    log(f"serve: kernel vs dense-gather engine: max logprob gap "
        f"{worst:.4f} (tolerance {ENGINE_LOGPROB_TOL}), {n_dis} token "
        f"disagreements (all at float32 near ties); dense run {d_s:.1f}s")
    worst32 = max(abs(a - b) for la, lb in zip(k_lps, ref_lps)
                  for a, b in zip(la, lb))
    # a greedy token the bf16 engine picked must be (near) the float32 top
    top_gap = max(float(ref_rows[i, j].max() - ref_rows[i, j, t])
                  for i, g in enumerate(k_tokens) for j, t in enumerate(g))
    log(f"serve: kernel engine vs float32 forward: max logprob gap "
        f"{worst32:.4f} (tolerance {F32_LOGPROB_TOL}); chosen tokens at "
        f"most {top_gap:.4f} nats under the float32 argmax (allowed "
        f"{NEAR_TIE_NATS})")
    if worst32 > F32_LOGPROB_TOL or top_gap > NEAR_TIE_NATS:
        raise AssertionError("kernel engine disagrees with float32 forward")

    # one completion through the HTTP front door of `accelerate-tpu serve`
    server_cfg = ServerConfig(port=0, model_id="qwen2-1.5b-smoke",
                              tokenizer="numeric")
    service = InferenceService(
        kern, get_tokenizer("numeric", cfg.vocab_size), server_cfg)
    door = HttpFrontDoor(service, server_cfg)
    body = {"prompt": [int(t) for t in prompts[1]],
            "max_tokens": s.serve_new_tokens, "temperature": 0}

    async def scenario():
        await door.start()
        try:
            answer = await _http_completion(door.port, body)
            return answer, kern.compile_stats(), kern.metrics.registry.counter(
                "serving_decode_path_total", path="kernel").value
        finally:
            await door.stop()  # drains, then closes the engine

    (status, raw), stats, kernel_steps = asyncio.run(scenario())
    if status != 200:
        raise AssertionError(f"POST /v1/completions -> {status}: {raw[:300]}")
    answer = json.loads(raw)
    http_tokens = answer["choices"][0]["token_ids"]
    log(f"serve: POST /v1/completions -> 200, {len(http_tokens)} tokens, "
        f"usage {answer['usage']}")
    if http_tokens != k_tokens[1]:
        # same prompt, same greedy engine: the prefix cache now serves the
        # prompt's pages, so only a near tie may move a token
        log(f"serve: HTTP tokens {http_tokens} vs submit tokens "
            f"{k_tokens[1]}")
        j = next(i for i, (a, b) in enumerate(zip(http_tokens, k_tokens[1]))
                 if a != b)
        gap = abs(float(ref_rows[1, j, http_tokens[j]])
                  - float(ref_rows[1, j, k_tokens[1][j]]))
        if gap > NEAR_TIE_NATS:
            raise AssertionError(
                f"HTTP completion differs away from a tie ({gap:.4f})")
    log(f"serve: compile_stats after the mix = {stats}; "
        f"serving_decode_path_total{{path=\"kernel\"}} = {kernel_steps:.0f}")
    if stats != {"admit": 1, "prefill": 1, "decode": 1}:
        raise AssertionError(f"compile counts moved: {stats}")
    if not kernel_steps > 0:
        raise AssertionError("the kernel decode path counter did not move")
    return {"kernel_steps": kernel_steps, "logprob_gap_dense": worst,
            "logprob_gap_f32": worst32}


# ---------------------------------------------------------------------------
# --multichip: the sharded train step against the same steps on one device
# ---------------------------------------------------------------------------


def _sharded_losses(s: Sizes, axes: dict, devices, seed: int,
                    expect_chip: bool):
    import jax
    import numpy as np
    import optax

    from accelerate_tpu import TrainState
    from accelerate_tpu.accelerator import Accelerator
    from accelerate_tpu.models import llama
    from accelerate_tpu.ops.kernel_mode import kernel_report
    from accelerate_tpu.state import PartialState
    from accelerate_tpu.utils.dataclasses import MeshConfig

    PartialState._reset_state()
    cfg = model_config(s, s.multi_layers, remat=True, remat_policy="dots")
    acc = Accelerator(mixed_precision="bf16", gradient_clipping=1.0,
                      mesh_config=MeshConfig(axes=dict(axes),
                                             devices=list(devices)))
    mesh = acc.state.mesh
    params = llama.init_params(cfg, jax.random.key(seed))
    ts = acc.prepare(TrainState.create(apply_fn=None, params=params,
                                       tx=optax.adamw(3e-4)))
    del params
    rng = np.random.default_rng(seed)
    ids = rng.integers(0, s.vocab_size,
                       (s.multi_batch, s.train_seq + 1)).astype(np.int32)
    (batch,) = list(acc.prepare([{"input_ids": ids}]))
    step = acc.train_step(lambda p, b: llama.causal_lm_loss(cfg, p, b))

    # where do the parameters live? the biggest leaf, shard by shard
    big = max(jax.tree_util.tree_leaves(ts.params), key=lambda x: x.size)
    shard_devs = sorted({sh.device.id for sh in big.addressable_shards})
    shard_shapes = {tuple(sh.data.shape) for sh in big.addressable_shards}
    log(f"multichip: mesh {dict(mesh.shape)} over {mesh.size} devices; "
        f"largest parameter {tuple(big.shape)} is held as shards "
        f"{sorted(shard_shapes)} on devices {shard_devs}")

    losses = []
    t0 = time.perf_counter()
    for i in range(s.multi_steps):
        ts, m = step(ts, batch)
        losses.append(float(m["loss"]))
        if i == 0:
            log(f"multichip: mesh {dict(mesh.shape)}: first step "
                f"{time.perf_counter() - t0:.1f}s including compile")
    path = _attention_path(cfg, s.train_seq)
    log(f"multichip: mesh {dict(mesh.shape)}: attention '{path}'"
        + (" under shard_map over the batch/head axes" if mesh.size > 1
           and path == "flash" else "")
        + f", kernels {kernel_report()}, losses "
        f"{[round(l, 4) for l in losses]}")
    if expect_chip and (path != "flash" or kernel_report().get(
            "flash_attention") != "compiled"):
        raise AssertionError(f"flash kernel not in use: {path}, "
                             f"{kernel_report()}")
    del ts, step, batch, m
    acc.free_memory()
    PartialState._reset_state()
    if expect_chip:
        jax.clear_caches()  # free the executables' device memory
    return losses, shard_devs, big.shape, shard_shapes


def multichip_phase(s: Sizes, expect_chip: bool, seed: int = 0,
                    meshes=None):
    """Same model, batch and steps on a 4-device mesh and on a 1-device
    mesh, through `Accelerator(mesh_config=...)`; nothing else runs."""
    import jax
    import numpy as np

    devices = jax.devices()
    if len(devices) < 4:
        raise AssertionError(f"--multichip needs 4 devices, found "
                             f"{len(devices)}")
    four = devices[:4]
    log(f"multichip: Qwen2-1.5B widths, depth {s.multi_layers} of "
        f"{s.full_layers} (a sharding check, not a memory fill), batch "
        f"{s.multi_batch} x seq {s.train_seq}, {s.multi_steps} steps")
    base, _, _, _ = _sharded_losses(s, {"data": 1}, four[:1], seed,
                                    expect_chip)
    for axes in (meshes or ({"fsdp": 4}, {"data": 2, "model": 2})):
        losses, shard_devs, shape, shard_shapes = _sharded_losses(
            s, axes, four, seed, expect_chip)
        if len(shard_devs) != 4:
            raise AssertionError(
                f"mesh {axes}: parameter shards live on {shard_devs}")
        if shard_shapes == {tuple(shape)}:
            raise AssertionError(
                f"mesh {axes}: the largest parameter is replicated whole")
        rel = [abs(a - b) / abs(b) for a, b in zip(losses, base)]
        log(f"multichip: mesh {axes} vs one device: relative loss gaps "
            f"{[round(r, 5) for r in rel]} (tolerance {MULTI_LOSS_RTOL})")
        if not np.all(np.isfinite(losses)) or max(rel) > MULTI_LOSS_RTOL:
            raise AssertionError(f"mesh {axes}: losses {losses} vs {base}")
        if not losses[-1] < losses[0]:
            raise AssertionError(f"mesh {axes}: loss did not fall: {losses}")
    return {"base": base}


# ---------------------------------------------------------------------------


def main(argv=None) -> int:
    parser = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    parser.add_argument("--multichip", action="store_true",
                        help="four chips: the sharded train step and its "
                             "one-device comparison, no other phase")
    parser.add_argument("--seed", type=int, default=0)
    args = parser.parse_args(argv)

    device = None
    ok = False
    work_dir = os.path.join(ROOT, ".chip_smoke_work")
    try:
        # children (none today) and tools find the package without install
        os.environ["PYTHONPATH"] = ROOT + os.pathsep + os.environ.get(
            "PYTHONPATH", "")
        import jax

        import accelerate_tpu  # noqa: F401  (fails in a bare directory)
        from accelerate_tpu.ops.kernel_mode import require_compiled

        d0 = jax.devices()[0]
        device = {"platform": d0.platform, "kind": d0.device_kind,
                  "count": len(jax.devices())}
        log(f"jax {jax.__version__}, devices: {device}")
        if d0.platform != "tpu":
            raise RuntimeError(
                f"no TPU: jax.devices()[0].platform is {d0.platform!r}; "
                "chip_smoke never carries on on another backend")
        require_compiled()  # an interpreted kernel is an error from here
        _count_cache_events()
        # the two private jax entry points notebook_launcher leans on
        from jax._src import hardware_utils, xla_bridge

        log(f"launcher probes: backends_are_initialized() = "
            f"{xla_bridge.backends_are_initialized()}, "
            f"num_available_tpu_chips_and_device_id() = "
            f"{hardware_utils.num_available_tpu_chips_and_device_id()}")
        shutil.rmtree(work_dir, ignore_errors=True)
        os.makedirs(work_dir)
        t0 = time.perf_counter()
        if args.multichip:
            multichip_phase(REAL, expect_chip=True, seed=args.seed)
            log_cache_events("multichip")
        else:
            train_phase(REAL, work_dir, expect_chip=True, seed=args.seed)
            log(f"train phase done at {time.perf_counter() - t0:.0f}s")
            log_cache_events("train")
            serve_phase(REAL, expect_chip=True, seed=args.seed)
            log_cache_events("serve")
        log(f"all phases passed in {time.perf_counter() - t0:.0f}s")
        ok = True
    except Exception:  # noqa: BLE001 - the boundary: reported, run fails
        traceback.print_exc(file=sys.stdout)
        sys.stdout.flush()
    finally:
        shutil.rmtree(work_dir, ignore_errors=True)
    print(json.dumps({"ok": ok, "device": device}), flush=True)
    return 0 if ok else 1


if __name__ == "__main__":
    sys.exit(main())
