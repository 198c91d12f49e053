"""Llama-family causal LM, TPU-first.

Flagship model for the framework's benchmarks (BASELINE.md targets Llama-3-8B
tokens/sec/chip). Design:

- params stack the L transformer layers on a leading dim; the forward runs
  `lax.scan` over them, so XLA compiles ONE layer body (fast compiles at any
  depth) — the idiomatic TPU replacement for Python-level layer loops.
- `remat` option wraps the scanned body in `jax.checkpoint` (activation
  checkpointing — replaces FSDP plugin activation_checkpointing,
  ref utils/dataclasses.py:1105-1112).
- attention backends: 'auto' (default — einsum up to 4k, pallas flash
  beyond, on TPU), 'einsum' (XLA), 'flash' (ops/flash_attention.py), 'ring'
  (sequence-parallel over the mesh `seq` axis, parallel/ring_attention.py),
  'ulysses' (head-scatter all-to-all, parallel/ulysses.py).
- naming matches sharding/rules.py so the planner yields Megatron-style
  TP + ZeRO layouts with no per-model code.
"""

from __future__ import annotations

import dataclasses
import functools
from functools import partial
from typing import Any

import jax
import jax.numpy as jnp

from .decode import (
    build_generate,
    build_streamed_generate,
    decode_attention,
    make_kv_caches,
    rope_table_len,
    scan_decode_layers,
)
from .common import (
    apply_rope,
    shifted_padding_masks,
    cross_entropy_loss,
    fused_head_loss,
    dense,
    dot_product_attention,
    init_dense,
    normal_init,
    part,
    repeat_kv,
    rms_norm,
    rope_frequencies,
    sp_constrain,
)


@dataclasses.dataclass(frozen=True)
class LlamaConfig:
    vocab_size: int = 32000
    hidden_size: int = 4096
    intermediate_size: int = 11008
    num_hidden_layers: int = 32
    num_attention_heads: int = 32
    num_key_value_heads: int = 32
    max_position_embeddings: int = 2048
    rope_theta: float = 10000.0
    # HF-style dict, e.g. {"rope_type": "llama3", ...}; normalized to a
    # sorted item tuple so the config stays hashable (jit/lru_cache keys)
    rope_scaling: Any = None
    rms_norm_eps: float = 1e-6
    # q/k/v projection biases, the Qwen2 layout (init_params mirrors it so
    # init and HF-import trees match structurally); the forward applies
    # whichever biases the param tree holds, so an HF-llama checkpoint with
    # an o_proj bias still imports and runs exactly
    attention_bias: bool = False
    # Mistral/Qwen2-style sliding-window attention: keys visible iff
    # q - key < window (applied as a band mask in the flash kernel with
    # out-of-band block skip, in the einsum path, and in the decode mask)
    sliding_window: int | None = None
    tie_word_embeddings: bool = False
    attention_backend: str = "auto"  # auto | einsum | flash | ring | ulysses
    # Megatron-style sequence parallelism (ref dataclasses.py:1249-1251):
    # hidden states constrain to a seq-dim sharding in the norm/residual
    # regions (common.sp_constrain) — 'seq' mesh axis if present, else the
    # TP 'model' axis, the Megatron SP group
    sequence_parallel: bool = False
    remat: bool = False
    remat_policy: str = "full"  # full | dots (save MXU outputs, recompute rest)

    def __post_init__(self):
        if isinstance(self.rope_scaling, dict):
            object.__setattr__(
                self, "rope_scaling", tuple(sorted(self.rope_scaling.items()))
            )

    @property
    def rope_scaling_dict(self) -> dict | None:
        return dict(self.rope_scaling) if self.rope_scaling else None

    @property
    def head_dim(self) -> int:
        return self.hidden_size // self.num_attention_heads

    @classmethod
    def llama3_8b(cls, **overrides) -> "LlamaConfig":
        return cls(
            vocab_size=128256, hidden_size=4096, intermediate_size=14336,
            num_hidden_layers=32, num_attention_heads=32, num_key_value_heads=8,
            max_position_embeddings=8192, rope_theta=500000.0, **overrides,
        )

    @classmethod
    def tiny(cls, **overrides) -> "LlamaConfig":
        """Test/debug size."""
        defaults = dict(
            vocab_size=256, hidden_size=64, intermediate_size=128,
            num_hidden_layers=2, num_attention_heads=4, num_key_value_heads=2,
            max_position_embeddings=128,
        )
        defaults.update(overrides)
        return cls(**defaults)


def select_attention_backend(
    backend: str, *, on_tpu: bool, decoding: bool, seq_len: int
) -> str:
    """Resolve 'auto' to a concrete attention backend.

    The einsum path materializes [B,H,S,S] f32 scores in HBM and is
    bandwidth-bound from ~1k context, so from s=1024 the pallas flash
    kernel is chosen (the crossover is not measured on the current
    code; benchmarks/sweep_attn.py is the sweep to rerun). Decode
    (kv_cache) keeps the mask-capable einsum path. Pure so the selection
    is contract-testable without TPU hardware
    (tests/test_compiled_contracts.py)."""
    if backend != "auto":
        return backend
    return (
        "flash" if on_tpu and not decoding and seq_len >= 1024 else "einsum"
    )


def init_params(config: LlamaConfig, key: jax.Array, dtype=jnp.float32) -> dict:
    """Stacked-layer param pytree."""
    keys = jax.random.split(key, 8)
    h, kv = config.hidden_size, config.num_key_value_heads * config.head_dim
    L = config.num_hidden_layers

    def stack(k, d_in, d_out, bias=False):
        out = {"kernel": normal_init(k, (L, d_in, d_out), 0.02, dtype)}
        if bias:
            out["bias"] = jnp.zeros((L, d_out), dtype)
        return out

    ab = config.attention_bias
    params = {
        "embed_tokens": {"embedding": normal_init(keys[0], (config.vocab_size, h), 0.02, dtype)},
        "layers": {
            "input_layernorm": {"scale": jnp.ones((L, h), dtype)},
            "attn": {
                "q_proj": stack(keys[1], h, h, bias=ab),
                "k_proj": stack(keys[2], h, kv, bias=ab),
                "v_proj": stack(keys[3], h, kv, bias=ab),
                "o_proj": stack(keys[4], h, h),
            },
            "post_attention_layernorm": {"scale": jnp.ones((L, h), dtype)},
            "mlp": {
                "gate_proj": stack(keys[5], h, config.intermediate_size),
                "up_proj": stack(keys[6], h, config.intermediate_size),
                "down_proj": stack(keys[7], config.intermediate_size, h),
            },
        },
        "norm": {"scale": jnp.ones((h,), dtype)},
    }
    if not config.tie_word_embeddings:
        params["lm_head"] = init_dense(
            jax.random.fold_in(key, 99), h, config.vocab_size, 0.02, dtype=dtype
        )
    return params


from .common import dense_maybe_fp8 as _dense_maybe_fp8  # shared swap point


def _attention(config: LlamaConfig, layer: dict, x, cos, sin, positions, mask,
               kv_cache=None, fp8=None):
    b, s, h = x.shape
    nh, nkv, hd = config.num_attention_heads, config.num_key_value_heads, config.head_dim
    fa = fp8["attn"] if fp8 is not None else {}
    with part("attn.project"):
        q, mq = _dense_maybe_fp8(x, layer["attn"]["q_proj"]["kernel"], fa.get("q_proj"))
        k, mk = _dense_maybe_fp8(x, layer["attn"]["k_proj"]["kernel"], fa.get("k_proj"))
        v, mv = _dense_maybe_fp8(x, layer["attn"]["v_proj"]["kernel"], fa.get("v_proj"))
        if "bias" in layer["attn"]["q_proj"]:
            q = q + layer["attn"]["q_proj"]["bias"].astype(q.dtype)
        if "bias" in layer["attn"]["k_proj"]:
            k = k + layer["attn"]["k_proj"]["bias"].astype(k.dtype)
        if "bias" in layer["attn"]["v_proj"]:
            v = v + layer["attn"]["v_proj"]["bias"].astype(v.dtype)
        q = q.reshape(b, s, nh, hd)
        k = k.reshape(b, s, nkv, hd)
        v = v.reshape(b, s, nkv, hd)
        q = apply_rope(q, cos, sin, positions)
        k = apply_rope(k, cos, sin, positions)
    new_cache = None
    if kv_cache is not None:
        # the shared cache-attend step (models/decode.py): dense stacked
        # caches keep the classic extend/mask/einsum path; the serving
        # engine's paged pool streams live pages through the Pallas
        # paged-attention kernel (GQA broadcast in-kernel, no repeat_kv)
        out, new_cache = decode_attention(
            q, k, v, kv_cache, positions, mask=mask,
            window=config.sliding_window, n_rep=nh // nkv)
    else:
        with part("attn.attend"):
            backend = select_attention_backend(
                config.attention_backend,
                on_tpu=jax.devices()[0].platform == "tpu",
                decoding=False,
                seq_len=s,
            )
            window = config.sliding_window
            # flash, ring, and ulysses all take [B, S] key-padding masks
            # natively (ring rotates mask chunks with K/V; ulysses all-gathers
            # the mask), so padded batches keep every fast path; all three take
            # `window` too (ring: exact global-position banding in the einsum
            # fold; ulysses: the band rides the flash kernel after the head
            # scatter)
            key_mask = (mask if mask is None or getattr(mask, "ndim", 0) == 2
                        else None)
            if backend == "ring" and (mask is None or key_mask is not None):
                # ring handles GQA itself: un-repeated K/V chunks ride the ring
                # (the repeat factor never touches ICI)
                from ..parallel.ring_attention import ring_attention

                out = ring_attention(q, k, v, causal=True, mask=key_mask,
                                     window=window)
            elif backend == "ulysses" and (mask is None or key_mask is not None):
                # ulysses also keeps GQA K/V un-repeated on the wire (repeat
                # happens after its all-to-all)
                from ..parallel.ulysses import ulysses_attention

                out = ulysses_attention(q, k, v, causal=True, mask=key_mask,
                                        window=window)
            else:
                k = repeat_kv(k, nh // nkv)
                v = repeat_kv(v, nh // nkv)
                if backend == "flash" and (
                    mask is None or getattr(mask, "ndim", 0) == 2
                ):
                    # a Mosaic kernel cannot be partitioned by GSPMD: under
                    # a mesh it runs per shard (batch x heads) in shard_map
                    from ..ops.flash_attention import flash_attention_on_mesh
                    from ..state import PartialState

                    mesh = (PartialState().mesh if PartialState._shared_state
                            else None)
                    out = flash_attention_on_mesh(q, k, v, mesh, causal=True,
                                                  mask=mask, window=window)
                else:
                    out = dot_product_attention(q, k, v, mask=mask, causal=True,
                                                window=window)
    with part("attn.output"):
        out = out.reshape(b, s, nh * hd)
        o, mo = _dense_maybe_fp8(out, layer["attn"]["o_proj"]["kernel"],
                                 fa.get("o_proj"))
        if "bias" in layer["attn"]["o_proj"]:
            o = o + layer["attn"]["o_proj"]["bias"].astype(o.dtype)
    new_fp8 = (
        {"q_proj": mq, "k_proj": mk, "v_proj": mv, "o_proj": mo}
        if fp8 is not None else None
    )
    return o, new_cache, new_fp8


def _mlp(layer: dict, x, fp8=None):
    fm = fp8["mlp"] if fp8 is not None else {}
    gate, mg = _dense_maybe_fp8(x, layer["mlp"]["gate_proj"]["kernel"],
                                fm.get("gate_proj"))
    up, mu = _dense_maybe_fp8(x, layer["mlp"]["up_proj"]["kernel"],
                              fm.get("up_proj"))
    down, md = _dense_maybe_fp8(jax.nn.silu(gate) * up,
                                layer["mlp"]["down_proj"]["kernel"],
                                fm.get("down_proj"))
    new_fp8 = (
        {"gate_proj": mg, "up_proj": mu, "down_proj": md}
        if fp8 is not None else None
    )
    return down, new_fp8


def _layer_body(config: LlamaConfig, x, layer, cos, sin, positions, mask,
                kv_cache=None, fp8=None):
    # `part` opens a `jax.named_scope` of `common.PARTS`; a norm is billed
    # with the part it feeds, a residual add with the part it closes
    with part("attn.project"):
        y = rms_norm(x, layer["input_layernorm"]["scale"],
                     config.rms_norm_eps)
    attn_out, new_cache, fp8_attn = _attention(
        config, layer, y, cos, sin, positions, mask, kv_cache, fp8)
    with part("attn.output"):
        x = x + attn_out
    with part("mlp"):
        mlp_out, fp8_mlp = _mlp(
            layer,
            rms_norm(x, layer["post_attention_layernorm"]["scale"],
                     config.rms_norm_eps),
            fp8,
        )
        x = x + mlp_out
    new_fp8 = (
        {"attn": fp8_attn, "mlp": fp8_mlp} if fp8 is not None else None
    )
    return x, new_cache, new_fp8


def forward(
    config: LlamaConfig,
    params: dict,
    input_ids: jax.Array,
    attention_mask: jax.Array | None = None,
    positions: jax.Array | None = None,
    kv_caches: Any = None,
    return_hidden: bool = False,
    fp8_state: Any = None,
) -> jax.Array | tuple:
    """Logits [B, S, V]; with kv_caches, returns (logits, new_caches);
    with `return_hidden`, the final normed hidden states [B, S, H] instead
    of logits (the chunked-loss path projects them itself). With
    `fp8_state` (see `init_fp8_state`), layer projections run in fp8 and the
    result is (out, new_fp8_state)."""
    if return_hidden and kv_caches is not None:
        raise ValueError("return_hidden is not supported on the decode "
                         "(kv_caches) path")
    if fp8_state is not None and kv_caches is not None:
        raise ValueError("fp8 is a training-path feature; decode "
                         "(kv_caches) runs bf16")
    with part("embed"):
        x = params["embed_tokens"]["embedding"][input_ids]
    if positions is None:
        positions = jnp.broadcast_to(
            jnp.arange(input_ids.shape[1]), input_ids.shape
        )
    cos, sin = rope_frequencies(
        config.head_dim,
        rope_table_len(config.max_position_embeddings, kv_caches),
        config.rope_theta, scaling=config.rope_scaling_dict)

    if kv_caches is not None:
        # decode path: caches stack on a leading layer dim and ride the same
        # lax.scan as training — ONE compiled layer body at any depth (the
        # old per-layer python loop compiled L bodies per decode program)
        def layer_step(y, layer, cache):
            return _layer_body(config, y, layer, cos, sin, positions,
                               attention_mask, cache)[:2]

        x, (nk, nv) = scan_decode_layers(layer_step, x, params["layers"],
                                         kv_caches)
        with part("head"):
            x = rms_norm(x, params["norm"]["scale"], config.rms_norm_eps)
            logits = _project_out(config, params, x)
        return logits, (nk, nv, kv_caches[2] + input_ids.shape[1])

    body = partial(_layer_body, config)
    sp = sp_constrain if config.sequence_parallel else (lambda y: y)
    x = sp(x)

    if fp8_state is not None:
        # per-layer metas ride the scan as xs; updated metas stack back on
        # the layer dim as ys — fp8 state threads like optimizer state
        def scan_body(carry, xs):
            layer, fp8_layer = xs
            y, _, new_fp8 = body(carry, layer, cos, sin, positions,
                                 attention_mask, fp8=fp8_layer)
            return sp(y), new_fp8

        scan_xs = (params["layers"], fp8_state["layers"])
    else:
        def scan_body(carry, layer):
            y, _, _ = body(carry, layer, cos, sin, positions, attention_mask)
            return sp(y), None

        scan_xs = params["layers"]

    if config.remat:
        # "dots" keeps MXU outputs resident and recomputes only cheap
        # elementwise ops — much less recompute than full remat for a modest
        # memory bump (the scaling-book selective-checkpoint recipe)
        if config.remat_policy not in ("full", "dots"):
            raise ValueError(
                f"unknown remat_policy {config.remat_policy!r}; use 'full' or 'dots'"
            )
        policy = (
            jax.checkpoint_policies.dots_with_no_batch_dims_saveable
            if config.remat_policy == "dots" else None
        )
        scan_body = jax.checkpoint(scan_body, prevent_cse=False, policy=policy)
    x, scan_ys = jax.lax.scan(scan_body, x, scan_xs)
    new_fp8_state = {"layers": scan_ys} if fp8_state is not None else None
    with part("head"):
        x = sp(rms_norm(x, params["norm"]["scale"], config.rms_norm_eps))
        if return_hidden:
            return (x, new_fp8_state) if fp8_state is not None else x
        out = _project_out(config, params, x)
    return (out, new_fp8_state) if fp8_state is not None else out


def forward_offloaded(
    config: LlamaConfig,
    dispatched_params: dict,
    input_ids: jax.Array,
    attention_mask: jax.Array | None = None,
    dtype=jnp.bfloat16,
) -> jax.Array:
    """Forward for params laid out by `big_modeling.dispatch_model` with a
    cpu/disk device map (ref big-model-inference path, SURVEY.md §2.4):
    layer slices stream host→device double-buffered around a jit'd layer
    body. Matches `forward` output on the same weights."""
    from ..big_modeling import streamed_forward

    positions = jnp.broadcast_to(jnp.arange(input_ids.shape[1]), input_ids.shape)
    cos, sin = rope_frequencies(
        config.head_dim, config.max_position_embeddings, config.rope_theta,
        scaling=config.rope_scaling_dict,
    )
    layer_step = jax.jit(
        lambda layer, x: _layer_body(
            config, x, layer, cos, sin, positions, attention_mask
        )[0]
    )

    def final(resident, x):
        return _project_decode(config, resident, x)

    return streamed_forward(
        dispatched_params,
        input_ids,
        embed_fn=lambda res, ids: res["embed_tokens"]["embedding"][ids],
        layer_fn=lambda layer, x, i: layer_step(layer, x),
        final_fn=final,
        dtype=dtype,
    )


def _project_out(config: LlamaConfig, params: dict, x):
    if config.tie_word_embeddings:
        return jnp.einsum(
            "bsh,vh->bsv", x, params["embed_tokens"]["embedding"].astype(x.dtype),
            preferred_element_type=jnp.float32,
        )
    return jnp.einsum(
        "bsh,hv->bsv", x, params["lm_head"]["kernel"].astype(x.dtype),
        preferred_element_type=jnp.float32,
    )


def causal_lm_loss(config: LlamaConfig, params: dict, batch: dict,
                   loss_chunk_size: int | None = None,
                   fp8_state: Any = None) -> jax.Array | tuple:
    """Next-token loss over a batch {input_ids, attention_mask?}.

    Large vocab x long sequence makes the [B, S, V] f32 logits the single
    biggest buffer of the step (e.g. 16 x 2048 x 32000 f32 = 4.2 GB). When
    S divides into blocks of `loss_chunk_size` positions (`loss_plan`:
    picked from B and V so that one block's f32 logits stay under
    `_LOGITS_BLOCK_BYTES`; the caller's explicit word wins, e.g. to fit a
    small chip), the head and its cross-entropy run as ONE op block by
    block (`common.fused_head_loss`) and the full logits never exist.
    What it holds: one block of logits and their gradient
    (rows x V x 6 bytes), the head's gradient in float32 (head-shaped) and
    the hidden rows' gradient [B, S, H]. Under `jax.grad` /
    `value_and_grad` (the intended use: a train step) the gradients of the
    hidden rows and of the head are made in the op's FORWARD, while a
    block's logits exist, so the logits are projected once and the backward
    has no vocabulary-wide product; not differentiated (an evaluation loop)
    the op computes the loss alone, block by block. Where S does not divide,
    the whole logits already fit one block, or `loss_chunk_size >= S`, the
    whole-logits path runs (`forward` + `cross_entropy_loss`).

    With `fp8_state` (mixed_precision="fp8"), layer projections run fp8 and
    the return is (loss, new_fp8_state) — the fused train step threads it
    through TrainState.fp8_state. The head is never fp8.

    The attention_mask threads into the forward as a key-padding mask
    (flash/ring/ulysses all take it natively) so padded tokens cannot leak
    into real tokens' attention, AND weights the loss. Positions stay
    sequential (0..S-1): batches should be RIGHT-padded — left-padded rows
    get correctly-masked attention but their real tokens sit at shifted
    rope positions vs a pretrained checkpoint's convention."""
    input_ids = batch["input_ids"]
    labels = input_ids[:, 1:]
    attn_mask, mask = shifted_padding_masks(batch.get("attention_mask"))
    B, S = labels.shape

    plan = loss_plan(B, S, config.vocab_size, loss_chunk_size)
    if plan["path"] == "full":
        out = forward(config, params, input_ids[:, :-1],
                      attention_mask=attn_mask, fp8_state=fp8_state)
        logits, new_fp8 = out if fp8_state is not None else (out, None)
        with part("loss"):
            loss = cross_entropy_loss(logits, labels, mask)
        return (loss, new_fp8) if fp8_state is not None else loss

    out = forward(config, params, input_ids[:, :-1],
                  attention_mask=attn_mask, return_hidden=True,
                  fp8_state=fp8_state)
    hidden, new_fp8 = out if fp8_state is not None else (out, None)
    tied = config.tie_word_embeddings
    # the head's three products are inside the op: `head` + `loss` are one
    # part here
    with part("loss"):
        head = (params["embed_tokens"]["embedding"] if tied
                else params["lm_head"]["kernel"]).astype(hidden.dtype)
        if mask is None:
            mask = jnp.ones((B, S), jnp.float32)
        loss_sum = fused_head_loss(hidden, head, labels, mask, tied,
                                   plan["rows_per_block"] // B)
        loss = loss_sum / jnp.maximum(jnp.sum(mask), 1)
    return (loss, new_fp8) if fp8_state is not None else loss


# One block of float32 logits in `fused_head_loss`. Sized on the chip (v5e,
# B 2, S 2048, V 151936, H 1536; PERF.md section 6, PR 37): blocks of 512 /
# 1,024 / 2,048 rows read 25.5k / 27.1k / 28.0k tokens/s in the train cell
# (21.3k before), so 2,048 rows of that vocabulary fit; a block's three
# products then sit far over the chip's ridge of ~240 FLOP a weight byte and
# the head's float32 gradient is read and written twice a step.
_LOGITS_BLOCK_BYTES = 1280 * 2**20


def loss_plan(B: int, S: int, V: int,
              loss_chunk_size: int | None = None) -> dict:
    """How `causal_lm_loss` computes the loss of a [B, S] batch over a
    V-wide head, from shapes alone: {"path": "fused" | "full",
    "rows_per_block", "blocks"}. `loss_chunk_size` is positions a block
    (each block takes them from all B rows); None picks as many as keep a
    block's float32 logits under `_LOGITS_BLOCK_BYTES`."""
    if loss_chunk_size is None:
        loss_chunk_size = max(1, _LOGITS_BLOCK_BYTES // 4 // max(1, B * V))
    chunk = _pick_chunk(S, loss_chunk_size)
    if chunk is None:
        return {"path": "full", "rows_per_block": B * S, "blocks": 1}
    return {"path": "fused", "rows_per_block": B * chunk, "blocks": S // chunk}


def _pick_chunk(S: int, target: int) -> int | None:
    """Largest divisor of S that is <= target; None when blocks are not
    worthwhile (S already fits one, or — e.g. prime S — the best divisor is
    so small the blocks would degenerate into per-token matmuls)."""
    if S <= target:
        return None
    best = None
    for c in range(min(target, S - 1), 0, -1):
        if S % c == 0:
            best = c
            break
    # a divisor far below the target (prime-ish S) degenerates the blocks
    # into per-token matmuls — prefer the full path then. When the memory
    # budget itself demands tiny blocks, honor them: slow beats OOM.
    if best is None or best < max(1, target // 8):
        return None
    return best


def init_fp8_state(config: LlamaConfig, history_len: int | None = None) -> dict:
    """Per-layer delayed-scaling metas for every layer projection (shared
    builder: ops/fp8.py stacked_fp8_metas; honors the Accelerator's
    FP8RecipeKwargs). Pass to `TrainState.create(fp8_state=...)` and train
    with `Accelerator(mixed_precision="fp8")`."""
    from ..ops.fp8 import stacked_fp8_metas

    return stacked_fp8_metas(config.num_hidden_layers, {
        "attn": ("q_proj", "k_proj", "v_proj", "o_proj"),
        "mlp": ("gate_proj", "up_proj", "down_proj"),
    }, history_len)


def init_kv_caches(config: LlamaConfig, batch: int, max_len: int, dtype=jnp.bfloat16):
    """Stacked decode caches: (k [L, B, M, KV, D], v [L, B, M, KV, D],
    cache_len scalar). The leading layer dim lets decode scan the layer body
    (program size independent of depth); cache_len is a traced scalar so
    decode steps never retrigger tracing."""
    return make_kv_caches(config.num_hidden_layers, batch, max_len,
                          config.num_key_value_heads, config.head_dim, dtype)


# Greedy/temperature decode with a KV cache (big-model-inference path;
# benchmark analogue of ref benchmarks/big_model_inference.py). Shared
# driver: one compiled prefill + one fused decode scan per (config, temp).
generate = build_generate(forward, init_kv_caches)


@functools.lru_cache(maxsize=8)
def make_decode_layer_step(config: LlamaConfig):
    """jit'd single-layer decode body for `streamed_generate` (offloaded
    weights). Cached per config so warm benchmark runs reuse the program."""

    @jax.jit
    def step(layer, x, positions, kv_cache):
        cos, sin = rope_frequencies(
            config.head_dim, kv_cache[0].shape[1], config.rope_theta,
            scaling=config.rope_scaling_dict,
        )
        y, cache, _ = _layer_body(config, x, layer, cos, sin, positions,
                                  None, kv_cache)
        return y, cache

    return step


def _project_decode(config: LlamaConfig, resident: dict, x):
    # the full forward norms before projecting (forward():377); the streamed
    # path must too or real checkpoints (norm.scale != 1) decode wrong
    with part("head"):
        x = rms_norm(x, resident["norm"]["scale"], config.rms_norm_eps)
        return _project_out(config, resident, x)


streamed_generate = build_streamed_generate(
    make_decode_layer_step,
    embed_fn=lambda config, res, ids, pos: res["embed_tokens"]["embedding"][ids],
    project_fn=_project_decode,
    cache_dims=lambda c: (c.num_key_value_heads, c.head_dim),
)
