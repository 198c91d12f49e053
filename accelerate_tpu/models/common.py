"""Shared pure-function model components.

No reference equivalent (Accelerate wraps user torch models); these exist so
the framework ships runnable model families for its examples/benchmarks, the
way the reference leans on HF Transformers. Everything is a pure function over
a params pytree whose naming matches sharding/rules.py, so the planner shards
any of these models with zero per-model annotation.

TPU notes: matmuls accumulate in fp32 (`preferred_element_type`), attention
uses einsum forms XLA maps onto the MXU, layers stack on a leading dim for
`lax.scan` (one compiled layer body regardless of depth).
"""

from __future__ import annotations

import contextlib
import dataclasses
import functools
import math
from typing import Any

import jax
import jax.numpy as jnp
import numpy as np

NEG_INF = -1e30

# The parts of a model a device operation is billed to, the same in every
# family, the train step and the engine's own programs. Flat and few; a
# layer is told by its order in the call, not by a name. A scope is HLO
# metadata (an operation's `op_name`): it changes no program and costs
# nothing on the device. docs/observability.md, "Device time by part".
PARTS = (
    "embed",
    "attn.project", "attn.indexer", "attn.select", "attn.attend",
    "attn.output",
    "cache.view", "cache.write",
    "mlp",
    "moe.route", "moe.sort", "moe.experts", "moe.combine", "moe.shared",
    "head", "sample", "loss", "optimizer",
)


class _Part(contextlib.ContextDecorator):
    """`jax.named_scope(name)`, opened anew at every entry: a decorated
    function may call itself (a grouped cache maps its groups through the
    function that was called), which one shared scope object would not
    survive."""

    def __init__(self, name: str):
        self.name = name
        self._open: list = []

    def __enter__(self):
        self._open.append(jax.named_scope(self.name))
        return self._open[-1].__enter__()

    def __exit__(self, *exc):
        return self._open.pop().__exit__(*exc)


def part(name: str):
    """The scope of one of `PARTS`, as a context or as a function's
    decorator; any other name raises."""
    if name not in PARTS:
        raise ValueError(f"{name!r} is not a part of the model; parts: "
                         f"{', '.join(PARTS)}")
    return _Part(name)


def hashable(value):
    """Nested dicts and lists as sorted item tuples (a config is a jit and
    lru_cache key)."""
    if isinstance(value, dict):
        return tuple(sorted((k, hashable(v)) for k, v in value.items()))
    if isinstance(value, (list, tuple)):
        return tuple(hashable(v) for v in value)
    return value


def sp_constrain(x: jax.Array, axis: str | None = None) -> jax.Array:
    """Sequence-parallel activation constraint (Megatron SP, ref
    dataclasses.py:1249-1251 `sequence_parallelism`): hint GSPMD to shard
    hidden states [B, S, H] along the sequence dim in the norm/residual
    regions, so those elementwise ops compute 1/n of the tokens per device
    instead of replicating. The TP matmuls stay sharded by the param specs;
    XLA inserts the Megatron allgather/reduce-scatter pair at the region
    boundaries on its own.

    Uses the live mesh from AcceleratorState; picks the `seq` axis if the
    mesh carries one (>1), else the `model` (TP) axis — Megatron SP reuses
    the TP group. A no-op outside an initialized state, under a mesh with
    neither axis, or when the sequence length does not divide the axis.
    """
    from ..sharding.planner import batch_spec, constrain
    from ..state import AcceleratorState

    if not AcceleratorState._shared_state:
        return x
    mesh = AcceleratorState().mesh
    if axis is None:
        axis = next(
            (a for a in ("seq", "model") if mesh.shape.get(a, 1) > 1), None
        )
    if axis is None or mesh.shape.get(axis, 1) <= 1:
        return x
    if x.ndim not in (2, 3) or x.shape[-2] % mesh.shape[axis]:
        return x
    from jax.sharding import PartitionSpec

    if x.ndim == 3:
        lead = batch_spec(mesh)[0]
        # the batch axes may include `axis` itself (e.g. a pure-TP mesh
        # where 'model' also absorbs batch) — never double-book an axis
        if lead == axis or (isinstance(lead, tuple) and axis in lead):
            lead = None
        spec = PartitionSpec(lead, axis, None)
    else:
        spec = PartitionSpec(axis, None)
    return constrain(x, mesh, spec)


def dense(x: jax.Array, kernel: jax.Array, bias: jax.Array | None = None) -> jax.Array:
    out = jnp.einsum("...d,df->...f", x, kernel, preferred_element_type=jnp.float32)
    out = out.astype(x.dtype)
    if bias is not None:
        out = out + bias
    return out


def dense_maybe_fp8(x, kernel, meta, bias=None):
    """te.Linear-style swap point shared by the model zoo: with an Fp8Meta
    pair the projection runs in fp8 (ops/fp8.py, replacing ref
    utils/transformer_engine.py:24-84); otherwise the ordinary bf16/f32
    dense. Returns (out, new_meta_or_None); bias (if any) adds in the
    output dtype after the (possibly fp8) matmul, matching te.Linear."""
    if meta is None:
        return dense(x, kernel, bias), None
    from ..ops.fp8 import fp8_dense

    out, new_meta = fp8_dense(x, kernel, meta)
    if bias is not None:
        out = out + bias.astype(out.dtype)
    return out, new_meta


def rms_norm(x: jax.Array, scale: jax.Array, eps: float = 1e-6) -> jax.Array:
    dtype = x.dtype
    x = x.astype(jnp.float32)
    var = jnp.mean(x * x, axis=-1, keepdims=True)
    return (x * jax.lax.rsqrt(var + eps)).astype(dtype) * scale


def layer_norm(x: jax.Array, scale: jax.Array, bias: jax.Array, eps: float = 1e-6) -> jax.Array:
    dtype = x.dtype
    x = x.astype(jnp.float32)
    mean = jnp.mean(x, axis=-1, keepdims=True)
    var = jnp.var(x, axis=-1, keepdims=True)
    out = (x - mean) * jax.lax.rsqrt(var + eps)
    return out.astype(dtype) * scale + bias


# --- rotary embeddings ------------------------------------------------------


def rope_frequencies(head_dim: int, max_len: int, theta: float = 10000.0,
                     scaling: dict | None = None) -> tuple:
    """Rotary cos/sin tables, optionally frequency-scaled.

    `scaling` follows the HF `rope_scaling` dict: `rope_type` of
    - "linear": positions stretched by `factor` (position interpolation);
    - "llama3": Llama-3.1 wavelength-banded scaling — wavelengths beyond
      `original_max_position_embeddings/low_freq_factor` divide by `factor`,
      short wavelengths stay, the band between interpolates smoothly;
    - "yarn": with `d = head_dim` and `f_i = theta^(-2i/d)`, the pair `i`
      keeps `f_i` below `low` and takes `f_i / factor` above `high`, a
      linear ramp between, where `low` / `high` are the pairs that turn
      `beta_fast` / `beta_slow` times over `original_max_position_embeddings`
      (`c(r) = d ln(original / (2 pi r)) / (2 ln theta)`, floored / ceiled);
      cos and sin are both multiplied by `attention_factor` (`0.1
      ln(factor) + 1` where the dict gives none), which scales the scores
      by its square.
    """
    inv_freq = 1.0 / (theta ** (np.arange(0, head_dim, 2) / head_dim))
    amplitude = 1.0
    if scaling:
        rope_type = scaling.get("rope_type", scaling.get("type", "default"))
        if rope_type == "llama3":
            factor = scaling["factor"]
            low = scaling["low_freq_factor"]
            high = scaling["high_freq_factor"]
            old_len = scaling["original_max_position_embeddings"]
            wavelen = 2 * np.pi / inv_freq
            scaled = np.where(wavelen > old_len / low, inv_freq / factor, inv_freq)
            smooth = (old_len / wavelen - low) / (high - low)
            smoothed = (1 - smooth) * scaled / factor + smooth * scaled
            medium = (wavelen <= old_len / low) & (wavelen >= old_len / high)
            inv_freq = np.where(medium, smoothed, scaled)
        elif rope_type == "linear":
            inv_freq = inv_freq / scaling["factor"]
        elif rope_type == "yarn":
            factor = scaling["factor"]
            old_len = scaling["original_max_position_embeddings"]

            def pair_turning(rotations):
                return (head_dim * math.log(old_len / (2 * math.pi * rotations))
                        / (2 * math.log(theta)))

            low = max(math.floor(pair_turning(scaling.get("beta_fast", 32))), 0)
            high = min(math.ceil(pair_turning(scaling.get("beta_slow", 1))),
                       head_dim - 1)
            ramp = np.clip((np.arange(head_dim // 2) - low)
                           / max(high - low, 1e-3), 0.0, 1.0)
            inv_freq = inv_freq / factor * ramp + inv_freq * (1 - ramp)
            amplitude = scaling.get("attention_factor")
            if amplitude is None:
                amplitude = 0.1 * math.log(factor) + 1.0
        elif rope_type not in ("default", None):
            raise ValueError(f"unsupported rope_scaling type {rope_type!r}")
    t = np.arange(max_len)
    freqs = np.outer(t, inv_freq)
    return (jnp.asarray(amplitude * np.cos(freqs), jnp.float32),
            jnp.asarray(amplitude * np.sin(freqs), jnp.float32))


def apply_rope(x: jax.Array, cos: jax.Array, sin: jax.Array, positions: jax.Array) -> jax.Array:
    """x: [B, S, H, D]; positions: [B, S]."""
    dtype = x.dtype
    cos = cos[positions][:, :, None, :]  # [B, S, 1, D/2]
    sin = sin[positions][:, :, None, :]
    x1, x2 = jnp.split(x.astype(jnp.float32), 2, axis=-1)
    out = jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], axis=-1)
    return out.astype(dtype)


def apply_mrope(x: jax.Array, cos: jax.Array, sin: jax.Array,
                positions: jax.Array, sections) -> jax.Array:
    """Multimodal rotary embedding (Qwen2-VL's M-RoPE), half-split pairs.

    x: [B, S, H, D]; positions: [3, B, S], the temporal, height and width
    position id of every token; `sections` (`mrope_section`): how many of
    the D/2 rotary pairs take each of the three rows, in order (their sum
    is D/2). Pair i rotates by the angle of ITS row's position, with its
    own frequency. Text gives the three rows one id, which is `apply_rope`
    at that id."""
    if sum(sections) != x.shape[-1] // 2 or positions.shape[0] != len(sections):
        raise ValueError(
            f"mrope sections {tuple(sections)} must sum to head_dim / 2 = "
            f"{x.shape[-1] // 2}, one position row each; got positions "
            f"{positions.shape}")
    row_of_pair = np.repeat(np.arange(len(sections)), sections)    # [D/2]
    pair = np.arange(x.shape[-1] // 2)
    # [B, S, D/2]: pair i at the position its section's row gives
    at = jnp.moveaxis(positions, 0, -1)[..., row_of_pair]
    dtype = x.dtype
    cos = cos[at, pair][:, :, None, :]
    sin = sin[at, pair][:, :, None, :]
    x1, x2 = jnp.split(x.astype(jnp.float32), 2, axis=-1)
    out = jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], axis=-1)
    return out.astype(dtype)


# --- attention --------------------------------------------------------------


def repeat_kv(x: jax.Array, n_rep: int) -> jax.Array:
    """GQA: repeat kv heads [B,S,Hkv,D] -> [B,S,Hkv*n_rep,D]."""
    if n_rep == 1:
        return x
    b, s, h, d = x.shape
    return jnp.broadcast_to(x[:, :, :, None, :], (b, s, h, n_rep, d)).reshape(b, s, h * n_rep, d)


def dot_product_attention(
    q: jax.Array,
    k: jax.Array,
    v: jax.Array,
    mask: jax.Array | None = None,
    causal: bool = False,
    window: int | None = None,
) -> jax.Array:
    """[B, S, H, D] attention with fp32 softmax (MXU-friendly einsum form).
    `window` limits causal reach to q - key < window (HF sliding-window
    convention)."""
    depth = q.shape[-1]
    scores = jnp.einsum(
        "bqhd,bkhd->bhqk", q, k, preferred_element_type=jnp.float32
    ) / math.sqrt(depth)
    if causal or window is not None:
        s_q, s_k = q.shape[1], k.shape[1]
        q_pos = jnp.arange(s_q)[:, None] + (s_k - s_q)  # bottom-aligned
        k_pos = jnp.arange(s_k)[None, :]
        keep = q_pos >= k_pos if causal else jnp.ones((s_q, s_k), jnp.bool_)
        if window is not None:
            keep = keep & (q_pos - k_pos < window)
        scores = jnp.where(keep[None, None], scores, -1e30)
    if mask is not None:
        # mask: [B, S_k] padding, [B, S_q, S_k], or [B, H|1, S_q, S_k]
        if mask.ndim == 2:
            mask = mask[:, None, None, :]
        elif mask.ndim == 3:
            mask = mask[:, None, :, :]
        scores = jnp.where(mask, scores, -1e30)
    probs = jax.nn.softmax(scores, axis=-1).astype(q.dtype)
    return jnp.einsum("bhqk,bkhd->bqhd", probs, v, preferred_element_type=jnp.float32).astype(q.dtype)


def blocked_attention(q, q_pos, k_view, v_view, key_pos, window, block,
                      lo=None, hi=None, select=None):
    """Causal attention of q [B, S, H, D] at positions `q_pos` [B, S] over
    keys `k_view` / `v_view` [B, R, Hkv, D] at positions `key_pos` [B, R]
    (negative: nothing there), `block` rows at a time in an online
    softmax; a `window` drops keys with `q - key >= window`, and `select`
    [B, S, R] bool (a learned selection) every key a query did not
    choose. Only blocks [lo, hi) are visited (all of them by default): the
    `[H, S, R]` scores never exist whole. Returns [B, S, H, D]. (A view of
    LATENT rows is attended by `ops/latent_chunk_attention.py`, which
    decompresses a block where it is attended, in one kernel.)"""
    B, S, H, D = q.shape
    R = k_view.shape[1]
    blk = min(block, R)
    if R % blk:
        pad = blk - R % blk
        k_view, v_view = (jnp.pad(a, ((0, 0), (0, pad), (0, 0), (0, 0)))
                          for a in (k_view, v_view))
        key_pos = jnp.pad(key_pos, ((0, 0), (0, pad)), constant_values=-1)
        if select is not None:
            select = jnp.pad(select, ((0, 0), (0, 0), (0, pad)))
    n_blocks = k_view.shape[1] // blk
    Hkv = k_view.shape[2]
    q5 = q.reshape(B, S, Hkv, H // Hkv, D)
    scale = 1.0 / math.sqrt(D)
    at = q_pos[:, None, None, :, None]

    def body(i, carry):
        m, l, acc = carry
        kb, vb = (jax.lax.dynamic_slice_in_dim(a, i * blk, blk, axis=1)
                  .astype(q.dtype) for a in (k_view, v_view))
        pb = jax.lax.dynamic_slice_in_dim(
            key_pos, i * blk, blk, axis=1)[:, None, None, None, :]
        s = jnp.einsum("bskgd,brkd->bkgsr", q5, kb,
                       preferred_element_type=jnp.float32) * scale
        see = (pb >= 0) & (pb <= at)
        if window is not None:
            see = see & (at - pb < window)
        if select is not None:
            see = see & jax.lax.dynamic_slice_in_dim(
                select, i * blk, blk, axis=2)[:, None, None]
        s = jnp.where(see, s, NEG_INF)
        m_new = jnp.maximum(m, jnp.max(s, axis=-1, keepdims=True))
        p = jnp.where(see, jnp.exp(s - m_new), 0.0)
        alpha = jnp.exp(m - m_new)
        pv = jnp.einsum("bkgsr,brkd->bkgsd", p.astype(q.dtype), vb,
                        preferred_element_type=jnp.float32)
        return (m_new, l * alpha + jnp.sum(p, axis=-1, keepdims=True),
                acc * alpha + pv)

    shape = (B, Hkv, H // Hkv, S)
    carry = (jnp.full(shape + (1,), NEG_INF, jnp.float32),
             jnp.zeros(shape + (1,), jnp.float32),
             jnp.zeros(shape + (D,), jnp.float32))
    _, l, acc = jax.lax.fori_loop(0 if lo is None else lo,
                                  n_blocks if hi is None else hi,
                                  body, carry)
    out = acc / jnp.maximum(l, 1e-30)                   # [B, Hkv, G, S, D]
    return jnp.moveaxis(out, 3, 1).reshape(B, S, H, D).astype(q.dtype)


@part("cache.write")
def write_view(view, rows, start, wraps: bool):
    """Rows [B, S, Hkv, D] written into view [B, R, Hkv, D] at positions
    `start` [B] onward, position p at row `p % R`. A view that keeps every
    position takes them as one slice; a ring, which may wrap, by a select
    over its (few) rows."""
    rows = rows.astype(view.dtype)
    if not wraps:
        return jax.vmap(lambda v, r, s: jax.lax.dynamic_update_slice(
            v, r, (s, 0, 0)))(view, rows, start)
    R, S = view.shape[1], rows.shape[1]
    off = (jnp.arange(R, dtype=jnp.int32)[None, :] - start[:, None]) % R
    new = jnp.take_along_axis(
        rows, jnp.minimum(off, S - 1)[:, :, None, None], axis=1)
    return jnp.where((off < S)[:, :, None, None], new, view)


# --- experts ----------------------------------------------------------------


def softmax_moe_layer(config, m: dict, x, token_mask=None):
    """A softmax-routed expert layer (`config.num_experts` experts, the
    `num_experts_per_tok` largest of a float32 softmax, renormalised iff
    `norm_topk_prob`; `ops/grouped_experts.py`) over x [B, S, h] -> (y,
    assignments per expert [E] of the tokens `token_mask` [B, S] keeps;
    all of them without a mask).
    Padding and dead lanes are routed and computed like any row (shapes
    are static); the mask only says which tokens the counters count."""
    from ..ops.grouped_experts import (
        expert_counts,
        grouped_swiglu_experts,
        softmax_topk_route,
    )

    c = config
    B, S, h = x.shape
    flat = x.reshape(B * S, h)
    experts, weights = softmax_topk_route(
        flat, m["router"]["kernel"], c.num_experts_per_tok, c.norm_topk_prob)
    e = m["experts"]
    y = grouped_swiglu_experts(flat, experts, weights, e["gate_proj"],
                               e["up_proj"], e["down_proj"])
    with part("moe.route"):
        counts = expert_counts(experts, c.num_experts,
                               None if token_mask is None
                               else token_mask.reshape(B * S))
    with part("moe.combine"):
        return y.astype(x.dtype).reshape(B, S, h), counts


# --- initializers -----------------------------------------------------------


def normal_init(key, shape, stddev: float = 0.02, dtype=jnp.float32):
    return jax.random.normal(key, shape, dtype) * stddev


def init_dense(key, d_in: int, d_out: int, stddev: float = 0.02, bias: bool = False,
               dtype=jnp.float32) -> dict:
    params = {"kernel": normal_init(key, (d_in, d_out), stddev, dtype)}
    if bias:
        params["bias"] = jnp.zeros((d_out,), dtype)
    return params


def token_nll(logits: jax.Array, labels: jax.Array,
              label_smoothing: float = 0.0) -> jax.Array:
    """Per-token negative log-likelihood in fp32 (stable under bf16 logits).
    Shared by the full and chunked loss paths."""
    logits = logits.astype(jnp.float32)
    log_probs = jax.nn.log_softmax(logits, axis=-1)
    nll = -jnp.take_along_axis(log_probs, labels[..., None], axis=-1)[..., 0]
    if label_smoothing > 0:
        smooth = -jnp.mean(log_probs, axis=-1)
        nll = (1 - label_smoothing) * nll + label_smoothing * smooth
    return nll


def shifted_padding_masks(mask):
    """(attention_mask, label_weights) for a next-token loss over
    `input_ids` with a [B, S] padding mask (1 = real).

    - attention: the key mask for the forward over input_ids[:, :-1];
    - label weights: a label counts only when IT is real AND its predicting
      token is real — the prediction made from a pad position (left-padded
      rows) has no valid context (a fully-masked attention row) and must
      not weight the loss.

    NOTE: for PACKED sequences (interior zeros separating segments) this
    also drops the first label after each gap — packed batches should build
    their own weights."""
    if mask is None:
        return None, None
    return mask[:, :-1], (mask[:, 1:] * mask[:, :-1]).astype(jnp.float32)


def cross_entropy_loss(
    logits: jax.Array, labels: jax.Array, mask: jax.Array | None = None,
    label_smoothing: float = 0.0,
) -> jax.Array:
    """Mean token cross-entropy in fp32."""
    nll = token_nll(logits, labels, label_smoothing)
    if mask is not None:
        return jnp.sum(nll * mask) / jnp.maximum(jnp.sum(mask), 1)
    return jnp.mean(nll)


def _head_loss_block(h, head, labels, weights, tied: bool, with_grads: bool):
    """One block of `fused_head_loss`: the weighted NLL summed over `h`
    [B, c, H] and, `with_grads`, the gradients of that sum by `h` and by
    `head` (float32, this block's part). The block's logits and their
    gradient live here and nowhere else."""
    logits = jnp.einsum("bsh,vh->bsv" if tied else "bsh,hv->bsv", h, head,
                        preferred_element_type=jnp.float32)
    shifted = logits - jnp.max(logits, axis=-1, keepdims=True)
    lse = jnp.log(jnp.sum(jnp.exp(shifted), axis=-1, keepdims=True))
    hit = labels[..., None] == jax.lax.broadcasted_iota(
        labels.dtype, logits.shape, logits.ndim - 1)
    nll = lse[..., 0] - jnp.sum(jnp.where(hit, shifted, 0.0), axis=-1)
    loss_sum = jnp.sum(nll * weights)
    if not with_grads:
        return loss_sum, None, None
    # d(loss_sum)/d(logits), float32, rounded ONCE where it enters the two
    # products (their other operand is in h's dtype already)
    d = ((jnp.exp(shifted - lse) - hit) * weights[..., None]).astype(h.dtype)
    dh = jnp.einsum("bsv,vh->bsh" if tied else "bsv,hv->bsh", d, head,
                    preferred_element_type=jnp.float32).astype(h.dtype)
    dhead = jnp.einsum("bsv,bsh->vh" if tied else "bsv,bsh->hv", d, h,
                       preferred_element_type=jnp.float32)
    return loss_sum, dh, dhead


def _head_loss_blocks(hidden, head, labels, weights, tied, chunk, with_grads):
    """`_head_loss_block` over S // chunk blocks of `chunk` positions (all B
    rows of each: a batch sharded over a mesh keeps every device busy in
    every block). The head's gradient adds up in float32 in the carry."""
    B, S, H = hidden.shape
    n = S // chunk

    def blocks(x):
        return jnp.moveaxis(x.reshape(B, n, chunk, *x.shape[2:]), 1, 0)

    def body(carry, xs):
        loss_sum, dhead = carry
        h, l, m = xs
        part, dh, dhead_part = _head_loss_block(h, head, l, m, tied,
                                                with_grads)
        if with_grads:
            dhead = dhead + dhead_part
        return (loss_sum + part, dhead), dh

    dhead0 = jnp.zeros(head.shape, jnp.float32) if with_grads else None
    (loss_sum, dhead), dh = jax.lax.scan(
        body, (jnp.float32(0.0), dhead0),
        (blocks(hidden), blocks(labels), blocks(weights)))
    if with_grads:
        dh = jnp.moveaxis(dh, 0, 1).reshape(B, S, H)
    return loss_sum, dh, dhead


@functools.partial(jax.custom_vjp, nondiff_argnums=(4, 5))
def fused_head_loss(hidden, head, labels, weights, tied: bool, chunk: int):
    """The LM head's projection and its cross-entropy as ONE op:
    `sum(token_nll(project(hidden), labels) * weights)` over blocks of
    `chunk` positions (S % chunk == 0), never holding more of the logits
    than one block `[B, chunk, V]` in float32.

    `hidden` [B, S, H] (after the final norm), `head` the tied embedding
    [V, H] (`tied`) or `lm_head.kernel` [H, V], already in `hidden`'s
    dtype; `labels` int [B, S], `weights` float32 [B, S].

    Differentiated (`jax.grad` / `value_and_grad`), the FORWARD makes the
    gradients too: each block's `d = (softmax - onehot) * weights` is used
    for `d @ head` and `d^T @ hidden` while it exists, so the logits are
    projected once, nothing vocabulary-wide is kept or recomputed, and the
    backward only scales the two residuals (`dh` [B, S, H] and the head's
    gradient, float32, head-shaped) by the incoming cotangent. Called
    without differentiation it computes the loss alone."""
    return _head_loss_blocks(hidden, head, labels, weights, tied, chunk,
                             with_grads=False)[0]


def _fused_head_loss_fwd(hidden, head, labels, weights, tied, chunk):
    loss_sum, dh, dhead = _head_loss_blocks(
        hidden, head, labels, weights, tied, chunk, with_grads=True)
    return loss_sum, (dh, dhead)


def _fused_head_loss_bwd(tied, chunk, residuals, g):
    dh, dhead = residuals  # head came in hidden's dtype, so dh's is both's
    return ((dh * g).astype(dh.dtype), (dhead * g).astype(dh.dtype),
            None, None)


fused_head_loss.defvjp(_fused_head_loss_fwd, _fused_head_loss_bwd)


_WIDE = 30  # a wide counter is (units of 2**30, the rest below 2**30)


def wide_count(pair) -> int:
    """A wide device counter (`Engine.device_counters()`) as a Python int."""
    return (int(pair[0]) << _WIDE) + int(pair[1])


def add_wide(total, x):
    """`total` (int32 [2], see `_WIDE`) plus `x` (int32, below 2**30): what
    a serving window counts (keys seen, tokens folded) passes 2**31."""
    low = total[1] + x
    return jnp.stack([total[0] + (low >> _WIDE), low & ((1 << _WIDE) - 1)])


def count_params(params: Any) -> int:
    return sum(int(np.prod(l.shape)) for l in jax.tree_util.tree_leaves(params))
