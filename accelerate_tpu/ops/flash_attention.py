"""Pallas flash attention (TPU).

Blockwise attention with online softmax: O(S) memory instead of the S x S
score matrix. No reference equivalent — the reference delegates attention to
torch/bnb kernels; this is part of the long-context answer (SURVEY.md §5)
together with parallel/ring_attention.py.

Forward is a pallas kernel with grid [batch*heads, q_blocks, k_blocks]
(k innermost): each step stages only (block_q, d) of Q and (block_k, d) of
K/V into VMEM — VMEM use is O(block), not O(S), so 32k+ contexts fit — and
carries the online-softmax state (running max / sum / accumulator) in VMEM
scratch across the k dimension. Causal variant no-ops fully masked k blocks
via `pl.when`. Backward is fused too (FlashAttention-2): the forward saves
only O and the per-row logsumexp; a dQ kernel (k innermost) and a dK/dV
kernel (q innermost) recompute the probability blocks on the fly, so both
directions are O(S) memory — no S x S score matrix anywhere.

On non-TPU backends the kernel runs in pallas interpret mode (slow, for
tests; decided and recorded in `ops/kernel_mode.py`); prefer
`dot_product_attention` there.

A Mosaic kernel is opaque to GSPMD ("Mosaic kernels cannot be
automatically partitioned"): under a mesh of more than one device call
`flash_attention_on_mesh`, which runs the kernel per shard inside
`jax.shard_map` over the batch and head axes.
"""

from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from . import kernel_mode

NEG_INF = -1e30
_LANES = 128  # TPU vector lane width; scalar-per-row state is kept 2D
_SUB = 8      # minimal lane width Mosaic accepts for a full-dim block: the
              # LSE rides as [BH, S, 8] (16x smaller than lane-broadcast)


def _pow2_floor(n: int) -> int:
    """Largest power of two <= n (0 for n < 1)."""
    return 1 << (n.bit_length() - 1) if n >= 1 else 0


def _mask_spec(heads: int, block_k: int, swap_grid: bool = False):
    """BlockSpec for the [B, SUB, S_k] key-padding mask: one copy per batch
    row, shared across `heads` heads via the index map. `swap_grid` matches
    the dK/dV kernel whose grid is (bh, k_blocks, q_blocks)."""
    if swap_grid:
        return pl.BlockSpec((1, _SUB, block_k),
                            lambda b, j, i: (b // heads, 0, j))
    return pl.BlockSpec((1, _SUB, block_k),
                        lambda b, i, j: (b // heads, 0, j))


def _apply_key_mask(mask_ref, s):
    """NEG_INF-out masked keys; mask block is [1, SUB, bk], one sublane row
    broadcasts over the q rows of s."""
    return jnp.where(mask_ref[0][:1, :] > 0, s, NEG_INF)


def _band_live(qi, ki, block_q, block_k, causal, window):
    """Whether k block `ki` can contribute to q block `qi`: under causality
    its first key must be visible to the block's last query; under a sliding
    window its last key must be inside the reach of the block's first query
    (key > q - window)."""
    live = (qi + 1) * block_q - 1 >= ki * block_k if causal else True
    if window is not None:
        live = live & (ki * block_k + block_k - 1 > qi * block_q - window)
    return live


def _band_mask(s, qi, ki, block_q, block_k, causal, window):
    q_pos = qi * block_q + jax.lax.broadcasted_iota(
        jnp.int32, (block_q, block_k), 0)
    k_pos = ki * block_k + jax.lax.broadcasted_iota(
        jnp.int32, (block_q, block_k), 1)
    keep = q_pos >= k_pos if causal else jnp.bool_(True)
    if window is not None:
        # HF sliding-window convention: key visible iff q - key < window
        # (reach of `window` positions INCLUDING the query itself)
        keep = keep & (q_pos - k_pos < window)
    return jnp.where(keep, s, NEG_INF)


def _flash_kernel(q_ref, k_ref, v_ref, *rest, causal: bool,
                  sm_scale: float, block_q: int, block_k: int,
                  num_k_blocks: int, with_lse: bool = False,
                  with_mask: bool = False, window: int | None = None):
    if with_mask:
        mask_ref, o_ref, *rest = rest
    else:
        mask_ref, (o_ref, *rest) = None, rest
    if with_lse:
        lse_ref, m_scr, l_scr, acc_scr = rest
    else:
        (lse_ref,), (m_scr, l_scr, acc_scr) = (None,), rest
    qi, ki = pl.program_id(1), pl.program_id(2)

    @pl.when(ki == 0)
    def _init():
        m_scr[...] = jnp.full_like(m_scr, NEG_INF)
        l_scr[...] = jnp.zeros_like(l_scr)
        acc_scr[...] = jnp.zeros_like(acc_scr)

    live = _band_live(qi, ki, block_q, block_k, causal, window)

    @pl.when(live)
    def _compute():
        # dots run on native (bf16) inputs with f32 accumulation: full MXU
        # rate on v5e/v5p (f32 matmul is 4x slower); softmax state stays f32
        q = q_ref[0]  # [bq, d]
        k = k_ref[0]  # [bk, d]
        v = v_ref[0]
        s = jnp.dot(q, k.T, preferred_element_type=jnp.float32) * sm_scale
        if causal or window is not None:
            s = _band_mask(s, qi, ki, block_q, block_k, causal, window)
        if mask_ref is not None:
            s = _apply_key_mask(mask_ref, s)
        m_prev = m_scr[...][:, :1]  # [bq, 1]
        l_prev = l_scr[...][:, :1]
        m_new = jnp.maximum(m_prev, jnp.max(s, axis=-1, keepdims=True))
        p = jnp.exp(s - m_new)
        if mask_ref is not None or window is not None:
            # a row with nothing visible in any block so far keeps m_new at
            # NEG_INF, where exp(s - m_new) would be exp(0)=1 per masked key
            # (a windowed live block can have rows entirely out of band) —
            # zero those explicitly
            p = jnp.where(s <= NEG_INF / 2, 0.0, p)
        alpha = jnp.exp(m_prev - m_new)
        l_new = l_prev * alpha + jnp.sum(p, axis=-1, keepdims=True)
        acc_scr[...] = acc_scr[...] * alpha + jnp.dot(
            p.astype(v.dtype), v, preferred_element_type=jnp.float32)
        m_scr[...] = jnp.broadcast_to(m_new, m_scr.shape)
        l_scr[...] = jnp.broadcast_to(l_new, l_scr.shape)

    @pl.when(ki == num_k_blocks - 1)
    def _finalize():
        l = l_scr[...][:, :1]
        o_ref[0] = (acc_scr[...] / jnp.maximum(l, 1e-30)).astype(o_ref.dtype)
        if lse_ref is not None:
            # logsumexp per row, lane-broadcast (the TPU-friendly layout the
            # backward kernels read without transposes). Fully-masked rows
            # (l == 0) pin lse to 0 so the backward's exp(s - lse) stays 0
            # instead of exp(NEG_INF - NEG_INF) garbage.
            lse = m_scr[...][:, :1] + jnp.log(jnp.maximum(l, 1e-30))
            lse = jnp.where(l > 0, lse, 0.0)
            lse_ref[0] = jnp.broadcast_to(lse, lse_ref.shape[1:])


def _flash_forward(q, k, v, causal: bool, block_q: int, block_k: int,
                   interpret: bool, save_residuals: bool = False, mask=None,
                   heads: int = 1, window: int | None = None):
    """q,k,v: [BH, S, D] -> [BH, S, D] (and LSE [BH, S, 8] if asked).
    mask: optional [B, SUB, S_k] key-padding mask (1 = attend), sublane-
    broadcast like the LSE residual and shared across `heads` heads via the
    index map (one HBM copy per batch row, not per head)."""
    bh, seq_q, d = q.shape
    seq_k = k.shape[1]
    sm_scale = 1.0 / math.sqrt(d)
    num_k_blocks = seq_k // block_k
    grid = (bh, seq_q // block_q, num_k_blocks)
    kernel = functools.partial(
        _flash_kernel, causal=causal, sm_scale=sm_scale,
        block_q=block_q, block_k=block_k, num_k_blocks=num_k_blocks,
        with_lse=save_residuals, with_mask=mask is not None, window=window,
    )
    out_shape = [jax.ShapeDtypeStruct((bh, seq_q, d), q.dtype)]
    out_specs = [pl.BlockSpec((1, block_q, d), lambda b, i, j: (b, i, 0))]
    if save_residuals:
        out_shape.append(jax.ShapeDtypeStruct((bh, seq_q, _SUB), jnp.float32))
        out_specs.append(pl.BlockSpec((1, block_q, _SUB), lambda b, i, j: (b, i, 0)))
    in_specs = [
        pl.BlockSpec((1, block_q, d), lambda b, i, j: (b, i, 0)),
        pl.BlockSpec((1, block_k, d), lambda b, i, j: (b, j, 0)),
        pl.BlockSpec((1, block_k, d), lambda b, i, j: (b, j, 0)),
    ]
    operands = [q, k, v]
    if mask is not None:
        in_specs.append(_mask_spec(heads, block_k))
        operands.append(mask)
    res = pl.pallas_call(
        kernel,
        out_shape=out_shape,
        grid=grid,
        in_specs=in_specs,
        out_specs=out_specs,
        scratch_shapes=[
            pltpu.VMEM((block_q, _LANES), jnp.float32),
            pltpu.VMEM((block_q, _LANES), jnp.float32),
            pltpu.VMEM((block_q, d), jnp.float32),
        ],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary"),
        ),
        interpret=interpret,
        name="flash_attention_fwd",
    )(*operands)
    if save_residuals:
        return res[0], res[1]
    return res[0]


def _flash_dq_kernel(q_ref, k_ref, v_ref, o_ref, do_ref, lse_ref, *rest,
                     causal: bool, sm_scale: float, block_q: int,
                     block_k: int, num_k_blocks: int,
                     with_mask: bool = False, window: int | None = None):
    """FlashAttention-2 backward, dQ pass: grid [BH, q_blocks, k_blocks]."""
    if with_mask:
        mask_ref, dq_ref, dq_scr = rest
    else:
        mask_ref, (dq_ref, dq_scr) = None, rest
    qi, ki = pl.program_id(1), pl.program_id(2)

    @pl.when(ki == 0)
    def _init():
        dq_scr[...] = jnp.zeros_like(dq_scr)

    live = _band_live(qi, ki, block_q, block_k, causal, window)

    @pl.when(live)
    def _compute():
        # native-dtype (bf16) MXU dots with f32 accumulation; f32-only for
        # the softmax state and elementwise math
        q = q_ref[0]
        k = k_ref[0]
        v = v_ref[0]
        do = do_ref[0]
        # per-row state: lse block is (1, bq, 8) -> column [bq, 1]; delta
        # recomputed from O/dO blocks (cheap elementwise, no HBM buffer)
        lse = lse_ref[0][:, :1]
        delta = jnp.sum(do.astype(jnp.float32) * o_ref[0].astype(jnp.float32),
                        axis=-1, keepdims=True)
        s = jnp.dot(q, k.T, preferred_element_type=jnp.float32) * sm_scale
        if causal or window is not None:
            s = _band_mask(s, qi, ki, block_q, block_k, causal, window)
        if mask_ref is not None:
            s = _apply_key_mask(mask_ref, s)
        p = jnp.exp(s - lse)                                   # [bq, bk]
        dp = jnp.dot(do, v.T, preferred_element_type=jnp.float32)
        ds = p * (dp - delta)
        dq_scr[...] += sm_scale * jnp.dot(
            ds.astype(k.dtype), k, preferred_element_type=jnp.float32)

    @pl.when(ki == num_k_blocks - 1)
    def _finalize():
        dq_ref[0] = dq_scr[...].astype(dq_ref.dtype)


def _flash_dkv_kernel(q_ref, k_ref, v_ref, o_ref, do_ref, lse_ref, *rest,
                      causal: bool, sm_scale: float, block_q: int,
                      block_k: int, num_q_blocks: int,
                      with_mask: bool = False, window: int | None = None):
    """FlashAttention-2 backward, dK/dV pass: grid [BH, k_blocks, q_blocks]."""
    if with_mask:
        mask_ref, dk_ref, dv_ref, dk_scr, dv_scr = rest
    else:
        mask_ref, (dk_ref, dv_ref, dk_scr, dv_scr) = None, rest
    ki, qi = pl.program_id(1), pl.program_id(2)

    @pl.when(qi == 0)
    def _init():
        dk_scr[...] = jnp.zeros_like(dk_scr)
        dv_scr[...] = jnp.zeros_like(dv_scr)

    live = _band_live(qi, ki, block_q, block_k, causal, window)

    @pl.when(live)
    def _compute():
        # native-dtype (bf16) MXU dots with f32 accumulation
        q = q_ref[0]
        k = k_ref[0]
        v = v_ref[0]
        do = do_ref[0]
        lse = lse_ref[0][:, :1]
        delta = jnp.sum(do.astype(jnp.float32) * o_ref[0].astype(jnp.float32),
                        axis=-1, keepdims=True)
        s = jnp.dot(q, k.T, preferred_element_type=jnp.float32) * sm_scale
        if causal or window is not None:
            s = _band_mask(s, qi, ki, block_q, block_k, causal, window)
        if mask_ref is not None:
            s = _apply_key_mask(mask_ref, s)
        p = jnp.exp(s - lse)                                   # [bq, bk]
        dp = jnp.dot(do, v.T, preferred_element_type=jnp.float32)
        ds = p * (dp - delta)
        # contract over the q dim without materializing transposes
        # (dot_general; MXU takes either operand order)
        contract_q = (((0,), (0,)), ((), ()))
        dv_scr[...] += jax.lax.dot_general(
            p.astype(do.dtype), do, contract_q,
            preferred_element_type=jnp.float32)
        dk_scr[...] += sm_scale * jax.lax.dot_general(
            ds.astype(q.dtype), q, contract_q,
            preferred_element_type=jnp.float32)

    @pl.when(qi == num_q_blocks - 1)
    def _finalize():
        dk_ref[0] = dk_scr[...].astype(dk_ref.dtype)
        dv_ref[0] = dv_scr[...].astype(dv_ref.dtype)


def _flash_backward(q, k, v, o, lse, do, causal: bool, block_q: int,
                    block_k: int, interpret: bool, mask=None,
                    heads: int = 1, window: int | None = None):
    """Fused O(S) backward: no S x S materialization.

    Per-row state stays near-compact: the saved residual is [BH, S] f32,
    re-broadcast transiently to [BH, S, 8] here (Mosaic's narrowest legal
    full-dim lane block); delta is recomputed inside the kernels from the
    O/dO blocks — no [BH, S, LANES] buffers in HBM."""
    bh, seq_q, d = q.shape
    seq_k = k.shape[1]
    sm_scale = 1.0 / math.sqrt(d)
    num_q_blocks = seq_q // block_q
    num_k_blocks = seq_k // block_k

    lse = jnp.broadcast_to(lse[..., None], (bh, seq_q, _SUB))

    q_spec = pl.BlockSpec((1, block_q, d), lambda b, i, j: (b, i, 0))
    row_spec = pl.BlockSpec((1, block_q, _SUB), lambda b, i, j: (b, i, 0))
    kq_spec = pl.BlockSpec((1, block_k, d), lambda b, i, j: (b, j, 0))

    dq_in_specs = [q_spec, kq_spec, kq_spec, q_spec, q_spec, row_spec]
    dq_operands = [q, k, v, o, do, lse]
    if mask is not None:
        dq_in_specs.append(_mask_spec(heads, block_k))
        dq_operands.append(mask)
    dq = pl.pallas_call(
        functools.partial(
            _flash_dq_kernel, causal=causal, sm_scale=sm_scale,
            block_q=block_q, block_k=block_k, num_k_blocks=num_k_blocks,
            with_mask=mask is not None, window=window,
        ),
        out_shape=jax.ShapeDtypeStruct((bh, seq_q, d), q.dtype),
        grid=(bh, num_q_blocks, num_k_blocks),
        in_specs=dq_in_specs,
        out_specs=q_spec,
        scratch_shapes=[pltpu.VMEM((block_q, d), jnp.float32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary"),
        ),
        interpret=interpret,
        name="flash_attention_bwd_dq",
    )(*dq_operands)

    # dK/dV pass: k blocks outer (parallel), q blocks inner (reduction)
    q_spec2 = pl.BlockSpec((1, block_q, d), lambda b, j, i: (b, i, 0))
    row_spec2 = pl.BlockSpec((1, block_q, _SUB), lambda b, j, i: (b, i, 0))
    k_spec2 = pl.BlockSpec((1, block_k, d), lambda b, j, i: (b, j, 0))
    dkv_in_specs = [q_spec2, k_spec2, k_spec2, q_spec2, q_spec2, row_spec2]
    dkv_operands = [q, k, v, o, do, lse]
    if mask is not None:
        dkv_in_specs.append(_mask_spec(heads, block_k, swap_grid=True))
        dkv_operands.append(mask)
    dk, dv = pl.pallas_call(
        functools.partial(
            _flash_dkv_kernel, causal=causal, sm_scale=sm_scale,
            block_q=block_q, block_k=block_k, num_q_blocks=num_q_blocks,
            with_mask=mask is not None, window=window,
        ),
        out_shape=[
            jax.ShapeDtypeStruct((bh, seq_k, d), k.dtype),
            jax.ShapeDtypeStruct((bh, seq_k, d), v.dtype),
        ],
        grid=(bh, num_k_blocks, num_q_blocks),
        in_specs=dkv_in_specs,
        out_specs=[k_spec2, k_spec2],
        scratch_shapes=[
            pltpu.VMEM((block_k, d), jnp.float32),
            pltpu.VMEM((block_k, d), jnp.float32),
        ],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary"),
        ),
        interpret=interpret,
        name="flash_attention_bwd_dkv",
    )(*dkv_operands)
    return dq, dk, dv


@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4, 5, 6, 7))
def _flash(q, k, v, causal, block_q, block_k, interpret, window):
    return _flash_forward(q, k, v, causal, block_q, block_k, interpret,
                          window=window)


def _flash_fwd(q, k, v, causal, block_q, block_k, interpret, window):
    o, lse = _flash_forward(q, k, v, causal, block_q, block_k, interpret,
                            save_residuals=True, window=window)
    # keep only one lane of the broadcast LSE as the saved residual
    # ([BH, S] f32, not [BH, S, 128]) — re-broadcast transiently in bwd
    return o, (q, k, v, o, lse[..., 0])


def _flash_bwd(causal, block_q, block_k, interpret, window, res, g):
    q, k, v, o, lse = res
    return _flash_backward(q, k, v, o, lse, g, causal, block_q, block_k,
                           interpret, window=window)


_flash.defvjp(_flash_fwd, _flash_bwd)


@functools.partial(jax.custom_vjp, nondiff_argnums=(4, 5, 6, 7, 8, 9))
def _flash_masked(q, k, v, mask, causal, block_q, block_k, interpret, heads,
                  window):
    """Masked variant: mask is [B, SUB, S_k] (1 = attend), nondifferentiable
    data threaded as a regular operand (its cotangent is zeros) and shared
    across heads by the kernels' index maps."""
    return _flash_forward(q, k, v, causal, block_q, block_k, interpret,
                          mask=mask, heads=heads, window=window)


def _flash_masked_fwd(q, k, v, mask, causal, block_q, block_k, interpret,
                      heads, window):
    o, lse = _flash_forward(q, k, v, causal, block_q, block_k, interpret,
                            save_residuals=True, mask=mask, heads=heads,
                            window=window)
    return o, (q, k, v, o, lse[..., 0], mask)


def _flash_masked_bwd(causal, block_q, block_k, interpret, heads, window,
                      res, g):
    q, k, v, o, lse, mask = res
    dq, dk, dv = _flash_backward(q, k, v, o, lse, g, causal, block_q,
                                 block_k, interpret, mask=mask, heads=heads,
                                 window=window)
    return dq, dk, dv, jnp.zeros_like(mask)


_flash_masked.defvjp(_flash_masked_fwd, _flash_masked_bwd)


def flash_attention(
    q: jax.Array,
    k: jax.Array,
    v: jax.Array,
    causal: bool = False,
    mask: jax.Array | None = None,
    window: int | None = None,
    block_q: int | None = None,
    block_k: int | None = None,
    interpret: bool | None = None,
) -> jax.Array:
    """[B, S, H, D] flash attention, fused forward AND backward. Heads must
    already be repeated (GQA: call models.common.repeat_kv first). Block
    sizes are clamped to power-of-two divisors of the sequence where needed:
    causal self-attention at a non-block-multiple length runs the kernel on
    unpadded pow2-divisor blocks when they stay >= 256, else pads to a block
    multiple (causally exact) and slices; non-causal shrinks blocks to the
    largest pow2 divisor of the length. Only lengths whose usable block
    would drop under 16 rows (Mosaic sublane floor) — e.g. s < 16, or
    non-causal odd lengths — fall back to einsum attention.

    `mask` is a key-padding mask — [B, S_k] (or any shape squeezable to it,
    e.g. [B, 1, 1, S_k]) with 1/True = attend — applied inside the kernel in
    forward and backward; fully-masked rows produce zero output. Full
    per-position [B, ..., S_q, S_k] masks fall back to einsum attention.

    `window` is a sliding-attention window in the HF Mistral convention —
    key visible iff q - key < window (reach includes the query) — applied as
    a band mask inside the kernels; blocks wholly outside the band are
    skipped entirely, so long-context windowed attention costs
    O(S * window), not O(S^2). Requires causal=True.

    Default blocks (512x1024 to ~2k context, 1024x1024 from 4k up) were
    picked by an earlier sweep (benchmarks/sweep_attn.py) whose numbers
    are not measured on the current code."""
    b, sq, h, d = q.shape
    if window is not None:
        if not causal:
            raise ValueError("window requires causal=True (sliding-window "
                             "attention is a causal-LM feature)")
        if window <= 0:
            raise ValueError(f"window must be positive, got {window}")
        if window >= k.shape[1]:
            window = None  # band wider than the sequence: plain causal
    sk = k.shape[1]
    key_mask = None
    if mask is not None:
        m = mask
        while m.ndim > 2 and m.shape[1] == 1:
            m = m[:, 0]
        if m.ndim == 2 and m.shape == (b, sk):
            key_mask = m
        else:
            from ..models.common import dot_product_attention

            return dot_product_attention(q, k, v, mask=mask, causal=causal,
                                         window=window)
    if block_q is None:
        block_q = 1024 if sq >= 4096 else 512
    if block_k is None:
        block_k = 1024
    # clamp blocks to the sequence, rounded down to a power of two (>= 16 for
    # Mosaic sublane tiling): an unaligned block (e.g. 300 rows after a plain
    # min()) fails Mosaic lowering on real TPUs even though interpret-mode
    # tests would pass, and a non-power-of-two block (e.g. 528) would make
    # the lcm pad target below explode to ~32x the sequence
    block_q = _pow2_floor(min(block_q, sq))
    block_k = _pow2_floor(min(block_k, sk))

    def _fallback():
        from ..models.common import dot_product_attention

        return dot_product_attention(q, k, v, mask=key_mask, causal=causal,
                                     window=window)

    # sq != sk would make the kernel's top-aligned causal mask disagree with
    # the bottom-aligned reference (and read past the k buffer when sq > sk)
    if block_q < 16 or block_k < 16 or (causal and sq != sk):
        return _fallback()
    if sq % block_q or sk % block_k:
        if causal:
            # first preference: shrink to power-of-two divisor blocks and run
            # unpadded — s=1280 runs at 256-blocks instead of padding to 2048
            bq2, bk2 = min(block_q, sq & -sq), min(block_k, sk & -sk)
            if bq2 >= 256 and bk2 >= 256:
                block_q, block_k = bq2, bk2
            else:
                # pad to a block multiple and slice the result: causally
                # exact, since padded keys (index >= sq) are only visible to
                # padded queries — the training loss slices inputs to S-1,
                # which would otherwise dodge the kernel entirely. Equal
                # blocks keep the lcm (= block_q) and so the pad under one
                # block's worth.
                block_k = min(block_k, block_q)
                multiple = math.lcm(block_q, block_k)
                target = -(-sq // multiple) * multiple
                pad = target - sq
                qp = jnp.pad(q, ((0, 0), (0, pad), (0, 0), (0, 0)))
                kp = jnp.pad(k, ((0, 0), (0, pad), (0, 0), (0, 0)))
                vp = jnp.pad(v, ((0, 0), (0, pad), (0, 0), (0, 0)))
                mp = (
                    jnp.pad(key_mask, ((0, 0), (0, pad)))
                    if key_mask is not None else None
                )
                out = flash_attention(qp, kp, vp, causal=True, mask=mp,
                                      window=window, block_q=block_q,
                                      block_k=block_k, interpret=interpret)
                return out[:, :sq]
        else:
            # non-causal can't pad (extra keys would get real softmax
            # weight); shrink to the largest power-of-two divisor of the
            # length so e.g. s=1920 (divisible by 128, not 512) still runs
            block_q = min(block_q, sq & -sq)
            block_k = min(block_k, sk & -sk)
            if block_q < 16 or block_k < 16:
                return _fallback()
    # only here is the kernel really about to run (the einsum fallbacks
    # above returned already), so only here is the mode decided/recorded
    interpret = kernel_mode.resolve_interpret("flash_attention", interpret)
    qf = q.transpose(0, 2, 1, 3).reshape(b * h, sq, d)
    kf = k.transpose(0, 2, 1, 3).reshape(b * h, sk, d)
    vf = v.transpose(0, 2, 1, 3).reshape(b * h, sk, d)
    if key_mask is not None:
        # [B, SUB, S_k] layout: Mosaic needs the sublane dim of a block to
        # be a multiple of 8 (same trick as the LSE residual); one copy per
        # batch row, shared across heads by the kernels' index maps
        # f32, not bf16: Mosaic's vector compare doesn't lower for bf16
        mf = jnp.broadcast_to(
            key_mask.astype(jnp.float32)[:, None, :], (b, _SUB, sk)
        )
        out = _flash_masked(qf, kf, vf, mf, causal, block_q, block_k,
                            interpret, h, window)
    else:
        out = _flash(qf, kf, vf, causal, block_q, block_k, interpret, window)
    return out.reshape(b, h, sq, d).transpose(0, 2, 1, 3)


def flash_attention_on_mesh(
    q: jax.Array,
    k: jax.Array,
    v: jax.Array,
    mesh,
    causal: bool = False,
    mask: jax.Array | None = None,
    window: int | None = None,
) -> jax.Array:
    """`flash_attention` under a device mesh: the kernel runs per shard
    inside `jax.shard_map`, batch split over the data-like axes
    (`BATCH_AXES`) and heads over the tensor-parallel axis — the layout
    the sharding planner already gives activations, so no resharding is
    added around the call. An axis whose size does not divide its dim is
    left out of the spec (that dim is then replicated over it: correct,
    just not split). The sequence is never split here (ring/ulysses do
    that). `mask` must be a [B, S_k] key-padding mask or None.

    Called with a one-device mesh, or from inside an enclosing
    `shard_map` (the pipeline stages: the mesh axes are already manual
    and the arrays already per-shard), it is the bare kernel."""
    from jax.sharding import PartitionSpec as P

    from ..utils.constants import AXIS_MODEL, BATCH_AXES

    fn = functools.partial(flash_attention, causal=causal, window=window)
    already_manual = bool(jax.sharding.get_abstract_mesh().manual_axes)
    if mesh is None or mesh.size == 1 or already_manual:
        return fn(q, k, v, mask=mask)
    b, _, h, _ = q.shape
    batch_axes, n = [], 1
    for a in BATCH_AXES:
        size = mesh.shape.get(a, 1)
        if size > 1 and b % (n * size) == 0:
            batch_axes.append(a)
            n *= size
    lead = tuple(batch_axes) if len(batch_axes) > 1 else (
        batch_axes[0] if batch_axes else None)
    tp = mesh.shape.get(AXIS_MODEL, 1)
    heads = AXIS_MODEL if tp > 1 and h % tp == 0 else None
    spec = P(lead, None, heads, None)
    args, in_specs = (q, k, v), (spec, spec, spec)
    if mask is not None:
        args, in_specs = args + (mask,), in_specs + (P(lead, None),)
    return jax.shard_map(
        lambda q, k, v, m=None: fn(q, k, v, mask=m), mesh=mesh,
        in_specs=in_specs, out_specs=spec, check_vma=False)(*args)
