"""Ring attention: sequence/context parallelism over the mesh `seq` axis.

The reference has NO context parallelism (SURVEY.md §2.2 — grep-verified
absent); this exceeds parity and is the long-context answer. Each device
holds a sequence chunk of Q/K/V; K/V chunks rotate around the ring via
`lax.ppermute` (XLA collective-permute over ICI) while per-chunk outputs
fold through a log-sum-exp combine. Peak memory is O(S_local) per device;
the S x S score matrix is never materialized globally.

Compute path: each ring step runs the pallas flash kernel
(ops/flash_attention.py — bf16 MXU dots, O(block) VMEM), so long-context
throughput is flash-rate, not einsum-rate. The backward is the ring form
of FlashAttention-2 (Liu et al.'s ring attention): the saved GLOBAL
logsumexp makes every chunk's recomputed probabilities exact, dQ
accumulates locally, and dK/dV accumulators ride the rotating K/V buffers
until a full rotation returns them to their owner device.

GQA: K/V ring un-repeated (kv heads only — the repeat factor never
touches ICI); heads repeat per chunk right before the kernel, and dK/dV
reduce back over the repeat groups.

Chunks too small for the kernel (under one 16-row block) fall back to the
einsum ring, same math at einsum rate.
"""

from __future__ import annotations

import math
from functools import partial

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from ..ops import kernel_mode
from ..utils.constants import AXIS_SEQ
from ..models.common import repeat_kv as _repeat_heads
from ..ops.flash_attention import (
    _flash_backward,
    _flash_forward,
    _pow2_floor,
)


NEG_INF = -1e30


# ---------------------------------------------------------------------------
# flash-kernel chunk helpers ([B, S, H, D] <-> kernel's [BH, S, D])
# ---------------------------------------------------------------------------


def _chunk_blocks(s_local: int) -> int:
    return _pow2_floor(min(512, s_local))


def _kernel_mask(mask, b, s):
    """[B, s] key mask -> the kernel's [B, SUB, s] sublane-broadcast f32."""
    from ..ops.flash_attention import _SUB

    return jnp.broadcast_to(mask.astype(jnp.float32)[:, None, :], (b, _SUB, s))


def _chunk_fwd(q, k, v, causal: bool, interpret: bool, mask=None):
    """One chunk pair through the flash kernel; returns (o, lse[B,H,S]).
    `mask` is this K/V chunk's [B, s] key-padding mask; a batch row whose
    chunk is fully masked reports lse = -inf so the streaming fold treats it
    as no contribution (the kernel itself pins such rows to lse = 0)."""
    b, s, h, d = q.shape
    blk = _chunk_blocks(s)
    qf = q.transpose(0, 2, 1, 3).reshape(b * h, s, d)
    kf = k.transpose(0, 2, 1, 3).reshape(b * h, s, d)
    vf = v.transpose(0, 2, 1, 3).reshape(b * h, s, d)
    o, lse = _flash_forward(qf, kf, vf, causal, blk, blk, interpret,
                            save_residuals=True,
                            mask=None if mask is None else _kernel_mask(mask, b, s),
                            heads=h)
    o = o.reshape(b, h, s, d).transpose(0, 2, 1, 3)
    lse = lse[..., 0].reshape(b, h, s)
    if mask is not None:
        # (the kernel already zeros such rows' outputs)
        any_key = jnp.any(mask > 0, axis=-1)  # [B]
        lse = jnp.where(any_key[:, None, None], lse, NEG_INF)
    return o, lse


def _chunk_bwd(q, k, v, o, lse, do, causal: bool, interpret: bool, mask=None):
    """Flash backward for one chunk pair using the GLOBAL lse — exactly the
    ring-attention backward: p = exp(s - lse_global) are the true
    (unnormalized-by-chunk) probabilities, delta = rowsum(do * o_global)."""
    b, s, h, d = q.shape
    blk = _chunk_blocks(s)
    to_f = lambda t: t.transpose(0, 2, 1, 3).reshape(b * h, s, d)  # noqa: E731
    dq, dk, dv = _flash_backward(
        to_f(q), to_f(k), to_f(v), to_f(o),
        lse.reshape(b * h, s), to_f(do),
        causal, blk, blk, interpret,
        mask=None if mask is None else _kernel_mask(mask, b, s), heads=h,
    )
    back = lambda t: t.reshape(b, h, s, d).transpose(0, 2, 1, 3)  # noqa: E731
    return back(dq), back(dk), back(dv)


def _reduce_heads(full, n_rep: int):
    """Sum gradients over the repeat groups back to kv heads."""
    if n_rep == 1:
        return full
    b, s, h, d = full.shape
    return full.reshape(b, s, h // n_rep, n_rep, d).sum(axis=3)


# ---------------------------------------------------------------------------
# ring forward/backward (runs INSIDE shard_map)
# ---------------------------------------------------------------------------


def _fold(out, lse, o_i, lse_i, visible):
    """Streaming log-sum-exp combine of per-chunk normalized outputs."""
    lse_i = jnp.where(visible, lse_i, NEG_INF)
    new_lse = jnp.logaddexp(lse, lse_i)
    safe = jnp.maximum(new_lse, NEG_INF / 2)
    w_old = jnp.exp(lse - safe)[..., None]
    w_new = jnp.exp(lse_i - safe)[..., None]
    # [B,H,S] weights onto [B,S,H,D] outputs
    w_old = w_old.transpose(0, 2, 1, 3)
    w_new = w_new.transpose(0, 2, 1, 3)
    return out * w_old + o_i * w_new, new_lse


def _ring_flash_fwd_impl(q, k, v, mask, axis_name, axis_size, causal, n_rep,
                         interpret):
    """Forward ring. `mask` is this device's [B, S_local] key-padding chunk
    (or None); it rotates around the ring WITH its K/V chunk."""
    my = jax.lax.axis_index(axis_name)
    b, s, h, d = q.shape
    perm = [(i, (i + 1) % axis_size) for i in range(axis_size)]

    # step 0: the diagonal chunk (causal within the chunk)
    o0, lse0 = _chunk_fwd(q, _repeat_heads(k, n_rep), _repeat_heads(v, n_rep),
                          causal, interpret, mask=mask)
    out, lse = o0.astype(jnp.float32), lse0

    def step(carry, t):
        out, lse, k_cur, v_cur, m_cur = carry
        k_cur = jax.lax.ppermute(k_cur, axis_name, perm)
        v_cur = jax.lax.ppermute(v_cur, axis_name, perm)
        if m_cur is not None:
            m_cur = jax.lax.ppermute(m_cur, axis_name, perm)
        src = (my - t) % axis_size

        def live(_):
            o_i, lse_i = _chunk_fwd(
                q, _repeat_heads(k_cur, n_rep), _repeat_heads(v_cur, n_rep),
                False, interpret, mask=m_cur,
            )
            return o_i.astype(jnp.float32), lse_i

        def dead(_):
            # chunk invisible under causality: skip the kernel entirely
            # (folding an unmasked chunk's exp(s - lse_global) could
            # overflow, and its compute would be discarded anyway)
            return jnp.zeros_like(out), jnp.full_like(lse, NEG_INF)

        if causal:
            o_i, lse_i = jax.lax.cond(src < my, live, dead, None)
        else:
            o_i, lse_i = live(None)
        out, lse = _fold(out, lse, o_i, lse_i, jnp.bool_(True))
        return (out, lse, k_cur, v_cur, m_cur), None

    if axis_size > 1:
        (out, lse, _, _, _), _ = jax.lax.scan(
            step, (out, lse, k, v, mask), jnp.arange(1, axis_size)
        )
    out = out.astype(q.dtype)
    if mask is not None:
        # rows with NO visible key anywhere (padded queries) folded to
        # lse = -inf; pin to 0 (the kernel's own convention) so the backward
        # computes p = exp(-inf - 0) = 0 instead of exp(-inf + inf) garbage
        lse = jnp.where(lse <= NEG_INF / 2, 0.0, lse)
    return out, (q, k, v, out, lse, mask)


def _ring_flash_bwd_impl(axis_name, axis_size, causal, n_rep, interpret,
                         res, g):
    q, k, v, o, lse, mask = res
    my = jax.lax.axis_index(axis_name)
    perm = [(i, (i + 1) % axis_size) for i in range(axis_size)]
    lse_f = lse  # [B,H,S] global logsumexp

    # diagonal chunk
    dq, dk0, dv0 = _chunk_bwd(
        q, _repeat_heads(k, n_rep), _repeat_heads(v, n_rep), o, lse_f, g,
        causal, interpret, mask=mask,
    )
    dq = dq.astype(jnp.float32)
    dk_cur = _reduce_heads(dk0.astype(jnp.float32), n_rep)
    dv_cur = _reduce_heads(dv0.astype(jnp.float32), n_rep)

    h_full = q.shape[2]

    def step(carry, t):
        dq, k_cur, v_cur, m_cur, dk_cur, dv_cur = carry
        k_cur = jax.lax.ppermute(k_cur, axis_name, perm)
        v_cur = jax.lax.ppermute(v_cur, axis_name, perm)
        if m_cur is not None:
            m_cur = jax.lax.ppermute(m_cur, axis_name, perm)
        dk_cur = jax.lax.ppermute(dk_cur, axis_name, perm)
        dv_cur = jax.lax.ppermute(dv_cur, axis_name, perm)
        src = (my - t) % axis_size

        def live(_):
            return _chunk_bwd(
                q, _repeat_heads(k_cur, n_rep), _repeat_heads(v_cur, n_rep),
                o, lse_f, g, False, interpret, mask=m_cur,
            )

        def dead(_):
            # invisible chunk: no contribution; skipping the kernel avoids
            # exp(s - lse_global) overflow (NaN via inf * 0) and the wasted
            # backward FLOPs
            b, s_l, _, d = q.shape
            return (
                jnp.zeros_like(q),
                jnp.zeros((b, s_l, h_full, d), k_cur.dtype),
                jnp.zeros((b, s_l, h_full, d), v_cur.dtype),
            )

        if causal:
            dq_i, dk_i, dv_i = jax.lax.cond(src < my, live, dead, None)
        else:
            dq_i, dk_i, dv_i = live(None)
        dq = dq + dq_i.astype(jnp.float32)
        dk_cur = dk_cur + _reduce_heads(dk_i.astype(jnp.float32), n_rep)
        dv_cur = dv_cur + _reduce_heads(dv_i.astype(jnp.float32), n_rep)
        return (dq, k_cur, v_cur, m_cur, dk_cur, dv_cur), None

    if axis_size > 1:
        (dq, _, _, _, dk_cur, dv_cur), _ = jax.lax.scan(
            step, (dq, k, v, mask, dk_cur, dv_cur), jnp.arange(1, axis_size)
        )
        # the accumulators have rotated axis_size-1 times; one more rotation
        # brings each chunk's dK/dV home to its owner
        dk_cur = jax.lax.ppermute(dk_cur, axis_name, perm)
        dv_cur = jax.lax.ppermute(dv_cur, axis_name, perm)
    return dq.astype(q.dtype), dk_cur.astype(k.dtype), dv_cur.astype(v.dtype)


@partial(jax.custom_vjp, nondiff_argnums=(3, 4, 5, 6, 7))
def _ring_flash(q, k, v, axis_name, axis_size, causal, n_rep, interpret):
    return _ring_flash_fwd(q, k, v, axis_name, axis_size, causal, n_rep,
                           interpret)[0]


def _ring_flash_fwd(q, k, v, axis_name, axis_size, causal, n_rep, interpret):
    out, (q, k, v, o, lse, _) = _ring_flash_fwd_impl(
        q, k, v, None, axis_name, axis_size, causal, n_rep, interpret)
    return out, (q, k, v, o, lse)


def _ring_flash_bwd(axis_name, axis_size, causal, n_rep, interpret, res, g):
    q, k, v, o, lse = res
    return _ring_flash_bwd_impl(axis_name, axis_size, causal, n_rep,
                                interpret, (q, k, v, o, lse, None), g)


_ring_flash.defvjp(_ring_flash_fwd, _ring_flash_bwd)


@partial(jax.custom_vjp, nondiff_argnums=(4, 5, 6, 7, 8))
def _ring_flash_masked(q, k, v, mask, axis_name, axis_size, causal, n_rep,
                       interpret):
    """Masked ring: mask is nondifferentiable data threaded as an operand
    (zero cotangent), its chunk riding the ring with K/V."""
    return _ring_flash_masked_fwd(q, k, v, mask, axis_name, axis_size,
                                  causal, n_rep, interpret)[0]


def _ring_flash_masked_fwd(q, k, v, mask, axis_name, axis_size, causal,
                           n_rep, interpret):
    return _ring_flash_fwd_impl(q, k, v, mask, axis_name, axis_size, causal,
                                n_rep, interpret)


def _ring_flash_masked_bwd(axis_name, axis_size, causal, n_rep, interpret,
                           res, g):
    mask = res[5]
    dq, dk, dv = _ring_flash_bwd_impl(axis_name, axis_size, causal, n_rep,
                                      interpret, res, g)
    return dq, dk, dv, jnp.zeros_like(mask)


_ring_flash_masked.defvjp(_ring_flash_masked_fwd, _ring_flash_masked_bwd)


def _ring_attention_local(q, k, v, *, axis_name: str, axis_size: int,
                          causal: bool, n_rep: int, interpret: bool):
    """Runs INSIDE shard_map. q: [B, S_local, H, D]; k/v may carry fewer
    (kv) heads — they ring un-repeated."""
    return _ring_flash(q, k, v, axis_name, axis_size, causal, n_rep,
                       interpret)


def _ring_attention_local_masked(q, k, v, mask, *, axis_name: str,
                                 axis_size: int, causal: bool, n_rep: int,
                                 interpret: bool):
    return _ring_flash_masked(q, k, v, mask, axis_name, axis_size, causal,
                              n_rep, interpret)


# ---------------------------------------------------------------------------
# einsum fallback ring (tiny chunks / no kernel)
# ---------------------------------------------------------------------------


def _ring_attention_local_einsum(q, k, v, mask=None, *, axis_name: str,
                                 axis_size: int, causal: bool, n_rep: int,
                                 window: int | None = None):
    my_idx = jax.lax.axis_index(axis_name)
    b, s_local, h, d = q.shape
    scale = 1.0 / math.sqrt(d)

    qf = (q.astype(jnp.float32) * scale).transpose(0, 2, 1, 3)  # [B,H,S,D]

    acc = jnp.zeros((b, h, s_local, d), jnp.float32)
    row_max = jnp.full((b, h, s_local), NEG_INF, jnp.float32)
    row_sum = jnp.zeros((b, h, s_local), jnp.float32)

    def fold_chunk(acc, row_max, row_sum, k_cur, v_cur, m_cur, src):
        kf = _repeat_heads(k_cur, n_rep).astype(jnp.float32).transpose(0, 2, 1, 3)
        vf = _repeat_heads(v_cur, n_rep).astype(jnp.float32).transpose(0, 2, 1, 3)
        s = jnp.einsum("bhqd,bhkd->bhqk", qf, kf)
        if causal or window is not None:
            # GLOBAL positions: this device's query chunk vs the held key
            # chunk's owner — the band is exact across chunk boundaries
            q_pos = my_idx * s_local + jax.lax.broadcasted_iota(
                jnp.int32, (s_local, s_local), 0
            )
            k_pos = src * s_local + jax.lax.broadcasted_iota(
                jnp.int32, (s_local, s_local), 1
            )
            vis = (q_pos >= k_pos) if causal else (q_pos == q_pos)
            if window is not None:
                # Mistral band: keys visible iff q - key < window
                vis = vis & (q_pos - k_pos < window)
            s = jnp.where(vis[None, None], s, NEG_INF)
        if m_cur is not None:
            s = jnp.where((m_cur > 0)[:, None, None, :], s, NEG_INF)
        m_new = jnp.maximum(row_max, jnp.max(s, axis=-1))
        # a row with nothing visible yet keeps m_new = NEG_INF; exp(s - m)
        # would be exp(0) = 1 per masked key — clamp the subtrahend
        safe_m = jnp.maximum(m_new, NEG_INF / 2)
        p = jnp.exp(s - safe_m[..., None])
        p = jnp.where(s <= NEG_INF / 2, 0.0, p)
        alpha = jnp.exp(jnp.maximum(row_max, NEG_INF / 2) - safe_m)
        row_sum_new = row_sum * alpha + jnp.sum(p, axis=-1)
        acc_new = acc * alpha[..., None] + jnp.einsum("bhqk,bhkd->bhqd", p, vf)
        return acc_new, m_new, row_sum_new

    # local chunk first, then axis_size-1 rotations (no wasted final permute)
    acc, row_max, row_sum = fold_chunk(acc, row_max, row_sum, k, v, mask,
                                       my_idx)
    perm = [(i, (i + 1) % axis_size) for i in range(axis_size)]

    def block(carry, step):
        acc, row_max, row_sum, k_cur, v_cur, m_cur = carry
        k_cur = jax.lax.ppermute(k_cur, axis_name, perm)
        v_cur = jax.lax.ppermute(v_cur, axis_name, perm)
        if m_cur is not None:
            m_cur = jax.lax.ppermute(m_cur, axis_name, perm)
        src = (my_idx - step) % axis_size  # owner of the chunk we now hold

        # skip chunks with NO visible pair: future chunks under causality,
        # and chunks entirely past the sliding window's reach — the latter
        # turns the windowed ring's compute from O(S^2/P) into O(S*W/P)
        vis = jnp.bool_(True)
        if causal:
            vis = src <= my_idx
        if window is not None:
            # closest pair of the chunk: (my-src)*s_local - (s_local-1)
            vis = vis & ((my_idx - src) * s_local < window + s_local - 1)

        def live(_):
            return fold_chunk(acc, row_max, row_sum, k_cur, v_cur, m_cur,
                              src)

        def dead(_):
            return acc, row_max, row_sum

        acc, row_max, row_sum = jax.lax.cond(vis, live, dead, None)
        return (acc, row_max, row_sum, k_cur, v_cur, m_cur), None

    if axis_size > 1:
        (acc, row_max, row_sum, _, _, _), _ = jax.lax.scan(
            block, (acc, row_max, row_sum, k, v, mask),
            jnp.arange(1, axis_size)
        )
    out = acc / jnp.maximum(row_sum, 1e-30)[..., None]
    return out.transpose(0, 2, 1, 3).astype(q.dtype)  # [B, S_local, H, D]


def ring_attention(
    q: jax.Array,
    k: jax.Array,
    v: jax.Array,
    causal: bool = True,
    mask: jax.Array | None = None,
    mesh=None,
    axis_name: str = AXIS_SEQ,
    window: int | None = None,
) -> jax.Array:
    """[B, S, H, D] attention with S sharded over the mesh `seq` axis.

    Call from inside a jitted model forward: wraps itself in `shard_map`
    over the provided (or ambient) mesh. Falls back to plain attention when
    the mesh has no seq axis. K/V may carry fewer heads (GQA) — they ring
    un-repeated and the repeat happens per chunk at the kernel boundary.

    `mask` is a [B, S] key-padding mask (1 = attend): it shards over the
    same `seq` axis and each chunk rotates the ring with its K/V, so padded
    fine-tuning batches keep the ring fast path (the kernel applies it in
    forward AND backward).

    `window` applies Mistral-style sliding-window attention (keys visible
    iff q - key < window; requires `causal=True`). The windowed ring runs
    the einsum fold with exact global-position banding — the pallas ring
    kernel has no cross-chunk band offsets (yet), and at ring scale the
    window keeps per-chunk score matrices small anyway.
    """
    if window is not None and not causal:
        # validated BEFORE the off-mesh fallback so single-device debug runs
        # fail the same way pod runs do
        raise ValueError("ring_attention window requires causal=True "
                         "(Mistral sliding-window semantics)")
    if mesh is None:
        from ..state import PartialState

        if PartialState._shared_state:
            mesh = PartialState().mesh
    if (
        mesh is None
        or axis_name not in mesh.axis_names
        or mesh.shape[axis_name] == 1
        or q.shape[1] % mesh.shape[axis_name] != 0
        or k.shape[1] % mesh.shape[axis_name] != 0
    ):
        # no seq axis, or sequence not divisible into ring chunks (e.g. the
        # S-1 tokens of a causal-LM loss): plain attention
        from ..models.common import dot_product_attention

        return dot_product_attention(q, _repeat_heads(k, q.shape[2] // k.shape[2]),
                                     _repeat_heads(v, q.shape[2] // v.shape[2]),
                                     mask=mask, causal=causal, window=window)
    if mask is not None and mask.shape != (q.shape[0], k.shape[1]):
        raise ValueError(
            f"ring_attention mask must be a [B, S_k] key-padding mask; got "
            f"{mask.shape} for B={q.shape[0]}, S_k={k.shape[1]}"
        )

    axis_size = mesh.shape[axis_name]
    n_rep = q.shape[2] // k.shape[2]
    s_local = q.shape[1] // axis_size
    blk = _chunk_blocks(s_local)
    # the pallas ring kernel carries no cross-chunk band offsets: windowed
    # rings run the (exact) einsum fold
    use_kernel = blk >= 16 and s_local % blk == 0 and window is None
    interpret = use_kernel and kernel_mode.resolve_interpret("ring_attention")

    seq_spec = P(None, axis_name, None, None)
    mask_spec = P(None, axis_name)
    if use_kernel:
        if mask is not None:
            fn = partial(
                _ring_attention_local_masked, axis_name=axis_name,
                axis_size=axis_size, causal=causal, n_rep=n_rep,
                interpret=interpret,
            )
            return jax.shard_map(
                fn, mesh=mesh,
                in_specs=(seq_spec, seq_spec, seq_spec, mask_spec),
                out_specs=seq_spec,
                check_vma=False,
            )(q, k, v, mask)
        fn = partial(
            _ring_attention_local, axis_name=axis_name, axis_size=axis_size,
            causal=causal, n_rep=n_rep, interpret=interpret,
        )
    else:
        fn = partial(
            _ring_attention_local_einsum, axis_name=axis_name,
            axis_size=axis_size, causal=causal, n_rep=n_rep, window=window,
        )
        if mask is not None:
            return jax.shard_map(
                fn, mesh=mesh,
                in_specs=(seq_spec, seq_spec, seq_spec, mask_spec),
                out_specs=seq_spec,
                check_vma=False,
            )(q, k, v, mask)
    return jax.shard_map(
        fn, mesh=mesh,
        in_specs=(seq_spec, seq_spec, seq_spec),
        out_specs=seq_spec,
        check_vma=False,
    )(q, k, v)
