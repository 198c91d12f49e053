"""Ulysses-style sequence parallelism: all-to-all head scatter.

Second long-context schedule next to ring attention (SURVEY.md §5 —
the reference has neither). Where ring attention rotates K/V chunks around
the `seq` axis, Ulysses re-shards: an all-to-all turns [B, S/P, H, D]
(sequence-sharded) into [B, S, H/P, D] (head-sharded), each device runs
ordinary full-sequence attention over its head slice, and a second
all-to-all restores sequence sharding. Two collectives per layer, full
attention locality in between — the better schedule when H >= ring size and
ICI all-to-all bandwidth is plentiful; ring wins when S is extreme or head
count is small (the trade described in the Ulysses/DeepSpeed and ring
papers, PAPERS.md).

Requires H % axis_size == 0 and S % axis_size == 0.
"""

from __future__ import annotations

from functools import partial

import jax
from jax.sharding import PartitionSpec as P

from ..utils.constants import AXIS_SEQ



def _ulysses_local(q, k, v, mask=None, *, axis_name: str, causal: bool,
                   n_rep: int, window: int | None = None):
    """Runs INSIDE shard_map. q: [B, S_local, H, D], k/v: [B, S_local,
    Hkv, D] — this device's sequence chunk. all_to_all trades the head dim
    for the sequence dim so attention sees the full sequence; GQA K/V
    scatter with their Hkv heads and repeat AFTER the collective, so the
    wire carries 1/n_rep of the repeated volume (same economy as the ring's
    un-repeated chunks). The local full-sequence attention runs the pallas
    flash kernel (which itself falls back to einsum for shapes under one
    block) with the all-gathered [B, S] key-padding mask."""
    from ..models.common import repeat_kv
    from ..ops.flash_attention import flash_attention

    # [B, S/P, H, D] -> [B, S, H/P, D]: split heads (axis 2) across the axis,
    # concatenate sequence chunks (axis 1).
    def scatter_heads(x):
        return jax.lax.all_to_all(
            x, axis_name, split_axis=2, concat_axis=1, tiled=True
        )

    def gather_heads(x):
        return jax.lax.all_to_all(
            x, axis_name, split_axis=1, concat_axis=2, tiled=True
        )

    q_full = scatter_heads(q)
    k_full = repeat_kv(scatter_heads(k), n_rep)
    v_full = repeat_kv(scatter_heads(v), n_rep)
    if mask is not None:
        # the [B, S/P] mask chunk is tiny next to K/V: one all_gather
        # rebuilds the full [B, S] key mask every device needs
        mask = jax.lax.all_gather(mask, axis_name, axis=1, tiled=True)
    # after the head scatter the device holds the FULL sequence, so the
    # sliding-window band applies exactly as in single-device flash
    out = flash_attention(q_full, k_full, v_full, causal=causal, mask=mask,
                          window=window)
    return gather_heads(out)


def ulysses_attention(
    q: jax.Array,
    k: jax.Array,
    v: jax.Array,
    causal: bool = True,
    mask: jax.Array | None = None,
    mesh=None,
    axis_name: str = AXIS_SEQ,
    window: int | None = None,
) -> jax.Array:
    """[B, S, H, D] attention with S sharded over the mesh `seq` axis via
    head-scatter all-to-all. K/V may carry fewer (GQA) heads — when the kv
    head count divides the axis they scatter un-repeated (n_rep× less ICI
    traffic) and repeat locally after the collective; otherwise they repeat
    up-front to keep the all_to_all legal. `mask` is a [B, S] key-padding
    mask (1 = attend), sharded over the seq axis and all-gathered inside.
    `window` applies Mistral-style sliding-window attention (keys visible
    iff q - key < window) — the post-scatter attention sees the full
    sequence, so the band rides the flash kernel unchanged. Falls back to
    plain attention when no seq axis exists or shapes don't divide."""
    if window is not None and not causal:
        # same check as ring_attention, BEFORE any fallback: off-mesh and
        # on-mesh calls must fail identically for invalid arguments
        raise ValueError("ulysses_attention window requires causal=True "
                         "(Mistral sliding-window semantics)")
    if mesh is None:
        from ..state import PartialState

        if PartialState._shared_state:
            mesh = PartialState().mesh
    axis_size = mesh.shape.get(axis_name, 1) if mesh is not None else 1
    n_rep = q.shape[2] // k.shape[2]
    if n_rep > 1 and axis_size > 1 and k.shape[2] % axis_size != 0:
        # kv heads don't divide the axis: repeat first (legal, just heavier)
        from ..models.common import repeat_kv

        k = repeat_kv(k, n_rep)
        v = repeat_kv(v, n_rep)
        n_rep = 1
    if (
        mesh is None
        or axis_size == 1
        or q.shape[1] % axis_size != 0
        or k.shape[1] % axis_size != 0
        or q.shape[2] % axis_size != 0
        or k.shape[2] % axis_size != 0
    ):
        from ..models.common import dot_product_attention, repeat_kv

        return dot_product_attention(q, repeat_kv(k, n_rep),
                                     repeat_kv(v, n_rep), mask=mask,
                                     causal=causal, window=window)
    if mask is not None and mask.shape != (q.shape[0], k.shape[1]):
        raise ValueError(
            f"ulysses_attention mask must be a [B, S_k] key-padding mask; "
            f"got {mask.shape} for B={q.shape[0]}, S_k={k.shape[1]}"
        )

    seq_spec = P(None, axis_name, None, None)
    fn = partial(_ulysses_local, axis_name=axis_name, causal=causal,
                 n_rep=n_rep, window=window)
    if mask is not None:
        return jax.shard_map(
            fn, mesh=mesh,
            in_specs=(seq_spec, seq_spec, seq_spec, P(None, axis_name)),
            out_specs=seq_spec,
            check_vma=False,
        )(q, k, v, mask)
    return jax.shard_map(
        fn, mesh=mesh,
        in_specs=(seq_spec, seq_spec, seq_spec),
        out_specs=seq_spec,
        check_vma=False,
    )(q, k, v)
