"""Pallas paged decode attention over a LATENT pool (multi-head latent
attention, absorbed form): 32 query heads over ONE shared key row a token.

What a latent cache holds for a token in a layer is one row `[c_kv | k_pe
| 0]`: the normed compressed KV (`kv_rank` lanes), the rotated shared
rope key (`rope` lanes), zero padding up to a whole number of 128-lane
tiles. In the absorbed form every query head carries `q_h = [q_nope_h
W_UK_h | q_pe_h | 0]` of the same width, so a head's score against a
cached token is ONE dot product with that row, and the value it sums is
the row's first `kv_rank` lanes: keys and values are the same bytes, read
once for all heads. The caller multiplies the result by `W_UV` afterwards.

The kernel takes the WHOLE stacked pool `[L, pages + 1, page_size,
width]` where it lies in HBM (`memory_space=pl.ANY`) and a layer index:
nothing slices a layer out of the pool around the call (the K/V kernel of
`ops/paged_attention.py` has the same form since PR 27; the two share no
page walker, by decision: each cell's programs stay their own). One grid
step is one slot. Inside it a loop with
a DYNAMIC trip count walks the slot's live pages in groups of
`pages_per_group`: each group's pages are copied page by page into one of
two VMEM buffers while the other is computed on, so the work follows the
live length, not the page table's capacity. The new token's row (position
== length) is folded as a last single-key update and handed back for the
engine to append; the kernel never writes the pool.

Masking is `models/decode.cached_attention_mask`'s: the query at position
`length` sees pool rows `< length` and its own row. Retired slots compute
garbage that the engine's `live` mask discards.

RING mode (`window=W`; trace name `WINDOW_KERNEL_NAME`): the pool is a ring
of pages a slot (`serving/cache.py`, RING mode: the page of positions [p *
page_size, (p + 1) * page_size) is table entry `p % pages_per_slot`) and
the query sees the last `W - 1` cached positions and itself. The walk
starts at the page of position `length + 1 - W` and ends at the page of
`length - 1`: at most `ceil((W - 1) / page_size) + 1` pages a slot
whatever the length, masked by POSITION at both ends. A slot given length
0 (the engine's dead lanes) walks no page.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from . import kernel_mode

NEG_INF = -1e30
KERNEL_NAME = "latent_paged_decode_attention"
WINDOW_KERNEL_NAME = "latent_paged_decode_attention_window"

__all__ = ["latent_paged_decode_attention", "latent_paged_decode_reference"]


def _kernel(table_ref, lengths_ref, layer_ref, q_ref, new_ref, pool_ref,
            o_ref, buf, sem, *, sm_scale: float, page_size: int,
            pages_per_slot: int, pages_per_group: int, value_width: int,
            window: int | None = None):
    s = pl.program_id(0)
    length = lengths_ref[s]
    layer = layer_ref[0]
    G, ps, P = pages_per_group, page_size, pages_per_slot
    rows = G * ps
    if window is None:
        n_groups = (length + rows - 1) // rows
    else:
        # the first cached position the query sees, its page, and the
        # page of the last cached position
        seen_from = jnp.maximum(length + 1 - window, 0)
        first_page = seen_from // ps
        last_page = jnp.maximum(length - 1, 0) // ps
        n_groups = jnp.where(length > 0,
                             (last_page - first_page + G) // G, 0)

    def each_copy(g, slot, do):
        """`do` on the copy of every page of group `g` into buffer `slot`."""
        for j in range(G):
            if window is None:
                # entries past the table's end re-read its last page: masked
                entry = jnp.minimum(g * G + j, P - 1)
            else:
                # pages past the last written one re-read it: masked
                entry = jnp.minimum(first_page + g * G + j, last_page) % P
            page = table_ref[s * P + entry]
            do(pltpu.make_async_copy(
                pool_ref.at[layer, page],
                buf.at[slot, pl.ds(j * ps, ps)], sem.at[slot]))

    def start(g, slot):
        each_copy(g, slot, lambda copy: copy.start())

    @pl.when(n_groups > 0)
    def _first():
        start(0, 0)

    q = q_ref[0]                                            # [H, W]
    H = q.shape[0]

    def fold(carry, s_blk, pv):
        """One online-softmax update with scaled, masked scores [H, n]."""
        m, l, acc = carry
        m_new = jnp.maximum(m, jnp.max(s_blk, axis=-1, keepdims=True))
        p = jnp.where(s_blk <= NEG_INF / 2, 0.0, jnp.exp(s_blk - m_new))
        alpha = jnp.exp(m - m_new)
        return (m_new, l * alpha + jnp.sum(p, axis=-1, keepdims=True),
                acc * alpha + pv(p))

    def body(g, carry):
        slot = g % 2

        @pl.when(g + 1 < n_groups)
        def _next():
            start(g + 1, 1 - slot)

        each_copy(g, slot, lambda copy: copy.wait())
        kv = buf[slot]                                      # [rows, W]
        s_blk = jax.lax.dot_general(
            q, kv, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32) * sm_scale
        pos = g * rows + jax.lax.broadcasted_iota(jnp.int32, (1, rows), 1)
        if window is None:
            see = pos < length
        else:
            pos = pos + first_page * ps
            see = (pos < length) & (pos >= seen_from)
        s_blk = jnp.where(see, s_blk, NEG_INF)
        return fold(carry, s_blk, lambda p: jnp.dot(
            p.astype(kv.dtype), kv[:, :value_width],
            preferred_element_type=jnp.float32))

    carry = (jnp.full((H, 1), NEG_INF, jnp.float32),
             jnp.zeros((H, 1), jnp.float32),
             jnp.zeros((H, value_width), jnp.float32))
    carry = jax.lax.fori_loop(0, n_groups, body, carry)
    # the new token's own row: one more key, always visible; on the VPU
    new = new_ref[0].astype(jnp.float32)                    # [1, W]
    s_new = jnp.sum(q.astype(jnp.float32) * new, axis=-1,
                    keepdims=True) * sm_scale
    _, l, acc = fold(carry, s_new, lambda p: p * new[:, :value_width])
    o_ref[0] = (acc / l).astype(o_ref.dtype)


def latent_paged_decode_attention(q, new_row, pool, layer, table, lengths, *,
                                  value_width: int, sm_scale: float,
                                  pages_per_group: int = 32,
                                  interpret: bool | None = None,
                                  window: int | None = None):
    """One decode step of absorbed latent attention for every slot.

    q [S, H, W]: each head's absorbed query, laid out like a pool row;
    new_row [S, W]: this step's latent row (folded in, and what the
    engine appends afterwards; in the pool's dtype); pool [L, pages + 1,
    page_size, W]; layer: int32 scalar; table [S, pages_per_slot] int32;
    lengths [S] int32. Returns o_lat [S, H, value_width] in q's dtype:
    the softmax-weighted sum of the rows' first `value_width` lanes.
    `window`: the table is a ring and the query sees the last `window - 1`
    cached positions and itself (RING mode, the head of this file)."""
    S, H, W = q.shape
    L, _, ps, Wp = pool.shape
    if Wp != W or new_row.shape != (S, W):
        raise ValueError(f"row widths differ: q {q.shape}, new_row "
                         f"{new_row.shape}, pool {pool.shape}")
    if W % 128 or value_width % 128 or value_width > W:
        raise ValueError(
            f"the latent row ({W}) and its value part ({value_width}) must "
            "be whole 128-lane tiles (pad the row with zeros)")
    P = table.shape[1]
    name = KERNEL_NAME
    if window is not None:
        name = WINDOW_KERNEL_NAME
        # two groups cover the most pages a window's cached rows span
        # (they may start anywhere in a page): the second is copied while
        # the first is computed on
        spanned = -(-(window - 1) // ps) + 1
        pages_per_group = -(-spanned // 2)
    G = max(1, min(pages_per_group, P))
    interpret = kernel_mode.resolve_interpret(name, interpret)
    kernel = functools.partial(
        _kernel, sm_scale=float(sm_scale), page_size=ps, pages_per_slot=P,
        pages_per_group=G, value_width=value_width, window=window)
    per_slot = lambda s, *_: (s, 0, 0)  # noqa: E731
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=3,
        grid=(S,),
        in_specs=[pl.BlockSpec((1, H, W), per_slot),
                  pl.BlockSpec((1, 1, W), per_slot),
                  pl.BlockSpec(memory_space=pl.ANY)],
        out_specs=pl.BlockSpec((1, H, value_width), per_slot),
        scratch_shapes=[pltpu.VMEM((2, G * ps, W), pool.dtype),
                        pltpu.SemaphoreType.DMA((2,))],
    )
    return pl.pallas_call(
        kernel,
        out_shape=jax.ShapeDtypeStruct((S, H, value_width), q.dtype),
        grid_spec=grid_spec,
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary",)),
        name=name,
        interpret=pltpu.InterpretParams() if interpret else False,
    )(table.reshape(-1).astype(jnp.int32), lengths.astype(jnp.int32),
      jnp.asarray(layer, jnp.int32).reshape(1), q.astype(pool.dtype),
      new_row[:, None, :], pool)


def latent_paged_decode_reference(q, new_row, pool, layer, table, lengths, *,
                                  value_width: int, sm_scale: float,
                                  window: int | None = None):
    """The same semantics by a dense gather and a plain float32 softmax:
    the executable specification the kernel's tests hold it to."""
    S, H, W = q.shape
    ps = pool.shape[2]
    R = table.shape[1] * ps
    rows = pool[layer][table].reshape(S, R, W).astype(jnp.float32)
    at = jnp.arange(R, dtype=jnp.int32)[None, :]
    if window is None:
        see = at < lengths[:, None]
    else:
        # row r of a ring holds the newest written position that is r
        # modulo R; a negative one was never written
        last = lengths[:, None] - 1
        at = last - (last - at) % R
        see = (at >= 0) & (at >= lengths[:, None] + 1 - window)
    # the new token's own row: one more key, always visible
    rows = jnp.concatenate(
        [rows, new_row.astype(jnp.float32)[:, None, :]], axis=1)
    see = jnp.concatenate([see, jnp.ones((S, 1), bool)], axis=1)
    s = jnp.einsum("shw,srw->shr", q.astype(pool.dtype).astype(jnp.float32),
                   rows) * sm_scale
    s = jnp.where(see[:, None, :], s, NEG_INF)
    p = jax.nn.softmax(s, axis=-1)
    return jnp.einsum("shr,srw->shw", p,
                      rows[:, :, :value_width]).astype(q.dtype)
