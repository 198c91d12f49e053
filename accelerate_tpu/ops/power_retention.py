"""Power retention: attention whose past is ONE fixed-size state a sequence.

A query head scores a key by a power of their inner product, decayed by a
learned gate, and normalises by the sum of its scores (Buckman, Gelada,
Zhang et al., arXiv:2507.04239). With `Gam_t = sum_{u <= t} gamma_u` the
cumulated log-gates of a KV head, for `s <= t`:

    A[t, s] = exp(Gam_t - Gam_s) * (q_t . k_s) ** p
    o_t     = sum_s A[t, s] v_s / (sum_s A[t, s] + eps)

An even power is non-negative, so the sum normalises. Because `(a . b) ** p
= phi(a) . phi(b)` for the symmetric p-th power `phi`, the same numbers
come out of a linear recurrence over a state that does not grow:

    S_t = g_t S_{t-1} + phi(k_t) v_t^T      z_t = g_t z_{t-1} + phi(k_t)
    o_t = phi(q_t)^T S_t / (phi(q_t)^T z_t + eps)          g_t = exp(gamma_t)

`p` = 2 is what is served (`models/brumby.py`); `p` = 1 is kept as a
control (`phi` the identity).

THE LAYOUT OF `phi` (p = 2, `d` lanes a head, d even): `d / 2 + 1` ROWS of
d lanes, row `o` holding `c_o x_i x_{(i - o) mod d}` at lane i: the
products of every lane with the lane `o` places before it, which is
`x * roll(x, o)`: no gather, one lane rotation a row. `c_0 = 1` (the
squares), `c_o = sqrt(2)` for `0 < o < d / 2` (every unordered pair once),
`c_{d/2} = 1` (every pair `{i, i + d/2}` twice, at lane i and at lane i +
d/2). So `phi(a) . phi(b) = (a . b) ** 2` exactly, over `(d / 2 + 1) d`
entries: 8,320 for d = 128, 64 more than the `d (d + 1) / 2` = 8,256 the
mathematics needs (0.8%), every row a whole 128-lane tile.

THE POOL (`models.contract.StatePool`; `serving/cache.py` `StateCache` owns
it): `s [L, entries + 1, G, D, dv]` and `z [L, entries + 1, G, normaliser_rows(d, p),
d]`, `D = state_rows(d, p)`. ONE entry is one sequence's whole state in every
layer; the last entry is a SPARE that takes the writes of lanes that must
leave no trace. Both ops below take the whole pool and a layer index and
hand the whole pool back, updated in place where the caller donates it:

- `retention_decode_step`: one token a lane. On the chip a Pallas kernel
  (`retention_decode_step` in a device trace), grid lanes x KV heads x
  blocks of `D`: a block of `S` is read once, decayed, `phi(k) v^T`
  added, `phi(q)^T S` accumulated for the KV head's query heads, and the
  block written back to where it was read (`input_output_aliases`).
  Memory-bound: a lane reads and writes its whole state every step.
- `retention_chunk`: a chunk of rows of ONE sequence from its state:
  inside the chunk the masked `A` (two products a head), across chunks
  `exp(Gam_t) phi(Q) S_0`, and `S_C = exp(Gam_C) S_0 + sum_s exp(Gam_C -
  Gam_s) phi(k_s) v_s^T`. Padded rows carry `gamma = 0` and `k = v = 0`
  (the caller's duty), so they leave the state as it was.

Products take operands of the queries' dtype (bfloat16 as served; the CPU
tests hand float32) and accumulate in float32; log-gates are summed in
float32; the state stays in the pool's dtype (float32 as served).
"""

from __future__ import annotations

import dataclasses
import functools

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from ..models.common import part
from ..models.contract import StateMeta, StatePool
from . import kernel_mode

DECODE_KERNEL = "retention_decode_step"
CHUNK_KERNEL = "retention_chunk"

__all__ = ["StateMeta", "StatePool", "feature_rows", "normaliser_rows", "phi",
           "retention_chunk", "retention_decode_step",
           "retention_quadratic", "retention_recurrent", "state_rows"]


def feature_rows(d: int, degree: int = 2) -> int:
    """Rows of d lanes in `phi` of a d-lane vector (module docstring)."""
    if degree not in (1, 2):
        raise ValueError(f"power retention of degree 1 or 2; got {degree}")
    if degree == 2 and d % 2:
        raise ValueError(f"a head of an even number of lanes; got {d}")
    return d // 2 + 1 if degree == 2 else 1


def state_rows(d: int, degree: int = 2) -> int:
    """Entries of `phi` of a d-lane vector: the rows of a head's state."""
    return feature_rows(d, degree) * d


def normaliser_rows(d: int, degree: int = 2) -> int:
    """Rows of d lanes the normaliser `z` of a head is STORED in: `phi`'s
    rows, up to a whole number of 8-row tiles (rows past `feature_rows`
    are never read: an array whose rows are no whole tile makes the TPU's
    compiler choose a layout of its own for it and copy the pool around
    every op that wants the plain one)."""
    return -(-feature_rows(d, degree) // 8) * 8


def _coefficients(d: int, degree: int) -> np.ndarray:
    c = np.full((feature_rows(d, degree),), np.sqrt(2.0), np.float32)
    c[0] = c[-1] = 1.0
    return c


def phi(x: jax.Array, degree: int = 2) -> jax.Array:
    """The symmetric `degree`-th power of x [..., d], float32 [...,
    state_rows(d)], in the module's layout."""
    x = x.astype(jnp.float32)
    if degree == 1:
        return x
    d = x.shape[-1]
    return jnp.concatenate([
        c * x * jnp.roll(x, o, axis=-1)
        for o, c in enumerate(_coefficients(d, degree))], axis=-1)


@part("cache.view")
def _read_entries(pool, layer, entries):
    """[B, ...]: a pool array's entries `entries` [B] of layer `layer`, one
    slice each (a gather over the entry axis makes the TPU's compiler
    re-lay the whole array out)."""
    B = entries.shape[0]
    lead = (1, 1) + pool.shape[2:]
    return jnp.stack([jax.lax.dynamic_slice(
        pool, (layer, entries[b]) + (0,) * (pool.ndim - 2), lead)[0, 0]
        for b in range(B)])


@part("cache.write")
def _write_entries(pool, layer, entries, new):
    """`pool` with `new` [B, ...] at entries `entries` of layer `layer`,
    one in-place slice update each, in the pool's dtype."""
    for b in range(entries.shape[0]):
        pool = jax.lax.dynamic_update_slice(
            pool, new[b][None, None].astype(pool.dtype),
            (layer, entries[b]) + (0,) * (pool.ndim - 2))
    return pool


# ---------------------------------------------------------------------------
# the plain forms: what the tests hold the pool forms to
# ---------------------------------------------------------------------------


def retention_quadratic(q, k, v, gamma, *, degree: int = 2,
                        eps: float = 1e-6) -> jax.Array:
    """The `[positions, positions]` form, float32. q [T, G, Hg, d], k [T, G,
    d], v [T, G, dv], gamma [T, G] (log-gates) -> o [T, G, Hg, dv]."""
    q, k, v = (a.astype(jnp.float32) for a in (q, k, v))
    T = q.shape[0]
    gam = jnp.cumsum(gamma.astype(jnp.float32), axis=0)            # [T, G]
    sees = jnp.arange(T)[:, None] >= jnp.arange(T)[None, :]
    decay = jnp.exp(jnp.where(sees[None], gam.T[:, :, None]
                              - gam.T[:, None, :], -jnp.inf))   # [G, T, T]
    a = jnp.einsum("tghd,sgd->ghts", q, k) ** degree * decay[:, None]
    return (jnp.einsum("ghts,sgv->tghv", a, v)
            / (jnp.sum(a, axis=-1).transpose(2, 0, 1)[..., None] + eps))


def retention_recurrent(q, k, v, gamma, *, degree: int = 2,
                        eps: float = 1e-6):
    """The recurrence, one token at a time from a zero state, float32.
    Shapes as `retention_quadratic`; -> (o, S [G, D, dv], z [G, D])."""
    q, k, v = (a.astype(jnp.float32) for a in (q, k, v))
    G, d, dv = k.shape[1], k.shape[2], v.shape[2]
    D = state_rows(d, degree)

    def step(carry, xs):
        S, z = carry
        q_t, k_t, v_t, g_t = xs
        pk = phi(k_t, degree)                                       # [G, D]
        g = jnp.exp(g_t)
        S = g[:, None, None] * S + pk[:, :, None] * v_t[:, None, :]
        z = g[:, None] * z + pk
        pq = phi(q_t, degree)                                   # [G, Hg, D]
        o = (jnp.einsum("ghD,gDv->ghv", pq, S)
             / (jnp.einsum("ghD,gD->gh", pq, z)[..., None] + eps))
        return (S, z), o

    (S, z), o = jax.lax.scan(
        step, (jnp.zeros((G, D, dv), jnp.float32),
               jnp.zeros((G, D), jnp.float32)),
        (q, k, v, gamma.astype(jnp.float32)))
    return o, S, z


# ---------------------------------------------------------------------------
# one token a lane
# ---------------------------------------------------------------------------


def _rows_a_block(n_o: int, d: int, dv: int, itemsize: int,
                  at_most: int = 1 << 20) -> int:
    """Feature rows a grid step of the decode kernel holds: the largest
    divisor of `n_o` whose block of `S` stays within `at_most` bytes (in
    and out, double-buffered: four such blocks in VMEM)."""
    best = 1
    for r in range(1, n_o + 1):
        if n_o % r == 0 and r * d * dv * itemsize <= at_most:
            best = r
    return best


def _decode_kernel(ent_ref, live_ref, layer_ref, q_ref, kvg_ref, s_ref,
                   z_ref, s_out, z_out, o_ref, kcol, krol, qrol, num, den,
                   *, rows: int, degree: int, eps: float, mxu):
    """One grid step: lane b, KV head g, feature rows [j * rows, (j + 1) *
    rows). `q_ref` [Hp, d]: the KV head's query heads, padded to Hp rows;
    `kvg_ref` [8, d]: k, v and the decay `exp(gamma)` (all lanes alike) in
    rows 0, 1, 2. Scratch: `kcol` [d, d] = k down the sublanes (every lane
    alike), `krol` the same rotated by the row at hand, `qrol` [Hp, d] the
    queries rotated likewise, `num` [Hp, dv], `den` [Hp, d]."""
    b, j = pl.program_id(0), pl.program_id(2)
    d = kcol.shape[0]
    n_o = feature_rows(d, degree)
    coeff = _coefficients(d, degree)
    sq2 = float(np.sqrt(2.0))

    @pl.when(live_ref[b] != 0)
    def _live():
        q = q_ref[...]
        k_row, v_row, g_row = kvg_ref[0:1, :], kvg_ref[1:2, :], kvg_ref[2:3, :]

        @pl.when(j == 0)
        def _first():
            at = (jax.lax.broadcasted_iota(jnp.int32, (d, d), 0)
                  == jax.lax.broadcasted_iota(jnp.int32, (d, d), 1))
            col = jnp.sum(jnp.where(at, jnp.broadcast_to(k_row, (d, d)), 0.0),
                          axis=1, keepdims=True)
            kcol[...] = jnp.broadcast_to(col, (d, d))
            krol[...] = kcol[...]
            qrol[...] = q
            num[...] = jnp.zeros_like(num)
            # z whole, a row at a time with static rotations: it is small
            acc = jnp.zeros(den.shape, jnp.float32)
            k8 = jnp.broadcast_to(k_row, q.shape)
            for o in range(n_o):
                if degree == 2:
                    pk = coeff[o] * k_row * pltpu.roll(k8, o, 1)[0:1, :]
                    pq = coeff[o] * q * pltpu.roll(q, o, 1)
                else:
                    pk, pq = k_row, q
                new = g_row * z_ref[o:o + 1, :].astype(jnp.float32) + pk
                z_out[o:o + 1, :] = new.astype(z_out.dtype)
                acc = acc + pq.astype(mxu).astype(jnp.float32) * new
            den[...] = acc

        for r in range(rows):
            if degree == 2:
                o = j * rows + r
                c = jnp.where((o == 0) | (o == d // 2), 1.0, sq2)
                pk = (c * kcol[...]) * krol[...]                     # [d, d]
                pq = (c * q) * qrol[...]                            # [Hp, d]
            else:
                pk, pq = kcol[...], q
            at = slice(r * d, (r + 1) * d)
            new = g_row * s_ref[at, :].astype(jnp.float32) + pk * v_row
            s_out[at, :] = new.astype(s_out.dtype)
            num[...] += jnp.dot(pq.astype(mxu), new.astype(mxu),
                                preferred_element_type=jnp.float32)
            if degree == 2:
                krol[...] = pltpu.roll(krol[...], 1, 0)
                qrol[...] = pltpu.roll(qrol[...], 1, 1)

        @pl.when(j == pl.num_programs(2) - 1)
        def _last():
            total = jnp.sum(den[...], axis=1, keepdims=True)
            o_ref[...] = (num[...] / (total + eps)).astype(o_ref.dtype)

    @pl.when(live_ref[b] == 0)
    def _dead():
        o_ref[...] = jnp.zeros_like(o_ref)


def _decode_pallas(q, k, v, gamma, pool: StatePool, layer, meta: StateMeta,
                   degree: int, eps: float, interpret):
    B, H, d = q.shape
    G, dv = v.shape[1], v.shape[2]
    if dv != d:
        raise ValueError(
            f"the decode kernel carries k and v in one block: value heads "
            f"as wide as key heads; got {dv} and {d}")
    Hg = H // G
    Hp = -(-Hg // 8) * 8
    n_o = feature_rows(d, degree)
    rows = _rows_a_block(n_o, d, dv, pool.s.dtype.itemsize)
    f32 = jnp.float32
    qp = jnp.pad(q.reshape(B, G, Hg, d).astype(f32),
                 ((0, 0), (0, 0), (0, Hp - Hg), (0, 0)))
    decay = jnp.exp(gamma.astype(f32))
    kvg = jnp.stack([k.astype(f32), v.astype(f32),
                     jnp.broadcast_to(decay[..., None], (B, G, d))], axis=2)
    kvg = jnp.pad(kvg, ((0, 0), (0, 0), (0, 5), (0, 0)))        # [B, G, 8, d]
    live = (meta.rows > 0).astype(jnp.int32)
    entries = jnp.where(live > 0, meta.entries, pool.spare).astype(jnp.int32)

    # a dead lane's steps all name ONE block of the spare entry: the
    # pipeline neither fetches nor writes a block again whose index stays
    def state_block(b, g, j, ent, live, layer):
        return (layer[0], ent[b], g * live[b], j * live[b], 0)

    def z_block(b, g, j, ent, live, layer):
        return (layer[0], ent[b], g * live[b], 0, 0)

    per_head = lambda b, g, j, *_: (b, g, 0, 0)  # noqa: E731
    s_spec = pl.BlockSpec((None, None, None, rows * d, dv), state_block)
    z_spec = pl.BlockSpec((None, None, None, pool.z.shape[3], d), z_block)
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=3,
        grid=(B, G, n_o // rows),
        in_specs=[pl.BlockSpec((None, None, Hp, d), per_head),
                  pl.BlockSpec((None, None, 8, d), per_head),
                  s_spec, z_spec],
        out_specs=[s_spec, z_spec,
                   pl.BlockSpec((None, None, Hp, dv), per_head)],
        scratch_shapes=[pltpu.VMEM((d, d), f32), pltpu.VMEM((d, d), f32),
                        pltpu.VMEM((Hp, d), f32), pltpu.VMEM((Hp, dv), f32),
                        pltpu.VMEM((Hp, d), f32)],
    )
    s, z, o = pl.pallas_call(
        functools.partial(_decode_kernel, rows=rows, degree=degree,
                          eps=float(eps), mxu=q.dtype),
        out_shape=[jax.ShapeDtypeStruct(pool.s.shape, pool.s.dtype),
                   jax.ShapeDtypeStruct(pool.z.shape, pool.z.dtype),
                   jax.ShapeDtypeStruct((B, G, Hp, dv), f32)],
        grid_spec=grid_spec,
        # operands count the scalar-prefetch arguments: s is the 6th
        input_output_aliases={5: 0, 6: 1},
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary", "arbitrary", "arbitrary")),
        name=DECODE_KERNEL,
        interpret=interpret,
    )(entries, live, jnp.asarray(layer, jnp.int32).reshape(1), qp, kvg,
      pool.s, pool.z)
    return (o[:, :, :Hg].reshape(B, H, dv),
            dataclasses.replace(pool, s=s, z=z))


def _decode_dense(q, k, v, gamma, pool: StatePool, layer, meta: StateMeta,
                  degree: int, eps: float):
    """`retention_decode_step` by a gather, the recurrence's one step in
    `jax.numpy` and a scatter: the path off the chip."""
    B, H, d = q.shape
    G = k.shape[1]
    f32 = jnp.float32
    live = meta.rows > 0
    entries = jnp.where(live, meta.entries, pool.spare)
    S = _read_entries(pool.s, layer, entries).astype(f32)      # [B, G, D, dv]
    n_o = feature_rows(d, degree)
    z_all = _read_entries(pool.z, layer, entries).astype(f32)
    z = z_all[:, :, :n_o].reshape(B, G, -1)
    g = jnp.exp(gamma.astype(f32))
    pk = phi(k, degree)                                            # [B, G, D]
    S = g[..., None, None] * S + pk[..., None] * v.astype(f32)[:, :, None, :]
    z = g[..., None] * z + pk
    pq = phi(q.reshape(B, G, H // G, d), degree).astype(q.dtype).astype(f32)
    num = jnp.einsum("bghD,bgDv->bghv", pq, S.astype(q.dtype).astype(f32))
    den = jnp.einsum("bghD,bgD->bgh", pq, z)
    o = jnp.where(live[:, None, None, None], num / (den[..., None] + eps), 0.0)
    # (dead lanes all name the spare: what lands there is never read)
    return (o.reshape(B, H, -1), dataclasses.replace(
        pool, s=_write_entries(pool.s, layer, entries, S),
        z=_write_entries(pool.z, layer, entries, z_all.at[:, :, :n_o].set(
            z.reshape(B, G, n_o, d)))))


def retention_decode_step(q, k, v, gamma, pool: StatePool, layer,
                          meta: StateMeta, *, degree: int = 2,
                          eps: float = 1e-6, interpret: bool | None = None):
    """One token of every lane through its state. q [B, H, d] (H = G x Hg,
    a KV head's query heads adjacent), k [B, G, d], v [B, G, dv], gamma [B,
    G] float32 log-gates, `pool` and `layer` (int32 scalar) as in the
    module docstring, `meta.entries` / `meta.rows` [B]. A lane with
    `rows` 0 is dead: its entry is not read, not written, and its output is
    zero. -> (o [B, H, dv] float32, the pool with layer `layer`'s states
    of the live lanes advanced by their token)."""
    if not pool.kernel:
        return _decode_dense(q, k, v, gamma, pool, layer, meta, degree, eps)
    interpret = kernel_mode.resolve_interpret(DECODE_KERNEL, interpret)
    return _decode_pallas(q, k, v, gamma, pool, layer, meta, degree, eps,
                          interpret)


# ---------------------------------------------------------------------------
# a chunk of rows a sequence
# ---------------------------------------------------------------------------


def _diagonals(d: int, degree: int):
    """(row, column) [n_o, d] of a d x d matrix that row `o` of `phi`'s
    layout reads: lane i of row o pairs lane i with lane (i - o) mod d."""
    o = np.arange(feature_rows(d, degree))[:, None]
    i = np.arange(d)[None, :]
    return np.broadcast_to(i, (o.shape[0], d)), (i - o) % d


def _normaliser(q, k, w, z0, degree: int):
    """What a chunk does with the normaliser's state WITHOUT `phi`: (each
    query's `phi(q) . z0` [B, G, Hg, C], the chunk's `sum_s w_s phi(k_s)`
    in z's layout [B, G, n_o, d]). q [B, C, G, Hg, d], k [B, C, G, d], w
    [B, C, G] float32, z0 [B, G, n_o, d] float32. For p = 2 both are
    quadratic forms over d x d matrices: `phi(q) . z = q^T Z q` with z's
    rows laid along Z's diagonals, and `sum_s w_s phi(k_s)` is the
    diagonals of the weighted Gram matrix `sum_s w_s k_s k_s^T`: two small
    float32 products a head in place of 8,320-wide ones."""
    f32, exact = jnp.float32, jax.lax.Precision.HIGHEST
    q, k = q.astype(f32), k.astype(f32)
    if degree == 1:
        return (jnp.einsum("bcghd,bgd->bghc", q, z0[:, :, 0], precision=exact),
                jnp.einsum("bcgd,bcg->bgd", k, w, precision=exact)[:, :, None])
    d = q.shape[-1]
    row, col = _diagonals(d, degree)
    c = _coefficients(d, degree)[:, None]
    B, G = z0.shape[:2]
    zm = jnp.zeros((B, G, d, d), f32).at[:, :, row, col].add(c * z0)
    den = jnp.einsum("bcghe,bcghe->bghc", jnp.einsum(
        "bcghd,bgde->bcghe", q, zm, precision=exact), q)
    gram = jnp.einsum("bcgd,bcge->bgde", k * w[..., None], k, precision=exact)
    return den, c * gram[:, :, row, col]


def _chunk_kernel(ent_ref, layer_ref, q_ref, kt_ref, wv_ref, decay_ref, s_ref,
                  s_out, num_ref, tile, *, rows: int, heads: int,
                  degree: int, mxu):
    """One grid step: one sequence's KV head. `q_ref` [Hg * C, d] (a query
    head's C rows adjacent), `kt_ref` [d, C] float32 (k transposed: `phi`
    down the sublanes, as the state's rows lie), `wv_ref` [C, dv] (`v`
    weighted by what is left of each row at the chunk's end), `decay_ref`
    [8, dv] (the chunk's whole decay, all alike), `s_ref` / `s_out` [D,
    dv] the head's state before and after, `num_ref` [Hg * C, dv] float32
    (`phi(Q) S_0`), `tile` [C, rows * d] scratch."""
    del ent_ref, layer_ref      # the block specs' own
    d, C = kt_ref.shape
    n_o = feature_rows(d, degree)
    coeff = _coefficients(d, degree)
    kt = kt_ref[...]
    decay = decay_ref[0:1, :]
    for o in range(n_o):                   # the state after the chunk
        pk = coeff[o] * kt * pltpu.roll(kt, o, 0) if degree == 2 else kt
        at = slice(o * d, (o + 1) * d)
        s_out[at, :] = (
            decay * s_ref[at, :].astype(jnp.float32) + jnp.dot(
                pk.astype(mxu), wv_ref[...],
                preferred_element_type=jnp.float32)).astype(s_out.dtype)
    num_ref[...] = jnp.zeros_like(num_ref)
    for first in range(0, n_o, rows):      # what the state held, read
        def head(h, carry, first=first):
            at = pl.ds(pl.multiple_of(h * C, C), C)
            q = q_ref[at, :].astype(jnp.float32)
            for r in range(rows):
                o = first + r
                pq = coeff[o] * q * pltpu.roll(q, o, 1) if degree == 2 else q
                tile[:, r * d:(r + 1) * d] = pq.astype(mxu)
            num_ref[at, :] += jnp.dot(
                tile[...], s_ref[first * d:(first + rows) * d, :].astype(mxu),
                preferred_element_type=jnp.float32)
            return carry

        jax.lax.fori_loop(0, heads, head, 0)


def _chunk_pallas(q, kt, wv, decay, pool: StatePool, layer, entries,
                  degree: int, interpret):
    """`phi(Q) S_0` [B, G, Hg * C, dv] float32 and the pool's `s` with the
    entries' states of layer `layer` advanced, in place. q [B, G, Hg * C,
    d], kt [B, G, d, C] float32, wv [B, G, C, dv], decay [B, G]."""
    B, G, HC, d = q.shape
    C, dv = wv.shape[2], wv.shape[3]
    n_o = feature_rows(d, degree)
    # (feature rows a product: the width of `tile`, whatever the state's dtype)
    rows = _rows_a_block(n_o, d, dv, 4)
    D = n_o * d
    decay = jnp.broadcast_to(decay.astype(jnp.float32)[:, :, None, None],
                             (B, G, 8, dv))
    per_head = lambda b, g, *_: (b, g, 0, 0)  # noqa: E731
    s_spec = pl.BlockSpec((None, None, None, D, dv),
                          lambda b, g, ent, layer: (layer[0], ent[b], g, 0, 0))
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,
        grid=(B, G),
        in_specs=[pl.BlockSpec((None, None, HC, d), per_head),
                  pl.BlockSpec((None, None, d, C), per_head),
                  pl.BlockSpec((None, None, C, dv), per_head),
                  pl.BlockSpec((None, None, 8, dv), per_head),
                  s_spec],
        out_specs=[s_spec, pl.BlockSpec((None, None, HC, dv), per_head)],
        scratch_shapes=[pltpu.VMEM((C, rows * d), q.dtype)],
    )
    return pl.pallas_call(
        functools.partial(_chunk_kernel, rows=rows, heads=HC // C,
                          degree=degree, mxu=q.dtype),
        out_shape=[jax.ShapeDtypeStruct(pool.s.shape, pool.s.dtype),
                   jax.ShapeDtypeStruct((B, G, HC, dv), jnp.float32)],
        grid_spec=grid_spec,
        input_output_aliases={6: 0},       # s, after the two scalar operands
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary", "arbitrary"),
            # a head's whole state in and out, double-buffered
            vmem_limit_bytes=64 << 20),
        name=CHUNK_KERNEL,
        interpret=interpret,
    )(entries.astype(jnp.int32), jnp.asarray(layer, jnp.int32).reshape(1),
      q, kt, wv, decay, pool.s)


def retention_chunk(q, k, v, gamma, pool: StatePool, layer, entries, *,
                    degree: int = 2, eps: float = 1e-6,
                    interpret: bool | None = None):
    """A chunk of C rows of every sequence of the batch, each from its
    state at `entries` [B] (int32, all different) of layer `layer`. q [B,
    C, H, d], k [B, C, G, d], v [B, C, G, dv], gamma [B, C, G] float32
    log-gates; a padded row carries `gamma` 0 and `k` = `v` = 0. -> (o [B,
    C, H, dv] float32, the pool with those states advanced by their
    chunk's rows). On the chip the two products that are as wide as the
    state run in a Pallas kernel (`retention_chunk` in a device trace) that
    makes `phi` a block at a time in VMEM and rewrites each head's state
    where it lies; everything else here is small and XLA's."""
    B, C, H, d = q.shape
    G, dv = k.shape[2], v.shape[3]
    Hg = H // G
    f32, mxu = jnp.float32, q.dtype
    gam = jnp.cumsum(gamma.astype(f32), axis=1)                  # [B, C, G]
    q5 = q.reshape(B, C, G, Hg, d)
    # inside the chunk: the masked A, two products a head
    sc = jnp.einsum("btghd,bsgd->bghts", q5, k.astype(mxu),
                    preferred_element_type=f32)
    sees = jnp.arange(C)[:, None] >= jnp.arange(C)[None, :]
    gt = jnp.moveaxis(gam, 1, 2)                                 # [B, G, C]
    decay = jnp.exp(jnp.where(sees, gt[..., :, None] - gt[..., None, :],
                              -jnp.inf))                      # [B, G, C, C]
    a = ((sc * sc if degree == 2 else sc) * decay[:, :, None]).astype(mxu)
    num = jnp.einsum("bghts,bsgv->bghtv", a, v.astype(mxu),
                     preferred_element_type=f32)
    den = jnp.sum(a.astype(f32), axis=-1)                    # [B, G, Hg, C]
    # across chunks: what the states hold of the rows before this chunk
    w = jnp.exp(gam[:, -1:] - gam)                               # [B, C, G]
    whole = jnp.exp(gam[:, -1])                                     # [B, G]
    n_o = feature_rows(d, degree)
    z_all = _read_entries(pool.z, layer, entries).astype(f32)
    z0 = z_all[:, :, :n_o]                                  # [B, G, n_o, d]
    den0, z_inc = _normaliser(q5, k, w, z0, degree)
    wv = (v.astype(f32) * w[..., None]).astype(mxu)
    if pool.kernel:
        interpret = kernel_mode.resolve_interpret(CHUNK_KERNEL, interpret)
        s, num0 = _chunk_pallas(
            jnp.moveaxis(q5, 1, 3).reshape(B, G, Hg * C, d),
            jnp.moveaxis(k.astype(f32), 1, 3), jnp.moveaxis(wv, 1, 2), whole,
            pool, layer, entries, degree, interpret)
        num0 = num0.reshape(B, G, Hg, C, dv)
    else:
        s0 = _read_entries(pool.s, layer, entries).astype(f32)
        num0 = jnp.einsum("bcghD,bgDv->bghcv", phi(q5, degree).astype(mxu),
                          s0.astype(mxu), preferred_element_type=f32)
        s_new = whole[..., None, None] * s0 + jnp.einsum(
            "bcgD,bcgv->bgDv", phi(k.astype(mxu), degree).astype(mxu), wv,
            preferred_element_type=f32)
        s = _write_entries(pool.s, layer, entries, s_new)
    into = jnp.exp(gt)[:, :, None, :]                         # [B, G, 1, C]
    o = ((num + into[..., None] * num0)
         / ((den + into * den0)[..., None] + eps))         # [B, G, Hg, C, dv]
    z = _write_entries(pool.z, layer, entries, z_all.at[:, :, :n_o].set(
        whole[..., None, None] * z0 + z_inc))
    return (jnp.moveaxis(o, 3, 1).reshape(B, C, H, dv),
            dataclasses.replace(pool, s=s, z=z))
