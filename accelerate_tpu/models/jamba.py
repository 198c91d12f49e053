"""A decoder whose layers are of two KINDS that keep different things of a
sequence: state-space layers (Mamba-1: Gu & Dao, arXiv:2312.00752), which
keep one recurrent STATE and a short convolution window whatever the
sequence's length, and a few attention layers, which keep K/V rows.
AI21-Jamba2-3B (`model_type` `jamba`) is this stack, dense, and is what the
benchmark serves (`chipbench/configs/jamba2-3b.json`).

Every layer (x of width h; pre-norm, plain residuals): `x <- x +
mixer(RMSNorm_in(x))`, then `x <- x + SwiGLU(RMSNorm_ff(x))`. Layer i is an
ATTENTION layer iff `i % attn_layer_period == attn_layer_offset`, else a
MAMBA layer. Final RMSNorm; the head is the embedding
(`tie_word_embeddings`).

- MAMBA mixer (`d = mamba_expand * h` channels, `n = mamba_d_state`, `taps =
  mamba_d_conv`, `r = mamba_dt_rank`), `y = RMSNorm_in(x)`: `[u; z] = y
  W_in`; `c_t = SiLU(b_c + sum_j w_c[j] * u_{t - taps + 1 + j})` (depthwise,
  causal; before the sequence's start `u` is zero); `[rt; B; C] = c_t W_x`
  (r + n + n); each of the three through an RMSNorm of its own (the jamba
  family's inner norms); `dt = softplus(rt W_dt + b_dt)`; `A = -exp(A_log)`;
  `S_t = exp(dt_t (x) A) * S_{t-1} + (dt_t * c_t) (x) B_t`, float32; `out =
  ((S_t C_t + D * c_t) * SiLU(z_t)) W_out`. The scan and the window are
  `ops/selective_scan.py`'s, where the layout stands (`A_log` is stored `[n,
  d]`, as the state lies).
- ATTENTION mixer: `q = y W_q` (H heads of h / H lanes), `k = y W_k`, `v = y
  W_v` (`num_key_value_heads` heads), no biases, NO rotation and no position
  term of any kind (the family has none), causal softmax of `q k^T /
  sqrt(D)` times v, through `W_o`.

What the cache holds: `cache_spec` declares a group of K/V PAGES for the
attention layers first (it grows with the context; the serving engine's
allocator means it) and a group of state ENTRIES for the Mamba layers
(`CacheSpec(kind="state")`: one block of `n` rows and one of `taps - 1`, `d`
lanes, float32: the state and the window). `forward` reads `kv_caches = (k, v,
third)` with k and v one entry a GROUP, in that order: the attention
layers' views, layerwise views or `PagedKV` pools first, and LAST the state
group, `models.contract.StatePool` in K's place and `StateMeta` in V's (who
the lanes are and how many of their rows are real). The pool comes back in
the same place, advanced. A call of one token a row advances each live
row's state (`ssm_decode_step`), a call of more folds each row's leading
`meta.rows` rows (`ssm_chunk_scan`); a chunk's padding gets `dt = 0` and
leaves state and window as they were.

`use_inner_norms` False and `conv_taps_skipped` 1 exist for the benchmark's
controls (a model that is NOT this one must fail the cell's check).

`SERVING`, at the foot: docs/serving.md, "What a served family declares".
"""

from __future__ import annotations

import dataclasses
from typing import Any

import jax
import jax.numpy as jnp
import numpy as np

from ..ops.selective_scan import (
    conv_chunk,
    conv_step,
    ssm_chunk_scan,
    ssm_decode_step,
)
from .common import (
    add_wide,
    blocked_attention,
    dense,
    normal_init,
    part,
    rms_norm,
    write_view,
)
from .contract import CacheSpec, ServingContract, StateMeta, StatePool
from .decode import build_generate, layer_view


@dataclasses.dataclass(frozen=True)
class JambaConfig:
    vocab_size: int = 65536
    hidden_size: int = 2560
    intermediate_size: int = 8192
    num_hidden_layers: int = 28
    num_attention_heads: int = 20
    num_key_value_heads: int = 1
    attn_layer_period: int = 14
    attn_layer_offset: int = 7
    expert_layer_period: int = 2
    expert_layer_offset: int = 1
    num_experts: int = 1
    num_experts_per_tok: int = 1
    mamba_d_state: int = 16
    mamba_d_conv: int = 4
    mamba_expand: int = 2
    mamba_dt_rank: int = 160
    mamba_conv_bias: bool = True
    mamba_proj_bias: bool = False
    max_position_embeddings: int = 262144
    rms_norm_eps: float = 1e-6
    hidden_act: str = "silu"
    sliding_window: Any = None
    tie_word_embeddings: bool = True
    # the attention layers' online softmax walks a view this many rows at
    # a time
    kv_block: int = 512
    # ASSUMED, see the configuration's file: float32 state and window
    state_dtype: Any = "float32"
    # the benchmark's controls: a model that is not this one
    use_inner_norms: bool = True
    conv_taps_skipped: int = 0

    def __post_init__(self):
        if self.num_experts != 1:
            raise ValueError(
                "an expert block inside a stack of state-space and attention "
                f"layers is not implemented (num_experts={self.num_experts}): "
                "every layer's feed-forward is the dense SwiGLU")
        if self.sliding_window is not None:
            raise ValueError(
                "a sliding window on the attention layers is not "
                f"implemented (sliding_window={self.sliding_window!r})")
        if (self.mamba_proj_bias or not self.mamba_conv_bias
                or not self.tie_word_embeddings or self.hidden_act != "silu"):
            raise ValueError(
                "only mamba_proj_bias=False, mamba_conv_bias=True, "
                "tie_word_embeddings=True and hidden_act='silu' are "
                "implemented")
        if self.hidden_size % self.num_attention_heads or (
                self.num_attention_heads % self.num_key_value_heads):
            raise ValueError(
                "heads of hidden_size / num_attention_heads lanes, a whole "
                "number of query heads a KV head")
        if self.mamba_d_conv < 2:
            raise ValueError(
                "a convolution of 2 taps or more (its window is the taps "
                f"before the last); got {self.mamba_d_conv}")
        if not self.attention_layers or not self.mamba_layers:
            raise ValueError(
                "attn_layer_period / attn_layer_offset leave no layer of one "
                "kind: this family is the two kinds together")
        object.__setattr__(self, "state_dtype",
                           jnp.dtype(self.state_dtype).name)

    @property
    def head_dim(self) -> int:
        return self.hidden_size // self.num_attention_heads

    @property
    def d_inner(self) -> int:
        return self.mamba_expand * self.hidden_size

    @property
    def attention_layers(self) -> tuple:
        """The layers that attend, by the rule of the family that
        introduced the two keys."""
        return tuple(i for i in range(self.num_hidden_layers)
                     if i % self.attn_layer_period == self.attn_layer_offset)

    @property
    def mamba_layers(self) -> tuple:
        return tuple(i for i in range(self.num_hidden_layers)
                     if i not in self.attention_layers)

    @classmethod
    def tiny(cls, **overrides) -> "JambaConfig":
        """Test size: one 128-wide KV head (the live-pages kernel's width)
        and 512 channels (whole 128-lane tiles), layers 1 and 3 attending."""
        defaults = dict(
            vocab_size=256, hidden_size=256, intermediate_size=256,
            num_hidden_layers=4, num_attention_heads=2,
            num_key_value_heads=1, attn_layer_period=2, attn_layer_offset=1,
            mamba_dt_rank=16, max_position_embeddings=512, kv_block=64)
        defaults.update(overrides)
        return cls(**defaults)


def cache_spec(config: JambaConfig):
    """The attention layers' K/V pages FIRST (the group that grows with the
    context), then the Mamba layers' entries: a state of `n` x d and a
    window of `taps - 1` x d a layer (the entries in its sublanes: the
    convolution is XLA's, over all lanes at once), float32 as served."""
    c = config
    return (
        CacheSpec(len(c.attention_layers), c.num_key_value_heads, c.head_dim,
                  layers=c.attention_layers),
        CacheSpec(len(c.mamba_layers), 1, c.d_inner, kind="state",
                  layers=c.mamba_layers, state_rows=c.mamba_d_state,
                  aux_rows=c.mamba_d_conv - 1, aux_entry_minor=True,
                  state_dtype=jnp.dtype(c.state_dtype)))


def dt_bias_init(channels: int) -> np.ndarray:
    """The step sizes' biases where no trained ones exist: such that
    `softplus(b)` is spread log-uniformly from 1e-3 to 1e-1 over the
    channels (the Mamba-1 initialisation's range): with `A = -(1 .. n)` a
    channel forgets within some 10 to some 1,000 tokens, short and long
    memories side by side."""
    dt = np.exp(np.linspace(np.log(1e-3), np.log(1e-1), channels))
    return (dt + np.log(-np.expm1(-dt))).astype(np.float32)


def init_params(config: JambaConfig, key: jax.Array,
                dtype=jnp.float32) -> dict:
    """Seeded weights: normal(0, 0.02) matrices, convolution taps uniform
    in (-1/2, 1/2) (fan-in 4), `A_log = log(1 .. n)` a channel, `D = 1`,
    `dt_bias_init`, every norm's scale 1. `A_log`, `D` and the step bias
    stay float32."""
    c = config
    h, f, d, n, r = (c.hidden_size, c.intermediate_size, c.d_inner,
                     c.mamba_d_state, c.mamba_dt_rank)
    H, G, D = c.num_attention_heads, c.num_key_value_heads, c.head_dim

    def mat(k, *shape):
        return {"kernel": normal_init(k, shape, 0.02, dtype)}

    def one(m):
        return {"scale": jnp.ones((m,), dtype)}

    layers = []
    for i in range(c.num_hidden_layers):
        k = jax.random.split(jax.random.fold_in(key, i), 10)
        layer = {"input_layernorm": one(h),
                 "pre_ff_layernorm": one(h),
                 "mlp": {"gate_proj": mat(k[0], h, f),
                         "up_proj": mat(k[1], h, f),
                         "down_proj": mat(k[2], f, h)}}
        if i in c.attention_layers:
            layer["attn"] = {
                "q_proj": mat(k[3], h, H * D), "k_proj": mat(k[4], h, G * D),
                "v_proj": mat(k[5], h, G * D), "o_proj": mat(k[6], H * D, h)}
        else:
            layer["mamba"] = {
                "in_proj": mat(k[3], h, 2 * d),
                "conv": {
                    "kernel": jax.random.uniform(
                        k[4], (c.mamba_d_conv, d), jnp.float32, -0.5,
                        0.5).astype(dtype),
                    "bias": jax.random.uniform(
                        k[5], (d,), jnp.float32, -0.5, 0.5).astype(dtype)},
                "x_proj": mat(k[6], d, r + 2 * n),
                "dt_norm": one(r), "b_norm": one(n), "c_norm": one(n),
                "dt_proj": {"kernel": normal_init(k[7], (r, d), 0.02, dtype),
                            "bias": jnp.asarray(dt_bias_init(d))},
                "A_log": jnp.broadcast_to(jnp.log(jnp.arange(
                    1, n + 1, dtype=jnp.float32))[:, None], (n, d)),
                "D": jnp.ones((d,), jnp.float32),
                "out_proj": mat(k[8], d, h)}
        layers.append(layer)
    return {
        "embed_tokens": {"embedding": normal_init(
            jax.random.fold_in(key, 1000), (c.vocab_size, h), 0.02, dtype)},
        "layers": layers,
        "norm": one(h),
    }


# ---------------------------------------------------------------------------
# the two mixers
# ---------------------------------------------------------------------------


def _mamba(config, m, x, pool: StatePool, layer: int, meta: StateMeta):
    """-> (mixer output [B, S, h], the pool with the state group's layer
    `layer` advanced). `x` [B, S, h], normed."""
    c = config
    B, S, _ = x.shape
    d, n, r = c.d_inner, c.mamba_d_state, c.mamba_dt_rank
    f32 = jnp.float32
    with part("attn.project"):
        uz = dense(x, m["in_proj"]["kernel"])
        u, z = uz[..., :d], uz[..., d:]
        conv = (m["conv"]["kernel"], m["conv"]["bias"], c.conv_taps_skipped)
        if S == 1:
            pre, pool = conv_step(u[:, 0], pool, layer, meta, *conv)
            pre = pre[:, None]
        else:
            pre, pool = conv_chunk(u, pool, layer, meta, *conv)
        act = jax.nn.silu(pre)                            # [B, S, d] float32
        proj = dense(act.astype(x.dtype), m["x_proj"]["kernel"])
        rt, Bm, Cm = (proj[..., :r], proj[..., r:r + n], proj[..., r + n:])
        if c.use_inner_norms:
            rt = rms_norm(rt, m["dt_norm"]["scale"], c.rms_norm_eps)
            Bm = rms_norm(Bm, m["b_norm"]["scale"], c.rms_norm_eps)
            Cm = rms_norm(Cm, m["c_norm"]["scale"], c.rms_norm_eps)
        dt = jax.nn.softplus(
            jnp.einsum("...r,rd->...d", rt, m["dt_proj"]["kernel"].astype(
                x.dtype), preferred_element_type=f32)
            + m["dt_proj"]["bias"].astype(f32))
        A = -jnp.exp(m["A_log"].astype(f32))
    with part("attn.attend"):
        if S == 1:
            y, pool = ssm_decode_step(dt[:, 0], act[:, 0], Bm[:, 0],
                                      Cm[:, 0], A, pool, layer, meta)
            y = y[:, None]
        else:
            y, pool = ssm_chunk_scan(dt, act, Bm, Cm, A, pool, layer, meta)
        y = y + m["D"].astype(f32) * act
    with part("attn.output"):
        y = (y * jax.nn.silu(z.astype(f32))).astype(x.dtype)
        out = dense(y, m["out_proj"]["kernel"])
    return out, pool


def _attention(config, a, x, positions, cache, rows_back: bool):
    """-> (attention output [B, S, h], this layer's new cache entry).
    `cache`: None; ("view", k [B, R, G, D], v, start [B]); or ("paged",
    PagedKV k at its layer, PagedKV v, PagedDecodeMeta). The new entry of a
    view is the updated view, or with `rows_back` this call's own rows."""
    c = config
    B, S, _ = x.shape
    H, G, D = c.num_attention_heads, c.num_key_value_heads, c.head_dim
    with part("attn.project"):
        q = dense(x, a["q_proj"]["kernel"]).reshape(B, S, H, D)
        k = dense(x, a["k_proj"]["kernel"]).reshape(B, S, G, D)
        v = dense(x, a["v_proj"]["kernel"]).reshape(B, S, G, D)
    new = None
    with part("attn.attend"):
        if cache is None:
            out = blocked_attention(q, positions, k, v, positions, None,
                                    c.kv_block)
        elif cache[0] == "paged":
            from ..ops.paged_attention import paged_decode_attention

            _, pk, pv, meta = cache
            out, new = paged_decode_attention(q, k, v, pk, pv, meta)
        else:
            _, view_k, view_v, start = cache
            view_k = write_view(view_k, k, start, False)
            view_v = write_view(view_v, v, start, False)
            R = view_k.shape[1]
            blk = min(c.kv_block, R)
            key_pos = jnp.broadcast_to(
                jnp.arange(R, dtype=jnp.int32)[None, :], (B, R))
            # rows past the last written position are masked; blocks past
            # it are not visited
            key_pos = jnp.where(key_pos < (start + S)[:, None], key_pos, -1)
            out = blocked_attention(
                q, positions, view_k, view_v, key_pos, None, c.kv_block,
                hi=jnp.minimum(jnp.max(positions) // blk + 1, -(-R // blk)))
            new = ((k.astype(view_k.dtype), v.astype(view_v.dtype))
                   if rows_back else (view_k, view_v))
    with part("attn.output"):
        out = dense(out.reshape(B, S, H * D), a["o_proj"]["kernel"])
    return out, new


# ---------------------------------------------------------------------------
# forward
# ---------------------------------------------------------------------------


def forward(config: JambaConfig, params: dict, input_ids: jax.Array,
            positions: jax.Array | None = None, kv_caches=None, *,
            logit_rows=None, token_mask=None, return_stats: bool = False):
    """Logits [B, S, V] float32 of token ids [B, S]; with `kv_caches`,
    `(logits, new_caches)`.

    `kv_caches` is `(k, v, third)`, k and v one entry a cache GROUP
    (`cache_spec`'s order). The attention layers' group, first: views `[2,
    B, R, G, D]` with `third` the rows already written (a scalar or one
    count a row of the batch), the updated views come back; one slot's
    views a layer at a time (`serving.cache.LayerwiseSlotView`, the serving
    engine's prefill), the chunk's own rows come back; or the paged pools
    (`PagedKV`, `third` a `PagedDecodeMeta`), this step's rows come back.
    The Mamba layers' group, last: a `StatePool` in k and a `StateMeta` in
    v (`rows` None: every row of every lane is real; `entries` None: lane
    b's entry is b); the pool comes back advanced, in place under
    donation. `positions` [B, S] are the attention layers' (None: from the
    rows already written, or 0). `logit_rows` [B] int32: the head for that
    one row of every sequence only (logits [B, 1, V]). `token_mask` is the
    engine's and not needed (`meta.rows` says the same). `return_stats`: a
    third result `{"tokens_scanned": int32 scalar, real rows x Mamba
    layers}`."""
    del token_mask
    c = config
    B, S = input_ids.shape
    attends, scans = c.attention_layers, c.mamba_layers
    if kv_caches is None:
        pool, meta, pages = _fresh_pool(c, B), StateMeta(None, None), None
    else:
        pool, meta = kv_caches[0][-1], kv_caches[1][-1]
        pages = (kv_caches[0][0], kv_caches[1][0])
    paged = pages is not None and getattr(pages[0], "is_paged_kv", False)
    views = pages is not None and not paged
    layerwise = views and getattr(pages[0], "is_layerwise_view", False)
    if paged and S != 1:
        raise ValueError(
            f"paged decode attention is one token a slot; got {S} (chunked "
            "prefill attends the slot's gathered views)")
    start = None
    if views:
        start = jnp.broadcast_to(kv_caches[2], (B,)).astype(jnp.int32)
    if positions is None:
        first = start[:, None] if views else 0
        positions = jnp.broadcast_to(
            first + jnp.arange(S, dtype=jnp.int32)[None, :], (B, S))
    rows = (jnp.full((B,), S, jnp.int32) if meta.rows is None
            else meta.rows.astype(jnp.int32))
    given, meta = meta, StateMeta(meta.entries, rows)
    if paged:       # the engine's meta carries one table a page group
        from ..ops.paged_attention import PagedDecodeMeta

        walk = PagedDecodeMeta(kv_caches[2].table[0], kv_caches[2].lengths,
                               rows=kv_caches[2].rows)

    with part("embed"):
        x = params["embed_tokens"]["embedding"][input_ids]
    new_k, new_v = [], []
    for i, layer in enumerate(params["layers"]):
        # a norm is billed with the part it feeds, a residual add with the
        # part it closes
        with part("attn.project"):
            y = rms_norm(x, layer["input_layernorm"]["scale"],
                         c.rms_norm_eps)
        if i in attends:
            j = attends.index(i)
            cache = None
            if paged:
                cache = ("paged", pages[0].at_layer(j), pages[1].at_layer(j),
                         walk)
            elif views:
                cache = ("view", layer_view(pages[0], j),
                         layer_view(pages[1], j), start)
            mixed, new = _attention(c, layer["attn"], y, positions, cache,
                                    layerwise)
            if new is not None:
                new_k.append(new[0])
                new_v.append(new[1])
        else:
            mixed, pool = _mamba(c, layer["mamba"], y, pool, scans.index(i),
                                 meta)
        with part("attn.output"):
            x = x + mixed
        with part("mlp"):
            y = rms_norm(x, layer["pre_ff_layernorm"]["scale"],
                         c.rms_norm_eps)
            m = layer["mlp"]
            x = x + dense(
                jax.nn.silu(dense(y, m["gate_proj"]["kernel"]))
                * dense(y, m["up_proj"]["kernel"]), m["down_proj"]["kernel"])
    with part("head"):
        x = rms_norm(x, params["norm"]["scale"], c.rms_norm_eps)
        if logit_rows is not None:
            x = jnp.take_along_axis(x, logit_rows[:, None, None], axis=1)
        logits = jnp.einsum(
            "bsh,vh->bsv", x,
            params["embed_tokens"]["embedding"].astype(x.dtype),
            preferred_element_type=jnp.float32)
    if kv_caches is None:
        out = (logits,)
    else:
        # the rows a decode step hands the engine to append and a chunk
        # over a slot's layerwise views to write; else the updated views,
        # stacked again; the pool after them, in its group's place
        with part("cache.write" if paged or layerwise else "cache.view"):
            new_k, new_v = jnp.stack(new_k), jnp.stack(new_v)
        out = (logits, ((new_k, pool), (new_v, given),
                        kv_caches[2] if paged else kv_caches[2] + S))
    if return_stats:
        with part("attn.attend"):
            out = out + ({"tokens_scanned":
                          jnp.sum(rows, dtype=jnp.int32) * len(scans)},)
    return out[0] if len(out) == 1 else out


# ---------------------------------------------------------------------------
# counters
# ---------------------------------------------------------------------------


def init_serving_stats(config: JambaConfig) -> dict:
    """The device counters one engine program accumulates, all zero: the
    tokens scanned into states, real rows x Mamba layers summed over calls
    (a wide counter: `common.wide_count` reads it), and the states zeroed
    (admissions; the engine's `admit` counts them beside the prefill
    program's)."""
    del config
    return {"tokens_scanned": jnp.zeros((2,), jnp.int32),
            "states_zeroed": jnp.zeros((), jnp.int32)}


def accumulate_serving_stats(total: dict, call: dict) -> dict:
    with part("attn.attend"):
        return dict(total, tokens_scanned=add_wide(
            total["tokens_scanned"], call["tokens_scanned"]))


def count_state_zeroed(total: dict) -> dict:
    """`total` with one more state zeroed."""
    return dict(total, states_zeroed=total["states_zeroed"] + 1)


def _fresh_pool(config: JambaConfig, batch: int) -> StatePool:
    """A pool of a call's own: `batch` zero entries and a spare."""
    c = config
    L, dtype = len(c.mamba_layers), jnp.dtype(c.state_dtype)
    return StatePool(
        jnp.zeros((L, batch + 1, 1, c.mamba_d_state, c.d_inner), dtype),
        jnp.zeros((L, c.mamba_d_conv - 1, batch + 1, c.d_inner), dtype))


def init_kv_caches(config: JambaConfig, batch: int, max_len: int,
                   dtype=jnp.bfloat16):
    """For `generate`: `max_len` rows of views for the attention layers,
    and zero states, one entry a row of the batch."""
    c = config
    views = jnp.zeros((len(c.attention_layers), batch, max_len,
                       c.num_key_value_heads, c.head_dim), dtype)
    return ((views, _fresh_pool(c, batch)), (views, StateMeta(None, None)),
            jnp.zeros((), jnp.int32))


generate = build_generate(forward, init_kv_caches)

SERVING = ServingContract(
    forward=forward, cache_spec=cache_spec, logit_rows=True,
    layerwise_views=True,
    init_stats=init_serving_stats, fold_stats=accumulate_serving_stats,
    count_state_zeroed=count_state_zeroed)
