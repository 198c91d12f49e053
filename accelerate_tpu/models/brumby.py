"""A decoder whose layers keep no K/V rows: every layer holds ONE recurrent
state of fixed size a sequence (power retention, Buckman, Gelada, Zhang et
al., arXiv:2507.04239). Brumby-14B-Base (`model_type` `brumby`) is this
block, a Qwen3-14B-shaped decoder with its attention replaced, and is what
the benchmark serves (`chipbench/configs/brumby-14b-base-d8.json`).

Per layer (x of width h; pre-norm, plain residuals; `y = RMSNorm(x)`; H
query heads over G KV heads of d lanes, H / G query heads a KV head):

- `q = y W_q` (H x d), `k = y W_k`, `v = y W_v` (G x d), no biases; a
  per-head RMSNorm of q and k over their d lanes and the half-split
  rotation by position with `rope_theta` (both ASSUMED kept from the Qwen3
  block whose keys the published configuration carries);
- one log-gate a KV head and token, `gamma = log sigmoid(y w_g + b_g)`, in
  float32 (ASSUMED: the published layer gates per head from the layer's
  input; the configuration has no key for it);
- power retention of degree p = 2 (`ops/power_retention.py`, where the
  equations stand): `o_t = sum_{s<=t} A[t, s] v_s / (sum_s A[t, s] + eps)`,
  `A[t, s] = exp(sum_{s<u<=t} gamma_u) (q_t . k_s)^p`, a query head against
  its KV head's k, v and gate; then `W_o`;
- SwiGLU MLP, final RMSNorm, untied head.

What the cache holds is NOT rows: `cache_spec` declares `kind="state"`, and
the serving engine hands `forward` its whole pool of states
(`serving/cache.py` `StateCache`; `kv_caches = (StatePool, None,
StateMeta)`), one entry a sequence. A call of one token a row advances each
live row's state by that token (`retention_decode_step`); a call of more
advances each row's state by its `meta.rows` real leading rows
(`retention_chunk`): a padded row gets `gamma = 0` and `k = v = 0` and
leaves the state as it was. With no cache the same ops run from a zero
state that is thrown away.

`retention_degree` 1 and `use_gate` False exist for the benchmark's
controls (a model that is NOT this one must fail the cell's check);
`state_dtype` bfloat16 is the reported what-if of a state kept in half the
bytes.

`SERVING`, at the foot: docs/serving.md, "What a served family declares".
"""

from __future__ import annotations

import dataclasses
from typing import Any

import jax
import jax.numpy as jnp
import numpy as np

from ..ops.power_retention import (
    StateMeta,
    StatePool,
    normaliser_rows,
    retention_chunk,
    retention_decode_step,
    state_rows,
)
from .common import add_wide, dense, normal_init, part, rms_norm
from .contract import CacheSpec, ServingContract
from .decode import build_generate


@dataclasses.dataclass(frozen=True)
class BrumbyConfig:
    vocab_size: int = 151936
    hidden_size: int = 5120
    intermediate_size: int = 17408
    num_hidden_layers: int = 40
    num_attention_heads: int = 40
    num_key_value_heads: int = 8
    head_dim: int = 128
    max_position_embeddings: int = 32768
    rms_norm_eps: float = 1e-6
    rope_theta: float = 1000000.0
    hidden_act: str = "silu"
    attention_bias: bool = False
    tie_word_embeddings: bool = False
    # ASSUMED, not published: see the head of this file
    retention_degree: int = 2
    use_gate: bool = True
    retention_eps: float = 1e-6
    state_dtype: Any = "float32"

    def __post_init__(self):
        if (self.attention_bias or self.tie_word_embeddings
                or self.hidden_act != "silu"):
            raise ValueError(
                "only attention_bias=False, tie_word_embeddings=False and "
                "hidden_act='silu' are implemented")
        if self.num_attention_heads % self.num_key_value_heads:
            raise ValueError("query heads must be a multiple of KV heads")
        state_rows(self.head_dim, self.retention_degree)  # raises what it must
        object.__setattr__(self, "state_dtype",
                           jnp.dtype(self.state_dtype).name)

    @classmethod
    def tiny(cls, **overrides) -> "BrumbyConfig":
        """Test size: 128-wide heads, so that the decode kernel runs as on
        the chip (whole 128-lane rows of `phi`)."""
        defaults = dict(
            vocab_size=256, hidden_size=64, intermediate_size=128,
            num_hidden_layers=2, num_attention_heads=4,
            num_key_value_heads=2, head_dim=128,
            max_position_embeddings=512, rope_theta=10000.0)
        defaults.update(overrides)
        return cls(**defaults)


def cache_spec(config: BrumbyConfig):
    """One state a sequence and layer: G matrices of `state_rows` x d and
    as many vectors of `state_rows` (the second block: `normaliser_rows`
    rows of d lanes), float32 as served."""
    return CacheSpec(
        num_layers=config.num_hidden_layers,
        heads=config.num_key_value_heads, width=config.head_dim,
        kind="state",
        state_rows=state_rows(config.head_dim, config.retention_degree),
        aux_rows=normaliser_rows(config.head_dim, config.retention_degree),
        state_dtype=jnp.dtype(config.state_dtype))


def gate_bias_init(heads: int) -> np.ndarray:
    """The gates' biases where no trained ones exist: spread evenly from 4
    to 9, so that `sigmoid` keeps 0.982 to 0.99988 of a state a token and
    a KV head remembers some 50 to some 8,000 tokens, short and long
    memories side by side as trained gated layers have them. (With a bias
    of zero every head would forget within two tokens and no state would
    carry anything of a long prompt.)"""
    return np.linspace(4.0, 9.0, heads).astype(np.float32)


def init_params(config: BrumbyConfig, key: jax.Array,
                dtype=jnp.float32) -> dict:
    c = config
    h, D, f = c.hidden_size, c.head_dim, c.intermediate_size
    H, G = c.num_attention_heads, c.num_key_value_heads

    def mat(k, *shape):
        return {"kernel": normal_init(k, shape, 0.02, dtype)}

    def one(n):
        return {"scale": jnp.ones((n,), dtype)}

    layers = []
    for i in range(c.num_hidden_layers):
        k = jax.random.split(jax.random.fold_in(key, i), 8)
        attn = {"q_proj": mat(k[0], h, H * D), "k_proj": mat(k[1], h, G * D),
                "v_proj": mat(k[2], h, G * D), "o_proj": mat(k[3], H * D, h),
                "gate_proj": dict(mat(k[4], h, G), bias=jnp.asarray(
                    gate_bias_init(G), dtype)),
                "q_norm": one(D), "k_norm": one(D)}
        layers.append({
            "input_layernorm": one(h),
            "attn": attn,
            "post_attention_layernorm": one(h),
            "mlp": {"gate_proj": mat(k[5], h, f), "up_proj": mat(k[6], h, f),
                    "down_proj": mat(k[7], f, h)},
        })
    return {
        "embed_tokens": {"embedding": normal_init(
            jax.random.fold_in(key, 1000), (c.vocab_size, h), 0.02, dtype)},
        "layers": layers,
        "norm": one(h),
        "lm_head": mat(jax.random.fold_in(key, 1001), h, c.vocab_size),
    }


def _rotate(x, positions, theta: float):
    """The half-split rotation of x [B, S, heads, d] by `positions` [B, S]
    (lane i pairs with lane i + d / 2). The angles are made from the
    positions, not read from a table: a state has no reach to size one by,
    and a slot may hold more positions than the configuration declares."""
    d = x.shape[-1]
    inv = jnp.asarray(1.0 / (theta ** (np.arange(0, d, 2) / d)), jnp.float32)
    ang = positions.astype(jnp.float32)[:, :, None, None] * inv
    cos, sin = jnp.cos(ang), jnp.sin(ang)
    x1, x2 = jnp.split(x.astype(jnp.float32), 2, axis=-1)
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin],
                           axis=-1).astype(x.dtype)


def _attention(config, a, x, positions, pool: StatePool, layer: int,
               meta: StateMeta, real):
    """-> (attention output [B, S, h], the pool with layer `layer`'s states
    advanced). `real` [B, S]: which rows are tokens (a chunk's padding is
    not)."""
    c = config
    B, S, _ = x.shape
    H, G, D = c.num_attention_heads, c.num_key_value_heads, c.head_dim
    with part("attn.project"):
        q = dense(x, a["q_proj"]["kernel"]).reshape(B, S, H, D)
        k = dense(x, a["k_proj"]["kernel"]).reshape(B, S, G, D)
        v = dense(x, a["v_proj"]["kernel"]).reshape(B, S, G, D)
        q = rms_norm(q, a["q_norm"]["scale"], c.rms_norm_eps)
        k = rms_norm(k, a["k_norm"]["scale"], c.rms_norm_eps)
        q = _rotate(q, positions, c.rope_theta)
        k = _rotate(k, positions, c.rope_theta)
        if c.use_gate:
            gamma = jax.nn.log_sigmoid(
                jnp.dot(x, a["gate_proj"]["kernel"].astype(x.dtype),
                        preferred_element_type=jnp.float32)
                + a["gate_proj"]["bias"].astype(jnp.float32))
        else:
            gamma = jnp.zeros((B, S, G), jnp.float32)
        if S > 1:
            # a padded row adds nothing to a state and decays nothing
            keep = real[:, :, None]
            gamma = jnp.where(keep, gamma, 0.0)
            k = jnp.where(keep[..., None], k, 0)
            v = jnp.where(keep[..., None], v, 0)
    with part("attn.attend"):
        kw = dict(degree=c.retention_degree, eps=c.retention_eps)
        if S == 1:
            out, pool = retention_decode_step(
                q[:, 0], k[:, 0], v[:, 0], gamma[:, 0], pool, layer, meta,
                **kw)
            out = out[:, None]
        else:
            out, pool = retention_chunk(q, k, v, gamma, pool, layer,
                                        meta.entries, **kw)
        out = out.astype(x.dtype)
    with part("attn.output"):
        out = dense(out.reshape(B, S, H * D), a["o_proj"]["kernel"])
    return out, pool


def forward(config: BrumbyConfig, params: dict, input_ids: jax.Array,
            positions: jax.Array | None = None, kv_caches=None, *,
            logit_rows=None, token_mask=None, return_stats: bool = False):
    """Logits [B, S, V] float32 of token ids [B, S]; with `kv_caches`,
    `(logits, new_caches)`.

    `kv_caches` is `(StatePool, None, StateMeta)`: the whole pool of
    states, each row's entry in it and how many of the row's S rows are
    tokens (`rows` None: all of them). S = 1 advances each row's state by
    one token where `rows` is 1 and leaves it untouched where it is 0; S >
    1 advances it by the row's leading `rows` tokens. The pool comes back
    with those states advanced (in place under donation) beside the same
    meta. `positions` [B, S]: where the rows stand in their sequences (a
    state does not know how many tokens it holds; None: from 0).
    `logit_rows` [B] int32: the head for that one
    row of every sequence only (logits [B, 1, V]). `token_mask` is the
    engine's for counters and is not needed here (`meta.rows` says the
    same). `return_stats`: a third result `{"tokens_folded": int32 scalar,
    real rows x layers}`."""
    del token_mask
    c = config
    B, S = input_ids.shape
    if kv_caches is None:
        # a pool of this call's own: B zero states and a spare
        pool, meta = _fresh_pool(c, B), StateMeta(
            jnp.arange(B, dtype=jnp.int32), None)
    else:
        pool, _, meta = kv_caches
    if positions is None:
        # (a state does not say how many tokens it holds: a call that
        # gives no positions is a sequence's first rows)
        positions = jnp.broadcast_to(
            jnp.arange(S, dtype=jnp.int32)[None, :], (B, S))
    rows = (jnp.full((B,), S, jnp.int32) if meta.rows is None
            else meta.rows.astype(jnp.int32))
    real = jnp.arange(S, dtype=jnp.int32)[None, :] < rows[:, None]
    step_meta = StateMeta(meta.entries, rows)

    with part("embed"):
        x = params["embed_tokens"]["embedding"][input_ids]
    for i, layer in enumerate(params["layers"]):
        # a norm is billed with the part it feeds, a residual add with the
        # part it closes
        with part("attn.project"):
            y = rms_norm(x, layer["input_layernorm"]["scale"],
                         c.rms_norm_eps)
        attn, pool = _attention(c, layer["attn"], y, positions, pool, i,
                                step_meta, real)
        with part("attn.output"):
            x = x + attn
        with part("mlp"):
            y = rms_norm(x, layer["post_attention_layernorm"]["scale"],
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
            "bsh,hv->bsv", x, params["lm_head"]["kernel"].astype(x.dtype),
            preferred_element_type=jnp.float32)
    out = (logits,) if kv_caches is None else (logits, (pool, None, meta))
    if return_stats:
        with part("attn.attend"):
            out = out + ({"tokens_folded": jnp.sum(rows, dtype=jnp.int32)
                          * c.num_hidden_layers},)
    return out[0] if len(out) == 1 else out


# ---------------------------------------------------------------------------
# counters
# ---------------------------------------------------------------------------


def init_serving_stats(config: BrumbyConfig) -> dict:
    """The device counters one engine program accumulates, all zero: the
    tokens folded into states, real rows x layers summed over calls (a wide
    counter: `common.wide_count` reads it), and the states zeroed
    (admissions; the engine's `admit` counts them beside the prefill
    program's)."""
    del config
    return {"tokens_folded": jnp.zeros((2,), jnp.int32),
            "states_zeroed": jnp.zeros((), jnp.int32)}


def accumulate_serving_stats(total: dict, call: dict) -> dict:
    with part("attn.attend"):
        return dict(total, tokens_folded=add_wide(
            total["tokens_folded"], call["tokens_folded"]))


def count_state_zeroed(total: dict) -> dict:
    """`total` with one more state zeroed."""
    return dict(total, states_zeroed=total["states_zeroed"] + 1)


def _fresh_pool(config: BrumbyConfig, batch: int) -> StatePool:
    c = config
    dtype = jnp.dtype(c.state_dtype)
    D = state_rows(c.head_dim, c.retention_degree)
    lead = (c.num_hidden_layers, batch + 1, c.num_key_value_heads)
    return StatePool(
        jnp.zeros(lead + (D, c.head_dim), dtype),
        jnp.zeros(lead + (normaliser_rows(c.head_dim, c.retention_degree),
                          c.head_dim), dtype))


def init_kv_caches(config: BrumbyConfig, batch: int, max_len: int,
                   dtype=jnp.bfloat16):
    """Zero states for `generate`: one entry a row of the batch, whatever
    `max_len` (a state does not grow)."""
    del max_len, dtype
    return (_fresh_pool(config, batch), None,
            StateMeta(jnp.arange(batch, dtype=jnp.int32), None))


generate = build_generate(forward, init_kv_caches)

SERVING = ServingContract(
    forward=forward, cache_spec=cache_spec, logit_rows=True,
    init_stats=init_serving_stats, fold_stats=accumulate_serving_stats,
    count_state_zeroed=count_state_zeroed)
