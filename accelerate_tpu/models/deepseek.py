"""The DeepSeek-V3 block: multi-head latent attention (MLA) and a sparse
expert layer with sigmoid routing, a shared expert and leading dense
layers. JoyAI-LLM-Flash (48B-A2.7B) is this block at other numbers, and is
what the benchmark serves (`chipbench/configs/joyai-llm-flash-d5.json`).

Attention, per layer (x of width h, H heads): `c_q = RMSNorm(x W_qa)`; `q =
c_q W_qb` -> H x (nope | rope); `[c_kv | k_pe] = x W_kva`; `c_kv =
RMSNorm(c_kv)`; `q_pe`, `k_pe` rotated (interleaved pairs), `k_pe` ONE head
shared by all; `[k_nope | v] = c_kv W_kvb` per head; `k = [k_nope | k_pe]`;
causal softmax of `q k^T / sqrt(nope + rope)` times `v`, through `W_o`.
What is cached for a token is ONE row `[c_kv | k_pe | 0]` (`kv_lora_rank +
qk_rope_head_dim` numbers, zero-padded to whole 128-lane tiles), never a
decompressed K or V. Two forms of the same mathematics read it:

- decompressed (prefill chunks, the cache-free forward): ONE Pallas kernel
  a layer, `latent_chunk_attention` (`ops/latent_chunk_attention.py`;
  interpreted where there is no TPU): a tile of rows is expanded through
  `W_kvb`, scored and folded into an online softmax in vector memory,
  dropped. The `[H, chunk, rows]` scores never exist, not even a tile's
  outside the kernel, and tiles past the last query are neither read nor
  computed;
- absorbed (decode): `q_lat_h = q_nope_h W_UK_h^T`, `score = q_lat_h .
  c_kv + q_pe_h . k_pe`, `o_h = (P c_kv) W_UV_h`: H query heads over one
  shared key row whose first `kv_lora_rank` lanes are also the value. On
  the serving engine's paged pool this is the Pallas kernel of
  `ops/latent_paged_attention.py`; on a dense cache a plain einsum.

Expert layer (layers >= `first_k_dense_replace`): `ops/grouped_experts.py`
(float32 sigmoid router with `e_score_correction_bias` in the choice only,
top-k weights normalised and scaled, dropless grouped products) plus the
shared expert on every token.

Layers are a LIST of per-layer dicts, walked by a Python loop: a layer's
expert weights (805 MB a matrix at the published widths) and the latent
pool then reach their kernels as whole arrays. Inside a `lax.scan` the
compiler copies each layer's slice out of a stacked array around every
kernel call (PERF.md, section 7).

`SERVING`, at the foot: docs/serving.md, "What a served family declares".
"""

from __future__ import annotations

import dataclasses
import math

import jax
import jax.numpy as jnp

from ..ops.grouped_experts import (
    expert_counts,
    grouped_swiglu_experts,
    sigmoid_topk_route,
)
from ..ops.latent_chunk_attention import latent_chunk_attention
from .common import dense, normal_init, part, rms_norm, rope_frequencies
from .contract import CacheSpec, ServingContract
from .decode import build_generate, layer_view, rope_table_len

NEG_INF = -1e30
_LANES = 128


@dataclasses.dataclass(frozen=True)
class DeepseekConfig:
    vocab_size: int = 129280
    hidden_size: int = 2048
    intermediate_size: int = 7168          # the leading dense layers' MLP
    moe_intermediate_size: int = 768       # one expert
    num_hidden_layers: int = 40
    num_attention_heads: int = 32
    q_lora_rank: int = 1536
    kv_lora_rank: int = 512
    qk_nope_head_dim: int = 128
    qk_rope_head_dim: int = 64
    v_head_dim: int = 128
    n_routed_experts: int = 256
    num_experts_per_tok: int = 8
    n_shared_experts: int = 1
    first_k_dense_replace: int = 1
    routed_scaling_factor: float = 2.5
    norm_topk_prob: bool = True
    scoring_func: str = "sigmoid"
    topk_method: str = "noaux_tc"
    n_group: int = 1
    topk_group: int = 1
    max_position_embeddings: int = 131072
    rope_theta: float = 32e6
    rope_interleave: bool = True
    rms_norm_eps: float = 1e-6

    def __post_init__(self):
        if self.scoring_func != "sigmoid" or self.topk_method != "noaux_tc":
            raise ValueError(
                "only scoring_func='sigmoid' with topk_method='noaux_tc' is "
                f"implemented; got {self.scoring_func!r}, "
                f"{self.topk_method!r}")
        if self.n_group != 1 or self.topk_group != 1:
            raise ValueError(
                "group-limited routing (n_group / topk_group > 1) is not "
                "implemented: with one group the group step is the identity")
        if not self.rope_interleave:
            raise ValueError("only rope_interleave=True is implemented")

    @property
    def qk_head_dim(self) -> int:
        return self.qk_nope_head_dim + self.qk_rope_head_dim

    @property
    def latent_width(self) -> int:
        """Numbers cached for a token in a layer: c_kv and k_pe."""
        return self.kv_lora_rank + self.qk_rope_head_dim

    @property
    def latent_row_width(self) -> int:
        """`latent_width` padded with zeros to whole 128-lane tiles, which
        is how wide a cache row IS (the chip would pad it in memory
        anyway); the kernel needs its value part tile-aligned too."""
        return -(-self.latent_width // _LANES) * _LANES

    @classmethod
    def tiny(cls, **overrides) -> "DeepseekConfig":
        """Test size; the row's value part stays one whole lane tile."""
        defaults = dict(
            vocab_size=256, hidden_size=64, intermediate_size=128,
            moe_intermediate_size=32, num_hidden_layers=3,
            num_attention_heads=4, q_lora_rank=48, kv_lora_rank=128,
            qk_nope_head_dim=16, qk_rope_head_dim=8, v_head_dim=16,
            n_routed_experts=8, num_experts_per_tok=2,
            max_position_embeddings=128)
        defaults.update(overrides)
        return cls(**defaults)


def cache_spec(config: DeepseekConfig):
    """What the serving engine's pool holds for this family: one latent
    row a token a layer, no V twin."""
    return CacheSpec(num_layers=config.num_hidden_layers, heads=1,
                     width=config.latent_row_width, kind="latent")


def init_params(config: DeepseekConfig, key: jax.Array,
                dtype=jnp.float32) -> dict:
    c = config
    h, H = c.hidden_size, c.num_attention_heads
    f, E = c.moe_intermediate_size, c.n_routed_experts

    def w(k, *shape):
        return {"kernel": normal_init(k, shape, 0.02, dtype)}

    def one(n):
        return {"scale": jnp.ones((n,), dtype)}

    def mlp(k, width):
        k = jax.random.split(k, 3)
        return {"gate_proj": w(k[0], h, width), "up_proj": w(k[1], h, width),
                "down_proj": w(k[2], width, h)}

    layers = []
    for i in range(c.num_hidden_layers):
        k = jax.random.split(jax.random.fold_in(key, i), 12)
        layer = {
            "input_layernorm": one(h),
            "attn": {
                "q_a_proj": w(k[0], h, c.q_lora_rank),
                "q_a_layernorm": one(c.q_lora_rank),
                "q_b_proj": w(k[1], c.q_lora_rank, H * c.qk_head_dim),
                "kv_a_proj": w(k[2], h, c.latent_width),
                "kv_a_layernorm": one(c.kv_lora_rank),
                "kv_b_proj": w(k[3], c.kv_lora_rank,
                               H * (c.qk_nope_head_dim + c.v_head_dim)),
                "o_proj": w(k[4], H * c.v_head_dim, h),
            },
            "post_attention_layernorm": one(h),
        }
        if i < c.first_k_dense_replace:
            layer["mlp"] = mlp(k[5], c.intermediate_size)
        else:
            layer["moe"] = {
                "router": {
                    "kernel": normal_init(k[6], (h, E), 0.02, dtype),
                    "e_score_correction_bias": jnp.zeros((E,), jnp.float32)},
                "experts": {
                    "gate_proj": normal_init(k[7], (E, h, f), 0.02, dtype),
                    "up_proj": normal_init(k[8], (E, h, f), 0.02, dtype),
                    "down_proj": normal_init(k[9], (E, f, h), 0.02, dtype)},
                "shared": mlp(k[10], f * c.n_shared_experts),
            }
        layers.append(layer)
    return {
        "embed_tokens": {"embedding": normal_init(
            jax.random.fold_in(key, 1000), (c.vocab_size, h), 0.02, dtype)},
        "layers": layers,
        "norm": one(h),
        "lm_head": w(jax.random.fold_in(key, 1001), h, c.vocab_size),
    }


# ---------------------------------------------------------------------------
# attention
# ---------------------------------------------------------------------------


def _rope_interleaved(x, cos, sin, positions):
    """x [B, S, H, D] rotated in ADJACENT pairs (lanes 2i, 2i + 1 by the
    angle of frequency i); cos/sin [table, D / 2]; positions [B, S]."""
    c = cos[positions][:, :, None, :]
    s = sin[positions][:, :, None, :]
    pairs = x.astype(jnp.float32).reshape(x.shape[:-1] + (-1, 2))
    x0, x1 = pairs[..., 0], pairs[..., 1]
    out = jnp.stack([x0 * c - x1 * s, x1 * c + x0 * s], axis=-1)
    return out.reshape(x.shape).astype(x.dtype)


def _kv_b(config, a, dtype):
    """W_kvb as [c, H, nope + v]: W_UK = [..., :nope], W_UV = [..., nope:]."""
    c = config
    return a["kv_b_proj"]["kernel"].astype(dtype).reshape(
        c.kv_lora_rank, c.num_attention_heads,
        c.qk_nope_head_dim + c.v_head_dim)


def _decompressed_attention(config, a, q_nope, q_pe, view, positions):
    """Causal attention of q [B, S, H, *] at `positions` [B, S] over the
    latent rows `view` [B, R, W] (row r is position r), K and V expanded
    from the rows a tile at a time where they are attended, in ONE kernel
    (`ops/latent_chunk_attention.py`) over the tiles up to the last
    query's; returns [B, S, H, v]."""
    key_pos = jnp.broadcast_to(
        jnp.arange(view.shape[1], dtype=jnp.int32)[None], view.shape[:2])
    return latent_chunk_attention(
        q_nope, q_pe, positions, view, key_pos,
        _kv_b(config, a, q_nope.dtype), live=(0, jnp.max(positions) + 1))


def _absorb_query(config, a, q_nope, q_pe):
    """[q_nope W_UK^T | q_pe | 0] per head, laid out like a cache row."""
    c = config
    w_uk = _kv_b(c, a, q_nope.dtype)[..., :c.qk_nope_head_dim]
    q_lat = jnp.einsum("bshd,chd->bshc", q_nope, w_uk,
                       preferred_element_type=jnp.float32
                       ).astype(q_nope.dtype)
    pad = jnp.zeros(q_pe.shape[:-1] + (c.latent_row_width - c.latent_width,),
                    q_pe.dtype)
    return jnp.concatenate([q_lat, q_pe, pad], axis=-1)


def _unabsorb_output(config, a, o_lat):
    """o_lat [B, S, H, c] through W_UV -> [B, S, H, v]."""
    c = config
    w_uv = _kv_b(c, a, o_lat.dtype)[..., c.qk_nope_head_dim:]
    return jnp.einsum("bshc,chd->bshd", o_lat, w_uv,
                      preferred_element_type=jnp.float32).astype(o_lat.dtype)


def _absorbed_attention(config, a, q_nope, q_pe, view, positions):
    """The absorbed form over a dense view [B, R, W], unblocked (decode:
    one query token a row of the batch)."""
    c = config
    q_abs = _absorb_query(c, a, q_nope, q_pe)
    view = view.astype(q_abs.dtype)
    s = jnp.einsum("bshw,brw->bhsr", q_abs, view,
                   preferred_element_type=jnp.float32
                   ) / math.sqrt(c.qk_head_dim)
    key_pos = jnp.arange(view.shape[1], dtype=jnp.int32)
    see = key_pos[None, None, None, :] <= positions[:, None, :, None]
    p = jax.nn.softmax(jnp.where(see, s, NEG_INF), axis=-1)
    o_lat = jnp.einsum("bhsr,brc->bshc", p.astype(q_abs.dtype),
                       view[..., :c.kv_lora_rank],
                       preferred_element_type=jnp.float32
                       ).astype(q_abs.dtype)
    return _unabsorb_output(c, a, o_lat)


def mla_project(config, a, x, cos, sin, positions, rescale: bool = False):
    """The two low-rank paths of latent attention over x [B, S, h] -> (c_q
    [B, S, q_lora_rank], q_nope [B, S, H, nope], q_pe [B, S, H, rope]
    rotated, the cache row [B, S, W] = [c_kv | k_pe rotated | 0]).
    `config` is anything with this block's widths (`num_attention_heads`,
    the two ranks, the three head widths, `latent_width`,
    `latent_row_width`, `rms_norm_eps`): another family's layer kind hands
    its own. `rescale`: both normed latents times `sqrt(hidden / rank)`
    (a family whose published config says so)."""
    c = config
    B, S, h = x.shape
    H = c.num_attention_heads
    with part("attn.project"):
        c_q = rms_norm(dense(x, a["q_a_proj"]["kernel"]),
                       a["q_a_layernorm"]["scale"], c.rms_norm_eps)
        if rescale:
            c_q = c_q * math.sqrt(h / c.q_lora_rank)
        q = dense(c_q, a["q_b_proj"]["kernel"]).reshape(B, S, H,
                                                        c.qk_head_dim)
        q_nope = q[..., :c.qk_nope_head_dim]
        q_pe = _rope_interleaved(q[..., c.qk_nope_head_dim:], cos, sin,
                                 positions)
        kv_a = dense(x, a["kv_a_proj"]["kernel"])
        c_kv = rms_norm(kv_a[..., :c.kv_lora_rank],
                        a["kv_a_layernorm"]["scale"], c.rms_norm_eps)
        if rescale:
            c_kv = c_kv * math.sqrt(h / c.kv_lora_rank)
        k_pe = _rope_interleaved(kv_a[..., None, c.kv_lora_rank:], cos, sin,
                                 positions)[:, :, 0]
        row = jnp.concatenate(
            [c_kv, k_pe, jnp.zeros(
                (B, S, c.latent_row_width - c.latent_width), x.dtype)],
            axis=-1)                                        # [B, S, W]
    return c_q, q_nope, q_pe, row


def _attention(config, a, x, cos, sin, positions, cache, layer_index,
               rows_back: bool = False):
    """-> (attention output [B, S, h], this layer's new cache entry: the
    updated dense view, or with `rows_back` this call's own rows [B, S, 1,
    W] as the view holds them; a paged step's one row)."""
    c = config
    B, S, _ = x.shape
    H = c.num_attention_heads
    _, q_nope, q_pe, row = mla_project(c, a, x, cos, sin, positions)

    if cache is None:
        with part("attn.attend"):
            out = _decompressed_attention(c, a, q_nope, q_pe, row, positions)
        new = None
    elif getattr(cache[0], "is_paged_kv", False):
        from ..ops.latent_paged_attention import latent_paged_decode_attention

        if S != 1:
            raise ValueError(
                f"paged latent attention is one token a slot; got {S} "
                "(chunked prefill attends the slot's gathered view)")
        pool, meta = cache[0].data, cache[2]
        new_row = row[:, 0].astype(cache[0].row_dtype)
        with part("attn.project"):
            q_abs = _absorb_query(c, a, q_nope, q_pe)[:, 0]
        # the pool's unit head axis folds away: [L, pages + 1, ps, W]
        with part("attn.attend"):
            o_lat = latent_paged_decode_attention(
                q_abs, new_row,
                pool.reshape(pool.shape[:2] + pool.shape[3:]),
                layer_index, meta.table, meta.lengths,
                value_width=c.kv_lora_rank,
                sm_scale=1.0 / math.sqrt(c.qk_head_dim))
        with part("attn.output"):
            out = _unabsorb_output(c, a, o_lat[:, None].astype(x.dtype))
        new = new_row[:, None, None, :]                     # [B, 1, 1, W]
    else:
        view, cache_len = cache[0][:, :, 0, :], cache[2]    # [B, M, W]
        with part("cache.write"):
            start = jnp.broadcast_to(cache_len, (B,))
            view = jax.vmap(lambda v, r, s: jax.lax.dynamic_update_slice(
                v, r, (s, 0)))(view, row.astype(view.dtype), start)
        with part("attn.attend"):
            # one query token a row: absorbed; a chunk: decompressed
            attend = (_absorbed_attention if S == 1
                      else _decompressed_attention)
            out = attend(c, a, q_nope, q_pe, view, positions)
        new = (row.astype(view.dtype) if rows_back else view)[:, :, None, :]
    with part("attn.output"):
        out = dense(out.reshape(B, S, H * c.v_head_dim),
                    a["o_proj"]["kernel"])
    return out, new


# ---------------------------------------------------------------------------
# the feed-forward halves
# ---------------------------------------------------------------------------


def _swiglu(m, x):
    act = jax.nn.silu(dense(x, m["gate_proj"]["kernel"])) * dense(
        x, m["up_proj"]["kernel"])
    return dense(act, m["down_proj"]["kernel"])


def moe_layer(config, m: dict, x, token_mask=None):
    """The expert layer over x [B, S, h] -> (y, assignments per expert [E]
    of the tokens `token_mask` [B, S] keeps; all of them without a mask;
    E is every expert the router chooses among, held here or not).
    The mask only says which tokens the counters count: padding and dead
    lanes are routed and computed like any row (shapes are static)."""
    c = config
    B, S, h = x.shape
    flat = x.reshape(B * S, h)
    experts, weights = sigmoid_topk_route(
        flat, m["router"]["kernel"], m["router"]["e_score_correction_bias"],
        c.num_experts_per_tok, c.routed_scaling_factor, c.norm_topk_prob)
    e = m["experts"]
    # a config that holds a share of its experts says which
    # (`experts_held`); the counters below stay over all the router's
    y = grouped_swiglu_experts(flat, experts, weights, e["gate_proj"],
                               e["up_proj"], e["down_proj"],
                               experts_held=getattr(c, "experts_held", None))
    with part("moe.shared"):
        y = (y + _swiglu(m["shared"], flat).astype(jnp.float32)).astype(
            x.dtype)
    with part("moe.route"):
        counts = expert_counts(experts, c.n_routed_experts,
                               None if token_mask is None
                               else token_mask.reshape(B * S))
    return y.reshape(B, S, h), counts


# ---------------------------------------------------------------------------
# forward
# ---------------------------------------------------------------------------


def forward(config: DeepseekConfig, params: dict, input_ids: jax.Array,
            positions: jax.Array | None = None, kv_caches=None, *,
            logit_rows=None, token_mask=None, return_stats: bool = False):
    """Logits [B, S, V] float32 of token ids [B, S]; with `kv_caches`,
    `(logits, new_caches)`.

    `kv_caches` is `(latent, None, cache_len)`: a dense stacked cache
    `[L, B, M, 1, W]` (new rows written at `cache_len`, a scalar or one
    length a row of the batch; the updated cache comes back), one slot's
    view a layer at a time (`serving.cache.LayerwiseSlotView`, the serving
    engine's prefill: each layer's view is gathered where the layer
    attends, and the chunk's own rows `[L, 1, S, 1, W]` come back for the
    engine to write), or the
    serving engine's paged pool (`PagedKV`, with `PagedDecodeMeta` in the
    third place; this step's rows `[L, B, 1, 1, W]` come back for the
    engine to append). `logit_rows` [B] int32: compute the head for that
    one row of every sequence only (logits [B, 1, V]). `token_mask`
    [B, S]: which tokens are real, for the counters. `return_stats`: a
    third result `{"expert_counts": [expert layers, E] int32}`, this
    call's assignments per expert."""
    c = config
    B, S = input_ids.shape
    dense_cache = paged = False
    if kv_caches is not None:
        paged = getattr(kv_caches[0], "is_paged_kv", False)
        dense_cache = not paged
    layerwise = dense_cache and getattr(
        kv_caches[0], "is_layerwise_view", False)
    if positions is None:
        start = kv_caches[2] if dense_cache else 0
        positions = (jnp.reshape(start, (-1, 1))
                     + jnp.arange(S, dtype=jnp.int32)[None, :])
        positions = jnp.broadcast_to(positions, (B, S))
    cos, sin = rope_frequencies(
        c.qk_rope_head_dim,
        rope_table_len(c.max_position_embeddings, kv_caches), c.rope_theta)
    with part("embed"):
        x = params["embed_tokens"]["embedding"][input_ids]
    new_rows, counts = [], []
    for i, layer in enumerate(params["layers"]):
        cache = None
        if dense_cache:
            cache = (layer_view(kv_caches[0], i), None, kv_caches[2])
        elif paged:
            cache = kv_caches
        # a norm is billed with the part it feeds, a residual add with the
        # part it closes
        with part("attn.project"):
            y = rms_norm(x, layer["input_layernorm"]["scale"],
                         c.rms_norm_eps)
        attn, new = _attention(c, layer["attn"], y, cos, sin, positions,
                               cache, i, rows_back=layerwise)
        new_rows.append(new)
        with part("attn.output"):
            x = x + attn
        if "moe" in layer:
            with part("moe.route"):
                y = rms_norm(x, layer["post_attention_layernorm"]["scale"],
                             c.rms_norm_eps)
            out, n = moe_layer(c, layer["moe"], y, token_mask)
            counts.append(n)
            with part("moe.combine"):
                x = x + out
        else:
            with part("mlp"):
                y = rms_norm(x, layer["post_attention_layernorm"]["scale"],
                             c.rms_norm_eps)
                x = x + _swiglu(layer["mlp"], y)
    with part("head"):
        x = rms_norm(x, params["norm"]["scale"], c.rms_norm_eps)
        if logit_rows is not None:
            x = jnp.take_along_axis(x, logit_rows[:, None, None], axis=1)
        logits = jnp.einsum(
            "bsh,hv->bsv", x, params["lm_head"]["kernel"].astype(x.dtype),
            preferred_element_type=jnp.float32)
    if kv_caches is None:
        out = (logits,)
    else:
        third = kv_caches[2] if paged else kv_caches[2] + S
        # the rows a decode step hands the engine to append and a chunk
        # over a slot's layerwise view to write; else the updated views,
        # stacked again
        with part("cache.write" if paged or layerwise else "cache.view"):
            new_rows = jnp.stack(new_rows)
        out = (logits, (new_rows, None, third))
    if return_stats:
        with part("moe.route"):
            stats = {"expert_counts": (
                jnp.stack(counts) if counts
                else jnp.zeros((0, c.n_routed_experts), jnp.int32))}
        out = out + (stats,)
    return out[0] if len(out) == 1 else out


def init_serving_stats(config: DeepseekConfig) -> dict:
    """The device counters one engine program accumulates (see
    `accumulate_serving_stats`), all zero."""
    n = config.num_hidden_layers - config.first_k_dense_replace
    return {"assignments": jnp.zeros((n, config.n_routed_experts), jnp.int32),
            "distinct_experts": jnp.zeros((n,), jnp.int32),
            "calls": jnp.zeros((), jnp.int32)}


def accumulate_serving_stats(total: dict, call: dict) -> dict:
    """Fold one call's `forward(..., return_stats=True)` result into the
    running counters: assignments per expert per expert layer, the sum
    over calls of the DISTINCT experts a call touched in each layer (what
    a call's expert weights cost in bytes), and the calls."""
    with part("moe.route"):
        n = call["expert_counts"]
        return {"assignments": total["assignments"] + n,
                "distinct_experts": total["distinct_experts"]
                + jnp.sum(n > 0, axis=-1, dtype=jnp.int32),
                "calls": total["calls"] + 1}


def init_kv_caches(config: DeepseekConfig, batch: int, max_len: int,
                   dtype=jnp.bfloat16):
    """A dense stacked latent cache: (rows [L, B, M, 1, W], None, 0)."""
    return (jnp.zeros((config.num_hidden_layers, batch, max_len, 1,
                       config.latent_row_width), dtype), None,
            jnp.zeros((), jnp.int32))


generate = build_generate(forward, init_kv_caches)

SERVING = ServingContract(
    forward=forward, cache_spec=cache_spec, logit_rows=True,
    layerwise_views=True,
    init_stats=init_serving_stats, fold_stats=accumulate_serving_stats)
