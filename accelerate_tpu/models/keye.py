"""A decoder whose attention CHOOSES its keys: in every layer a second,
learned scorer (an indexer) ranks the cached positions of a query and
attention is a softmax over the `topk` best of them alone, beside a
softmax-routed expert layer in every block. Keye-VL-2.0-30B-A3B's language
model (`model_type` `KeyeVL2`, `sa_config`) is this block, and is what the
benchmark serves (`chipbench/configs/keye-vl2-30b-a3b-d6.json`); the vision
tower is not on the served text path and is not here.

Per layer (x of width h; pre-norm, plain residuals; `y = RMSNorm(x)`):

- attention as a Qwen3-MoE block: `q = y W_q` (H heads of D), `k = y W_k`,
  `v = y W_v` (Hkv heads of D), no biases; a per-head RMSNorm of q and k
  over their D lanes (`qk_norm`, ASSUMED: the configuration has no key for
  it); multimodal rotary embedding (`rope_scaling.mrope_section`: the D/2
  half-split pairs split over a temporal, a height and a width position
  id; text gives all three the same id, which is the 1-D rotation);
- the indexer (`sa_config`): J = `indexer_num_heads` queries `qI_j = y
  W_qI` of w = `indexer_head_dim` lanes, ONE key `kI = LayerNorm(y W_kI)`
  of w lanes a token, both rotated by the temporal position over all w
  lanes; head weights `a_j = (y W_w)_j / sqrt(J w)`; the index score of
  query t and key s <= t is `I[t, s] = sum_j a[t, j] relu(qI[t, j] .
  kI[s])`, in float32 from operands as they are cached. `S_t` is the
  `topk` positions of largest `I[t, .]` among `s <= t` (all of them while
  `t + 1 <= topk`), ties to the LOWER position;
- `o_t = sum_{s in S_t} softmax_{s in S_t}(q_t . k_s / sqrt(D)) v_s` a
  head, H / Hkv query heads a KV head; then `W_o`;
- `y = RMSNorm(x)`, a float32 softmax over `num_experts` router logits,
  the `num_experts_per_tok` largest renormalised, the weighted sum of those
  experts' SwiGLU (`common.softmax_moe_layer`, as `models/mellum.py`).

ASSUMED beyond `qk_norm` (the configuration's file lists them): qI and a
come from the layer's normed input; the indexer's rotation, its key's
LayerNorm and the `1 / sqrt(J w)` folded into a follow DeepSeek-V3.2's
published indexer, without its Hadamard rotation (an orthogonal map of
qI and kI both, which changes no score) and without its float8 rounding;
`topk` counts TOKENS; `q_chunk_size` / `kv_chunk_size` are the tiles of a
blocked implementation and change no result.

What the cache holds: a K row, a V row AND the index key of every token
and layer, the last as a SIDE ROW under the same page ids
(`serving/cache.py`, `CacheSpec.side_width`), so a prefix hit, a fork and
a release carry it. The forward reads the cache in three forms of the
same mathematics:

- no cache: scores, selection and attention over the sequence's own keys;
- views (`kv_caches = (WithSide(k, kI), v, cache_len)`, all `[L, B, R, *,
  *]`: the engine's prefill chunks and its dense decode, and `generate`):
  this call's rows written at `cache_len`, every query's index scores
  over the view in blocks, the exact selection, and
  `common.blocked_attention` masked by the selection as well as by
  position (a chunk's every query has its own set). The selection is
  `exact_topk_mask_rows`: 32 or more query rows (a prefill chunk) take
  the rows kernel, which reads only the columns below the chunk's last
  position and keeps a tile's keys in vector memory through its passes;
  fewer (a dense decode's one row, a tiny chunk) XLA's loop;
- the paged pools (`WithSide(PagedKV k, PagedKV kI)`, `PagedKV v`,
  `PagedDecodeMeta`): one token a slot through `ops/sparse_paged_attention.py`
  (the selection there is `exact_topk_mask`, XLA's loop over `[slots, R]`).

`SERVING`, at the foot: docs/serving.md, "What a served family declares".
"""

from __future__ import annotations

import dataclasses
from typing import Any

import jax
import jax.numpy as jnp

from ..ops.sparse_paged_attention import (
    exact_topk_mask,
    exact_topk_mask_rows,
    indexer_paged_scores,
    indexer_scores,
    selection_columns,
    sparse_paged_decode_attention,
)
from .common import (
    add_wide,
    apply_mrope,
    apply_rope,
    blocked_attention,
    dense,
    hashable,
    layer_norm,
    normal_init,
    part,
    rms_norm,
    rope_frequencies,
    softmax_moe_layer,
    wide_count,  # noqa: F401  (who reads the counters takes it from here)
    write_view,
)
from .contract import CacheSpec, ServingContract, WithSide
from .decode import build_generate, layer_view, rope_table_len
from .deepseek import accumulate_serving_stats as _accumulate_experts


# the selection's wide device counters, in the order `_attention` tallies
# them: every forward the keys its queries could see and those they
# selected, a forward over views also the columns its selection scanned
# and the columns the views held, times the query rows. The engine keeps
# the last two for its prefill chunks alone (`init_chunk_stats`)
SELECTION_COUNTERS = ("keys_visible", "keys_selected")
CHUNK_COUNTERS = ("select_columns_scanned", "select_columns_total")


@dataclasses.dataclass(frozen=True)
class KeyeConfig:
    vocab_size: int = 151936
    hidden_size: int = 2048
    intermediate_size: int = 6144      # a dense layer's MLP; no layer is one
    moe_intermediate_size: int = 768   # one expert
    num_hidden_layers: int = 48
    num_attention_heads: int = 32
    num_key_value_heads: int = 4
    head_dim: int = 128
    num_experts: int = 128
    num_experts_per_tok: int = 8
    norm_topk_prob: bool = True
    decoder_sparse_step: int = 1
    mlp_only_layers: Any = ()
    # {"mrope_section": [t, h, w], "rope_type": "default"}, as published
    rope_scaling: Any = None
    rope_theta: float = 10000000.0
    # {"indexer_head_dim", "indexer_num_heads", "indexer_num_kv_heads",
    # "topk", "q_chunk_size", "kv_chunk_size"}, as published
    sa_config: Any = None
    max_position_embeddings: int = 262144
    rms_norm_eps: float = 1e-6
    hidden_act: str = "silu"
    attention_bias: bool = False
    tie_word_embeddings: bool = False
    # ASSUMED, not published: see the head of this file
    qk_norm: bool = True
    # view rows attended, and scored, at a time
    kv_block: int = 1024

    def __post_init__(self):
        sa = dict(self.sa_config or {
            "indexer_head_dim": 64, "indexer_num_heads": 16,
            "indexer_num_kv_heads": 1, "topk": 2048, "q_chunk_size": 512,
            "kv_chunk_size": 512})
        if sa.get("indexer_num_kv_heads", 1) != 1 or sa["topk"] < 1:
            raise ValueError(
                "only ONE indexer key a token (indexer_num_kv_heads=1) and "
                f"topk >= 1 are implemented; got {sa}")
        object.__setattr__(self, "sa_config", hashable(sa))
        rope = dict(self.rope_scaling or {"mrope_section": [
            self.head_dim // 8, 3 * self.head_dim // 16,
            3 * self.head_dim // 16], "rope_type": "default"})
        kind = rope.get("rope_type", rope.get("type", "default"))
        sections = tuple(rope.get("mrope_section") or (self.head_dim // 2,))
        if kind != "default" or sum(sections) != self.head_dim // 2 \
                or len(sections) not in (1, 3):
            raise ValueError(
                "only rope_type 'default' with mrope_section of one or "
                "three entries that sum to head_dim / 2 is implemented; "
                f"got {rope}")
        object.__setattr__(self, "rope_scaling", hashable(rope))
        if self.decoder_sparse_step != 1 or tuple(self.mlp_only_layers):
            raise ValueError(
                "only an expert layer in every block (decoder_sparse_step=1,"
                " mlp_only_layers=[]) is implemented")
        object.__setattr__(self, "mlp_only_layers", ())
        if not self.norm_topk_prob:
            raise ValueError(
                "norm_topk_prob=False (the chosen experts' softmax weights "
                "used without renormalising) is not implemented")
        if (self.attention_bias or self.tie_word_embeddings
                or self.hidden_act != "silu"):
            raise ValueError(
                "only attention_bias=False, tie_word_embeddings=False and "
                "hidden_act='silu' are implemented")
        if self.num_attention_heads % self.num_key_value_heads:
            raise ValueError("query heads must be a multiple of KV heads")

    @property
    def indexer(self) -> dict:
        return dict(self.sa_config)

    @property
    def topk(self) -> int:
        return self.indexer["topk"]

    @property
    def mrope_section(self) -> tuple:
        return tuple(dict(self.rope_scaling)["mrope_section"])

    @classmethod
    def tiny(cls, **overrides) -> "KeyeConfig":
        """Test size: 128-wide heads so that the sparse kernel runs, a
        `topk` small enough that a test's context passes it."""
        defaults = dict(
            vocab_size=256, hidden_size=64, intermediate_size=128,
            moe_intermediate_size=32, num_hidden_layers=2,
            num_attention_heads=4, num_key_value_heads=2, head_dim=128,
            num_experts=8, num_experts_per_tok=2,
            max_position_embeddings=512, kv_block=16, rope_theta=10000.0,
            rope_scaling={"mrope_section": [16, 24, 24],
                          "rope_type": "default"},
            sa_config={"indexer_head_dim": 64, "indexer_num_heads": 4,
                       "indexer_num_kv_heads": 1, "topk": 24,
                       "q_chunk_size": 8, "kv_chunk_size": 8})
        defaults.update(overrides)
        return cls(**defaults)


def cache_spec(config: KeyeConfig):
    """K and V rows of every position, and the index key beside them in the
    same pages."""
    return CacheSpec(
        num_layers=config.num_hidden_layers,
        heads=config.num_key_value_heads, width=config.head_dim,
        side_width=config.indexer["indexer_head_dim"])


def init_params(config: KeyeConfig, key: jax.Array,
                dtype=jnp.float32) -> dict:
    c = config
    h, D = c.hidden_size, c.head_dim
    H, Hkv = c.num_attention_heads, c.num_key_value_heads
    f, E = c.moe_intermediate_size, c.num_experts
    J, w = c.indexer["indexer_num_heads"], c.indexer["indexer_head_dim"]

    def mat(k, *shape):
        return {"kernel": normal_init(k, shape, 0.02, dtype)}

    def one(n):
        return {"scale": jnp.ones((n,), dtype)}

    layers = []
    for i in range(c.num_hidden_layers):
        k = jax.random.split(jax.random.fold_in(key, i), 11)
        attn = {"q_proj": mat(k[0], h, H * D), "k_proj": mat(k[1], h, Hkv * D),
                "v_proj": mat(k[2], h, Hkv * D), "o_proj": mat(k[3], H * D, h),
                "indexer": {
                    "q_proj": mat(k[8], h, J * w), "k_proj": mat(k[9], h, w),
                    "k_norm": {"scale": jnp.ones((w,), dtype),
                               "bias": jnp.zeros((w,), dtype)},
                    "weights_proj": mat(k[10], h, J)}}
        if c.qk_norm:
            attn.update(q_norm=one(D), k_norm=one(D))
        layers.append({
            "input_layernorm": one(h),
            "attn": attn,
            "post_attention_layernorm": one(h),
            "moe": {
                "router": mat(k[4], h, E),
                "experts": {
                    "gate_proj": normal_init(k[5], (E, h, f), 0.02, dtype),
                    "up_proj": normal_init(k[6], (E, h, f), 0.02, dtype),
                    "down_proj": normal_init(k[7], (E, f, h), 0.02, dtype)}},
        })
    return {
        "embed_tokens": {"embedding": normal_init(
            jax.random.fold_in(key, 1000), (c.vocab_size, h), 0.02, dtype)},
        "layers": layers,
        "norm": one(h),
        "lm_head": mat(jax.random.fold_in(key, 1001), h, c.vocab_size),
    }


# ---------------------------------------------------------------------------
# attention
# ---------------------------------------------------------------------------


def view_index_score_blocks(config, qI, wts, side_view, q_pos, key_pos):
    """float32 index scores of queries qI [B, S, J, w] (weights wts [B, S,
    J]) at positions `q_pos` [B, S] over the index keys `side_view` [B, R,
    w] at positions `key_pos` [B, R] (negative: nothing there); `-inf`
    where the query does not see the key. In blocks of `kv_block` keys,
    and left in them: [n, B, S, block], column r of the view at `[r //
    block, ..., r % block]`, the columns past R `-inf`. The `[S, J, R]`
    products never exist whole, and no `[B, S, R]` array is laid out: the
    selection reads the blocks where they lie (`exact_topk_mask_rows`)."""
    B, R, w = side_view.shape
    blk = min(config.kv_block, R)
    pad = -R % blk
    side_view = jnp.pad(side_view, ((0, 0), (0, pad), (0, 0)))
    key_pos = jnp.pad(key_pos, ((0, 0), (0, pad)), constant_values=-1)
    n = (R + pad) // blk

    def block(xs):
        keys, pos = xs                                # [B, blk, w], [B, blk]
        s = indexer_scores(qI, wts, keys[:, None])               # [B, S, blk]
        see = (pos[:, None, :] >= 0) & (pos[:, None, :] <= q_pos[:, :, None])
        return jnp.where(see, s, -jnp.inf)

    return jax.lax.map(block, (
        jnp.moveaxis(side_view.reshape(B, n, blk, w), 1, 0),
        jnp.moveaxis(key_pos.reshape(B, n, blk), 1, 0)))      # [n, B, S, blk]


def _attention(config, a, x, rope, rope_i, positions, cache, token_mask,
               rows_back: bool = False):
    """-> (attention output [B, S, h], this layer's new cache entry, (keys
    visible, keys selected) of the tokens `token_mask` keeps, and over
    views or no cache (columns scanned, columns held) by the selection:
    `SELECTION_COUNTERS`, `CHUNK_COUNTERS`). `cache`:
    None; ("view", k [B, R, Hkv, D], v, kI [B, R, 1, w], start [B]); or
    ("paged", PagedKV k at its layer, PagedKV v, PagedKV kI,
    PagedDecodeMeta). `positions` [3, B, S]. The new entry of a view is
    the updated view, or with `rows_back` this call's own rows (k, v, kI
    [B, S, *, *], as the view holds them)."""
    c = config
    B, S, _ = x.shape
    H, Hkv, D = c.num_attention_heads, c.num_key_value_heads, c.head_dim
    J, w = c.indexer["indexer_num_heads"], c.indexer["indexer_head_dim"]
    at = positions[0]                          # the temporal row: causality
    with part("attn.project"):
        q = dense(x, a["q_proj"]["kernel"]).reshape(B, S, H, D)
        k = dense(x, a["k_proj"]["kernel"]).reshape(B, S, Hkv, D)
        v = dense(x, a["v_proj"]["kernel"]).reshape(B, S, Hkv, D)
        if c.qk_norm:
            q = rms_norm(q, a["q_norm"]["scale"], c.rms_norm_eps)
            k = rms_norm(k, a["k_norm"]["scale"], c.rms_norm_eps)
        q = apply_mrope(q, *rope, positions, c.mrope_section)
        k = apply_mrope(k, *rope, positions, c.mrope_section)
    with part("attn.indexer"):
        ix = a["indexer"]
        qI = apply_rope(dense(x, ix["q_proj"]["kernel"]).reshape(B, S, J, w),
                        *rope_i, at)
        kI = layer_norm(dense(x, ix["k_proj"]["kernel"]),
                        ix["k_norm"]["scale"], ix["k_norm"]["bias"],
                        c.rms_norm_eps)
        kI = apply_rope(kI[:, :, None, :], *rope_i, at)        # [B, S, 1, w]
        wts = jnp.dot(x, ix["weights_proj"]["kernel"].astype(x.dtype),
                      preferred_element_type=jnp.float32) * (J * w) ** -0.5
    new = None
    with part("attn.attend"):
        if cache is not None and cache[0] == "paged":
            _, pk, pv, pi, meta = cache
            kI = kI.astype(pi.row_dtype)
            ps = pk.data.shape[3]
            # every cached position's score, and the new token's own at
            # column `length` (its key is not in the pool yet)
            with part("attn.indexer"):
                scores = indexer_paged_scores(
                    qI[:, 0].astype(pi.data.dtype), wts[:, 0], pi, meta, ps)
                own = indexer_scores(qI[:, 0].astype(kI.dtype), wts[:, 0],
                                     kI[:, 0])                      # [B, 1]
                col = jnp.arange(scores.shape[1], dtype=jnp.int32)[None, :]
                scores = jnp.where(col == meta.lengths[:, None], own, scores)
            with part("attn.select"):
                select = exact_topk_mask(scores, c.topk)
            out, (k_row, v_row) = sparse_paged_decode_attention(
                q, k, v, pk, pv, meta, select)
            new = (k_row, v_row, kI)
            select = select[:, None]
            columns = ()
        else:
            if cache is None:
                view_k, view_v, view_i, key_pos = k, v, kI, at
                lo = hi = live = None
            else:
                _, view_k, view_v, view_i, start = cache
                R = view_k.shape[1]
                view_k = write_view(view_k, k, start, False)
                view_v = write_view(view_v, v, start, False)
                view_i = write_view(view_i, kI, start, False)
                new = (view_k, view_v, view_i)
                if rows_back:
                    new = tuple(r.astype(view.dtype)
                                for r, view in zip((k, v, kI), new))
                rows = jnp.arange(R, dtype=jnp.int32)[None, :]
                key_pos = jnp.where(rows < (start + S)[:, None], rows, -1)
                blk = min(c.kv_block, R)
                lo = jnp.zeros((), jnp.int32)
                hi = jnp.minimum(jnp.max(at) // blk + 1, -(-R // blk))
                # the columns that may hold a visible key
                live = jnp.minimum(jnp.max(at) + 1, R)
            with part("attn.indexer"):
                scores = view_index_score_blocks(
                    c, qI.astype(view_i.dtype), wts, view_i[:, :, 0], at,
                    key_pos)
            with part("attn.select"):
                R = view_i.shape[1]
                select = exact_topk_mask_rows(scores, c.topk, live,
                                              columns=R)        # [B, S, R]
                columns = selection_columns((B, S, R), live)
            out = blocked_attention(q, at, view_k, view_v, key_pos, None,
                                    c.kv_block, lo, hi, select=select)
        with part("attn.select"):
            counted = (jnp.ones((B, S), bool) if token_mask is None
                       else token_mask)
            visible = jnp.sum(jnp.where(counted, at + 1, 0),
                              dtype=jnp.int32)
            chosen = jnp.sum(select & counted[:, :, None], dtype=jnp.int32)
    with part("attn.output"):
        out = dense(out.reshape(B, S, H * D), a["o_proj"]["kernel"])
    return out, new, (visible, chosen) + columns


# ---------------------------------------------------------------------------
# forward
# ---------------------------------------------------------------------------


def forward(config: KeyeConfig, params: dict, input_ids: jax.Array,
            positions: jax.Array | None = None, kv_caches=None, *,
            logit_rows=None, token_mask=None, return_stats: bool = False):
    """Logits [B, S, V] float32 of token ids [B, S]; with `kv_caches`,
    `(logits, new_caches)`.

    `positions`: [B, S] (text: the three rotary rows are this one) or
    [3, B, S] (temporal, height, width; causality and the indexer go by
    the temporal row). `kv_caches` is `(WithSide(k, kI), v, third)`
    (`serving/cache.py`). Views: k, v `[L, B, R, Hkv, D]`, kI `[L, B, R, 1,
    w]`, `third` the rows already written (a scalar, or one count a row of
    the batch); the updated views come back. One slot's views a layer at a
    time (`serving.cache.LayerwiseSlotView`s, the serving engine's
    prefill): each layer's view is gathered where the layer attends, and
    the chunk's own rows `[L, 1, S, *, *]` come back for the engine to
    write. The serving engine's paged pools: `PagedKV`s and a
    `PagedDecodeMeta`; this step's rows `[L, B, 1, *, *]` come back for the
    engine to append. `logit_rows` [B] int32: the
    head for that one row of every sequence only (logits [B, 1, V]).
    `token_mask` [B, S]: which tokens are real, for the counters.
    `return_stats`: a third result `{"expert_counts": [layers, E],
    "keys_visible", "keys_selected": int32 scalars, summed over the real
    tokens and the layers; over views also "select_columns_scanned",
    "select_columns_total": the columns the selection read and the columns
    of the view, times the query rows, summed over the layers}`."""
    c = config
    B, S = input_ids.shape
    paged = kv_caches is not None and getattr(
        kv_caches[0].rows, "is_paged_kv", False)
    views = kv_caches is not None and not paged
    layerwise = views and getattr(
        kv_caches[0].rows, "is_layerwise_view", False)
    if paged and S != 1:
        raise ValueError(
            f"sparse paged decode attention is one token a slot; got {S} "
            "(chunked prefill attends the slot's gathered views)")
    start = None
    if views:
        start = jnp.broadcast_to(kv_caches[2], (B,)).astype(jnp.int32)
    if positions is None:
        first = start[:, None] if views else 0
        positions = jnp.broadcast_to(
            first + jnp.arange(S, dtype=jnp.int32)[None, :], (B, S))
    if positions.ndim == 2:
        positions = jnp.broadcast_to(positions[None], (3, B, S))
    table_len = c.max_position_embeddings
    if kv_caches is not None:
        table_len = (rope_table_len(table_len, kv_caches) if paged
                     else max(table_len, kv_caches[0].rows.shape[2]))
    rope = rope_frequencies(c.head_dim, table_len, c.rope_theta)
    rope_i = rope_frequencies(c.indexer["indexer_head_dim"], table_len,
                              c.rope_theta)

    with part("embed"):
        x = params["embed_tokens"]["embedding"][input_ids]
    new_k, new_v, new_i, counts = [], [], [], []
    # a decode step over the paged pools counts the keys; a chunk over
    # views also the columns its selection scanned and held
    tallies = (jnp.zeros((), jnp.int32),) * (2 if paged else 4)
    for i, layer in enumerate(params["layers"]):
        cache = None
        if paged:
            cache = ("paged", kv_caches[0].rows.at_layer(i),
                     kv_caches[1].at_layer(i), kv_caches[0].side.at_layer(i),
                     kv_caches[2])
        elif views:
            cache = ("view", *(layer_view(a, i) for a in (
                kv_caches[0].rows, kv_caches[1], kv_caches[0].side)), start)
        # a norm is billed with the part it feeds, a residual add with the
        # part it closes
        with part("attn.project"):
            y = rms_norm(x, layer["input_layernorm"]["scale"],
                         c.rms_norm_eps)
        attn, new, n = _attention(
            c, layer["attn"], y, rope, rope_i, positions, cache, token_mask,
            rows_back=layerwise)
        if new is not None:
            new_k.append(new[0])
            new_v.append(new[1])
            new_i.append(new[2])
        with part("attn.select"):
            tallies = tuple(t + more for t, more in zip(tallies, n))
        with part("attn.output"):
            x = x + attn
        with part("moe.route"):
            y = rms_norm(x, layer["post_attention_layernorm"]["scale"],
                         c.rms_norm_eps)
        out, n = softmax_moe_layer(c, layer["moe"], y, token_mask)
        counts.append(n)
        with part("moe.combine"):
            x = x + out
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
        # the rows a decode step hands the engine to append and a chunk
        # over a slot's layerwise views to write; else the updated views,
        # stacked again
        with part("cache.write" if paged or layerwise else "cache.view"):
            new = (WithSide(jnp.stack(new_k), jnp.stack(new_i)),
                   jnp.stack(new_v))
        out = (logits, new + (kv_caches[2] if paged else kv_caches[2] + S,))
    if return_stats:
        with part("moe.route"):
            counts = jnp.stack(counts)
        out = out + ({"expert_counts": counts,
                      **dict(zip(SELECTION_COUNTERS + CHUNK_COUNTERS,
                                 tallies))},)
    return out[0] if len(out) == 1 else out


# ---------------------------------------------------------------------------
# counters
# ---------------------------------------------------------------------------

def init_serving_stats(config: KeyeConfig) -> dict:
    """The device counters one engine program accumulates, all zero: the
    expert layer's (`models/deepseek.py`), and the keys the program's
    queries could see and the keys they selected, summed over real tokens,
    layers and calls (`wide_count` reads them)."""
    n = config.num_hidden_layers
    return {"assignments": jnp.zeros((n, config.num_experts), jnp.int32),
            "distinct_experts": jnp.zeros((n,), jnp.int32),
            "calls": jnp.zeros((), jnp.int32),
            "keys_visible": jnp.zeros((2,), jnp.int32),
            "keys_selected": jnp.zeros((2,), jnp.int32)}


def accumulate_serving_stats(total: dict, call: dict) -> dict:
    with part("attn.select"):
        keys = dict(
            keys_visible=add_wide(total["keys_visible"],
                                  call["keys_visible"]),
            keys_selected=add_wide(total["keys_selected"],
                                   call["keys_selected"]))
    return dict(_accumulate_experts(total, call), **keys)


def init_chunk_stats(config) -> dict:
    """The device counters that the engine's `prefill` alone accumulates
    (`CHUNK_COUNTERS`, wide, all zero): they are no argument of `decode`."""
    return {name: jnp.zeros((2,), jnp.int32) for name in CHUNK_COUNTERS}


def accumulate_chunk_stats(total: dict, call: dict) -> dict:
    with part("attn.select"):
        return {name: add_wide(total[name], call[name])
                for name in CHUNK_COUNTERS}


def init_kv_caches(config: KeyeConfig, batch: int, max_len: int,
                   dtype=jnp.bfloat16):
    """Views for `generate`."""
    L = config.num_hidden_layers
    kv = jnp.zeros((L, batch, max_len, config.num_key_value_heads,
                    config.head_dim), dtype)
    side = jnp.zeros((L, batch, max_len, 1,
                      config.indexer["indexer_head_dim"]), dtype)
    return WithSide(kv, side), kv, jnp.zeros((), jnp.int32)


generate = build_generate(forward, init_kv_caches)

SERVING = ServingContract(
    forward=forward, cache_spec=cache_spec, logit_rows=True,
    layerwise_views=True,
    init_stats=init_serving_stats, fold_stats=accumulate_serving_stats,
    init_chunk_stats=init_chunk_stats, fold_chunk_stats=accumulate_chunk_stats)
