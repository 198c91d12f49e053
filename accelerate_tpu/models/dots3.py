"""A decoder whose layers are LATENT attention of two kinds, mixed by layer
(`layer_types`), over an expert layer of which one chip holds a SHARE.
dots3-note-prev (`model_type` `dots3_note`) is this block, and is what the
benchmark serves (`chipbench/configs/dots3-note-prev-d5-ep8.json`); its
vision tower, audio encoder and draft head are not on the served text path
and are not here.

Per layer (x of width h; pre-norm, plain residuals; `y = RMSNorm(x)`). Both
kinds are `models/deepseek.py`'s multi-head latent attention at their own
numbers (`mla_project`: `c_q = s_q RMSNorm(y W_qa)`, `q = c_q W_qb` -> H x
(nope | rope), `[c | k_r] = y W_kva`, `c_kv = s_kv RMSNorm(c)`, the rope
parts rotated in adjacent pairs, `k_r` ONE head shared by all), with the
two normed latents RESCALED (`apply_mla_qkv_lora_rescale`: `s_q = sqrt(h /
q_lora_rank)`, `s_kv = sqrt(h / kv_lora_rank)`) and a HEADWISE GATE (`g =
sigmoid(y W_g)`, one scalar a head, times the head's output before `W_o`):

- a `full_attention` layer (the unprefixed keys: 128 heads over a 512 + 64
  row) reads only the keys a learned INDEXER selects, as `models/keye.py`
  does over K/V rows: `q_I = c_q W_qI` (J heads of w lanes), ONE index key
  `k_I = LayerNorm(y W_kI)` a token, both rotated over their first
  `qk_rope_head_dim` lanes, head weights `(y W_w) / sqrt(J w)`, `I[t, s] =
  sum_j w[t, j] relu(q_I[t, j] . k_I[s])`; the query attends the
  `index_topk` positions `s <= t` of largest score (all while `t + 1 <=
  index_topk`; ties to the lower position), softmax over those alone;
- a `sliding_attention` layer (the `swa_` keys: 64 heads over a 1024 + 64
  row, its own rotary base) sees positions `t - sliding_window_size + 1 ..
  t`, no indexer.

Feed-forward: the first `first_k_dense_replace` layers a dense SwiGLU;
every other layer `models/deepseek.py`'s expert layer (sigmoid `noaux_tc`
router over ALL `n_routed_experts`, top-k weights normalised over all k
chosen, a shared expert on every token) of which this program HOLDS the
experts `experts_held = (first, count)`: the expert arrays are `[count,
...]`, an assignment to an absent expert is dropped before the grouped
products (`ops/grouped_experts.py`), and what goes on to the next layer is
the partial sum: the held experts' part plus the shared expert. That is one
chip's share of an expert-parallel layer WITHOUT its exchange; nothing here
stands in for the absent chips. `experts_held` None holds them all.

What the cache holds (`cache_spec`): two LATENT groups (`serving/cache.py`
`GroupedPagedCache`). The full layers' group keeps every position: one row
`[c_kv | k_r | 0]` a token and layer and the index key beside it as a SIDE
ROW under the same page ids. The sliding layers' group is a ring of pages a
slot holding rows of its own (wider) width. The forward reads them in three
forms of the same mathematics:

- no cache: every query over the sequence's own rows;
- views (`kv_caches = (rows a group, (None, None), cache_len)`, the first
  group's in a `WithSide` with its index keys: the engine's prefill chunks
  and its dense decode, and `generate`): this call's rows written, then the
  view attended in the EXPANDED form (at a chunk's 512 queries the absorbed
  form costs 2.2 times the operations and read 24% slower on the chip:
  `PERF.md` section 6, PR 43), masked by the selection on a full layer and
  by position on a sliding one. A full layer's selection is
  `exact_topk_mask_rows` (`ops/sparse_paged_attention.py`): 32 or more
  query rows (a prefill chunk) take the rows kernel, which reads only the
  columns below the chunk's last position and keeps a tile's keys in
  vector memory through its passes; fewer keep XLA's loop.
  Both of these are ONE Pallas kernel a layer, `latent_chunk_attention`
  (`ops/latent_chunk_attention.py`; interpreted where there is no TPU): a
  tile of rows is decompressed through `W_kvb`, scored, masked and folded
  into an online softmax in vector memory, over the tiles that may hold a
  visible key and no others;
- the paged pools (`PagedKV` a group, `PagedDecodeMeta` with a table a
  group): one token a slot in ABSORBED form, a full layer through
  `indexer_paged_scores`, `exact_topk_mask` and
  `sparse_latent_paged_decode_attention` (`ops/sparse_paged_attention.py`),
  a sliding layer through the ring mode of `latent_paged_decode_attention`
  (`ops/latent_paged_attention.py`).

`SERVING`, at the foot: docs/serving.md, "What a served family declares".
"""

from __future__ import annotations

import dataclasses
import math
import types
from typing import Any

import jax
import jax.numpy as jnp

from ..ops.latent_chunk_attention import latent_chunk_attention
from ..ops.sparse_paged_attention import (
    exact_topk_mask,
    exact_topk_mask_rows,
    indexer_paged_scores,
    indexer_scores,
    selection_columns,
)
from .common import (
    add_wide,
    dense,
    layer_norm,
    normal_init,
    part,
    rms_norm,
    rope_frequencies,
    wide_count,  # noqa: F401  (who reads the counters takes it from here)
    write_view,
)
from .contract import CacheSpec, ServingContract, WithSide, ring_positions
from .decode import build_generate, layer_view, rope_table_len
from .deepseek import (
    _absorb_query,
    _kv_b,
    _rope_interleaved,
    _swiglu,
    _unabsorb_output,
    mla_project,
    moe_layer,
)
from .deepseek import accumulate_serving_stats as _accumulate_experts
from .keye import (
    CHUNK_COUNTERS,
    SELECTION_COUNTERS,
    accumulate_chunk_stats,
    init_chunk_stats,
    view_index_score_blocks,
)

FULL, SLIDING = "full_attention", "sliding_attention"
_LANES = 128


@dataclasses.dataclass(frozen=True)
class Dots3Config:
    vocab_size: int = 152064
    hidden_size: int = 5120
    intermediate_size: int = 13824         # the leading dense layers' MLP
    moe_intermediate_size: int = 1536      # one expert
    num_hidden_layers: int = 46
    # one entry a layer, as published; None is the published pattern
    # (layers 0 and 1 full, then every fourth)
    layer_types: Any = None
    # full layers
    num_attention_heads: int = 128
    num_key_value_heads: int = 128
    q_lora_rank: int = 1024
    kv_lora_rank: int = 512
    qk_nope_head_dim: int = 128
    qk_rope_head_dim: int = 64
    v_head_dim: int = 128
    rope_theta: float = 8e7
    rope_scaling: Any = None
    attention_gate_type: str = "headwise"
    index_head_dim: int = 128
    index_n_heads: int = 64
    index_topk: int = 2048
    # sliding layers
    swa_num_attention_heads: int = 64
    swa_num_key_value_heads: int = 64
    swa_q_lora_rank: int = 1024
    swa_kv_lora_rank: int = 1024
    swa_qk_nope_head_dim: int = 192
    swa_qk_rope_head_dim: int = 64
    swa_v_head_dim: int = 128
    swa_rope_theta: float = 50000.0
    swa_attention_gate_type: str = "headwise"
    sliding_window_size: int = 513         # counts the token itself
    apply_mla_qkv_lora_rescale: bool = True
    # experts: the router chooses among `n_routed_experts`; this program
    # holds `experts_held = (first, count)` of them, None = all
    n_routed_experts: int = 256
    experts_held: Any = None
    num_experts_per_tok: int = 8
    n_shared_experts: int = 1
    first_k_dense_replace: int = 1
    moe_layer_freq: int = 1
    routed_scaling_factor: float = 1.0
    norm_topk_prob: bool = True
    scoring_func: str = "sigmoid"
    topk_method: str = "noaux_tc"
    attention_bias: bool = False
    hidden_act: str = "silu"
    tie_word_embeddings: bool = False
    max_position_embeddings: int = 524288
    rms_norm_eps: float = 1e-5
    # view rows the indexer scores at a time (`keye.view_index_score_blocks`)
    kv_block: int = 1024

    def __post_init__(self):
        kinds = self.layer_types
        if kinds is None:
            kinds = [FULL if i < 2 or (i - 1) % 4 == 0 else SLIDING
                     for i in range(self.num_hidden_layers)]
        kinds = tuple(kinds)
        unknown = sorted(set(kinds) - {FULL, SLIDING})
        if unknown or len(kinds) != self.num_hidden_layers:
            raise ValueError(
                f"layer_types names one of {FULL!r} / {SLIDING!r} a layer "
                f"({self.num_hidden_layers}); got {len(kinds)} entries, "
                f"unknown kinds {unknown}")
        object.__setattr__(self, "layer_types", kinds)
        if self.scoring_func != "sigmoid" or self.topk_method != "noaux_tc":
            raise ValueError(
                "only scoring_func='sigmoid' with topk_method='noaux_tc' is "
                f"implemented; got {self.scoring_func!r}, "
                f"{self.topk_method!r}")
        if (self.attention_gate_type != "headwise"
                or self.swa_attention_gate_type != "headwise"):
            raise ValueError(
                "only the headwise attention gate is implemented; got "
                f"{self.attention_gate_type!r}, "
                f"{self.swa_attention_gate_type!r}")
        if (self.num_key_value_heads != self.num_attention_heads
                or self.swa_num_key_value_heads
                != self.swa_num_attention_heads):
            raise ValueError(
                "latent attention decompresses a key and a value for every "
                "query head: num_key_value_heads equals num_attention_heads "
                "in both layer kinds")
        if (self.attention_bias or self.tie_word_embeddings
                or self.hidden_act != "silu" or self.rope_scaling
                or self.moe_layer_freq != 1):
            raise ValueError(
                "only attention_bias=False, tie_word_embeddings=False, "
                "hidden_act='silu', rope_scaling=None and moe_layer_freq=1 "
                "are implemented")
        if self.index_topk < 1 or self.sliding_window_size < 1:
            raise ValueError("index_topk and sliding_window_size are >= 1")
        held = self.experts_held
        if held is not None:
            held = tuple(int(n) for n in held)
            if (len(held) != 2 or held[0] < 0 or held[1] < 1
                    or held[0] + held[1] > self.n_routed_experts):
                raise ValueError(
                    "experts_held is (first, count) inside the "
                    f"{self.n_routed_experts} routed experts; got {held}")
            object.__setattr__(self, "experts_held", held)

    @property
    def experts_here(self) -> int:
        """Routed experts whose weights this program holds."""
        return (self.n_routed_experts if self.experts_held is None
                else self.experts_held[1])

    def layers_of(self, kind: str) -> tuple:
        return tuple(i for i, t in enumerate(self.layer_types) if t == kind)

    def mla(self, kind: str):
        """Layer kind `kind`'s latent attention, under the names
        `models/deepseek.py`'s functions read off a config."""
        p = "" if kind == FULL else "swa_"
        get = lambda name: getattr(self, p + name)  # noqa: E731
        width = get("kv_lora_rank") + get("qk_rope_head_dim")
        return types.SimpleNamespace(
            num_attention_heads=get("num_attention_heads"),
            q_lora_rank=get("q_lora_rank"),
            kv_lora_rank=get("kv_lora_rank"),
            qk_nope_head_dim=get("qk_nope_head_dim"),
            qk_rope_head_dim=get("qk_rope_head_dim"),
            v_head_dim=get("v_head_dim"),
            qk_head_dim=get("qk_nope_head_dim") + get("qk_rope_head_dim"),
            rope_theta=get("rope_theta"),
            latent_width=width,
            latent_row_width=-(-width // _LANES) * _LANES,
            rms_norm_eps=self.rms_norm_eps)

    @classmethod
    def tiny(cls, **overrides) -> "Dots3Config":
        """Test size: a dense full layer, an expert full layer and two
        sliding ones; both rows' value parts whole lane tiles of DIFFERENT
        widths, a `index_topk` and a window small enough to be crossed."""
        defaults = dict(
            vocab_size=256, hidden_size=64, intermediate_size=128,
            moe_intermediate_size=32, num_hidden_layers=4,
            layer_types=(FULL, FULL, SLIDING, SLIDING),
            num_attention_heads=4, num_key_value_heads=4, q_lora_rank=48,
            kv_lora_rank=128, qk_nope_head_dim=16, qk_rope_head_dim=8,
            v_head_dim=16, rope_theta=10000.0, index_head_dim=128,
            index_n_heads=4, index_topk=24, swa_num_attention_heads=2,
            swa_num_key_value_heads=2, swa_q_lora_rank=32,
            swa_kv_lora_rank=256, swa_qk_nope_head_dim=24,
            swa_qk_rope_head_dim=8, swa_v_head_dim=16,
            swa_rope_theta=1000.0, sliding_window_size=9,
            n_routed_experts=8, num_experts_per_tok=2,
            max_position_embeddings=512, kv_block=16)
        defaults.update(overrides)
        return cls(**defaults)


def _groups(config: Dots3Config):
    """[(layer kind, window, the kind's layers)] for the kinds that have
    layers, full first: the cache's groups, in the cache's order."""
    out = [(FULL, None, config.layers_of(FULL)),
           (SLIDING, config.sliding_window_size, config.layers_of(SLIDING))]
    return [g for g in out if g[2]]


def cache_spec(config: Dots3Config):
    """One latent group a layer kind: every position, and the index key
    beside the row, for the full layers; the last `sliding_window_size`
    positions, in rows of their own width, for the sliding ones."""
    groups = _groups(config)
    if groups[0][0] != FULL:
        raise ValueError(
            "a model with no full_attention layer is not implemented: the "
            "cache's first group keeps every position")
    return tuple(CacheSpec(
        num_layers=len(layers), heads=1,
        width=config.mla(kind).latent_row_width, kind="latent",
        window=window, layers=layers,
        side_width=config.index_head_dim if kind == FULL else 0)
        for kind, window, layers in groups)


def init_params(config: Dots3Config, key: jax.Array,
                dtype=jnp.float32) -> dict:
    c = config
    h, f = c.hidden_size, c.moe_intermediate_size
    E, held = c.n_routed_experts, c.experts_here

    def w(k, *shape):
        return {"kernel": normal_init(k, shape, 0.02, dtype)}

    def one(n):
        return {"scale": jnp.ones((n,), dtype)}

    def mlp(k, width):
        k = jax.random.split(k, 3)
        return {"gate_proj": w(k[0], h, width), "up_proj": w(k[1], h, width),
                "down_proj": w(k[2], width, h)}

    layers = []
    for i, kind in enumerate(c.layer_types):
        k = jax.random.split(jax.random.fold_in(key, i), 16)
        m = c.mla(kind)
        H = m.num_attention_heads
        attn = {
            "q_a_proj": w(k[0], h, m.q_lora_rank),
            "q_a_layernorm": one(m.q_lora_rank),
            "q_b_proj": w(k[1], m.q_lora_rank, H * m.qk_head_dim),
            "kv_a_proj": w(k[2], h, m.latent_width),
            "kv_a_layernorm": one(m.kv_lora_rank),
            "kv_b_proj": w(k[3], m.kv_lora_rank,
                           H * (m.qk_nope_head_dim + m.v_head_dim)),
            "o_proj": w(k[4], H * m.v_head_dim, h),
            "gate_proj": w(k[11], h, H),
        }
        if kind == FULL:
            J, wI = c.index_n_heads, c.index_head_dim
            attn["indexer"] = {
                "q_proj": w(k[12], m.q_lora_rank, J * wI),
                "k_proj": w(k[13], h, wI),
                "k_norm": {"scale": jnp.ones((wI,), dtype),
                           "bias": jnp.zeros((wI,), dtype)},
                "weights_proj": w(k[14], h, J)}
        layer = {"input_layernorm": one(h), "attn": attn,
                 "post_attention_layernorm": one(h)}
        if i < c.first_k_dense_replace:
            layer["mlp"] = mlp(k[5], c.intermediate_size)
        else:
            layer["moe"] = {
                "router": {
                    "kernel": normal_init(k[6], (h, E), 0.02, dtype),
                    "e_score_correction_bias": jnp.zeros((E,), jnp.float32)},
                "experts": {
                    "gate_proj": normal_init(k[7], (held, h, f), 0.02, dtype),
                    "up_proj": normal_init(k[8], (held, h, f), 0.02, dtype),
                    "down_proj": normal_init(k[9], (held, f, h), 0.02,
                                             dtype)},
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


def _index_inputs(config, ix, x, c_q, cos, sin, positions):
    """-> (q_I [B, S, J, w], k_I [B, S, 1, w], head weights [B, S, J]
    float32) of a full layer: the index queries from the query latent, the
    index key from the layer's normed input."""
    c = config
    B, S, _ = x.shape
    J, w, r = c.index_n_heads, c.index_head_dim, c.qk_rope_head_dim

    def rotated(t):     # the first `qk_rope_head_dim` lanes of every head
        return jnp.concatenate(
            [_rope_interleaved(t[..., :r], cos, sin, positions), t[..., r:]],
            axis=-1)

    qI = rotated(dense(c_q, ix["q_proj"]["kernel"]).reshape(B, S, J, w))
    kI = layer_norm(dense(x, ix["k_proj"]["kernel"]), ix["k_norm"]["scale"],
                    ix["k_norm"]["bias"], c.rms_norm_eps)
    kI = rotated(kI[:, :, None, :])
    wts = jnp.dot(x, ix["weights_proj"]["kernel"].astype(x.dtype),
                  preferred_element_type=jnp.float32) * (J * w) ** -0.5
    return qI, kI, wts


def _attend_view(m, a, q_nope, q_pe, positions, view, key_pos, window, live,
                 select):
    """The queries over a view of latent rows [B, R, 1, W] at positions
    `key_pos` [B, R] -> [B, S, H, v]: ONE kernel a layer
    (`ops/latent_chunk_attention.py`), a tile of rows decompressed through
    `W_kvb`, scored, masked and folded where it is attended, over the
    tiles that hold a row of `live` alone."""
    return latent_chunk_attention(
        q_nope, q_pe, positions, view[:, :, 0], key_pos,
        _kv_b(m, a, q_nope.dtype), select=select, window=window, live=live)


def _attention(config, kind, a, x, rope, positions, cache, token_mask,
               rows_back: bool = False):
    """-> (attention output [B, S, h], this layer's new cache entry, (keys
    visible, keys selected) of the tokens `token_mask` keeps, zeros on a
    sliding layer). `cache`: None; ("view", rows [B, R, 1, W], kI [B, R, 1,
    w] or None, start [B]); or ("paged", PagedKV rows at its layer, PagedKV
    kI or None, this group's PagedDecodeMeta). The new entry is (rows, kI
    or None): the updated views, with `rows_back` this call's own rows, a
    paged step's one row."""
    c = config
    m = c.mla(kind)
    full = kind == FULL
    window = None if full else c.sliding_window_size
    B, S, _ = x.shape
    H, kvr = m.num_attention_heads, m.kv_lora_rank
    cos, sin = rope
    c_q, q_nope, q_pe, row = mla_project(
        m, a, x, cos, sin, positions, rescale=c.apply_mla_qkv_lora_rescale)
    with part("attn.project"):
        gate = jax.nn.sigmoid(jnp.dot(
            x, a["gate_proj"]["kernel"].astype(x.dtype),
            preferred_element_type=jnp.float32))            # [B, S, H]
    if full:
        with part("attn.indexer"):
            qI, kI, wts = _index_inputs(c, a["indexer"], x, c_q, cos, sin,
                                        positions)
    visible = chosen = jnp.zeros((), jnp.int32)
    select = new_i = None
    paged = cache is not None and cache[0] == "paged"
    # a forward over views also counts the columns its selection scanned
    # and held (`keye.CHUNK_COUNTERS`)
    columns = () if paged else (visible, visible)
    if paged:
        from ..ops.latent_paged_attention import latent_paged_decode_attention
        from ..ops.sparse_paged_attention import (
            sparse_latent_paged_decode_attention,
        )

        _, pool, side, meta = cache
        data = pool.data.reshape(pool.data.shape[:2] + pool.data.shape[3:])
        new_row = row[:, 0].astype(pool.row_dtype)
        with part("attn.project"):
            q_abs = _absorb_query(m, a, q_nope, q_pe)[:, 0]
        scale = 1.0 / math.sqrt(m.qk_head_dim)
        if full:
            kI = kI.astype(side.row_dtype)
            ps = pool.data.shape[3]
            # every cached position's score, and the new token's own at
            # column `length` (its key is not in the pool yet)
            with part("attn.indexer"):
                scores = indexer_paged_scores(
                    qI[:, 0].astype(side.data.dtype), wts[:, 0], side, meta,
                    ps)
                own = indexer_scores(qI[:, 0].astype(kI.dtype), wts[:, 0],
                                     kI[:, 0])                      # [B, 1]
                col = jnp.arange(scores.shape[1], dtype=jnp.int32)[None, :]
                scores = jnp.where(col == meta.lengths[:, None], own, scores)
            with part("attn.select"):
                select = exact_topk_mask(scores, c.index_topk)
            with part("attn.attend"):
                o_lat = sparse_latent_paged_decode_attention(
                    q_abs, new_row, data, pool.layer, meta, select,
                    value_width=kvr, sm_scale=scale)
            select, new_i = select[:, None], kI
        else:
            with part("attn.attend"):
                o_lat = latent_paged_decode_attention(
                    q_abs, new_row, data, pool.layer, meta.table,
                    meta.lengths, value_width=kvr, sm_scale=scale,
                    window=window)
        with part("attn.output"):
            out = _unabsorb_output(m, a, o_lat[:, None].astype(x.dtype))
        new = (new_row[:, None, None, :], new_i)
    else:
        live = None
        if cache is None:
            view, view_i, key_pos = row[:, :, None, :], kI if full else None, \
                positions
            new = None
        else:
            _, view, view_i, start = cache
            R = view.shape[1]
            wraps = window is not None
            view = write_view(view, row[:, :, None, :], start, wraps)
            if full:
                view_i = write_view(view_i, kI, start, False)
            new = (view, view_i)
            if rows_back:
                new = (row[:, :, None, :].astype(view.dtype),
                       kI.astype(view_i.dtype) if full else None)
            last = start + S - 1
            key_pos = ring_positions(R, last)
            # the rows that may hold a visible key: rows are positions
            # until a ring wraps; from then on every row of the (short)
            # ring may
            first = jnp.zeros((), jnp.int32)
            end = jnp.minimum(jnp.max(positions) + 1, R)
            if wraps:
                wrapped = jnp.max(last) >= R
                first = jnp.where(wrapped, 0, jnp.maximum(
                    jnp.min(positions) - window + 1, 0))
                end = jnp.where(wrapped, R, end)
            live = (first, end)
        if full:
            with part("attn.indexer"):
                scores = view_index_score_blocks(
                    c, qI.astype(view_i.dtype), wts, view_i[:, :, 0],
                    positions, key_pos)
            with part("attn.select"):
                # the columns that may hold a visible key: rows are
                # positions in a full layer's view
                end = None if live is None else live[1]
                R = view_i.shape[1]
                select = exact_topk_mask_rows(scores, c.index_topk, end,
                                              columns=R)
                columns = selection_columns((B, S, R), end)
        with part("attn.attend"):
            out = _attend_view(m, a, q_nope, q_pe, positions, view, key_pos,
                               window, live, select)
    if full:
        with part("attn.select"):
            counted = (jnp.ones((B, S), bool) if token_mask is None
                       else token_mask)
            visible = jnp.sum(jnp.where(counted, positions + 1, 0),
                              dtype=jnp.int32)
            chosen = jnp.sum(select & counted[:, :, None], dtype=jnp.int32)
    with part("attn.output"):
        out = (out.astype(jnp.float32) * gate[..., None]).astype(x.dtype)
        out = dense(out.reshape(B, S, H * m.v_head_dim),
                    a["o_proj"]["kernel"])
    return out, new, (visible, chosen) + columns


# ---------------------------------------------------------------------------
# forward
# ---------------------------------------------------------------------------


def forward(config: Dots3Config, params: dict, input_ids: jax.Array,
            positions: jax.Array | None = None, kv_caches=None, *,
            logit_rows=None, token_mask=None, return_stats: bool = False):
    """Logits [B, S, V] float32 of token ids [B, S]; with `kv_caches`,
    `(logits, new_caches)`.

    `kv_caches` is `(rows, vs, third)` with `rows` one entry a cache GROUP
    (`cache_spec`'s order), the first group's a `WithSide` of its latent
    rows and its index keys, and `vs` a None a group (a latent pool has no
    V). Views: rows `[L_g, B, R_g, 1, W_g]`, index keys `[L_0, B, R_0, 1,
    w]`, `third` the rows already written (a scalar or one count a row of
    the batch); the updated views come back. One slot's views a layer at a
    time (`serving.cache.LayerwiseSlotView`s, the serving engine's
    prefill): each layer's view is gathered where the layer attends, and
    the chunk's own rows `[L_g, 1, S, 1, *]` a group come back for the
    engine to write. The serving engine's paged pools: `PagedKV`s and a
    `PagedDecodeMeta` whose `table` is one table a group; this step's rows
    `[L_g, B, 1, 1, *]` a group come back for the engine to append.
    `logit_rows` [B] int32: the head for that one row of every sequence
    only (logits [B, 1, V]). `token_mask` [B, S]: which tokens are real,
    for the counters. `return_stats`: a third result `{"expert_counts":
    [expert layers, n_routed_experts], "assignments_routed",
    "assignments_held": [expert layers] (the real tokens' assignments, and
    those of them to an expert held here), "keys_visible", "keys_selected":
    int32 scalars, summed over the real tokens and the full layers; over
    views also "select_columns_scanned", "select_columns_total"
    (`keye.CHUNK_COUNTERS`)}`."""
    c = config
    B, S = input_ids.shape
    groups = _groups(c)
    paged = kv_caches is not None and getattr(
        kv_caches[0][0].rows, "is_paged_kv", False)
    views = kv_caches is not None and not paged
    layerwise = views and getattr(
        kv_caches[0][0].rows, "is_layerwise_view", False)
    if paged and S != 1:
        raise ValueError(
            f"paged latent attention is one token a slot; got {S} (chunked "
            "prefill attends the slot's gathered views)")
    start = None
    if views:
        start = jnp.broadcast_to(kv_caches[2], (B,)).astype(jnp.int32)
    if positions is None:
        first = start[:, None] if views else 0
        positions = jnp.broadcast_to(
            first + jnp.arange(S, dtype=jnp.int32)[None, :], (B, S))
    table_len = c.max_position_embeddings
    if kv_caches is not None:
        table_len = (rope_table_len(table_len, kv_caches) if paged
                     else max(table_len, kv_caches[0][0].rows.shape[2]))
    rope = {kind: rope_frequencies(c.mla(kind).qk_rope_head_dim, table_len,
                                   c.mla(kind).rope_theta)
            for kind, _, _ in groups}
    # layer -> (its group, its index inside the group)
    place = {layer: (g, j) for g, (_, _, layers) in enumerate(groups)
             for j, layer in enumerate(layers)}
    if paged:
        from ..ops.paged_attention import PagedDecodeMeta

        metas = [PagedDecodeMeta(kv_caches[2].table[g], kv_caches[2].lengths,
                                 rows=kv_caches[2].rows)
                 for g in range(len(groups))]

    def group_rows(g):
        """(the group's rows, its index keys or None), as handed."""
        held = kv_caches[0][g]
        return (held.rows, held.side) if g == 0 else (held, None)

    with part("embed"):
        x = params["embed_tokens"]["embedding"][input_ids]
    new_rows = [[] for _ in groups]
    new_side, counts = [], []
    tallies = (jnp.zeros((), jnp.int32),) * (2 if paged else 4)
    for i, layer in enumerate(params["layers"]):
        g, j = place[i]
        kind = groups[g][0]
        cache = None
        if paged:
            rows, side = group_rows(g)
            cache = ("paged", rows.at_layer(j),
                     None if side is None else side.at_layer(j), metas[g])
        elif views:
            rows, side = group_rows(g)
            cache = ("view", layer_view(rows, j),
                     None if side is None else layer_view(side, j), start)
        # a norm is billed with the part it feeds, a residual add with the
        # part it closes
        with part("attn.project"):
            y = rms_norm(x, layer["input_layernorm"]["scale"],
                         c.rms_norm_eps)
        attn, new, n = _attention(
            c, kind, layer["attn"], y, rope[kind], positions, cache,
            token_mask, rows_back=layerwise)
        if new is not None:
            new_rows[g].append(new[0])
            if new[1] is not None:
                new_side.append(new[1])
        with part("attn.select"):
            tallies = tuple(t + more for t, more in zip(tallies, n))
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
        # the rows a decode step hands the engine to append and a chunk
        # over a slot's layerwise views to write; else the updated views,
        # stacked again
        with part("cache.write" if paged or layerwise else "cache.view"):
            stacked = [jnp.stack(rows) for rows in new_rows]
            stacked[0] = WithSide(stacked[0], jnp.stack(new_side))
        out = (logits, (tuple(stacked), (None,) * len(groups),
                        kv_caches[2] if paged else kv_caches[2] + S))
    if return_stats:
        with part("moe.route"):
            counts = (jnp.stack(counts) if counts
                      else jnp.zeros((0, c.n_routed_experts), jnp.int32))
            first, held = c.experts_held or (0, c.n_routed_experts)
            shares = {
                "assignments_routed": jnp.sum(counts, axis=-1,
                                              dtype=jnp.int32),
                "assignments_held": jnp.sum(counts[:, first:first + held],
                                            axis=-1, dtype=jnp.int32)}
        out = out + (dict(shares, expert_counts=counts,
                          **dict(zip(SELECTION_COUNTERS + CHUNK_COUNTERS,
                                     tallies))),)
    return out[0] if len(out) == 1 else out


# ---------------------------------------------------------------------------
# counters
# ---------------------------------------------------------------------------


def init_serving_stats(config: Dots3Config) -> dict:
    """The device counters one engine program accumulates, all zero: the
    expert layer's (`models/deepseek.py`: assignments per expert of ALL
    the router's experts, held or not); per expert layer the assignments
    the router made for real tokens (`assignments_routed`: tokens x top-k)
    and those that landed on an expert this program holds
    (`assignments_held`), both wide (`wide_count` reads a row); and the
    selection's (`models/keye.py`: keys visible and keys selected, summed
    over real tokens, full layers and calls)."""
    c = config
    n = c.num_hidden_layers - c.first_k_dense_replace
    return {"assignments": jnp.zeros((n, c.n_routed_experts), jnp.int32),
            "distinct_experts": jnp.zeros((n,), jnp.int32),
            "calls": jnp.zeros((), jnp.int32),
            "assignments_routed": jnp.zeros((n, 2), jnp.int32),
            "assignments_held": jnp.zeros((n, 2), jnp.int32),
            "keys_visible": jnp.zeros((2,), jnp.int32),
            "keys_selected": jnp.zeros((2,), jnp.int32)}


def accumulate_serving_stats(total: dict, call: dict) -> dict:
    with part("moe.route"):
        wide = jax.vmap(add_wide)
        shares = {name: wide(total[name], call[name])
                  for name in ("assignments_routed", "assignments_held")}
    with part("attn.select"):
        keys = {name: add_wide(total[name], call[name])
                for name in ("keys_visible", "keys_selected")}
    return dict(_accumulate_experts(total, call), **shares, **keys)


def init_kv_caches(config: Dots3Config, batch: int, max_len: int,
                   dtype=jnp.bfloat16):
    """Views for `generate`: every group keeps `max_len` rows (a prompt is
    one call here, so a sliding group's view never wraps)."""
    views = []
    for kind, _, layers in _groups(config):
        rows = jnp.zeros((len(layers), batch, max_len, 1,
                          config.mla(kind).latent_row_width), dtype)
        if kind == FULL:
            rows = WithSide(rows, jnp.zeros(
                (len(layers), batch, max_len, 1, config.index_head_dim),
                dtype))
        views.append(rows)
    return tuple(views), (None,) * len(views), jnp.zeros((), jnp.int32)


generate = build_generate(forward, init_kv_caches)

SERVING = ServingContract(
    forward=forward, cache_spec=cache_spec, logit_rows=True,
    layerwise_views=True,
    init_stats=init_serving_stats, fold_stats=accumulate_serving_stats,
    init_chunk_stats=init_chunk_stats, fold_chunk_stats=accumulate_chunk_stats)
