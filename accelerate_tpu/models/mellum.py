"""A decoder whose layers differ in KIND: sliding-window and full
attention mixed by layer (`layer_types`), each kind with its own rotary
table (`rope_parameters`), and a softmax-routed expert layer in every
block. Mellum2-12B-A2.5B (`model_type` `mellum`) is this block, and is what
the benchmark serves (`chipbench/configs/mellum2-12b-a2.5b-d8.json`).

Per layer (x of width h, H query heads and Hkv key/value heads of width D,
which is a key of its own: H x D need not be h): `y = RMSNorm(x)`; `q = y
W_q`, `k = y W_k`, `v = y W_v`, no biases; q and k get a per-head RMSNorm
over their D lanes (`qk_norm`: the configuration has no key for it, see
the field); both are rotated in half-split pairs by the LAYER KIND's
table; causal softmax of `q k^T / sqrt(D)` over the keys the kind sees (a
`sliding_attention` layer's query at position i sees key j iff `0 <= i -
j < sliding_window`; a `full_attention` layer's every earlier key), times
v, through `W_o`. Then `y = RMSNorm(x)`, `p = softmax(y W_r)` over all
experts in float32, the `num_experts_per_tok` largest renormalised, and
the weighted sum of those experts' SwiGLU (`ops/grouped_experts.py`:
dropless grouped products, operations proportional to tokens x top-k).
No dense layer and no shared expert.

What the kinds mean for a cache: a full layer keeps every position of a
request, a sliding layer needs the last `sliding_window`. `cache_spec`
therefore declares one GROUP a kind (`serving/cache.py CacheSpec`), and
the serving engine holds a pool a group: pages that grow with the context
for the full layers, a ring of pages a slot for the sliding ones. The
forward reads them in three forms of the same mathematics:

- no cache: every query over the sequence's own keys, in blocks;
- views (`kv_caches = (k a group, v a group, cache_len)`; the engine's
  prefill chunks and its dense decode, and `generate`): a group's view
  holds position p at row `p % rows`, this call's rows are written there,
  and the queries run an online softmax over the view's blocks, masked by
  POSITION. A view that keeps every position never wraps; a ring does,
  and its stale rows come out at negative positions;
- the paged pools (`PagedKV` a group, `PagedDecodeMeta` with a table a
  group): one token a slot through the live-pages kernel
  (`ops/paged_attention.py`), named `paged_decode_attention` on a full
  layer and `paged_decode_attention_window` on a sliding one, whose walk
  the window bounds.

Layers are a LIST of per-layer dicts walked by a Python loop, as in
`models/deepseek.py` and for its reason: a layer's expert matrices (0.8 GB
at the published widths) and the pools reach their kernels as whole
arrays.

`SERVING`, at the foot: docs/serving.md, "What a served family declares".
"""

from __future__ import annotations

import dataclasses
from typing import Any

import jax
import jax.numpy as jnp

from .common import (
    apply_rope,
    blocked_attention,
    dense,
    hashable,
    normal_init,
    part,
    rms_norm,
    rope_frequencies,
    softmax_moe_layer,
    write_view,
)
from .contract import CacheSpec, ServingContract, ring_positions
from .decode import build_generate, layer_view, rope_table_len
from .deepseek import accumulate_serving_stats as _accumulate_experts

FULL, SLIDING = "full_attention", "sliding_attention"


@dataclasses.dataclass(frozen=True)
class MellumConfig:
    vocab_size: int = 98304
    hidden_size: int = 2304
    intermediate_size: int = 7168      # a dense layer's MLP; no layer is one
    moe_intermediate_size: int = 896   # one expert
    num_hidden_layers: int = 28
    num_attention_heads: int = 32
    num_key_value_heads: int = 4
    head_dim: int = 128
    num_experts: int = 64
    num_experts_per_tok: int = 8
    norm_topk_prob: bool = True
    sliding_window: int = 1024
    # one entry a layer, as published; None is the published pattern (every
    # fourth layer full, the others sliding)
    layer_types: Any = None
    mlp_layer_types: Any = None
    # {layer kind: HF rope parameters}, as published; None is theta 500000
    # with no scaling for both kinds
    rope_parameters: Any = None
    max_position_embeddings: int = 131072
    rms_norm_eps: float = 1e-6
    hidden_act: str = "silu"
    attention_bias: bool = False
    tie_word_embeddings: bool = False
    # ASSUMED, not published: q and k get a per-head RMSNorm over their
    # `head_dim` lanes before the rotation, the convention of the block
    # whose keys the published config carries (head_dim apart from hidden /
    # heads, num_experts, moe_intermediate_size, norm_topk_prob). The
    # config has no key for it; the plain reference reads this one.
    qk_norm: bool = True
    # view rows attended at a time
    kv_block: int = 1024

    def __post_init__(self):
        kinds = self.layer_types
        if kinds is None:
            kinds = [FULL if (i + 1) % 4 == 0 else SLIDING
                     for i in range(self.num_hidden_layers)]
        kinds = tuple(kinds)
        unknown = sorted(set(kinds) - {FULL, SLIDING})
        if unknown or len(kinds) != self.num_hidden_layers:
            raise ValueError(
                f"layer_types names one of {FULL!r} / {SLIDING!r} a layer "
                f"({self.num_hidden_layers}); got {len(kinds)} entries, "
                f"unknown kinds {unknown}")
        object.__setattr__(self, "layer_types", kinds)
        mlps = tuple(self.mlp_layer_types
                     or ("sparse",) * self.num_hidden_layers)
        if set(mlps) != {"sparse"} or len(mlps) != self.num_hidden_layers:
            raise ValueError(
                "only mlp_layer_types of 'sparse' in every layer is "
                f"implemented (no dense layer); got {sorted(set(mlps))}, "
                f"{len(mlps)} entries")
        object.__setattr__(self, "mlp_layer_types", mlps)
        rope = dict(self.rope_parameters or {})
        rope = {kind: dict(rope.get(kind) or {"rope_type": "default",
                                              "rope_theta": 500000.0})
                for kind in (FULL, SLIDING)}
        for kind, p in rope.items():
            if p.get("rope_type", "default") not in ("default", "yarn"):
                raise ValueError(
                    f"rope_parameters[{kind!r}]: only rope_type 'default' "
                    f"and 'yarn' are implemented; got {p['rope_type']!r}")
        object.__setattr__(self, "rope_parameters", hashable(rope))
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

    def rope_of(self, kind: str) -> dict:
        """The HF rope parameters of layer kind `kind`."""
        return dict(dict(self.rope_parameters)[kind])

    def layers_of(self, kind: str) -> tuple:
        return tuple(i for i, t in enumerate(self.layer_types) if t == kind)

    @classmethod
    def tiny(cls, **overrides) -> "MellumConfig":
        """Test size: 8 layers in the published pattern, 128-wide heads so
        that the live-pages kernel runs, a window and a YaRN original
        length small enough to be crossed in a test."""
        defaults = dict(
            vocab_size=256, hidden_size=64, intermediate_size=128,
            moe_intermediate_size=32, num_hidden_layers=8,
            num_attention_heads=8, num_key_value_heads=4, head_dim=128,
            num_experts=8, num_experts_per_tok=2, sliding_window=32,
            max_position_embeddings=512, kv_block=16,
            rope_parameters={
                FULL: {"rope_type": "yarn", "rope_theta": 10000.0,
                       "factor": 4.0, "original_max_position_embeddings": 64,
                       "beta_fast": 32, "beta_slow": 1},
                SLIDING: {"rope_type": "default", "rope_theta": 10000.0}})
        defaults.update(overrides)
        return cls(**defaults)


def _groups(config: MellumConfig):
    """[(layer kind, window, the kind's layers)] for the kinds that have
    layers, full first: the cache's groups, in the cache's order."""
    out = [(FULL, None, config.layers_of(FULL)),
           (SLIDING, config.sliding_window, config.layers_of(SLIDING))]
    return [g for g in out if g[2]]


def cache_spec(config: MellumConfig):
    """One group a layer kind: every position for the full layers, the
    last `sliding_window` for the sliding ones."""
    groups = _groups(config)
    if groups[0][0] != FULL:
        raise ValueError(
            "a model with no full_attention layer is not implemented: the "
            "cache's first group keeps every position")
    return tuple(CacheSpec(
        num_layers=len(layers), heads=config.num_key_value_heads,
        width=config.head_dim, window=window, layers=layers)
        for _, window, layers in groups)


def init_params(config: MellumConfig, key: jax.Array,
                dtype=jnp.float32) -> dict:
    c = config
    h, D = c.hidden_size, c.head_dim
    H, Hkv = c.num_attention_heads, c.num_key_value_heads
    f, E = c.moe_intermediate_size, c.num_experts

    def w(k, *shape):
        return {"kernel": normal_init(k, shape, 0.02, dtype)}

    def one(n):
        return {"scale": jnp.ones((n,), dtype)}

    layers = []
    for i in range(c.num_hidden_layers):
        k = jax.random.split(jax.random.fold_in(key, i), 8)
        attn = {"q_proj": w(k[0], h, H * D), "k_proj": w(k[1], h, Hkv * D),
                "v_proj": w(k[2], h, Hkv * D), "o_proj": w(k[3], H * D, h)}
        if c.qk_norm:
            attn.update(q_norm=one(D), k_norm=one(D))
        layers.append({
            "input_layernorm": one(h),
            "attn": attn,
            "post_attention_layernorm": one(h),
            "moe": {
                "router": w(k[4], h, E),
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
        "lm_head": w(jax.random.fold_in(key, 1001), h, c.vocab_size),
    }


# ---------------------------------------------------------------------------
# attention
# ---------------------------------------------------------------------------


def _attend_view(config, q, k, v, positions, view_k, view_v, start, window,
                 rows_back: bool):
    """This call's K/V rows written into a group's view [B, R, Hkv, D] and
    the queries attended over it -> (out, new view k, new view v), or with
    `rows_back` (out, this call's rows k, v [B, S, Hkv, D] as the views
    hold them)."""
    S, R = q.shape[1], view_k.shape[1]
    wraps = window is not None
    view_k = write_view(view_k, k, start, wraps)
    view_v = write_view(view_v, v, start, wraps)
    last = start + S - 1
    blk = min(config.kv_block, R)
    n_blocks = -(-R // blk)
    hi = jnp.max(positions) // blk + 1
    lo = jnp.zeros((), jnp.int32)
    if wraps:
        # rows are positions until the ring wraps; from then on every
        # block of the (short) ring may hold a key the window reaches
        wrapped = jnp.max(last) >= R
        lo = jnp.where(wrapped, 0, jnp.maximum(
            jnp.min(positions) - window + 1, 0) // blk)
        hi = jnp.where(wrapped, n_blocks, hi)
    out = blocked_attention(q, positions, view_k, view_v,
                            ring_positions(R, last), window,
                            config.kv_block, lo, jnp.minimum(hi, n_blocks))
    if rows_back:
        return out, k.astype(view_k.dtype), v.astype(view_v.dtype)
    return out, view_k, view_v


def _attention(config, a, x, rope, positions, window, cache,
               rows_back: bool = False):
    """-> (attention output [B, S, h], this layer's new cache entry).
    `cache`: None; ("view", k [B, R, Hkv, D], v, start [B]); or ("paged",
    PagedKV k at its layer, PagedKV v, this group's PagedDecodeMeta). The
    new entry of a view is the updated view, or with `rows_back` this
    call's own rows."""
    c = config
    B, S, _ = x.shape
    H, Hkv, D = c.num_attention_heads, c.num_key_value_heads, c.head_dim
    with part("attn.project"):
        q = dense(x, a["q_proj"]["kernel"]).reshape(B, S, H, D)
        k = dense(x, a["k_proj"]["kernel"]).reshape(B, S, Hkv, D)
        v = dense(x, a["v_proj"]["kernel"]).reshape(B, S, Hkv, D)
        if c.qk_norm:
            q = rms_norm(q, a["q_norm"]["scale"], c.rms_norm_eps)
            k = rms_norm(k, a["k_norm"]["scale"], c.rms_norm_eps)
        q = apply_rope(q, *rope, positions)
        k = apply_rope(k, *rope, positions)
    new = None
    with part("attn.attend"):
        if cache is None:
            out = blocked_attention(q, positions, k, v, positions, window,
                                    c.kv_block)
        elif cache[0] == "paged":
            from ..ops.paged_attention import paged_decode_attention

            _, pk, pv, meta = cache
            out, new = paged_decode_attention(
                q, k, v, pk, pv, meta, window=window,
                ring=window is not None)
        else:
            _, view_k, view_v, start = cache
            out, *new = _attend_view(c, q, k, v, positions, view_k, view_v,
                                     start, window, rows_back)
    with part("attn.output"):
        out = dense(out.reshape(B, S, H * D), a["o_proj"]["kernel"])
    return out, new


# ---------------------------------------------------------------------------
# forward
# ---------------------------------------------------------------------------


def forward(config: MellumConfig, params: dict, input_ids: jax.Array,
            positions: jax.Array | None = None, kv_caches=None, *,
            logit_rows=None, token_mask=None, return_stats: bool = False):
    """Logits [B, S, V] float32 of token ids [B, S]; with `kv_caches`,
    `(logits, new_caches)`.

    `kv_caches` is `(k, v, third)` with k and v one entry a cache GROUP
    (`cache_spec`'s order). Views: `[L_g, B, R_g, Hkv, D]` a group and
    `third` the rows already written, a scalar or one count a row of the
    batch; the updated views come back. One slot's views a layer at a time
    (`serving.cache.LayerwiseSlotView` a group, the serving engine's
    prefill): each layer's view is gathered where the layer attends, and
    the chunk's own rows `[L_g, 1, S, Hkv, D]` a group come back for the
    engine to write. The serving engine's paged pools:
    `PagedKV` a group and a `PagedDecodeMeta` whose `table` is one table a
    group; this step's rows `[L_g, B, 1, Hkv, D]` a group come back for
    the engine to append. `logit_rows` [B] int32: the head for that one
    row of every sequence only (logits [B, 1, V]). `token_mask` [B, S]:
    which tokens are real, for the counters. `return_stats`: a third
    result `{"expert_counts": [layers, E] int32}`."""
    c = config
    B, S = input_ids.shape
    groups = _groups(c)
    paged = kv_caches is not None and getattr(
        kv_caches[0][0], "is_paged_kv", False)
    views = kv_caches is not None and not paged
    layerwise = views and getattr(
        kv_caches[0][0], "is_layerwise_view", False)
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
    table_len = c.max_position_embeddings
    if kv_caches is not None:
        table_len = (rope_table_len(table_len, kv_caches) if paged
                     else max(table_len, kv_caches[0][0].shape[2]))
    rope = {}
    for kind, _, _ in groups:
        p = c.rope_of(kind)
        rope[kind] = rope_frequencies(
            c.head_dim, table_len, p.get("rope_theta", 500000.0),
            scaling=p if p.get("rope_type", "default") != "default" else None)
    # layer -> (its group, its index inside the group)
    place = {layer: (g, j) for g, (_, _, layers) in enumerate(groups)
             for j, layer in enumerate(layers)}
    if paged:
        from ..ops.paged_attention import PagedDecodeMeta

        metas = [PagedDecodeMeta(kv_caches[2].table[g], kv_caches[2].lengths,
                                 rows=kv_caches[2].rows)
                 for g in range(len(groups))]

    with part("embed"):
        x = params["embed_tokens"]["embedding"][input_ids]
    new_k = [[] for _ in groups]
    new_v = [[] for _ in groups]
    counts = []
    for i, layer in enumerate(params["layers"]):
        g, j = place[i]
        kind, window, _ = groups[g]
        cache = None
        if paged:
            cache = ("paged", kv_caches[0][g].at_layer(j),
                     kv_caches[1][g].at_layer(j), metas[g])
        elif views:
            cache = ("view", layer_view(kv_caches[0][g], j),
                     layer_view(kv_caches[1][g], j), start)
        # a norm is billed with the part it feeds, a residual add with the
        # part it closes
        with part("attn.project"):
            y = rms_norm(x, layer["input_layernorm"]["scale"],
                         c.rms_norm_eps)
        attn, new = _attention(c, layer["attn"], y, rope[kind], positions,
                               window, cache, rows_back=layerwise)
        if new is not None:
            new_k[g].append(new[0])
            new_v[g].append(new[1])
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
            new_k, new_v = (tuple(jnp.stack(rows) for rows in new)
                            for new in (new_k, new_v))
        out = (logits, (new_k, new_v,
                        kv_caches[2] if paged else kv_caches[2] + S))
    if return_stats:
        with part("moe.route"):
            out = out + ({"expert_counts": jnp.stack(counts)},)
    return out[0] if len(out) == 1 else out


def init_serving_stats(config: MellumConfig) -> dict:
    """The device counters one engine program accumulates
    (folded by `models/deepseek.py`'s `accumulate_serving_stats`), all
    zero: assignments per expert per layer, the distinct experts a call
    touched in each layer summed over calls, and the calls."""
    n = config.num_hidden_layers
    return {"assignments": jnp.zeros((n, config.num_experts), jnp.int32),
            "distinct_experts": jnp.zeros((n,), jnp.int32),
            "calls": jnp.zeros((), jnp.int32)}


def init_kv_caches(config: MellumConfig, batch: int, max_len: int,
                   dtype=jnp.bfloat16):
    """Views for `generate`: every group keeps `max_len` rows (a prompt is
    one call here, so a sliding group's view never wraps)."""
    shape = (batch, max_len, config.num_key_value_heads, config.head_dim)
    views = tuple(jnp.zeros((len(layers),) + shape, dtype)
                  for _, _, layers in _groups(config))
    return views, views, jnp.zeros((), jnp.int32)


generate = build_generate(forward, init_kv_caches)

SERVING = ServingContract(
    forward=forward, cache_spec=cache_spec, logit_rows=True,
    layerwise_views=True,
    init_stats=init_serving_stats, fold_stats=_accumulate_experts)
