"""OPT causal LM (the OPT-30B rows of the reference's big-model-inference
benchmark, ref benchmarks/README.md:34-35).

Same TPU-first scan-over-stacked-layers layout. OPT specifics: learned
position embeddings with a +2 offset (an artifact of fairseq's padding
convention that every OPT checkpoint bakes in), pre-LN decoder layers
(do_layer_norm_before=True on all published sizes >= 350M), ReLU MLP,
biases everywhere, and an LM head tied to the token embedding.
"""

from __future__ import annotations

import dataclasses
import functools

import jax
import jax.numpy as jnp

from .common import (
    cross_entropy_loss,
    shifted_padding_masks,
    dense,
    dense_maybe_fp8,
    dot_product_attention,
    layer_norm,
    normal_init,
)
from .decode import (
    build_generate,
    build_streamed_generate,
    decode_attention,
    make_kv_caches,
    scan_decode_layers,
)


@dataclasses.dataclass(frozen=True)
class OPTConfig:
    vocab_size: int = 50272
    hidden_size: int = 7168
    ffn_dim: int = 28672
    num_hidden_layers: int = 48
    num_attention_heads: int = 56
    max_position_embeddings: int = 2048
    layer_norm_eps: float = 1e-5

    @property
    def head_dim(self) -> int:
        return self.hidden_size // self.num_attention_heads

    @classmethod
    def tiny(cls, **overrides) -> "OPTConfig":
        defaults = dict(
            vocab_size=256, hidden_size=64, ffn_dim=128,
            num_hidden_layers=2, num_attention_heads=4,
            max_position_embeddings=128,
        )
        defaults.update(overrides)
        return cls(**defaults)


_POSITION_OFFSET = 2  # fairseq convention baked into every OPT checkpoint


def init_params(config: OPTConfig, key: jax.Array, dtype=jnp.float32) -> dict:
    keys = jax.random.split(key, 8)
    h, L, f = config.hidden_size, config.num_hidden_layers, config.ffn_dim

    def lin(k, d_in, d_out):
        return {
            "kernel": normal_init(k, (L, d_in, d_out), 0.02, dtype),
            "bias": jnp.zeros((L, d_out), dtype),
        }

    def ln():
        return {"scale": jnp.ones((L, h), dtype), "bias": jnp.zeros((L, h), dtype)}

    return {
        "embed_tokens": {"embedding": normal_init(keys[0], (config.vocab_size, h), 0.02, dtype)},
        "embed_positions": {"embedding": normal_init(
            keys[1], (config.max_position_embeddings + _POSITION_OFFSET, h), 0.02, dtype)},
        "layers": {
            "self_attn_layer_norm": ln(),
            "attn": {
                "q_proj": lin(keys[2], h, h),
                "k_proj": lin(keys[3], h, h),
                "v_proj": lin(keys[4], h, h),
                "out_proj": lin(keys[5], h, h),
            },
            "final_layer_norm": ln(),
            "mlp": {
                "fc1": lin(keys[6], h, f),
                "fc2": lin(keys[7], f, h),
            },
        },
        "final_layer_norm": {
            "scale": jnp.ones((h,), dtype), "bias": jnp.zeros((h,), dtype)
        },
    }


def _layer_body(config: OPTConfig, x, layer, mask, positions=None,
                kv_cache=None, fp8=None):
    b, s, h = x.shape
    nh, hd = config.num_attention_heads, config.head_dim
    eps = config.layer_norm_eps
    fa = fp8["attn"] if fp8 is not None else {}
    fm = fp8["mlp"] if fp8 is not None else {}

    y = layer_norm(x, layer["self_attn_layer_norm"]["scale"],
                   layer["self_attn_layer_norm"]["bias"], eps)
    a = layer["attn"]
    q, m_q = dense_maybe_fp8(y, a["q_proj"]["kernel"], fa.get("q_proj"),
                             a["q_proj"]["bias"])
    k, m_k = dense_maybe_fp8(y, a["k_proj"]["kernel"], fa.get("k_proj"),
                             a["k_proj"]["bias"])
    v, m_v = dense_maybe_fp8(y, a["v_proj"]["kernel"], fa.get("v_proj"),
                             a["v_proj"]["bias"])
    q, k, v = (t.reshape(b, s, nh, hd) for t in (q, k, v))
    new_cache = None
    if kv_cache is not None:
        # shared cache-attend step (models/decode.py): dense stacked
        # caches keep the classic extend/mask/einsum path; the serving
        # engine's paged pool streams live pages through the Pallas
        # paged-attention kernel instead of gathering
        attn, new_cache = decode_attention(q, k, v, kv_cache, positions,
                                           mask=mask)
    else:
        attn = dot_product_attention(q, k, v, mask=mask, causal=True)
    o, m_o = dense_maybe_fp8(attn.reshape(b, s, h), a["out_proj"]["kernel"],
                             fa.get("out_proj"), a["out_proj"]["bias"])
    x = x + o

    y = layer_norm(x, layer["final_layer_norm"]["scale"],
                   layer["final_layer_norm"]["bias"], eps)
    y, m_f1 = dense_maybe_fp8(y, layer["mlp"]["fc1"]["kernel"],
                              fm.get("fc1"), layer["mlp"]["fc1"]["bias"])
    y = jax.nn.relu(y)
    y, m_f2 = dense_maybe_fp8(y, layer["mlp"]["fc2"]["kernel"],
                              fm.get("fc2"), layer["mlp"]["fc2"]["bias"])
    x = x + y
    new_fp8 = (
        {"attn": {"q_proj": m_q, "k_proj": m_k, "v_proj": m_v,
                  "out_proj": m_o},
         "mlp": {"fc1": m_f1, "fc2": m_f2}}
        if fp8 is not None else None
    )
    return x, new_cache, new_fp8


def _project_out(config: OPTConfig, params: dict, x):
    x = layer_norm(x, params["final_layer_norm"]["scale"],
                   params["final_layer_norm"]["bias"], config.layer_norm_eps)
    return jnp.einsum(
        "bsh,vh->bsv", x, params["embed_tokens"]["embedding"].astype(x.dtype),
        preferred_element_type=jnp.float32,
    )


def forward(
    config: OPTConfig,
    params: dict,
    input_ids: jax.Array,
    attention_mask: jax.Array | None = None,
    positions: jax.Array | None = None,
    kv_caches=None,
    fp8_state=None,
) -> jax.Array | tuple:
    """Logits [B, S, V] (LM head tied to embed_tokens); with `kv_caches`
    (see `init_kv_caches`), returns (logits, new_caches). `positions` are
    logical 0-based token positions — the fairseq +2 offset is applied
    internally at the embedding lookup. With `fp8_state` (see
    `init_fp8_state`), layer projections run fp8 and the result is
    (logits, new_fp8_state)."""
    if fp8_state is not None and kv_caches is not None:
        raise ValueError("fp8 is a training-path feature; decode "
                         "(kv_caches) runs bf16")
    if positions is None:
        if attention_mask is not None and kv_caches is None:
            # HF OPT derives positions from the mask cumsum, so left-padded
            # batches start real tokens at position 0; pads sit at -1, which
            # lands on the fairseq padding_idx row (1) after the +2 offset
            m = attention_mask.astype(jnp.int32)
            positions = jnp.cumsum(m, axis=1) * m - 1
        elif attention_mask is not None:
            # a masked CACHED prefill can't infer positions: the mask spans
            # the whole cache, not the prompt, so the cumsum trick doesn't
            # apply — silent arange would misplace left-padded tokens
            raise ValueError(
                "opt.forward with kv_caches and attention_mask needs "
                "explicit `positions`: derive them from the prompt's real "
                "tokens (left pads would otherwise get shifted embeddings)"
            )
        else:
            positions = jnp.broadcast_to(
                jnp.arange(input_ids.shape[1]), input_ids.shape
            )
    x = (params["embed_tokens"]["embedding"][input_ids]
         + params["embed_positions"]["embedding"][positions + _POSITION_OFFSET])

    if kv_caches is not None:
        def layer_step(y, layer, cache):
            return _layer_body(config, y, layer, attention_mask, positions,
                               cache)[:2]

        x, (nk, nv) = scan_decode_layers(layer_step, x, params["layers"],
                                         kv_caches)
        return (_project_out(config, params, x),
                (nk, nv, kv_caches[2] + input_ids.shape[1]))

    if fp8_state is not None:
        def scan_body(carry, xs):
            layer, f = xs
            y, _, nf = _layer_body(config, carry, layer, attention_mask,
                                   fp8=f)
            return y, nf

        x, new_fp8 = jax.lax.scan(
            scan_body, x, (params["layers"], fp8_state["layers"])
        )
        return _project_out(config, params, x), {"layers": new_fp8}

    def scan_body(carry, layer):
        return _layer_body(config, carry, layer, attention_mask)[0], None

    x, _ = jax.lax.scan(scan_body, x, params["layers"])
    return _project_out(config, params, x)


def init_kv_caches(config: OPTConfig, batch: int, max_len: int,
                   dtype=jnp.bfloat16):
    return make_kv_caches(config.num_hidden_layers, batch, max_len,
                          config.num_attention_heads, config.head_dim, dtype)


generate = build_generate(forward, init_kv_caches)


def causal_lm_loss(config: OPTConfig, params: dict, batch: dict,
                   fp8_state=None) -> jax.Array | tuple:
    """Next-token loss; with `fp8_state` (mixed_precision="fp8") returns
    (loss, new_fp8_state)."""
    input_ids = batch["input_ids"]
    labels = input_ids[:, 1:]
    attn_mask, mask = shifted_padding_masks(batch.get("attention_mask"))
    out = forward(config, params, input_ids[:, :-1],
                  attention_mask=attn_mask, fp8_state=fp8_state)
    if fp8_state is not None:
        logits, new_fp8 = out
        return cross_entropy_loss(logits, labels, mask), new_fp8
    return cross_entropy_loss(out, labels, mask)


def init_fp8_state(config: OPTConfig, history_len: int | None = None) -> dict:
    """Per-layer delayed-scaling metas for the six layer projections
    (shared builder: ops/fp8.py stacked_fp8_metas; honors the Accelerator's
    FP8RecipeKwargs)."""
    from ..ops.fp8 import stacked_fp8_metas

    return stacked_fp8_metas(config.num_hidden_layers, {
        "attn": ("q_proj", "k_proj", "v_proj", "out_proj"),
        "mlp": ("fc1", "fc2"),
    }, history_len)


@functools.lru_cache(maxsize=8)
def make_decode_layer_step(config: OPTConfig):
    """jit'd single-layer decode body for `streamed_generate` (offloaded
    weights — the reference's OPT-30B cpu-offload benchmark rows)."""

    @jax.jit
    def step(layer, x, positions, kv_cache):
        y, cache, _ = _layer_body(config, x, layer, None, positions, kv_cache)
        return y, cache

    return step


def _embed_decode(config: OPTConfig, res: dict, ids, pos):
    return (res["embed_tokens"]["embedding"][ids]
            + res["embed_positions"]["embedding"][pos + _POSITION_OFFSET])


# _project_out includes the final layer norm, so it is directly the
# streamed path's projection
streamed_generate = build_streamed_generate(
    make_decode_layer_step,
    embed_fn=_embed_decode,
    project_fn=lambda config, res, x: _project_out(config, res, x),
    cache_dims=lambda c: (c.num_attention_heads, c.head_dim),
)
