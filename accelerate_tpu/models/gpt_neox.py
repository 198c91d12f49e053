"""GPT-NeoX causal LM (the GPT-NeoX-20B row of the reference's
big-model-inference benchmark, ref benchmarks/README.md:31-32).

Same TPU-first scan-over-stacked-layers layout as llama/gpt2. NeoX
specifics: parallel residual (attention and MLP both read the same layer
input and add into it together), partial rotary embeddings (first
`rotary_pct` of each head's dims rotate, the rest pass through), a fused
per-head-interleaved qkv projection, LayerNorms with biases, and an untied
`embed_out` LM head.
"""

from __future__ import annotations

import dataclasses
import functools

import jax
import jax.numpy as jnp

from .common import (
    apply_rope,
    cross_entropy_loss,
    dense,
    dense_maybe_fp8,
    dot_product_attention,
    layer_norm,
    normal_init,
    rope_frequencies,
    shifted_padding_masks,
)
from .decode import (
    build_generate,
    build_streamed_generate,
    decode_attention,
    make_kv_caches,
    rope_table_len,
    scan_decode_layers,
)


@dataclasses.dataclass(frozen=True)
class GPTNeoXConfig:
    vocab_size: int = 50432
    hidden_size: int = 6144
    intermediate_size: int = 24576
    num_hidden_layers: int = 44
    num_attention_heads: int = 64
    max_position_embeddings: int = 2048
    rotary_pct: float = 0.25
    rotary_emb_base: float = 10000.0
    layer_norm_eps: float = 1e-5
    use_parallel_residual: bool = True

    @property
    def head_dim(self) -> int:
        return self.hidden_size // self.num_attention_heads

    @property
    def rotary_ndims(self) -> int:
        return int(self.head_dim * self.rotary_pct)

    @classmethod
    def tiny(cls, **overrides) -> "GPTNeoXConfig":
        defaults = dict(
            vocab_size=256, hidden_size=64, intermediate_size=128,
            num_hidden_layers=2, num_attention_heads=4,
            max_position_embeddings=128,
        )
        defaults.update(overrides)
        return cls(**defaults)


def init_params(config: GPTNeoXConfig, key: jax.Array, dtype=jnp.float32) -> dict:
    keys = jax.random.split(key, 7)
    h, L, f = config.hidden_size, config.num_hidden_layers, config.intermediate_size

    def lin(k, d_in, d_out):
        return {
            "kernel": normal_init(k, (L, d_in, d_out), 0.02, dtype),
            "bias": jnp.zeros((L, d_out), dtype),
        }

    def ln():
        return {"scale": jnp.ones((L, h), dtype), "bias": jnp.zeros((L, h), dtype)}

    return {
        "embed_in": {"embedding": normal_init(keys[0], (config.vocab_size, h), 0.02, dtype)},
        "layers": {
            "input_layernorm": ln(),
            "attn": {
                "query_key_value": lin(keys[1], h, 3 * h),
                "dense": lin(keys[2], h, h),
            },
            "post_attention_layernorm": ln(),
            "mlp": {
                "dense_h_to_4h": lin(keys[3], h, f),
                "dense_4h_to_h": lin(keys[4], f, h),
            },
        },
        "final_layer_norm": {
            "scale": jnp.ones((h,), dtype), "bias": jnp.zeros((h,), dtype)
        },
        "embed_out": {"kernel": normal_init(keys[5], (h, config.vocab_size), 0.02, dtype)},
    }


def _partial_rope(x, cos, sin, positions, rotary_ndims: int):
    """Rotate only the first `rotary_ndims` of each head's dims."""
    rot, rest = x[..., :rotary_ndims], x[..., rotary_ndims:]
    rot = apply_rope(rot, cos, sin, positions)
    return jnp.concatenate([rot, rest], axis=-1)


def _layer_body(config: GPTNeoXConfig, x, layer, cos, sin, positions, mask,
                kv_cache=None, fp8=None):
    b, s, h = x.shape
    nh, hd = config.num_attention_heads, config.head_dim
    eps = config.layer_norm_eps
    fa = fp8["attn"] if fp8 is not None else {}
    fm = fp8["mlp"] if fp8 is not None else {}

    attn_in = layer_norm(x, layer["input_layernorm"]["scale"],
                         layer["input_layernorm"]["bias"], eps)
    qkv, m_qkv = dense_maybe_fp8(
        attn_in, layer["attn"]["query_key_value"]["kernel"],
        fa.get("query_key_value"), layer["attn"]["query_key_value"]["bias"])
    # NeoX packs qkv per head: out dim layout is [head][q|k|v][head_dim]
    qkv = qkv.reshape(b, s, nh, 3, hd)
    q, k, v = qkv[..., 0, :], qkv[..., 1, :], qkv[..., 2, :]
    q = _partial_rope(q, cos, sin, positions, config.rotary_ndims)
    k = _partial_rope(k, cos, sin, positions, config.rotary_ndims)
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
    attn_out, m_ad = dense_maybe_fp8(
        attn.reshape(b, s, h), layer["attn"]["dense"]["kernel"],
        fa.get("dense"), layer["attn"]["dense"]["bias"])

    mlp_in = (
        layer_norm(x, layer["post_attention_layernorm"]["scale"],
                   layer["post_attention_layernorm"]["bias"], eps)
        if config.use_parallel_residual
        else layer_norm(x + attn_out,
                        layer["post_attention_layernorm"]["scale"],
                        layer["post_attention_layernorm"]["bias"], eps)
    )
    y, m_up = dense_maybe_fp8(
        mlp_in, layer["mlp"]["dense_h_to_4h"]["kernel"],
        fm.get("dense_h_to_4h"), layer["mlp"]["dense_h_to_4h"]["bias"])
    y = jax.nn.gelu(y.astype(jnp.float32), approximate=False).astype(x.dtype)
    mlp_out, m_dn = dense_maybe_fp8(
        y, layer["mlp"]["dense_4h_to_h"]["kernel"],
        fm.get("dense_4h_to_h"), layer["mlp"]["dense_4h_to_h"]["bias"])

    new_fp8 = (
        {"attn": {"query_key_value": m_qkv, "dense": m_ad},
         "mlp": {"dense_h_to_4h": m_up, "dense_4h_to_h": m_dn}}
        if fp8 is not None else None
    )
    # both residual modes add the same three terms — the difference is
    # entirely in what mlp_in read above (x alone vs x + attn_out)
    return x + attn_out + mlp_out, new_cache, new_fp8


def _project_out(config: GPTNeoXConfig, params: dict, x):
    x = layer_norm(x, params["final_layer_norm"]["scale"],
                   params["final_layer_norm"]["bias"], config.layer_norm_eps)
    return jnp.einsum(
        "bsh,hv->bsv", x, params["embed_out"]["kernel"].astype(x.dtype),
        preferred_element_type=jnp.float32,
    )


def forward(
    config: GPTNeoXConfig,
    params: dict,
    input_ids: jax.Array,
    attention_mask: jax.Array | None = None,
    positions: jax.Array | None = None,
    kv_caches=None,
    fp8_state=None,
) -> jax.Array | tuple:
    """Logits [B, S, V] via the untied embed_out head; with `kv_caches`
    (see `init_kv_caches`), returns (logits, new_caches) — the
    incremental-decode path behind `generate`. With `fp8_state` (see
    `init_fp8_state`), layer projections run fp8 and the result is
    (logits, new_fp8_state)."""
    if fp8_state is not None and kv_caches is not None:
        raise ValueError("fp8 is a training-path feature; decode "
                         "(kv_caches) runs bf16")
    x = params["embed_in"]["embedding"][input_ids]
    if positions is None:
        positions = jnp.broadcast_to(
            jnp.arange(input_ids.shape[1]), input_ids.shape
        )
    cos, sin = rope_frequencies(
        config.rotary_ndims,
        rope_table_len(config.max_position_embeddings, kv_caches),
        config.rotary_emb_base,
    )

    if kv_caches is not None:
        def layer_step(y, layer, cache):
            return _layer_body(config, y, layer, cos, sin, positions,
                               attention_mask, cache)[:2]

        x, (nk, nv) = scan_decode_layers(layer_step, x, params["layers"],
                                         kv_caches)
        return (_project_out(config, params, x),
                (nk, nv, kv_caches[2] + input_ids.shape[1]))

    if fp8_state is not None:
        def scan_body(carry, xs):
            layer, f = xs
            y, _, nf = _layer_body(config, carry, layer, cos, sin, positions,
                                   attention_mask, fp8=f)
            return y, nf

        x, new_fp8 = jax.lax.scan(
            scan_body, x, (params["layers"], fp8_state["layers"])
        )
        return _project_out(config, params, x), {"layers": new_fp8}

    def scan_body(carry, layer):
        return _layer_body(config, carry, layer, cos, sin, positions,
                           attention_mask)[0], None

    x, _ = jax.lax.scan(scan_body, x, params["layers"])
    return _project_out(config, params, x)


def init_kv_caches(config: GPTNeoXConfig, batch: int, max_len: int,
                   dtype=jnp.bfloat16):
    return make_kv_caches(config.num_hidden_layers, batch, max_len,
                          config.num_attention_heads, config.head_dim, dtype)


generate = build_generate(forward, init_kv_caches)


def causal_lm_loss(config: GPTNeoXConfig, params: dict, batch: dict,
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


def init_fp8_state(config: GPTNeoXConfig,
                   history_len: int | None = None) -> dict:
    """Per-layer delayed-scaling metas for the four layer projections
    (shared builder: ops/fp8.py stacked_fp8_metas; honors the Accelerator's
    FP8RecipeKwargs)."""
    from ..ops.fp8 import stacked_fp8_metas

    return stacked_fp8_metas(config.num_hidden_layers, {
        "attn": ("query_key_value", "dense"),
        "mlp": ("dense_h_to_4h", "dense_4h_to_h"),
    }, history_len)


@functools.lru_cache(maxsize=8)
def make_decode_layer_step(config: GPTNeoXConfig):
    """jit'd single-layer decode body for `streamed_generate` (offloaded
    weights — the reference's GPT-NeoX-20B cpu-offload benchmark rows)."""

    @jax.jit
    def step(layer, x, positions, kv_cache):
        # size the table by the cache reach too: decoding past
        # max_position_embeddings must extend the rotary angles, not let the
        # gather clamp every overflow token to the last row
        max_len = max(config.max_position_embeddings, kv_cache[0].shape[1])
        cos, sin = rope_frequencies(
            config.rotary_ndims, max_len, config.rotary_emb_base,
        )
        y, cache, _ = _layer_body(config, x, layer, cos, sin, positions,
                                  None, kv_cache)
        return y, cache

    return step


# _project_out includes the final layer norm, so it is directly the
# streamed path's projection
streamed_generate = build_streamed_generate(
    make_decode_layer_step,
    embed_fn=lambda config, res, ids, pos: res["embed_in"]["embedding"][ids],
    project_fn=lambda config, res, x: _project_out(config, res, x),
    cache_dims=lambda c: (c.num_attention_heads, c.head_dim),
)
