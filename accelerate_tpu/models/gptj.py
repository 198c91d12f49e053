"""GPT-J causal LM (the GPT-J-6B rows of the reference's big-model-inference
benchmark, ref benchmarks/README.md:29-30).

Same TPU-first scan-over-stacked-layers layout as the other families.
GPT-J specifics: a SINGLE LayerNorm per layer feeding both attention and
MLP (parallel residual), partial rotary embeddings in the interleaved
"rotate every two" convention (unlike llama/NeoX's rotate-half), no
attention biases, and an untied LM head WITH bias.
"""

from __future__ import annotations

import dataclasses
import functools

import jax
import jax.numpy as jnp
import numpy as np

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
    rope_table_len,
    scan_decode_layers,
)


@dataclasses.dataclass(frozen=True)
class GPTJConfig:
    vocab_size: int = 50400
    hidden_size: int = 4096          # n_embd
    num_hidden_layers: int = 28      # n_layer
    num_attention_heads: int = 16    # n_head
    max_position_embeddings: int = 2048  # n_positions
    rotary_dim: int = 64
    layer_norm_epsilon: float = 1e-5

    @property
    def head_dim(self) -> int:
        return self.hidden_size // self.num_attention_heads

    @classmethod
    def tiny(cls, **overrides) -> "GPTJConfig":
        defaults = dict(
            vocab_size=256, hidden_size=64, num_hidden_layers=2,
            num_attention_heads=4, max_position_embeddings=128, rotary_dim=8,
        )
        defaults.update(overrides)
        return cls(**defaults)


def init_params(config: GPTJConfig, key: jax.Array, dtype=jnp.float32) -> dict:
    keys = jax.random.split(key, 8)
    h, L = config.hidden_size, config.num_hidden_layers

    def lin(k, d_in, d_out, bias=True):
        out = {"kernel": normal_init(k, (L, d_in, d_out), 0.02, dtype)}
        if bias:
            out["bias"] = jnp.zeros((L, d_out), dtype)
        return out

    return {
        "wte": {"embedding": normal_init(keys[0], (config.vocab_size, h), 0.02, dtype)},
        "layers": {
            "ln_1": {"scale": jnp.ones((L, h), dtype), "bias": jnp.zeros((L, h), dtype)},
            "attn": {
                "q_proj": lin(keys[1], h, h, bias=False),
                "k_proj": lin(keys[2], h, h, bias=False),
                "v_proj": lin(keys[3], h, h, bias=False),
                "out_proj": lin(keys[4], h, h, bias=False),
            },
            "mlp": {
                "fc_in": lin(keys[5], h, 4 * h),
                "fc_out": lin(keys[6], 4 * h, h),
            },
        },
        "ln_f": {"scale": jnp.ones((h,), dtype), "bias": jnp.zeros((h,), dtype)},
        "lm_head": {
            "kernel": normal_init(keys[7], (h, config.vocab_size), 0.02, dtype),
            "bias": jnp.zeros((config.vocab_size,), dtype),
        },
    }


def _interleaved_rope_tables(rotary_dim: int, max_len: int, dtype=jnp.float32):
    inv_freq = 1.0 / (10000.0 ** (np.arange(0, rotary_dim, 2) / rotary_dim))
    t = np.arange(max_len)
    freqs = np.einsum("i,j->ij", t, inv_freq)          # [T, rot/2]
    return jnp.asarray(np.sin(freqs), dtype), jnp.asarray(np.cos(freqs), dtype)


def _rotate_every_two(x):
    x1 = x[..., ::2]
    x2 = x[..., 1::2]
    return jnp.stack((-x2, x1), axis=-1).reshape(x.shape)


def _apply_interleaved_rope(x, sin, cos, positions):
    """GPT-J rotary: pairs are interleaved (dims 0&1, 2&3, ...) rather than
    split-half; sin/cos repeat per pair. Rotation math runs f32 but the
    output keeps x's dtype (bf16 checkpoints must not upcast the residual
    stream — the layer scan carry dtype is fixed)."""
    sin_p = jnp.repeat(sin[positions], 2, axis=-1)[:, :, None, :]
    cos_p = jnp.repeat(cos[positions], 2, axis=-1)[:, :, None, :]
    xf = x.astype(jnp.float32)
    return (xf * cos_p + _rotate_every_two(xf) * sin_p).astype(x.dtype)


def _layer_body(config: GPTJConfig, x, layer, sin, cos, positions, mask,
                kv_cache=None, fp8=None):
    b, s, h = x.shape
    nh, hd, rot = config.num_attention_heads, config.head_dim, config.rotary_dim
    eps = config.layer_norm_epsilon
    fa = fp8["attn"] if fp8 is not None else {}
    fm = fp8["mlp"] if fp8 is not None else {}

    y = layer_norm(x, layer["ln_1"]["scale"], layer["ln_1"]["bias"], eps)
    q, m_q = dense_maybe_fp8(y, layer["attn"]["q_proj"]["kernel"], fa.get("q_proj"))
    k, m_k = dense_maybe_fp8(y, layer["attn"]["k_proj"]["kernel"], fa.get("k_proj"))
    v, m_v = dense_maybe_fp8(y, layer["attn"]["v_proj"]["kernel"], fa.get("v_proj"))
    q, k, v = (t.reshape(b, s, nh, hd) for t in (q, k, v))
    q = jnp.concatenate([
        _apply_interleaved_rope(q[..., :rot], sin, cos, positions),
        q[..., rot:],
    ], axis=-1)
    k = jnp.concatenate([
        _apply_interleaved_rope(k[..., :rot], sin, cos, positions),
        k[..., rot:],
    ], axis=-1)
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
    attn_out, m_o = dense_maybe_fp8(
        attn.reshape(b, s, h), layer["attn"]["out_proj"]["kernel"],
        fa.get("out_proj"))

    # parallel residual off the SAME ln_1 output
    m, m_fi = dense_maybe_fp8(y, layer["mlp"]["fc_in"]["kernel"],
                              fm.get("fc_in"), layer["mlp"]["fc_in"]["bias"])
    m = jax.nn.gelu(m.astype(jnp.float32), approximate=True).astype(x.dtype)
    mlp_out, m_fo = dense_maybe_fp8(m, layer["mlp"]["fc_out"]["kernel"],
                                    fm.get("fc_out"),
                                    layer["mlp"]["fc_out"]["bias"])
    new_fp8 = (
        {"attn": {"q_proj": m_q, "k_proj": m_k, "v_proj": m_v,
                  "out_proj": m_o},
         "mlp": {"fc_in": m_fi, "fc_out": m_fo}}
        if fp8 is not None else None
    )
    return x + attn_out + mlp_out, new_cache, new_fp8


def _project_out(config: GPTJConfig, params: dict, x):
    x = layer_norm(x, params["ln_f"]["scale"], params["ln_f"]["bias"],
                   config.layer_norm_epsilon)
    return jnp.einsum(
        "bsh,hv->bsv", x, params["lm_head"]["kernel"].astype(x.dtype),
        preferred_element_type=jnp.float32,
    ) + params["lm_head"]["bias"].astype(jnp.float32)


def forward(
    config: GPTJConfig,
    params: dict,
    input_ids: jax.Array,
    attention_mask: jax.Array | None = None,
    positions: jax.Array | None = None,
    kv_caches=None,
    fp8_state=None,
) -> jax.Array | tuple:
    """Logits [B, S, V]; with `kv_caches` (see `init_kv_caches`), returns
    (logits, new_caches) — the incremental-decode path behind `generate`.
    With `fp8_state` (see `init_fp8_state`), layer projections run fp8 and
    the result is (logits, new_fp8_state)."""
    if fp8_state is not None and kv_caches is not None:
        raise ValueError("fp8 is a training-path feature; decode "
                         "(kv_caches) runs bf16")
    x = params["wte"]["embedding"][input_ids]
    if positions is None:
        positions = jnp.broadcast_to(
            jnp.arange(input_ids.shape[1]), input_ids.shape
        )
    sin, cos = _interleaved_rope_tables(
        config.rotary_dim,
        rope_table_len(config.max_position_embeddings, kv_caches))

    if kv_caches is not None:
        def layer_step(y, layer, cache):
            return _layer_body(config, y, layer, sin, cos, positions,
                               attention_mask, cache)[:2]

        x, (nk, nv) = scan_decode_layers(layer_step, x, params["layers"],
                                         kv_caches)
        return (_project_out(config, params, x),
                (nk, nv, kv_caches[2] + input_ids.shape[1]))

    if fp8_state is not None:
        def scan_body(carry, xs):
            layer, f = xs
            y, _, nf = _layer_body(config, carry, layer, sin, cos, positions,
                                   attention_mask, fp8=f)
            return y, nf

        x, new_fp8 = jax.lax.scan(
            scan_body, x, (params["layers"], fp8_state["layers"])
        )
        return _project_out(config, params, x), {"layers": new_fp8}

    def scan_body(carry, layer):
        return _layer_body(config, carry, layer, sin, cos, positions,
                           attention_mask)[0], None

    x, _ = jax.lax.scan(scan_body, x, params["layers"])
    return _project_out(config, params, x)


def init_kv_caches(config: GPTJConfig, batch: int, max_len: int,
                   dtype=jnp.bfloat16):
    return make_kv_caches(config.num_hidden_layers, batch, max_len,
                          config.num_attention_heads, config.head_dim, dtype)


generate = build_generate(forward, init_kv_caches)


def causal_lm_loss(config: GPTJConfig, params: dict, batch: dict,
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


def init_fp8_state(config: GPTJConfig, history_len: int | None = None) -> dict:
    """Per-layer delayed-scaling metas for the six layer projections
    (shared builder: ops/fp8.py stacked_fp8_metas; honors the Accelerator's
    FP8RecipeKwargs)."""
    from ..ops.fp8 import stacked_fp8_metas

    return stacked_fp8_metas(config.num_hidden_layers, {
        "attn": ("q_proj", "k_proj", "v_proj", "out_proj"),
        "mlp": ("fc_in", "fc_out"),
    }, history_len)


@functools.lru_cache(maxsize=8)
def make_decode_layer_step(config: GPTJConfig):
    """jit'd single-layer decode body for `streamed_generate` (offloaded
    weights — the reference's GPT-J-6B cpu-offload benchmark rows)."""

    @jax.jit
    def step(layer, x, positions, kv_cache):
        max_len = max(config.max_position_embeddings, kv_cache[0].shape[1])
        sin, cos = _interleaved_rope_tables(config.rotary_dim, max_len)
        y, cache, _ = _layer_body(config, x, layer, sin, cos, positions,
                                  None, kv_cache)
        return y, cache

    return step


# _project_out includes the final layer norm, so it is directly the
# streamed path's projection
streamed_generate = build_streamed_generate(
    make_decode_layer_step,
    embed_fn=lambda config, res, ids, pos: res["wte"]["embedding"][ids],
    project_fn=lambda config, res, x: _project_out(config, res, x),
    cache_dims=lambda c: (c.num_attention_heads, c.head_dim),
)
