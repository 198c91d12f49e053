"""GPT-2 causal LM (the GPT-J/NeoX-class coverage of the reference's
big-model-inference benchmark, ref benchmarks/README.md:25-36, toward
arbitrary-architecture import parity).

Same TPU-first layout as llama: layers stack on a leading L dim and the
forward scans one compiled layer body. GPT-2 specifics: learned position
embeddings, pre-LN with biases, fused qkv (`c_attn`), gelu_new MLP, and a
word-embedding-tied LM head. HF stores these as Conv1D ([in, out] kernels
— no transpose on import, unlike nn.Linear).
"""

from __future__ import annotations

import dataclasses
import functools

import jax
import jax.numpy as jnp

from .common import (
    dense,
    dense_maybe_fp8,
    dot_product_attention,
    layer_norm,
    normal_init,
    shifted_padding_masks,
    token_nll,
    cross_entropy_loss,
)
from .decode import (
    build_generate,
    build_streamed_generate,
    decode_attention,
    make_kv_caches,
    scan_decode_layers,
)


@dataclasses.dataclass(frozen=True)
class GPT2Config:
    vocab_size: int = 50257
    hidden_size: int = 768          # n_embd
    num_hidden_layers: int = 12     # n_layer
    num_attention_heads: int = 12   # n_head
    max_position_embeddings: int = 1024  # n_positions
    layer_norm_epsilon: float = 1e-5

    @property
    def head_dim(self) -> int:
        return self.hidden_size // self.num_attention_heads

    @classmethod
    def tiny(cls, **overrides) -> "GPT2Config":
        defaults = dict(
            vocab_size=256, hidden_size=64, num_hidden_layers=2,
            num_attention_heads=4, max_position_embeddings=128,
        )
        defaults.update(overrides)
        return cls(**defaults)


def init_params(config: GPT2Config, key: jax.Array, dtype=jnp.float32) -> dict:
    keys = jax.random.split(key, 6)
    h, L = config.hidden_size, config.num_hidden_layers

    def lin(k, d_in, d_out):
        return {
            "kernel": normal_init(k, (L, d_in, d_out), 0.02, dtype),
            "bias": jnp.zeros((L, d_out), dtype),
        }

    def ln():
        return {"scale": jnp.ones((L, h), dtype), "bias": jnp.zeros((L, h), dtype)}

    return {
        "wte": {"embedding": normal_init(keys[0], (config.vocab_size, h), 0.02, dtype)},
        "wpe": {"embedding": normal_init(keys[1], (config.max_position_embeddings, h), 0.01, dtype)},
        "layers": {
            "ln_1": ln(),
            "attn": {
                "c_attn": lin(keys[2], h, 3 * h),
                "c_proj": lin(keys[3], h, h),
            },
            "ln_2": ln(),
            "mlp": {
                "c_fc": lin(keys[4], h, 4 * h),
                "c_proj": lin(keys[5], 4 * h, h),
            },
        },
        "ln_f": {"scale": jnp.ones((h,), dtype), "bias": jnp.zeros((h,), dtype)},
    }


def _layer_body(config: GPT2Config, x, layer, mask, positions=None,
                kv_cache=None, fp8=None):
    b, s, h = x.shape
    nh, hd = config.num_attention_heads, config.head_dim
    eps = config.layer_norm_epsilon
    fa = fp8["attn"] if fp8 is not None else {}
    fm = fp8["mlp"] if fp8 is not None else {}

    y = layer_norm(x, layer["ln_1"]["scale"], layer["ln_1"]["bias"], eps)
    qkv, m_qkv = dense_maybe_fp8(
        y, layer["attn"]["c_attn"]["kernel"], fa.get("c_attn"),
        layer["attn"]["c_attn"]["bias"])
    q, k, v = jnp.split(qkv, 3, axis=-1)
    q = q.reshape(b, s, nh, hd)
    k = k.reshape(b, s, nh, hd)
    v = v.reshape(b, s, nh, hd)
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
    attn = attn.reshape(b, s, h)
    a_out, m_ap = dense_maybe_fp8(
        attn, layer["attn"]["c_proj"]["kernel"], fa.get("c_proj"),
        layer["attn"]["c_proj"]["bias"])
    x = x + a_out

    y = layer_norm(x, layer["ln_2"]["scale"], layer["ln_2"]["bias"], eps)
    y, m_fc = dense_maybe_fp8(
        y, layer["mlp"]["c_fc"]["kernel"], fm.get("c_fc"),
        layer["mlp"]["c_fc"]["bias"])
    y = jax.nn.gelu(y.astype(jnp.float32), approximate=True).astype(x.dtype)
    m_out, m_mp = dense_maybe_fp8(
        y, layer["mlp"]["c_proj"]["kernel"], fm.get("c_proj"),
        layer["mlp"]["c_proj"]["bias"])
    x = x + m_out
    new_fp8 = (
        {"attn": {"c_attn": m_qkv, "c_proj": m_ap},
         "mlp": {"c_fc": m_fc, "c_proj": m_mp}}
        if fp8 is not None else None
    )
    return x, new_cache, new_fp8


def forward(
    config: GPT2Config,
    params: dict,
    input_ids: jax.Array,
    attention_mask: jax.Array | None = None,
    positions: jax.Array | None = None,
    kv_caches=None,
    fp8_state=None,
) -> jax.Array | tuple:
    """Logits [B, S, V]; LM head tied to wte (GPT-2 always ties).
    With `kv_caches` (see `init_kv_caches`), returns (logits, new_caches) —
    the incremental-decode path behind `generate`. With `fp8_state` (see
    `init_fp8_state`), layer projections run fp8 and the result is
    (logits, new_fp8_state)."""
    if fp8_state is not None and kv_caches is not None:
        raise ValueError("fp8 is a training-path feature; decode "
                         "(kv_caches) runs bf16")
    if positions is None:
        positions = jnp.broadcast_to(
            jnp.arange(input_ids.shape[1]), input_ids.shape
        )
    x = params["wte"]["embedding"][input_ids] + params["wpe"]["embedding"][positions]

    if kv_caches is not None:
        def layer_step(y, layer, cache):
            return _layer_body(config, y, layer, attention_mask, positions,
                               cache)[:2]

        x, (nk, nv) = scan_decode_layers(layer_step, x, params["layers"],
                                         kv_caches)
        x = layer_norm(x, params["ln_f"]["scale"], params["ln_f"]["bias"],
                       config.layer_norm_epsilon)
        logits = jnp.einsum(
            "bsh,vh->bsv", x, params["wte"]["embedding"].astype(x.dtype),
            preferred_element_type=jnp.float32,
        )
        return logits, (nk, nv, kv_caches[2] + input_ids.shape[1])

    if fp8_state is not None:
        # per-layer metas ride the scan as xs, updated metas stack as ys
        # (the same threading as models/llama.py forward)
        def scan_body(carry, xs):
            layer, f = xs
            y, _, nf = _layer_body(config, carry, layer, attention_mask,
                                   fp8=f)
            return y, nf

        x, new_fp8 = jax.lax.scan(
            scan_body, x, (params["layers"], fp8_state["layers"])
        )
    else:
        def scan_body(carry, layer):
            return _layer_body(config, carry, layer, attention_mask)[0], None

        x, _ = jax.lax.scan(scan_body, x, params["layers"])
    x = layer_norm(x, params["ln_f"]["scale"], params["ln_f"]["bias"],
                   config.layer_norm_epsilon)
    logits = jnp.einsum(
        "bsh,vh->bsv", x, params["wte"]["embedding"].astype(x.dtype),
        preferred_element_type=jnp.float32,
    )
    return (logits, {"layers": new_fp8}) if fp8_state is not None else logits


def init_kv_caches(config: GPT2Config, batch: int, max_len: int,
                   dtype=jnp.bfloat16):
    return make_kv_caches(config.num_hidden_layers, batch, max_len,
                          config.num_attention_heads, config.head_dim, dtype)


generate = build_generate(forward, init_kv_caches)


def causal_lm_loss(config: GPT2Config, params: dict, batch: dict,
                   fp8_state=None) -> jax.Array | tuple:
    """Next-token loss; with `fp8_state` (mixed_precision="fp8") returns
    (loss, new_fp8_state) — the fused train step threads it through
    TrainState.fp8_state."""
    input_ids = batch["input_ids"]
    labels = input_ids[:, 1:]
    attn_mask, mask = shifted_padding_masks(batch.get("attention_mask"))
    out = forward(config, params, input_ids[:, :-1],
                  attention_mask=attn_mask, fp8_state=fp8_state)
    if fp8_state is not None:
        logits, new_fp8 = out
        return cross_entropy_loss(logits, labels, mask), new_fp8
    return cross_entropy_loss(out, labels, mask)


def init_fp8_state(config: GPT2Config, history_len: int | None = None) -> dict:
    """Per-layer delayed-scaling metas for the four layer projections
    (shared builder: ops/fp8.py stacked_fp8_metas; honors the Accelerator's
    FP8RecipeKwargs)."""
    from ..ops.fp8 import stacked_fp8_metas

    return stacked_fp8_metas(config.num_hidden_layers, {
        "attn": ("c_attn", "c_proj"),
        "mlp": ("c_fc", "c_proj"),
    }, history_len)


@functools.lru_cache(maxsize=8)
def make_decode_layer_step(config: GPT2Config):
    """jit'd single-layer decode body for `streamed_generate` (offloaded
    weights)."""

    @jax.jit
    def step(layer, x, positions, kv_cache):
        y, cache, _ = _layer_body(config, x, layer, None, positions, kv_cache)
        return y, cache

    return step


def _project_decode(config: GPT2Config, res: dict, x):
    # includes the final ln_f + tied-wte head (what forward applies)
    x = layer_norm(x, res["ln_f"]["scale"], res["ln_f"]["bias"],
                   config.layer_norm_epsilon)
    return jnp.einsum(
        "bsh,vh->bsv", x, res["wte"]["embedding"].astype(x.dtype),
        preferred_element_type=jnp.float32,
    )


streamed_generate = build_streamed_generate(
    make_decode_layer_step,
    embed_fn=lambda config, res, ids, pos: (
        res["wte"]["embedding"][ids] + res["wpe"]["embedding"][pos]),
    project_fn=_project_decode,
    cache_dims=lambda c: (c.num_attention_heads, c.head_dim),
)
