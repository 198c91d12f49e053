"""Plain reference of Brumby-14B-Base (`model_type` `brumby`), as its
`config.json` and its mechanism's paper describe it: a Qwen3-14B-shaped
decoder (pre-norm RMSNorm residual blocks without biases, `num_attention_
heads` query heads over `num_key_value_heads` key/value heads of `head_dim`
lanes, SwiGLU MLP, untied embedding and head) whose attention is POWER
RETENTION (Buckman, Gelada, Zhang et al., arXiv:2507.04239). With
`gamma_{t,g}` the log-gate of KV head g at token t and `Gam_t = sum_{u<=t}
gamma_u`, for `s <= t`:

    A[t, s] = exp(Gam_t - Gam_s) * (q_t . k_s) ** p
    o_t     = sum_s A[t, s] v_s / (sum_s A[t, s] + eps)

a query head against its KV head's k, v and gate. It is written here as it
stands, the `[positions, positions]` matrix A: no recurrent state, no
feature map of the p-th power, no chunks. (The program keeps the state
form; that both give the same numbers is what the cell checks.)

ASSUMED (the configuration's file lists each under `assumed`; `config.json`
has no key for any of them): the degree p = 2; one log-gate a KV head and
token from the layer's normed input, `gamma = log sigmoid(y w_g + b_g)`; q
and k get a per-head RMSNorm over their `head_dim` lanes and then the
half-split rotation by position with `rope_theta` (the Qwen3 block's
convention, whose keys the file carries); `eps`; the sum of A as the
normaliser. With no trained weights the gates' biases are spread evenly
from 4 to 9 over the KV heads (`gate_bias`): `sigmoid` then keeps 0.982 to
0.99988 of the past a token, memories of some 50 to some 8,000 tokens side
by side.

Straightforward `jax.numpy`, float32, with no kernel, no cache and no
batching. Every matrix product is a `jnp.matmul` or a two-operand
`jnp.einsum` and nothing here knows of a lower precision: the controls
round those products' operands from outside (`lower_precision.py`). It
imports nothing of `accelerate_tpu` or of the other references; the
weights come from `make_params`, the benchmark's own initialiser, which
the harness also hands to the program. The caller sets
`jax.default_matmul_precision("highest")`.

Departures from the published description, none of which changes the
mathematics: retention runs in blocks of query rows, each against its own
full `[block, positions]` rows of A (so the `[positions, positions]` matrix
exists a block of rows at a time and 26,624 positions fit), the MLP runs a
block of rows and the head a block of the vocabulary at a time, and
parameters stored in bfloat16 are cast to float32 a piece at a time (the
float32 pass then fits beside 8.4 GB of resident bfloat16 weights).
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np

# ---------------------------------------------------------------------------
# parameters: the program's tree (accelerate_tpu/models/brumby.py reads the
# same names). kind "w" = normal(0, 0.02), "one" = ones, "gate_bias" = see
# the head of this file.
# ---------------------------------------------------------------------------


def _leaves(cfg: dict):
    h, D, f = cfg["hidden_size"], cfg["head_dim"], cfg["intermediate_size"]
    H, G = cfg["num_attention_heads"], cfg["num_key_value_heads"]
    out = [(("embed_tokens", "embedding"), (cfg["vocab_size"], h), "w")]
    for i in range(cfg["num_hidden_layers"]):
        L = ("layers", i)
        out += [
            (L + ("input_layernorm", "scale"), (h,), "one"),
            (L + ("attn", "q_proj", "kernel"), (h, H * D), "w"),
            (L + ("attn", "k_proj", "kernel"), (h, G * D), "w"),
            (L + ("attn", "v_proj", "kernel"), (h, G * D), "w"),
            (L + ("attn", "o_proj", "kernel"), (H * D, h), "w"),
            (L + ("attn", "gate_proj", "kernel"), (h, G), "w"),
            (L + ("attn", "gate_proj", "bias"), (G,), "gate_bias"),
            (L + ("attn", "q_norm", "scale"), (D,), "one"),
            (L + ("attn", "k_norm", "scale"), (D,), "one"),
            (L + ("post_attention_layernorm", "scale"), (h,), "one"),
            (L + ("mlp", "gate_proj", "kernel"), (h, f), "w"),
            (L + ("mlp", "up_proj", "kernel"), (h, f), "w"),
            (L + ("mlp", "down_proj", "kernel"), (f, h), "w"),
        ]
    out += [(("norm", "scale"), (h,), "one"),
            (("lm_head", "kernel"), (h, cfg["vocab_size"]), "w")]
    return out


def param_count(cfg: dict) -> int:
    return int(sum(np.prod(shape) for _, shape, _ in _leaves(cfg)))


def seed_words(seed: int) -> np.ndarray:
    """A seed of up to 64 bits as the two uint32 words of a threefry key,
    so that the seed is DATA to the jitted initialiser (one compile for
    every seed) and seeds above 2**31 need no 64-bit mode."""
    seed = int(seed) & 0xFFFFFFFFFFFFFFFF
    return np.array([seed >> 32, seed & 0xFFFFFFFF], np.uint32)


def _largest_divisor(n: int, at_most: int) -> int:
    return max(d for d in range(1, max(1, min(n, at_most)) + 1) if n % d == 0)


def _normal(key, shape, stddev, dtype, block_elements=1 << 26):
    """normal(0, stddev) in `dtype`, a large leaf drawn in blocks of its
    leading axis so that no float32 copy of the whole leaf exists (the
    embedding and the head are 778 M parameters each)."""
    tail = int(np.prod(shape[1:])) if len(shape) > 1 else 1
    if int(np.prod(shape)) <= block_elements:
        return (jax.random.normal(key, shape, jnp.float32)
                * stddev).astype(dtype)
    rows = _largest_divisor(shape[0], max(1, block_elements // tail))
    blocks = jax.lax.map(
        lambda k: (jax.random.normal(k, (rows,) + tuple(shape[1:]),
                                     jnp.float32) * stddev).astype(dtype),
        jax.random.split(key, shape[0] // rows))
    return blocks.reshape(shape)


def gate_bias(heads: int) -> np.ndarray:
    return np.linspace(4.0, 9.0, heads).astype(np.float32)


def make_params(cfg: dict, words, dtype=jnp.float32) -> dict:
    """Every leaf from the seed, on the device, in `dtype`; call it jitted
    (`words` traced). Leaf i draws from fold_in(key, i). `layers` is a
    list of per-layer trees."""
    key = jax.random.wrap_key_data(jnp.asarray(words, jnp.uint32))
    tree: dict = {"layers": [{} for _ in range(cfg["num_hidden_layers"])]}
    for i, (path, shape, kind) in enumerate(_leaves(cfg)):
        leaf = (jnp.ones(shape, dtype) if kind == "one"
                else jnp.asarray(gate_bias(shape[0]), dtype)
                if kind == "gate_bias"
                else _normal(jax.random.fold_in(key, i), shape, 0.02, dtype))
        node = tree
        for name in path[:-1]:
            node = node[name] if isinstance(node, list) else \
                node.setdefault(name, {})
        node[path[-1]] = leaf
    return tree


# ---------------------------------------------------------------------------
# forward
# ---------------------------------------------------------------------------


def _f32(x):
    return x.astype(jnp.float32)


def _rms_norm(x, scale, eps):
    var = jnp.mean(x * x, axis=-1, keepdims=True)
    return x * jax.lax.rsqrt(var + eps) * _f32(scale)


def _rope(x, theta):
    """x [T, heads, D], positions 0..T-1, half-split pairs (lane i with
    lane i + D / 2)."""
    T, D = x.shape[0], x.shape[-1]
    inv = jnp.asarray(1.0 / (theta ** (np.arange(0, D, 2) / D)), jnp.float32)
    ang = jnp.arange(T, dtype=jnp.float32)[:, None, None] * inv
    cos, sin = jnp.cos(ang), jnp.sin(ang)
    x1, x2 = jnp.split(x, 2, axis=-1)
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], -1)


def _retention(cfg, a, x, rows_per_block=128):
    """Power retention over x [T, h] (float32, normed) -> [T, h]."""
    H, G, D = (cfg["num_attention_heads"], cfg["num_key_value_heads"],
               cfg["head_dim"])
    p, eps = cfg["retention_degree"], cfg["retention_eps"]
    T = x.shape[0]
    q = jnp.matmul(x, _f32(a["q_proj"]["kernel"])).reshape(T, H, D)
    k = jnp.matmul(x, _f32(a["k_proj"]["kernel"])).reshape(T, G, D)
    v = jnp.matmul(x, _f32(a["v_proj"]["kernel"])).reshape(T, G, D)
    q = _rms_norm(q, a["q_norm"]["scale"], cfg["rms_norm_eps"])
    k = _rms_norm(k, a["k_norm"]["scale"], cfg["rms_norm_eps"])
    q, k = _rope(q, cfg["rope_theta"]), _rope(k, cfg["rope_theta"])
    if cfg["use_gate"]:
        gamma = jax.nn.log_sigmoid(
            jnp.matmul(x, _f32(a["gate_proj"]["kernel"]))
            + _f32(a["gate_proj"]["bias"]))                        # [T, G]
    else:
        gamma = jnp.zeros((T, G), jnp.float32)
    gam = jnp.cumsum(gamma, axis=0).T                               # [G, T]
    blk = _largest_divisor(T, rows_per_block)
    n = T // blk
    at = jnp.arange(T)

    def block(args):
        q_blk, gam_blk, when = args        # [blk, G, Hg, D], [G, blk], [blk]
        sees = when[:, None] >= at[None, :]                     # [blk, T]
        decay = jnp.exp(jnp.where(
            sees[None], gam_blk[:, :, None] - gam[:, None, :], -jnp.inf))
        s = jnp.einsum("qghd,tgd->ghqt", q_blk, k)
        weight = (s * s if p == 2 else s) * decay[:, None]     # A's rows
        total = jnp.sum(weight, axis=-1)                       # [G, Hg, blk]
        out = jnp.einsum("ghqt,tgd->qghd", weight, v)
        return out / (jnp.moveaxis(total, 2, 0)[..., None] + eps)

    o = jax.lax.map(block, (
        q.reshape(n, blk, G, H // G, D),
        jnp.moveaxis(gam.reshape(G, n, blk), 1, 0), at.reshape(n, blk)))
    return jnp.matmul(o.reshape(T, H * D), _f32(a["o_proj"]["kernel"]))


def _swiglu(x, m, rows_per_block=1024):
    """SwiGLU over x [T, h], a block of rows at a time."""
    gate, up, down = (_f32(m[name]["kernel"])
                      for name in ("gate_proj", "up_proj", "down_proj"))
    blk = _largest_divisor(x.shape[0], rows_per_block)
    return jax.lax.map(
        lambda y: jnp.matmul(jax.nn.silu(jnp.matmul(y, gate))
                             * jnp.matmul(y, up), down),
        x.reshape(-1, blk, x.shape[1])).reshape(x.shape)


def hidden_states(cfg: dict, params: dict, ids):
    """Final normed hidden states [T, h] float32 of token ids [T]."""
    eps = cfg["rms_norm_eps"]
    x = _f32(params["embed_tokens"]["embedding"][ids])
    for layer in params["layers"]:
        x = x + _retention(cfg, layer["attn"], _rms_norm(
            x, layer["input_layernorm"]["scale"], eps))
        x = x + _swiglu(_rms_norm(
            x, layer["post_attention_layernorm"]["scale"], eps), layer["mlp"])
    return _rms_norm(x, params["norm"]["scale"], eps)


def head(cfg: dict, params: dict, hidden, columns_per_block=32768):
    """Logits (float32) of hidden rows [N, h]; untied. A block of the
    vocabulary at a time."""
    w = params["lm_head"]["kernel"]
    blk = _largest_divisor(w.shape[1], columns_per_block)
    out = jax.lax.map(
        lambda j: jnp.matmul(hidden, _f32(jax.lax.dynamic_slice_in_dim(
            w, j * blk, blk, axis=1))),
        jnp.arange(w.shape[1] // blk))                       # [n, N, blk]
    return jnp.moveaxis(out, 0, 1).reshape(hidden.shape[0], -1)


def logits(cfg: dict, params: dict, ids):
    """[T, V] float32 logits of token ids [T] (tests; small sizes)."""
    return head(cfg, params, hidden_states(cfg, params, ids))


def position_gaps(cfg: dict, params: dict, ids, first, tokens, dtype=None):
    """One served request, teacher-forced. `ids` [T] is its prompt followed
    by its served tokens (then padding, which a causal A never lets an
    earlier position see); `tokens` [C] are candidates for positions first
    .. first+C-1. Returns (how far each candidate's logit lies below the
    best logit at its position, the token this forward itself puts first
    there, each candidate's log-probability). Always float32 (`dtype` is
    the harness's and has one meaning here)."""
    hid = hidden_states(cfg, params, ids)
    rows = jax.lax.dynamic_slice_in_dim(hid, first - 1, tokens.shape[0],
                                        axis=0)
    out = head(cfg, params, rows)
    took = jnp.take_along_axis(out, tokens[:, None], axis=-1)[:, 0]
    return (out.max(axis=-1) - took, jnp.argmax(out, axis=-1),
            took - jax.nn.logsumexp(out, axis=-1))
