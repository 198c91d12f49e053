"""Plain reference of the JoyAI-LLM-Flash decoder (`model_type`
`joyai_llm_flash`: the DeepSeek-V3 block at other numbers), as its
`config.json` describes it: pre-norm RMSNorm residual blocks, multi-head
latent attention with K and V DECOMPRESSED from the normed latent (no
absorption, no cache), rotary embedding on adjacent lane pairs with ONE
rope key head shared by all query heads, a dense SwiGLU MLP in the first
`first_k_dense_replace` layers and, in the others, a float32 sigmoid
router (`noaux_tc`, one group: the `num_experts_per_tok` largest of `score
+ e_score_correction_bias`, weighted by the scores WITHOUT the bias,
normalised, times `routed_scaling_factor`), every routed expert's
contribution by a masked combine, and the shared expert on every token.

Straightforward `jax.numpy`, float32, with no kernel, no cache and no
batching. Every matrix product is a `jnp.matmul` or a two-operand
`jnp.einsum` and nothing here knows of a lower precision: the controls
round those products' operands from outside (`lower_precision.py`). It
imports nothing of `accelerate_tpu`; the weights come from `make_params`,
the benchmark's own initialiser, which the harness also hands to the
program. The caller sets `jax.default_matmul_precision("highest")`.

Departures from the published description, none of which changes the
mathematics: attention runs in blocks of query rows and the experts one
after another (`lax.scan` over the expert axis, each expert applied to
EVERY token and masked), with parameters stored in bfloat16 cast to
float32 a piece at a time, so that a 17,920-token teacher-forced pass
fits beside 11 GB of resident weights. The multi-token-prediction module
(`num_nextn_predict_layers`) is not part of the served logits and is left
out, as the configuration's file says.

How served tokens are judged (`position_gaps`). The 8 experts are the
largest 8 of 256 scores, and at the configuration's stated precision
(bfloat16 activations) the 8th and the 9th change places for about a
token in three somewhere in the four expert layers, each time putting
another expert's output where an eighth of the routed sum was: ONE such
token's logits move as far as float8 moves every token's (`PERF.md`
section 6). A largest gap over the served tokens therefore cannot tell the
two apart, and enumerating a token's near ties cannot either (the same
section). What can is a number that one token cannot carry: the gap
`position_gaps` returns for a position is the MEDIAN of the gaps of that
position and the `GAP_WINDOW - 1` before it. The log-probabilities stay
one position each.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np

# ---------------------------------------------------------------------------
# parameters: the program's tree (accelerate_tpu/models/deepseek.py reads
# the same names). kind "w" = normal(0, 0.02), "one" = ones, "bias" = the
# router's float32 correction bias, normal(0, 0.05): small, and not zero,
# so that the experts chosen and their weights differ as published.
# ---------------------------------------------------------------------------


def _leaves(cfg: dict):
    h, H = cfg["hidden_size"], cfg["num_attention_heads"]
    qr, kvr = cfg["q_lora_rank"], cfg["kv_lora_rank"]
    nope, rope, vd = (cfg["qk_nope_head_dim"], cfg["qk_rope_head_dim"],
                      cfg["v_head_dim"])
    f, E = cfg["moe_intermediate_size"], cfg["n_routed_experts"]
    out = [(("embed_tokens", "embedding"), (cfg["vocab_size"], h), "w")]
    for i in range(cfg["num_hidden_layers"]):
        L = ("layers", i)
        out += [
            (L + ("input_layernorm", "scale"), (h,), "one"),
            (L + ("attn", "q_a_proj", "kernel"), (h, qr), "w"),
            (L + ("attn", "q_a_layernorm", "scale"), (qr,), "one"),
            (L + ("attn", "q_b_proj", "kernel"), (qr, H * (nope + rope)), "w"),
            (L + ("attn", "kv_a_proj", "kernel"), (h, kvr + rope), "w"),
            (L + ("attn", "kv_a_layernorm", "scale"), (kvr,), "one"),
            (L + ("attn", "kv_b_proj", "kernel"), (kvr, H * (nope + vd)), "w"),
            (L + ("attn", "o_proj", "kernel"), (H * vd, h), "w"),
            (L + ("post_attention_layernorm", "scale"), (h,), "one"),
        ]
        if i < cfg["first_k_dense_replace"]:
            d = cfg["intermediate_size"]
            out += [(L + ("mlp", "gate_proj", "kernel"), (h, d), "w"),
                    (L + ("mlp", "up_proj", "kernel"), (h, d), "w"),
                    (L + ("mlp", "down_proj", "kernel"), (d, h), "w")]
        else:
            s = f * cfg["n_shared_experts"]
            out += [
                (L + ("moe", "router", "kernel"), (h, E), "w"),
                (L + ("moe", "router", "e_score_correction_bias"), (E,),
                 "bias"),
                (L + ("moe", "experts", "gate_proj"), (E, h, f), "w"),
                (L + ("moe", "experts", "up_proj"), (E, h, f), "w"),
                (L + ("moe", "experts", "down_proj"), (E, f, h), "w"),
                (L + ("moe", "shared", "gate_proj", "kernel"), (h, s), "w"),
                (L + ("moe", "shared", "up_proj", "kernel"), (h, s), "w"),
                (L + ("moe", "shared", "down_proj", "kernel"), (s, h), "w"),
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
    routed experts are 4.8 B parameters in the cell)."""
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


def make_params(cfg: dict, words, dtype=jnp.float32) -> dict:
    """Every leaf from the seed, on the device, in `dtype` (the router's
    correction bias stays float32); call it jitted (`words` traced). Leaf
    i draws from fold_in(key, i). `layers` is a list of per-layer trees."""
    key = jax.random.wrap_key_data(jnp.asarray(words, jnp.uint32))
    tree: dict = {"layers": [{} for _ in range(cfg["num_hidden_layers"])]}
    for i, (path, shape, kind) in enumerate(_leaves(cfg)):
        k = jax.random.fold_in(key, i)
        if kind == "one":
            leaf = jnp.ones(shape, dtype)
        elif kind == "bias":
            leaf = jax.random.normal(k, shape, jnp.float32) * 0.05
        else:
            leaf = _normal(k, shape, 0.02, dtype)
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
    """x [T, H, D] at positions 0..T-1, rotated in ADJACENT pairs
    (`rope_interleave`): lanes (2i, 2i + 1) by the angle t * theta^(-2i/D)."""
    T, D = x.shape[0], x.shape[-1]
    inv = jnp.asarray(1.0 / (theta ** (np.arange(0, D, 2) / D)), jnp.float32)
    ang = jnp.arange(T, dtype=jnp.float32)[:, None] * inv[None, :]
    cos, sin = jnp.cos(ang)[:, None, :], jnp.sin(ang)[:, None, :]
    pairs = x.reshape(x.shape[:-1] + (D // 2, 2))
    x0, x1 = pairs[..., 0], pairs[..., 1]
    return jnp.stack([x0 * cos - x1 * sin, x1 * cos + x0 * sin],
                     axis=-1).reshape(x.shape)


def _attention(cfg, a, x, rows_per_block=256):
    """Causal multi-head latent attention over x [T, h], K and V
    decompressed from the normed latent as published."""
    H = cfg["num_attention_heads"]
    nope, rope, vd = (cfg["qk_nope_head_dim"], cfg["qk_rope_head_dim"],
                      cfg["v_head_dim"])
    kvr, eps, theta = (cfg["kv_lora_rank"], cfg["rms_norm_eps"],
                       cfg["rope_theta"])
    T = x.shape[0]
    c_q = _rms_norm(jnp.matmul(x, _f32(a["q_a_proj"]["kernel"])),
                    a["q_a_layernorm"]["scale"], eps)
    q = jnp.matmul(c_q, _f32(a["q_b_proj"]["kernel"])).reshape(
        T, H, nope + rope)
    kv_a = jnp.matmul(x, _f32(a["kv_a_proj"]["kernel"]))
    c_kv = _rms_norm(kv_a[:, :kvr], a["kv_a_layernorm"]["scale"], eps)
    k_pe = _rope(kv_a[:, None, kvr:], theta)               # [T, 1, rope]
    kv = jnp.matmul(c_kv, _f32(a["kv_b_proj"]["kernel"])).reshape(
        T, H, nope + vd)
    q = jnp.concatenate([q[..., :nope], _rope(q[..., nope:], theta)], -1)
    k = jnp.concatenate([kv[..., :nope],
                         jnp.broadcast_to(k_pe, (T, H, rope))], -1)
    v = kv[..., nope:]
    blk = _largest_divisor(T, rows_per_block)

    def block(args):
        q_blk, start = args
        s = jnp.einsum("qhd,khd->hqk", q_blk, k) / np.sqrt(nope + rope)
        rows = start + jnp.arange(blk)
        s = jnp.where(rows[None, :, None] >= jnp.arange(T)[None, None, :],
                      s, -jnp.inf)
        return jnp.einsum("hqk,khd->qhd", jax.nn.softmax(s, axis=-1), v)

    o = jax.lax.map(block, (q.reshape(T // blk, blk, H, nope + rope),
                            jnp.arange(0, T, blk)))
    return jnp.matmul(o.reshape(T, H * vd), _f32(a["o_proj"]["kernel"]))


def _swiglu(x, gate, up, down):
    return jnp.matmul(jax.nn.silu(jnp.matmul(x, _f32(gate)))
                      * jnp.matmul(x, _f32(up)), _f32(down))


def route(cfg, m, x):
    """(experts [T, k], weights [T, k]) of x [T, h], float32."""
    r = m["router"]
    scores = jax.nn.sigmoid(jnp.matmul(x, _f32(r["kernel"])))
    _, experts = jax.lax.top_k(
        scores + _f32(r["e_score_correction_bias"]),
        cfg["num_experts_per_tok"])
    weights = jnp.take_along_axis(scores, experts, axis=-1)
    if cfg["norm_topk_prob"]:
        weights = weights / (jnp.sum(weights, axis=-1, keepdims=True) + 1e-20)
    return experts, weights * cfg["routed_scaling_factor"]


def moe(cfg, m, x):
    """The expert layer over x [T, h]: every routed expert is applied to
    every token and its result kept where the router chose it."""
    experts, weights = route(cfg, m, x)
    e = m["experts"]

    def one(y, xs):
        index, gate, up, down = xs
        w = jnp.sum(jnp.where(experts == index, weights, 0.0), axis=-1)
        return y + w[:, None] * _swiglu(x, gate, up, down), None

    y, _ = jax.lax.scan(
        one, jnp.zeros_like(x),
        (jnp.arange(cfg["n_routed_experts"]), e["gate_proj"], e["up_proj"],
         e["down_proj"]))
    s = m["shared"]
    return y + _swiglu(x, s["gate_proj"]["kernel"], s["up_proj"]["kernel"],
                       s["down_proj"]["kernel"])


def hidden_states(cfg: dict, params: dict, ids):
    """Final normed hidden states [T, h] float32 of token ids [T]."""
    eps = cfg["rms_norm_eps"]
    x = _f32(params["embed_tokens"]["embedding"][ids])
    for layer in params["layers"]:
        x = x + _attention(
            cfg, layer["attn"],
            _rms_norm(x, layer["input_layernorm"]["scale"], eps))
        y = _rms_norm(x, layer["post_attention_layernorm"]["scale"], eps)
        if "moe" in layer:
            x = x + moe(cfg, layer["moe"], y)
        else:
            d = layer["mlp"]
            x = x + _swiglu(y, d["gate_proj"]["kernel"],
                            d["up_proj"]["kernel"], d["down_proj"]["kernel"])
    return _rms_norm(x, params["norm"]["scale"], eps)


def head(cfg: dict, params: dict, hidden):
    """Logits (float32) of hidden rows [..., h]; untied."""
    return jnp.matmul(hidden, _f32(params["lm_head"]["kernel"]))


def logits(cfg: dict, params: dict, ids):
    """[T, V] float32 logits of token ids [T] (tests; small sizes)."""
    return head(cfg, params, hidden_states(cfg, params, ids))


# positions in the running median of `position_gaps`: an answer of the cell
# has at least 32. Through the cell on the chip (PERF.md section 6) 10-13%
# of the bfloat16 program's served tokens and 46-58% of the float8
# control's first choices lie below the reference's best at all: half of 32
# in a row is out of the one's reach (20 sound runs read exactly 0) and
# within the other's (0.08-0.28).
GAP_WINDOW = 32


def _running_median(x, window):
    """out[j] = the median of x[j - window + 1 .. j] (of an even window
    the upper of the two middle values); 0 for the first `window - 1`
    positions, which are judged inside the later windows."""
    at = jnp.arange(x.shape[0])
    back = jnp.maximum(at[:, None] - jnp.arange(window)[None, :], 0)
    med = jnp.sort(x[back], axis=-1)[:, window // 2]
    return jnp.where(at >= window - 1, med, 0.0)


def position_gaps(cfg: dict, params: dict, ids, first, tokens, dtype=None):
    """One served request, teacher-forced. `ids` [T] is its prompt followed
    by its served tokens (then padding, which causal attention never lets
    an earlier position see); `tokens` [C] are candidates for positions
    first .. first+C-1. Returns (how far the candidates' logits lie below
    the best logit at their positions: at each position the median over it
    and the GAP_WINDOW - 1 positions before it, see the head of this file;
    the token this forward itself puts first at each position; each
    candidate's log-probability). Always float32 (`dtype` is the
    harness's and has one meaning here)."""
    hid = hidden_states(cfg, params, ids)
    rows = jax.lax.dynamic_slice_in_dim(hid, first - 1, tokens.shape[0],
                                        axis=0)
    out = head(cfg, params, rows)
    took = jnp.take_along_axis(out, tokens[:, None], axis=-1)[:, 0]
    return (_running_median(out.max(axis=-1) - took, GAP_WINDOW),
            jnp.argmax(out, axis=-1),
            took - jax.nn.logsumexp(out, axis=-1))
