"""Plain reference of the Mellum2-12B-A2.5B decoder (`model_type`
`mellum`), as its `config.json` describes it: pre-norm RMSNorm residual
blocks without biases; grouped-query attention (`num_attention_heads`
query heads over `num_key_value_heads` key/value heads of `head_dim`
lanes, a key of its own) whose KIND a layer takes from `layer_types`: a
`sliding_attention` layer's query at position i sees key j iff `0 <= i -
j < sliding_window` and rotates by the plain table, a `full_attention`
layer's sees every earlier key and rotates by the YaRN table
(`rope_parameters`), both in half-split pairs; and in every layer a
softmax router in float32 over all `num_experts`, the
`num_experts_per_tok` largest probabilities renormalised
(`norm_topk_prob`), every expert's SwiGLU contribution by a masked
combine. No dense layer, no shared expert. Untied embedding and head.

ASSUMED (the configuration's file says so, and carries the switch as
`qk_norm`): q and k get a per-head RMSNorm over their `head_dim` lanes
before the rotation. `config.json` has no key for it.

Straightforward `jax.numpy`, float32, with no kernel, no cache and no
batching. Every matrix product is a `jnp.matmul` or a two-operand
`jnp.einsum` and nothing here knows of a lower precision: the controls
round those products' operands from outside (`lower_precision.py`). It
imports nothing of `accelerate_tpu` or of the other references; the
weights come from `make_params`, the benchmark's own initialiser, which
the harness also hands to the program. The caller sets
`jax.default_matmul_precision("highest")`.

Departures from the published description, none of which changes the
mathematics: attention runs in blocks of query rows against a full
`[block, positions]` mask of the layer's kind, and the experts one after
another (`lax.scan` over the expert axis, each applied to EVERY token and
masked), with parameters stored in bfloat16 cast to float32 a piece at a
time, so that a 32,768-token teacher-forced pass fits beside 7.6 GB of
resident weights. The multi-token-prediction head of the model card has
no key in `config.json`, is no part of the served logits, and is left out.

How served tokens are judged (`position_gaps`): as
`references/joyai_llm_flash.py` judges them, in this file's own copy. The
8 experts are the largest 8 of 64 probabilities, and at the stated
precision (bfloat16 activations) the 8th and the 9th change places for
some tokens in some layer, each time putting another expert's output where
about an eighth of the routed sum was; ONE such token's logits move as far
as float8 moves every token's. The gap returned for a position is
therefore the MEDIAN of the gaps of that position and the `GAP_WINDOW - 1`
before it, which one token cannot carry. The log-probabilities stay one
position each.
"""

from __future__ import annotations

import math

import jax
import jax.numpy as jnp
import numpy as np

FULL, SLIDING = "full_attention", "sliding_attention"

# ---------------------------------------------------------------------------
# parameters: the program's tree (accelerate_tpu/models/mellum.py reads the
# same names). kind "w" = normal(0, 0.02), "one" = ones.
# ---------------------------------------------------------------------------


def _leaves(cfg: dict):
    h, D = cfg["hidden_size"], cfg["head_dim"]
    H, Hkv = cfg["num_attention_heads"], cfg["num_key_value_heads"]
    f, E = cfg["moe_intermediate_size"], cfg["num_experts"]
    out = [(("embed_tokens", "embedding"), (cfg["vocab_size"], h), "w")]
    for i in range(cfg["num_hidden_layers"]):
        L = ("layers", i)
        out += [
            (L + ("input_layernorm", "scale"), (h,), "one"),
            (L + ("attn", "q_proj", "kernel"), (h, H * D), "w"),
            (L + ("attn", "k_proj", "kernel"), (h, Hkv * D), "w"),
            (L + ("attn", "v_proj", "kernel"), (h, Hkv * D), "w"),
            (L + ("attn", "o_proj", "kernel"), (H * D, h), "w"),
        ]
        if cfg["qk_norm"]:
            out += [(L + ("attn", "q_norm", "scale"), (D,), "one"),
                    (L + ("attn", "k_norm", "scale"), (D,), "one")]
        out += [
            (L + ("post_attention_layernorm", "scale"), (h,), "one"),
            (L + ("moe", "router", "kernel"), (h, E), "w"),
            (L + ("moe", "experts", "gate_proj"), (E, h, f), "w"),
            (L + ("moe", "experts", "up_proj"), (E, h, f), "w"),
            (L + ("moe", "experts", "down_proj"), (E, f, h), "w"),
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
    routed experts are 3.2 B parameters in the cell)."""
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
    """Every leaf from the seed, on the device, in `dtype`; call it jitted
    (`words` traced). Leaf i draws from fold_in(key, i). `layers` is a
    list of per-layer trees."""
    key = jax.random.wrap_key_data(jnp.asarray(words, jnp.uint32))
    tree: dict = {"layers": [{} for _ in range(cfg["num_hidden_layers"])]}
    for i, (path, shape, kind) in enumerate(_leaves(cfg)):
        leaf = (jnp.ones(shape, dtype) if kind == "one"
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


def rotary_table(cfg: dict, kind: str, positions: int):
    """(cos, sin) [positions, head_dim / 2] float32 of layer kind `kind`,
    from `rope_parameters[kind]`. `default`: the angle of pair i at
    position t is `t * theta^(-2i/d)`. `yarn`: with `f_i = theta^(-2i/d)`,
    `c(r) = d ln(original / (2 pi r)) / (2 ln theta)`, `low = max(floor(
    c(beta_fast)), 0)`, `high = min(ceil(c(beta_slow)), d - 1)` and `ramp_i
    = clip((i - low) / (high - low), 0, 1)`, the pair's frequency is `(f_i
    / factor) ramp_i + f_i (1 - ramp_i)`, and cos and sin are both
    multiplied by `attention_factor`."""
    p = cfg["rope_parameters"][kind]
    d, theta = cfg["head_dim"], float(p["rope_theta"])
    i = np.arange(d // 2, dtype=np.float64)
    freq = theta ** (-2.0 * i / d)
    amplitude = 1.0
    if p["rope_type"] == "yarn":
        original = p["original_max_position_embeddings"]

        def c(r):
            return d * math.log(original / (2 * math.pi * r)) / (
                2 * math.log(theta))

        low = max(math.floor(c(p["beta_fast"])), 0)
        high = min(math.ceil(c(p["beta_slow"])), d - 1)
        ramp = np.clip((i - low) / (high - low), 0.0, 1.0)
        freq = freq / p["factor"] * ramp + freq * (1.0 - ramp)
        amplitude = p["attention_factor"]
    elif p["rope_type"] != "default":
        raise ValueError(p["rope_type"])
    ang = np.arange(positions, dtype=np.float64)[:, None] * freq[None, :]
    return (jnp.asarray(amplitude * np.cos(ang), jnp.float32),
            jnp.asarray(amplitude * np.sin(ang), jnp.float32))


def _rope(x, table):
    """x [T, H, D] at positions 0..T-1, rotated in HALF-SPLIT pairs (lane
    i with lane i + D/2)."""
    cos, sin = (t[:, None, :] for t in table)
    half = x.shape[-1] // 2
    x1, x2 = x[..., :half], x[..., half:]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], -1)


def sees(cfg: dict, kind: str, queries, keys):
    """[queries, keys] bool: whether a layer of kind `kind` lets the query
    at each position of `queries` see the key at each of `keys`."""
    gap = queries[:, None] - keys[None, :]
    if kind == FULL:
        return gap >= 0
    return (gap >= 0) & (gap < cfg["sliding_window"])


def _attention(cfg, a, x, kind, rows_per_block=128):
    """Causal grouped-query attention of layer kind `kind` over x [T, h]."""
    H, Hkv, D = (cfg["num_attention_heads"], cfg["num_key_value_heads"],
                 cfg["head_dim"])
    T = x.shape[0]
    q = jnp.matmul(x, _f32(a["q_proj"]["kernel"])).reshape(T, H, D)
    k = jnp.matmul(x, _f32(a["k_proj"]["kernel"])).reshape(T, Hkv, D)
    v = jnp.matmul(x, _f32(a["v_proj"]["kernel"])).reshape(T, Hkv, D)
    if cfg["qk_norm"]:
        q = _rms_norm(q, a["q_norm"]["scale"], cfg["rms_norm_eps"])
        k = _rms_norm(k, a["k_norm"]["scale"], cfg["rms_norm_eps"])
    table = rotary_table(cfg, kind, T)
    q, k = _rope(q, table), _rope(k, table)
    blk = _largest_divisor(T, rows_per_block)
    everyone = jnp.arange(T)

    def block(args):
        q_blk, start = args                     # [blk, Hkv, G, D]
        s = jnp.einsum("qkgd,tkd->kgqt", q_blk, k) / math.sqrt(D)
        mask = sees(cfg, kind, start + jnp.arange(blk), everyone)
        s = jnp.where(mask[None, None], s, -jnp.inf)
        return jnp.einsum("kgqt,tkd->qkgd", jax.nn.softmax(s, axis=-1), v)

    o = jax.lax.map(block, (q.reshape(T // blk, blk, Hkv, H // Hkv, D),
                            jnp.arange(0, T, blk)))
    return jnp.matmul(o.reshape(T, H * D), _f32(a["o_proj"]["kernel"]))


def _swiglu(x, gate, up, down):
    return jnp.matmul(jax.nn.silu(jnp.matmul(x, _f32(gate)))
                      * jnp.matmul(x, _f32(up)), _f32(down))


def route(cfg, m, x):
    """(experts [T, k], weights [T, k]) of x [T, h], float32: softmax over
    all experts, the k largest, divided by their sum."""
    probs = jax.nn.softmax(jnp.matmul(x, _f32(m["router"]["kernel"])),
                           axis=-1)
    weights, experts = jax.lax.top_k(probs, cfg["num_experts_per_tok"])
    if cfg["norm_topk_prob"]:
        weights = weights / jnp.sum(weights, axis=-1, keepdims=True)
    return experts, weights


def moe(cfg, m, x):
    """The expert layer over x [T, h]: every expert is applied to every
    token and its result kept where the router chose it."""
    experts, weights = route(cfg, m, x)
    e = m["experts"]

    def one(y, xs):
        index, gate, up, down = xs
        w = jnp.sum(jnp.where(experts == index, weights, 0.0), axis=-1)
        return y + w[:, None] * _swiglu(x, gate, up, down), None

    y, _ = jax.lax.scan(
        one, jnp.zeros_like(x),
        (jnp.arange(cfg["num_experts"]), e["gate_proj"], e["up_proj"],
         e["down_proj"]))
    return y


def hidden_states(cfg: dict, params: dict, ids):
    """Final normed hidden states [T, h] float32 of token ids [T]."""
    eps = cfg["rms_norm_eps"]
    x = _f32(params["embed_tokens"]["embedding"][ids])
    for layer, kind in zip(params["layers"], cfg["layer_types"]):
        x = x + _attention(
            cfg, layer["attn"],
            _rms_norm(x, layer["input_layernorm"]["scale"], eps), kind)
        x = x + moe(cfg, layer["moe"], _rms_norm(
            x, layer["post_attention_layernorm"]["scale"], eps))
    return _rms_norm(x, params["norm"]["scale"], eps)


def head(cfg: dict, params: dict, hidden):
    """Logits (float32) of hidden rows [..., h]; untied."""
    return jnp.matmul(hidden, _f32(params["lm_head"]["kernel"]))


def logits(cfg: dict, params: dict, ids):
    """[T, V] float32 logits of token ids [T] (tests; small sizes)."""
    return head(cfg, params, hidden_states(cfg, params, ids))


# positions in the running median of `position_gaps`: an answer of the cell
# has at least 32 (see the head of this file, and PERF.md section 6 for the
# readings the limit was set from).
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
