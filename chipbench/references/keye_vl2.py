"""Plain reference of the Keye-VL-2.0-30B-A3B language model (`model_type`
`KeyeVL2`), as its `config.json` describes it: pre-norm RMSNorm residual
blocks without biases; grouped-query attention (`num_attention_heads` query
heads over `num_key_value_heads` key/value heads of `head_dim` lanes)
rotated by the multimodal rotary embedding (`rope_scaling.mrope_section`:
the `head_dim / 2` half-split pairs split over a temporal, a height and a
width position id; text gives all three the same id), whose keys a learned
INDEXER chooses (`sa_config`): `indexer_num_heads` index queries and ONE
index key of `indexer_head_dim` lanes a token, the index score `I[t, s] =
sum_j a[t, j] relu(qI[t, j] . kI[s])` for `s <= t`, the `topk` positions of
largest score (all of them while `t + 1 <= topk`; ties to the lower
position), and a softmax over those positions alone; and in every layer a
softmax router in float32 over all `num_experts`, the `num_experts_per_tok`
largest probabilities renormalised (`norm_topk_prob`), every expert's
SwiGLU contribution by a masked combine. No dense layer, no shared expert
(`decoder_sparse_step` 1, `mlp_only_layers` []). Untied embedding and
head. The vision tower is not on the text path and is left out.

ASSUMED (the configuration's file lists each under `assumed`; `config.json`
has no key for any of them): q and k get a per-head RMSNorm over their
`head_dim` lanes before the rotation (`qk_norm`, the Qwen3-MoE block's
convention); the index queries and the head weights come from the layer's
normed input; the index key gets a LayerNorm (scale and bias) over its
lanes, index queries and key are rotated by the temporal position over all
their lanes in half-split pairs with the model's `rope_theta`, and the head
weights are `(y W_w) / sqrt(indexer_num_heads x indexer_head_dim)`, as in
DeepSeek-V3.2's published indexer, without its Hadamard rotation (an
orthogonal map of queries and key both: no score changes) and without its
float8 rounding; `topk` counts tokens; `q_chunk_size` / `kv_chunk_size` are
the tiles of a blocked implementation and change no result.

Straightforward `jax.numpy`, float32, with no kernel, no cache and no
batching. Every matrix product is a `jnp.matmul` or a two-operand
`jnp.einsum` and nothing here knows of a lower precision: the controls
round those products' operands from outside (`lower_precision.py`). It
imports nothing of `accelerate_tpu` or of the other references; the
weights come from `make_params`, the benchmark's own initialiser, which
the harness also hands to the program. The caller sets
`jax.default_matmul_precision("highest")`.

Departures from the published description, none of which changes the
mathematics: attention runs in blocks of query rows, each against its own
full `[block, positions]` row of the index-score matrix, of the selection
and of the attention scores (so the `[positions, positions]` matrices exist
a block of rows at a time and 43,008 positions fit); the selection is
written out as the k-th largest score of a row (`lax.top_k`, exact) and a
running count of the ties at it; and the experts run one after another
(`lax.scan` over the expert axis, each applied to EVERY token and masked),
with parameters stored in bfloat16 cast to float32 a piece at a time, so
that the pass fits beside 8.75 GB of resident weights.

How served tokens are judged (`position_gaps`): as
`references/mellum2.py` judges them, in this file's own copy. The 8
experts are the largest 8 of 128 probabilities, and at the stated precision
(bfloat16 activations) the 8th and the 9th change places for some tokens in
some layer; ONE such token's logits move as far as float8 moves every
token's. The same holds of the 2,048th and 2,049th index score, at a
2,048th of the weight. The gap returned for a position is therefore the
MEDIAN of the gaps of that position and the `GAP_WINDOW - 1` before it,
which one token cannot carry. The log-probabilities stay one position each.
"""

from __future__ import annotations

import math

import jax
import jax.numpy as jnp
import numpy as np

# ---------------------------------------------------------------------------
# parameters: the program's tree (accelerate_tpu/models/keye.py reads the
# same names). kind "w" = normal(0, 0.02), "one" = ones, "zero" = zeros.
# ---------------------------------------------------------------------------


def _leaves(cfg: dict):
    h, D = cfg["hidden_size"], cfg["head_dim"]
    H, Hkv = cfg["num_attention_heads"], cfg["num_key_value_heads"]
    f, E = cfg["moe_intermediate_size"], cfg["num_experts"]
    J = cfg["sa_config"]["indexer_num_heads"]
    w = cfg["sa_config"]["indexer_head_dim"]
    out = [(("embed_tokens", "embedding"), (cfg["vocab_size"], h), "w")]
    for i in range(cfg["num_hidden_layers"]):
        L = ("layers", i)
        out += [
            (L + ("input_layernorm", "scale"), (h,), "one"),
            (L + ("attn", "q_proj", "kernel"), (h, H * D), "w"),
            (L + ("attn", "k_proj", "kernel"), (h, Hkv * D), "w"),
            (L + ("attn", "v_proj", "kernel"), (h, Hkv * D), "w"),
            (L + ("attn", "o_proj", "kernel"), (H * D, h), "w"),
            (L + ("attn", "indexer", "q_proj", "kernel"), (h, J * w), "w"),
            (L + ("attn", "indexer", "k_proj", "kernel"), (h, w), "w"),
            (L + ("attn", "indexer", "k_norm", "scale"), (w,), "one"),
            (L + ("attn", "indexer", "k_norm", "bias"), (w,), "zero"),
            (L + ("attn", "indexer", "weights_proj", "kernel"), (h, J), "w"),
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
    routed experts are 3.6 B parameters in the cell)."""
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
                else jnp.zeros(shape, dtype) if kind == "zero"
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


def _layer_norm(x, scale, bias, eps):
    mean = jnp.mean(x, axis=-1, keepdims=True)
    var = jnp.mean((x - mean) ** 2, axis=-1, keepdims=True)
    return (x - mean) * jax.lax.rsqrt(var + eps) * _f32(scale) + _f32(bias)


def text_positions(length: int) -> np.ndarray:
    """[3, T]: text gives the temporal, height and width rows one id."""
    return np.broadcast_to(np.arange(length)[None, :], (3, length))


def rotary_table(theta: float, lanes: int, positions, sections=None):
    """(cos, sin) [T, lanes / 2] float32. The angle of pair i (lane i with
    lane i + lanes / 2) at a token is `p * theta^(-2i / lanes)`, where p is
    the token's position in the row that pair i belongs to: with `sections`
    (`mrope_section`, as many entries as `positions` [rows, T] has rows)
    the first `sections[0]` pairs take row 0, the next `sections[1]` row 1,
    and so on; without, every pair takes row 0. `positions` is a NumPy
    array: the angles are float64 before the cosine."""
    pairs = lanes // 2
    freq = float(theta) ** (-2.0 * np.arange(pairs, dtype=np.float64) / lanes)
    row = (np.zeros(pairs, np.int64) if sections is None
           else np.repeat(np.arange(len(sections)), sections))
    ang = np.asarray(positions, np.float64)[row, :].T * freq[None, :]
    return (jnp.asarray(np.cos(ang), jnp.float32),
            jnp.asarray(np.sin(ang), jnp.float32))


def _rope(x, table):
    """x [T, H, D] rotated in HALF-SPLIT pairs by `table` [T, D / 2]."""
    cos, sin = (t[:, None, :] for t in table)
    half = x.shape[-1] // 2
    x1, x2 = x[..., :half], x[..., half:]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], -1)


def select(scores, visible, k: int):
    """[rows, T] bool: in each row the `k` visible positions of largest
    score, all of them where fewer are visible; among equal scores the
    lower positions first."""
    masked = jnp.where(visible, scores, -jnp.inf)
    kth = jax.lax.top_k(masked, min(k, masked.shape[-1]))[0][:, -1:]
    above = masked > kth
    tie = visible & (masked == kth)
    room = k - jnp.sum(above, axis=-1, keepdims=True)
    return (above & visible) | (tie & (jnp.cumsum(tie, axis=-1) <= room))


def _attention(cfg, a, x, positions, rows_per_block=128,
               selection: bool = True):
    """Grouped-query attention over the keys the indexer selects, x [T, h],
    positions [3, T]. `selection=False` attends every earlier key (tests
    and controls: what the model is NOT)."""
    H, Hkv, D = (cfg["num_attention_heads"], cfg["num_key_value_heads"],
                 cfg["head_dim"])
    sa = cfg["sa_config"]
    J, w = sa["indexer_num_heads"], sa["indexer_head_dim"]
    T = x.shape[0]
    q = jnp.matmul(x, _f32(a["q_proj"]["kernel"])).reshape(T, H, D)
    k = jnp.matmul(x, _f32(a["k_proj"]["kernel"])).reshape(T, Hkv, D)
    v = jnp.matmul(x, _f32(a["v_proj"]["kernel"])).reshape(T, Hkv, D)
    if cfg["qk_norm"]:
        q = _rms_norm(q, a["q_norm"]["scale"], cfg["rms_norm_eps"])
        k = _rms_norm(k, a["k_norm"]["scale"], cfg["rms_norm_eps"])
    table = rotary_table(cfg["rope_theta"], D, positions,
                         cfg["rope_scaling"]["mrope_section"])
    q, k = _rope(q, table), _rope(k, table)
    ix = a["indexer"]
    table_i = rotary_table(cfg["rope_theta"], w, positions[:1])
    q_i = _rope(jnp.matmul(x, _f32(ix["q_proj"]["kernel"])).reshape(T, J, w),
                table_i)
    k_i = _layer_norm(jnp.matmul(x, _f32(ix["k_proj"]["kernel"])),
                      ix["k_norm"]["scale"], ix["k_norm"]["bias"],
                      cfg["rms_norm_eps"])
    k_i = _rope(k_i[:, None, :], table_i)[:, 0]                     # [T, w]
    a_i = jnp.matmul(x, _f32(ix["weights_proj"]["kernel"])) / math.sqrt(J * w)
    blk = _largest_divisor(T, rows_per_block)
    when = jnp.asarray(positions[0])

    def block(args):
        q_blk, qi_blk, ai_blk, at = args
        sees = at[:, None] >= when[None, :]                     # [blk, T]
        if selection:
            index = jnp.sum(jax.nn.relu(jnp.einsum("qjw,tw->qjt", qi_blk, k_i))
                            * ai_blk[:, :, None], axis=1)       # [blk, T]
            sees = select(index, sees, sa["topk"])
        s = jnp.einsum("qkgd,tkd->kgqt", q_blk, k) / math.sqrt(D)
        s = jnp.where(sees[None, None], s, -jnp.inf)
        return jnp.einsum("kgqt,tkd->qkgd", jax.nn.softmax(s, axis=-1), v)

    n = T // blk
    o = jax.lax.map(block, (q.reshape(n, blk, Hkv, H // Hkv, D),
                            q_i.reshape(n, blk, J, w),
                            a_i.reshape(n, blk, J), when.reshape(n, blk)))
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


def hidden_states(cfg: dict, params: dict, ids, positions=None,
                  selection: bool = True):
    """Final normed hidden states [T, h] float32 of token ids [T] at
    `positions` [3, T] (text positions by default)."""
    eps = cfg["rms_norm_eps"]
    if positions is None:
        positions = text_positions(ids.shape[0])
    x = _f32(params["embed_tokens"]["embedding"][ids])
    for layer in params["layers"]:
        x = x + _attention(
            cfg, layer["attn"],
            _rms_norm(x, layer["input_layernorm"]["scale"], eps), positions,
            selection=selection)
        x = x + moe(cfg, layer["moe"], _rms_norm(
            x, layer["post_attention_layernorm"]["scale"], eps))
    return _rms_norm(x, params["norm"]["scale"], eps)


def head(cfg: dict, params: dict, hidden):
    """Logits (float32) of hidden rows [..., h]; untied."""
    return jnp.matmul(hidden, _f32(params["lm_head"]["kernel"]))


def logits(cfg: dict, params: dict, ids, positions=None,
           selection: bool = True):
    """[T, V] float32 logits of token ids [T] (tests; small sizes)."""
    return head(cfg, params, hidden_states(cfg, params, ids, positions,
                                           selection))


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
