"""Plain reference of the dots3-note-prev text decoder (`model_type`
`dots3_note`), as its `config.json` describes it: pre-norm RMSNorm residual
blocks; multi-head latent attention of TWO kinds mixed by `layer_types`,
both with K and V DECOMPRESSED from the normed latent (no absorption, no
cache), the rope parts rotated in adjacent lane pairs, ONE rope key head
shared by all query heads, the two normed latents rescaled
(`apply_mla_qkv_lora_rescale`) and a headwise sigmoid gate on the heads'
outputs:

- `full_attention` (the unprefixed keys): the query attends the
  `index_topk` positions of largest INDEX SCORE among `s <= t` (all of
  them while `t + 1 <= index_topk`; ties to the lower position), `I[t, s] =
  sum_j w[t, j] relu(q_I[t, j] . k_I[s])` over every position, softmax over
  the selected positions alone;
- `sliding_attention` (the `swa_` keys, their own rotary base): a position
  mask, `0 <= t - s < sliding_window_size`.

Then a dense SwiGLU MLP in the first `first_k_dense_replace` layers and, in
the others, a float32 sigmoid router over ALL `router_experts` (`noaux_tc`,
one group: the `num_experts_per_tok` largest of `score +
e_score_correction_bias`, weighted by the scores WITHOUT the bias,
normalised over all the chosen, times `routed_scaling_factor`), every HELD
routed expert's contribution by a masked combine, and the shared expert on
every token. Untied embedding and head.

THE SHARE. The configuration's file describes ONE chip's share of a layer
that eight chips share: `experts_held = [first, count]` of the
`router_experts` routed experts (the expert arrays hold those `count`), and
`vocab_size` rows of the vocabulary. This reference is given the same
share and computes the same partial sum: an assignment to an expert that
is not held contributes nothing, and the partial result goes on to the
next layer, as in the program.

ASSUMED (the configuration's file lists each under `assumed`; `config.json`
names the mechanism by a key and gives no formula): the rescale is `sqrt(
hidden_size / rank)` on each normed latent; the gate is `sigmoid(y W_g)`
from the layer's normed input, one scalar a head, times the head's output
before `W_o`; the index queries come from the query latent `c_q`, the
index key gets a LayerNorm (scale and bias), index queries and key are
rotated over their first `qk_rope_head_dim` lanes, and the head weights
are `(y W_w) / sqrt(index_n_heads x index_head_dim)`, as DeepSeek-V3.2's
published indexer without its Hadamard rotation and float8 rounding.

Straightforward `jax.numpy`, float32, with no kernel, no cache and no
batching. Every matrix product is a `jnp.matmul` or a two-operand
`jnp.einsum` and nothing here knows of a lower precision: the controls
round those products' operands from outside (`lower_precision.py`). It
imports nothing of `accelerate_tpu` or of the other references; the
weights come from `make_params`, the benchmark's own initialiser, which
the harness also hands to the program. The caller sets
`jax.default_matmul_precision("highest")`.

Departures from the published description, none of which changes the
mathematics, all so that 43,008 positions of 128 heads fit beside 8.2 GB of
resident weights: the heads run a GROUP at a time (K and V of a group
decompressed whole, the group's rows of `W_o` applied and summed), inside a
group the queries a block at a time against their full `[block,
positions]` row of scores; a full layer's selection is computed ONCE, a
block of queries at a time against every position, and kept as bits; a
sliding layer's query block reads the slice of keys its window can reach
and masks it by position; the dense MLP runs in blocks of rows; the
experts run one after another (`lax.scan`, each applied to EVERY token and
masked), with parameters stored in bfloat16 cast to float32 a piece at a
time. The selection is written out as the k-th largest score of a row
(`lax.top_k`, exact) and a running count of the ties at it.

How served tokens are judged (`position_gaps`): as
`references/joyai_llm_flash.py` judges them, in this file's own copy: the
gap returned for a position is the MEDIAN of the gaps of that position and
the `GAP_WINDOW - 1` before it, which one token whose 8th and 9th expert,
or 2,048th and 2,049th key, changed places in bfloat16 cannot carry. The
log-probabilities stay one position each.
"""

from __future__ import annotations

import math

import jax
import jax.numpy as jnp
import numpy as np

FULL, SLIDING = "full_attention", "sliding_attention"

# ---------------------------------------------------------------------------
# parameters: the program's tree (accelerate_tpu/models/dots3.py reads the
# same names). kind "w" = normal(0, 0.02), "one" = ones, "zero" = zeros,
# "bias" = the router's float32 correction bias, normal(0, 0.05).
# ---------------------------------------------------------------------------


def kind_of(cfg: dict, kind: str) -> dict:
    """The latent attention of layer kind `kind`, under unprefixed names."""
    p = "" if kind == FULL else "swa_"
    return {name: cfg[p + name] for name in (
        "num_attention_heads", "q_lora_rank", "kv_lora_rank",
        "qk_nope_head_dim", "qk_rope_head_dim", "v_head_dim", "rope_theta")}


def _leaves(cfg: dict):
    h = cfg["hidden_size"]
    f, held = cfg["moe_intermediate_size"], cfg["n_routed_experts"]
    E = cfg["router_experts"]
    J, wI = cfg["index_n_heads"], cfg["index_head_dim"]
    out = [(("embed_tokens", "embedding"), (cfg["vocab_size"], h), "w")]
    for i, kind in enumerate(cfg["layer_types"]):
        L = ("layers", i)
        k = kind_of(cfg, kind)
        H, qr, kvr = (k["num_attention_heads"], k["q_lora_rank"],
                      k["kv_lora_rank"])
        nope, rope, vd = (k["qk_nope_head_dim"], k["qk_rope_head_dim"],
                          k["v_head_dim"])
        out += [
            (L + ("input_layernorm", "scale"), (h,), "one"),
            (L + ("attn", "q_a_proj", "kernel"), (h, qr), "w"),
            (L + ("attn", "q_a_layernorm", "scale"), (qr,), "one"),
            (L + ("attn", "q_b_proj", "kernel"), (qr, H * (nope + rope)), "w"),
            (L + ("attn", "kv_a_proj", "kernel"), (h, kvr + rope), "w"),
            (L + ("attn", "kv_a_layernorm", "scale"), (kvr,), "one"),
            (L + ("attn", "kv_b_proj", "kernel"), (kvr, H * (nope + vd)), "w"),
            (L + ("attn", "o_proj", "kernel"), (H * vd, h), "w"),
            (L + ("attn", "gate_proj", "kernel"), (h, H), "w"),
        ]
        if kind == FULL:
            X = L + ("attn", "indexer")
            out += [
                (X + ("q_proj", "kernel"), (qr, J * wI), "w"),
                (X + ("k_proj", "kernel"), (h, wI), "w"),
                (X + ("k_norm", "scale"), (wI,), "one"),
                (X + ("k_norm", "bias"), (wI,), "zero"),
                (X + ("weights_proj", "kernel"), (h, J), "w"),
            ]
        out += [(L + ("post_attention_layernorm", "scale"), (h,), "one")]
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
                (L + ("moe", "experts", "gate_proj"), (held, h, f), "w"),
                (L + ("moe", "experts", "up_proj"), (held, h, f), "w"),
                (L + ("moe", "experts", "down_proj"), (held, f, h), "w"),
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
    leading axis so that no float32 copy of the whole leaf exists (a
    layer's held experts are 252 M parameters a matrix in the cell)."""
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
    tree: dict = {"layers": [{} for _ in cfg["layer_types"]]}
    for i, (path, shape, kind) in enumerate(_leaves(cfg)):
        k = jax.random.fold_in(key, i)
        if kind == "one":
            leaf = jnp.ones(shape, dtype)
        elif kind == "zero":
            leaf = jnp.zeros(shape, dtype)
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

# query rows a block of attention and of the selection (whose [rows, index
# heads, positions] products are the largest temporary), heads a group,
# rows a block of the dense MLP
ROWS_PER_BLOCK = 128
SELECT_ROWS = 32
HEADS_PER_GROUP = 8
MLP_ROWS = 2048


def _f32(x):
    return x.astype(jnp.float32)


def _rms_norm(x, scale, eps):
    var = jnp.mean(x * x, axis=-1, keepdims=True)
    return x * jax.lax.rsqrt(var + eps) * _f32(scale)


def _layer_norm(x, scale, bias, eps):
    mean = jnp.mean(x, axis=-1, keepdims=True)
    var = jnp.mean((x - mean) ** 2, axis=-1, keepdims=True)
    return (x - mean) * jax.lax.rsqrt(var + eps) * _f32(scale) + _f32(bias)


def _rope(x, theta):
    """x [T, H, D] at positions 0..T-1, rotated in ADJACENT pairs: lanes
    (2i, 2i + 1) by the angle t * theta^(-2i/D); the angles are float64
    before the cosine."""
    T, D = x.shape[0], x.shape[-1]
    inv = 1.0 / (float(theta) ** (np.arange(0, D, 2, dtype=np.float64) / D))
    ang = np.arange(T, dtype=np.float64)[:, None] * inv[None, :]
    cos = jnp.asarray(np.cos(ang), jnp.float32)[:, None, :]
    sin = jnp.asarray(np.sin(ang), jnp.float32)[:, None, :]
    pairs = x.reshape(x.shape[:-1] + (D // 2, 2))
    x0, x1 = pairs[..., 0], pairs[..., 1]
    return jnp.stack([x0 * cos - x1 * sin, x1 * cos + x0 * sin],
                     axis=-1).reshape(x.shape)


def _rope_first(x, lanes, theta):
    """The first `lanes` lanes of every head of x [T, H, D] rotated."""
    return jnp.concatenate([_rope(x[..., :lanes], theta), x[..., lanes:]], -1)


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


def _pack(mask):
    """[rows, T] bool -> [rows, ceil(T / 32)] uint32, bit b of word j is
    position 32 j + b."""
    rows, T = mask.shape
    pad = -T % 32
    bits = jnp.pad(mask, ((0, 0), (0, pad))).reshape(rows, -1, 32)
    return jnp.sum(bits.astype(jnp.uint32)
                   << jnp.arange(32, dtype=jnp.uint32), axis=-1,
                   dtype=jnp.uint32)


def _unpack(words, T):
    bits = (words[:, :, None] >> jnp.arange(32, dtype=jnp.uint32)) & 1
    return bits.reshape(words.shape[0], -1)[:, :T] == 1


def selection(cfg, ix, x, c_q):
    """A full layer's selection for every query, packed ([T, T / 32]
    uint32): index scores of a block of queries against EVERY position,
    then the exact top `index_topk` among the positions `s <= t`."""
    J, wI, r = cfg["index_n_heads"], cfg["index_head_dim"], \
        cfg["qk_rope_head_dim"]
    T, theta = x.shape[0], cfg["rope_theta"]
    q_i = _rope_first(jnp.matmul(c_q, _f32(ix["q_proj"]["kernel"])).reshape(
        T, J, wI), r, theta)
    k_i = _layer_norm(jnp.matmul(x, _f32(ix["k_proj"]["kernel"])),
                      ix["k_norm"]["scale"], ix["k_norm"]["bias"],
                      cfg["rms_norm_eps"])
    k_i = _rope_first(k_i[:, None, :], r, theta)[:, 0]              # [T, w]
    a_i = jnp.matmul(x, _f32(ix["weights_proj"]["kernel"])) / math.sqrt(J * wI)
    blk = _largest_divisor(T, SELECT_ROWS)

    def block(args):
        qi_blk, ai_blk, start = args
        at = start + jnp.arange(blk)
        sees = at[:, None] >= jnp.arange(T)[None, :]
        index = jnp.sum(jax.nn.relu(jnp.einsum("qjw,tw->qjt", qi_blk, k_i))
                        * ai_blk[:, :, None], axis=1)           # [blk, T]
        return _pack(select(index, sees, cfg["index_topk"]))

    n = T // blk
    words = jax.lax.map(block, (q_i.reshape(n, blk, J, wI),
                                a_i.reshape(n, blk, J),
                                jnp.arange(0, T, blk)))
    return words.reshape(T, -1)


def _attention(cfg, kind, a, x, use_selection: bool = True):
    """Latent attention of layer kind `kind` over x [T, h] (the layer's
    normed input), K and V decompressed from the normed latent as
    published. `use_selection=False` lets a full layer attend every earlier
    key (tests and controls: what the model is NOT)."""
    k = kind_of(cfg, kind)
    H, qr, kvr = k["num_attention_heads"], k["q_lora_rank"], k["kv_lora_rank"]
    nope, rope, vd = (k["qk_nope_head_dim"], k["qk_rope_head_dim"],
                      k["v_head_dim"])
    theta, eps = k["rope_theta"], cfg["rms_norm_eps"]
    T, h = x.shape
    window = cfg["sliding_window_size"] if kind == SLIDING else None
    c_q = _rms_norm(jnp.matmul(x, _f32(a["q_a_proj"]["kernel"])),
                    a["q_a_layernorm"]["scale"], eps)
    kv_a = jnp.matmul(x, _f32(a["kv_a_proj"]["kernel"]))
    c_kv = _rms_norm(kv_a[:, :kvr], a["kv_a_layernorm"]["scale"], eps)
    if cfg["apply_mla_qkv_lora_rescale"]:
        c_q = c_q * math.sqrt(h / qr)
        c_kv = c_kv * math.sqrt(h / kvr)
    k_pe = _rope(kv_a[:, None, kvr:], theta)                # [T, 1, rope]
    gate = jax.nn.sigmoid(jnp.matmul(x, _f32(a["gate_proj"]["kernel"])))
    blk = _largest_divisor(T, ROWS_PER_BLOCK)
    n = T // blk
    chosen = None
    if kind == FULL and use_selection:
        chosen = selection(cfg, a["indexer"], x, c_q)        # [T, T / 32]
    # keys a query block reads: a sliding layer's window reaches back
    # `window - 1` positions from the block's first query
    span = T if window is None else min(T, window - 1 + blk)
    G = _largest_divisor(H, HEADS_PER_GROUP)
    w_qb = a["q_b_proj"]["kernel"].reshape(qr, H // G, G, nope + rope)
    w_kvb = a["kv_b_proj"]["kernel"].reshape(kvr, H // G, G, nope + vd)
    w_o = a["o_proj"]["kernel"].reshape(H // G, G * vd, h)

    def heads(out, xs):
        """One group of G heads over every query; its part of `W_o`."""
        qb, kvb, ob, g_gate = xs
        q = jnp.matmul(c_q, _f32(qb).reshape(qr, -1)).reshape(
            T, G, nope + rope)
        q = jnp.concatenate([q[..., :nope], _rope(q[..., nope:], theta)], -1)
        kv = jnp.matmul(c_kv, _f32(kvb).reshape(kvr, -1)).reshape(
            T, G, nope + vd)
        keys = jnp.concatenate(
            [kv[..., :nope], jnp.broadcast_to(k_pe, (T, G, rope))], -1)
        values = kv[..., nope:]

        def block(args):
            q_blk, start, words = args
            first = jnp.clip(start + blk - span, 0, T - span)
            k_blk = jax.lax.dynamic_slice_in_dim(keys, first, span, axis=0)
            v_blk = jax.lax.dynamic_slice_in_dim(values, first, span, axis=0)
            at = (start + jnp.arange(blk))[:, None]
            key_at = (first + jnp.arange(span))[None, :]
            sees = at >= key_at
            if window is not None:
                sees = sees & (at - key_at < window)
            if words is not None:
                sees = sees & _unpack(words, T)
            s = jnp.einsum("qhd,khd->hqk", q_blk, k_blk) / math.sqrt(
                nope + rope)
            s = jnp.where(sees[None], s, -jnp.inf)
            return jnp.einsum("hqk,khd->qhd", jax.nn.softmax(s, axis=-1),
                              v_blk)

        o = jax.lax.map(block, (
            q.reshape(n, blk, G, nope + rope), jnp.arange(0, T, blk),
            None if chosen is None else chosen.reshape(n, blk, -1)))
        o = o.reshape(T, G, vd) * g_gate[:, :, None]
        return out + jnp.matmul(o.reshape(T, G * vd), _f32(ob)), None

    out, _ = jax.lax.scan(
        heads, jnp.zeros((T, h), jnp.float32),
        (jnp.moveaxis(w_qb, 1, 0), jnp.moveaxis(w_kvb, 1, 0), w_o,
         jnp.moveaxis(gate.reshape(T, H // G, G), 1, 0)))
    return out


def _swiglu(x, gate, up, down):
    return jnp.matmul(jax.nn.silu(jnp.matmul(x, _f32(gate)))
                      * jnp.matmul(x, _f32(up)), _f32(down))


def dense_mlp(x, d):
    """The dense SwiGLU over x [T, h], a block of rows at a time."""
    T = x.shape[0]
    rows = _largest_divisor(T, MLP_ROWS)
    return jax.lax.map(
        lambda xb: _swiglu(xb, d["gate_proj"]["kernel"],
                           d["up_proj"]["kernel"], d["down_proj"]["kernel"]),
        x.reshape(T // rows, rows, -1)).reshape(x.shape)


def route(cfg, m, x):
    """(experts [T, k], weights [T, k]) of x [T, h], float32: over ALL the
    router's experts, held here or not."""
    r = m["router"]
    scores = jax.nn.sigmoid(jnp.matmul(x, _f32(r["kernel"])))
    _, experts = jax.lax.top_k(
        scores + _f32(r["e_score_correction_bias"]),
        cfg["num_experts_per_tok"])
    weights = jnp.take_along_axis(scores, experts, axis=-1)
    if cfg["norm_topk_prob"]:
        weights = weights / (jnp.sum(weights, axis=-1, keepdims=True) + 1e-20)
    return experts, weights * cfg["routed_scaling_factor"]


def routed(cfg, m, x):
    """The HELD routed experts' part of the layer over x [T, h]: every
    held expert is applied to every token and its result kept, at the
    router's weight, where the router chose it."""
    experts, weights = route(cfg, m, x)
    first, count = cfg["experts_held"]
    e = m["experts"]

    def one(y, xs):
        index, gate, up, down = xs
        w = jnp.sum(jnp.where(experts == index, weights, 0.0), axis=-1)
        return y + w[:, None] * _swiglu(x, gate, up, down), None

    y, _ = jax.lax.scan(
        one, jnp.zeros_like(x),
        (first + jnp.arange(count), e["gate_proj"], e["up_proj"],
         e["down_proj"]))
    return y


def shared(m, x):
    s = m["shared"]
    return _swiglu(x, s["gate_proj"]["kernel"], s["up_proj"]["kernel"],
                   s["down_proj"]["kernel"])


def hidden_states(cfg: dict, params: dict, ids, use_selection: bool = True):
    """Final normed hidden states [T, h] float32 of token ids [T]."""
    eps = cfg["rms_norm_eps"]
    x = _f32(params["embed_tokens"]["embedding"][ids])
    for kind, layer in zip(cfg["layer_types"], params["layers"]):
        x = x + _attention(
            cfg, kind, layer["attn"],
            _rms_norm(x, layer["input_layernorm"]["scale"], eps),
            use_selection)
        y = _rms_norm(x, layer["post_attention_layernorm"]["scale"], eps)
        if "moe" in layer:
            x = x + routed(cfg, layer["moe"], y) + shared(layer["moe"], y)
        else:
            x = x + dense_mlp(y, layer["mlp"])
    return _rms_norm(x, params["norm"]["scale"], eps)


def head(cfg: dict, params: dict, hidden):
    """Logits (float32) of hidden rows [..., h]; untied."""
    return jnp.matmul(hidden, _f32(params["lm_head"]["kernel"]))


def logits(cfg: dict, params: dict, ids, use_selection: bool = True):
    """[T, V] float32 logits of token ids [T] (tests; small sizes)."""
    return head(cfg, params, hidden_states(cfg, params, ids, use_selection))


# positions in the running median of `position_gaps`: an answer of the cell
# has at least 64 (see the head of this file, and PERF.md section 6 for the
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
