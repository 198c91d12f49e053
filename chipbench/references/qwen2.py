"""Plain reference of the Qwen2 decoder (the Llama layout plus q/k/v biases
and tied embeddings), as published: RMSNorm, rotate-half RoPE, grouped-query
causal attention, SwiGLU MLP, next-token cross-entropy, AdamW with a clip
by the global norm.

Straightforward `jax.numpy`, in whatever dtype the parameters have, with no
kernel, no KV cache and no batching. Every matrix product is a
`jnp.matmul` or a `jnp.einsum` and nothing here knows of a lower precision:
the controls round those products' operands from outside
(`lower_precision.py`). It imports nothing of `accelerate_tpu` and takes
nothing that the program has made: the weights come from `make_params`,
the benchmark's own initialiser, which the harness also hands to the
program. The caller sets `jax.default_matmul_precision("highest")`.

Departures from the published code, none of which changes the mathematics:
layers are stacked on a leading axis and walked by `lax.scan` (one
compiled body at any depth), the layer body is recomputed in the backward
pass (`jax.checkpoint`) and the cross-entropy runs in blocks of rows, so
that the float32 run fits one chip once the program's state is freed.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np

# parameter tree (what the program's `models/llama.py` and this file both
# read): name -> (shape builder, kind). kind "w" = normal(0, 0.02),
# "b" = normal(0, 0.02) bias, "one" = ones.


def _leaves(cfg: dict):
    h = cfg["hidden_size"]
    hd = h // cfg["num_attention_heads"]
    kv = cfg["num_key_value_heads"] * hd
    f = cfg["intermediate_size"]
    L = cfg["num_hidden_layers"]
    out = [
        (("embed_tokens", "embedding"), (cfg["vocab_size"], h), "w"),
        (("layers", "input_layernorm", "scale"), (L, h), "one"),
        (("layers", "attn", "q_proj", "kernel"), (L, h, h), "w"),
        (("layers", "attn", "q_proj", "bias"), (L, h), "b"),
        (("layers", "attn", "k_proj", "kernel"), (L, h, kv), "w"),
        (("layers", "attn", "k_proj", "bias"), (L, kv), "b"),
        (("layers", "attn", "v_proj", "kernel"), (L, h, kv), "w"),
        (("layers", "attn", "v_proj", "bias"), (L, kv), "b"),
        (("layers", "attn", "o_proj", "kernel"), (L, h, h), "w"),
        (("layers", "post_attention_layernorm", "scale"), (L, h), "one"),
        (("layers", "mlp", "gate_proj", "kernel"), (L, h, f), "w"),
        (("layers", "mlp", "up_proj", "kernel"), (L, h, f), "w"),
        (("layers", "mlp", "down_proj", "kernel"), (L, f, h), "w"),
        (("norm", "scale"), (h,), "one"),
    ]
    if not cfg.get("tie_word_embeddings", False):
        out.append((("lm_head", "kernel"), (h, cfg["vocab_size"]), "w"))
    return out


def leaf_names(cfg: dict) -> list[str]:
    return [".".join(path) for path, _, _ in _leaves(cfg)]


def param_count(cfg: dict) -> int:
    return int(sum(np.prod(shape) for _, shape, _ in _leaves(cfg)))


def seed_words(seed: int) -> np.ndarray:
    """A seed of up to 64 bits as the two uint32 words of a threefry key,
    so that the seed is DATA to the jitted initialiser (one compile for
    every seed) and seeds above 2**31 need no 64-bit mode."""
    seed = int(seed) & 0xFFFFFFFFFFFFFFFF
    return np.array([seed >> 32, seed & 0xFFFFFFFF], np.uint32)


def make_params(cfg: dict, words, dtype=jnp.float32) -> dict:
    """Every leaf from the seed, on the device, in `dtype`; call it jitted
    (`words` traced). Leaf i draws from fold_in(key, i)."""
    key = jax.random.wrap_key_data(jnp.asarray(words, jnp.uint32))
    tree: dict = {}
    for i, (path, shape, kind) in enumerate(_leaves(cfg)):
        if kind == "one":
            leaf = jnp.ones(shape, dtype)
        else:
            leaf = (jax.random.normal(jax.random.fold_in(key, i), shape,
                                      jnp.float32) * 0.02).astype(dtype)
        node = tree
        for name in path[:-1]:
            node = node.setdefault(name, {})
        node[path[-1]] = leaf
    return tree


def flatten(cfg: dict, tree: dict) -> list:
    """The tree's leaves in `leaf_names` order."""
    out = []
    for path, _, _ in _leaves(cfg):
        node = tree
        for name in path:
            node = node[name]
        out.append(node)
    return out


# ---------------------------------------------------------------------------
# forward
# ---------------------------------------------------------------------------


def _rms_norm(x, scale, eps):
    x32 = x.astype(jnp.float32)
    var = jnp.mean(x32 * x32, axis=-1, keepdims=True)
    return (x32 * jax.lax.rsqrt(var + eps)).astype(x.dtype) * scale


def _rope(x, theta):
    """x [B, T, H, D], positions 0..T-1, rotate-half convention."""
    T, D = x.shape[1], x.shape[-1]
    inv = jnp.asarray(1.0 / (theta ** (np.arange(0, D, 2) / D)), jnp.float32)
    ang = jnp.arange(T, dtype=jnp.float32)[:, None] * inv[None, :]
    cos, sin = jnp.cos(ang)[:, None, :], jnp.sin(ang)[:, None, :]
    x1, x2 = jnp.split(x.astype(jnp.float32), 2, axis=-1)
    out = jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], -1)
    return out.astype(x.dtype)


def _layer(cfg, x, layer):
    """One decoder layer over x [B, T, h]."""
    nh, nkv = cfg["num_attention_heads"], cfg["num_key_value_heads"]
    hd = cfg["hidden_size"] // nh
    eps, theta = cfg["rms_norm_eps"], cfg["rope_theta"]
    B, T = x.shape[:2]
    a = layer["attn"]

    mm = jnp.matmul
    y = _rms_norm(x, layer["input_layernorm"]["scale"], eps)
    q = (mm(y, a["q_proj"]["kernel"]) + a["q_proj"]["bias"]).reshape(
        B, T, nh, hd)
    k = (mm(y, a["k_proj"]["kernel"]) + a["k_proj"]["bias"]).reshape(
        B, T, nkv, hd)
    v = (mm(y, a["v_proj"]["kernel"]) + a["v_proj"]["bias"]).reshape(
        B, T, nkv, hd)
    q, k = _rope(q, theta), _rope(k, theta)
    q = q.reshape(B, T, nkv, nh // nkv, hd)
    s = jnp.einsum("btkgd,bskd->bkgts", q, k).astype(
        jnp.float32) / np.sqrt(hd)
    causal = jnp.arange(T)[:, None] >= jnp.arange(T)[None, :]
    s = jnp.where(causal, s, -jnp.inf)
    p = jax.nn.softmax(s, axis=-1).astype(x.dtype)
    o = jnp.einsum("bkgts,bskd->btkgd", p, v).reshape(
        B, T, nh * hd)
    x = x + mm(o, a["o_proj"]["kernel"])
    y = _rms_norm(x, layer["post_attention_layernorm"]["scale"], eps)
    m = layer["mlp"]
    act = jax.nn.silu(mm(y, m["gate_proj"]["kernel"])) * mm(
        y, m["up_proj"]["kernel"])
    return x + mm(act, m["down_proj"]["kernel"])


def _cast(tree, dtype):
    if dtype is None:
        return tree
    return jax.tree_util.tree_map(lambda w: w.astype(dtype), tree)


def hidden_states(cfg: dict, params: dict, ids, remat: bool = False,
                  dtype=None):
    """Final normed hidden states [B, T, h] of token ids [B, T]. With
    `dtype`, parameters stored in another type (bfloat16 as served) are
    cast to it one layer at a time."""
    x = _cast(params["embed_tokens"]["embedding"][ids], dtype)

    def body(x, layer):
        return _layer(cfg, x, _cast(layer, dtype)), None

    if remat:
        body = jax.checkpoint(body, prevent_cse=False)
    x, _ = jax.lax.scan(body, x, params["layers"])
    return _rms_norm(x, _cast(params["norm"]["scale"], dtype),
                     cfg["rms_norm_eps"])


def head(cfg: dict, params: dict, hidden, dtype=None):
    """Logits (float32) of hidden rows [..., h]."""
    if cfg.get("tie_word_embeddings", False):
        w = params["embed_tokens"]["embedding"].T
    else:
        w = params["lm_head"]["kernel"]
    return jnp.matmul(hidden, _cast(w, dtype)).astype(jnp.float32)


def position_gaps(cfg: dict, params: dict, ids, first, tokens, dtype=None):
    """One served request, teacher-forced. `ids` [T] is its prompt followed
    by its served tokens (then padding, which causal attention never lets
    an earlier position see); `tokens` [C] are candidates for positions
    first .. first+C-1. Returns (how far each candidate's logit lies below
    the best logit at its position, the token this forward itself puts
    first there, each candidate's log-probability)."""
    hid = hidden_states(cfg, params, ids[None, :], dtype=dtype)[0]
    rows = jax.lax.dynamic_slice_in_dim(hid, first - 1, tokens.shape[0],
                                        axis=0)
    logits = head(cfg, params, rows, dtype)
    took = jnp.take_along_axis(logits, tokens[:, None], axis=-1)[:, 0]
    return (logits.max(axis=-1) - took, jnp.argmax(logits, axis=-1),
            took - jax.nn.logsumexp(logits, axis=-1))


# ---------------------------------------------------------------------------
# training: loss, gradients, AdamW
# ---------------------------------------------------------------------------


def loss_fn(cfg: dict, params: dict, ids, rows_per_block: int = 256):
    """Mean next-token cross-entropy of ids [B, T+1], float32, the logits
    made and dropped in blocks of rows."""
    hid = hidden_states(cfg, params, ids[:, :-1], remat=True)
    labels = ids[:, 1:].reshape(-1)
    hid = hid.reshape(-1, hid.shape[-1])
    n = hid.shape[0]
    blk = rows_per_block if n % rows_per_block == 0 else n
    hid = hid.reshape(n // blk, blk, -1)
    labels = labels.reshape(n // blk, blk)

    @jax.checkpoint
    def block(h, l):
        logits = head(cfg, params, h)
        lse = jax.nn.logsumexp(logits, axis=-1)
        took = jnp.take_along_axis(logits, l[:, None], axis=-1)[:, 0]
        return jnp.sum(lse - took)

    total = jax.lax.scan(
        lambda acc, hl: (acc + block(*hl), None), jnp.float32(0.0),
        (hid, labels))[0]
    return total / n


def adamw_step(cfg: dict, opt: dict, params, m, v, count, ids):
    """One optimizer step as the cell states it: gradient of `loss_fn`,
    clip by the global norm, AdamW. Returns (params, m, v, loss, per-leaf
    norms of the clipped gradient, its per-leaf probes)."""
    loss, grads = jax.value_and_grad(
        lambda p: loss_fn(cfg, p, ids))(params)
    gl = flatten(cfg, grads)
    gnorm = jnp.sqrt(sum(jnp.sum(jnp.square(g.astype(jnp.float32)))
                         for g in gl))
    clip = opt.get("clip_global_norm")
    factor = jnp.minimum(1.0, clip / gnorm) if clip else 1.0
    b1, b2, eps = opt["b1"], opt["b2"], opt["eps"]
    lr, wd = opt["learning_rate"], opt["weight_decay"]
    t = count + 1

    def leaf(p, g, m_, v_):
        g = (g * factor).astype(p.dtype)
        m_ = b1 * m_ + (1 - b1) * g
        v_ = b2 * v_ + (1 - b2) * g * g
        mh = m_ / (1 - b1 ** t)
        vh = v_ / (1 - b2 ** t)
        p = p - lr * (mh / (jnp.sqrt(vh) + eps) + wd * p)
        return p.astype(g.dtype), m_.astype(g.dtype), v_.astype(g.dtype)

    out = jax.tree_util.tree_map(leaf, params, grads, m, v)
    pick = lambda i: jax.tree_util.tree_map(  # noqa: E731
        lambda o: o[i], out, is_leaf=lambda o: isinstance(o, tuple))
    norms = jnp.stack([jnp.sqrt(jnp.sum(jnp.square(
        g.astype(jnp.float32)))) * factor for g in gl])
    probes = leaf_probes(cfg, grads) * factor
    return pick(0), pick(1), pick(2), loss, norms, probes


def leaf_norms_of_difference(cfg: dict, a: dict, b: dict):
    """Per leaf, the float32 norm of a - b, in `leaf_names` order."""
    return jnp.stack([
        jnp.sqrt(jnp.sum(jnp.square(x.astype(jnp.float32)
                                    - y.astype(jnp.float32))))
        for x, y in zip(flatten(cfg, a), flatten(cfg, b))])


PROBES = 16


def leaf_probes(cfg: dict, a: dict):
    """[leaves, PROBES]: each leaf's inner products with PROBES fixed
    pseudo-random directions (a multiplicative hash of the element's
    index, uniform in [-0.5, 0.5)). Two gradients are compared through
    these few numbers, so that neither has to be kept whole beside the
    other: the relative distance of the probe vectors estimates the
    relative norm of the gradients' DIFFERENCE, which a lower precision
    moves ten times more than it moves the norm itself."""
    rows = []
    for x in flatten(cfg, a):
        flat = x.reshape(-1).astype(jnp.float32)
        i = jax.lax.iota(jnp.uint32, flat.shape[0])
        row = []
        for k in range(PROBES):
            h = (i + jnp.uint32(k * 7919 + 1)) * jnp.uint32(2654435761)
            h = (h ^ (h >> 15)) * jnp.uint32(2246822519)
            r = (h >> 8).astype(jnp.float32) / 16777216.0 - 0.5
            row.append(jnp.sum(flat * r))
        rows.append(jnp.stack(row))
    return jnp.stack(rows)


def leaf_norms(cfg: dict, a: dict):
    return jnp.stack([jnp.sqrt(jnp.sum(jnp.square(x.astype(jnp.float32))))
                      for x in flatten(cfg, a)])
