"""Plain reference of AI21-Jamba2-3B (`model_type` `jamba`), as its
`config.json` and the papers of its two mechanisms describe it: 28 pre-norm
RMSNorm residual blocks, `x <- x + mixer(RMSNorm(x))` then `x <- x +
SwiGLU(RMSNorm(x))`, the mixer of layer i an ATTENTION layer iff `i %
attn_layer_period == attn_layer_offset`, else a MAMBA layer (Mamba-1: Gu &
Dao, arXiv:2312.00752, with the jamba family's three inner norms); final
RMSNorm; the head is the embedding (`tie_word_embeddings`). Dense:
`num_experts` is 1, so the family builds no expert block in any layer.

MAMBA mixer (`d = mamba_expand * hidden_size`, `n = mamba_d_state`, `taps =
mamba_d_conv`, `r = mamba_dt_rank`), over the normed rows `h_t`:

    [u_t; z_t] = h_t W_in                                  (2 x d, no bias)
    c_t = SiLU(b_c + sum_{j < taps} w_c[j] * u_{t - taps + 1 + j})
                    (depthwise, causal; u before the sequence's start is 0)
    [r_t; B_t; C_t] = c_t W_x                                   (r + n + n)
    r_t, B_t, C_t <- RMSNorm_dt(r_t), RMSNorm_B(B_t), RMSNorm_C(C_t)
    dt_t = softplus(r_t W_dt + b_dt)                                    (d)
    A = -exp(A_log)                                                (n x d)
    S_t = exp(dt_t * A) * S_{t-1} + (dt_t * c_t) (x) B_t    (n x d, S_-1 = 0)
    y_t = sum_n S_t[n, :] * C_t[n] + D * c_t
    out_t = (y_t * SiLU(z_t)) W_out                              (no bias)

It is written here as it stands: the recurrence position by position, one
plain `jax.lax.scan` over t with no chunking and no kernel, the convolution
as `taps` shifted sums. ATTENTION mixer: `q = h W_q` (`num_attention_heads`
heads of hidden_size / heads lanes), `k = h W_k`, `v = h W_v`
(`num_key_value_heads` heads), no biases, NO rotation and no position term
of any kind, causal softmax of `q k^T / sqrt(D)` times v, through `W_o`.

ASSUMED (the configuration's file lists each under `assumed`): the order of
the layer kinds from `attn_layer_offset` / `attn_layer_period`; no
positional encoding; the seeded values of what training would set (`A_log =
log(1 .. n)` a channel, `D = 1`, `b_dt` such that `softplus(b_dt)` is spread
log-uniformly over [1e-3, 1e-1], the convolution's taps and bias uniform in
(-1/2, 1/2), inner norm scales 1); state and window in float32.

Straightforward `jax.numpy`, float32, with no kernel, no cache and no
batching. Every matrix product is a `jnp.matmul` or a two-operand
`jnp.einsum` and nothing here knows of a lower precision: the controls
round those products' operands from outside (`lower_precision.py`). It
imports nothing of `accelerate_tpu` or of the other references; the
weights come from `make_params`, the benchmark's own initialiser, which
the harness also hands to the program. The caller sets
`jax.default_matmul_precision("highest")`.

Departures from the published description, none of which changes the
mathematics: `A_log` is stored `[n, d]` (the published tensor is `[d, n]`);
attention runs a block of query rows at a time, each against every position
(so the `[positions, positions]` scores exist a block of rows at a time),
the MLP a block of rows and the head a block of the vocabulary at a time,
and parameters stored in bfloat16 are cast to float32 a piece at a time
(the float32 pass then fits beside 6.06 GB of resident bfloat16 weights).
"""

from __future__ import annotations

import math

import jax
import jax.numpy as jnp
import numpy as np

# ---------------------------------------------------------------------------
# parameters: the program's tree (accelerate_tpu/models/jamba.py reads the
# same names). kind "w" = normal(0, 0.02), "one" = ones, "conv" = uniform
# (-1/2, 1/2); "a_log", "d_one", "dt_bias" = see the head of this file, kept
# float32 whatever `dtype` (they are what the recurrence's rates are made
# of: 0.18 M numbers a layer).
# ---------------------------------------------------------------------------


def attention_layers(cfg: dict) -> tuple:
    return tuple(i for i in range(cfg["num_hidden_layers"])
                 if i % cfg["attn_layer_period"] == cfg["attn_layer_offset"])


def _leaves(cfg: dict):
    h, f = cfg["hidden_size"], cfg["intermediate_size"]
    H, G = cfg["num_attention_heads"], cfg["num_key_value_heads"]
    D = h // H
    d, n = cfg["mamba_expand"] * h, cfg["mamba_d_state"]
    r, taps = cfg["mamba_dt_rank"], cfg["mamba_d_conv"]
    attends = attention_layers(cfg)
    out = [(("embed_tokens", "embedding"), (cfg["vocab_size"], h), "w")]
    for i in range(cfg["num_hidden_layers"]):
        L = ("layers", i)
        out.append((L + ("input_layernorm", "scale"), (h,), "one"))
        if i in attends:
            out += [
                (L + ("attn", "q_proj", "kernel"), (h, H * D), "w"),
                (L + ("attn", "k_proj", "kernel"), (h, G * D), "w"),
                (L + ("attn", "v_proj", "kernel"), (h, G * D), "w"),
                (L + ("attn", "o_proj", "kernel"), (H * D, h), "w")]
        else:
            M = L + ("mamba",)
            out += [
                (M + ("in_proj", "kernel"), (h, 2 * d), "w"),
                (M + ("conv", "kernel"), (taps, d), "conv"),
                (M + ("conv", "bias"), (d,), "conv"),
                (M + ("x_proj", "kernel"), (d, r + 2 * n), "w"),
                (M + ("dt_norm", "scale"), (r,), "one"),
                (M + ("b_norm", "scale"), (n,), "one"),
                (M + ("c_norm", "scale"), (n,), "one"),
                (M + ("dt_proj", "kernel"), (r, d), "w"),
                (M + ("dt_proj", "bias"), (d,), "dt_bias"),
                (M + ("A_log",), (n, d), "a_log"),
                (M + ("D",), (d,), "d_one"),
                (M + ("out_proj", "kernel"), (d, h), "w")]
        out += [
            (L + ("pre_ff_layernorm", "scale"), (h,), "one"),
            (L + ("mlp", "gate_proj", "kernel"), (h, f), "w"),
            (L + ("mlp", "up_proj", "kernel"), (h, f), "w"),
            (L + ("mlp", "down_proj", "kernel"), (f, h), "w")]
    out.append((("norm", "scale"), (h,), "one"))
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
    embedding is 168 M parameters)."""
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


def dt_bias(channels: int) -> np.ndarray:
    """`b_dt` such that `softplus(b_dt)` runs log-uniformly from 1e-3 to
    1e-1 over the channels: with `A = -(1 .. n)` memories of some 10 to
    some 1,000 tokens side by side."""
    dt = np.exp(np.linspace(np.log(1e-3), np.log(1e-1), channels))
    return (dt + np.log(-np.expm1(-dt))).astype(np.float32)


def make_params(cfg: dict, words, dtype=jnp.float32) -> dict:
    """Every leaf from the seed, on the device, in `dtype` (the rates'
    leaves in float32); call it jitted (`words` traced). Leaf i draws from
    fold_in(key, i). `layers` is a list of per-layer trees."""
    key = jax.random.wrap_key_data(jnp.asarray(words, jnp.uint32))
    tree: dict = {"layers": [{} for _ in range(cfg["num_hidden_layers"])]}
    for i, (path, shape, kind) in enumerate(_leaves(cfg)):
        if kind == "one":
            leaf = jnp.ones(shape, dtype)
        elif kind == "d_one":
            leaf = jnp.ones(shape, jnp.float32)
        elif kind == "dt_bias":
            leaf = jnp.asarray(dt_bias(shape[0]))
        elif kind == "a_log":
            leaf = jnp.broadcast_to(jnp.log(jnp.arange(
                1, shape[0] + 1, dtype=jnp.float32))[:, None], shape)
        elif kind == "conv":
            leaf = jax.random.uniform(jax.random.fold_in(key, i), shape,
                                      jnp.float32, -0.5, 0.5).astype(dtype)
        else:
            leaf = _normal(jax.random.fold_in(key, i), shape, 0.02, dtype)
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


def _mamba(cfg, m, x):
    """The Mamba mixer over x [T, h] (float32, normed) -> [T, h]."""
    T = x.shape[0]
    d, n = cfg["mamba_expand"] * cfg["hidden_size"], cfg["mamba_d_state"]
    r, taps, eps = cfg["mamba_dt_rank"], cfg["mamba_d_conv"], cfg["rms_norm_eps"]
    uz = jnp.matmul(x, _f32(m["in_proj"]["kernel"]))
    u, z = uz[:, :d], uz[:, d:]
    # the causal depthwise convolution, `taps` shifted sums
    padded = jnp.concatenate([jnp.zeros((taps - 1, d), jnp.float32), u])
    w = _f32(m["conv"]["kernel"])
    c = _f32(m["conv"]["bias"]) + sum(
        w[j] * padded[j:j + T] for j in range(taps))
    c = jax.nn.silu(c)
    proj = jnp.matmul(c, _f32(m["x_proj"]["kernel"]))
    rt = _rms_norm(proj[:, :r], m["dt_norm"]["scale"], eps)
    Bm = _rms_norm(proj[:, r:r + n], m["b_norm"]["scale"], eps)
    Cm = _rms_norm(proj[:, r + n:], m["c_norm"]["scale"], eps)
    dt = jax.nn.softplus(jnp.matmul(rt, _f32(m["dt_proj"]["kernel"]))
                         + _f32(m["dt_proj"]["bias"]))
    A = -jnp.exp(_f32(m["A_log"]))                                   # [n, d]

    def step(S, row):
        dt_t, c_t, b_t, c_out = row
        S = jnp.exp(dt_t[None, :] * A) * S + (dt_t * c_t)[None, :] * b_t[:, None]
        return S, jnp.sum(S * c_out[:, None], axis=0)

    _, y = jax.lax.scan(step, jnp.zeros((n, d), jnp.float32),
                        (dt, c, Bm, Cm))
    y = (y + _f32(m["D"]) * c) * jax.nn.silu(z)
    return jnp.matmul(y, _f32(m["out_proj"]["kernel"]))


def _attention(cfg, a, x, rows_per_block=256):
    """Causal multi-query attention over x [T, h] (float32, normed), no
    position term -> [T, h]; a block of query rows against every position."""
    H, G = cfg["num_attention_heads"], cfg["num_key_value_heads"]
    D = cfg["hidden_size"] // H
    T = x.shape[0]
    q = jnp.matmul(x, _f32(a["q_proj"]["kernel"])).reshape(T, G, H // G, D)
    k = jnp.matmul(x, _f32(a["k_proj"]["kernel"])).reshape(T, G, D)
    v = jnp.matmul(x, _f32(a["v_proj"]["kernel"])).reshape(T, G, D)
    blk = _largest_divisor(T, rows_per_block)
    at = jnp.arange(T)

    def block(args):
        q_blk, when = args                         # [blk, G, Hg, D], [blk]
        s = jnp.einsum("qghd,tgd->ghqt", q_blk, k) / math.sqrt(D)
        s = jnp.where((when[:, None] >= at[None, :])[None, None], s, -jnp.inf)
        return jnp.einsum("ghqt,tgd->qghd", jax.nn.softmax(s, axis=-1), v)

    o = jax.lax.map(block, (q.reshape(T // blk, blk, G, H // G, D),
                            at.reshape(T // blk, blk)))
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
        y = _rms_norm(x, layer["input_layernorm"]["scale"], eps)
        x = x + (_attention(cfg, layer["attn"], y) if "attn" in layer
                 else _mamba(cfg, layer["mamba"], y))
        x = x + _swiglu(_rms_norm(x, layer["pre_ff_layernorm"]["scale"], eps),
                        layer["mlp"])
    return _rms_norm(x, params["norm"]["scale"], eps)


def head(cfg: dict, params: dict, hidden, rows_per_block=16384):
    """Logits (float32) of hidden rows [N, h]; the head is the embedding. A
    block of the vocabulary at a time."""
    w = params["embed_tokens"]["embedding"]                          # [V, h]
    blk = _largest_divisor(w.shape[0], rows_per_block)
    out = jax.lax.map(
        lambda j: jnp.einsum("nh,vh->nv", hidden, _f32(
            jax.lax.dynamic_slice_in_dim(w, j * blk, blk, axis=0))),
        jnp.arange(w.shape[0] // blk))                       # [n, N, blk]
    return jnp.moveaxis(out, 0, 1).reshape(hidden.shape[0], -1)


def logits(cfg: dict, params: dict, ids):
    """[T, V] float32 logits of token ids [T] (tests; small sizes)."""
    return head(cfg, params, hidden_states(cfg, params, ids))


def position_gaps(cfg: dict, params: dict, ids, first, tokens, dtype=None):
    """One served request, teacher-forced. `ids` [T] is its prompt followed
    by its served tokens (then padding, which nothing causal lets an
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
