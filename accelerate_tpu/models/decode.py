"""Shared KV-cache decode + generate driver for the model zoo.

The reference's only published benchmark is load + *generate* time for
GPT-J-6B / GPT-NeoX-20B / OPT-30B / T0pp (ref benchmarks/README.md:25-36,
benchmarks/big_model_inference.py) — so decode is a first-class path for
every causal family here, not just the flagship.

Design (TPU-first):
- caches stack on a leading layer dim ([L, B, M, H, D]) and ride the same
  `lax.scan` over layers as training — ONE compiled layer body at any depth.
- `cache_len` is a traced scalar: decode steps at any position share one
  compiled program (no per-position retracing).
- the whole decode loop is ONE compiled program (`lax.scan` over steps with
  (last_token, caches) as carry) — a single dispatch for all tokens instead
  of a host round-trip per token, which dominates on remote devices.
- each family keeps its own `forward(config, params, ids, positions=...,
  kv_caches=...) -> (logits, new_caches)`; `build_generate` turns that
  uniform signature into a compiled prefill + fused-decode pair, cached per
  (config, temperature) so repeat calls never recompile.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from .common import dot_product_attention, part, repeat_kv


def make_kv_caches(num_layers: int, batch: int, max_len: int,
                   num_kv_heads: int, head_dim: int, dtype=jnp.bfloat16):
    """Stacked decode caches: (k [L, B, M, H, D], v [L, B, M, H, D],
    cache_len scalar)."""
    shape = (num_layers, batch, max_len, num_kv_heads, head_dim)
    return (
        jnp.zeros(shape, dtype),
        jnp.zeros(shape, dtype),
        jnp.zeros((), jnp.int32),
    )


def rope_table_len(config_max: int, kv_caches) -> int:
    """Rotary-table length covering both the config's trained range and the
    cache reach: decoding past max_position_embeddings must extend the
    angles, not gather-clamp every overflow position to the last row."""
    if kv_caches is None:
        return config_max
    if getattr(kv_caches[2], "is_paged_meta", False):
        # paged pool: the cache reach is one slot's view (pages_per_slot
        # * page_size), not the pool's page count
        return max(config_max, kv_caches[2].rows)
    return max(config_max, kv_caches[0].shape[2])


def layer_view(views, layer: int):
    """Layer `layer`'s view [B, R, ...] of what a family that loops over
    its layers was handed in a stacked view's place: an array [L, B, R,
    ...], sliced; or one slot's pages read a layer at a time
    (`serving.cache.LayerwiseSlotView`, what the serving engine's prefill
    hands a family that declares `takes_layerwise_views`), gathered now."""
    if getattr(views, "is_layerwise_view", False):
        return views.at_layer(layer)
    with part("cache.view"):
        return views[layer]


def extend_cache(kv_cache, k, v):
    """Write this step's K/V [B, S, H, D] at cache_len.

    Returns (k_full, v_full, new_cache) where k_full/v_full are the whole
    [B, M, H, D] buffers (attend over them with a position mask — see
    `cached_attention_mask`) and new_cache has cache_len advanced by S.
    """
    ck, cv, cache_len = kv_cache
    zero = jnp.zeros((), jnp.int32)
    k_full = jax.lax.dynamic_update_slice(
        ck, k.astype(ck.dtype), (zero, cache_len, zero, zero))
    v_full = jax.lax.dynamic_update_slice(
        cv, v.astype(cv.dtype), (zero, cache_len, zero, zero))
    return k_full, v_full, (k_full, v_full, cache_len + k.shape[1])


def cached_attention_mask(k_len: int, positions, mask=None):
    """[B, S_q, S_k] decode mask: query at position p attends to cached
    positions <= p (causality holds within the prefill chunk too). An
    optional [B, S_k] key-padding mask over the WHOLE cache ANDs in."""
    if mask is not None and mask.shape[-1] != k_len:
        raise ValueError(
            f"attention_mask covers {mask.shape[-1]} positions but the KV "
            f"cache holds {k_len}; on the decode path the mask must span the "
            "whole cache — pad it to the cache length (1 = attend)"
        )
    kv_mask = jnp.arange(k_len)[None, None, :] <= positions[:, :, None]
    return kv_mask if mask is None else mask[:, None, :] & kv_mask


def windowed_cached_attention_mask(k_len: int, positions, mask=None,
                                   window: int | None = None):
    """`cached_attention_mask` with a sliding window: cached keys older than
    `window` positions (q - key >= window, HF Mistral convention) drop out,
    so single-token decode steps past the window match the full forward."""
    kv_mask = cached_attention_mask(k_len, positions, mask)
    if window is None:
        return kv_mask
    in_band = jnp.arange(k_len)[None, None, :] > positions[:, :, None] - window
    return kv_mask & in_band


def decode_attention(q, k, v, kv_cache, positions, mask=None,
                     window: int | None = None, n_rep: int = 1):
    """The decode-path cache-attend step every causal family shares:
    write this step's K/V into the cache, attend over it, return
    (attn_out, new_cache). Dispatches on the cache flavor:

    - dense stacked caches ((k, v, cache_len) of [B, M, Hkv, D]
      buffers): exactly the classic pipeline — `extend_cache`,
      `windowed_cached_attention_mask`, GQA `repeat_kv`, einsum
      attention. `new_cache` is the familiar (k_full, v_full, len+S).
    - the serving engine's paged pool (`ops.paged_attention.PagedKV`
      pair + `PagedDecodeMeta` in the cache_len slot): each slot's live
      pages stream through the Pallas paged-attention kernel in place —
      no gather, no repeat_kv (the GQA group broadcast happens
      in-kernel). `new_cache` then carries this step's per-slot K/V
      ROWS ([B, 1, Hkv, D], cast to the pool's row dtype) for the
      engine to scatter — the traced program never rewrites the pool.

    The paged check is an attribute marker so the dense path (training,
    single-request generate) never imports the pallas-backed module."""
    if getattr(kv_cache[0], "is_paged_kv", False):
        from ..ops.paged_attention import paged_decode_attention

        if mask is not None:
            raise ValueError(
                "key-padding masks are not supported on the paged decode "
                "path (the engine's position masking is in-kernel)")
        pk, pv, meta = kv_cache
        with part("attn.attend"):
            out, (k_row, v_row) = paged_decode_attention(
                q, k, v, pk, pv, meta, window=window)
        return out, (k_row, v_row, meta)
    with part("cache.write"):
        k_full, v_full, new_cache = extend_cache(kv_cache, k, v)
    with part("attn.attend"):
        m = windowed_cached_attention_mask(k_full.shape[1], positions, mask,
                                           window)
        out = dot_product_attention(q, repeat_kv(k_full, n_rep),
                                    repeat_kv(v_full, n_rep), mask=m,
                                    causal=False)
    return out, new_cache


def scan_decode_layers(layer_step, x, layers, kv_caches):
    """The decode path's `lax.scan` over layers, for either cache flavor:
    ONE compiled layer body at any depth.

    `layer_step(x, layer, cache) -> (y, new_cache)` is a family's layer
    body on the cached path; `layers` its stacked layer params;
    `kv_caches` the stacked `(k, v, cache_len)`. Returns `(x, (nk, nv))`
    with the layers' new caches stacked again.

    What rides the scan beside the layer params depends on the flavor,
    and is known here only: a dense cache [L, B, M, H, D] is sliced a
    layer a step, as a scan does; the serving engine's paged pool
    (`PagedKV`) is NOT scanned over: it is closed over whole, the scan
    carries `arange(L)`, and each step reads the pool at its own index
    (the paged kernel takes the stacked pool and a layer: no per-layer
    slice of the pool is ever made)."""
    ck, cv, cache_len = kv_caches
    if getattr(ck, "is_paged_kv", False):
        def layer_caches(i):
            return ck.at_layer(i), cv.at_layer(i), cache_len

        xs = jnp.arange(ck.data.shape[0], dtype=jnp.int32)
    else:
        def layer_caches(kv):
            return kv[0], kv[1], cache_len

        xs = (ck, cv)

    def body(carry, step):
        layer, at = step
        y, (nk, nv, _) = layer_step(carry, layer, layer_caches(at))
        return y, (nk, nv)

    return jax.lax.scan(body, x, (layers, xs))


def _is_batched_keys(key) -> bool:
    """A batch of PRNG keys (one per row) vs a single key: typed key arrays
    batch when they carry any leading dims; raw uint32 keys are [2] single,
    [B, 2] batched."""
    if key is None or not hasattr(key, "dtype"):
        return False
    if jnp.issubdtype(key.dtype, jax.dtypes.prng_key):
        return key.ndim >= 1
    return key.ndim >= 2


@part("sample")
def sample_token(logits, key, temperature: float):
    """Next token from the last position's logits: argmax at temperature 0,
    else temperature-scaled categorical. The ONE sampling rule shared by the
    on-device, streamed, T5, and serving decode paths.

    `key` may be a single key (one stream for the whole batch — fine when
    the batch is one request's beams) or a batch of per-row keys ([B] typed
    or [B, 2] raw): the serving engine samples each slot with its own
    request's key so concurrent requests never share a stream."""
    if temperature == 0.0:
        return jnp.argmax(logits[:, -1], axis=-1)
    last = logits[:, -1] / temperature
    if _is_batched_keys(key):
        return jax.vmap(jax.random.categorical)(key, last)
    return jax.random.categorical(key, last)


def build_generate(forward, init_caches):
    """Greedy/temperature `generate` for a causal family.

    `forward(config, params, input_ids, positions=..., kv_caches=...)` must
    return (logits, new_caches) on the cached path; `init_caches(config,
    batch, max_len, dtype=...)` builds the stacked caches. The returned
    generate() mirrors the reference's big-model-inference usage
    (ref benchmarks/big_model_inference.py:94-108): prompt in, prompt+new
    tokens out.
    """

    @functools.lru_cache(maxsize=32)
    def _programs(config, temperature: float):
        def select(logits, k):
            return sample_token(logits, k, temperature)

        @jax.jit
        def prefill(params, input_ids, caches, k):
            logits, caches = forward(config, params, input_ids,
                                     kv_caches=caches)
            return select(logits, k), caches

        @jax.jit
        def decode_all(params, last, caches, steps, keys):
            b = last.shape[0]

            def body(carry, xs):
                last, caches = carry
                pos, k = xs
                positions = jnp.broadcast_to(pos, (b, 1))
                logits, caches = forward(
                    config, params, last[:, None], positions=positions,
                    kv_caches=caches,
                )
                return (select(logits, k), caches), last

            (final, _), emitted = jax.lax.scan(body, (last, caches),
                                               (steps, keys))
            # emitted[i] is the token fed at step i ([T, B]); final is last
            return jnp.concatenate([emitted.T, final[:, None]], axis=1)

        return prefill, decode_all

    def generate(config, params, input_ids, max_new_tokens: int = 32,
                 temperature: float = 0.0, key=None):
        b, prompt_len = input_ids.shape
        total = prompt_len + max_new_tokens
        # bucket the cache length so nearby (prompt, budget) pairs share one
        # compiled decode scan: rows past `total` are never written and sit
        # at positions the causal mask always hides, so tokens are
        # unchanged while distinct prompt lengths stop forcing a fresh
        # decode_all compile each (position tables cap the bucket)
        limit = getattr(config, "max_position_embeddings", None) or total
        caches = init_caches(config, b, min(max(-(-total // 32) * 32, total),
                                            max(limit, total)))
        if key is None:
            key = jax.random.key(0)
        prefill, decode_all = _programs(config, float(temperature))
        key, sub = jax.random.split(key)
        last, caches = prefill(params, input_ids, caches, sub)
        if max_new_tokens == 1:
            return jnp.concatenate([input_ids, last[:, None]], axis=1)
        keys = jax.random.split(key, max_new_tokens - 1)
        steps = jnp.arange(prompt_len, prompt_len + max_new_tokens - 1,
                           dtype=jnp.int32)
        new_tokens = decode_all(params, last, caches, steps, keys)
        return jnp.concatenate([input_ids, new_tokens], axis=1)

    # introspection hook: tests pin the bucketing contract (two prompt
    # lengths in one bucket -> ONE compiled decode scan) via
    # generate._programs(config, temp)[1]._cache_size()
    generate._programs = _programs
    return generate


def build_streamed_generate(make_layer_step, embed_fn, project_fn,
                            cache_dims):
    """Offloaded-weights `streamed_generate` for a causal family (the
    reference benchmark's cpu-offload rows, ref benchmarks/README.md:27-36):
    weights stream host→device double-buffered around the family's jit'd
    layer body while per-layer KV caches stay device-resident.

    - `make_layer_step(config)` -> jit'd `(layer, x, positions, (k, v,
      cache_len)) -> (x, new_cache)` (lru_cache it so warm calls reuse the
      compiled program);
    - `embed_fn(config, resident, ids, positions)` / `project_fn(config,
      resident, x)` run on the resident (non-stacked) modules — project_fn
      must INCLUDE the final norm (the full forwards apply it before their
      head);
    - `cache_dims(config)` -> (num_kv_heads, head_dim) for the cache shape.
    """

    def streamed_generate(config, params, input_ids,
                          max_new_tokens: int = 32, **kw):
        from ..big_modeling import streamed_generate as _sg

        kw.setdefault("dtype", jnp.bfloat16)
        cdt = kw["dtype"] or jnp.bfloat16
        nh, hd = cache_dims(config)
        return _sg(
            params, input_ids,
            embed_fn=lambda res, ids, pos: embed_fn(config, res, ids, pos),
            layer_step_fn=make_layer_step(config),
            project_fn=lambda res, x: project_fn(config, res, x),
            init_layer_cache=lambda b, m: (jnp.zeros((b, m, nh, hd), cdt),
                                           jnp.zeros((b, m, nh, hd), cdt)),
            max_new_tokens=max_new_tokens, **kw,
        )

    return streamed_generate
