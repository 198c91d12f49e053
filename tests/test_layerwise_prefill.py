"""A prefill chunk over its slot's views a layer at a time
(`serving.cache.LayerwiseSlotView`, `paged_write_chunk`: what the engine
hands a family that declares `layerwise_views`) against the same
chunk over the whole stacked views (`paged_slot_view`, `paged_write_slot`:
what every other family is handed): the chunk's logits and every byte of
the pools afterwards are EQUAL, bit for bit, in the three families that
loop over their layers, at tiny float32 sizes on the CPU.

The pools start full of noise, the trash page too: rows past a slot's
length, another tenant's or the trash page's, are in every view and must
count for nothing in either form."""

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from accelerate_tpu.models import deepseek, keye, llama, mellum
from accelerate_tpu.models.contract import ServingContract
from accelerate_tpu.serving import Engine, EngineConfig
from accelerate_tpu.serving.cache import (
    GroupedPagedCache,
    PagedKVCache,
    paged_slot_view,
    paged_write_chunk,
    paged_write_slot,
)

PAGE, CHUNK, SLOT = 8, 8, 1

FAMILIES = {
    "keye": (keye, keye.KeyeConfig.tiny),
    "deepseek": (deepseek, deepseek.DeepseekConfig.tiny),
    # one period of the published pattern: three sliding layers, one full
    "mellum": (mellum, lambda: mellum.MellumConfig.tiny(
        num_hidden_layers=4)),
}

# name -> (max_len, the slot's length before the chunk, the chunk's real
# tokens). R = max_len + CHUNK rows in whole pages; a tiny config walks a
# view in blocks of 16 rows (`kv_block`; deepseek's chunk kernel takes so
# short a view as one tile)
CASES = {
    # R 48, three whole blocks
    "start 0": (40, 0, CHUNK),
    # the chunk starts inside page 2: pages 0 and 1 (a prefix another
    # request shares) and page 2's first rows keep their bytes
    "a prefix hit that starts mid-page": (40, 19, 5),
    # the slot ends with this chunk: its padding lies in `pad_slack`,
    # whose pages the slot was never given (the trash page)
    "the last chunk of a full slot": (40, 37, 3),
    # R 56 = three and a half blocks: the reader pads the ragged last one
    "R no multiple of kv_block": (48, 27, CHUNK),
}
# (mellum only) the sliding layers' ring holds 48 rows: positions 44..51
# wrap inside the chunk; 91..98 after the ring has gone round twice
RING_CASES = {
    "a ring that wraps inside the chunk": (120, 44, CHUNK),
    "a ring on its second round, a padded chunk": (120, 91, 5),
}


def _noise(key, like):
    return jax.random.normal(key, like.shape, jnp.float32).astype(like.dtype)


def _setup(name, max_len, length):
    """-> (family, config, params, a cache full of noise with `SLOT` at
    `length`, the slot's table row(s) with the pages its `max_len` rows
    take and the trash page behind them)."""
    family, tiny = FAMILIES[name]
    cfg = tiny()
    params = family.init_params(cfg, jax.random.key(1), jnp.float32)
    spec = ServingContract.of(family).cache_spec(cfg)
    shape = dict(num_slots=2, max_len=max_len, dtype=jnp.float32,
                 page_size=PAGE, pad_slack=CHUNK)
    if isinstance(spec, tuple):
        cache = GroupedPagedCache.create(spec, **shape)
    else:
        cache = PagedKVCache.create(
            spec.num_layers, num_kv_heads=spec.heads, head_dim=spec.width,
            latent=spec.kind == "latent", side_width=spec.side_width,
            **shape)
    keys = iter(jax.random.split(jax.random.key(2), 16))
    groups = cache.groups if isinstance(spec, tuple) else (cache,)
    groups = tuple(dataclasses.replace(
        g, k=_noise(next(keys), g.k),
        v=None if g.v is None else _noise(next(keys), g.v),
        side=None if g.side is None else _noise(next(keys), g.side),
        lengths=g.lengths.at[SLOT].set(length)) for g in groups)
    rows = []
    for g in groups:
        held = g.pages_per_slot if g.ring else -(-max_len // PAGE)
        # the slot's pages, out of order, none of them page 0
        row = np.full((g.pages_per_slot,), g.trash_page, np.int32)
        row[:held] = 1 + (np.arange(held) * 5) % (g.num_pages - 1)
        assert len(set(row[:held])) == held
        rows.append(jnp.asarray(row))
    if isinstance(spec, tuple):
        return (family, cfg, params,
                dataclasses.replace(cache, groups=groups), tuple(rows))
    return family, cfg, params, groups[0], rows[0]


def _chunk(family, cfg, params, cache, table_row, ids, real_len, layerwise):
    """One prefill chunk as `Engine.prefill` runs it, in either form."""
    @jax.jit
    def program(params, cache, table_row, ids, real_len):
        ks, vs, length = paged_slot_view(cache, table_row, SLOT,
                                         by_layer=layerwise)
        positions = (length + jnp.arange(CHUNK, dtype=jnp.int32))[None, :]
        logits, (nk, nv, _) = family.forward(
            cfg, params, ids[None, :], positions=positions,
            kv_caches=(ks, vs, length))
        if layerwise:
            cache = paged_write_chunk(cache, table_row, SLOT, nk, nv,
                                      real_len)
        else:
            cache = paged_write_slot(cache, table_row, SLOT, nk, nv,
                                     real_len, CHUNK)
        return logits, cache

    return program(params, cache, table_row, ids, real_len)


def _pools(cache):
    """name -> array of every pool the cache holds, and the lengths."""
    groups = cache.groups if isinstance(cache, GroupedPagedCache) else (
        cache,)
    out = {}
    for g, group in enumerate(groups):
        for field in ("k", "v", "side", "lengths"):
            if getattr(group, field) is not None:
                out[f"{field}{g}"] = np.asarray(getattr(group, field))
    return out


@pytest.mark.parametrize("name,case", [
    (name, case) for name in FAMILIES for case in CASES] + [
    ("mellum", case) for case in RING_CASES])
def test_a_layerwise_chunk_equals_the_whole_view_chunk(name, case):
    max_len, length, real_len = {**CASES, **RING_CASES}[case]
    family, cfg, params, cache, table_row = _setup(name, max_len, length)
    ids = jax.random.randint(jax.random.key(3), (CHUNK,), 0, cfg.vocab_size)
    before = _pools(cache)
    whole_logits, whole = _chunk(family, cfg, params, cache, table_row, ids,
                                 jnp.int32(real_len), layerwise=False)
    logits, layerwise = _chunk(family, cfg, params, cache, table_row, ids,
                               jnp.int32(real_len), layerwise=True)
    assert np.isfinite(np.asarray(logits)).all()
    np.testing.assert_array_equal(np.asarray(logits),
                                  np.asarray(whole_logits))
    after, after_whole = _pools(layerwise), _pools(whole)
    assert after.keys() == after_whole.keys() == before.keys()
    for pool in after:
        np.testing.assert_array_equal(after[pool], after_whole[pool], pool)
    assert int(after["lengths0"][SLOT]) == length + real_len
    # the chunk was written: K's rows of the page it starts in changed, and
    # nothing before the slot's length did (pages a prefix hit shares)
    first = table_row[0] if isinstance(table_row, tuple) else table_row
    page = int(first[length // PAGE])
    at = length % PAGE
    assert (after["k0"][:, page, :, at] != before["k0"][:, page, :, at]).all()
    np.testing.assert_array_equal(after["k0"][:, page, :, :at],
                                  before["k0"][:, page, :, :at])
    for shared in np.asarray(first[:length // PAGE]):
        for pool in ("k0", "v0", "side0"):
            if pool in after:
                np.testing.assert_array_equal(after[pool][:, shared],
                                              before[pool][:, shared])


def test_a_layerwise_view_is_the_stacked_views_layer():
    """`at_layer(i)` is `paged_slot_view(...)[i]`, group by group, and
    `shape` the stacked views' (the family sizes its rotary table by it)."""
    _, _, _, cache, table_row = _setup("mellum", 48, 27)
    by_layer, _, _ = paged_slot_view(cache, table_row, SLOT,
                                     by_layer=True)
    whole, _, _ = paged_slot_view(cache, table_row, SLOT)
    assert [v.shape for v in by_layer] == [v.shape for v in whole] == [
        (1, 1, 56, 4, 128), (3, 1, 48, 4, 128)]
    for views, stack in zip(by_layer, whole):
        for i in range(stack.shape[0]):
            np.testing.assert_array_equal(np.asarray(views.at_layer(i)),
                                          np.asarray(stack[i]))


@pytest.mark.parametrize("family,layerwise", [
    (keye, True), (deepseek, True), (mellum, True), (llama, False)])
def test_the_engine_asks_the_family(family, layerwise, monkeypatch):
    """The engine hands layerwise views to a family that DECLARES that it
    takes them, and the stacked views to every other one."""
    import accelerate_tpu.serving.engine as engine

    asked = []

    real = engine.paged_slot_view

    def spy(cache, table_row, slot, by_layer=False):
        asked.append(by_layer)
        return real(cache, table_row, slot, by_layer)

    monkeypatch.setattr(engine, "paged_slot_view", spy)
    tiny = {keye: keye.KeyeConfig.tiny, deepseek: deepseek.DeepseekConfig.tiny,
            mellum: lambda: mellum.MellumConfig.tiny(num_hidden_layers=4),
            llama: llama.LlamaConfig.tiny}[family]
    cfg = tiny()
    assert ServingContract.of(family).layerwise_views == layerwise
    eng = Engine(family, cfg, family.init_params(cfg, jax.random.key(0)),
                 EngineConfig(num_slots=2, max_len=32, prefill_chunk=8,
                              page_size=8, cache_dtype=jnp.float32,
                              paged_attention=False,
                              prefix_cache=family is not mellum))
    r = eng.submit(np.arange(1, 12, dtype=np.int32), max_new_tokens=2)
    eng.run_until_idle()
    assert len(r.tokens) == 2
    assert set(asked) == {layerwise}
