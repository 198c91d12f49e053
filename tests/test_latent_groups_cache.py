"""The cache of a family whose layers are LATENT attention of two kinds
(`serving/cache.py`): a latent pool with a side row (an indexer's key in
the latent row's pages), a latent pool as a ring, and `GroupedPagedCache`
over latent groups of different widths. Model-free: rows are numbers that
say where they belong, so every view can be read back exactly."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from accelerate_tpu.serving.cache import (
    CacheSpec,
    GroupedPagedCache,
    LayerwiseSlotView,
    PagedKVCache,
    WithSide,
    paged_admit_slot,
    paged_append_rows,
    paged_batch_view,
    paged_slot_view,
    paged_write_chunk,
    ring_positions,
)

PS, CHUNK, W_FULL, W_RING, SIDE, WINDOW = 16, 16, 256, 384, 128, 9
SPECS = (
    CacheSpec(num_layers=2, heads=1, width=W_FULL, kind="latent",
              layers=(0, 1), side_width=SIDE),
    CacheSpec(num_layers=3, heads=1, width=W_RING, kind="latent",
              window=WINDOW, layers=(2, 3, 4)),
)


def _cache(slots=2, max_len=96, num_pages=20):
    return GroupedPagedCache.create(SPECS, slots, max_len, dtype=jnp.float32,
                                    page_size=PS, pad_slack=CHUNK,
                                    num_pages=num_pages)


def _rows(layers, positions, width, salt):
    """[L, 1, n, 1, width]: layer l, position p, lane w holds a number
    that names all three."""
    l = np.arange(layers)[:, None, None]
    p = np.asarray(positions)[None, :, None]
    w = np.arange(width)[None, None, :]
    return jnp.asarray((salt + 1000 * l + p + w / 1000.0)[:, None, :, None, :],
                       jnp.float32)


def test_a_latent_pool_takes_a_side_row_and_a_window():
    with_side = PagedKVCache.create(2, 2, 64, 1, W_FULL, page_size=PS,
                                    num_pages=10, latent=True,
                                    side_width=SIDE)
    assert with_side.v is None and with_side.latent
    assert with_side.k.shape == (2, 11, 1, PS, W_FULL)
    assert with_side.side.shape == (2, 11, PS * SIDE // 128, 128)
    assert with_side.page_nbytes == 2 * PS * (W_FULL + SIDE) * 2
    assert with_side.side_page_nbytes == 2 * PS * SIDE * 2
    ring = PagedKVCache.create(3, 2, 64, 1, W_RING, page_size=PS,
                               pad_slack=CHUNK, latent=True, window=WINDOW)
    # the window, one chunk, and a page of rounding: 2 + 1 pages a slot
    assert ring.ring and ring.v is None and ring.pages_per_slot == 3
    assert ring.k.shape == (3, 2 * 3 + 1, 1, PS, W_RING)
    assert ring.page_nbytes == 3 * PS * W_RING * 2


@pytest.mark.parametrize("bad,match", [
    (dict(latent=True, kv_dtype="int8"), "int8 latent pool"),
    (dict(latent=True, window=8, side_width=SIDE), "inside a ring"),
    (dict(latent=True, window=0), "at least 1"),
    (dict(latent=True, side_width=48), "whole 128-lane rows"),
])
def test_what_a_latent_pool_still_refuses(bad, match):
    with pytest.raises(ValueError, match=match):
        PagedKVCache.create(2, 2, 64, 1, W_FULL, page_size=PS, num_pages=10,
                            **bad)


def test_latent_groups_of_different_widths():
    cache = _cache()
    full, ring = cache.groups
    assert cache.layers == ((0, 1), (2, 3, 4))
    assert full.k.shape == (2, 21, 1, PS, W_FULL) and full.v is None
    assert ring.k.shape == (3, 2 * 3 + 1, 1, PS, W_RING) and ring.v is None
    assert full.side.shape == (2, 21, PS, 128) and ring.side is None
    # one pool's books are the first group's
    assert cache.latent and cache.num_pages == 20 and cache.side_width == SIDE
    assert cache.page_nbytes == full.page_nbytes
    assert cache.nbytes() == full.nbytes() + ring.nbytes()
    assert [s.label for s in SPECS] == ["full", "window9"]


@pytest.mark.parametrize("specs,match", [
    ((SPECS[0], CacheSpec(3, 4, 128, window=9, layers=(2, 3, 4))),
     "ONE kind"),
    ((CacheSpec(2, 1, W_FULL, kind="latent"), SPECS[1]), "name their layers"),
    ((SPECS[1], SPECS[0]), "keeps every position"),
    ((SPECS[0], CacheSpec(3, 1, W_RING, kind="latent", window=9,
                          layers=(2, 3, 4), side_width=SIDE)),
     "inside a ring"),
])
def test_what_a_grouped_cache_refuses(specs, match):
    with pytest.raises(ValueError, match=match):
        GroupedPagedCache.create(specs, 2, 64, page_size=PS, pad_slack=CHUNK)


def _write(cache, tables, slot, start, real, salt=0.0):
    """One chunk of `CHUNK` rows at positions start.. into `slot`, of
    which `real` are real; rows name their layer and position."""
    at = start + np.arange(CHUNK)
    rows = (WithSide(_rows(2, at, W_FULL, salt),
                     _rows(2, at, SIDE, salt + 0.5)),
            _rows(3, at, W_RING, salt))
    return paged_write_chunk(cache, tables, jnp.int32(slot), rows,
                             (None, None), jnp.int32(real))


def _tables(cache, full_pages, ring_pages):
    full = np.full((cache.pages_per_slot,), cache.trash_page, np.int32)
    full[:len(full_pages)] = full_pages
    return jnp.asarray(full), jnp.asarray(np.asarray(ring_pages, np.int32))


def test_the_side_row_follows_its_page_through_every_write_and_view():
    """Three chunks (the last padded) and two decode rows through the ONE
    `_scatter_rows`: a slot's views, stacked and a layer at a time, hold
    each position's latent row and ITS index key, in both groups."""
    cache = _cache()
    tables = _tables(cache, [7, 3, 11, 5], [4, 1, 6])
    slot = 1
    cache = paged_admit_slot(cache, jnp.int32(slot), jnp.int32(0))
    for start, real in ((0, 16), (16, 16), (32, 5)):
        cache = _write(cache, tables, slot, start, real)
    assert int(cache.lengths[slot]) == 37
    assert [int(g.lengths[slot]) for g in cache.groups] == [37, 37]
    # two decode steps: slot 1 live, slot 0 dead with an all-trash table
    both = tuple(jnp.stack([jnp.full_like(t, g.trash_page), t])
                 for t, g in zip(tables, cache.groups))
    for at in (37, 38):
        def both_lanes(rows):   # [L, 1, 1, 1, w] -> [L, 2, 1, w]
            return jnp.tile(rows[:, :, 0], (1, 2, 1, 1))

        rows = (WithSide(both_lanes(_rows(2, [at], W_FULL, 0.0)),
                         both_lanes(_rows(2, [at], SIDE, 0.5))),
                both_lanes(_rows(3, [at], W_RING, 0.0)))
        cache = paged_append_rows(cache, both, rows, (None, None),
                                  jnp.asarray([False, True]))
    assert cache.lengths.tolist() == [0, 39]
    (full, ring), vs, length = paged_slot_view(cache, tables, jnp.int32(slot))
    assert vs == (None, None) and int(length) == 39
    want = np.asarray(_rows(2, np.arange(39), W_FULL, 0.0))[:, 0, :, 0]
    np.testing.assert_array_equal(np.asarray(full.rows)[:, 0, :39, 0], want)
    want_i = np.asarray(_rows(2, np.arange(39), SIDE, 0.5))[:, 0, :, 0]
    np.testing.assert_array_equal(np.asarray(full.side)[:, 0, :39, 0], want_i)
    # the ring (48 rows) has not wrapped yet: rows are positions
    want_r = np.asarray(_rows(3, np.arange(39), W_RING, 0.0))[:, 0, :, 0]
    np.testing.assert_array_equal(np.asarray(ring)[:, 0, :39, 0], want_r)
    # a layer at a time: the same rows, one gather a layer
    (lw, rw), _, _ = paged_slot_view(cache, tables, jnp.int32(slot),
                                     by_layer=True)
    assert isinstance(lw.rows, LayerwiseSlotView) and lw.side.shape == (
        2, 1, cache.rows, 1, SIDE)
    for layer in range(2):
        np.testing.assert_array_equal(np.asarray(lw.rows.at_layer(layer)),
                                      np.asarray(full.rows)[layer])
        np.testing.assert_array_equal(np.asarray(lw.side.at_layer(layer)),
                                      np.asarray(full.side)[layer])
    for layer in range(3):
        np.testing.assert_array_equal(np.asarray(rw.at_layer(layer)),
                                      np.asarray(ring)[layer])
    # every slot's views at once (the dense decode's)
    (bf, br), bv = paged_batch_view(cache, both)
    assert bv == (None, None)
    for batch, one in ((bf.rows, full.rows), (bf.side, full.side),
                       (br, ring)):
        np.testing.assert_array_equal(np.asarray(batch)[:, 1],
                                      np.asarray(one)[:, 0])


def test_padding_and_dead_lanes_land_on_the_trash_page_in_both_groups():
    """A last chunk's padded rows past the slot's pages and a dead lane's
    step: no page but the slot's own and the trash page changes, in the
    full group, its side rows and the ring."""
    cache = _cache()
    noise = jax.tree.map(
        lambda a: jax.random.normal(jax.random.key(a.size % 97), a.shape,
                                    a.dtype) if a.dtype == jnp.float32 else a,
        cache)
    before = jax.tree.map(np.asarray, noise)
    # ONE full page for 5 real rows + 11 padded: the padding's second page
    # does not exist in the table
    tables = _tables(noise, [9], [2, 5, 0])
    after = _write(paged_admit_slot(noise, jnp.int32(0), jnp.int32(0)),
                   tables, 0, 0, 5, salt=7.0)
    both = tuple(jnp.stack([t, jnp.full_like(t, g.trash_page)])
                 for t, g in zip(tables, after.groups))
    rows = (WithSide(jnp.ones((2, 2, 1, W_FULL)), jnp.ones((2, 2, 1, SIDE))),
            jnp.ones((3, 2, 1, W_RING)))
    after = paged_append_rows(after, both, rows, (None, None),
                              jnp.asarray([True, False]))
    for g, (was, now, own) in enumerate((
            (before.groups[0].k, after.groups[0].k, [9]),
            (before.groups[0].side, after.groups[0].side, [9]),
            (before.groups[1].k, after.groups[1].k, [2, 5, 0]))):
        changed = np.flatnonzero(
            (np.asarray(now) != was).reshape(was.shape[0], was.shape[1],
                                             -1).any(axis=(0, 2)))
        assert set(changed) <= set(own) | {was.shape[1] - 1}, (g, changed)
        assert set(own[:1]) <= set(changed)
    assert after.lengths.tolist() == [6, 0]


def test_a_latent_ring_after_it_has_wrapped():
    """A ring of 3 pages (48 rows) under 5 chunks: row r holds the newest
    position that is r modulo 48, in every layer of the ring group, and
    `ring_positions` says which; the full group beside it keeps every
    position."""
    cache = _cache(max_len=96, num_pages=20)
    tables = _tables(cache, [2, 4, 6, 8, 10, 12, 14], [3, 0, 5])
    cache = paged_admit_slot(cache, jnp.int32(0), jnp.int32(0))
    for start in range(0, 80, 16):
        cache = _write(cache, tables, 0, start, 16)
    assert int(cache.lengths[0]) == 80
    (full, ring), _, _ = paged_slot_view(cache, tables, jnp.int32(0))
    held = np.asarray(ring_positions(48, 79))
    assert held.min() == 32 and held.max() == 79 and len(set(held)) == 48
    want = np.asarray(_rows(3, held, W_RING, 0.0))[:, 0, :, 0]
    np.testing.assert_array_equal(np.asarray(ring)[:, 0, :, 0], want)
    np.testing.assert_array_equal(
        np.asarray(full.rows)[:, 0, :80, 0],
        np.asarray(_rows(2, np.arange(80), W_FULL, 0.0))[:, 0, :, 0])
    # a decode row at position 80 overwrites position 32's, nothing else
    rows = (WithSide(_rows(2, [80], W_FULL, 0.0)[:, :, 0],
                     _rows(2, [80], SIDE, 0.5)[:, :, 0]),
            _rows(3, [80], W_RING, 0.0)[:, :, 0])
    one = tuple(t[None] for t in tables)
    small = GroupedPagedCache(
        tuple(g.__class__(**{**g.__dict__, "lengths": g.lengths[:1]})
              for g in cache.groups), cache.layers)
    small = paged_append_rows(small, one, rows, (None, None),
                              jnp.asarray([True]))
    (_, ring2), _, _ = paged_slot_view(small, tables, jnp.int32(0))
    held2 = np.asarray(ring_positions(48, 80))
    assert held2[32] == 80
    assert (np.delete(held2, 32) == np.delete(held, 32)).all()
    np.testing.assert_array_equal(
        np.asarray(ring2)[:, 0, :, 0],
        np.asarray(_rows(3, held2, W_RING, 0.0))[:, 0, :, 0])
