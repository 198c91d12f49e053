"""`serving/cache.py` model-free: a group of state ENTRIES beside a group
of K/V PAGES in one `GroupedPagedCache`, a state pool whose shape is the
family's, and what the combinations refuse."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from accelerate_tpu.models.contract import StatePool
from accelerate_tpu.serving.cache import (
    CacheSpec,
    GroupedPagedCache,
    PagedAllocator,
    StateCache,
    create_cache,
    paged_admit_slot,
    paged_append_rows,
    paged_slot_view,
    paged_write_chunk,
    state_admit_slot,
)

PS, CHUNK = 4, 8
PAGES = CacheSpec(2, 1, 128, layers=(1, 3))
STATE = CacheSpec(3, 1, 256, kind="state", layers=(0, 2, 4), state_rows=16,
                  aux_rows=3, aux_entry_minor=True)


def _cache(slots=3, pages=12):
    return GroupedPagedCache.create((PAGES, STATE), slots, 32,
                                    dtype=jnp.float32, page_size=PS,
                                    pad_slack=CHUNK, num_pages=pages)


def test_a_state_group_stands_beside_the_page_group():
    """The first group's books are the pages'; the state group has one
    entry a slot and a spare, shaped as the family declared."""
    cache = _cache()
    assert len(cache.groups) == 1 and cache.layers == ((1, 3),)
    assert cache.state_layers == (0, 2, 4)
    assert isinstance(cache.state, StateCache)
    assert cache.state.s.shape == (3, 4, 1, 16, 256)
    assert cache.state.z.shape == (3, 3, 4, 256)     # entries in sublanes
    assert cache.state.z_entry_axis == 2
    assert (cache.num_pages, cache.trash_page, cache.page_size) == (12, 12, PS)
    assert cache.page_nbytes == 2 * 2 * PS * 128 * 4
    assert cache.state.num_pages == 3 and cache.state.trash_page == 3
    assert cache.state.page_nbytes == 3 * (16 + 3) * 256 * 4
    assert cache.nbytes() == (13 * cache.page_nbytes
                              + 4 * cache.state.page_nbytes)
    leaves = jax.tree.leaves(cache)
    assert len(leaves) == 6      # k, v, lengths; s, z, the state's lengths
    again = jax.tree.unflatten(jax.tree.structure(cache), leaves)
    assert again.state_layers == (0, 2, 4) and again.state.z_entry_axis == 2


def test_create_cache_reads_the_kinds_off_the_specs():
    ec = type("EC", (), dict(num_slots=2, max_len=32, cache_dtype=jnp.float32,
                             page_size=PS, num_pages=11, kv_dtype=None))
    cache = create_cache((PAGES, STATE), ec, CHUNK)
    assert cache.state.s.shape[1] == 3 and cache.num_pages == 11


def test_the_page_group_is_written_and_viewed_as_a_first_group_alone():
    """A chunk's rows and a decode step's row land in the page group as
    they do without a state beside it; the state group is not touched by
    the page groups' functions."""
    cache = _cache()
    table = np.full((cache.pages_per_slot,), cache.trash_page, np.int32)
    table[:4] = [5, 2, 9, 0]
    rows = jnp.arange(2 * CHUNK * 128, dtype=jnp.float32).reshape(
        2, 1, CHUNK, 1, 128)
    cache = paged_write_chunk(cache, (jnp.asarray(table),), jnp.int32(1),
                              (rows,), (rows + 0.5,), jnp.int32(6))
    assert list(np.asarray(cache.lengths)) == [0, 6, 0]
    ks, vs, length = paged_slot_view(cache, (jnp.asarray(table),),
                                     jnp.int32(1))
    assert int(length) == 6 and len(ks) == 1
    np.testing.assert_array_equal(ks[0][:, 0, :6], rows[:, 0, :6])
    np.testing.assert_array_equal(vs[0][:, 0, :6], rows[:, 0, :6] + 0.5)
    tables = np.full((3, cache.pages_per_slot), cache.trash_page, np.int32)
    tables[1] = table
    one = jnp.ones((2, 3, 1, 128), jnp.float32)
    cache = paged_append_rows(cache, (jnp.asarray(tables),), (one,),
                              (2 * one,), jnp.array([False, True, False]))
    assert list(np.asarray(cache.lengths)) == [0, 7, 0]
    np.testing.assert_array_equal(cache.groups[0].k[:, 2, 0, 2], one[:, 1, 0])
    assert float(jnp.abs(cache.state.s).max()) == 0.0


def test_admission_zeroes_the_slots_entry_in_both_blocks():
    """Slot i's entry is entry i: `state_admit_slot` zeroes it in every
    layer, state and window, and no other entry; `paged_admit_slot` sets
    the page group's length."""
    cache = _cache()
    noise = jax.random.normal(jax.random.key(0), cache.state.s.shape)
    wnoise = jax.random.normal(jax.random.key(1), cache.state.z.shape)
    cache = cache.with_state(StatePool(noise, wnoise))
    cache = paged_admit_slot(cache, jnp.int32(1), jnp.int32(5))
    after = state_admit_slot(cache, jnp.int32(1), jnp.int32(1))
    assert float(jnp.abs(after.state.s[:, 1]).max()) == 0.0
    assert float(jnp.abs(after.state.z[:, :, 1]).max()) == 0.0
    for e in (0, 2, 3):
        np.testing.assert_array_equal(after.state.s[:, e], noise[:, e])
        np.testing.assert_array_equal(after.state.z[:, :, e], wnoise[:, :, e])
    assert list(np.asarray(after.lengths)) == [0, 5, 0]   # the pages' own


def test_one_allocation_a_request_and_no_second_free_list():
    """The allocator of such a cache allocates and releases pages as a
    first group's alone does; the entry follows the slot, so the span says
    one entry an allocation and `allocations_live` counts them."""
    from accelerate_tpu.telemetry.trace import (
        configure_tracing,
        flight_recorder,
    )

    alloc = PagedAllocator(page_size=PS, num_pages=12, pad_slack=CHUNK,
                           prefix_cache=False, entries_beside=True)
    assert alloc.ring_pools == ()
    req = type("R", (), dict(prompt=np.arange(9, dtype=np.int32),
                             prompt_len=9, max_new_tokens=3))
    configure_tracing(True)
    try:
        a = alloc.allocate(req)
        b = alloc.allocate(req)
        too_many = alloc.allocate(req)
        spans = [s["attrs"] for s in flight_recorder()
                 if s["name"] == "serving.kv.allocate"][-3:]
    finally:
        configure_tracing(False)
    assert len(a.pages) == len(b.pages) == 5 and too_many is None
    assert [(s["full_pages"], s["state_entries"]) for s in spans] == [
        (5, 1), (5, 1), (0, 0)]
    assert alloc.allocations_live == 2 and alloc.pages_in_use == 10
    slot = type("S", (), dict(alloc=a, request=req, prompt_done=9, index=0))
    alloc.release(slot, finished=True)
    assert alloc.allocations_live == 1 and alloc.pages_in_use == 5


@pytest.mark.parametrize("specs,match", [
    ((STATE, PAGES), "keeps every position"),
    ((PAGES, STATE, STATE), "second state group"),
    ((CacheSpec(2, 1, 128, kind="latent", layers=(1, 3)), STATE),
     "beside latent rows"),
    ((PAGES, CacheSpec(1, 1, 128, window=9, layers=(5,)), STATE),
     "ONE group of K/V"),
    ((CacheSpec(2, 1, 128, layers=(1, 3), side_width=64), STATE),
     "a side row"),
    ((PAGES, CacheSpec(3, 1, 256, kind="state", state_rows=16)),
     "names its layers"),
], ids=["state-first", "two-states", "latent", "ring", "side", "no-layers"])
def test_what_a_state_group_is_not_implemented_beside(specs, match):
    with pytest.raises(ValueError, match=match):
        GroupedPagedCache.create(specs, 2, 32, page_size=PS, pad_slack=CHUNK)


def test_a_states_shape_is_the_familys():
    """`StateCache` lays out what the spec says and asks nothing of the
    rows: 100 rows of 128 lanes (no whole number of `width`), no second
    block, a second block entry-major or entry-minor."""
    plain = StateCache.create(
        CacheSpec(2, 2, 128, kind="state", state_rows=100), 2, 64)
    assert plain.s.shape == (2, 3, 2, 100, 128) and plain.z is None
    assert plain.page_nbytes == 2 * 2 * 100 * 128 * 4
    major = StateCache.create(
        CacheSpec(2, 2, 128, kind="state", state_rows=8, aux_rows=5), 2, 64)
    assert major.z.shape == (2, 3, 2, 5, 128) and major.z_entry_axis == 1
    minor = StateCache.create(
        CacheSpec(2, 2, 128, kind="state", state_rows=8, aux_rows=5,
                  aux_entry_minor=True), 2, 64)
    assert minor.z.shape == (2, 10, 3, 128) and minor.z_entry_axis == 2
    zeroed = state_admit_slot(plain, jnp.int32(0), jnp.int32(1))
    assert zeroed.z is None
    with pytest.raises(ValueError, match="0 entries"):
        StateCache.create(CacheSpec(2, 2, 128, kind="state", state_rows=128),
                          2, 64, num_entries=0)
    with pytest.raises(ValueError, match="state_rows"):
        StateCache.create(CacheSpec(2, 2, 128, kind="state", state_rows=0),
                          2, 64)
