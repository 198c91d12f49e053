"""What a model family declares to be served (`ServingContract`, bound to
`SERVING` at the foot of a family module), and the plain descriptions the
families and the serving layer share. A leaf: it imports nothing of this
package, so the arrows read `ops <- models <- serving`
(docs/serving.md, "What a served family declares")."""

from __future__ import annotations

import dataclasses
import inspect
from typing import Any, Callable, NamedTuple

import jax
import jax.numpy as jnp


@dataclasses.dataclass(frozen=True)
class CacheSpec:
    """What a family caches for one token in one layer, as the family
    declares it (`ServingContract.cache_spec`; families that declare
    nothing are GQA/MHA stacks and get `kind="kv"` from their config).

    `kind="kv"`: a K row and a V row of `heads` x `width`, two pools.
    `kind="latent"`: ONE row of `heads` x `width` (heads = 1 for MLA)
    that is key and value at once; the pool is one array and
    `PagedKVCache.v` is None. A page is `page_size` token positions of
    either kind, so the allocator, the prefix index, admission and the
    scheduler do not know the kind.

    A family whose layers differ in KIND returns a tuple of these, one
    GROUP a layer kind (`GroupedPagedCache`): `layers` are the model's
    layers the group holds, in order, and `window` is the group's
    retention rule: None keeps every position of a request, W keeps the
    last W (a ring of pages a slot, whatever the request's length). The
    first group keeps every position: it is the one whose pages grow with
    the context, and the one the allocator's books, the prefix index and
    the engine's page gauges mean.

    `kind="state"`: NO row a token. What a layer keeps of a sequence is
    one STATE of fixed size, whatever the sequence's length, and its shape
    is the FAMILY's: `heads` blocks of `state_rows` x `width` and, beside
    each, a second block of `aux_rows` x `width` (0: none), in
    `state_dtype` (`StateCache`, which lays them out as `StatePool.s` and
    `.z` and knows nothing of what the rows mean: power retention's
    matrix and its normaliser, `models/brumby.py`; a state-space layer's
    state and its convolution window, `models/jamba.py`).
    `aux_entry_minor`: the second block lies `[layers, heads * aux_rows,
    entries + 1, width]`, the ENTRIES in the tile's sublanes, which is how
    rows want to lie that plain XLA reads for every lane at once (a
    window's row j of all lanes is then one dense `[lanes, width]` slice;
    entry-major, the TPU's compiler re-lays the whole pool out to get
    it); False: `[layers, entries + 1, heads, aux_rows, width]`, an entry's
    rows one block, as a kernel that walks entries takes them. It is not
    addressed by position, so nothing of it can be shared, published or
    cut at a page: the pool's unit, where the allocator and the gauges say
    "page", is an ENTRY, one sequence's whole state in every layer.
    Alone it is the whole cache; as the LAST of a tuple, after one group of
    K/V rows that keeps every position, it is a group of entries BESIDE a
    group of pages (`GroupedPagedCache.state`): slot i's entry is entry i,
    so the one allocation a request makes is its pages.

    `side_width` > 0: a third per-token row of that many lanes, in the
    pool's dtype, that lives in the SAME pages as K and V (`PagedKVCache`,
    SIDE ROW): what a family keeps for a second scorer of its keys (a
    learned indexer's key). Like K and V it depends only on the tokens
    before it, so it is cached, shared and released with its page."""

    num_layers: int
    heads: int
    width: int
    kind: str = "kv"
    window: int | None = None
    layers: tuple | None = None
    side_width: int = 0
    state_rows: int = 0
    aux_rows: int = 0
    aux_entry_minor: bool = False
    state_dtype: Any = jnp.float32

    @property
    def label(self) -> str:
        """The group's name in gauges and debug output."""
        if self.kind == "state":
            return "state"
        return "full" if self.window is None else f"window{self.window}"


class WithSide(NamedTuple):
    """What stands in K's place wherever a cache with a side row hands K
    to a family or takes it back: K's rows, views or pool, and the side
    row's beside them (the same leading axes, one head)."""

    rows: Any
    side: Any


@dataclasses.dataclass(frozen=True)
class StatePool:
    """The state of every sequence in every layer of a `kind="state"` spec,
    as a family forward is handed it and hands it back: `s` [layers,
    entries + 1, heads, state_rows, width] and `z` [layers, entries + 1,
    heads, aux_rows, width], or with the spec's `aux_entry_minor` [layers,
    heads * aux_rows, entries + 1, width] (None without a second block);
    the last entry is the SPARE. `kernel`: the family's ops take their
    Pallas kernels (static)."""

    s: jax.Array
    z: jax.Array | None
    kernel: bool = False

    is_state_pool = True

    @property
    def spare(self) -> int:
        """The entry that takes the writes of lanes that leave no trace."""
        return self.s.shape[1] - 1


jax.tree_util.register_pytree_node(
    StatePool, lambda p: ((p.s, p.z), p.kernel),
    lambda kernel, sz: StatePool(sz[0], sz[1], kernel))


class StateMeta(NamedTuple):
    """`entries` [B] int32: each lane's pool entry (None: lane b's is entry
    b). `rows` [B] int32: how many of the lane's rows in this call are real
    (of a chunk, its leading rows; of a decode step, 1 or 0: a lane with 0
    leaves its entry as it is)."""

    entries: Any
    rows: Any


def ring_positions(rows: int, last):
    """The position each of a ring view's `rows` rows holds once
    positions 0..`last` are written (`last` [...] int32 -> [..., rows]):
    row r holds the newest position that is r modulo `rows`; a negative
    position means that nothing of this request is there. A view that
    never wraps (`last < rows`) is the same rule."""
    last = jnp.asarray(last, jnp.int32)[..., None]
    return last - (last - jnp.arange(rows, dtype=jnp.int32)) % rows


def kv_stack_spec(config) -> CacheSpec:
    """The K/V stack read off a config: GQA families carry
    num_key_value_heads, MHA families fall back to num_attention_heads."""
    kv = getattr(config, "num_key_value_heads", None)
    if kv is None:
        kv = config.num_attention_heads
    return CacheSpec(config.num_hidden_layers, kv, config.head_dim)


@dataclasses.dataclass(frozen=True)
class ServingContract:
    """The whole of what `serving.Engine` may ask of a family; the fields'
    table is in docs/serving.md. `forward` follows the uniform decode
    contract (`models/decode.py`); `cache_spec(config)` gives a `CacheSpec`
    or a TUPLE of them, one group a layer kind; `logit_rows`: `forward`
    takes `logit_rows=` and computes the head for that row alone;
    `layerwise_views`: a prefill chunk hands `forward` its slot's views a
    layer at a time and takes the chunk's rows back; `init_stats(config)` /
    `fold_stats(total, call)`: device counters a program (`forward` is then
    handed `token_mask=` and `return_stats=True`); the `*_chunk_stats` pair:
    what the prefill chunks count alone; `count_state_zeroed(total)`: a
    state family's count of the entries `admit` zeroes."""

    forward: Callable
    cache_spec: Callable = kv_stack_spec
    logit_rows: bool = False
    layerwise_views: bool = False
    init_stats: Callable | None = None
    fold_stats: Callable | None = None
    init_chunk_stats: Callable | None = None
    fold_chunk_stats: Callable | None = None
    count_state_zeroed: Callable | None = None

    def __post_init__(self):
        for init, fold in (("init_stats", "fold_stats"),
                           ("init_chunk_stats", "fold_chunk_stats")):
            if (getattr(self, init) is None) != (getattr(self, fold) is None):
                raise ValueError(f"{init} and {fold} are declared together "
                                 "or not at all")
        if self.logit_rows and "logit_rows" not in inspect.signature(
                self.forward).parameters:
            raise ValueError(f"logit_rows=True, but {self.forward!r} takes "
                             "no `logit_rows` parameter")

    @classmethod
    def of(cls, family) -> "ServingContract":
        """What `Engine` was handed, resolved in this ONE place: a contract
        as it is, a module's `SERVING`, and for a module without one or a
        bare `forward` callable the default K/V stack around it."""
        if isinstance(family, cls):
            return family
        if callable(family):
            return cls(forward=family)
        return getattr(family, "SERVING", None) or cls(forward=family.forward)
