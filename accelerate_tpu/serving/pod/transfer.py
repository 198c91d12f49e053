"""Pod layer 2 plumbing: KV pages as the unit of prefill->decode transfer.

The paged cache (serving/cache.py) made a request's KV state a list of
fixed-size, location-free pages — which is exactly what makes
disaggregation possible: a prefill worker computes a prompt's KV into its
own pool, and the pages (pool rows + the page-table fragment naming them)
ship to a decode worker that owns the slot for the request's decode
lifetime. This module is the device-side half of that hand-off:

- `extract`: gather one slot's table row out of the pool into a dense
  [L, pages_per_slot, H, page_size, D] block. FIXED shape — the block
  always spans the full table row (trash-padded rows gather the trash
  page) so every extraction hits the same compiled program. The host then
  keeps only the `n_prompt_pages` that carry real prompt KV; on a real
  pod this block is what crosses DCN/ICI (a production transport would
  ship the prompt pages only — the fixed-shape block is the
  compile-count-flat testing/CPU form of the same hand-off).

- `install`: scatter a shipped block into the decode worker's pool at
  its freshly allocated page indices (row padded with the trash page
  beyond the prompt pages, so the dead lanes write nowhere), and seed
  the slot's last-token register with the first generated token the
  prefill worker sampled. Also fixed-shape, also one compile.

Correctness under sharing: the decode worker's allocator may have
matched a prefix of the shipped prompt in its OWN radix tree, in which
case the leading allocated pages are mapped copy-on-write. Installing
over them is safe for the same reason prefill's window scatter is: both
workers run identical programs over identical params, so a shared prompt
page's shipped bytes ARE the cached page's bytes — a value-identical
rewrite, however many sharers race. Rows past `prompt_len` in the last
shipped page (chunk padding, or a decode step the prefill worker ran
before the router reclaimed the slot) are masked by the position
invariant and overwritten by the decode worker's own appends.

`KVPageShipment` is deliberately plain host data (numpy + ints): it IS
the wire format — `distributed/wire.py` serializes exactly these fields,
for in-process workers too.
"""

from __future__ import annotations

import dataclasses
from functools import partial
from typing import Any

import jax
import jax.numpy as jnp
import numpy as np

from ..cache import StateCache

__all__ = ["KVPageShipment", "PageTransport", "place_shipment"]


@dataclasses.dataclass
class KVPageShipment:
    """One prompt's prefilled KV state, in transit prefill -> decode.

    `k_pages`/`v_pages` are the fixed-shape extracted block
    ([L, pages_per_slot, H, page_size, D] host numpy); only the first
    `n_prompt_pages` carry prompt KV (the rest rode along for shape
    stability and are dropped at install). `first_token` is the first
    generated token — sampled on the prefill worker from the final
    prompt logits, so the decode worker starts from exactly the state a
    single-engine prefill would have left."""

    prompt: np.ndarray
    first_token: int
    n_prompt_pages: int
    k_pages: np.ndarray
    v_pages: np.ndarray
    key_raw: np.ndarray          # uint32[2] — the request's sampling key
    temperature: float
    max_new_tokens: int
    eos_token_id: int | None
    src_worker: int = -1
    extracted_at: float = 0.0    # router clock; the page_transfer span start
    # the first token's model logprob (models emit per-token logprobs —
    # ISSUE 12), so the decode-side internal's logprob list stays aligned
    # with its tokens; None only for shipments from pre-logprob senders
    first_logprob: float | None = None
    # int8 pools ship their codes as-is plus the per-row-per-head scale
    # blocks ([L, pages_per_slot, H, page_size]) — the wire carries half
    # the bytes of a bf16 shipment; None on bf16 pools
    k_scales: np.ndarray | None = None
    v_scales: np.ndarray | None = None

    @property
    def page_bytes(self) -> int:
        """Real payload bytes (prompt pages only), the number a transport
        would put on the wire."""
        per_page = self.k_pages[:, 0].nbytes + self.v_pages[:, 0].nbytes
        if self.k_scales is not None:
            per_page += self.k_scales[:, 0].nbytes + self.v_scales[:, 0].nbytes
        return self.n_prompt_pages * per_page


class PageTransport:
    """Per-worker jitted extract/install pair.

    Shapes are fixed by the worker's pool, so each side compiles exactly
    once per engine lifetime — the pod's compile count stays flat per
    role however the request mix, prompt lengths, or hit/miss pattern
    change. Meshed workers pin `install`'s out_shardings to the engine's
    pool layout for the same fixed-point reason the engine pins its own
    programs (serving/pod/mesh.py)."""

    def __init__(self, engine):
        if getattr(engine.cache, "groups", None) is not None:
            raise ValueError(
                "page shipments of a cache with one group a layer kind are "
                "not implemented: a KVPageShipment carries ONE pool's "
                "pages, and a window group's ring is no prefix (ROADMAP M2)")
        if isinstance(engine.cache, StateCache):
            raise ValueError(
                "page shipments of a state pool are not implemented: a "
                "KVPageShipment carries pages of K and V rows, and this "
                "family keeps one recurrent state a sequence: what would "
                "ship is a snapshot of an entry (ROADMAP M4)")
        if engine.cache.latent:
            raise ValueError(
                "page shipments of a latent pool are not implemented: a "
                "KVPageShipment carries a K and a V half (ROADMAP M3)")
        if engine.cache.side is not None:
            raise ValueError(
                "page shipments of a pool with a side row a token are not "
                "implemented: a KVPageShipment carries a K and a V half, "
                "and a page here holds an indexer's keys too (ROADMAP M8)")
        self._engine = engine
        self._quantized = engine.cache.quantized
        install_out = None
        if engine._mesh_shardings is not None:
            cache_sh, rep = engine._mesh_shardings
            install_out = (cache_sh, rep)

        if self._quantized:
            # int8 pool: codes ship verbatim with their scale blocks —
            # no dequant/requant round-trip (which would drift the codes;
            # shipped pages must stay byte-identical to the prefill
            # worker's, the same invariant COW sharing relies on)
            @jax.jit
            def extract(cache, rows):
                return (cache.k[:, rows], cache.v[:, rows],
                        cache.k_scale[:, rows], cache.v_scale[:, rows])

            @partial(jax.jit, donate_argnums=(0, 1),
                     out_shardings=install_out)
            def install(cache, tokens, slot, rows, k_pages, v_pages,
                        first_tok, k_scales, v_scales):
                return (
                    dataclasses.replace(
                        cache,
                        k=cache.k.at[:, rows].set(k_pages),
                        v=cache.v.at[:, rows].set(v_pages),
                        k_scale=cache.k_scale.at[:, rows].set(k_scales),
                        v_scale=cache.v_scale.at[:, rows].set(v_scales),
                    ),
                    tokens.at[slot].set(first_tok),
                )
        else:
            @jax.jit
            def extract(cache, rows):
                # rows: [pages_per_slot] int32 (traced data — any mapping,
                # one program); gathers [L, P, H, ps, D] per buffer
                return cache.k[:, rows], cache.v[:, rows]

            @partial(jax.jit, donate_argnums=(0, 1),
                     out_shardings=install_out)
            def install(cache, tokens, slot, rows, k_pages, v_pages,
                        first_tok):
                # trash-padded `rows` entries scatter their pages into the
                # reserved trash page — dead writes, never a live page
                return (
                    dataclasses.replace(
                        cache,
                        k=cache.k.at[:, rows].set(
                            k_pages.astype(cache.k.dtype)),
                        v=cache.v.at[:, rows].set(
                            v_pages.astype(cache.v.dtype)),
                    ),
                    tokens.at[slot].set(first_tok),
                )

        self._extract_p = extract
        self._install_p = install

    def compile_stats(self) -> dict[str, int]:
        return {
            "extract": self._extract_p._cache_size(),
            "install": self._install_p._cache_size(),
        }

    # -- prefill side --------------------------------------------------------

    def extract_shipment(self, pages: list[int], request,
                         src_worker: int = -1,
                         extracted_at: float = 0.0) -> KVPageShipment:
        """Pull a prefilled slot's pages off the prefill worker into a
        shipment. `pages` is the slot's allocation (recorded at
        admission); the request must still hold them — extract BEFORE the
        slot retires or the pool may reallocate the partial last page."""
        eng = self._engine
        row = np.full((eng.cache.pages_per_slot,), eng.cache.trash_page,
                      np.int32)
        row[:len(pages)] = pages
        eng._strict_audit("extract", self._extract_p, (eng.cache, row))
        out = self._extract_p(eng.cache, row)
        k_scales = v_scales = None
        if self._quantized:
            k_pages, v_pages, k_scales, v_scales = out
            k_scales, v_scales = np.asarray(k_scales), np.asarray(v_scales)
        else:
            k_pages, v_pages = out
        n_prompt = -(-request.prompt_len // eng.cache.page_size)
        return KVPageShipment(
            prompt=request.prompt,
            first_token=int(request.tokens[0]),
            first_logprob=(float(request.logprobs[0])
                           if request.logprobs else None),
            n_prompt_pages=n_prompt,
            k_pages=np.asarray(k_pages),
            v_pages=np.asarray(v_pages),
            key_raw=np.asarray(jax.device_get(request.key), np.uint32),
            temperature=request.temperature,
            max_new_tokens=request.max_new_tokens,
            eos_token_id=request.eos_token_id,
            src_worker=src_worker,
            extracted_at=extracted_at,
            k_scales=k_scales,
            v_scales=v_scales,
        )

    # -- decode side ---------------------------------------------------------

    def install_shipment(self, shipment: KVPageShipment, slot_index: int,
                         alloc) -> None:
        """Land a shipment in this decode worker: pages scattered into
        the allocation's indices, the slot's length set to the full
        prompt (`reused_len=prompt_len` through the ordinary admit
        program — to the pool a shipped prompt IS a fully reused
        prefix), key/temperature installed, last-token register seeded.
        After this the slot decodes exactly as if the worker had
        prefilled the prompt itself."""
        eng = self._engine
        row = np.full((eng.cache.pages_per_slot,), eng.cache.trash_page,
                      np.int32)
        row[:shipment.n_prompt_pages] = alloc.pages[:shipment.n_prompt_pages]
        args = (eng.cache, eng._tokens, jnp.int32(slot_index), row,
                shipment.k_pages, shipment.v_pages,
                jnp.int32(shipment.first_token))
        if self._quantized:
            args += (shipment.k_scales, shipment.v_scales)
        eng._strict_audit("install", self._install_p, args)
        eng.cache, eng._tokens = self._install_p(*args)
        admit_args = (eng.cache, eng._slot_keys, eng._temps,
                      jnp.int32(slot_index),
                      jnp.asarray(shipment.key_raw, jnp.uint32),
                      jnp.float32(shipment.temperature),
                      jnp.int32(int(shipment.prompt.shape[0])))
        # a pure decode worker first meets the admit program HERE — the
        # strict audit must still cover it once
        eng._strict_audit("admit", eng._admit_p, admit_args)
        eng.cache, eng._slot_keys, eng._temps = eng._admit_p(*admit_args)


def place_shipment(engine, transport: PageTransport, shipment: KVPageShipment,
                   now: float):
    """Land one shipment on `engine` end-to-end: internal Request built
    from the shipment, pages allocated (prefix-reuse aware), slot adopted
    RUNNING, table row written, pages installed, stale host mirrors
    dropped, first token + admission booked. Returns
    ``(internal, slot, alloc)`` or ``None`` when the engine has no free
    slot or pages right now (nothing mutated on None).

    The pod worker's `shipment` handler is its caller, whichever
    transport the shipment arrived over.
    """
    from ..scheduler import Request

    if engine.scheduler.live_slots >= len(engine.scheduler.slots):
        return None
    internal = Request(
        prompt=shipment.prompt,
        max_new_tokens=shipment.max_new_tokens,
        temperature=shipment.temperature,
        key=shipment.key_raw,
        eos_token_id=shipment.eos_token_id,
    )
    # nothing that can raise may sit between allocate and the
    # adopt/rollback pair that owns its outcome (ATP201 exception window)
    alloc = engine.allocator.allocate(internal)
    if alloc is None:
        return None
    internal.submitted_at = now
    slot = engine.scheduler.adopt_running(internal, alloc, now=now)
    if slot is None:               # raced: give the pages back
        engine.allocator.rollback(alloc)
        return None
    engine._table[slot.index, :] = engine.cache.trash_page
    engine._table[slot.index, :len(alloc.pages)] = alloc.pages
    transport.install_shipment(shipment, slot.index, alloc)
    # host-resident prefix chunks were re-homed to fresh pages by
    # allocate(); the shipment just wrote those pages with the exact
    # bytes the mirror holds, so the mirror is dead — drop it instead of
    # fetching (skips a host->device copy). After install on purpose:
    # the slot claim must complete before any non-essential bookkeeping
    # call could raise (ATP201 discipline).
    if alloc.swap_ins:
        for node, _page in alloc.swap_ins:
            engine._host_tier.discard(node)
    # seed the first token so EOS/budget accounting continues exactly
    # where the prefill worker left off; its logprob rides the shipment
    # so the internal's logprob list stays index-aligned
    engine.scheduler.note_token(slot, shipment.first_token, now=now,
                                logprob=shipment.first_logprob)
    engine.metrics.note_admission(
        internal.prompt_len, alloc.reused_len,
        host_pages=len(alloc.swap_ins or ()),
        table_pages=len(alloc.pages), run_pages=alloc.run_pages)
    return internal, slot, alloc
