"""Continuous-batching serving engine: many requests, ONE compiled decode.

The inference surface this replaces is one blocking `generate()` per
request (`models/decode.py`): batch fixed at call time, every sequence at
the same depth, no cross-request multiplexing. The engine instead drives
exactly three compiled programs for its whole lifetime, whatever the
request mix:

- `admit`:   set a slot's length to the reused prefix length (0 on a cold
             miss), install the request's PRNG key and temperature (slot
             index is traced — one program for any slot);
- `prefill`: one fixed-size prompt chunk into one slot (prompts pad to the
             chunk, lengths advance by real tokens only — serving/cache.py);
- `decode`:  one token for EVERY slot, the family `forward` vmapped over
             slots with per-slot lengths/positions. Retired or prefilling
             slots ride along as masked lanes — fixed shapes are the price
             of never recompiling, and their lanes are reused the moment a
             queued request lands.

The KV store behind all three is a PAGED pool (`serving/cache.py
PagedKVCache`): each slot maps an ordered list of fixed-size pages
instead of a contiguous stripe, and the programs gather the slot's pages
into the familiar contiguous view / scatter the update back. Page tables
are host-side numpy ([slots, pages_per_slot] int32, padded with the
reserved trash page) passed to each dispatch as traced data — hit/miss
mixes, evictions, and remapping never change a program shape, so the
compile count stays flat at three. The host-side `PrefixIndex` +
`PagedAllocator` give cross-request prefix reuse: at admission the
longest cached prompt prefix is matched in a radix tree and those pages
are mapped copy-on-write (refcounted, full pages only — never written
again), so prefill runs ONLY on the uncached suffix; at retirement the
request's full prompt pages are released back into the tree instead of
wiped. Under shared-prefix traffic (system prompts, few-shot headers)
this removes the dominant prefill FLOPs and the TTFT they cost.

Sampling is per-slot: each request's PRNG key is installed at admit and
the step key derives as `fold_in(request_key, position)`, so streams never
correlate across slots and a request's sample sequence is independent of
how prefills/decodes interleave. Temperature is a traced per-slot scalar
(greedy and sampled requests share the same program).

Token delivery is one small device->host read a program: `prefill` and
`decode` hand the host its own output (the sampled tokens and their
logprobs, apart from the donated [S] token register), and the engine reads
it ONE STEP LATE: `step()` dispatches the next program first and only then
fetches and commits the last one's results, so the chip works through the
read and the host pass instead of waiting for them (`_Unread`, `settle`).
A token reaches `request.tokens` in the `step()` after the one that
computed it; `stream()`/`astream()`/`run_until_idle()` drive until every
token is committed.

Speculative decoding (`EngineConfig(speculative=(family, config, params),
draft_k=K)`, off by default — the three-program contract above is
unchanged when off) replaces the one-token decode step with a
draft/verify pair: a small family member drafts K tokens per slot (K
sequential steps of the cheap model against its own dense slot cache),
the target verifies all K in ONE batched K-token paged forward, and the
standard accept rule commits the agreed prefix plus one correction token
— exact-match for greedy (byte-identical output by construction),
rejection sampling for sampled requests (the committed distribution IS
the target's). Five fixed-shape programs (admit/prefill/draft_prefill/
draft/verify), each compiled once: per-slot accept counts are traced
data, so the compile count stays flat whatever the accept pattern.

Both decode flavors emit per-token LOGPROBS (log-softmax of the raw
target logits at the emitted token): the accept rule needs target
probabilities anyway, and the handle's `logprobs` list is what lets the
HTTP door return OpenAI `logprobs` and rank `best_of` by true cumulative
logprob. `fork()` clones a request COW-style: the parent's full prompt
pages are published into the radix tree as prefill completes them, so an
n-way fan-out pays ONE prompt prefill and siblings diverge at their
first private page.
"""

from __future__ import annotations

import asyncio
import dataclasses
import time
from contextlib import nullcontext
from functools import partial
from typing import Any, AsyncIterator, Iterator, NamedTuple

import jax
import jax.numpy as jnp
import numpy as np

from ..models.common import count_params, part
from ..models.contract import CacheSpec, ServingContract, StateMeta
from ..models.decode import sample_token
from ..profiler import StepTimer, causal_lm_infer_flops
from ..telemetry.cost import CostTable, resolve_sample_every
from ..telemetry.export import start_metrics_server
from ..telemetry.registry import MetricsRegistry
from ..telemetry.trace import (
    head_sample,
    new_trace_id,
    next_span_id,
    record_span,
    span,
    tracing_enabled,
)
from ..telemetry.watchdog import StallWatchdog, resolve_stall_timeout
from .cache import (
    PagedAllocator,
    SlotKVCache,
    create_cache,
    paged_admit_slot,
    paged_append_batch,
    paged_append_rows,
    paged_append_window,
    paged_batch_view,
    paged_decode_operands,
    paged_slot_view,
    paged_write_chunk,
    paged_write_slot,
    slot_caches,
    state_admit_slot,
    write_slot,
)
from .metrics import ServingMetrics
from .sanitizer import SanitizerViolation, check_engine, resolve_sanitize
from .scheduler import Request, Scheduler, Slot, SlotState

__all__ = ["Engine", "EngineConfig"]


def _phase(name: str, **attrs):
    """A leaf phase of the engine's host pass (docs/observability.md,
    "Engine phases"). `trace=0`: a phase belongs to the engine's pass and
    to no request, so it never joins the trace of a request-scoped span
    open above it (`serving.submit` is joined to its request's) and stays
    out of the per-trace index. The shared null span when tracing is off."""
    return span(name, trace=0, **attrs)


def prepare_request_tracing(req: Request, trace_id, trace_parent,
                            trace_sampled) -> None:
    """Install the request's trace identity at submit time — shared by
    `Engine.submit` and the pod router's front door so a request is traced
    identically whether one engine or a worker fleet serves it. The id is
    minted whenever tracing is on, sampled or not (request-id plumbing
    must not depend on the sampling rate); a sampled request pre-allocates
    its root span id so children can parent onto it before the root closes
    at the terminal state."""
    req.trace_id = trace_id
    req.trace_parent = trace_parent
    if trace_sampled is None:
        req.trace_sampled = head_sample(req.tenant)
    else:
        req.trace_sampled = bool(trace_sampled) and tracing_enabled()
    if req.trace_id is None and tracing_enabled():
        req.trace_id = new_trace_id()
    if req.trace_sampled:
        req.span_id = next_span_id()


def close_request_trace(req: Request, end: float) -> None:
    """Close a terminal request's retrospective spans: the decode-lifetime
    child (first token -> terminal) and the root `serving.request` span
    carrying status/reason/shed_code. EVERY terminal path must land here
    exactly once — finished, cancelled, rejected, shed — whether the
    request died in an engine or at the pod router before any engine saw
    it."""
    if not req.trace_sampled:
        return
    if req.first_token_at is not None and end > req.first_token_at:
        # decode lifetime: first token -> terminal (prefill chunks
        # are their own child spans; this is the streaming tail)
        record_span("serving.decode_lifetime", req.first_token_at, end,
                    trace=req.trace_id, parent=req.span_id,
                    tokens=len(req.tokens))
    attrs: dict[str, Any] = {
        "request_id": req.request_id,
        "tenant": req.tenant,
        "status": req.status.value,
        "prompt_len": req.prompt_len,
        "tokens": len(req.tokens),
    }
    if req.ttft_s is not None:
        attrs["ttft_s"] = req.ttft_s
    if req.reject_reason is not None:
        attrs["reason"] = req.reject_reason
    if req.shed_code is not None:
        attrs["shed_code"] = req.shed_code
    if req.parent_id is not None:
        # fork parentage rides the root span: a COW fan-out's siblings
        # all name the request whose prompt pages they share
        attrs["forked_from"] = req.parent_id
    record_span("serving.request", req.submitted_at, end,
                trace=req.trace_id, parent=req.trace_parent,
                span_id=req.span_id, **attrs)


@dataclasses.dataclass(frozen=True)
class EngineConfig:
    """Serving knobs. `max_len` bounds prompt+generated per slot (admission
    rejects longer requests); `prefill_chunk` trades prefill efficiency
    against how long a long prompt may stall decode (one chunk).

    Observability: `metrics_port` serves the engine's telemetry registry
    as a Prometheus endpoint from a background thread (0 = ephemeral
    port, read it from `engine.metrics_server.port`; None defers to
    `ACCELERATE_TPU_METRICS_PORT`, unset = off). `watchdog_timeout_s`
    arms a stall watchdog ticked by `step()` — after that much silence it
    dumps all-thread stacks / HBM stats / the span flight recorder to the
    log (None defers to `ACCELERATE_TPU_STALL_TIMEOUT_S`, unset = off)."""

    num_slots: int = 4
    max_len: int = 512
    prefill_chunk: int = 32
    max_queue: int = 64
    cache_dtype: Any = jnp.bfloat16
    seed: int = 0
    donate: bool = True
    # paged KV pool: per-request memory is allocated in `page_size`-token
    # pages at admission, and prompt prefixes already cached (full pages
    # of an earlier request's prompt) are mapped instead of recomputed.
    # `num_pages` sizes the pool (None = num_slots * pages_per_slot —
    # capacity parity with the old dense cache; MORE keeps retired
    # prefixes cached longer, LESS trades HBM for eviction churn).
    # `prefix_cache=False` keeps the paged layout but disables
    # cross-request reuse (every admission is a cold miss) — the A/B
    # baseline for the prefill-savings benchmark.
    page_size: int = 16
    num_pages: int | None = None
    prefix_cache: bool = True
    # hierarchical KV (ISSUE 16): byte budget for the host-DRAM overflow
    # tier (serving/host_tier.py). > 0 turns eviction from destruction
    # into demotion — refcount-0 prefixes falling out of the HBM pool
    # swap OUT to pinned host numpy (async, off the engine step), and a
    # later radix hit on a host-resident prefix swaps back IN through
    # the jitted PageTransport pair before admission, so the effective
    # prefix cache is host-memory-sized while compile counts stay flat.
    # 0 (default) = off, eviction destroys (the pre-ISSUE-16 behavior).
    # Sizing: capacity_pages = host_tier_bytes // cache.page_nbytes;
    # with kv_dtype="int8" each page is ~half the bf16 bytes, so the
    # same budget holds ~2x the prefix tokens.
    host_tier_bytes: int = 0
    # decode attention op. True: the Pallas paged-attention kernel
    # (ops/paged_attention.py) walks the page table INSIDE attention —
    # pages are read once, in place, only live pages per slot, GQA
    # broadcast in-kernel; one batched forward replaces the per-slot
    # vmap. False: the reference dense-gather path (paged_batch_view
    # before the vmapped forward — O(pool) reads per token). "auto"
    # picks the kernel on a single-device TPU and the dense path
    # elsewhere (on CPU the kernel runs in interpret mode — exact, and
    # what the tier-1 exactness tests drive explicitly, but far too slow
    # to default to; on a meshed engine the kernel is opaque to GSPMD,
    # which would gather the head-sharded pool around it — explicit True
    # there is an error). Either way the compile count stays flat at
    # admit/prefill/decode = 1/1/1.
    paged_attention: Any = "auto"
    # KV pool storage dtype. None stores pages in `cache_dtype`; "int8"
    # stores int8 codes + per-row-per-head bf16 scales (serving/cache.py)
    # — half the bytes per page, so a fixed HBM budget holds ~2x the
    # pages (= concurrent users). Both attention paths dequantize (the
    # kernel per page in VMEM, the dense path at gather); prefill/decode
    # writes quantize; pod shipments carry codes + scales, halving wire
    # bytes too. Accuracy is gated in tests by a logit-error bound and
    # greedy-token agreement.
    kv_dtype: Any = None
    # draft-model speculative decoding (ISSUE 12): a (family, config,
    # params) triple for a SMALL family member sharing the target's
    # vocabulary (the zoo's size-matched pairs — gpt2/gptj, llama
    # variants — or a distilled/truncated sibling). When set, decode
    # becomes draft-k-tokens + verify-in-one-batched-forward +
    # accept/fallback: greedy requests accept on exact match (output
    # byte-identical to the non-speculative engine), sampled requests
    # run standard rejection sampling (the committed distribution is
    # exactly the target's). None (default) keeps the classic one-token
    # decode and the exact three-program contract. Not supported on a
    # meshed engine or with paged_attention=True (the kernel is a
    # single-token op; "auto" resolves to the dense verify path).
    speculative: Any = None
    # tokens the draft proposes per speculative step (>= 1). Accepted
    # tokens per step range [1, draft_k]; raise it when the draft agrees
    # often (accept rate stays high), lower it when disagreement makes
    # late proposals worthless. docs/serving.md covers tuning.
    draft_k: int = 4
    # multi-tenant scheduling: an iterable/dict of scheduler.TenantSpec
    # (priority tiers, DRR weights, TTFT SLOs). None = the single
    # "default" tenant, i.e. plain FIFO — the pre-tenancy behavior.
    # All of it is host-side policy: the three compiled programs are
    # identical with or without tenants.
    tenants: Any = None
    metrics_port: int | None = None
    watchdog_timeout_s: float | None = None
    # device-cost attribution (ISSUE 11): every Kth call of each engine
    # program pays a block_until_ready fence pair so its TRUE device
    # duration lands in program_device_time_seconds{program=...}; with
    # the static cost table (FLOPs/bytes captured once per compiled
    # program) that yields live decode MFU / HBM-bandwidth utilization /
    # MXU-idle and the goodput number in metrics_summary(). Host-side
    # only — programs and compile counts are untouched. None defers to
    # ACCELERATE_TPU_COST_SAMPLE_EVERY (default 16); 0 disables
    # sampling (the static table still captures).
    cost_sample_every: int | None = None
    # incident bundles: when the stall watchdog fires (or the server's
    # drive loop dies), a self-contained bundle directory — metrics
    # snapshot, flight-recorder chrome trace, scheduler/allocator dumps,
    # all-thread stacks, device memory stats — lands here for
    # `accelerate-tpu incident list/show`. None defers to
    # ACCELERATE_TPU_INCIDENT_DIR; unset = log-only stall reports.
    incident_dir: str | None = None
    # strict="warn"|"error" audits each engine program ONCE, at its first
    # use: a mesh-placement check on the argument arrays (params leaked
    # onto a multi-device mesh -> ATP101, caught at the placement, since
    # GSPMD-inserted collectives don't exist yet in the lowering) plus the
    # lowered (pre-XLA, tracing cost only) program text: host-transfer
    # scan (ATP102) and the program's CollectiveContract over explicit
    # collectives (a psum snuck into the family forward). `contracts`
    # maps program name ("admit"/"prefill"/"decode") to an
    # analysis.CollectiveContract; None = the single-host default (NO
    # collectives, exhaustively) — or, when `mesh` is set, the
    # tensor-parallel `analysis.contracts.pod_program_contracts()` (the
    # sharded programs MUST carry the TP collectives; see below).
    # Findings land in the engine registry as
    # analysis_findings_total{rule=...}.
    strict: str | None = None
    contracts: Any = None
    # serving-state sanitizer (the runtime half of the ATP2xx lifecycle
    # audit, serving/sanitizer.py): after every engine step validate the
    # cross-structure invariants static analysis can't see — page
    # conservation across free list / radix tree / slot allocations,
    # refcounts vs live mappings (downward-closed along root paths),
    # device page-table discipline, length bounds, scheduler books.
    # Host-side only: programs and compile counts are untouched (pinned
    # by test). A violation raises SanitizerViolation with the broken
    # invariant named, after writing an incident bundle when
    # `incident_dir` is configured. None defers to the
    # ACCELERATE_TPU_SANITIZE env var (the test suite turns it on for
    # every tier-1 engine); default off in production — the checks walk
    # the whole tree each step.
    sanitize: Any = None
    # SPMD serving (serving/pod layer 1): a `jax.sharding.Mesh` with a
    # "model" axis. The engine then places its KV pool (sharded over KV
    # heads when they divide the axis, replicated otherwise) and its
    # per-slot state (replicated) on the mesh, and pins each program's
    # out_shardings to the same layout — without the pin GSPMD is free to
    # pick a different output sharding each step and the cache's sharding
    # (part of the jit cache key) never reaches a fixed point, so the
    # compile count creeps instead of staying flat at three. Params must
    # be mesh-placed by the caller (`serving.pod.shard_params`, or the
    # `serving.pod.sharded_engine` factory that does all of this).
    # strict-mode audits switch to the COMPILED program text (GSPMD
    # inserts the TP collectives after lowering), which costs one extra
    # XLA compile per program at first use.
    mesh: Any = None


# The engine options a pool OTHER than one K/V stack does not implement
# yet, by the trait of the cache a family declares (`cache_spec`): what the
# error calls the trait's fallback, then for each option what porting it
# would take. ROADMAP M3 (latent), M2 (grouped), M8 (side), M4 (state).
# A cache with several traits (latent groups, the first with a side row)
# is refused by each of its traits, all named in the one error: the traits
# compose, the options do not.
_OPTIONS = {
    "prefix_cache=True": lambda ec: ec.prefix_cache,
    "kv_dtype='int8'": lambda ec: ec.kv_dtype is not None,
    "host_tier_bytes > 0": lambda ec: ec.host_tier_bytes > 0,
    "mesh": lambda ec: ec.mesh is not None,
    "speculative": lambda ec: ec.speculative is not None,
}
_UNPORTED = {
    # one latent row a token (CacheSpec.kind='latent')
    "latent": ("a K/V pool", {
        "kv_dtype='int8'": "int8 latent pages",
        "host_tier_bytes > 0": "the host tier's page shipments carry a K "
                               "and a V half",
        "mesh": "a sharded latent pool and its kernel",
        "speculative": "the verify step's multi-token latent attention"}),
    # one group a layer kind (cache_spec gives a tuple)
    "grouped": ("a one-kind pool", {
        "prefix_cache=True": "a hit at position p needs a window layer's "
                             "last rows before p, which a ring has "
                             "overwritten: published pages need a retention "
                             "rule of their own",
        "kv_dtype='int8'": "an int8 ring and its kernel",
        "host_tier_bytes > 0": "a page shipment is one pool's pages",
        "mesh": "sharded groups and their kernels",
        "speculative": "the verify step's multi-token window attention"}),
    # a side row a token beside K and V (CacheSpec.side_width)
    "side": ("attention over every key", {
        "kv_dtype='int8'": "the side row's codes and scales, and a sparse "
                           "kernel that reads int8 pages",
        "host_tier_bytes > 0": "a page shipment carries a K and a V half, "
                               "no side row",
        "mesh": "a sharded index pool, and the selection across shards",
        "speculative": "the verify step's multi-token selection"}),
    # one state a sequence and no rows (CacheSpec.kind='state'): each of
    # these needs a SNAPSHOT of a state, a copy of an entry taken at a
    # position, which nothing makes yet
    "state": ("K/V rows", {
        "prefix_cache=True": "a state is not addressed by position, so no "
                             "part of it is another prompt's prefix: a hit "
                             "needs the state as it stood after the shared "
                             "tokens, a snapshot published at a boundary",
        "kv_dtype='int8'": "int8 codes of a state and the kernels that "
                           "decay them",
        "host_tier_bytes > 0": "the host tier ships pages of rows; an "
                               "evicted state would be a snapshot of an entry",
        "mesh": "a state pool sharded over KV heads and its kernels under "
                "GSPMD",
        "speculative": "a rejected draft token cannot be cut off a state: "
                       "the verify step needs the state before its window "
                       "to roll back to"}),
}


def _refuse_unported(ec: "EngineConfig", spec: CacheSpec, groups) -> None:
    """Raise for every option that is set and that a trait of this
    family's cache does not implement: nothing falls back silently."""
    traits = []
    if spec.kind == "latent":
        traits.append(("latent", "this family caches one latent row a token "
                       "(CacheSpec.kind='latent')"))
    if groups is not None:
        traits.append(("grouped", "this family's layers differ in kind "
                       f"(cache_spec gives {len(groups)} groups: "
                       f"{[g.label for g in groups]})"))
    if spec.kind == "state":
        traits.append(("state", "this family keeps one recurrent state a "
                       "sequence and no K/V rows (CacheSpec.kind='state')"))
    elif any(g.kind == "state" for g in groups or ()):
        traits.append(("state", "layers of this family keep one recurrent "
                       "state a sequence BESIDE the other layers' K/V rows "
                       "(a group of CacheSpec.kind='state')"))
    sides = [g.side_width for g in (groups or (spec,)) if g.side_width]
    if sides:
        traits.append(("side", "this family caches a side row a token "
                       f"beside its rows (CacheSpec.side_width="
                       f"{sides[0]}: an indexer's key, which chooses "
                       "the keys attention reads)"))
    refused = []
    for trait, said in traits:
        instead, options = _UNPORTED[trait]
        unported = [f"{option} ({takes})" for option, takes in options.items()
                    if _OPTIONS[option](ec)]
        if trait == "side" and any(g.side_width for g in (groups or ())[1:]):
            unported.append("layers that differ in kind with a side row "
                            "INSIDE a ring group (a side row in a ring of "
                            "pages)")
        if unported:
            refused.append(
                f"{said}, which is not implemented together with: "
                + "; ".join(unported) + f". Nothing falls back to {instead}.")
    if refused:     # every trait that refuses says so, in one error
        raise ValueError(" ".join(refused))


def _resolve_paged_attention(setting, mesh, speculative=None) -> bool:
    """EngineConfig.paged_attention -> use-the-kernel bool (see the
    config field's comment for the policy)."""
    if setting == "auto":
        return (mesh is None and speculative is None
                and jax.devices()[0].platform == "tpu")
    use = bool(setting)
    if use and mesh is not None:
        raise ValueError(
            "paged_attention=True is not supported on a meshed engine: a "
            "pallas kernel is opaque to GSPMD, which would gather the "
            "head-sharded pool around it instead of partitioning the "
            "kernel. Meshed engines keep the dense-gather decode path "
            "('auto' resolves to False there); single-device pod decode "
            "workers (tensor_parallel=1) can use the kernel.")
    if use and speculative is not None:
        raise ValueError(
            "paged_attention=True is not supported with speculative "
            "decoding: the Pallas kernel folds exactly ONE new token's "
            "K/V as its final online-softmax update, but the verify step "
            "is a draft_k-token forward. Leave paged_attention='auto' "
            "(the speculative verify uses the dense-gather path).")
    return use


class _Unread(NamedTuple):
    """A dispatched program whose results the host has not read: `out` is
    the program's (tokens, logprobs) output on the device, its copy to the
    host already started; `lanes` names who is owed what, as (slot, the
    request the slot held at dispatch, index into `out`)."""

    program: str
    out: tuple
    lanes: list


def _as_raw_key(key) -> jax.Array:
    """uint32[2] key data from a typed key, raw key, or None."""
    if key is None:
        return None
    if (hasattr(key, "dtype")
            and jnp.issubdtype(key.dtype, jax.dtypes.prng_key)):
        return jax.random.key_data(key)
    return jnp.asarray(key, jnp.uint32)


class Engine:
    """Front-end: `submit()` -> request handle, `stream()`/`astream()` for
    tokens as they land, `cancel()`, `step()`/`run_until_idle()` to drive.

    `family` is any model-zoo module following the uniform decode contract
    (`forward(config, params, ids, positions=..., kv_caches=...) ->
    (logits, new_caches)` — see models/decode.py), that forward callable
    directly, or a `models.contract.ServingContract`; what a module asks
    for beyond a K/V stack is its `SERVING`.
    """

    def __init__(
        self,
        family,
        config,
        params,
        engine_config: EngineConfig | None = None,
        tracker=None,
        log_every: int = 0,
        clock=time.monotonic,
    ):
        self.config = config
        self.params = params
        self.engine_config = ec = engine_config or EngineConfig()
        if ec.mesh is not None and getattr(ec.mesh, "size", 1) <= 1:
            # a 1-device "mesh" IS single-device serving: there are no
            # collectives to contract-pin and no layouts to hold at a
            # fixed point — normalizing it away here keeps
            # `sharded_engine(..., tensor_parallel=1)` (and a 1-device
            # host) on the ordinary single-device path instead of
            # tripping the meshed strict audit, which demands sharded
            # args and TP reductions that can never exist on one chip
            self.engine_config = ec = dataclasses.replace(ec, mesh=None)
        # what the family declares is read HERE and nowhere else
        self._serving = serving = ServingContract.of(family)
        spec = serving.cache_spec(config)
        # one group a layer kind (serving/cache.py GroupedPagedCache), or
        # None: the one pool every layer shares
        self._cache_groups = spec if isinstance(spec, tuple) else None
        self._cache_spec = spec[0] if self._cache_groups else spec
        # the spec of the layers that keep a state a sequence, whether it
        # is the whole cache or a group of entries beside the pages; None
        # for a family that keeps rows only
        self._state_spec = next(
            (s for s in self._cache_groups or (spec,) if s.kind == "state"),
            None)
        self._tracker = tracker
        self._log_every = log_every
        self._last_logged = 0
        self._clock = clock

        # validate config BEFORE any thread/port side effects below — a
        # bad value must not leak a bound metrics port or a live watchdog
        if ec.strict is not None and ec.strict not in ("warn", "error"):
            raise ValueError(
                f"strict must be None, 'warn', or 'error'; got {ec.strict!r}")
        _refuse_unported(ec, self._cache_spec, self._cache_groups)
        self._spec = ec.speculative is not None
        if self._spec:
            if ec.mesh is not None:
                raise ValueError(
                    "speculative decoding is not supported on a meshed "
                    "engine yet: the draft would need its own placement "
                    "and the verify program its own pod contract — run "
                    "speculation on single-device engines (or pod decode "
                    "workers at tensor_parallel=1, speculative unset)")
            if ec.draft_k < 1:
                raise ValueError(f"draft_k must be >= 1, got {ec.draft_k}")
            try:
                dfam, dcfg, dparams = ec.speculative
            except (TypeError, ValueError):
                raise ValueError(
                    "speculative must be a (family, config, params) triple "
                    "for the draft model")
            if getattr(dcfg, "vocab_size", None) != config.vocab_size:
                raise ValueError(
                    f"draft vocab_size ({getattr(dcfg, 'vocab_size', None)})"
                    f" must match the target's ({config.vocab_size}): "
                    "drafted tokens are verified by id")
            self._draft_serving = ServingContract.of(dfam)
            self._draft_config = dcfg
            self._draft_params = dparams
        self._use_paged_kernel = _resolve_paged_attention(
            ec.paged_attention, ec.mesh, ec.speculative)
        self._contracts = ec.contracts
        if ec.strict is not None and self._contracts is None:
            if ec.mesh is not None:
                from ..analysis.contracts import pod_program_contracts

                self._contracts = pod_program_contracts(
                    num_layers=getattr(config, "num_hidden_layers", None))
            else:
                from ..analysis.contracts import serving_program_contracts

                self._contracts = serving_program_contracts(
                    paged_kernel=self._use_paged_kernel,
                    speculative=self._spec)
        # name -> None (audited clean/warned) | AnalysisViolation (cached:
        # re-raised on every later use without re-counting the findings)
        self._audited: dict = {}
        self._sanitize = resolve_sanitize(ec.sanitize)

        # pad_slack covers BOTH overshoot sources: chunk padding can spill
        # chunk-1 rows past max_len, and a speculative verify can write up
        # to draft_k candidate rows past the last budgeted token (the slot
        # retires mid-window; the extra rows land in reserved private
        # pages and are never attended)
        self._pad_slack = max(ec.prefill_chunk,
                              ec.draft_k if self._spec else 0)
        # one set of counters a program: a reader wants the decode steps'
        # experts apart from the chunks'
        stats = None if serving.init_stats is None else {
            "prefill": serving.init_stats(config),
            "decode": serving.init_stats(config)}
        # what a family counts in its prefill chunks ALONE is kept out of
        # the cache: `decode` is not handed it
        self._chunk_stats = (None if serving.init_chunk_stats is None
                             else serving.init_chunk_stats(config))
        self.cache = create_cache(spec, ec, self._pad_slack, stats)
        if self._spec:
            draft = self._draft_serving.cache_spec(self._draft_config)
            if isinstance(draft, tuple) or draft.kind != "kv":
                raise ValueError(
                    "a draft model with a latent cache is not implemented")
            dl, dkv, dhd = draft.num_layers, draft.heads, draft.width
            # the draft's own state is a DENSE slot cache (it is small,
            # and its K/V is a different model's — cached target pages
            # can never seed it, which is why prefix hits run draft-only
            # catch-up chunks)
            self._draft_cache = SlotKVCache.create(
                dl, ec.num_slots, ec.max_len, dkv, dhd,
                dtype=ec.cache_dtype, pad_slack=self._pad_slack)
        # SPMD serving: place the pool + per-slot state on the mesh and
        # remember the layout — _build_programs pins it as out_shardings
        # so every step's outputs land exactly where its inputs live (the
        # compile-count-flat fixed point; see EngineConfig.mesh)
        self._mesh_shardings = None
        if ec.mesh is not None:
            from .pod.mesh import cache_state_shardings

            self._mesh_shardings = cache_state_shardings(self.cache, ec.mesh)
            self.cache = jax.device_put(self.cache, self._mesh_shardings[0])
        # per-engine registry (not the process default) so concurrent
        # engines in one process never collide on series; the histograms
        # are streaming sketches, so a server that steps forever still
        # holds O(1) metric memory
        self.registry = MetricsRegistry()
        self.metrics = ServingMetrics(registry=self.registry)
        self.timer = StepTimer(warmup_steps=1, registry=self.registry,
                               name="serving_step")
        # per-program roofline attribution: static FLOPs/bytes captured
        # once per compiled program + sampled fence-pair device timing
        # (see EngineConfig.cost_sample_every)
        # num_chips matches the registration source: engine programs
        # register from the PRE-partition lowering (global FLOPs), so a
        # meshed engine's utilization divides by the whole mesh's peak;
        # a single-device engine is one chip however many the host shows
        self.cost = CostTable(registry=self.registry,
                              sample_every=resolve_sample_every(
                                  ec.cost_sample_every),
                              num_chips=(ec.mesh.size
                                         if ec.mesh is not None else 1))
        self._n_params: int | None = None  # resolved at first fallback
        # host-side page accounting: prefix radix tree + free list. The
        # lambdas read self.metrics at call time, so reset_metrics()'s
        # replacement instance keeps receiving events.
        self.allocator = PagedAllocator(
            page_size=self.cache.page_size,
            num_pages=self.cache.num_pages,
            pad_slack=self._pad_slack,
            prefix_cache=ec.prefix_cache,
            on_evict=lambda n: self.metrics.note_page_evictions(n),
            on_unmap=self._unmap_slot,
            rings=tuple((g.pages_per_slot, g.num_pages)
                        for g in self._ring_groups()),
            state_entries=self._cache_spec is self._state_spec,
            entries_beside=self._state_beside,
        )
        # COW forking: parent_id -> parent handle, consulted by the
        # admission hold below (entries drop as parents reach a terminal
        # state, so the map is bounded by live fan-outs)
        self._fork_parents: dict[int, Request] = {}
        # in-flight prefill dedup (ISSUE 16): request_ids currently held
        # behind a leader's prefill, so each follower counts exactly one
        # dedup hit however many steps it waits
        self._dedup_held: set[int] = set()
        if ec.prefix_cache:
            self.allocator.hold_admission = self._hold_admission
        # hierarchical KV: host-DRAM overflow tier + its jitted swap
        # transport (the pod PageTransport pair — extract on swap-out,
        # install on swap-in — compiles once each, so swap mixes never
        # move the compile count)
        self._host_tier = None
        self._swap_transport = None
        if ec.host_tier_bytes > 0:
            from .host_tier import HostTier
            from .pod.transfer import PageTransport

            self._swap_transport = PageTransport(self)
            self._host_tier = HostTier(self, ec.host_tier_bytes)
            self.allocator.swap_out = self._host_tier.offer
            self.allocator.swap_stall = self._host_tier.would_stall
            self.allocator.index.drop_host = self._host_tier.discard
        self.scheduler = Scheduler(ec.num_slots, ec.max_len,
                                   max_queue=ec.max_queue, clock=clock,
                                   allocator=self.allocator,
                                   tenants=ec.tenants,
                                   prefill_chunk=ec.prefill_chunk)
        # host-side page tables, one row per slot, padded with the trash
        # page: idle/retired lanes gather (and dead-write) only trash
        self._table = np.full(
            (ec.num_slots, self.cache.pages_per_slot),
            self.cache.trash_page, np.int32)
        # and one table a window group: a slot's row is its ring
        self._ring_tables = [
            np.full((ec.num_slots, g.pages_per_slot), g.trash_page, np.int32)
            for g in self._ring_groups()]
        # opt-in observability: Prometheus endpoint + stall watchdog
        self.metrics_server = start_metrics_server(
            ec.metrics_port, registry=self.registry)
        self.watchdog: StallWatchdog | None = None
        wd_timeout = resolve_stall_timeout(ec.watchdog_timeout_s)
        if wd_timeout is not None:
            self.watchdog = StallWatchdog(
                wd_timeout, name="serving-engine",
                incident_dir=ec.incident_dir, registry=self.registry,
                dumps=self.incident_dumps).start()

        self._tokens = jnp.zeros((ec.num_slots,), jnp.int32)
        self._slot_keys = jax.random.key_data(
            jax.random.split(jax.random.key(ec.seed), ec.num_slots))
        self._temps = jnp.zeros((ec.num_slots,), jnp.float32)
        if self._mesh_shardings is not None:
            rep = self._mesh_shardings[1]
            self._tokens = jax.device_put(self._tokens, rep)
            self._slot_keys = jax.device_put(self._slot_keys, rep)
            self._temps = jax.device_put(self._temps, rep)
        self._base_key = jax.random.key(ec.seed)
        # the one dispatched program whose results are not committed yet
        self._unread: _Unread | None = None
        # admission hook: called as on_admit(slot, request) at the END of
        # every admission, after the slot's page table and device state
        # are installed. First-class (like PagedAllocator's on_evict/
        # on_unmap) because external control planes — the pod router —
        # must observe the page allocation the instant it exists: a short
        # prompt can admit, prefill, and retire inside ONE step(), and
        # the allocation dies with the slot.
        self.on_admit: Any = None
        self._build_programs()

    @property
    def _state_beside(self) -> bool:
        """A group of state entries stands beside the page groups (alone,
        the state's spec IS the cache's)."""
        return (self._state_spec is not None
                and self._state_spec is not self._cache_spec)

    def _ring_groups(self) -> tuple:
        """The cache's window groups (none without a grouped cache)."""
        return self.cache.groups[1:] if self._cache_groups else ()

    def _tables(self, slot: int | None = None):
        """The host's page tables as a program takes them, copied (they
        change in place at the next admission or release, and a program
        in flight keeps its copy): the table, or the row of `slot`; one a
        group under a grouped cache."""
        at = slice(None) if slot is None else slot
        if not self._cache_groups:
            return self._table[at].copy()
        return (self._table[at].copy(),
                *(t[at].copy() for t in self._ring_tables))

    # -- compiled programs ---------------------------------------------------

    def _build_programs(self) -> None:
        forward, config = self._serving.forward, self.config
        chunk = self.engine_config.prefill_chunk
        # what a family is handed beyond the uniform decode contract
        # follows from what it DECLARES, one field a thing (`ServingContract`
        # says what each means); a family that counts sees all slots'
        # tokens in ONE dense-decode forward, as under the kernel
        serving = self._serving
        one_row, layerwise = serving.logit_rows, serving.layerwise_views
        fold_stats, fold_chunk = serving.fold_stats, serving.fold_chunk_stats
        count_zeroed = serving.count_state_zeroed
        grouped = self._cache_groups is not None
        # a family that keeps a state a sequence is handed the whole pool
        # and hands it back; `kernel` says which form its ops take
        state = self._cache_spec is self._state_spec
        kernel = self._use_paged_kernel
        # ... or, beside page groups, its state group's pool after the page
        # groups' operands and takes it back in the same place
        beside = self._state_beside

        def with_state(cache, ks, vs, entries, rows, kernel=kernel):
            """The page groups' operands with the state group's after
            them: its pool in K's place, who the lanes are in V's."""
            if not beside:
                return ks, vs
            return ((*ks, cache.state.pool(kernel)),
                    (*vs, StateMeta(entries, rows)))

        def take_state(cache, nk, nv):
            """-> (the cache with the pool a forward handed back in its
            state group, the page groups' new rows or views)."""
            if not beside:
                return cache, nk, nv
            return cache.with_state(nk[-1]), nk[:-1], nv[:-1]

        def serving_forward(program, params, cache, ids, positions,
                            kv_caches, logit_rows, token_mask):
            """-> (logits, new caches, cache, this call's counts): `forward`,
            with the head for `logit_rows` only ([B, 1, V]) where it takes
            them, and the family's counters, where it has any, folded into
            the cache's."""
            extra = {"logit_rows": logit_rows} if one_row else {}
            if cache.stats is not None:
                extra.update(token_mask=token_mask, return_stats=True)
            out = forward(config, params, ids, positions=positions,
                          kv_caches=kv_caches, **extra)
            if cache.stats is not None:
                cache = cache.with_stats(dict(
                    cache.stats, **{program: fold_stats(
                        cache.stats[program], out[2])}))
            return out[0], out[1], cache, (
                None if cache.stats is None else out[2])

        # donation lets the (large) cache be updated in place; that it IS,
        # on the chip, takes the page-granular write of
        # cache._scatter_rows besides (a row scatter into the donated pool
        # was copied around); (1, 2) = cache, tokens in both programs.
        # What the host reads of a step is a third, never-donated output
        # (live lanes of `next_tok` are the register's new entries)
        don = (1, 2) if self.engine_config.donate else ()
        don_admit = (0, 1, 2) if self.engine_config.donate else ()
        # meshed engines pin output shardings to the input layout so the
        # jit cache key reaches its fixed point on the FIRST compile
        # (inputs are placed to exactly these shardings in __init__)
        admit_out = step_out = prefill_out = None
        if self._mesh_shardings is not None:
            cache_sh, rep = self._mesh_shardings
            admit_out = (cache_sh, rep, rep)
            # cache, the token register, the host's (tokens, logprobs)
            step_out = (cache_sh, rep, (rep, rep))
            prefill_out = step_out + (rep,)     # and the chunks' counters

        @part("sample")
        def sample_slot(logits, key_raw, position, temp):
            """One slot's next token from [V] logits: traced temperature
            selects greedy vs sampled, the step key derives from the
            request key and the token's position (deterministic under any
            prefill/decode interleave). Also returns the token's logprob
            under the UNSCALED model distribution (temperature-free, so
            greedy and sampled scores are comparable — the best_of
            ranking currency)."""
            key = jax.random.fold_in(jax.random.wrap_key_data(key_raw),
                                     position)
            greedy = jnp.argmax(logits, axis=-1).astype(jnp.int32)
            scaled = logits / jnp.maximum(temp, 1e-6)
            sampled = sample_token(scaled[None, None, :], key, 1.0)[0]
            tok = jnp.where(temp > 0.0, sampled.astype(jnp.int32), greedy)
            lp = jax.nn.log_softmax(logits)[tok]
            return tok, lp

        def count_zeroed_state(cache):
            if cache.stats is None or count_zeroed is None:
                return cache
            return cache.with_stats(dict(
                cache.stats, prefill=count_zeroed(cache.stats["prefill"])))

        if self._spec:
            @partial(jax.jit, donate_argnums=don_admit + ((3,) if don_admit
                                                          else ()),
                     out_shardings=None)
            def admit(cache, slot_keys, temps, dlengths, slot, key_raw,
                      temp, reused_len):
                # a prefix hit starts the TARGET slot's length at the
                # reused prefix; the draft always starts cold (its K/V is
                # a different model's — catch-up chunks rebuild it)
                cache = paged_admit_slot(cache, slot, reused_len)
                slot_keys = slot_keys.at[slot].set(key_raw)
                temps = temps.at[slot].set(temp)
                dlengths = dlengths.at[slot].set(0)
                return cache, slot_keys, temps, dlengths
        elif state:
            @partial(jax.jit, donate_argnums=don_admit)
            def admit(cache, slot_keys, temps, slot, key_raw, temp, entry):
                # the slot's entry is zeroed: a state is read whole
                cache = count_zeroed_state(
                    state_admit_slot(cache, slot, entry))
                slot_keys = slot_keys.at[slot].set(key_raw)
                temps = temps.at[slot].set(temp)
                return cache, slot_keys, temps
        else:
            @partial(jax.jit, donate_argnums=don_admit,
                     out_shardings=admit_out)
            def admit(cache, slot_keys, temps, slot, key_raw, temp,
                      reused_len):
                # a prefix hit starts the slot's length at the reused
                # prefix (those pages already hold its K/V); a miss
                # starts at zero
                cache = paged_admit_slot(cache, slot, reused_len)
                if beside:      # and the slot's entry is zeroed
                    cache = count_zeroed_state(
                        state_admit_slot(cache, slot, slot))
                slot_keys = slot_keys.at[slot].set(key_raw)
                temps = temps.at[slot].set(temp)
                return cache, slot_keys, temps

        @partial(jax.jit, donate_argnums=don + ((9,) if don else ()),
                 out_shardings=prefill_out)
        def prefill(params, cache, tokens, slot_keys, temps, slot,
                    table_row, ids, real_len, chunk_stats=None):
            if state:
                length = cache.lengths[slot]
                kvc = (cache.pool(kernel), None,
                       StateMeta(table_row[:1], real_len[None]))
            else:
                ks, vs, length = paged_slot_view(cache, table_row, slot,
                                                 by_layer=layerwise)
                kvc = (*with_state(cache, ks, vs, slot[None],
                                   real_len[None]), length)
            positions = (length + jnp.arange(chunk, dtype=jnp.int32))[None, :]
            logits, (nk, nv, _), cache, counted = serving_forward(
                "prefill", params, cache, ids[None, :], positions,
                kvc, (real_len - 1)[None],
                (jnp.arange(chunk) < real_len)[None, :])
            if chunk_stats is not None:
                chunk_stats = fold_chunk(chunk_stats, counted)
            cache, nk, nv = take_state(cache, nk, nv)
            with part("sample"):
                if one_row:  # the one row that is read, not the chunk's
                    last = logits[0, 0].astype(jnp.float32)
                else:
                    last = jax.lax.dynamic_index_in_dim(
                        logits[0].astype(jnp.float32), real_len - 1,
                        keepdims=False)
            if state:       # the pool came back, the slot's entry advanced
                cache = cache.commit(
                    nk, cache.lengths.at[slot].set(length + real_len))
            elif layerwise:   # the chunk's rows came back
                cache = paged_write_chunk(cache, table_row, slot, nk, nv,
                                          real_len)
            else:           # the rows, out of the updated views
                cache = paged_write_slot(cache, table_row, slot, nk, nv,
                                         real_len, chunk)
            with part("sample"):
                new_len = length + real_len
                tok, lp = sample_slot(last, slot_keys[slot], new_len,
                                      temps[slot])
                tokens = tokens.at[slot].set(tok)
            # (tok, lp) is the host's: `tokens` is donated to the next
            # program, which may be dispatched before the host reads
            return cache, tokens, (tok, lp), chunk_stats

        decode = None
        if self._spec:
            pass  # draft/verify replace the one-token decode below
        elif state:
            @partial(jax.jit, donate_argnums=don)
            def decode(params, cache, tokens, slot_keys, temps, live, table):
                # one batched forward; every layer's op reads each live
                # lane's state once and writes it back where it lay. A lane
                # that is not live (idle, mid-prefill, finished) has no
                # real row: the op sends it to the SPARE entry and its own
                # state stays as it is
                lengths = cache.lengths
                meta = StateMeta(table[:, 0], live.astype(jnp.int32))
                logits, (pool, _, _), cache, _ = serving_forward(
                    "decode", params, cache, tokens[:, None],
                    lengths[:, None], (cache.pool(kernel), None, meta),
                    jnp.zeros_like(lengths), live[:, None])
                with part("sample"):
                    last = logits[:, 0].astype(jnp.float32)
                    next_tok, lps = jax.vmap(sample_slot)(
                        last, slot_keys, lengths + 1, temps)
                    tokens = jnp.where(live, next_tok, tokens)
                with part("cache.write"):
                    cache = cache.commit(
                        pool, lengths + live.astype(jnp.int32))
                return cache, tokens, (next_tok, lps)
        elif self._use_paged_kernel:
            from ..ops.paged_attention import PagedDecodeMeta

            rows = self.cache.rows
            latent = self._cache_spec.kind == "latent"

            @partial(jax.jit, donate_argnums=don, out_shardings=step_out)
            def decode(params, cache, tokens, slot_keys, temps, live, table):
                # the Pallas kernel walks the page table INSIDE attention:
                # no gather, no per-slot vmap — one batched forward whose
                # cache-attend step (models/decode.decode_attention)
                # streams each slot's live pages through VMEM in place and
                # hands back only the per-slot new K/V rows to scatter
                lengths = cache.lengths
                # the K/V kernel's work follows the lengths it is given:
                # a retired or mid-prefill lane (a stale length; its
                # result is discarded below) walks no page at length 0.
                # The one-pool latent kernel's program stays as it was
                # (PR 26); latent GROUPS' kernels honour `live`
                with part("cache.view"):
                    walked = (lengths if latent and not grouped
                              else jnp.where(live, lengths, 0))
                # (a state group's dead lanes go to the spare entry: `rows`)
                kvc = (*with_state(cache, *paged_decode_operands(cache),
                                   None, live.astype(jnp.int32)),
                       PagedDecodeMeta(table, walked, rows=rows))
                logits, (row_k, row_v, _), cache, _ = serving_forward(
                    "decode", params, cache, tokens[:, None],
                    lengths[:, None], kvc, jnp.zeros_like(lengths),
                    live[:, None])
                cache, row_k, row_v = take_state(cache, row_k, row_v)
                with part("sample"):
                    last = logits[:, 0].astype(jnp.float32)
                    next_tok, lps = jax.vmap(sample_slot)(
                        last, slot_keys, cache.lengths + 1, temps)
                    tokens = jnp.where(live, next_tok, tokens)
                # (one row array a group under a grouped cache; no V rows
                # from a latent pool)
                with part("cache.write"):
                    row_k, row_v = jax.tree.map(lambda r: r[:, :, 0],
                                                (row_k, row_v))
                cache = paged_append_rows(cache, table, row_k, row_v, live)
                return cache, tokens, (next_tok, lps)
        else:
            @partial(jax.jit, donate_argnums=don, out_shardings=step_out)
            def decode(params, cache, tokens, slot_keys, temps, live, table):
                # the dense-gather reference path: one [L, S, R, H, D]
                # view of every slot's pages gathered OUTSIDE the vmap,
                # exactly the layout the family forward already vmaps
                # over; the per-page indices are traced data
                k_all, v_all = paged_batch_view(cache, table)

                def single(tok, length, k_slot, v_slot):
                    logits, (nk, nv, _) = forward(
                        config, params, tok[None, None],
                        positions=length[None, None],
                        kv_caches=(k_slot[:, None], v_slot[:, None], length),
                    )
                    return (logits[0, 0].astype(jnp.float32), nk[:, 0],
                            nv[:, 0])

                if cache.stats is not None or grouped:
                    # ONE batched forward with a length a slot (a family
                    # that counts sees every slot's token in one call, as
                    # under the kernel; a family with groups is handed
                    # one view a group)
                    lengths = cache.lengths
                    logits, (nk, nv, _), cache, _ = serving_forward(
                        "decode", params, cache, tokens[:, None],
                        lengths[:, None],
                        (*with_state(cache, k_all, v_all, None,
                                     live.astype(jnp.int32), kernel=False),
                         lengths),
                        jnp.zeros_like(lengths), live[:, None])
                    cache, nk, nv = take_state(cache, nk, nv)
                    last = logits[:, 0].astype(jnp.float32)
                else:
                    last, nk, nv = jax.vmap(
                        single, in_axes=(0, 0, 1, 1), out_axes=(0, 1, 1)
                    )(tokens, cache.lengths, k_all, v_all)
                with part("sample"):
                    next_tok, lps = jax.vmap(sample_slot)(
                        last, slot_keys, cache.lengths + 1, temps)
                    tokens = jnp.where(live, next_tok, tokens)
                cache = paged_append_batch(cache, table, nk, nv, live)
                return cache, tokens, (next_tok, lps)

        self._admit_p, self._prefill_p, self._decode_p = admit, prefill, decode
        if self._spec:
            self._build_speculative_programs(sample_slot)

    def _build_speculative_programs(self, sample_slot) -> None:
        """The speculative replacement for the decode step, as fixed-shape
        programs (ISSUE 12):

        - `draft_prefill`: one chunk of the DRAFT model's prompt prefill
          into its dense slot cache (the draft re-reads the whole prompt,
          including any target-side reused prefix — cached pages hold the
          TARGET's K/V, which can't seed a different model);
        - `draft`: K sequential one-token steps of the draft, scanned
          inside one program — proposals + the draft's full logits ride
          out for the accept rule;
        - `verify`: ONE batched K-token target forward over every slot's
          paged view (exactly PR 10's short-sequence paged forward), the
          accept rule, and the fixed-shape commit (accepted rows go to
          their pages, rejected rows are not written — per-slot counts
          are traced data, so accept patterns never change a shape).

        Sampling keys: token at absolute position p in the NON-speculative
        engine uses fold_in(request_key, p); the speculative step needs
        three independent draws per position (draft proposal, accept
        uniform, residual resample), derived as fold_in(fold_in(key, p),
        tag) with distinct tags — still slot-decorrelated and
        schedule-independent, and independent of each other, which is
        what the rejection-sampling correctness argument requires."""
        forward, config = self._serving.forward, self.config
        dforward, dcfg = self._draft_serving.forward, self._draft_config
        chunk = self.engine_config.prefill_chunk
        K = self.engine_config.draft_k
        S = self.engine_config.num_slots
        don = (1, 2) if self.engine_config.donate else ()
        don_d = (1,) if self.engine_config.donate else ()
        DRAFT_TAG, ACCEPT_TAG, RESID_TAG = 1, 2, 3

        @partial(jax.jit, donate_argnums=don_d)
        def draft_prefill(dparams, dcache, slot, ids, real_len):
            ks, vs, length = slot_caches(dcache, slot)
            positions = (length + jnp.arange(chunk, dtype=jnp.int32))[None, :]
            _, (nk, nv, _) = dforward(dcfg, dparams, ids[None, :],
                                      positions=positions,
                                      kv_caches=(ks, vs, length))
            return write_slot(dcache, slot, nk, nv, real_len)

        @partial(jax.jit, donate_argnums=don_d)
        def draft(dparams, dcache, tokens, slot_keys, temps):
            def single(tok, length, ks, vs):
                logits, (nk, nv, _) = dforward(
                    dcfg, dparams, tok[None, None],
                    positions=length[None, None],
                    kv_caches=(ks[:, None], vs[:, None], length))
                return logits[0, 0].astype(jnp.float32), nk[:, 0], nv[:, 0]

            def propose(lg, key_raw, pos, temp):
                key = jax.random.fold_in(
                    jax.random.fold_in(jax.random.wrap_key_data(key_raw),
                                       pos), DRAFT_TAG)
                greedy = jnp.argmax(lg, axis=-1).astype(jnp.int32)
                sampled = jax.random.categorical(
                    key, lg / jnp.maximum(temp, 1e-6)).astype(jnp.int32)
                return jnp.where(temp > 0.0, sampled, greedy)

            def body(carry, _):
                tok, k_all, v_all, lengths = carry
                lg, nk, nv = jax.vmap(single, in_axes=(0, 0, 1, 1),
                                      out_axes=(0, 1, 1))(
                    tok, lengths, k_all, v_all)
                nxt = jax.vmap(propose)(lg, slot_keys, lengths + 1, temps)
                return (nxt, nk, nv, lengths + 1), (nxt, lg)

            (_, nk, nv, _), (d_toks, d_logits) = jax.lax.scan(
                body, (tokens, dcache.k, dcache.v, dcache.lengths),
                None, length=K)
            # scan stacks on a leading step dim: -> [S, K] / [S, K, V]
            return (d_toks.T, jnp.moveaxis(d_logits, 0, 1),
                    dataclasses.replace(dcache, k=nk, v=nv,
                                        lengths=dcache.lengths + K))

        @partial(jax.jit, donate_argnums=don)
        def verify(params, cache, tokens, slot_keys, temps, live, table,
                   d_toks, d_logits):
            # inputs per slot: [t0, d1..d_{K-1}] at positions L..L+K-1 —
            # row j's logits is the target distribution for the token at
            # position L+j+1, i.e. proposal d_{j+1}'s judge
            ids = jnp.concatenate([tokens[:, None], d_toks[:, :K - 1]],
                                  axis=1)
            k_all, v_all = paged_batch_view(cache, table)

            def single(ids_s, length, ks, vs):
                positions = (length
                             + jnp.arange(K, dtype=jnp.int32))[None, :]
                logits, (nk, nv, _) = forward(
                    config, params, ids_s[None, :], positions=positions,
                    kv_caches=(ks[:, None], vs[:, None], length))
                return logits[0].astype(jnp.float32), nk[:, 0], nv[:, 0]

            t_logits, nk, nv = jax.vmap(single, in_axes=(0, 0, 1, 1),
                                        out_axes=(0, 1, 1))(
                ids, cache.lengths, k_all, v_all)

            def accept_slot(tl, dl, dt, key_raw, base, temp):
                # tl/dl [K, V] target/draft logits; dt [K] proposals
                key = jax.random.wrap_key_data(key_raw)
                pos = base + 1 + jnp.arange(K, dtype=jnp.int32)
                greedy_ok = dt == jnp.argmax(tl, axis=-1).astype(jnp.int32)
                p = jax.nn.softmax(tl / jnp.maximum(temp, 1e-6), axis=-1)
                q = jax.nn.softmax(dl / jnp.maximum(temp, 1e-6), axis=-1)
                p_tok = jnp.take_along_axis(p, dt[:, None], axis=1)[:, 0]
                q_tok = jnp.take_along_axis(q, dt[:, None], axis=1)[:, 0]

                def u_at(po):
                    return jax.random.uniform(jax.random.fold_in(
                        jax.random.fold_in(key, po), ACCEPT_TAG))

                # accept d_i with prob min(1, p(d_i)/q(d_i)) — spelled
                # u*q < p so q=0 (a proposal the draft couldn't have
                # sampled) auto-rejects without a division
                samp_ok = jax.vmap(u_at)(pos) * q_tok < p_tok
                ok = jnp.where(temp > 0.0, samp_ok, greedy_ok)
                prefix = jnp.cumprod(ok.astype(jnp.int32))
                n_acc = prefix.sum()
                c = jnp.where(n_acc == K, K, n_acc + 1)
                # correction at the first rejected position: sample the
                # residual max(p - q, 0)/Z — together with the accepts
                # this reproduces the target distribution exactly
                r = jnp.minimum(n_acc, K - 1)
                resid = jnp.maximum(p[r] - q[r], 0.0)
                resid = jnp.where(resid.sum() > 1e-9, resid, p[r])
                rkey = jax.random.fold_in(
                    jax.random.fold_in(key, base + 1 + r), RESID_TAG)
                corr_sampled = jax.random.categorical(
                    rkey, jnp.log(resid + 1e-30)).astype(jnp.int32)
                corr_greedy = jnp.argmax(tl[r], axis=-1).astype(jnp.int32)
                corr = jnp.where(temp > 0.0, corr_sampled, corr_greedy)
                j = jnp.arange(K, dtype=jnp.int32)
                committed = jnp.where(j < n_acc, dt, corr)
                logp = jax.nn.log_softmax(tl, axis=-1)
                lps = jnp.take_along_axis(logp, committed[:, None],
                                          axis=1)[:, 0]
                return (committed, c.astype(jnp.int32),
                        n_acc.astype(jnp.int32), lps)

            committed, counts, n_acc, lps = jax.vmap(accept_slot)(
                t_logits, d_logits, d_toks, slot_keys, cache.lengths, temps)
            counts = jnp.where(live, counts, 0)
            n_acc = jnp.where(live, n_acc, 0)
            new_tok = committed[jnp.arange(S), jnp.maximum(counts, 1) - 1]
            tokens = jnp.where(live, new_tok, tokens)
            # keep exactly the accepted inputs' K/V rows (t0..d_{c-1});
            # rejected candidates' rows are left unwritten by the
            # fixed-shape window write
            rows = cache.lengths[:, None] + jnp.arange(K, dtype=jnp.int32)
            idx = rows[None, :, :, None, None]
            win_k = jnp.take_along_axis(nk, idx, axis=2)
            win_v = jnp.take_along_axis(nv, idx, axis=2)
            cache = paged_append_window(cache, table, win_k, win_v,
                                        counts, live)
            return cache, tokens, committed, counts, n_acc, lps

        self._draft_prefill_p = draft_prefill
        self._draft_p = draft
        self._verify_p = verify

    def device_counters(self) -> dict:
        """The family's own counters (`ServingContract.init_stats`), one
        set a program ("prefill", "decode"), as NumPy arrays; {} for a
        family that declares none. "prefill" also holds what the family
        counts in its chunks alone (`init_chunk_stats`). They
        accumulate on the device inside the two programs and cross to the
        host HERE, on demand: nothing on a step's path reads them."""
        if self.cache is None or self.cache.stats is None:
            return {}
        self.settle()  # counts and committed tokens of the same programs
        stats = dict(self.cache.stats)
        if self._chunk_stats is not None:
            stats["prefill"] = dict(stats["prefill"], **self._chunk_stats)
        return jax.tree_util.tree_map(np.asarray, stats)

    def compile_stats(self) -> dict[str, int]:
        """Compiled-program counts per engine program — the recompile
        guard: these must stay flat however the request mix changes.
        Speculative engines report their five programs (the one-token
        decode is never built); classic engines keep the exact
        admit/prefill/decode triple."""
        out = {
            "admit": self._admit_p._cache_size(),
            "prefill": self._prefill_p._cache_size(),
        }
        if self._spec:
            out["draft_prefill"] = self._draft_prefill_p._cache_size()
            out["draft"] = self._draft_p._cache_size()
            out["verify"] = self._verify_p._cache_size()
        else:
            out["decode"] = self._decode_p._cache_size()
        if self._swap_transport is not None:
            # host tier on: the swap pair must stay flat too, whatever
            # the swap-out/swap-in mix (keys match the pod transport's)
            out.update(self._swap_transport.compile_stats())
        return out

    # -- request API ---------------------------------------------------------

    def submit(
        self,
        prompt,
        max_new_tokens: int = 32,
        temperature: float = 0.0,
        key=None,
        eos_token_id: int | None = None,
        deadline_s: float | None = None,
        tenant: str = "default",
        slo_ttft_s: float | None = None,
        trace_id=None,
        trace_parent=0,
        trace_sampled: bool | None = None,
        parent_id: int | None = None,
    ) -> Request:
        """Queue one generation request; returns its handle immediately.
        Overload is reported on the handle (`status` REJECTED with
        `reject_reason`, a machine-readable `shed_code`, and a
        `retry_after_s` backoff hint), never deferred to an OOM.
        `tenant` routes the request through that tenant's priority tier /
        DRR share; `slo_ttft_s` overrides the tenant's TTFT SLO for this
        request. `trace_id`/`trace_parent` join the request to an
        externally minted trace (the HTTP layer's, or an inbound W3C
        traceparent); with tracing enabled and no id supplied the engine
        mints one, so direct engine callers get request ids too.
        Whether SPANS record is the per-tenant head-sampling decision —
        made here unless the caller passes `trace_sampled` (the server
        decides ONCE per HTTP request so n/best_of siblings sample
        together; a half-sampled fan-out is noise). An unsampled request
        keeps its id (request-id plumbing must not depend on the
        sampling rate)."""
        prompt = np.asarray(prompt, np.int32).reshape(-1)
        if prompt.size == 0:
            raise ValueError("empty prompt")
        if max_new_tokens < 1:
            raise ValueError("max_new_tokens must be >= 1")
        req = Request(
            prompt=prompt, max_new_tokens=max_new_tokens,
            temperature=float(temperature), key=key,
            eos_token_id=eos_token_id, deadline_s=deadline_s,
            tenant=tenant, slo_ttft_s=slo_ttft_s, parent_id=parent_id,
        )
        prepare_request_tracing(req, trace_id, trace_parent, trace_sampled)
        with self._request_span("serving.submit", req,
                                prompt_len=req.prompt_len) as sp:
            # drain first, THEN capacity-check: a slot freed since the last
            # step (or an expired entry still holding a queue position) must
            # make room before this request is judged against max_queue —
            # the queue bound covers genuinely *waiting* requests only
            self._admit_pending()
            self.scheduler.submit(req)
            # pressure/displacement victims shed INSIDE submit have no other
            # path into the metrics — drain them before reporting the
            # newcomer
            victims = self.scheduler.drain_shed()
            for victim in victims:
                self._finalize_request(victim)
            if req.done:
                self._finalize_request(req)
            else:
                # eager admission: a free slot absorbs the request now, so
                # TTFT doesn't wait for the next step() call
                self._admit_pending()
            sp.set(admitted=req.admitted_at is not None,
                   shed=len(victims) + int(req.done))
        return req

    def fork(
        self,
        parent: Request,
        max_new_tokens: int | None = None,
        temperature: float | None = None,
        key=None,
        eos_token_id: Any = "inherit",
        deadline_s: float | None = None,
        slo_ttft_s: float | None = None,
        trace_id=None,
        trace_parent=0,
        trace_sampled: bool | None = None,
    ) -> Request:
        """COW-fork `parent`: a new request on the same prompt that
        SHARES the parent's prompt pages instead of re-prefilling them.

        Mechanism: the parent is marked `share_prompt`, which publishes
        its full prompt pages into the radix tree the moment prefill
        completes them (`PagedAllocator.publish_prompt` — mid-flight,
        not at retirement), plus immediately here for whatever is
        already prefilled. The fork is then an ordinary submission whose
        admission maps the published pages copy-on-write and diverges at
        its first private page — an n-way `n`/`best_of` fan-out pays ONE
        prompt prefill (each sibling still prefills the final partial
        page: the last prompt token must produce its own first-token
        logits). Works at any parent phase: queued (pages publish as
        they prefill), running, or finished (pages are in the tree
        already); a cancelled parent's published pages survive in the
        tree, so forks keep their sharing — the COW refcounts isolate
        every sibling. Unset generation knobs inherit the parent's;
        `key` should differ per fork or siblings sample identical
        streams (None derives a distinct key from the fork's request
        id). With `prefix_cache=False` the fork still runs, it just
        re-prefills — sharing needs the radix tree."""
        if self._state_spec is not None:
            raise ValueError(
                "fork: this family keeps one recurrent state a sequence "
                + ("in a group of entries BESIDE its groups of K/V pages "
                   "(a grouped cache with CacheSpec.kind='state')"
                   if self._state_beside else
                   "and no K/V rows (CacheSpec.kind='state')")
                + "; a fork shares its parent's prompt PAGES, and a state "
                "has none: it needs a snapshot of the parent's state after "
                "the prompt, which is not implemented. Submit the prompt "
                "again.")
        parent.share_prompt = True
        if not parent.done:
            self._fork_parents[parent.request_id] = parent
        for slot in self.scheduler.slots:
            if slot.request is parent:
                self.allocator.publish_prompt(slot)
                break
        return self.submit(
            parent.prompt,
            max_new_tokens=(parent.max_new_tokens if max_new_tokens is None
                            else max_new_tokens),
            temperature=(parent.temperature if temperature is None
                         else temperature),
            key=key,
            eos_token_id=(parent.eos_token_id if eos_token_id == "inherit"
                          else eos_token_id),
            deadline_s=deadline_s,
            tenant=parent.tenant,
            slo_ttft_s=slo_ttft_s,
            trace_id=trace_id,
            trace_parent=trace_parent,
            trace_sampled=trace_sampled,
            parent_id=parent.request_id,
        )

    def cancel(self, request: Request) -> bool:
        self.settle()  # a token on its way may finish the request first
        if self.scheduler.cancel(request):
            self._finalize_request(request)
            return True
        return False

    def finish(self, request: Request) -> bool:
        """Retire a running request as FINISHED before its budget (e.g.
        a server-side stop sequence matched): counts in the finished/
        latency metrics, prompt pages cached for reuse."""
        self.settle()
        if self.scheduler.finish_early(request):
            self._finalize_request(request)
            return True
        return False

    def stream(self, request: Request) -> Iterator[int]:
        """Yield the request's tokens as the engine produces them, driving
        `step()` while the request is live."""
        sent = 0
        while True:
            while sent < len(request.tokens):
                yield request.tokens[sent]
                sent += 1
            if request.done or not self.step():
                break
        yield from request.tokens[sent:]

    async def astream(self, request: Request) -> AsyncIterator[int]:
        """`stream()` for asyncio callers: yields control to the loop
        between engine steps so concurrent coroutines interleave."""
        sent = 0
        while True:
            while sent < len(request.tokens):
                yield request.tokens[sent]
                sent += 1
            if request.done or not self.step():
                break
            await asyncio.sleep(0)
        for tok in request.tokens[sent:]:
            yield tok

    # -- the drive loop ------------------------------------------------------

    def step(self) -> bool:
        """Run one scheduler action (admissions + one prefill chunk OR one
        batched decode step), then read and commit the results of the
        program the PREVIOUS step dispatched: the chip runs this step's
        program while the host reads, commits and comes back to dispatch
        the next. A step with nothing to dispatch commits what is unread.
        Returns False when the engine is idle with every token committed."""
        if self.metrics.started_at is None:
            self.metrics.started_at = self._clock()
        if self.watchdog is not None:
            self.watchdog.tick()
        self._admit_pending()
        # an idle engine records nothing: callers poll step() in a tight
        # loop, and with no live slot there is nothing to schedule
        kind = None
        if self.scheduler.live_slots:
            with _phase("serving.schedule") as sp:
                action = self.scheduler.next_action()
                kind = action[0] if action else None
                sp.set(action=kind or "none")
        unread = self._unread
        if kind is None and unread is None:
            self.metrics.stopped_at = self._clock()
            if self._sanitize:
                self._sanity_check()
            return False
        t0 = self._clock()
        # dispatch first (a program that owes the host tokens takes
        # `_unread`'s place; if the dispatch raises, `unread` stays there)
        if kind == "prefill":
            self._run_prefill_chunk(action[1])
        elif kind == "decode":
            self._run_decode(action[1])
        if unread is not None:
            if self._unread is unread:
                self._unread = None
            self._read_and_commit(unread, behind=kind)
        if self._spec:
            # the speculative step's own books (draft progress, accepted
            # counts) decide its next inputs: it keeps the synchronous order
            self.settle()
        with _phase("serving.bookkeeping"):
            self.metrics.stopped_at = self._clock()
            # the EMA behind the scheduler's SLO / Retry-After estimates —
            # host-side bookkeeping only, nothing traced
            self.scheduler.note_step_time(self.metrics.stopped_at - t0)
            self.metrics.observe_step(self.scheduler.live_slots,
                                      self.engine_config.num_slots,
                                      self.scheduler.queue_depth)
            # keep the goodput gauge live for mid-run scrapes (a handful of
            # host float ops — the device never sees it)
            self._goodput()
            self._maybe_log()
            if self._sanitize:
                self._sanity_check()
        return True

    def _sanity_check(self) -> None:
        """Run the serving-state sanitizer (EngineConfig(sanitize=True)):
        cross-structure invariants after this step. On a violation the
        incident-bundle machinery captures the engine's debug state
        before the structured SanitizerViolation propagates."""
        try:
            check_engine(self)
        except SanitizerViolation as e:
            self._write_sanitizer_incident(e)
            raise

    def _write_sanitizer_incident(self, e: SanitizerViolation) -> None:
        from ..telemetry.watchdog import (
            build_exception_report,
            resolve_incident_dir,
            write_incident_bundle,
        )

        incident_dir = resolve_incident_dir(
            self.engine_config.incident_dir)
        if incident_dir is None:
            return
        try:
            report = build_exception_report(e, name="sanitizer")
            report["check"] = e.check
            report["details"] = e.details
            write_incident_bundle(
                incident_dir, report, registry=self.registry,
                dumps=self.incident_dumps(), name="sanitizer")
        except Exception:
            pass  # the violation itself must still propagate

    def run_until_idle(self) -> None:
        while self.step():
            pass

    def _leave_unread(self, program: str, out, lanes: list) -> None:
        """The program just dispatched owes `lanes` a token each: start
        its results' copy to the host and leave the read to the next
        `step()` (or to `settle`)."""
        for leaf in out:
            leaf.copy_to_host_async()
        for slot, _, _ in lanes:
            slot.unread += 1
        self._unread = _Unread(program, out, lanes)

    def _read_and_commit(self, unread: _Unread, behind=None) -> None:
        """Fetch a dispatched program's tokens and logprobs (the one wait
        for the chip in the host pass) and commit them. `behind` names the
        program dispatched since, which the chip runs meanwhile."""
        with _phase("serving.host_read", program=unread.program,
                    behind=behind or "none"):
            toks, lps = jax.device_get(unread.out)
        self.metrics.note_result_read(overlapped=behind is not None)
        with _phase("serving.commit", tokens=len(unread.lanes)) as sp:
            if unread.program == "decode":
                self.timer.tick(block_on=None)
            finished = 0
            for slot, req, at in unread.lanes:
                if slot.request is not req:
                    # the request finished on an EOS the host could not
                    # count ahead, and its lane rode this step dead
                    continue
                slot.unread -= 1
                if self.scheduler.note_token(slot, int(toks[at]),
                                             logprob=float(lps[at])):
                    self._finalize_request(req)
                    finished += 1
            sp.set(finished=finished)

    def settle(self) -> None:
        """Commit the unread program's results now. Whatever acts on a
        request's books from outside `step()` (cancel, finish, a counter
        read, a metrics reset) calls this first, so that it never sees a
        request with a token still on its way."""
        unread, self._unread = self._unread, None
        if unread is not None:
            self._read_and_commit(unread)

    def _admit_pending(self) -> None:
        """Shed expired/doomed queued requests, then admit from the
        queue into free slots. Observation goes through the scheduler's
        shed log — the one path that also covers victims shed inside
        submit() (queue-pressure and tier-displacement sheds)."""
        sched = self.scheduler
        depth = sched.queue_depth
        if not depth and not sched.shed_log:
            return  # nothing waits: no work, and an idle engine records nothing
        with _phase("serving.admit_pending", queue_depth=depth) as sp:
            now = self._clock()
            sched.shed_expired(now)
            shed = sched.drain_shed()
            for req in shed:
                self._finalize_request(req)
            admitted = sched.admissions(now)
            for slot, req in admitted:
                self._run_admit(slot, req)
            sp.set(admitted=len(admitted), shed=len(shed))

    def _strict_audit(self, name: str, jitted, args: tuple) -> None:
        """Strict-mode program passes, once per program, at first use.

        Two layers: (1) a direct mesh-placement check on the argument
        arrays. On a single-host engine an arg spanning >1 device means
        GSPMD will insert collectives at partitioning time, AFTER the
        lowering this audit reads — the 'params leaked onto a mesh'
        hazard, caught at the placement itself. On a MESHED engine
        (EngineConfig.mesh) the check inverts: a prefill/decode whose
        every argument is fully replicated (or single-device) means the
        params were never sharded — each device computes the whole model
        and tensor parallelism silently bought nothing. (2) the program
        text: single-host engines read the lowering (tracing cost only —
        shard_map-explicit collectives and host callbacks are visible
        there); meshed engines read the COMPILED optimized HLO (one extra
        XLA compile per program, once — the GSPMD-inserted TP collectives
        only exist there) and check it against the pod contract."""
        if self.engine_config.strict is None:
            return
        from ..analysis.findings import Finding, run_cached_audit
        from ..analysis.program import find_host_transfers

        pname = f"serving.{name}"
        on_mesh = self.engine_config.mesh is not None

        def audit():
            findings = []
            meshed = [
                leaf for leaf in jax.tree_util.tree_leaves(args)
                if isinstance(leaf, jax.Array)
                and len(leaf.sharding.device_set) > 1
            ]
            if meshed and not on_mesh:
                ndev = max(len(leaf.sharding.device_set) for leaf in meshed)
                findings.append(Finding(
                    rule="ATP101",
                    message=(
                        f"{len(meshed)} argument array(s) span {ndev} "
                        "devices: GSPMD inserts collectives after lowering, "
                        "invisible to this audit — a single-host engine "
                        "expects unplaced params (sharded-serving setups "
                        "must configure EngineConfig(mesh=...), which "
                        "audits compiled HLO against the pod contracts)"),
                    path=f"<program:{pname}>",
                    source=f"mesh-placed args x{len(meshed)}",
                ))
            if on_mesh and name in ("prefill", "decode") and not any(
                    isinstance(leaf, jax.Array)
                    and len(leaf.sharding.device_set) > 1
                    and not leaf.sharding.is_fully_replicated
                    for leaf in jax.tree_util.tree_leaves(args)):
                findings.append(Finding(
                    rule="ATP101",
                    message=(
                        "tensor-parallel engine with no sharded argument: "
                        "params were not mesh-placed (pass them through "
                        "serving.pod.shard_params, or use the "
                        "serving.pod.sharded_engine factory) — every "
                        "device is computing the full model"),
                    path=f"<program:{pname}>",
                    source="mesh engine, fully-replicated args",
                ))
            if on_mesh:
                # GSPMD collectives exist only post-partitioning: audit
                # the compiled text (one extra compile, cached audit)
                text = jitted.lower(*args).compile().as_text()
            else:
                text = jitted.lower(*args).as_text()
            findings += find_host_transfers(text, name=pname)
            contract = (self._contracts or {}).get(name)
            if contract is not None:
                findings += contract.check(text)
            return findings

        run_cached_audit(
            self._audited, name, self.engine_config.strict, audit,
            on_finding=lambda f: self.registry.counter(
                "analysis_findings_total", rule=f.rule).inc(),
            label=f"engine program {pname!r}",
        )

    def _ensure_cost(self, name: str, program, args: tuple) -> None:
        """Capture the program's static cost ONCE, at its first dispatch
        — `lower()` on the jitted program (tracing cost only, no extra
        XLA compile: the jit's own executable cache is what
        compile_stats() counts, and it is untouched). Backends that
        report no cost_analysis fall back to the analytic per-family
        estimate."""
        if self.cost.has(name):
            return
        try:
            src = program.lower(*args)
        except Exception:
            src = None
        self.cost.register(name, src,
                           fallback=lambda: self._analytic_cost(name))

    def _dispatch(self, name: str, program, args: tuple, fence_in, opened,
                  timed: bool = True, unread: list | None = None):
        """The ONE way a program of the engine's own is dispatched -> its
        outputs: strict audit and cost sheet (each at a program's first
        dispatch), the sampled fence pair from `fence_in` to the outputs,
        and inside it `opened`, the caller's span, around the call (`timed`:
        under `timer.dispatch()`; all but `admit`). `unread`: the lanes owed
        a token each, left unread INSIDE the span (`decode`'s)."""
        self._strict_audit(name, program, args)
        self._ensure_cost(name, program, args)
        with self.cost.maybe_sample(name, fence_in=fence_in) as sample:
            with opened:
                with self.timer.dispatch() if timed else nullcontext():
                    out = program(*args)
                if unread is not None:
                    self._leave_unread(name, out[2], unread)
            sample(out)
        return out

    def _analytic_cost(self, name: str) -> tuple[float, float]:
        """Analytic fallback (flops, bytes) per program call when the
        backend reports nothing: ~2 FLOPs/param/token + the attention-
        over-cache term (profiler.causal_lm_infer_flops), bytes = one
        full weight read + the KV rows touched. The mid-stream context
        length is unknown statically; max_len/2 is the documented
        approximation."""
        cfg, ec, serving = self.config, self.engine_config, self._serving
        if name in ("draft", "draft_prefill"):
            cfg, serving = self._draft_config, self._draft_serving
            if getattr(self, "_n_draft_params", None) is None:
                self._n_draft_params = count_params(self._draft_params)
            n = self._n_draft_params
        else:
            if self._n_params is None:
                self._n_params = count_params(self.params)
            n = self._n_params
        spec = serving.cache_spec(cfg)
        if isinstance(spec, tuple):
            # groups: every layer counted as keeping its rows whole (an
            # upper bound for the window groups)
            spec = dataclasses.replace(
                spec[0], num_layers=sum(g.num_layers for g in spec))
        num_layers = spec.num_layers
        hidden = getattr(cfg, "hidden_size", 0) or (
            getattr(cfg, "num_attention_heads", 1) * spec.width)
        avg_ctx = max(1, ec.max_len // 2)
        elt = 2  # bf16 weights/activations
        # one K row + one V row, or the one latent row
        kv_row = spec.heads * spec.width * elt * (
            1 if spec.kind == "latent" else 2)
        if name == "decode":
            tokens = ec.num_slots
            flops = causal_lm_infer_flops(n, tokens, num_layers, hidden,
                                          kv_len=avg_ctx)
            nbytes = n * elt + tokens * num_layers * avg_ctx * kv_row
        elif name == "verify":
            # one K-token forward per slot — the batched verify is
            # decode with draft_k tokens per lane
            tokens = ec.num_slots * ec.draft_k
            flops = causal_lm_infer_flops(n, tokens, num_layers, hidden,
                                          kv_len=avg_ctx)
            nbytes = (n * elt
                      + ec.num_slots * num_layers * avg_ctx * kv_row)
        elif name == "draft":
            # K sequential one-token draft steps over every slot
            tokens = ec.num_slots * ec.draft_k
            flops = causal_lm_infer_flops(n, tokens, num_layers, hidden,
                                          kv_len=avg_ctx)
            nbytes = ec.draft_k * n * elt \
                + tokens * num_layers * avg_ctx * kv_row
        elif name in ("prefill", "draft_prefill"):
            tokens = ec.prefill_chunk
            flops = causal_lm_infer_flops(n, tokens, num_layers, hidden,
                                          kv_len=avg_ctx)
            nbytes = (n * elt + tokens * num_layers * kv_row
                      + num_layers * avg_ctx * kv_row)
        else:  # admit: per-slot bookkeeping only, no model math
            flops, nbytes = 0.0, float(ec.num_slots * 16)
        return float(flops), float(nbytes)

    def _hold_fork_child(self, req: Request) -> bool:
        """Admission hold for COW forks: a fork child stays QUEUED until
        its parent's full prompt pages are published (or the parent is
        terminal — then whatever made it into the tree is all there will
        be). Admitting earlier would cold-prefill the shared prompt and
        forfeit the single-prefill property the fork exists for. Progress
        is guaranteed: a live parent's prefill advances every engine
        step, and a shed/cancelled parent releases the hold immediately."""
        if req.parent_id is None:
            return False
        parent = self._fork_parents.get(req.parent_id)
        if parent is None or parent.done:
            return False
        want = (req.prompt_len - 1) // self.engine_config.page_size
        if want <= 0:
            return False  # nothing shareable: sub-page prompts admit cold
        for slot in self.scheduler.slots:
            if slot.request is parent:
                have = min(slot.prompt_done, parent.prompt_len) \
                    // self.engine_config.page_size
                return have < want
        return True  # parent still queued: its prefill hasn't started

    def _hold_admission(self, req: Request) -> bool:
        """The allocator's admission-hold hook: COW fork children wait
        for their parent's publish (above), and — cache-aware scheduling,
        ISSUE 16 — any queued request whose full shareable prefix is
        currently being prefilled by another request waits for that
        leader's pages instead of duplicating the prefill."""
        return self._hold_fork_child(req) or self._hold_for_dedup(req)

    def _hold_for_dedup(self, req: Request) -> bool:
        """In-flight prefill dedup. If a PREFILL-state slot's prompt
        covers `req`'s full shareable prefix, flag that leader to
        publish its prompt pages mid-flight (`publish_prompt`, the COW
        fork machinery) and hold `req` until the published pages cover
        it — N concurrent identical prompts then cost ONE full prefill
        (each follower still prefills its private sub-page tail).

        Bounded by policy: a request never waits on a LOWER-priority
        tier's leader (a gold request never waits on a bronze leader),
        and the hold re-evaluates every admission attempt, so a leader
        that is cancelled, shed, or finished early simply stops
        matching and the follower re-prefills cold — waits are bounded
        by the leader's own prefill progress, which advances every
        step."""
        want = (req.prompt_len - 1) // self.engine_config.page_size
        if want <= 0:
            return False
        if len(self.allocator.index.match(req.prompt)) >= want:
            # the tree already covers us (HBM or host) — admit now
            self._dedup_held.discard(req.request_id)
            return False
        k = want * self.engine_config.page_size
        my_tier = self.scheduler.tenant_priority(req.tenant)
        head = req.prompt[:k]
        for slot in self.scheduler.slots:
            leader = slot.request
            if (slot.state is not SlotState.PREFILL or leader is None
                    or leader is req):
                continue
            if leader.prompt_len < k \
                    or self.scheduler.tenant_priority(leader.tenant) > my_tier:
                continue
            if not np.array_equal(np.asarray(leader.prompt[:k]), head):
                continue
            leader.share_prompt = True  # publish from the next chunk on
            if self.allocator.publish_prompt(slot) >= want:
                self._dedup_held.discard(req.request_id)
                return False
            if req.request_id not in self._dedup_held:
                self._dedup_held.add(req.request_id)
                self.metrics.note_dedup_hit()
            return True
        self._dedup_held.discard(req.request_id)
        return False

    def _unmap_slot(self, index: int) -> None:
        """Allocator callback at release: reset the slot's page table to
        all-trash BEFORE its pages can be reallocated, so the retired
        lane's masked ride-along writes in later decode steps can never
        land in a page now owned by someone else."""
        self._table[index, :] = self.cache.trash_page
        for table, group in zip(self._ring_tables, self._ring_groups()):
            table[index, :] = group.trash_page
        self._set_page_gauges()

    def _set_page_gauges(self) -> None:
        alloc = self.allocator
        self.metrics.set_page_gauges(
            alloc.pages_in_use, alloc.pages_free,
            alloc.pages_in_use * self.cache.page_nbytes)
        if self._state_spec is not None:
            # entries held: alone, the allocator's pages ARE entries;
            # beside pages, one a live allocation
            state = self.cache.state if self._state_beside else self.cache
            held = (alloc.allocations_live if self._state_beside
                    else alloc.pages_in_use)
            self.metrics.set_state_bytes_gauge(held * state.page_nbytes)
        if self.cache.side is not None:
            self.metrics.set_side_bytes_gauge(
                alloc.pages_in_use * self.cache.side_page_nbytes)
        if self._cache_groups:
            self.metrics.set_group_page_gauges(dict(zip(
                (g.label for g in self._cache_groups),
                (alloc.pages_in_use, *alloc.ring_pages_in_use,
                 *((alloc.allocations_live,) if self._state_beside
                   else ())))))

    def _run_swap_in(self, slot: Slot, req: Request, alloc) -> None:
        """Install a host-resident prefix's bytes into the pages the
        allocator reserved for it, through the jitted transport install
        (fixed [pages_per_slot] block, trash-padded — every swap mix
        hits the one compiled program). int8 pools land codes + scales
        verbatim: byte-identical to what swap-out extracted, the same
        bit-stability COW sharing relies on. One install covers the
        whole admission: a matched prefix is at most pages_per_slot - 1
        pages (the last prompt token always prefills)."""
        t0 = self._clock()
        cache, tp = self.cache, self._swap_transport
        P = cache.pages_per_slot
        rows = np.full((P,), cache.trash_page, np.int32)
        k_blk = np.zeros((cache.k.shape[0], P) + cache.k.shape[2:],
                         cache.k.dtype)
        v_blk = np.zeros_like(k_blk)
        ks_blk = vs_blk = None
        if cache.quantized:
            ks_blk = np.zeros(
                (cache.k_scale.shape[0], P) + cache.k_scale.shape[2:],
                cache.k_scale.dtype)
            vs_blk = np.zeros_like(ks_blk)
        for i, (node, page) in enumerate(alloc.swap_ins):
            data = self._host_tier.fetch(node)
            rows[i] = page
            k_blk[:, i] = data["k"]
            v_blk[:, i] = data["v"]
            if cache.quantized:
                ks_blk[:, i] = data["k_scale"]
                vs_blk[:, i] = data["v_scale"]
        # first_tok=0 rides along into the slot's last-token register —
        # dead state until prefill overwrites it (same masking argument
        # as the trash-page dead writes)
        args = (cache, self._tokens, jnp.int32(slot.index), rows,
                k_blk, v_blk, jnp.int32(0))
        if cache.quantized:
            args += (ks_blk, vs_blk)
        self._strict_audit("install", tp._install_p, args)
        with self._request_span("serving.swap_in", req, slot=slot.index,
                                pages=len(alloc.swap_ins)):
            self.cache, self._tokens = tp._install_p(*args)
        self.metrics.note_swap_in(len(alloc.swap_ins),
                                  self._clock() - t0)

    def _run_admit(self, slot: Slot, req: Request) -> None:
        key_raw = _as_raw_key(req.key)
        if key_raw is None:
            key_raw = jax.random.key_data(
                jax.random.fold_in(self._base_key, req.request_id))
        alloc = slot.alloc
        row = self._table[slot.index]
        row[:] = self.cache.trash_page
        row[:len(alloc.pages)] = alloc.pages
        for table, group, ring in zip(self._ring_tables, self._ring_groups(),
                                      alloc.rings):
            table[slot.index, :] = group.trash_page
            table[slot.index, :len(ring)] = ring
        if alloc.swap_ins:
            # host-resident prefix: land the swapped-out bytes in the
            # freshly reserved pages BEFORE the admit program publishes
            # the reused length (nothing reads the pages in between)
            self._run_swap_in(slot, req, alloc)
        self.metrics.note_admission(req.prompt_len, alloc.reused_len,
                                    host_pages=len(alloc.swap_ins or ()),
                                    table_pages=len(alloc.pages),
                                    run_pages=alloc.run_pages)
        self._set_page_gauges()
        if req.trace_sampled:
            # the queue-wait span is only known in retrospect: it closes
            # the moment admission happens
            record_span("serving.queue_wait", req.submitted_at,
                        req.admitted_at, trace=req.trace_id,
                        parent=req.span_id, tenant=req.tenant)
        # (a state pool's admit takes the slot's entry in the reused
        # length's place: there is no prefix to reuse, and the entry is
        # what it zeroes)
        tail = (jnp.int32(slot.index), key_raw, jnp.float32(req.temperature),
                jnp.int32(alloc.pages[0]
                          if self._cache_spec is self._state_spec
                          else alloc.reused_len))
        if self._spec:
            slot.draft_done = 0
            args = (self.cache, self._slot_keys, self._temps,
                    self._draft_cache.lengths) + tail
        else:
            args = (self.cache, self._slot_keys, self._temps) + tail
        out = self._dispatch(
            "admit", self._admit_p, args, self.cache,
            self._request_span("serving.admit", req, slot=slot.index,
                               reused_len=alloc.reused_len), timed=False)
        self.cache, self._slot_keys, self._temps = out[:3]
        if self._spec:
            self._draft_cache = dataclasses.replace(
                self._draft_cache, lengths=out[3])
        if self.on_admit is not None:
            self.on_admit(slot, req)

    def _run_draft_chunk(self, slot: Slot, upto: int) -> None:
        """One draft-model prefill chunk over [draft_done, upto). Capped
        at `upto` (the target's prompt_done) so a catch-up over a reused
        prefix lands EXACTLY where the target sits and the two then
        advance over identical windows."""
        chunk = self.engine_config.prefill_chunk
        req = slot.request
        with _phase("serving.stage_inputs", h2d_bytes=4 * chunk + 8):
            start = slot.draft_done
            real = min(chunk, upto - start)
            ids = np.zeros((chunk,), np.int32)
            ids[:real] = req.prompt[start:start + real]
            args = (self._draft_params, self._draft_cache,
                    jnp.int32(slot.index), ids, jnp.int32(real))
        self._draft_cache = self._dispatch(
            "draft_prefill", self._draft_prefill_p, args, self._draft_cache,
            self._request_span("serving.draft_prefill", req, slot=slot.index,
                               chunk_start=start, chunk_tokens=real))
        slot.draft_done += real

    def _run_prefill_chunk(self, slot: Slot) -> None:
        chunk = self.engine_config.prefill_chunk
        req = slot.request
        if self._spec and slot.draft_done < slot.prompt_done:
            # the draft has no cached prefix to reuse: draft-only
            # catch-up chunks rebuild its prompt state up to the
            # target's reused length before the joint chunks begin.
            # NOT counted in prefill_chunks: that counter prices TARGET
            # prefill work (goodput multiplies it by the target prefill
            # program's device time, and the prefix-reuse A/B compares
            # it) — a draft-sized catch-up chunk is neither
            self._run_draft_chunk(slot, slot.prompt_done)
            return
        row = self._table[slot.index]
        with _phase("serving.stage_inputs",
                    h2d_bytes=row.nbytes + 4 * chunk + 8):
            start = slot.prompt_done  # includes the reused prefix on a hit
            real = min(chunk, req.prompt_len - start)
            ids = np.zeros((chunk,), np.int32)
            ids[:real] = req.prompt[start:start + real]
            args = (self.params, self.cache, self._tokens, self._slot_keys,
                    self._temps, jnp.int32(slot.index),
                    self._tables(slot.index), ids, jnp.int32(real),
                    self._chunk_stats)
        self.cache, self._tokens, out, self._chunk_stats = self._dispatch(
            "prefill", self._prefill_p, args, (self.cache, self._tokens),
            self._request_span("serving.prefill", req, slot=slot.index,
                               chunk_start=start, chunk_tokens=real))
        if self._spec:
            # joint chunk: the draft processes the same window, so both
            # prompts complete on the same engine step
            self._run_draft_chunk(slot, start + real)
        with _phase("serving.commit", tokens=0, finished=0):
            self.metrics.note_prefill_chunk()
            done = self.scheduler.note_prefill_chunk(slot, real)
            if req.share_prompt:
                # fork parent: every full prompt page this chunk completed
                # becomes shareable NOW — forks queued behind us map it at
                # admission instead of re-prefilling
                self.allocator.publish_prompt(slot)
            if done:
                # the chunk that completed the prompt also produced the
                # request's first token (TTFT is measured where it is
                # committed); the slot decodes on from the register
                self._leave_unread("prefill", out, [(slot, req, ())])

    def _run_decode(self, slots: list[Slot]) -> None:
        if self._spec:
            self._run_spec_decode(slots)
            return
        num_slots = self.engine_config.num_slots
        with _phase("serving.stage_inputs",
                    h2d_bytes=self._table.nbytes + num_slots):
            live = np.zeros((num_slots,), bool)
            for s in slots:
                live[s.index] = True
            args = (self.params, self.cache, self._tokens, self._slot_keys,
                    self._temps, live, self._tables())
            links = self._step_links(slots)
        self.cache, self._tokens, _ = self._dispatch(
            "decode", self._decode_p, args, (self.cache, self._tokens),
            span("serving.decode", links=links),
            unread=[(s, s.request, s.index) for s in slots])
        self.metrics.note_decode_step(
            "kernel" if self._use_paged_kernel else "dense")

    def _run_spec_decode(self, slots: list[Slot]) -> None:
        """One speculative step for every decoding slot: draft K
        proposals per slot, verify them in ONE batched K-token target
        forward, commit the accepted prefix (plus the correction token)
        — between 1 and K tokens land per slot per step. The draft's
        cache adopts the verified lengths afterwards: by construction
        its valid rows are exactly the target's (inputs t0..d_{c-1}), so
        the two models stay position-synchronized without a catch-up."""
        K = self.engine_config.draft_k
        num_slots = self.engine_config.num_slots
        with _phase("serving.stage_inputs", h2d_bytes=0):
            live = np.zeros((num_slots,), bool)
            for s in slots:
                live[s.index] = True
            links = self._step_links(slots)
            dargs = (self._draft_params, self._draft_cache, self._tokens,
                     self._slot_keys, self._temps)
        d_toks, d_logits, new_dcache = self._dispatch(
            "draft", self._draft_p, dargs, self._draft_cache,
            span("serving.draft", links=links))
        with _phase("serving.stage_inputs",
                    h2d_bytes=self._table.nbytes + num_slots):
            vargs = (self.params, self.cache, self._tokens, self._slot_keys,
                     self._temps, live, self._table, d_toks, d_logits)
        (self.cache, self._tokens, committed, counts, n_acc,
         lps) = self._dispatch(
            "verify", self._verify_p, vargs, (self.cache, self._tokens),
            span("serving.verify", links=links))
        # the draft cache's valid rows now equal the target's: adopt the
        # committed lengths (rejected proposals' draft rows fall past the
        # length, masked exactly like the target's rejected rows) — but
        # for LIVE lanes only. A non-live slot holding a request is
        # mid-PREFILL, where the draft lags the target (prefix hits start
        # the target at the reused length while the draft rebuilds from
        # zero): adopting the target's length there would shift every
        # later catch-up write onto wrong rows/positions and silently
        # corrupt that request's draft state. Its true progress is the
        # host-tracked draft_done; idle lanes reset at admit, so 0 is
        # fine. The draft program advanced every lane by K regardless —
        # dead lanes' stray rows sit at/past the restored length and are
        # masked or overwritten. jnp.where yields a FRESH buffer, so the
        # pool's lengths never alias into the draft cache (the next
        # donating dispatch must not see one buffer through two args).
        with _phase("serving.commit", tokens=0, finished=0):
            restore = np.zeros((num_slots,), np.int32)
            for s in self.scheduler.slots:
                if s.request is not None and not live[s.index]:
                    restore[s.index] = s.draft_done
            self._draft_cache = dataclasses.replace(
                new_dcache, lengths=jnp.where(jnp.asarray(live),
                                              self.cache.lengths,
                                              jnp.asarray(restore)))
        with _phase("serving.host_read", program="verify"):
            toks = np.asarray(committed)   # [S, K] — the per-step host read
            cnts = np.asarray(counts)
            accs = np.asarray(n_acc)
            lps = np.asarray(lps)
        with _phase("serving.commit") as sp:
            self.timer.tick(block_on=None)
            self.metrics.note_decode_step("speculative")
            tokens = finished = 0
            for s in slots:
                self.metrics.note_speculation(K, int(accs[s.index]))
                req = s.request
                for j in range(int(cnts[s.index])):
                    tokens += 1
                    if self.scheduler.note_token(
                            s, int(toks[s.index, j]),
                            logprob=float(lps[s.index, j])):
                        # retired mid-window (budget or EOS): the remaining
                        # committed tokens are discarded — their rows sit
                        # past the slot's final length in reserved private
                        # pages and are never attended
                        self._finalize_request(req)
                        finished += 1
                        break
            sp.set(tokens=tokens, finished=finished)

    # -- request tracing -----------------------------------------------------

    @staticmethod
    def _step_links(slots: list[Slot]) -> list | None:
        """One decode step serves EVERY live slot, so its dispatch span
        belongs to no single request: span LINKS carry each sampled
        request's trace id instead (bounded by num_slots). Built only
        while tracing is on: the step pays nothing for it otherwise."""
        if not tracing_enabled():
            return None
        return [s.request.trace_id for s in slots
                if s.request is not None and s.request.trace_sampled] or None

    @staticmethod
    def _request_span(name: str, req: Request, **attrs):
        """A live span joined to the request's trace when it is sampled,
        the plain engine-wide span otherwise (engine-level spans predate
        request tracing and must keep recording for unsampled traffic)."""
        if req.trace_sampled:
            return span(name, trace=req.trace_id, parent=req.span_id,
                        **attrs)
        return span(name, **attrs)

    def _trace_terminal(self, req: Request) -> None:
        """Close the request's retrospective spans at its terminal state
        (the shared `close_request_trace` path — the pod router closes its
        requests through the same helper)."""
        end = req.finished_at
        if end is None:
            end = self._clock()
        close_request_trace(req, end)

    def _finalize_request(self, req: Request) -> None:
        """The one terminal path: close the request's trace, then fold it
        into the metrics (TTFT/per-token exemplars carry the trace id)."""
        # a terminal fork parent releases any held children (the hold
        # predicate also checks req.done — this just bounds the map)
        self._fork_parents.pop(req.request_id, None)
        self._trace_terminal(req)
        self.metrics.observe_request(req)

    # -- live introspection (the /debug endpoints read these) ----------------

    @staticmethod
    def _request_info(req: Request, now: float) -> dict:
        info = {
            "request_id": req.request_id,
            "trace_id": req.trace_id,
            "tenant": req.tenant,
            "status": req.status.value,
            "prompt_len": req.prompt_len,
            "max_new_tokens": req.max_new_tokens,
            "tokens": len(req.tokens),
            "age_s": round(now - req.submitted_at, 6),
        }
        if req.ttft_s is not None:
            info["ttft_s"] = round(req.ttft_s, 6)
        if req.slo_ttft_s is not None:
            info["slo_ttft_s"] = req.slo_ttft_s
        if req.deadline_s is not None:
            info["deadline_s"] = req.deadline_s
        if req.parent_id is not None:
            info["forked_from"] = req.parent_id
        if req.share_prompt:
            info["fork_parent"] = True
        return info

    def debug_requests(self) -> dict:
        """In-flight request state, queued and running, each entry
        carrying its trace id — the live half of 'where did the time
        go'. Read-only and JSON-safe."""
        now = self._clock()
        return {
            "queued": [self._request_info(r, now)
                       for r in self.scheduler.queue],
            "running": [self._request_info(s.request, now)
                        for s in self.scheduler.slots
                        if s.request is not None],
        }

    def debug_slots(self) -> list[dict]:
        """Slot occupancy: state, owning request/trace, prefill progress,
        and how many pool pages each slot maps."""
        out = []
        for s in self.scheduler.slots:
            entry: dict[str, Any] = {"index": s.index,
                                     "state": s.state.value}
            if s.request is not None:
                entry.update({
                    "request_id": s.request.request_id,
                    "trace_id": s.request.trace_id,
                    "tenant": s.request.tenant,
                    "prompt_done": s.prompt_done,
                    "prompt_len": s.request.prompt_len,
                    "tokens": len(s.request.tokens),
                })
                if s.alloc is not None:
                    entry["pages"] = len(s.alloc.pages)
                    entry["reused_len"] = s.alloc.reused_len
            out.append(entry)
        return out

    def debug_pages(self) -> dict:
        """Page-pool and radix-tree state: capacity, occupancy, and the
        prefix-reuse counters (host-side totals, exact)."""
        alloc = self.allocator
        return {
            "page_size": alloc.page_size,
            "num_pages": self.cache.num_pages,
            "pages_in_use": alloc.pages_in_use,
            "pages_free": alloc.pages_free,
            "prefix_cache": alloc.prefix_cache,
            "cached_pages": alloc.index.cached_pages,
            "mapped_pages": alloc.index.mapped_pages,
            "prefix_lookups": alloc.lookups,
            "prefix_hits": alloc.hits,
            "tokens_reused": alloc.tokens_reused,
            "evictions": alloc.evictions,
            "host_pages": alloc.index.host_pages,
            **({"host_tier": self._host_tier.stats()}
               if self._host_tier is not None else {}),
            # a cache with one group a layer kind: every group's books
            # (the keys above are the first group's, which keeps every
            # position)
            **({"groups": [
                {"group": spec.label, "layers": list(spec.layers),
                 "window": spec.window, "pages_per_slot": g.pages_per_slot,
                 "num_pages": g.num_pages, "pages_in_use": used}
                for spec, g, used in zip(
                    self._cache_groups, self.cache.groups,
                    (alloc.pages_in_use, *alloc.ring_pages_in_use))]}
               if self._cache_groups else {}),
        }

    def debug_scheduler(self) -> dict:
        """The scheduler's policy state (per-tenant queues, DRR deficits,
        SLO EMA, shed counters)."""
        return self.scheduler.debug_state()

    def incident_dumps(self) -> dict:
        """Everything an incident bundle should freeze about this engine:
        the same snapshots the /debug endpoints serve, plus compile
        counts (a recompile storm is itself a finding). Per-section
        best-effort: the watchdog thread calls this while the engine may
        still be mutating (a slow stall is not a dead one), and one
        section's failure must not cost the others."""
        out: dict[str, Any] = {}
        for name, build in (
            ("requests", self.debug_requests),
            ("slots", self.debug_slots),
            ("pages", self.debug_pages),
            ("scheduler", self.debug_scheduler),
            ("compile_stats", self.compile_stats),
            ("cost_table", self.cost.snapshot),
        ):
            try:
                out[name] = build()
            except Exception as e:
                out[name] = {"error": f"{type(e).__name__}: {e}"}
        return out

    # -- metrics -------------------------------------------------------------

    def _goodput(self) -> float | None:
        """Serving goodput: estimated device seconds spent producing
        tokens that were DELIVERED, over wall-clock. Decode device time
        (sampled mean x steps) counts the fraction of slot-lanes whose
        tokens reached a finished request; prefill counts the finished
        fraction of admissions (a re-prefill after a shed never
        finishes, so it drops out). Queue waits, sheds, and idle gaps
        are excluded by construction — they ARE the gap between goodput
        and 1.0. None until a device-time sample and wall window exist;
        the serving_goodput gauge tracks the latest value."""
        m = self.metrics
        if (m.started_at is None or m.stopped_at is None
                or m.stopped_at <= m.started_at):
            return None
        wall = m.stopped_at - m.started_at
        useful = 0.0
        # the decode-role program: the speculative engine's verify step
        # IS its decode (token lanes = slots x draft_k per step)
        dec = self.cost.mean_device_time(
            "verify" if self._spec else "decode")
        steps = m.decode_steps
        lanes = self.engine_config.num_slots * (
            self.engine_config.draft_k if self._spec else 1)
        if dec is not None and steps:
            useful += dec * steps * min(1.0, m.tokens_out / (steps * lanes))
        pre = self.cost.mean_device_time("prefill")
        if pre is not None and m.prefill_chunks and m.prefix_lookups:
            useful += pre * m.prefill_chunks * min(
                1.0, m.finished / m.prefix_lookups)
        if useful <= 0.0:
            return None
        g = min(1.0, useful / wall)
        m.set_goodput(g)
        return g

    def reset_metrics(self) -> None:
        """Drop accumulated samples (e.g. after a warmup pass). Compiled
        programs, slot state, and in-flight requests are untouched. The
        registry's series objects survive (zeroed in place), so the
        Prometheus endpoint and any cached metric handles stay live."""
        self.settle()  # a read belongs to the window of its dispatch
        self.registry.reset()
        self.metrics = ServingMetrics(registry=self.registry)
        # static program costs survive a metrics reset (the compiled
        # programs didn't change) — re-set their zeroed gauges; the
        # device-time sketches restart empty with the other series
        self.cost.republish()
        self.timer = StepTimer(warmup_steps=0, registry=self.registry,
                               name="serving_step")
        # page-pool gauges reflect CURRENT state, not a window: re-sync
        # (the prefix tree and its cached pages survive a metrics reset)
        self._set_page_gauges()
        if self._host_tier is not None:
            self.metrics.set_host_tier_gauges(self._host_tier.pages_in_use,
                                              self._host_tier.bytes_in_use)
        # decode_steps restarts from 0, so the log guard must too — a stale
        # value would swallow the first post-reset log point
        self._last_logged = 0
        # a warmup pass's compile-heavy steps would otherwise keep
        # inflating the scheduler's step-time EMA (and with it every SLO
        # floor / Retry-After estimate) long into steady state
        self.scheduler.step_time_ema = 0.0

    def metrics_summary(self) -> dict[str, float]:
        """Flat serving metrics (TTFT/per-token percentiles, occupancy,
        queue depth, tokens/sec) + the StepTimer's host-overhead meters."""
        out = self.metrics.summary()
        # pool capacity next to the in-use bytes gauge: pages a fixed HBM
        # budget holds = budget / page_nbytes, which int8 pages double
        out["pages_capacity"] = float(self.cache.num_pages)
        if self.timer._dispatch_hist.count:
            out["host_dispatch_us_mean"] = self.timer.host_dispatch_us
        # roofline attribution (ISSUE 11): measured device time per
        # program + the derived MFU / HBM-bandwidth / MXU-idle numbers
        # for decode — what the chip was DOING, not just how long. On a
        # speculative engine the decode-role program is VERIFY (the
        # batched K-token target forward), so the decode_* keys read it
        # — decode_mxu_idle_fraction stays the before/after A-vs-B
        # number ISSUE 12's acceptance quotes.
        decode_prog = "verify" if self._spec else "decode"
        for prog in (decode_prog, "prefill"):
            sheet = self.cost.roofline(prog) or {}
            name = "decode" if prog == decode_prog else prog
            if "device_time_mean_s" in sheet:
                out[f"{name}_device_time_mean_ms"] = (
                    sheet["device_time_mean_s"] * 1e3)
                out[f"{name}_device_time_p99_ms"] = (
                    sheet["device_time_p99_s"] * 1e3)
            if prog == decode_prog:
                for src, dst in (("mfu", "decode_mfu"),
                                 ("mxu_idle_fraction",
                                  "decode_mxu_idle_fraction"),
                                 ("hbm_bw_util", "decode_hbm_bw_util"),
                                 ("arith_intensity",
                                  "decode_arith_intensity")):
                    if src in sheet:
                        out[dst] = float(sheet[src])
        g = self._goodput()
        if g is not None:
            out["goodput"] = g
        out.update({f"compiles_{k}": float(v)
                    for k, v in self.compile_stats().items()})
        return out

    def close(self) -> None:
        """Stop the background observability threads (exporter, watchdog).
        Idempotent; the engine itself stays usable."""
        if self.metrics_server is not None:
            self.metrics_server.stop()
            self.metrics_server = None
        if self.watchdog is not None:
            self.watchdog.stop()
            self.watchdog = None
        if self._host_tier is not None:
            self._host_tier.close()

    def _maybe_log(self) -> None:
        if not self._tracker or not self._log_every:
            return
        steps = self.metrics.decode_steps
        # decode_steps only advances on decode, but step() also fires for
        # prefill/admission — without the last-logged guard every such step
        # re-logs the same decode step (duplicate rows; strictly-increasing
        # trackers drop them)
        if steps and steps % self._log_every == 0 and steps != self._last_logged:
            self._last_logged = steps
            self._tracker.log(self.metrics_summary(), step=steps)
