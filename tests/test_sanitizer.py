"""Serving-state sanitizer (ISSUE 13): the runtime half of the ATP2xx
lifecycle audit.

The suite-wide half of the acceptance lives in conftest.py — every
engine tier-1 builds runs with ACCELERATE_TPU_SANITIZE=1, so the whole
serving/speculative/pod surface is a sanitizer pass. This module proves
the sanitizer itself: deliberately corrupted engines FIRE with a
message naming the broken invariant, compile counts stay flat with the
checks on, the config/env resolution works, the pod router's joins are
covered, and a violation writes an incident bundle before propagating.
"""

import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from accelerate_tpu.models import gpt2
from accelerate_tpu.serving import (
    Engine,
    EngineConfig,
    RequestStatus,
    SanitizerViolation,
)
from accelerate_tpu.serving.sanitizer import resolve_sanitize


@pytest.fixture(scope="module", autouse=True)
def _persistent_compile_cache(tmp_path_factory):
    from accelerate_tpu.utils.environment import configure_compilation_cache

    prev = os.environ.get("ACCELERATE_TPU_COMPILATION_CACHE_MIN_COMPILE_SECS")
    os.environ.setdefault(
        "ACCELERATE_TPU_COMPILATION_CACHE_MIN_COMPILE_SECS", "0")
    configure_compilation_cache(
        str(tmp_path_factory.mktemp("xla_cache")), force=True)
    yield
    # scoped: hand the process back with caching OFF — a later module that
    # re-traces an AOT-compiled train step would deserialize a threshold-0
    # entry from this dir and segfault jaxlib (ISSUE 16 hit this the moment
    # an engine module sorted before test_launched_scripts)
    if prev is None:
        os.environ.pop(
            "ACCELERATE_TPU_COMPILATION_CACHE_MIN_COMPILE_SECS", None)
    configure_compilation_cache("off", force=True)


@pytest.fixture(scope="module")
def gpt2_setup():
    cfg = gpt2.GPT2Config.tiny()
    params = gpt2.init_params(cfg, jax.random.key(0))
    return cfg, params


def _engine(cfg, params, **overrides):
    defaults = dict(num_slots=2, max_len=64, prefill_chunk=8, page_size=8,
                    cache_dtype=jnp.float32, sanitize=True)
    defaults.update(overrides)
    return Engine(gpt2, cfg, params, EngineConfig(**defaults))


def _prompt(rng, n, vocab):
    return rng.integers(0, vocab, (n,)).astype(np.int32)


def _serve_one(eng, cfg, seed=0, n=9, budget=3):
    rng = np.random.default_rng(seed)
    r = eng.submit(_prompt(rng, n, cfg.vocab_size), max_new_tokens=budget)
    eng.run_until_idle()
    assert r.status is RequestStatus.FINISHED
    return r


# ---------------------------------------------------------------------------
# config / env resolution
# ---------------------------------------------------------------------------


def test_resolve_sanitize_explicit_beats_env(monkeypatch):
    monkeypatch.setenv("ACCELERATE_TPU_SANITIZE", "1")
    assert resolve_sanitize(None) is True
    assert resolve_sanitize(False) is False
    monkeypatch.setenv("ACCELERATE_TPU_SANITIZE", "")
    assert resolve_sanitize(None) is False
    assert resolve_sanitize(True) is True


def test_sanitize_false_really_disables(gpt2_setup):
    cfg, params = gpt2_setup
    eng = _engine(cfg, params, sanitize=False)
    _serve_one(eng, cfg)
    eng._table[0, 0] = 0          # idle rows must be trash — corruption
    assert eng.step() is False    # no check, no raise


# ---------------------------------------------------------------------------
# the corrupted-pool proofs: each invariant fires with a useful message
# ---------------------------------------------------------------------------


def test_fires_on_stale_idle_table_row(gpt2_setup):
    cfg, params = gpt2_setup
    eng = _engine(cfg, params)
    _serve_one(eng, cfg)
    eng._table[0, 0] = 0          # a retired lane's row points at page 0
    with pytest.raises(SanitizerViolation) as ei:
        eng.step()
    assert ei.value.check == "table"
    assert "trash" in str(ei.value)
    assert ei.value.details["slot"] == 0


def test_fires_on_free_list_duplicate(gpt2_setup):
    cfg, params = gpt2_setup
    eng = _engine(cfg, params)
    _serve_one(eng, cfg)
    free = eng.allocator.pool._free
    free.append(free[0])          # one page, two free-list entries
    with pytest.raises(SanitizerViolation) as ei:
        eng.step()
    assert ei.value.check == "page-conservation"
    assert "duplicate" in str(ei.value)


def test_fires_on_refcount_corruption(gpt2_setup):
    cfg, params = gpt2_setup
    eng = _engine(cfg, params)
    _serve_one(eng, cfg, n=17)    # retirement caches 2 full prompt pages
    index = eng.allocator.index
    assert index.cached_pages >= 1
    node = next(iter(index.root.children.values()))
    node.refcount += 1            # phantom mapping: nobody holds this
    with pytest.raises(SanitizerViolation) as ei:
        eng.step()
    assert ei.value.check == "refcount"
    assert ei.value.details["page"] == node.page


@pytest.mark.parametrize("fault", ["lost", "stray", "unbounded"])
def test_fires_on_eviction_candidates_out_of_step(gpt2_setup, fault):
    """The prefix index keeps its eviction candidates standing in a heap
    (PR 41): a transition that does not reach it is silent until some
    later admission evicts the wrong page or finds the heap dry."""
    cfg, params = gpt2_setup
    eng = _engine(cfg, params)
    _serve_one(eng, cfg, n=17)    # retirement caches 2 full prompt pages
    index = eng.allocator.index
    leaf = next(iter(index.root.children.values()))
    leaf = next(iter(leaf.children.values()))
    assert index._evictable(leaf) and [e[3] for e in index._lru] == [leaf]
    if fault == "lost":
        index._lru.clear()        # the leaf's release never reached it
        words = "no live entry"
    elif fault == "stray":
        # a detached page that still passes for attached
        ghost = type(leaf)(b"ghost", 0, leaf)
        index._lru.append((ghost.last_used, ghost.page, 0, ghost))
        words = "not evictable leaves"
    else:
        index._lru.extend([(0, -1, -i, leaf)
                           for i in range(index.lru_bound())])
        words = "outgrew its bound"
    with pytest.raises(SanitizerViolation) as ei:
        eng.step()
    assert ei.value.check == "eviction-candidates"
    assert words in str(ei.value)


def test_fires_on_lost_page(gpt2_setup):
    """A page missing from free+tree+slots entirely (the classic leak
    end-state) breaks conservation."""
    cfg, params = gpt2_setup
    eng = _engine(cfg, params)
    _serve_one(eng, cfg)
    eng.allocator.pool._free.pop()        # a page vanishes
    with pytest.raises(SanitizerViolation) as ei:
        eng.step()
    assert ei.value.check == "page-conservation"
    assert "lost or double-counted" in str(ei.value)


def test_fires_on_scheduler_book_corruption(gpt2_setup):
    cfg, params = gpt2_setup
    eng = _engine(cfg, params, num_slots=1, max_queue=4)
    rng = np.random.default_rng(3)
    r1 = eng.submit(_prompt(rng, 9, cfg.vocab_size), max_new_tokens=20)
    r2 = eng.submit(_prompt(rng, 9, cfg.vocab_size), max_new_tokens=2)
    assert r2.status is RequestStatus.QUEUED
    r2.status = RequestStatus.RUNNING     # a queued request claims RUNNING
    with pytest.raises(SanitizerViolation) as ei:
        eng.step()
    assert ei.value.check == "scheduler-books"
    assert ei.value.details["request_id"] == r2.request_id
    # un-corrupt so the engine can drain (suite hygiene)
    r2.status = RequestStatus.QUEUED
    eng.cancel(r1)
    eng.cancel(r2)


# ---------------------------------------------------------------------------
# the two-tier (ISSUE 16) joins: host residency vs the tier's mirror
# ---------------------------------------------------------------------------


def _host_tier_engine(cfg, params, rng, serves=2):
    """An engine with host-resident radix nodes: one prompt cached, then
    churned out to the tier. Two serves are the cheapest churn that
    leaves a host-resident node; serves=3 builds a deeper host chain
    (a parent->child pair) for the suffix-property test."""
    eng = _engine(cfg, params, page_size=4, num_pages=18,
                  host_tier_bytes=1 << 28)
    for _ in range(serves):
        r = eng.submit(_prompt(rng, 33, cfg.vocab_size), max_new_tokens=2)
        eng.run_until_idle()
        assert r.status is RequestStatus.FINISHED
    assert eng.allocator.index.host_pages > 0
    return eng


def test_fires_on_host_node_without_mirror(gpt2_setup):
    """A host-resident node whose tier entry vanished is a prefix whose
    bytes are GONE — a hit would install garbage."""
    cfg, params = gpt2_setup
    eng = _host_tier_engine(cfg, params, np.random.default_rng(20))
    node = next(iter(eng._host_tier._entries))
    del eng._host_tier._entries[node]
    with pytest.raises(SanitizerViolation) as ei:
        eng.step()
    assert ei.value.check == "page-conservation"
    assert "mirror" in str(ei.value)
    eng.close()


def test_fires_on_host_node_claiming_hbm_page(gpt2_setup):
    """A host-resident node still naming an HBM page double-owns it —
    the residency flip and the page release must be atomic."""
    cfg, params = gpt2_setup
    eng = _host_tier_engine(cfg, params, np.random.default_rng(21))
    node = next(iter(eng._host_tier._entries))
    node.page = 0
    with pytest.raises(SanitizerViolation) as ei:
        eng.step()
    assert ei.value.check == "page-conservation"
    assert "host-resident" in str(ei.value)
    eng.close()


def test_fires_on_host_pages_counter_drift(gpt2_setup):
    cfg, params = gpt2_setup
    eng = _host_tier_engine(cfg, params, np.random.default_rng(22))
    eng.allocator.index.host_pages += 1
    with pytest.raises(SanitizerViolation) as ei:
        eng.step()
    assert ei.value.check == "page-conservation"
    assert "host_pages" in str(ei.value)
    eng.close()


def test_fires_on_hbm_child_under_host_parent(gpt2_setup):
    """Residency must be a suffix property along any root path —
    eviction drains leaf-first, so an HBM node under a host parent
    means the eviction order was violated."""
    cfg, params = gpt2_setup
    eng = _host_tier_engine(cfg, params, np.random.default_rng(23), serves=3)
    node = next(n for n in eng._host_tier._entries if n.children)
    child = next(iter(node.children.values()))
    assert child.residency == "host"
    # fake an HBM child: give it a page the sanitizer can see
    eng._host_tier.discard(child)
    child.residency = "hbm"
    child.page = eng.allocator.pool._free[0]
    with pytest.raises(SanitizerViolation) as ei:
        eng.step()
    assert ei.value.check == "page-conservation"
    eng.close()


# ---------------------------------------------------------------------------
# acceptance pins: host-side only, compile counts flat, PR 12 surface
# ---------------------------------------------------------------------------


def test_compile_counts_flat_with_sanitizer_on(gpt2_setup):
    """The sanitizer is host-side only: driving mixed waves (cold, hot
    prefix hit, sampled) with sanitize=True compiles each program
    exactly once — same pin as the classic guard."""
    cfg, params = gpt2_setup
    eng = _engine(cfg, params, sanitize=True)
    rng = np.random.default_rng(5)
    shared = _prompt(rng, 18, cfg.vocab_size)
    for temp in (0.0, 0.9):
        reqs = [eng.submit(np.concatenate(
                    [shared, _prompt(rng, 2 + i, cfg.vocab_size)]),
                    max_new_tokens=3, temperature=temp)
                for i in range(2)]
        eng.run_until_idle()
        assert all(r.status is RequestStatus.FINISHED for r in reqs)
    assert eng.compile_stats() == {"admit": 1, "prefill": 1, "decode": 1}
    assert eng.metrics.prefix_hits >= 1


def test_fork_and_speculative_run_sanitized(gpt2_setup):
    """The PR 12 surface under explicit sanitize=True: a COW fork
    fan-out with a mid-flight parent cancel, and a speculative engine's
    accept/rollback paths, both complete with the checks on every
    step."""
    cfg, params = gpt2_setup
    eng = _engine(cfg, params, max_len=96, sanitize=True)
    rng = np.random.default_rng(6)
    prompt = _prompt(rng, 24, cfg.vocab_size)
    parent = eng.submit(prompt, max_new_tokens=6, temperature=0.7,
                        key=np.array([1, 0], np.uint32))
    forks = [eng.fork(parent, key=np.array([1, i + 1], np.uint32))
             for i in (1, 2)]
    while len(parent.tokens) < 2:
        eng.step()
    assert eng.cancel(parent)
    eng.run_until_idle()
    assert all(f.status is RequestStatus.FINISHED for f in forks)
    assert eng.allocator.index.mapped_pages == 0

    spec = _engine(cfg, params, sanitize=True,
                   speculative=(gpt2, cfg, params), draft_k=3)
    r = spec.submit(_prompt(rng, 9, cfg.vocab_size), max_new_tokens=6)
    spec.run_until_idle()
    assert r.status is RequestStatus.FINISHED
    assert len(r.tokens) == 6


# ---------------------------------------------------------------------------
# pod router joins
# ---------------------------------------------------------------------------


def test_router_fires_on_stale_admit_snapshot(gpt2_setup):
    from accelerate_tpu.serving.pod import PodEngine

    cfg, params = gpt2_setup
    pod = PodEngine(gpt2, cfg, params,
                    EngineConfig(num_slots=2, max_len=64, prefill_chunk=8,
                                 cache_dtype=jnp.float32, sanitize=True))
    rng = np.random.default_rng(7)
    r = pod.submit(_prompt(rng, 9, cfg.vocab_size), max_new_tokens=3)
    pod.run_until_idle()
    assert r.status is RequestStatus.FINISHED
    # a snapshot entry whose internal is long gone: the leak class the
    # worker-books join exists for (the snapshots live on the worker
    # that took them; the router pumps it in its own step)
    pod.workers[0].local._admit_pages[123456] = [0, 1]
    with pytest.raises(SanitizerViolation) as ei:
        pod.step()
    assert ei.value.check == "worker-books"
    assert "snapshot" in str(ei.value)


# ---------------------------------------------------------------------------
# incident-bundle attachment
# ---------------------------------------------------------------------------


def test_violation_writes_incident_bundle(gpt2_setup, tmp_path):
    from accelerate_tpu.telemetry.watchdog import (
        list_incident_bundles,
        load_incident_bundle,
    )

    cfg, params = gpt2_setup
    eng = _engine(cfg, params, incident_dir=str(tmp_path))
    _serve_one(eng, cfg)
    eng._table[0, 0] = 0
    with pytest.raises(SanitizerViolation):
        eng.step()
    bundles = list_incident_bundles(str(tmp_path))
    assert bundles, "a sanitizer violation must leave an incident bundle"
    bundle = load_incident_bundle(bundles[-1]["path"])
    report = bundle.get("report", bundle)
    text = str(report)
    assert "table" in text and "sanitizer" in text.lower()
