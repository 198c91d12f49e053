"""The contract between `serving.Engine` and a model family
(`accelerate_tpu/models/contract.py`, docs/serving.md "What a served family
declares"): what a family declares is ONE object, `SERVING`, that the engine
reads in one place; the descriptions both layers share live BELOW both; and
the engine dispatches its programs through one helper whose spans nest as
they did before it.

Everything here is tiny and on the CPU: shapes and host-side structure, no
interpreted kernel (`paged_attention=False` wherever an engine steps)."""

import ast
import inspect
import pathlib

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import accelerate_tpu
from accelerate_tpu.models import (
    brumby,
    deepseek,
    dots3,
    gpt2,
    jamba,
    keye,
    llama,
    mellum,
)
from accelerate_tpu.models.contract import (
    CacheSpec,
    ServingContract,
    WithSide,
    kv_stack_spec,
)
from accelerate_tpu.serving import Engine, EngineConfig
from accelerate_tpu.serving import cache as serving_cache
from accelerate_tpu.serving.cache import (
    GroupedPagedCache,
    PagedKVCache,
    StateCache,
    create_cache,
    paged_decode_operands,
)
from accelerate_tpu.telemetry.trace import (
    clear_flight_recorder,
    configure_tracing,
    flight_recorder,
)

# name -> (module, its tiny config, what an engine of it cannot take)
FAMILIES = {
    "llama": (llama, llama.LlamaConfig.tiny, {}),
    "gpt2": (gpt2, gpt2.GPT2Config.tiny, {}),
    "deepseek": (deepseek, deepseek.DeepseekConfig.tiny, {}),
    "mellum": (mellum, mellum.MellumConfig.tiny, {"prefix_cache": False}),
    "keye": (keye, keye.KeyeConfig.tiny, {}),
    "brumby": (brumby, lambda: brumby.BrumbyConfig.tiny(head_dim=16),
               {"prefix_cache": False}),
    "dots3": (dots3, dots3.Dots3Config.tiny, {"prefix_cache": False}),
    "jamba": (jamba, jamba.JambaConfig.tiny, {"prefix_cache": False}),
}
DECLARING = {"deepseek", "mellum", "keye", "brumby", "dots3", "jamba"}
each_family = pytest.mark.parametrize("name", list(FAMILIES))


def _engine(family, cfg, params, **kw):
    """A tiny engine (shapes in `params`' place will do where no program
    runs: an engine keeps its params and reads them in its programs)."""
    kw = {"num_slots": 2, "max_len": 32, "prefill_chunk": 8, "page_size": 8,
          "cache_dtype": jnp.float32, "paged_attention": False, **kw}
    return Engine(family, cfg, params, EngineConfig(**kw))


# ---------------------------------------------------------------------------
# what `ServingContract.of` resolves, family by family
# ---------------------------------------------------------------------------


@each_family
def test_logit_rows_is_declared_exactly_when_forward_takes_it(name):
    module = FAMILIES[name][0]
    contract = ServingContract.of(module)
    assert contract.forward is module.forward
    assert (name in DECLARING) == hasattr(module, "SERVING")
    takes = "logit_rows" in inspect.signature(module.forward).parameters
    assert contract.logit_rows == takes
    # the counters come in whole pairs, and only a family that loops over
    # its layers in Python takes its views a layer at a time
    assert (contract.init_stats is None) == (contract.fold_stats is None)
    assert contract.layerwise_views == (
        name in {"deepseek", "mellum", "keye", "dots3", "jamba"})
    assert (contract.init_chunk_stats is not None) == (
        name in {"keye", "dots3"})
    assert (contract.count_state_zeroed is not None) == (
        name in {"brumby", "jamba"})


@each_family
def test_cache_spec_is_the_modules_or_the_kv_stack_off_the_config(name):
    module, tiny, _ = FAMILIES[name]
    cfg = tiny()
    spec = ServingContract.of(module).cache_spec(cfg)
    if hasattr(module, "cache_spec"):
        assert spec == module.cache_spec(cfg)
    else:
        assert ServingContract.of(module).cache_spec is kv_stack_spec
        kv = getattr(cfg, "num_key_value_heads", None)
        assert spec == CacheSpec(
            cfg.num_hidden_layers,
            cfg.num_attention_heads if kv is None else kv, cfg.head_dim)
    assert isinstance(spec, (CacheSpec, tuple))   # groups are a TUPLE


@each_family
def test_an_engine_of_the_module_and_one_of_its_contract_hold_equal_caches(
        name):
    module, tiny, refused = FAMILIES[name]
    cfg = tiny()
    contract = ServingContract.of(module)
    params = jax.eval_shape(
        lambda: module.init_params(cfg, jax.random.key(0)))
    engines = [_engine(family, cfg, params, **refused)
               for family in (module, contract)]
    try:
        a, b = (jax.tree.map(lambda x: (x.shape, x.dtype), e.cache)
                for e in engines)
        assert a == b
        assert type(engines[0].cache) is type(engines[1].cache)
        assert all(e._serving == contract for e in engines)
        assert ([None if e._chunk_stats is None else sorted(e._chunk_stats)
                 for e in engines][0]
                == (None if contract.init_chunk_stats is None
                    else sorted(contract.init_chunk_stats(cfg))))
    finally:
        for e in engines:
            e.close()


# ---------------------------------------------------------------------------
# what is silent no longer
# ---------------------------------------------------------------------------


def _plain_forward(config, params, ids, positions=None, kv_caches=None):
    return llama.forward(config, params, ids, positions=positions,
                         kv_caches=kv_caches)


@pytest.mark.parametrize("half", ["init_stats", "fold_stats",
                                  "init_chunk_stats", "fold_chunk_stats"])
def test_a_half_declared_pair_of_counters_raises(half):
    with pytest.raises(ValueError, match="together or not at all"):
        ServingContract(forward=deepseek.forward, logit_rows=True,
                        **{half: lambda *a: {}})


def test_logit_rows_on_a_forward_without_the_parameter_raises():
    with pytest.raises(ValueError, match="takes no `logit_rows`"):
        ServingContract(forward=_plain_forward, logit_rows=True)
    # the claim is held to the forward's own signature, not to a wrapper's
    with pytest.raises(ValueError, match="takes no `logit_rows`"):
        ServingContract(forward=lambda config, params, ids, **kw: None,
                        logit_rows=True)
    assert ServingContract(forward=deepseek.forward,
                           logit_rows=True).logit_rows


def test_a_bare_callable_is_the_default_kv_stack_around_it():
    contract = ServingContract.of(_plain_forward)
    assert contract == ServingContract(forward=_plain_forward)
    assert contract.cache_spec is kv_stack_spec and not contract.logit_rows
    assert ServingContract.of(contract) is contract
    cfg = llama.LlamaConfig.tiny()
    params = llama.init_params(cfg, jax.random.key(0))
    prompt = np.arange(1, 12, dtype=np.int32)
    served = []
    for family in (_plain_forward, llama):
        eng = _engine(family, cfg, params)
        try:
            r = eng.submit(prompt, max_new_tokens=3)
            eng.run_until_idle()
            served.append(list(r.tokens))
            assert isinstance(eng.cache, PagedKVCache)
        finally:
            eng.close()
    assert served[0] == served[1] and len(served[0]) == 3


# ---------------------------------------------------------------------------
# the layers: ops <- models <- serving
# ---------------------------------------------------------------------------


def test_nothing_under_models_or_ops_imports_the_serving_layer():
    """At any depth: a function-level import is how the cycle used to be
    hidden."""
    root = pathlib.Path(accelerate_tpu.__file__).parent
    found = []
    files = sorted((root / "models").glob("*.py")) + sorted(
        (root / "ops").glob("*.py"))
    assert len(files) > 20
    for path in files:
        for node in ast.walk(ast.parse(path.read_text())):
            names = []
            if isinstance(node, ast.ImportFrom):
                # `from ..serving.cache import x`, `from .. import serving`
                names = [node.module or ""] + [
                    f"{node.module or ''}.{a.name}" for a in node.names]
            elif isinstance(node, ast.Import):
                names = [a.name for a in node.names]
            for name in names:
                if "serving" in name.split("."):
                    found.append(f"{path.name}:{node.lineno} {name}")
    assert not found, found
    # the leaf itself imports nothing of this package
    leaf = ast.parse((root / "models" / "contract.py").read_text())
    for node in ast.walk(leaf):
        if isinstance(node, ast.ImportFrom):
            assert node.level == 0 and not node.module.startswith(
                "accelerate_tpu"), node.module
    # and the old names still resolve where tests and docs import them
    assert serving_cache.CacheSpec is CacheSpec
    assert serving_cache.WithSide is WithSide


def test_the_state_pool_is_no_ops_own():
    """`serving/cache.py` lays a state out as the family's spec says and
    imports nothing of the op that reads it: the pool a forward is handed
    is `models.contract.StatePool`, which power retention's module only
    re-exports."""
    from accelerate_tpu.models import contract
    from accelerate_tpu.ops import power_retention

    root = pathlib.Path(accelerate_tpu.__file__).parent
    for node in ast.walk(ast.parse((root / "serving" / "cache.py").read_text())):
        names = []
        if isinstance(node, ast.ImportFrom):
            names = [node.module or ""] + [a.name for a in node.names]
        elif isinstance(node, ast.Import):
            names = [a.name for a in node.names]
        assert not any("power_retention" in n or "selective_scan" in n
                       for n in names), (node.lineno, names)
    assert power_retention.StatePool is contract.StatePool
    assert power_retention.StateMeta is contract.StateMeta
    assert serving_cache.StatePool is contract.StatePool
    spec = ServingContract.of(brumby).cache_spec(brumby.BrumbyConfig.tiny())
    pool = jax.eval_shape(lambda: StateCache.create(spec, 2, 32).pool())
    assert isinstance(pool, contract.StatePool)
    assert pool.s.shape == (2, 3, 2, 65 * 128, 128)
    assert pool.z.shape == (2, 3, 2, 72, 128)      # what brumby declares


# ---------------------------------------------------------------------------
# the two decisions that moved beside the cache classes
# ---------------------------------------------------------------------------


@each_family
def test_create_cache_picks_the_class_a_spec_gets(name):
    module, tiny, _ = FAMILIES[name]
    spec = ServingContract.of(module).cache_spec(tiny())
    ec = EngineConfig(num_slots=2, max_len=32, page_size=8,
                      cache_dtype=jnp.float32)
    cache = jax.eval_shape(lambda: create_cache(spec, ec, pad_slack=8))
    want = {"mellum": GroupedPagedCache, "dots3": GroupedPagedCache,
            "jamba": GroupedPagedCache,
            "brumby": StateCache}.get(name, PagedKVCache)
    assert type(cache) is want
    first = spec[0] if isinstance(spec, tuple) else spec
    assert cache.latent == (first.kind == "latent")
    assert (cache.side is not None) == bool(first.side_width)
    if want is GroupedPagedCache:
        # one pool a PAGE group; a group of state entries stands beside
        assert [g.k.shape[0] for g in cache.groups] == [
            s.num_layers for s in spec if s.kind != "state"]
        assert (cache.state is not None) == (name == "jamba")
    else:
        assert jax.tree.leaves(cache)[0].shape[0] == spec.num_layers


@pytest.mark.parametrize("name", ["llama", "deepseek", "mellum", "keye",
                                  "dots3", "jamba"])
def test_paged_decode_operands_hands_the_pools_as_a_forward_takes_them(name):
    module, tiny, _ = FAMILIES[name]
    spec = ServingContract.of(module).cache_spec(tiny())
    ec = EngineConfig(num_slots=2, max_len=32, page_size=8,
                      cache_dtype=jnp.float32)
    cache = create_cache(spec, ec, pad_slack=8)
    k, v = paged_decode_operands(cache)
    groups = cache.groups if isinstance(spec, tuple) else (cache,)
    ks = k if isinstance(spec, tuple) else (k,)
    assert len(ks) == len(groups)
    assert (v is None) == cache.latent
    if v is not None:
        vs = v if isinstance(spec, tuple) else (v,)
        assert [p.data is g.v for p, g in zip(vs, groups)] == [True] * len(
            groups)
    for pool, group in zip(ks, groups):
        if group.side is not None:
            assert isinstance(pool, WithSide)
            assert pool.side.data is group.side
            pool = pool.rows
        # the WHOLE pool, the very array: nothing is gathered or copied
        assert pool.is_paged_kv and pool.data is group.k
        assert pool.scales is group.k_scale


# ---------------------------------------------------------------------------
# one dispatch helper: the spans nest as the six sites had them
# ---------------------------------------------------------------------------

# what `record_span` writes in retrospect, on the request's clock: they
# enclose nothing
_RETROSPECTIVE = {"serving.request", "serving.decode_lifetime",
                  "serving.queue_wait"}


def _nesting(events):
    """({(span, the parent it NAMES or None, the live span open around it
    or None)}, {the spans of one engine step, in order})."""
    by_id = {e["span_id"]: e["name"] for e in events}
    ours = [e for e in events if e["name"].startswith("serving.")]
    live = sorted((e for e in ours if e["name"] not in _RETROSPECTIVE),
                  key=lambda e: (e["start_ns"], -e["dur_ns"]))
    found = set()
    for e in ours:
        inside = None
        if e["name"] not in _RETROSPECTIVE:
            start, end = e["start_ns"], e["start_ns"] + e["dur_ns"]
            around = [p for p in live if p is not e
                      and p["start_ns"] <= start
                      and end <= p["start_ns"] + p["dur_ns"]]
            if around:
                inside = min(around, key=lambda p: p["dur_ns"])["name"]
        found.add((e["name"][8:], (by_id.get(e["parent_id"]) or "")[8:]
                   or None, inside and inside[8:]))
    steps, step = set(), []
    for e in live:
        step.append(e["name"][8:])
        if step[-1] == "bookkeeping":    # a step's last phase
            steps.add(" ".join(step))
            step = []
    return found, steps


def _traced_session(family, cfg, **kw):
    """Three requests over two slots (the third is admitted from `step()`,
    not from its `submit`), traced."""
    eng = _engine(family, cfg, family.init_params(cfg, jax.random.key(0)),
                  **kw)
    configure_tracing(True, annotate=False)
    clear_flight_recorder()
    try:
        reqs = [eng.submit(np.arange(lo, hi, dtype=np.int32),
                           max_new_tokens=new)
                for lo, hi, new in ((1, 12, 3), (3, 9, 2), (5, 10, 2))]
        eng.run_until_idle()
        events = flight_recorder()
    finally:
        configure_tracing(False)
        clear_flight_recorder()
        eng.close()
    assert [len(r.tokens) for r in reqs] == [3, 2, 2]
    return _nesting(events)


# (span, the parent it names, the live span around it), as the parent tree
# of PR 47 recorded them for the same sessions
_COMMON = {
    ("admit", "request", "admit_pending"),
    ("admit_pending", None, "submit"),
    ("admit_pending", None, None),
    ("bookkeeping", None, None),
    ("commit", None, None),
    ("decode_lifetime", "request", None),
    ("host_read", None, None),
    ("kv.allocate", "admit_pending", "admit_pending"),
    ("kv.release", "commit", "commit"),
    ("prefill", "request", None),
    ("queue_wait", "request", None),
    ("request", None, None),
    ("schedule", None, None),
    ("stage_inputs", None, None),
    ("submit", "request", None),
}
_CLASSIC = _COMMON | {("decode", None, None)}
_CLASSIC_STEPS = {
    "submit admit_pending kv.allocate admit submit admit_pending "
    "kv.allocate admit submit admit_pending admit_pending schedule "
    "stage_inputs prefill commit bookkeeping",
    "admit_pending schedule stage_inputs prefill commit bookkeeping",
    "admit_pending schedule stage_inputs decode host_read commit "
    "bookkeeping",
    "admit_pending schedule stage_inputs prefill commit host_read commit "
    "bookkeeping",
    "admit_pending schedule host_read commit kv.release kv.release "
    "bookkeeping",
    "admit_pending kv.allocate admit schedule stage_inputs prefill commit "
    "bookkeeping",
    "schedule stage_inputs decode host_read commit bookkeeping",
    "schedule host_read commit kv.release bookkeeping",
}
_SPECULATIVE = _COMMON | {("draft", None, None), ("verify", None, None),
                          ("draft_prefill", "request", None)}
_SPECULATIVE_STEPS = {
    "submit admit_pending kv.allocate admit submit admit_pending "
    "kv.allocate admit submit admit_pending admit_pending schedule "
    "stage_inputs prefill stage_inputs draft_prefill commit bookkeeping",
    "admit_pending schedule stage_inputs prefill stage_inputs "
    "draft_prefill commit host_read commit bookkeeping",
    "admit_pending schedule stage_inputs draft stage_inputs verify commit "
    "host_read commit bookkeeping",
    "admit_pending schedule stage_inputs draft stage_inputs verify commit "
    "host_read commit kv.release kv.release bookkeeping",
    "admit_pending kv.allocate admit schedule stage_inputs prefill "
    "stage_inputs draft_prefill commit host_read commit bookkeeping",
    "schedule stage_inputs draft stage_inputs verify commit host_read "
    "commit kv.release bookkeeping",
}


@pytest.mark.parametrize("name", ["llama", "deepseek"])
def test_the_spans_of_a_traced_session_nest_as_they_did(name):
    module, tiny, _ = FAMILIES[name]
    found, steps = _traced_session(module, tiny())
    assert found == _CLASSIC
    assert steps == _CLASSIC_STEPS


def test_the_spans_of_a_speculative_session_nest_as_they_did():
    cfg = llama.LlamaConfig.tiny()
    draft = llama.init_params(cfg, jax.random.key(5))
    found, steps = _traced_session(llama, cfg, speculative=(llama, cfg, draft),
                                   draft_k=2)
    assert found == _SPECULATIVE
    assert steps == _SPECULATIVE_STEPS
