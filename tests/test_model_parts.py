"""One vocabulary of parts through every program the benchmark times
(`models/common.py` `PARTS`): every heavy operation of the four served
families' engine programs and of the train step carries a part in its
`op_name`, the backward's through `transpose(jvp(...))`; and a scope is
metadata only: the programs compile to the same HLO with the scopes
taken away."""

import contextlib
import re

import jax
import jax.numpy as jnp
import numpy as np
import optax
import pytest

from accelerate_tpu.models import (
    brumby,
    common,
    deepseek,
    dots3,
    jamba,
    keye,
    llama,
    mellum,
)
from accelerate_tpu.serving import Engine, EngineConfig
from chipbench.harness import trace_scopes

HEAVY = re.compile(r"[\]})] (dot|convolution|custom-call|while)\(")
OP_NAME = re.compile(r'op_name="([^"]*)"')
# an instruction's metadata, and the tables of source locations it indexes
METADATA = re.compile(
    r', metadata=\{(?:[^}"]|"[^"]*")*\}'
    r"|^(?:FileNames|FunctionNames|FileLocations|StackFrames)\n(?:.+\n)*",
    re.M)

FAMILIES = {
    "llama": (llama, llama.LlamaConfig.tiny, dict(
        num_slots=2, max_len=32, prefill_chunk=8)),
    "deepseek": (deepseek, deepseek.DeepseekConfig.tiny, dict(
        num_slots=2, max_len=32, prefill_chunk=8, page_size=8)),
    "mellum": (mellum, mellum.MellumConfig.tiny, dict(
        num_slots=2, max_len=64, prefill_chunk=16, page_size=8,
        prefix_cache=False)),
    "keye": (keye, keye.KeyeConfig.tiny, dict(
        num_slots=2, max_len=64, prefill_chunk=16, page_size=16)),
    "brumby": (brumby, lambda: brumby.BrumbyConfig.tiny(head_dim=16), dict(
        num_slots=2, max_len=64, prefill_chunk=16, num_pages=2,
        prefix_cache=False)),
    "dots3": (dots3, lambda: dots3.Dots3Config.tiny(experts_held=(2, 4)),
              dict(num_slots=2, max_len=64, prefill_chunk=16, page_size=16,
                   prefix_cache=False)),
    "jamba": (jamba, jamba.JambaConfig.tiny, dict(
        num_slots=2, max_len=64, prefill_chunk=16, page_size=16,
        prefix_cache=False)),
}


def _programs(name, **engine):
    """The family's tiny engine's `prefill` and `decode`, each with the
    arguments the engine itself would call it with."""
    family, tiny, shape = FAMILIES[name]
    cfg = tiny()
    engine = dict(dict(shape, cache_dtype=jnp.float32,
                       paged_attention=False), **engine)
    eng = Engine(family, cfg, family.init_params(cfg, jax.random.key(0)),
                 EngineConfig(**engine))
    state = (eng.params, eng.cache, eng._tokens, eng._slot_keys, eng._temps)
    chunk = eng.engine_config.prefill_chunk
    return {
        "prefill": (eng._prefill_p, state + (
            jnp.int32(0), eng._tables(0), np.zeros((chunk,), np.int32),
            jnp.int32(chunk))),
        "decode": (eng._decode_p, state + (
            np.ones((eng.engine_config.num_slots,), bool), eng._tables())),
    }


# what the compiler turns into a dot, a convolution, a custom call or a loop
HEAVY_PRIMITIVES = {"dot_general", "ragged_dot", "ragged_dot_general",
                    "conv_general_dilated", "pallas_call", "top_k", "sort",
                    "while", "scan"}


def _traced_ops(jaxpr, prefix=""):
    """(primitive, name stack) of every equation of a traced program, the
    equations of what it calls and loops over included, each under the
    names of the calls around it: what the compiler is handed as an
    instruction's `op_name`."""
    for eqn in jaxpr.eqns:
        name = "/".join(x for x in (prefix, str(eqn.source_info.name_stack))
                        if x)
        yield eqn.primitive.name, name
        for inner in jax.core.jaxprs_in_params(eqn.params):
            yield from _traced_ops(inner, name)


def _heavy_ops(fn, args):
    """(opcode, op_name) of every heavy operation: of the TRACED program,
    and of the COMPILED one's dots, convolutions, custom calls and loops
    that still have an `op_name` (the CPU's compiler rewrites a batched dot
    into a new instruction without metadata; what the chip's compiler
    keeps is read on the chip, from a trace's `(unscoped)`)."""
    traced = [(p, n) for p, n in _traced_ops(jax.make_jaxpr(fn)(*args).jaxpr)
              if p in HEAVY_PRIMITIVES]
    compiled = []
    for line in fn.lower(*args).compile().as_text().splitlines():
        m, name = HEAVY.search(line), OP_NAME.search(line)
        if m and name:
            compiled.append((m.group(1), name.group(1)))
    assert len(traced) > 5 and len(compiled) > 5
    return traced + compiled


def _unbilled(heavy):
    """The heavy operations no part claims. A loop over LAYERS holds every
    part and is none of them: its own (self) time is the loop's control,
    and its body's operations are held to the rule one by one."""
    missing = []
    for opcode, name in heavy:
        if trace_scopes.part_of(name) != trace_scopes.UNSCOPED:
            continue
        if opcode in ("while", "scan") and len({
                trace_scopes.part_of(n) for _, n in heavy
                if not name or n.startswith(name + "/")}
                - {trace_scopes.UNSCOPED}) > 1:
            continue
        missing.append((opcode, name))
    return missing


def test_the_benchmark_reads_the_programs_own_vocabulary():
    assert trace_scopes.PARTS == common.PARTS
    assert len(set(common.PARTS)) == len(common.PARTS)


def test_a_name_outside_the_vocabulary_raises():
    with pytest.raises(ValueError, match="not a part"):
        common.part("mla.absorb")
    with common.part("attn.project"):
        pass

    @common.part("cache.write")
    def decorated(x):
        return x + 1

    text = jax.jit(decorated).lower(jnp.zeros((4,))).as_text(
        debug_info=True)
    assert "cache.write" in text

    @common.part("cache.view")
    def recursive(x, depth):
        return x * 2 if depth == 0 else recursive(x, depth - 1) + 1

    def both(x):
        return recursive(x, 2), jnp.sin(x)

    text = jax.jit(both).lower(jnp.zeros((4,))).as_text(debug_info=True)
    sine = [line for line in text.splitlines() if "sine" in line]
    assert sine and "cache.view" not in sine[0], sine


@pytest.mark.parametrize("program", ["prefill", "decode"])
@pytest.mark.parametrize("attention", ["dense", "kernel"])
@pytest.mark.parametrize("family", sorted(FAMILIES))
def test_every_heavy_operation_of_an_engine_program_has_a_part(
        family, attention, program):
    fn, args = _programs(
        family, paged_attention=attention == "kernel")[program]
    heavy = _heavy_ops(fn, args)
    assert _unbilled(heavy) == []
    parts = {trace_scopes.part_of(n) for _, n in heavy}
    assert {"attn.project", "attn.attend", "attn.output", "head"} <= parts
    assert ({"mlp"} if family in ("llama", "brumby", "jamba")
            else {"moe.experts"}) <= parts
    if family in ("keye", "dots3"):
        assert {"attn.indexer", "attn.select"} <= parts
    if family == "dots3":   # a dense first layer AND expert layers
        assert {"mlp", "moe.shared"} <= parts


def _train_step():
    cfg = llama.LlamaConfig.tiny()
    from accelerate_tpu.training import TrainState, clip_by_global_norm

    state = TrainState.create(
        apply_fn=None, params=llama.init_params(cfg, jax.random.key(0)),
        tx=optax.adamw(1e-3))

    def step(state, ids):
        loss, grads = jax.value_and_grad(
            lambda p: llama.causal_lm_loss(cfg, p, {"input_ids": ids},
                                           loss_chunk_size=12))(state.params)
        grads, _ = clip_by_global_norm(grads, 1.0)
        return state.apply_gradients(grads), loss

    return jax.jit(step), (state, jnp.zeros((2, 49), jnp.int32))


def test_the_train_steps_backward_carries_its_forwards_part():
    step, args = _train_step()
    heavy = _heavy_ops(step, args)
    assert _unbilled(heavy) == []
    backward = {trace_scopes.part_of(n) for _, n in heavy
                if "transpose(jvp(" in n}
    assert {"attn.project", "attn.attend", "attn.output", "mlp"} <= backward
    # the head's three products run in the loss's FORWARD (PR 37)
    assert sum(trace_scopes.part_of(n) == "loss" for op, n in heavy
               if op == "dot_general") == 3
    names = OP_NAME.findall(step.lower(*args).compile().as_text())
    assert "optimizer" in {trace_scopes.part_of(n) for n in names}


def _stripped(fn, args):
    """A compiled program's text without metadata. XLA names an instruction
    after its `op_name`'s last component, so the names are metadata too:
    they are numbered again in order of appearance."""
    names: dict = {}
    return re.sub(r"%[\w.\-]+",
                  lambda m: names.setdefault(m.group(0), f"%{len(names)}"),
                  METADATA.sub("", fn.lower(*args).compile().as_text()))


@pytest.mark.parametrize("family", sorted(FAMILIES))
def test_scopes_change_a_decode_programs_metadata_and_nothing_else(
        family, monkeypatch):
    scoped = _stripped(*_programs(family)["decode"])
    assert "op_name" not in scoped and " dot(" in scoped
    # every scope of the program opens through this one call, at its entry
    monkeypatch.setattr(jax, "named_scope",
                        lambda name: contextlib.nullcontext())
    assert _stripped(*_programs(family)["decode"]) == scoped
