"""Compiled-program performance contracts.

Throughput comes only from a chip run, which a builder has to ask for.
These tests are the hardware-independent guardrail: they lower the
key programs to optimized HLO on the virtual 8-device CPU mesh and assert
the *structure* GSPMD must produce — the collective pattern is what sets
the performance class of each parallelism mode, and it is identical on the
CPU and TPU SPMD partitioners even though wall-clock isn't measured.

Contracts (pattern: the reference's threshold-gate idea,
ref test_utils/scripts/external_deps/test_performance.py:195-203, applied
to program text instead of accuracy):

1. ZeRO-3 fwd+bwd all-gathers params and reduce-scatters grads — it must
   NOT degenerate to a replicated all-reduce step.
2. ZeRO-1 fwd+bwd is pure data-parallel: grads all-reduce, params are
   never all-gathered (they are already replicated).
3. ZeRO-1's full train step still shards the optimizer moments: the
   update path reduce-scatters grads into moment shards and all-gathers
   only the param delta.
4. One ring-attention rotation is exactly one collective-permute per
   rotated buffer (K and V) — and the ring never all-gathers the sequence.
5. `attention_backend='auto'` selects the pallas flash kernel at/beyond
   1024 tokens on TPU (pure-function contract; the kernel itself needs
   hardware).
6. Repeated `train_step` calls with same-shaped inputs hit the jit cache —
   no recompile.

Note: XLA's CPU backend lowers reduce-scatter to all-to-all(+local reduce)
in optimized HLO, so the reduce-scatter clauses accept either spelling
(`require` groups).

Contracts are `accelerate_tpu.analysis.CollectiveContract`s (ISSUE 4).
The collective-permute pins of the `jax.shard_map` programs live in ONE
table — `analysis.contracts._SHARD_MAP_TABLE` — resolved by `contract_for`.
"""

import re

import jax
import jax.numpy as jnp
import numpy as np
import optax
import pytest
from jax.sharding import Mesh

from accelerate_tpu import TrainState
from accelerate_tpu.accelerator import Accelerator
from accelerate_tpu.analysis import (
    CollectiveContract,
    collective_counts,
    contract_for,
)
from accelerate_tpu.models import llama
from accelerate_tpu.utils import MeshConfig
from accelerate_tpu.utils.dataclasses import DeepSpeedPlugin


def _zero_step_and_batch(
    stage: int, grad_accum_steps: int = 1, use_grad_accum_buffer: bool = False
):
    acc = Accelerator(
        deepspeed_plugin=DeepSpeedPlugin(zero_stage=stage),
        gradient_accumulation_steps=grad_accum_steps,
    )
    cfg = llama.LlamaConfig.tiny()
    params = llama.init_params(cfg, jax.random.key(0))
    ts = acc.prepare(TrainState.create(
        apply_fn=None, params=params, tx=optax.adamw(1e-3),
        use_grad_accum_buffer=use_grad_accum_buffer,
    ))
    ids = np.zeros((8, 65), dtype=np.int32)
    loader = acc.prepare([{"input_ids": ids}])
    (batch,) = list(loader)
    step = acc.train_step(lambda p, b: llama.causal_lm_loss(cfg, p, b))
    grad_only = jax.jit(jax.grad(lambda p, b: llama.causal_lm_loss(cfg, p, b)))
    return cfg, ts, batch, step, grad_only


class TestZeroCollectiveStructure:
    # params sharded on fsdp: fwd+bwd must materialize them via all-gather,
    # and grads must come back SHARDED (reduce-scatter, spelled all-to-all
    # + local reduce by the CPU partitioner), never as a replicated
    # all-reduce-only step
    ZERO3_FWD_BWD = CollectiveContract(
        name="zero3.fwd_bwd",
        at_least={"all-gather": 1},
        require=(("reduce-scatter", "all-to-all"),),
    )
    # ZeRO-1 params are replicated: an all-gather in fwd+bwd means the
    # planner sharded them; grads must all-reduce across the data shards
    ZERO1_FWD_BWD = CollectiveContract(
        name="zero1.fwd_bwd",
        forbid=("all-gather", "all-to-all"),
        at_least={"all-reduce": 1},
    )

    def test_zero3_gathers_params_and_scatters_grads(self):
        _, ts, batch, step, grad_only = _zero_step_and_batch(3)
        self.ZERO3_FWD_BWD.enforce(
            grad_only.lower(ts.params, batch).compile().as_text()
        )

    def test_zero1_fwd_bwd_never_gathers_params(self):
        _, ts, batch, step, grad_only = _zero_step_and_batch(1)
        self.ZERO1_FWD_BWD.enforce(
            grad_only.lower(ts.params, batch).compile().as_text()
        )

    def test_zero1_update_shards_moments(self):
        """The full ZeRO-1 step shards optimizer moments even though params
        replicate: grads reduce-scatter into moment shards and only the
        param delta is all-gathered (the r5 fix — before it, stages 1/2
        silently degenerated to DDP with replicated moments)."""
        _, ts, batch, step, _ = _zero_step_and_batch(1)
        # moments actually sharded on device
        big_moments = [
            leaf
            for leaf in jax.tree_util.tree_leaves(ts.opt_state)
            if hasattr(leaf, "sharding") and leaf.size > 1000
        ]
        assert big_moments, "no large optimizer-state leaves found"
        sharded = [
            leaf
            for leaf in big_moments
            if any(s is not None for s in leaf.sharding.spec)
        ]
        assert sharded, (
            "ZeRO-1 optimizer moments are fully replicated — the stage "
            "degenerated to DDP"
        )
        # the update path reduce-scatters grads into moment shards and
        # all-gathers only the param delta
        CollectiveContract(
            name="zero1.full_step",
            at_least={"all-gather": 1},
            require=(("reduce-scatter", "all-to-all"),),
        ).enforce(step.lower(ts, batch).compile().as_text())

    def test_zero3_step_executes(self):
        """The contract programs must also run (shape/dtype sanity)."""
        _, ts, batch, step, _ = _zero_step_and_batch(3)
        ts2, metrics = step(ts, batch)
        assert jnp.isfinite(metrics["loss"])


class TestRingCollectiveStructure:
    def _qkv(self):
        B, S, H, D = 2, 1024, 4, 32
        q = jnp.ones((B, S, H, D))
        k = jnp.ones((B, S, 2, D))  # GQA: fewer K/V heads ride the ring
        v = jnp.ones((B, S, 2, D))
        return q, k, v

    def test_ring_forward_is_two_permutes_no_gather(self):
        from accelerate_tpu.parallel.ring_attention import ring_attention

        mesh = Mesh(np.array(jax.devices()).reshape(8), ("seq",))
        q, k, v = self._qkv()
        fwd = jax.jit(
            lambda q, k, v: ring_attention(q, k, v, causal=True, mesh=mesh)
        )
        # exact permute pin per shard_map lowering + never-gather structure,
        # both from the shared per-jax-version table
        contract_for("ring_attention.forward").enforce(
            fwd.lower(q, k, v).compile().as_text()
        )

    def test_ring_backward_keeps_ring_structure(self):
        from accelerate_tpu.parallel.ring_attention import ring_attention

        mesh = Mesh(np.array(jax.devices()).reshape(8), ("seq",))
        q, k, v = self._qkv()
        bwd = jax.jit(
            jax.grad(
                lambda q, k, v: ring_attention(
                    q, k, v, causal=True, mesh=mesh
                ).sum(),
                argnums=(0, 1, 2),
            )
        )
        # fwd K/V + bwd recompute K/V/mask-free + dK/dV return rings: the
        # exact figure is pinned (per lowering, in the shared table) so a
        # rewrite that silently gathers or doubles rotations fails here
        contract_for("ring_attention.backward").enforce(
            bwd.lower(q, k, v).compile().as_text()
        )


class TestAttentionAutoSelection:
    """Pure-function contract for the auto backend threshold; the pallas
    kernel itself is validated on hardware (benchmarks/sweep_attn.py)."""

    def test_long_context_on_tpu_selects_flash(self):
        sel = llama.select_attention_backend
        assert sel("auto", on_tpu=True, decoding=False, seq_len=1024) == "flash"
        assert sel("auto", on_tpu=True, decoding=False, seq_len=8192) == "flash"

    def test_short_context_keeps_einsum(self):
        sel = llama.select_attention_backend
        assert sel("auto", on_tpu=True, decoding=False, seq_len=512) == "einsum"

    def test_decode_keeps_einsum(self):
        sel = llama.select_attention_backend
        assert sel("auto", on_tpu=True, decoding=True, seq_len=4096) == "einsum"

    def test_cpu_keeps_einsum(self):
        sel = llama.select_attention_backend
        assert sel("auto", on_tpu=False, decoding=False, seq_len=4096) == "einsum"

    def test_explicit_backend_is_passed_through(self):
        sel = llama.select_attention_backend
        for b in ("einsum", "flash", "ring", "ulysses"):
            assert sel(b, on_tpu=False, decoding=False, seq_len=64) == b


class TestJitCacheStability:
    def test_train_step_does_not_recompile(self):
        """Same-shaped batches must reuse the compiled executable: a shape
        or dtype leak in the step (python scalars captured as weak types,
        re-built closures, ...) shows up here as a growing cache."""
        acc = Accelerator(mesh_config=MeshConfig(axes={"fsdp": 8}))
        cfg = llama.LlamaConfig.tiny()
        params = llama.init_params(cfg, jax.random.key(0))
        ts = acc.prepare(
            TrainState.create(
                apply_fn=None, params=params, tx=optax.adamw(1e-3)
            )
        )
        rng = np.random.default_rng(0)
        step = acc.train_step(lambda p, b: llama.causal_lm_loss(cfg, p, b))
        for _ in range(3):
            ids = rng.integers(0, cfg.vocab_size, (8, 65)).astype(np.int32)
            loader = acc.prepare([{"input_ids": ids}])
            (batch,) = list(loader)
            ts, metrics = step(ts, batch)
        assert step._cache_size() == 1, (
            f"train_step compiled {step._cache_size()} times for "
            "identically-shaped batches"
        )

    def test_eval_step_does_not_recompile(self):
        acc = Accelerator()
        cfg = llama.LlamaConfig.tiny()
        params = llama.init_params(cfg, jax.random.key(0))
        params = acc.prepare_params(params)
        ev = acc.eval_step(lambda p, b: llama.causal_lm_loss(cfg, p, b))
        ids = np.zeros((4, 33), dtype=np.int32)
        loader = acc.prepare([{"input_ids": ids}])
        (batch,) = list(loader)
        for _ in range(3):
            ev(params, batch)
        assert ev._cache_size() == 1


class TestTensorParallelStructure:
    def test_tp_fwd_syncs_activations_not_params(self):
        """Megatron-style TP: column/row-parallel matmuls communicate
        *activations* (all-reduce / reduce-scatter of the row-parallel
        output), never gather whole weight matrices."""
        acc = Accelerator(
            mesh_config=MeshConfig(axes={"fsdp": 2, "model": 4})
        )
        cfg = llama.LlamaConfig.tiny()
        params = llama.init_params(cfg, jax.random.key(0))
        params = acc.prepare_params(params)
        ids = np.zeros((8, 65), dtype=np.int32)
        loader = acc.prepare([{"input_ids": ids}])
        (batch,) = list(loader)
        grad_only = jax.jit(
            jax.grad(lambda p, b: llama.causal_lm_loss(cfg, p, b))
        )
        counts = collective_counts(
            grad_only.lower(params, batch).compile().as_text()
        )
        assert counts["all-reduce"] > 0, dict(counts)


class TestStepReuseAcrossLayouts:
    def test_step_repins_for_a_new_mesh_layout(self):
        """A train_step reused after re-preparing under a different mesh
        must get fresh output pins (new jit entry), not outputs silently
        forced back onto the first layout (r5 review finding)."""
        from accelerate_tpu.state import PartialState

        cfg = llama.LlamaConfig.tiny()
        ids = np.zeros((8, 65), dtype=np.int32)

        acc1 = Accelerator(mesh_config=MeshConfig(axes={"fsdp": 8}))
        params = llama.init_params(cfg, jax.random.key(0))
        ts1 = acc1.prepare(TrainState.create(
            apply_fn=None, params=params, tx=optax.adamw(1e-3)))
        loader = acc1.prepare([{"input_ids": ids}])
        (batch1,) = list(loader)
        step = acc1.train_step(lambda p, b: llama.causal_lm_loss(cfg, p, b))
        ts1, _ = step(ts1, batch1)

        PartialState._reset_state()
        acc2 = Accelerator(mesh_config=MeshConfig(axes={"data": 8}))
        params = llama.init_params(cfg, jax.random.key(0))
        ts2 = acc2.prepare(TrainState.create(
            apply_fn=None, params=params, tx=optax.adamw(1e-3)))
        loader = acc2.prepare([{"input_ids": ids}])
        (batch2,) = list(loader)
        ts2, m = step(ts2, batch2)
        assert jnp.isfinite(m["loss"])
        # outputs keep the SECOND layout (replicated params on the data
        # mesh), not the first (fsdp-sharded)
        big = max(
            jax.tree_util.tree_leaves(ts2.params), key=lambda x: x.size
        )
        assert not any(s is not None for s in big.sharding.spec), (
            f"output forced onto a stale layout: {big.sharding.spec}"
        )
        # and the steady state holds per layout: one more call, no growth
        before = step._cache_size()
        ts2, _ = step(ts2, batch2)
        assert step._cache_size() == before


class TestUlyssesCollectiveStructure:
    def test_ulysses_rides_all_to_all_only(self):
        """Ulysses scatters heads with all-to-all (sequence re-gathered
        per-head, never as a whole): the program must carry all-to-alls
        and NO sequence all-gather or ring permute. Counts are not pinned
        — XLA's CPU backend decomposes one logical a2a into per-pair ops."""
        from accelerate_tpu.parallel.ulysses import ulysses_attention

        mesh = Mesh(np.array(jax.devices()).reshape(8), ("seq",))
        B, S, H, D = 2, 1024, 8, 32
        q = jnp.ones((B, S, H, D))
        k = jnp.ones((B, S, 8, D))
        v = jnp.ones((B, S, 8, D))
        contract = contract_for("ulysses.attention")
        for fn in (
            jax.jit(lambda q, k, v: ulysses_attention(
                q, k, v, causal=True, mesh=mesh)),
            jax.jit(jax.grad(
                lambda q, k, v: ulysses_attention(
                    q, k, v, causal=True, mesh=mesh).sum(),
                argnums=(0, 1, 2),
            )),
        ):
            contract.enforce(fn.lower(q, k, v).compile().as_text())


class TestZero2GradAccumSharding:
    def test_grad_accum_buffer_shards_like_moments(self):
        """ZeRO-2: the persistent gradient store (the accumulation buffer)
        shards on the fsdp axis along with the moments, while params stay
        replicated — and the accumulating step still runs."""
        cfg, ts, batch, step, _ = _zero_step_and_batch(
            2, grad_accum_steps=2, use_grad_accum_buffer=True
        )
        big_params = [
            leaf for leaf in jax.tree_util.tree_leaves(ts.params)
            if leaf.size > 1000
        ]
        assert all(
            not any(s is not None for s in leaf.sharding.spec)
            for leaf in big_params
        ), "ZeRO-2 params must replicate"
        big_accum = [
            leaf for leaf in jax.tree_util.tree_leaves(ts.grad_accum)
            if leaf.size > 1000
        ]
        assert big_accum and all(
            any(s is not None for s in leaf.sharding.spec)
            for leaf in big_accum
        ), "ZeRO-2 grad-accum buffer must shard on the fsdp axis"
        for _ in range(4):  # two full accumulation windows
            ts, m = step(ts, batch)
        assert jnp.isfinite(m["loss"])
        assert step._cache_size() == 1


class TestPipelineCollectiveStructure:
    def test_schedules_shift_activations_never_gather(self):
        """GPipe and 1F1B move activations stage-to-stage with
        collective-permute (one fwd shift + one bwd shift in the loop
        bodies) and must never all-gather activations or params across
        the stage axis; grads sync with all-reduce only."""
        from accelerate_tpu.parallel import (
            pipeline_value_and_grad,
            stack_layers_into_stages,
        )

        mesh = Mesh(np.array(jax.devices()).reshape(2, 4), ("data", "stage"))
        staged = stack_layers_into_stages(
            {"w": jax.random.normal(jax.random.key(1), (4, 16, 16)) * 0.1}, 4
        )
        x = jax.random.normal(jax.random.key(2), (8, 16))
        t = jax.random.normal(jax.random.key(3), (8, 16))
        for sched in ("gpipe", "1f1b"):
            fn = jax.jit(lambda sp, x, t, s=sched: pipeline_value_and_grad(
                lambda p, xx: jnp.tanh(xx @ p["w"][0]),
                lambda y, tt: jnp.mean((y - tt) ** 2),
                sp, x, t, num_micro_batches=4, mesh=mesh, schedule=s))
            contract_for("pipeline.step").enforce(
                fn.lower(staged, x, t).compile().as_text()
            )


class TestFp8StepStability:
    def test_fp8_train_step_does_not_recompile(self):
        """The fp8 metas thread through TrainState like optimizer state:
        repeated steps must reuse one executable (a meta that changed
        shape/dtype across steps would force a retrace here)."""
        acc = Accelerator(
            mixed_precision="fp8",
            mesh_config=MeshConfig(axes={"fsdp": 8}),
        )
        cfg = llama.LlamaConfig.tiny()
        params = llama.init_params(cfg, jax.random.key(0))
        ts = acc.prepare(TrainState.create(
            apply_fn=None, params=params, tx=optax.adamw(1e-3),
            fp8_state=llama.init_fp8_state(cfg),
        ))
        ids = np.zeros((8, 65), dtype=np.int32)
        loader = acc.prepare([{"input_ids": ids}])
        (batch,) = list(loader)
        step = acc.train_step(
            lambda p, b, **kw: llama.causal_lm_loss(cfg, p, b, **kw)
        )
        for _ in range(3):
            ts, m = step(ts, batch)
        assert jnp.isfinite(m["loss"])
        assert step._cache_size() == 1
        # the delayed-scaling state actually moved: a regression that
        # drops new_fp8 from the returned state would leave it identical
        # to a fresh init (scales at ones, histories at zeros)
        fresh = jax.tree_util.tree_leaves(llama.init_fp8_state(cfg))
        moved = [
            not np.array_equal(np.asarray(a), np.asarray(b))
            for a, b in zip(jax.tree_util.tree_leaves(ts.fp8_state), fresh)
        ]
        assert any(moved), "fp8 metas never updated across steps" 


class TestPrefillViewStructure:
    """A prefill chunk of a family that loops over its layers holds its
    slot's view ONE LAYER at a time and returns its own rows: the traced
    program and the compiled one hold no array of the stacked views'
    shape `[L, 1, R, ...]`, in or out (PERF.md, PR 39: the stacks around
    every chunk were a fifth of the keye cell's chunk). The llama family's
    layers are one scan over the stacked views, which it keeps."""

    @staticmethod
    def _prefill(family, cfg, **engine):
        from accelerate_tpu.serving import Engine, EngineConfig

        eng = Engine(family, cfg, family.init_params(cfg, jax.random.key(0)),
                     EngineConfig(num_slots=2, max_len=48, prefill_chunk=8,
                                  page_size=8, cache_dtype=jnp.float32,
                                  paged_attention=False, **engine))
        chunk = eng.engine_config.prefill_chunk
        lowered = eng._prefill_p.lower(
            eng.params, eng.cache, eng._tokens, eng._slot_keys, eng._temps,
            jnp.int32(0), eng._tables(0), np.zeros((chunk,), np.int32),
            jnp.int32(chunk))
        groups = getattr(eng.cache, "groups", (eng.cache,))
        return lowered, [(g.num_layers, g.rows) for g in groups]

    @staticmethod
    def _stacked(lowered, layers, rows):
        """The arrays `[layers, 1, rows, ...]` of the traced program (MLIR
        types) and of the compiled one (HLO shapes)."""
        return (re.findall(rf"tensor<{layers}x1x{rows}x[0-9x]*f32>",
                           lowered.as_text())
                + re.findall(rf"f32\[{layers},1,{rows},[0-9,]*\]",
                             lowered.compile().as_text()))

    @pytest.mark.parametrize("name", ["keye", "deepseek", "mellum", "dots3"])
    def test_looping_families_hold_no_stacked_view(self, name):
        from accelerate_tpu.models import deepseek, dots3, keye, mellum

        family, cfg, engine = {
            "keye": (keye, keye.KeyeConfig.tiny(), {}),
            "deepseek": (deepseek, deepseek.DeepseekConfig.tiny(), {}),
            "mellum": (mellum, mellum.MellumConfig.tiny(num_hidden_layers=4),
                       dict(prefix_cache=False)),
            "dots3": (dots3, dots3.Dots3Config.tiny(),
                      dict(prefix_cache=False)),
        }[name]
        lowered, groups = self._prefill(family, cfg, **engine)
        assert len(groups) == (2 if name in ("mellum", "dots3") else 1)
        for layers, rows in groups:
            assert rows == 56 or (name == "mellum" and rows == 48) or (
                name == "dots3" and rows == 32)
            assert self._stacked(lowered, layers, rows) == []

    def test_llama_takes_the_stacked_views(self):
        cfg = llama.LlamaConfig.tiny()
        lowered, [(layers, rows)] = self._prefill(llama, cfg)
        assert len(self._stacked(lowered, layers, rows)) >= 4


class TestLatentGroupsInPlace:
    """The programs of a family whose cache is latent GROUPS (the full
    layers' pool with its index keys beside it, the sliding layers' ring:
    `models/dots3.py`) update all three pool arrays in place: each is
    aliased, argument to result, in `prefill` and `decode`; and under the
    kernels every Pallas call of `decode` is handed its WHOLE stacked pool
    (2.2 GB in the benchmark's cell: nothing slices a layer out; that the
    chip's compiler then copies neither pool is held in
    `tests/test_chip_compile.py`)."""

    @staticmethod
    def _programs(kernel):
        from accelerate_tpu.models import dots3
        from accelerate_tpu.serving import Engine, EngineConfig

        cfg = dots3.Dots3Config.tiny(experts_held=(0, 4))
        eng = Engine(dots3, cfg, dots3.init_params(cfg, jax.random.key(0)),
                     EngineConfig(num_slots=2, max_len=48, prefill_chunk=16,
                                  page_size=16, cache_dtype=jnp.float32,
                                  prefix_cache=False, paged_attention=kernel))
        state = (eng.params, eng.cache, eng._tokens, eng._slot_keys,
                 eng._temps)
        return eng, {
            "prefill": (eng._prefill_p, state + (
                jnp.int32(0), eng._tables(0), np.zeros((16,), np.int32),
                jnp.int32(16))),
            "decode": (eng._decode_p, state + (
                np.ones((2,), bool), eng._tables())),
        }

    @pytest.mark.parametrize("program", ["prefill", "decode"])
    def test_both_pools_and_the_index_keys_are_aliased(self, program):
        eng, programs = self._programs(kernel=False)
        fn, args = programs[program]
        full, ring = eng.cache.groups
        assert full.v is None and ring.v is None
        text = fn.lower(*args).compile().as_text()
        aliases = re.search(r"input_output_alias=\{[^\n]*?\}, entry",
                            text).group(0)
        flat = jax.tree.leaves(args)
        for array in (full.k, full.side, ring.k):
            at = next(i for i, leaf in enumerate(flat) if leaf is array)
            assert re.search(rf"\({at}, \{{\}}", aliases), (program, at)

    def test_the_kernels_take_their_whole_stacked_pools(self):
        eng, programs = self._programs(kernel=True)
        fn, args = programs["decode"]
        full, ring = eng.cache.groups
        calls = [eqn for eqn in _all_eqns(jax.make_jaxpr(fn)(*args).jaxpr)
                 if eqn.primitive.name == "pallas_call"]
        # a full layer: the scores and the sparse latent kernel; a sliding
        # layer: the ring kernel
        assert len(calls) == 2 * 2 + 2
        took = [tuple(v.aval.shape for v in eqn.invars) for eqn in calls]

        def folded(pool):   # the unit head axis folds away
            return tuple(d for i, d in enumerate(pool.shape) if i != 2)

        assert sum(folded(full.k) in t for t in took) == 2
        assert sum(full.side.shape in t for t in took) == 2
        assert sum(folded(ring.k) in t for t in took) == 2
        # and nothing of ONE layer's slice of a pool is made around them
        shapes = {v.aval.shape for eqn in _all_eqns(
            jax.make_jaxpr(fn)(*args).jaxpr) for v in eqn.outvars}
        for pool in (full.k, full.side, ring.k):
            assert folded(pool)[1:] not in shapes
            assert pool.shape[1:] not in shapes


class TestStatePoolInPlace:
    """The programs of a family that keeps one recurrent state a sequence
    (`CacheSpec.kind == "state"`) are handed the WHOLE pool and hand it
    back: both pool arrays are aliased, argument to result, in `admit`,
    `prefill` and `decode`, and the compiled programs hold no copy of
    either (4.67 GB in the benchmark's cell; the chip's compiler is held
    to the same in `tests/test_chip_compile.py`)."""

    @staticmethod
    def _programs(kernel):
        from accelerate_tpu.models import brumby
        from accelerate_tpu.serving import Engine, EngineConfig

        cfg = brumby.BrumbyConfig.tiny(head_dim=16)
        eng = Engine(brumby, cfg, brumby.init_params(cfg, jax.random.key(0)),
                     EngineConfig(num_slots=2, max_len=48, prefill_chunk=8,
                                  num_pages=2, cache_dtype=jnp.float32,
                                  prefix_cache=False, paged_attention=kernel))
        state = (eng.params, eng.cache, eng._tokens, eng._slot_keys,
                 eng._temps)
        return eng, {
            "admit": (eng._admit_p, (
                eng.cache, eng._slot_keys, eng._temps, jnp.int32(0),
                eng._slot_keys[0], jnp.float32(0.0), jnp.int32(1))),
            "prefill": (eng._prefill_p, state + (
                jnp.int32(0), eng._tables(0), np.zeros((8,), np.int32),
                jnp.int32(8))),
            "decode": (eng._decode_p, state + (
                np.ones((2,), bool), eng._tables())),
        }

    @pytest.mark.parametrize("program", ["admit", "prefill", "decode"])
    def test_the_pool_is_aliased_and_never_copied(self, program):
        eng, programs = self._programs(kernel=False)
        fn, args = programs[program]
        pool = (eng.cache.s, eng.cache.z)
        compiled = fn.lower(*args).compile()
        text = compiled.as_text()
        aliases = re.search(r"input_output_alias=\{[^\n]*?\}, entry",
                            text).group(0)
        flat = jax.tree.leaves(args)
        for array in pool:
            at = next(i for i, leaf in enumerate(flat) if leaf is array)
            assert re.search(rf"\({at}, \{{\}}", aliases), (program, at)
            shape = ",".join(map(str, array.shape))
            # (the CPU's compiler copies the pool once around a chunk's
            # slice-then-update in the plain `jax.numpy` form; the chip's,
            # with the kernels, does not: tests/test_chip_compile.py)
            assert program == "prefill" or not re.search(
                rf"= f32\[{shape}\]\S* copy\(", text), program

    def test_the_kernels_take_the_pool_aliased(self):
        """Traced with the kernels (interpreted on the CPU): each layer's
        `pallas_call` of `decode` and of `prefill` names the pool's `s` as
        an aliased operand."""
        _, programs = self._programs(kernel=True)
        for name, aliased in (("decode", 2), ("prefill", 1)):
            fn, args = programs[name]
            calls = [eqn for eqn in _all_eqns(jax.make_jaxpr(fn)(*args).jaxpr)
                     if eqn.primitive.name == "pallas_call"]
            assert len(calls) == 2, name                  # one a layer
            for eqn in calls:
                assert len(eqn.params["input_output_aliases"]) == aliased


class TestStateBesidePagesInPlace:
    """The programs of a family that keeps a group of state ENTRIES beside
    a group of K/V pages (`GroupedPagedCache.state`): all four pool arrays
    (K, V, the states, the windows) are aliased, argument to result, in
    `admit`, `prefill` and `decode`, and `jit_decode` holds no copy of any
    (1.07 GB + 2.6 GB in the benchmark's cell; the chip's compiler is held
    to the same in `tests/test_chip_compile.py`)."""

    @staticmethod
    def _programs(kernel):
        from accelerate_tpu.models import jamba
        from accelerate_tpu.serving import Engine, EngineConfig

        cfg = jamba.JambaConfig.tiny()
        eng = Engine(jamba, cfg, jamba.init_params(cfg, jax.random.key(0)),
                     EngineConfig(num_slots=2, max_len=48, prefill_chunk=8,
                                  page_size=16, cache_dtype=jnp.float32,
                                  prefix_cache=False, paged_attention=kernel))
        regs = (eng.params, eng.cache, eng._tokens, eng._slot_keys,
                eng._temps)
        return eng, {
            "admit": (eng._admit_p, (
                eng.cache, eng._slot_keys, eng._temps, jnp.int32(0),
                eng._slot_keys[0], jnp.float32(0.0), jnp.int32(0))),
            "prefill": (eng._prefill_p, regs + (
                jnp.int32(0), eng._tables(0), np.zeros((8,), np.int32),
                jnp.int32(8))),
            "decode": (eng._decode_p, regs + (
                np.ones((2,), bool), eng._tables())),
        }

    @pytest.mark.parametrize("program", ["admit", "prefill", "decode"])
    def test_every_pool_is_aliased_and_decode_copies_none(self, program):
        eng, programs = self._programs(kernel=False)
        fn, args = programs[program]
        cache = eng.cache
        pools = (cache.groups[0].k, cache.groups[0].v, cache.state.s,
                 cache.state.z)
        text = fn.lower(*args).compile().as_text()
        aliases = re.search(r"input_output_alias=\{[^\n]*?\}, entry",
                            text).group(0)
        flat = jax.tree.leaves(args)
        for array in pools:
            at = next(i for i, leaf in enumerate(flat) if leaf is array)
            assert re.search(rf"\({at}, \{{\}}", aliases), (program, at)
            shape = ",".join(map(str, array.shape))
            # the states: no copy. (The CPU's compiler copies a pool once
            # around a slice-then-update in the plain `jax.numpy` form, a
            # chunk's state, a step's windows, and the K/V pool around the
            # dense decode's page scatter, as for every family: the
            # chip's, held in tests/test_chip_compile.py, copies none.)
            assert (program == "prefill" or array is not pools[2]
                    or not re.search(rf"= f32\[{shape}\]\S* copy\(", text)
                    ), (program, shape)

    def test_the_kernels_take_their_pools_whole(self):
        """Traced with the kernels (interpreted on the CPU): `decode` holds
        the scan's decode kernel once a Mamba layer with the state pool
        aliased, and the attention kernel once an attention layer;
        `prefill` the chunk kernel once a Mamba layer."""
        _, programs = self._programs(kernel=True)
        for name, scans in (("decode", "ssm_decode_step"),
                            ("prefill", "ssm_chunk_scan")):
            fn, args = programs[name]
            calls = [eqn for eqn in _all_eqns(jax.make_jaxpr(fn)(*args).jaxpr)
                     if eqn.primitive.name == "pallas_call"]
            by_name = {}
            for eqn in calls:
                by_name.setdefault(eqn.params["name"], []).append(eqn)
            assert len(by_name[scans]) == 2, (name, sorted(by_name))
            for eqn in by_name[scans]:
                assert len(eqn.params["input_output_aliases"]) == 1
            attends = [k for k in by_name if "paged_decode_attention" in k]
            assert (len(attends) == 1 and len(by_name[attends[0]]) == 2
                    ) == (name == "decode"), (name, sorted(by_name))


def _all_eqns(jaxpr):
    for eqn in jaxpr.eqns:
        yield eqn
        for inner in jax.core.jaxprs_in_params(eqn.params):
            yield from _all_eqns(inner)
