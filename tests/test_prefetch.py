"""Zero-overhead steady-state fast path: device-side input prefetch, cached
train-step dispatch (treedef-keyed pins, AOT warmup), and the persistent
compilation cache. All CPU-runnable under the virtual 8-device mesh."""

import os

import jax
import jax.numpy as jnp
import numpy as np
import optax
import pytest
from jax.sharding import NamedSharding, PartitionSpec

from accelerate_tpu import TrainState
from accelerate_tpu.accelerator import Accelerator, _CompiledTrainStep
from accelerate_tpu.data import DataLoaderShard, DevicePrefetchIterator
from accelerate_tpu.models import llama
from accelerate_tpu.utils.dataclasses import DataLoaderConfiguration, MeshConfig


def _mesh():
    return MeshConfig.data_parallel().build(jax.devices())


# ---------------------------------------------------------------------------
# DevicePrefetchIterator
# ---------------------------------------------------------------------------


class TestDevicePrefetchIterator:
    def test_ordering_preserved(self):
        out = list(DevicePrefetchIterator(range(10), lambda x: x * 10, depth=3))
        assert out == [i * 10 for i in range(10)]

    def test_transfers_stay_within_depth_ahead(self):
        """`place` (the async device_put stand-in) runs ahead of the
        consumer, but never more than depth+1 batches ahead (the +1 is the
        batch handed out)."""
        placed = []
        it = DevicePrefetchIterator(range(10), lambda x: placed.append(x) or x,
                                    depth=2)
        consumed = 0
        for _ in it:
            consumed += 1
            assert len(placed) <= consumed + 2
        assert consumed == 10 and len(placed) == 10

    def test_prefetch_is_eager_after_first_next(self):
        placed = []
        it = DevicePrefetchIterator(range(10), lambda x: placed.append(x) or x,
                                    depth=3)
        assert next(it) == 0
        # depth filled before hand-out, topped back up after
        assert len(placed) == 4

    def test_empty_and_exhaustion(self):
        it = DevicePrefetchIterator([], lambda x: x, depth=2)
        with pytest.raises(StopIteration):
            next(it)
        it = DevicePrefetchIterator([1], lambda x: x, depth=4)
        assert next(it) == 1
        with pytest.raises(StopIteration):
            next(it)

    def test_depth_floor_is_one(self):
        assert list(DevicePrefetchIterator(range(3), lambda x: x, depth=0)) == [0, 1, 2]


# ---------------------------------------------------------------------------
# DataLoaderShard with the device buffer
# ---------------------------------------------------------------------------


def _dict_batches(n_batches, rows=8, start=0):
    return [
        {"x": np.arange(start + i * rows, start + (i + 1) * rows,
                        dtype=np.float32).reshape(rows, 1)}
        for i in range(n_batches)
    ]


class TestLoaderDevicePrefetch:
    def test_ordering_and_placement(self):
        loader = DataLoaderShard(_dict_batches(5), mesh=_mesh(),
                                 device_prefetch_depth=3)
        seen = [np.asarray(b["x"])[:, 0] for b in loader]
        flat = np.concatenate(seen)
        assert flat.tolist() == list(np.arange(40, dtype=np.float32))
        out = list(iter(loader))
        assert all(isinstance(b["x"], jax.Array) for b in out)
        assert all(
            isinstance(b["x"].sharding, jax.sharding.NamedSharding) for b in out
        )

    def test_epoch_boundary_bumps_epoch_and_reiterates(self):
        loader = DataLoaderShard(_dict_batches(4), mesh=_mesh(),
                                 device_prefetch_depth=2)
        first = [np.asarray(b["x"]) for b in loader]
        assert loader.epoch == 1  # full pass advances the epoch
        second = [np.asarray(b["x"]) for b in loader]
        assert loader.epoch == 2
        assert len(first) == len(second) == 4
        np.testing.assert_array_equal(first[0], second[0])

    def test_uneven_tail_remainder_survives_prefetch(self):
        """end_of_dataloader's one-batch-ahead detection and the remainder
        bookkeeping must still fire with the device buffer in between."""
        batches = _dict_batches(3) + [
            {"x": np.arange(24, 27, dtype=np.float32).reshape(3, 1)}
        ]
        loader = DataLoaderShard(batches, mesh=_mesh(),
                                 device_prefetch_depth=2)
        sizes = []
        for b in loader:
            sizes.append(int(b["x"].shape[0]))
            if sizes[-1] == 8 and len(sizes) < 4:
                assert not loader.end_of_dataloader
        assert loader.end_of_dataloader
        # 3 real rows, padded up to the per-host device multiple (8)
        assert sizes[-1] == 8
        assert loader.remainder == 3

    def test_drop_last_style_source_not_padded(self):
        """A source that already dropped its tail (equal-size batches only)
        must flow through the prefetch pipeline without padding or
        remainder tracking."""
        loader = DataLoaderShard(_dict_batches(3), mesh=_mesh(),
                                 device_prefetch_depth=2)
        sizes = [int(b["x"].shape[0]) for b in loader]
        assert sizes == [8, 8, 8]
        assert loader.remainder == -1

    def test_depth_zero_disables_device_buffer(self):
        loader = DataLoaderShard(_dict_batches(3), mesh=_mesh(),
                                 device_prefetch_depth=0)
        out = [np.asarray(b["x"])[:, 0] for b in loader]
        assert np.concatenate(out).tolist() == list(np.arange(24, dtype=np.float32))

    def test_config_threads_depth_through_prepare(self):
        acc = Accelerator(
            dataloader_config=DataLoaderConfiguration(device_prefetch_depth=5,
                                                      prefetch_size=3)
        )
        loader = acc.prepare(_dict_batches(2))
        assert isinstance(loader, DataLoaderShard)
        assert loader.device_prefetch_depth == 5
        assert loader.prefetch_size == 3

    def test_explicit_kwarg_beats_config(self):
        from accelerate_tpu.data import prepare_data_loader

        loader = prepare_data_loader(
            _dict_batches(2), mesh=_mesh(),
            config=DataLoaderConfiguration(),  # defaults: depth 2, size 2
            device_prefetch_depth=0, prefetch_size=7,
        )
        assert loader.device_prefetch_depth == 0
        assert loader.prefetch_size == 7


# ---------------------------------------------------------------------------
# cached dispatch (_CompiledTrainStep)
# ---------------------------------------------------------------------------


def _make_toy_step():
    # a FRESH function object per test: jax.jit shares its dispatch cache
    # across wrappers of the same function, so a module-level step_fn would
    # leak `_cache_size()` entries between tests
    def _toy_step(state, *batch):
        new = jax.tree_util.tree_map(lambda x: x + 1.0, state)
        metrics = {"loss": jnp.float32(0.0)}
        return new, metrics

    return _toy_step


def _placed_state(tree):
    mesh = _mesh()
    rep = NamedSharding(mesh, PartitionSpec())
    return jax.tree_util.tree_map(lambda x: jax.device_put(x, rep), tree)


class TestCachedDispatch:
    def test_treedef_collision_gets_separate_jits(self):
        """Regression: two states with DIFFERENT treedefs but identical
        flattened sharding tuples must not share a jit — the out_shardings
        pytree is built from the first structure and would reject (or
        mispin) the second."""
        step = _CompiledTrainStep(_make_toy_step(), donate=False)
        a = _placed_state({"a": jnp.ones((8,)), "b": jnp.ones((8,))})
        b = _placed_state({"c": {"d": jnp.ones((8,)), "e": jnp.ones((8,))}})
        out_a, _ = step(a)
        out_b, _ = step(b)
        assert set(out_a) == {"a", "b"}
        assert set(out_b) == {"c"} and set(out_b["c"]) == {"d", "e"}
        assert len(step._by_layout) == 2
        assert step._pin_computations == 2

    def test_pin_tree_computed_once_across_steps(self):
        """Acceptance: steady-state dispatch is a cache hit — exactly ONE
        pin-tree computation for a fixed state structure over N steps."""
        step = _CompiledTrainStep(_make_toy_step(), donate=False)
        state = _placed_state({"w": jnp.zeros((8, 4)), "b": jnp.zeros((4,))})
        for i in range(10):
            state, _ = step(state)
        assert step._pin_computations == 1
        assert float(np.asarray(state["b"][0])) == 10.0

    def test_identity_fast_path_reset_on_new_layout(self):
        step = _CompiledTrainStep(_make_toy_step(), donate=False)
        state = _placed_state({"w": jnp.zeros((8, 4))})
        state, _ = step(state)
        # a re-prepared state with a DIFFERENT layout must get fresh pins
        mesh = _mesh()
        sharded = NamedSharding(mesh, PartitionSpec("data"))
        other = {"w": jax.device_put(np.zeros((8, 4), np.float32), sharded)}
        out, _ = step(other)
        assert step._pin_computations == 2
        assert out["w"].sharding == sharded

    def test_accelerator_train_step_pin_count(self):
        """End-to-end: the real fused train step over a prepared TrainState
        computes its pin tree once no matter how many steps run."""
        acc = Accelerator(mesh_config=MeshConfig(axes={"fsdp": 8}))
        cfg = llama.LlamaConfig.tiny()
        params = llama.init_params(cfg, jax.random.key(0))
        ts = acc.prepare(
            TrainState.create(apply_fn=None, params=params,
                              tx=optax.adamw(1e-3))
        )
        step = acc.train_step(lambda p, b: llama.causal_lm_loss(cfg, p, b))
        rng = np.random.default_rng(0)
        for _ in range(4):
            ids = rng.integers(0, cfg.vocab_size, (8, 65)).astype(np.int32)
            loader = acc.prepare([{"input_ids": ids}])
            (batch,) = list(loader)
            ts, metrics = step(ts, batch)
        assert step._pin_computations == 1
        assert step._cache_size() == 1


class TestWarmup:
    def test_warmup_compiles_without_executing(self):
        step = _CompiledTrainStep(_make_toy_step(), donate=True)
        state = _placed_state({"w": jnp.zeros((8, 4))})
        batch = jnp.ones((8, 2))
        compiled = step.warmup(state, batch)
        assert compiled is not None
        # nothing executed, nothing donated: the state is still usable
        assert float(np.asarray(state["w"][0, 0])) == 0.0
        # idempotent for the same signature
        assert step.warmup(state, batch) is compiled

    def test_warmed_up_steps_never_touch_the_jit_cache(self):
        step = _CompiledTrainStep(_make_toy_step(), donate=False)
        state = _placed_state({"w": jnp.zeros((8, 4))})
        batch = jnp.ones((8, 2))
        step.warmup(state, batch)
        for _ in range(5):
            state, _ = step(state, batch)
        # every call dispatched to the AOT executable — the jit cache is
        # still cold, and the first loop step paid dispatch only
        assert step._cache_size() == 0
        assert float(np.asarray(state["w"][0, 0])) == 5.0

    def test_midloop_warmup_resets_identity_fast_path(self):
        """warmup() for an upcoming batch shape must be consulted by the
        next call even when the loop's identity fast path is active."""
        step = _CompiledTrainStep(_make_toy_step(), donate=False)
        state = _placed_state({"w": jnp.zeros((8, 4))})
        batch_a, batch_b = jnp.ones((8, 2)), jnp.ones((16, 2))
        step.warmup(state, batch_a)
        state, _ = step(state, batch_a)
        step.warmup(state, batch_b)          # precompile the next shape
        state, _ = step(state, batch_b)      # must hit the fresh executable
        assert step._cache_size() == 0
        assert float(np.asarray(state["w"][0, 0])) == 2.0

    def test_batch_shape_drift_falls_back_to_jit(self):
        step = _CompiledTrainStep(_make_toy_step(), donate=False)
        state = _placed_state({"w": jnp.zeros((8, 4))})
        step.warmup(state, jnp.ones((8, 2)))
        state, _ = step(state, jnp.ones((8, 2)))     # AOT path
        state, _ = step(state, jnp.ones((16, 2)))    # drifted: jit path
        assert float(np.asarray(state["w"][0, 0])) == 2.0
        assert step._cache_size() == 1

    def test_alternating_shape_warmups_compile_once_each(self):
        """Regression (PR 1 review item): the AOT cache kept ONE executable
        per layout key, so alternating warmups across two batch shapes
        evicted each other and recompiled every time. Keyed by
        (layout, batch signature) they must each compile exactly once."""
        step = _CompiledTrainStep(_make_toy_step(), donate=False)
        state = _placed_state({"w": jnp.zeros((8, 4))})
        batch_a, batch_b = jnp.ones((8, 2)), jnp.ones((16, 2))
        first_a = step.warmup(state, batch_a)
        first_b = step.warmup(state, batch_b)
        for _ in range(3):
            assert step.warmup(state, batch_a) is first_a
            assert step.warmup(state, batch_b) is first_b
        assert step._aot_compiles == 2
        assert len(step._aot) == 2
        # both warmed shapes dispatch AOT — the jit cache stays cold
        state, _ = step(state, batch_a)
        state, _ = step(state, batch_b)
        state, _ = step(state, batch_a)
        assert step._cache_size() == 0
        assert float(np.asarray(state["w"][0, 0])) == 3.0


# ---------------------------------------------------------------------------
# persistent compilation cache
# ---------------------------------------------------------------------------


class TestCompilationCache:
    def test_smoke_writes_and_reuses_entries(self, tmp_path, monkeypatch):
        from accelerate_tpu.utils import environment as env_mod
        from accelerate_tpu.utils.constants import (
            ENV_COMPILATION_CACHE_MIN_COMPILE_SECS,
            ENV_COMPILATION_CACHE_MIN_ENTRY_BYTES,
        )
        from jax.experimental.compilation_cache import compilation_cache as cc

        cache_dir = str(tmp_path / "xla-cache")
        monkeypatch.setenv(ENV_COMPILATION_CACHE_MIN_COMPILE_SECS, "0")
        monkeypatch.setenv(ENV_COMPILATION_CACHE_MIN_ENTRY_BYTES, "-1")
        prev_dir = jax.config.jax_compilation_cache_dir
        try:
            applied = env_mod.configure_compilation_cache(cache_dir, force=True)
            assert applied == cache_dir
            # a fresh computation compiles and persists
            x = jnp.arange(17.0)
            jax.jit(lambda v: jnp.cos(v) * 17.0 + v)(x).block_until_ready()
            entries = os.listdir(cache_dir)
            assert entries, "no persistent cache entries written"
        finally:
            jax.config.update("jax_compilation_cache_dir", prev_dir)
            cc.reset_cache()
            env_mod._compilation_cache_dir_applied = None

    def test_env_disable(self, monkeypatch):
        from accelerate_tpu.utils import environment as env_mod
        from accelerate_tpu.utils.constants import ENV_COMPILATION_CACHE

        monkeypatch.setenv(ENV_COMPILATION_CACHE, "off")
        assert env_mod.configure_compilation_cache() is None

    def test_partial_state_records_dir(self, tmp_path, monkeypatch):
        from accelerate_tpu.state import PartialState
        from accelerate_tpu.utils import environment as env_mod
        from accelerate_tpu.utils.constants import ENV_COMPILATION_CACHE
        from jax.experimental.compilation_cache import compilation_cache as cc

        cache_dir = str(tmp_path / "state-cache")
        monkeypatch.setenv(ENV_COMPILATION_CACHE, cache_dir)
        prev_dir = jax.config.jax_compilation_cache_dir
        try:
            state = PartialState()
            assert state.compilation_cache_dir == cache_dir
            assert os.path.isdir(cache_dir)
        finally:
            jax.config.update("jax_compilation_cache_dir", prev_dir)
            cc.reset_cache()
            env_mod._compilation_cache_dir_applied = None


    def test_jax_env_var_places_the_cache_and_nothing_moves_it(
            self, tmp_path, monkeypatch):
        """Where JAX_COMPILATION_CACHE_DIR is set, the cache is kept there:
        `configure_compilation_cache()` returns that directory and points
        jax at NO other path — the repo's own variable no longer beats
        it, and the in-checkout default is not applied."""
        from accelerate_tpu.utils import environment as env_mod
        from accelerate_tpu.utils.constants import ENV_COMPILATION_CACHE

        placed = str(tmp_path / "placed-from-outside")
        monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", placed)
        monkeypatch.setenv(ENV_COMPILATION_CACHE, str(tmp_path / "ours"))
        updates = []
        real_update = jax.config.update

        def spy(name, value):
            updates.append((name, value))
            return real_update(name, value)

        monkeypatch.setattr(jax.config, "update", spy)
        # jax reads the variable itself at import; model that state
        prev_dir = jax.config.jax_compilation_cache_dir
        real_update("jax_compilation_cache_dir", placed)
        try:
            assert env_mod.configure_compilation_cache() == placed
            assert jax.config.jax_compilation_cache_dir == placed
            assert [u for u in updates
                    if u[0] == "jax_compilation_cache_dir"] == []
            assert not os.path.exists(str(tmp_path / "ours"))
        finally:
            real_update("jax_compilation_cache_dir", prev_dir)
            env_mod._compilation_cache_dir_applied = None

    def test_default_is_one_fixed_directory_inside_the_checkout(
            self, monkeypatch):
        """Unset everywhere: `<checkout>/.jax_cache` — not under the
        user's home, and not built from a temporary name, a pid or the
        time (the path is part of every cache key)."""
        from accelerate_tpu.utils import environment as env_mod

        repo = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
        monkeypatch.setenv("HOME", "/nonexistent-home")
        monkeypatch.setenv("XDG_CACHE_HOME", "/nonexistent-xdg")
        want = os.path.join(repo, ".jax_cache")
        assert env_mod.default_compilation_cache_dir() == want
        assert env_mod.default_compilation_cache_dir() == want  # stable
        ignored = open(os.path.join(repo, ".gitignore")).read().split()
        assert ".jax_cache/" in ignored

    def test_two_processes_resolve_the_same_directory(self):
        """Two fresh processes (different pids, different start times)
        with no cache variable set configure the SAME directory."""
        import subprocess
        import sys

        from accelerate_tpu.test_utils import checkout_child_env

        code = ("from accelerate_tpu.utils.environment import "
                "configure_compilation_cache as c; print(c())")
        env = checkout_child_env({"JAX_PLATFORMS": "cpu"})
        for var in ("JAX_COMPILATION_CACHE_DIR",
                    "ACCELERATE_TPU_COMPILATION_CACHE"):
            env.pop(var, None)
        outs = [subprocess.run([sys.executable, "-c", code], env=env,
                               capture_output=True, text=True, timeout=120)
                for _ in range(2)]
        paths = [o.stdout.strip().splitlines()[-1] for o in outs]
        repo = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
        assert paths == [os.path.join(repo, ".jax_cache")] * 2, (
            paths, outs[0].stderr[-500:])


# ---------------------------------------------------------------------------
# tier-1 collection guard
# ---------------------------------------------------------------------------


def test_prefetch_tests_are_tier1_collected():
    """The ROADMAP tier-1 command runs `pytest tests/ -m 'not slow'`; the
    fast-path tests in this file must be collected by it (i.e. none are
    gated behind a slow marker or a collection error).

    This guard executing at all proves the file imports and collects under
    the tier-1 flags, so the only property left to check is that no test
    here hides behind a slow marker — read off the AST instead of running
    a nested ``pytest.main`` collection, which cost ~12s of whole-session
    overhead (plugin/rewrite setup against a multi-GB heap) inside the
    full tier-1 run.
    """
    roadmap = os.path.join(os.path.dirname(__file__), os.pardir, "ROADMAP.md")
    with open(roadmap) as f:
        text = f.read()
    assert "-m 'not slow'" in text and "pytest tests/" in text, (
        "tier-1 command changed; update this guard"
    )

    import ast

    with open(os.path.abspath(__file__)) as f:
        tree = ast.parse(f.read())
    names: list = []
    slow_marked: list = []

    def scan(body, prefix=""):
        for node in body:
            if isinstance(node, ast.ClassDef) and node.name.startswith("Test"):
                scan(node.body, prefix=f"{node.name}::")
            elif isinstance(node, (ast.FunctionDef, ast.AsyncFunctionDef)) \
                    and node.name.startswith("test_"):
                names.append(prefix + node.name)
                if any("slow" in ast.dump(dec)
                       for dec in node.decorator_list):
                    slow_marked.append(prefix + node.name)

    scan(tree.body)
    assert len(names) >= 15, names
    assert slow_marked == [], slow_marked
