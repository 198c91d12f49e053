"""The Accelerator facade.

TPU-native analogue of ref src/accelerate/accelerator.py (3409 LoC,
`Accelerator` at :163). The public surface is kept — prepare / accumulate /
backward / clip_grad_norm_ / gather / gather_for_metrics / save_state /
trackers — but the engine underneath is different by design (SURVEY.md §7):

- `prepare()` does not wrap modules in DDP/FSDP/DeepSpeed engines
  (ref :1428-1550); it plans `NamedSharding`s over one mesh and places
  pytrees (sharding/planner.py).
- The hot loop does not orchestrate backward/clip/step eagerly
  (ref :2093-2270); `train_step()` compiles loss, grad, accumulation, clip,
  optimizer update, and the mixed-precision policy into ONE donated XLA
  program. An eager-compatible path (`compute_gradients`/`backward`/`step`)
  remains for reference-style loops.
- Mixed precision is a compile-time dtype policy, not a runtime autocast
  (ref :3293): bf16 compute over fp32 master params; fp16 gets a dynamic
  loss scale (training.DynamicLossScale) replacing torch GradScaler.
"""

from __future__ import annotations

import contextlib
import dataclasses
import functools
import math
import os
import warnings
import weakref
from typing import Any, Callable, Iterable

import jax
import jax.numpy as jnp
import numpy as np
import optax

from .data import DataLoaderDispatcher, DataLoaderShard, prepare_data_loader, skip_first_batches
from .logging import get_logger
from .optimizer import AcceleratedOptimizer
from .scheduler import AcceleratedScheduler
from .sharding import (
    plan_optimizer_sharding,
    plan_sharding,
    shard_pytree,
    transformer_rules,
)
from .state import AcceleratorState, GradientState, PartialState
from .telemetry.cost import CostTable, fence as _cost_fence, resolve_sample_every
from .telemetry.export import start_metrics_server
from .models.common import part
from .telemetry.registry import get_registry
from .telemetry.trace import span
from .telemetry.watchdog import StallWatchdog, resolve_stall_timeout
from .training import (
    DynamicLossScale,
    TrainState,
    cast_floating,
    clip_by_global_norm,
)
from .utils import operations as ops
from .utils.dataclasses import (
    AutocastKwargs,
    ContextParallelPlugin,
    DataLoaderConfiguration,
    DeepSpeedPlugin,
    FP8RecipeKwargs,
    FullyShardedDataParallelPlugin,
    GradientAccumulationPlugin,
    InitProcessGroupKwargs,
    JitConfig,
    KwargsHandler,
    MegatronLMPlugin,
    MeshConfig,
    PrecisionType,
    ProjectConfiguration,
)
from .utils.memory import release_memory

logger = get_logger(__name__)


def _is_params_pytree(obj: Any) -> bool:
    if not isinstance(obj, dict) or not obj:
        return False
    leaves = jax.tree_util.tree_leaves(obj)
    return bool(leaves) and all(
        isinstance(l, (jax.Array, np.ndarray)) or hasattr(l, "shape") for l in leaves
    )


def _is_optimizer(obj: Any) -> bool:
    return isinstance(obj, optax.GradientTransformation) or (
        hasattr(obj, "init") and hasattr(obj, "update") and not isinstance(obj, TrainState)
    )


def _is_dataloader(obj: Any) -> bool:
    if isinstance(obj, (DataLoaderShard, DataLoaderDispatcher)):
        return True
    return hasattr(obj, "__iter__") and not isinstance(obj, (dict, str, bytes))


class _CompiledTrainStep:
    """Jit wrapper that pins the output TrainState's shardings to the
    input's shardings, with cached (near-zero host cost) steady-state
    dispatch.

    Without the pin, XLA is free to pick output shardings for the new
    state (normalized specs, replicated-in sharded-out small leaves), the
    second call sees differently-sharded inputs, and the whole program
    compiles twice — minutes of wasted compile at real model sizes and a
    layout reshuffle between steps. Pinning out == in makes step 1 the
    steady state and keeps donation layouts exact.

    The pin is keyed by the input state's (treedef, per-leaf sharding)
    layout, so a step reused after re-preparing under a different mesh/plan
    (new Accelerator in a notebook, differently-laid-out checkpoint restore)
    gets a fresh jit with matching pins rather than outputs silently forced
    back to a stale layout. The treedef is part of the key: two states with
    different structures but identical flattened shardings must not share a
    jit whose out_shardings pytree was built from the first structure.

    Dispatch cost: because out == in is pinned, the state RETURNED by a call
    is guaranteed to have the layout of the state passed in — so the common
    `state, m = step(state, batch)` loop is recognized by object identity
    (a weakref to the last output) and skips the per-leaf layout walk
    entirely. The pin tree itself is computed only on a layout-cache miss
    (`_pin_computations` counts these; it stays at 1 for a fixed state
    structure no matter how many steps run).

    `warmup()` AOT-compiles eagerly (e.g. while the input pipeline fills)
    and the resulting executable serves subsequent calls, so step 1 of the
    training loop pays dispatch only, not trace+compile.
    """

    def __init__(self, step_fn: Callable, donate: bool,
                 strict: str | None = None, contract=None,
                 replication_threshold: int = 1 << 26,
                 on_finding: Callable | None = None,
                 cost_table: CostTable | None = None,
                 cost_name: str = "train_step"):
        self._step_fn = step_fn
        self._donate = donate
        self._by_layout: dict = {}   # (treedef, leaf shardings) -> jitted
        self._aot: dict = {}         # (layout key, batch signature) -> compiled
        self._last: tuple | None = None  # (weakref(last out state), fn, jitted)
        self._pin_computations = 0   # pin-tree builds (cache misses)
        self._aot_compiles = 0       # AOT lower+compile runs (cache misses)
        self._on_dispatch: Callable | None = None  # telemetry hook
        # strict mode (ISSUE 4): program passes run ONCE per
        # (layout, batch signature) at trace time — the audit rides the
        # warmup/AOT path, so the compile it needs is the compile the
        # dispatch cache keeps; steady-state calls never re-audit
        self._strict = strict
        self._contract = contract
        self._replication_threshold = replication_threshold
        self._on_finding = on_finding
        # akey -> None (audited clean/warned) | AnalysisViolation (cached:
        # re-raised on every later dispatch attempt WITHOUT re-running the
        # audit, so telemetry counts each finding once)
        self._audited: dict = {}
        # device-cost attribution (ISSUE 11): the static FLOPs/bytes of
        # each compiled variant land in `cost_table` once per akey (the
        # same key the AOT/audit caches use), and every Kth dispatch is
        # fence-timed into program_device_time_seconds{program=train_step}
        # — MFU from MEASURED device time, not free-running wall windows
        self._cost = cost_table
        self._cost_name = cost_name
        self._cost_keys: set = set()

    def _layout_key(self, state):
        leaves, treedef = jax.tree_util.tree_flatten(state)
        # pin only mesh-placed leaves (NamedSharding, i.e. the state went
        # through prepare): an unprepared state's single-device leaves must
        # stay unspecified or they'd conflict with mesh-wide shard_map
        # calls inside the model (mixtral a2a)
        pins = tuple(
            leaf.sharding
            if isinstance(leaf, jax.Array)
            and isinstance(leaf.sharding, jax.sharding.NamedSharding)
            else None
            for leaf in leaves
        )
        return (treedef, pins)

    def _ensure(self, state):
        key = self._layout_key(state)
        jitted = self._by_layout.get(key)
        if jitted is None:
            self._pin_computations += 1
            pins = jax.tree_util.tree_unflatten(key[0], list(key[1]))
            # metrics stay unspecified (None) — constraining a potentially
            # large user aux pytree to replicated would force a gather
            jitted = jax.jit(
                self._step_fn,
                donate_argnums=(0,) if self._donate else (),
                out_shardings=(pins, None),
            )
            self._by_layout[key] = jitted
        return jitted, key

    @staticmethod
    def _batch_sig(batch):
        return (
            jax.tree_util.tree_structure(batch),
            tuple(
                (tuple(leaf.shape), str(leaf.dtype))
                if hasattr(leaf, "shape") and hasattr(leaf, "dtype")
                else repr(leaf)
                for leaf in jax.tree_util.tree_leaves(batch)
            ),
        )

    def warmup(self, state, *batch):
        """Eagerly AOT-compile for this state layout and batch shape WITHOUT
        executing a step (no buffers are donated, no arrays change). Returns
        the compiled executable; subsequent `__call__`s with matching
        shapes dispatch straight to it. With the persistent compilation
        cache enabled (utils.environment.configure_compilation_cache), a
        relaunch's warmup deserializes instead of recompiling."""
        jitted, key = self._ensure(state)
        # keyed by (layout, batch signature) — NOT one slot per layout:
        # alternating warmups across two batch shapes must each stay
        # cached instead of evicting one another and recompiling every
        # time (tests/test_prefetch.py::TestWarmup)
        akey = (key, self._batch_sig(batch))
        compiled = self._aot.get(akey)
        if compiled is None:
            self._aot_compiles += 1
            lowered = jitted.lower(state, *batch)
            if self._cost is not None and akey not in self._cost_keys:
                # static cost capture rides the lowering the compile
                # needs anyway — zero extra work, once per (layout,
                # batch sig); a re-warm for a new shape refreshes the
                # entry. The LOWERED (pre-partition) stage reports
                # GLOBAL FLOPs, matching the cost table's
                # peak-x-num_chips denominator (the Compiled stage is
                # the post-SPMD per-device program — registering it
                # would silently flip the entry's meaning per path)
                self._cost_keys.add(akey)
                self._cost.register(self._cost_name, lowered, replace=True)
            compiled = self._aot[akey] = lowered.compile()
            # drop the identity fast path: it would keep dispatching to the
            # callable captured before this warmup and never consult the
            # fresh executable (e.g. warming up for an upcoming batch-shape
            # change mid-loop)
            self._last = None
        if self._strict is not None:
            # strict-mode program passes over the freshly compiled step:
            # declared CollectiveContract, host-transfer scan, replication
            # audit. The once-per-key cache / count-once / warn-survives
            # semantics live in run_cached_audit, shared with the serving
            # engine's per-program audit.
            from .analysis.findings import run_cached_audit
            from .analysis.program import audit_compiled_step

            run_cached_audit(
                self._audited, akey, self._strict,
                lambda: audit_compiled_step(
                    compiled, state=state, contract=self._contract,
                    replication_threshold=self._replication_threshold),
                on_finding=self._on_finding,
                label="the compiled train step",
            )
        return compiled

    def __call__(self, state, *batch):
        # sampled device-time measurement: every Kth call pays a fence
        # pair so the TRUE device step duration (not the async dispatch)
        # lands in the cost table's histogram. Host-side only — the
        # compiled program and the dispatch caches are untouched.
        sampling = (self._cost is not None
                    and self._cost.sample_due(self._cost_name))
        if sampling:
            if not self._cost.has(self._cost_name):
                # plain-jit path that never warmed: capture the static
                # cost from a lowering once (tracing cost only)
                try:
                    self._cost.register(self._cost_name,
                                        self.lower(state, *batch))
                except Exception:
                    pass
            _cost_fence(state)
            compiles_before = self._aot_compiles + self._cache_size()
            t0 = self._cost.clock()
        with span("accelerate_tpu.train_step.dispatch"):
            last = self._last
            if last is not None and last[0]() is state:
                # steady state: this state object IS our previous output,
                # whose layout the out_shardings pin fixed — no tree walk
                # needed
                fn, jitted = last[1], last[2]
            else:
                jitted, key = self._ensure(state)
                akey = (key, self._batch_sig(batch))
                if (self._strict is not None
                        and self._audited.get(akey, False) is not None):
                    # not recorded clean: unaudited (trace-time audit rides
                    # the AOT compile — zero extra compiles) or a cached
                    # violation warmup re-raises
                    fn = self.warmup(state, *batch)
                else:
                    fn = self._aot.get(akey, jitted)
            try:
                out = fn(state, *batch)
            except (TypeError, ValueError):
                if fn is jitted:
                    raise
                # batch shape/dtype drifted from the signature this
                # executable was warmed for (the identity fast path skips
                # the signature check); the AOT executable rejects the
                # args before any donation, so retrying is safe — first
                # against another warmed executable for this
                # (layout, signature), else the jit path. The executable
                # that just failed must never be retried (its rejection
                # may not be signature-visible, e.g. device drift).
                failed = fn
                jitted, key = self._ensure(state)
                akey = (key, self._batch_sig(batch))
                if (self._strict is not None
                        and self._audited.get(akey, False) is not None):
                    # the drifted signature was never audited (or carries a
                    # cached violation) — the retry must NOT sidestep strict
                    # mode via the bare jit path
                    fn = self.warmup(state, *batch)
                else:
                    fn = self._aot.get(akey)
                if fn is None or fn is failed:
                    fn = jitted
                try:
                    out = fn(state, *batch)
                except (TypeError, ValueError):
                    if fn is jitted:
                        raise
                    fn = jitted
                    out = jitted(state, *batch)
            try:
                ref = weakref.ref(out[0])
            except TypeError:  # plain-container states (dicts) aren't weakref-able
                ref = None
            self._last = None if ref is None else (ref, fn, jitted)
        if sampling:
            _cost_fence(out)
            # a sampled call that COMPILED (first sight of a new layout /
            # batch signature, on either the AOT or plain-jit path) must
            # not record: a 30s compile logged as one 'device time'
            # sample would poison the mean/p99 and the derived MFU gauge
            if self._aot_compiles + self._cache_size() == compiles_before:
                self._cost.record_device_time(self._cost_name,
                                              self._cost.clock() - t0)
        if self._on_dispatch is not None:
            self._on_dispatch()
        return out

    def lower(self, state, *batch):
        return self._ensure(state)[0].lower(state, *batch)

    def _cache_size(self) -> int:
        return sum(j._cache_size() for j in self._by_layout.values())


class Accelerator:
    """ref accelerator.py:163. One instance per process; state is global."""

    def __init__(
        self,
        *,
        device_placement: bool = True,
        split_batches: bool = False,
        mixed_precision: str | PrecisionType | None = None,
        gradient_accumulation_steps: int = 1,
        cpu: bool = False,
        dataloader_config: DataLoaderConfiguration | None = None,
        deepspeed_plugin: DeepSpeedPlugin | None = None,
        fsdp_plugin: FullyShardedDataParallelPlugin | None = None,
        megatron_lm_plugin: MegatronLMPlugin | None = None,
        context_parallel_plugin: ContextParallelPlugin | None = None,
        mesh_config: MeshConfig | None = None,
        sharding_rules=None,
        rng_types: list | None = None,
        log_with=None,
        project_dir: str | None = None,
        project_config: ProjectConfiguration | None = None,
        gradient_accumulation_plugin: GradientAccumulationPlugin | None = None,
        step_scheduler_with_optimizer: bool = True,
        jit_config: JitConfig | None = None,
        gradient_clipping: float | None = None,
        kwargs_handlers: list | None = None,
        metrics_port: int | None = None,
        stall_timeout_s: float | None = None,
        cost_sample_every: int | None = None,
        strict: str | None = None,
    ):
        self.project_configuration = project_config or ProjectConfiguration(
            project_dir=project_dir
        )
        if project_dir is not None and self.project_configuration.project_dir is None:
            self.project_configuration.set_directories(project_dir)

        # --- kwargs handlers (ref accelerator.py:338-376) --------------------
        # AutocastKwargs(enabled=False) pins compute to f32 (the XLA analogue
        # of exiting torch.autocast); InitProcessGroupKwargs.timeout reaches
        # jax.distributed.initialize; FP8RecipeKwargs rides into fp8 helpers.
        self.autocast_handler: AutocastKwargs | None = None
        self.init_handler: InitProcessGroupKwargs | None = None
        self.fp8_recipe_handler: FP8RecipeKwargs | None = None
        for handler in kwargs_handlers or []:
            if not isinstance(handler, KwargsHandler):
                raise ValueError(
                    f"Unsupported kwargs handler {handler!r}: expected a "
                    "KwargsHandler instance (AutocastKwargs, "
                    "InitProcessGroupKwargs, FP8RecipeKwargs)."
                )
            for attr, cls in (
                ("autocast_handler", AutocastKwargs),
                ("init_handler", InitProcessGroupKwargs),
                ("fp8_recipe_handler", FP8RecipeKwargs),
            ):
                if isinstance(handler, cls):
                    if getattr(self, attr) is not None:
                        raise ValueError(
                            f"You can only pass one {cls.__name__} in "
                            "kwargs_handlers."
                        )
                    setattr(self, attr, handler)
                    break
            else:
                raise ValueError(
                    f"Unsupported kwargs handler type "
                    f"{type(handler).__name__}: GradScaler/DDP handlers have "
                    "no TPU meaning (mesh plugins configure parallelism; see "
                    "MeshConfig)."
                )

        # --- plugin resolution from the launch env protocol ------------------
        # `accelerate-tpu config`/`launch` serialize ZeRO/FSDP/CP choices as
        # ACCELERATE_TPU_* env (utils/constants.py) so a saved yaml is
        # launch-ready with no hand-edits (replaces ref env promotion
        # ACCELERATE_USE_* state.py:892-910). Explicit plugins always win.
        from .utils.constants import (
            ENV_CP_DEGREE,
            ENV_CP_MODE,
            ENV_FSDP_STRATEGY,
            ENV_ZERO_STAGE,
        )

        if deepspeed_plugin is None and os.environ.get(ENV_ZERO_STAGE):
            deepspeed_plugin = DeepSpeedPlugin(
                zero_stage=int(os.environ[ENV_ZERO_STAGE])
            )
        if fsdp_plugin is None and os.environ.get(ENV_FSDP_STRATEGY):
            fsdp_plugin = FullyShardedDataParallelPlugin(
                sharding_strategy=os.environ[ENV_FSDP_STRATEGY]
            )
        env_cp_mode = os.environ.get(ENV_CP_MODE)
        if context_parallel_plugin is None and env_cp_mode and env_cp_mode != "none":
            context_parallel_plugin = ContextParallelPlugin(
                mode=env_cp_mode,
                seq_degree=int(os.environ.get(ENV_CP_DEGREE, "2")),
            )

        # --- mesh resolution: explicit > env > plugins > default DP ----------
        self.deepspeed_plugin = deepspeed_plugin
        self.fsdp_plugin = fsdp_plugin
        self.megatron_lm_plugin = megatron_lm_plugin
        self.context_parallel_plugin = context_parallel_plugin
        resolved_mesh = mesh_config or MeshConfig.from_env()
        if resolved_mesh is None:
            axes: dict[str, int] = {}
            for plugin in (fsdp_plugin, deepspeed_plugin, megatron_lm_plugin,
                           context_parallel_plugin):
                if plugin is not None:
                    for a, s in plugin.to_mesh_axes().items():
                        axes[a] = s
            from .utils.constants import AXIS_DATA

            wilds = [a for a, s in axes.items() if s == -1]
            if len(wilds) > 1:
                # Two fill-the-rest axes (e.g. FSDP's fsdp=-1 plus a
                # default-degree CP plugin's seq=-1) is ambiguous. Keep the
                # FIRST — plugin order puts the memory-critical sharding
                # axes (fsdp/zero) before seq — and say what was dropped,
                # instead of silently losing parameter sharding.
                for a in wilds[1:]:
                    axes.pop(a)
                warnings.warn(
                    f"multiple plugins asked for a fill-the-rest mesh axis "
                    f"({wilds}); keeping {wilds[0]!r} and dropping "
                    f"{wilds[1:]} — pass an explicit degree (e.g. "
                    "ContextParallelPlugin(seq_degree=2)) to combine them.",
                    stacklevel=2,
                )
            if axes and not wilds:
                # a plugin set with only fixed-size axes (e.g. a lone
                # ContextParallelPlugin's seq=N) must still cover every
                # device: data fills the remainder
                axes.setdefault(AXIS_DATA, -1)
            resolved_mesh = MeshConfig(axes=axes) if axes else None
        state_kwargs: dict = {}
        if self.init_handler is not None and self.init_handler.timeout is not None:
            state_kwargs["timeout"] = self.init_handler.timeout
        self.state = AcceleratorState(
            mixed_precision=mixed_precision, cpu=cpu,
            mesh_config=resolved_mesh, **state_kwargs,
        )
        # visible to parallel.context_attention without an Accelerator handle
        self.state.context_parallel_plugin = context_parallel_plugin
        # visible to ops.fp8.resolve_history_len (models' init_fp8_state)
        self.state.fp8_recipe_handler = self.fp8_recipe_handler

        # --- gradient accumulation (ref :421, dataclasses.py:586) ------------
        if gradient_accumulation_plugin is None:
            env_steps = int(os.environ.get("ACCELERATE_TPU_GRADIENT_ACCUMULATION_STEPS",
                                           gradient_accumulation_steps))
            gradient_accumulation_plugin = GradientAccumulationPlugin(num_steps=env_steps)
        self.gradient_state = GradientState(gradient_accumulation_plugin)

        self.device_placement = device_placement
        self.dataloader_config = dataloader_config or DataLoaderConfiguration(
            split_batches=split_batches
        )
        self.rng_types = rng_types
        self.step_scheduler_with_optimizer = step_scheduler_with_optimizer
        self.jit_config = jit_config or JitConfig()
        self.sharding_rules = sharding_rules or transformer_rules()
        if gradient_clipping is None and deepspeed_plugin is not None:
            gradient_clipping = deepspeed_plugin.gradient_clipping
        self.gradient_clipping = gradient_clipping

        self._optimizers: list[AcceleratedOptimizer] = []
        self._schedulers: list[AcceleratedScheduler] = []
        self._dataloaders: list = []
        self._models: list = []
        self._custom_objects: list = []
        self._prepared_params_sharding = None
        self._opt_plan_source = None
        self._shard_opt = True
        self.flag_tensor = None
        self.step = 0

        # trackers (ref :399-402, tracking wired in init_trackers)
        self.log_with = log_with if isinstance(log_with, (list, tuple)) else (
            [log_with] if log_with is not None else []
        )
        self.trackers = []

        # validated before the exporter/watchdog threads start: a bad value
        # must not leak a bound port or a live thread (same ordering as
        # EngineConfig.strict in serving/engine.py)
        if strict is not None and strict not in ("warn", "error"):
            raise ValueError(
                f"strict must be None, 'warn', or 'error'; got {strict!r}")

        # --- telemetry (ISSUE 3): shared registry + opt-in exporter/watchdog
        # The registry is the process-wide default: StepTimer/checkpointing
        # instrumentation lands in the same series the exporter serves.
        # Both background threads are OFF unless asked for (kwarg or env),
        # so plain scripts/tests never grow threads.
        self.telemetry = get_registry()
        self.metrics_server = None
        self.stall_watchdog: StallWatchdog | None = None
        if self.is_main_process:
            self.metrics_server = start_metrics_server(
                metrics_port, registry=self.telemetry)
        wd_timeout = resolve_stall_timeout(stall_timeout_s)
        if wd_timeout is not None:
            self.stall_watchdog = StallWatchdog(
                wd_timeout, name=f"accelerator-rank{self.process_index}"
            ).start()
        self._c_train_steps = self.telemetry.counter(
            "accelerator_train_steps_total")
        self._c_logs = self.telemetry.counter("accelerator_log_calls_total")
        # device-cost attribution (ISSUE 11): static FLOPs/bytes per
        # compiled train step + sampled fence-pair device timing, shared
        # by every train_step() this accelerator builds. Cadence:
        # `cost_sample_every` kwarg, else ACCELERATE_TPU_COST_SAMPLE_EVERY,
        # default every 16th step (one device sync per 16 steps); 0
        # disables sampling.
        self.cost_table = CostTable(
            registry=self.telemetry,
            sample_every=resolve_sample_every(cost_sample_every),
            num_chips=jax.device_count)
        self._cost_names_built = 0

        # --- strict mode (ISSUE 4): transfer guard + trace-time program audit
        # strict="warn" logs implicit device->host transfers and warns on
        # program-pass findings; strict="error" disallows implicit
        # device->host transfers (`float(loss)`, `np.asarray(arr)` — jax
        # raises at the sync site; explicit jax.device_get stays legal) and
        # raises AnalysisViolation at trace time when a train step's lowered
        # program violates its declared CollectiveContract / carries host
        # callbacks. Only the d2h direction is guarded: h2d transfers are
        # how constants and batches are born. The guard is process-global
        # jax config; end_training() restores the previous value.
        self.strict = strict
        self._prev_transfer_guard = None
        if strict is not None:
            self._prev_transfer_guard = getattr(
                jax.config, "jax_transfer_guard_device_to_host", "allow"
            ) or "allow"
            jax.config.update(
                "jax_transfer_guard_device_to_host",
                "log" if strict == "warn" else "disallow",
            )

        # checkpoint hooks (ref :2798,:2964)
        self._save_model_state_pre_hook = {}
        self._load_model_state_pre_hook = {}

    # ------------------------------------------------------------------ state
    @property
    def mesh(self):
        return self.state.mesh

    @property
    def distributed_type(self):
        return self.state.distributed_type

    @property
    def num_processes(self) -> int:
        return self.state.num_processes

    @property
    def process_index(self) -> int:
        return self.state.process_index

    @property
    def local_process_index(self) -> int:
        return self.state.local_process_index

    @property
    def device(self):
        return self.state.device

    @property
    def is_main_process(self) -> bool:
        return self.state.is_main_process

    @property
    def is_local_main_process(self) -> bool:
        return self.state.is_local_main_process

    @property
    def is_last_process(self) -> bool:
        return self.state.is_last_process

    @property
    def mixed_precision(self) -> str:
        return str(self.state.mixed_precision)

    @property
    def compute_dtype(self):
        if self.autocast_handler is not None and not self.autocast_handler.enabled:
            # autocast disabled: compute in full precision regardless of the
            # mixed_precision policy (ref autocast(enabled=False) semantics)
            return jnp.float32
        if self.state.mixed_precision == PrecisionType.BF16:
            return jnp.bfloat16
        if self.state.mixed_precision == PrecisionType.FP16:
            return jnp.float16
        if self.state.mixed_precision == PrecisionType.FP8:
            # fp8 is a matmul-level format (fp8_dense inside the model);
            # everything else — norms, softmax, residuals — runs bf16
            return jnp.bfloat16
        return jnp.float32

    @property
    def gradient_accumulation_steps(self) -> int:
        return self.gradient_state.num_steps

    @gradient_accumulation_steps.setter
    def gradient_accumulation_steps(self, value: int) -> None:
        self.gradient_state.plugin.num_steps = value

    @property
    def sync_gradients(self) -> bool:
        return self.gradient_state.sync_gradients

    @property
    def use_distributed(self) -> bool:
        return self.state.use_distributed

    # ---------------------------------------------------------- process ctl
    def wait_for_everyone(self) -> None:
        self.state.wait_for_everyone()

    def print(self, *args, **kwargs) -> None:
        self.state.print(*args, **kwargs)

    def split_between_processes(self, inputs, apply_padding: bool = False):
        return self.state.split_between_processes(inputs, apply_padding)

    def on_main_process(self, function):
        return self.state.on_main_process(function)

    def on_local_main_process(self, function):
        return self.state.on_local_main_process(function)

    def on_process(self, function, process_index: int = 0):
        return self.state.on_process(function, process_index)

    def main_process_first(self):
        return self.state.main_process_first()

    def local_main_process_first(self):
        return self.state.local_main_process_first()

    # -------------------------------------------------------------- prepare
    def prepare(self, *args, device_placement: list | None = None):
        """Shard/wrap each object by type (ref accelerator.py:1180-1314).

        - param pytree (dict of arrays) -> sharded per the rule planner
        - `TrainState`                  -> params+opt_state sharded
        - optax transformation          -> `AcceleratedOptimizer` (bound to the
                                           params prepared in the same call)
        - iterable / torch DataLoader   -> `DataLoaderShard`
        - schedule callable             -> `AcceleratedScheduler`
        """
        if device_placement is not None and len(device_placement) != len(args):
            raise ValueError(
                f"device_placement has {len(device_placement)} entries for {len(args)} objects"
            )
        # pass 1: params/TrainState (so optimizers can bind to sharded params)
        results: list[Any] = list(args)
        prepared_params = None
        for i, obj in enumerate(args):
            if isinstance(obj, TrainState):
                results[i] = self.prepare_train_state(obj)
                prepared_params = results[i].params
            elif _is_params_pytree(obj):
                results[i] = self.prepare_params(obj)
                prepared_params = results[i]
        # pass 2: everything else
        for i, obj in enumerate(results):
            if isinstance(obj, TrainState) or obj is prepared_params:
                continue
            if _is_optimizer(obj) and not isinstance(obj, AcceleratedOptimizer):
                results[i] = self.prepare_optimizer(obj, params=prepared_params)
            elif isinstance(obj, AcceleratedScheduler):
                pass
            elif callable(obj) and not _is_dataloader(obj) and not _is_params_pytree(obj):
                results[i] = self.prepare_scheduler(obj)
            elif _is_dataloader(obj) and not isinstance(
                obj, (DataLoaderShard, DataLoaderDispatcher)
            ):
                results[i] = self.prepare_data_loader(obj)
        return results[0] if len(results) == 1 else tuple(results)

    def _plan_param_and_opt_sharding(self, params: Any) -> tuple[Any, Any]:
        """(param_plan, opt_plan_source) per the active plugins — the ONE
        place the ZeRO-stage decision tree lives:

        - ZeRO-3 / FSDP FULL_SHARD: params shard; optimizer state follows.
        - ZeRO-1/2: params replicate but the optimizer moments shard —
          planned as if params were fsdp-sharded (GSPMD reduce-scatters
          grads into moment shards and all-gathers only the update delta).
          Without this the stages degenerate to DDP.
        - stage 0 / NO_SHARD / shard_optimizer_state=False: both replicate.

        Also records both plans for the separate `prepare_optimizer` path.
        """
        shard = True
        if self.fsdp_plugin is not None:
            shard = self.fsdp_plugin.shard_params
        elif self.deepspeed_plugin is not None:
            shard = self.deepspeed_plugin.shard_params
        shard_opt = True
        if self.deepspeed_plugin is not None:
            shard_opt = self.deepspeed_plugin.shard_optimizer_state
        param_plan = plan_sharding(
            params, self.mesh, self.sharding_rules, shard_params=shard
        )
        if not shard_opt:
            opt_plan_source = jax.tree_util.tree_map(
                lambda _: jax.sharding.NamedSharding(
                    self.mesh, jax.sharding.PartitionSpec()),
                param_plan,
            )
        elif shard:
            opt_plan_source = param_plan
        else:
            opt_plan_source = plan_sharding(
                params, self.mesh, self.sharding_rules, shard_params=True
            )
        self._prepared_params_sharding = param_plan
        self._opt_plan_source = opt_plan_source
        self._shard_opt = shard_opt
        return param_plan, opt_plan_source

    def prepare_params(self, params: Any) -> Any:
        """Plan + place a parameter pytree (replaces model.to(device) + wrap,
        ref :1411-1550)."""
        plan, _ = self._plan_param_and_opt_sharding(params)
        if not self.device_placement:
            return params
        return shard_pytree(params, plan)

    def prepare_model(self, model: Any, device_placement: bool | None = None) -> Any:
        """Parity alias (ref :1316): params pytrees are the model here."""
        if _is_params_pytree(model):
            return self.prepare_params(model)
        if isinstance(model, TrainState):
            return self.prepare_train_state(model)
        self._models.append(model)
        return model

    def prepare_train_state(self, ts: TrainState) -> TrainState:
        param_plan, opt_plan_source = self._plan_param_and_opt_sharding(
            ts.params
        )
        params = shard_pytree(ts.params, param_plan)
        opt_plan = plan_optimizer_sharding(ts.tx, ts.opt_state, opt_plan_source, self.mesh)
        self._warn_unsharded_quantized_moments(opt_plan)
        # Optimizers whose init returns the params THEMSELVES as state
        # (optax.contrib.schedule_free's z, lookahead's slow weights) make
        # the donated fused step hand XLA the same buffer twice ("Attempt to
        # donate the same buffer twice"), and on the CPU collective backend
        # the failed replicated Execute wedges every later collective. Copy
        # exactly the aliased leaves before placement.
        param_ids = {id(l) for l in jax.tree_util.tree_leaves(ts.params)}
        opt_state_src = jax.tree_util.tree_map(
            lambda x: jnp.array(x) if id(x) in param_ids else x, ts.opt_state
        )
        opt_state = shard_pytree(opt_state_src, opt_plan)
        needs_scale = self.state.mixed_precision == PrecisionType.FP16
        # Place the remaining leaves on the mesh too: a stray
        # SingleDeviceSharding leaf forces train_step to recompile on its
        # second call when XLA's output shardings replace it
        # (tests/test_compiled_contracts.py::TestJitCacheStability).
        replicated = jax.sharding.NamedSharding(
            self.mesh, jax.sharding.PartitionSpec()
        )
        place_rep = lambda tree: jax.tree_util.tree_map(  # noqa: E731
            lambda x: jax.device_put(x, replicated), tree
        )
        loss_scale = (
            ts.loss_scale
            if ts.loss_scale is not None or not needs_scale
            else DynamicLossScale.create()
        )
        return dataclasses.replace(
            ts,
            params=params,
            opt_state=opt_state,
            step=jax.device_put(ts.step, replicated),
            # grads shard like the optimizer moments (ZeRO-2 semantics:
            # the accumulation buffer is the persistent gradient store)
            grad_accum=(
                shard_pytree(ts.grad_accum, opt_plan_source)
                if ts.grad_accum is not None
                else None
            ),
            loss_scale=place_rep(loss_scale),
            fp8_state=place_rep(ts.fp8_state),
        )

    def _warn_unsharded_quantized_moments(self, opt_plan: Any) -> None:
        """8-bit Adam x ZeRO composition check, surfaced at prepare() time
        (ADVICE r4): quantized moments shard along their blocks dim on the
        fsdp axis; if a block count doesn't divide, that moment replicates
        and the ZeRO memory saving silently shrinks — tell the user here,
        not in a rank-0 log line after the first step."""
        from .sharding.planner import count_replicated_quantized
        from .utils.constants import AXIS_FSDP

        if not getattr(self, "_shard_opt", True):
            return  # replication was requested; nothing to warn about
        fsdp_size = dict(self.mesh.shape).get(AXIS_FSDP, 1)
        if fsdp_size <= 1:
            return
        n_replicated, n_total = count_replicated_quantized(opt_plan)
        if n_replicated:
            warnings.warn(
                f"{n_replicated} of {n_total} adamw_8bit quantized "
                f"moments have block counts that do not divide the fsdp axis "
                f"({fsdp_size}) and will REPLICATE — the optimizer-state "
                "memory saving of ZeRO shrinks accordingly. Pad parameter "
                "sizes to multiples of 256*fsdp or use plain optax.adamw "
                "under ZeRO.",
                stacklevel=3,
            )

    def prepare_optimizer(
        self, tx, params: Any = None, device_placement: bool | None = None
    ) -> AcceleratedOptimizer:
        """ref :2011. Binds the optax transformation to prepared params."""
        opt_sharding = None
        if params is not None and self._prepared_params_sharding is not None:
            opt_state = tx.init(params)
            # _opt_plan_source already encodes the full ZeRO decision tree
            # (_plan_param_and_opt_sharding), including the replicate-all
            # case for shard_optimizer_state=False
            source = self._opt_plan_source or self._prepared_params_sharding
            opt_sharding = plan_optimizer_sharding(
                tx, opt_state, source, self.mesh
            )
            self._warn_unsharded_quantized_moments(opt_sharding)
            opt_state = shard_pytree(opt_state, opt_sharding)
            opt = AcceleratedOptimizer(
                tx, params=params, opt_state=opt_state,
                param_sharding=self._prepared_params_sharding,
                opt_sharding=opt_sharding,
            )
        else:
            opt = AcceleratedOptimizer(tx, params=params)
        self._optimizers.append(opt)
        return opt

    def prepare_data_loader(self, data_loader, device_placement: bool | None = None,
                            slice_fn_for_dispatch=None):
        """ref :1958."""
        put_on_device = (
            device_placement if device_placement is not None else self.device_placement
        )
        prepared = prepare_data_loader(
            data_loader,
            put_on_device=put_on_device,
            rng_types=self.rng_types,
            mesh=self.mesh,
            config=self.dataloader_config,
        )
        self._dataloaders.append(prepared)
        return prepared

    def prepare_scheduler(self, schedule: Callable) -> AcceleratedScheduler:
        """ref :2052."""
        sched = AcceleratedScheduler(
            schedule,
            self._optimizers,
            step_with_optimizer=self.step_scheduler_with_optimizer,
            split_batches=self.dataloader_config.split_batches,
        )
        self._schedulers.append(sched)
        return sched

    # ------------------------------------------------------------- hot loop
    @contextlib.contextmanager
    def accumulate(self, *models):
        """ref accelerator.py:1025-1059. Tracks the micro-step counter and
        flips `sync_gradients` at accumulation boundaries (or end of epoch
        when `sync_with_dataloader`)."""
        self.step += 1
        end = (
            self.gradient_state.sync_with_dataloader
            and self.gradient_state.end_of_dataloader
        )
        sync = (
            self.step % self.gradient_state.num_steps == 0
            or end
            or self.gradient_state.plugin.sync_each_batch
        )
        self.gradient_state._set_sync_gradients(sync)
        yield

    @contextlib.contextmanager
    def no_sync(self, model=None):
        """ref :910-948. Forces accumulation (no optimizer step)."""
        prev = self.gradient_state.sync_gradients
        self.gradient_state._set_sync_gradients(False)
        try:
            yield
        finally:
            self.gradient_state._set_sync_gradients(prev)

    def compute_gradients(
        self, loss_fn: Callable, params: Any, *batch, has_aux: bool = False
    ):
        """Jitted value_and_grad with the mixed-precision policy applied —
        the functional stand-in for `loss.backward()` (ref :2093). Returns
        (loss, grads) or ((loss, aux), grads)."""
        if self.state.mixed_precision == PrecisionType.FP8:
            # the eager path has nowhere to thread the delayed-scaling metas;
            # running it in bf16 would silently drop the fp8 the user asked
            # for
            raise NotImplementedError(
                "mixed_precision='fp8' requires the fused "
                "accelerator.train_step() path (it threads Fp8Meta state "
                "through TrainState); the eager compute_gradients/backward "
                "chain does not support fp8."
            )
        fn = self._grad_fn_cache_get(loss_fn, has_aux)
        return fn(params, *batch)

    def _grad_fn_cache_get(self, loss_fn, has_aux):
        cache = getattr(self, "_grad_fns", None)
        if cache is None:
            cache = self._grad_fns = {}
        key = (id(loss_fn), has_aux)
        if key not in cache:
            dtype = self.compute_dtype

            def wrapped(params, *batch):
                cparams = cast_floating(params, dtype)
                return loss_fn(cparams, *batch)

            cache[key] = jax.jit(jax.value_and_grad(wrapped, has_aux=has_aux))
        return cache[key]

    def backward(self, loss_or_grads: Any = None, *, grads: Any = None, **kwargs) -> None:
        """Accumulate gradients scaled by 1/num_steps (ref :2093-2125).

        In the functional world the caller passes *gradients* (from
        `compute_gradients`); passing a bare loss raises with guidance."""
        if grads is None:
            grads = loss_or_grads
        if grads is None or not jax.tree_util.tree_leaves(grads):
            raise ValueError(
                "accelerator.backward needs gradients: "
                "loss, grads = accelerator.compute_gradients(loss_fn, params, batch); "
                "accelerator.backward(grads)"
            )
        if isinstance(grads, (jax.Array, np.ndarray)) and np.ndim(grads) == 0:
            raise ValueError(
                "Got a scalar loss. JAX has no backward tape: compute grads with "
                "accelerator.compute_gradients(...) and pass them here, or use the "
                "fused accelerator.train_step(...)."
            )
        scale = 1.0 / self.gradient_state.num_steps
        for opt in self._optimizers:
            opt.accumulate_grads(grads, scale)

    def clip_grad_norm_(self, parameters=None, max_norm: float = 1.0, norm_type: int = 2):
        """ref :2221-2270. Clips all prepared optimizers' gradient buffers as
        ONE group (matching torch's clip over the full parameter list) and
        returns the joint pre-clip global norm. `parameters` is accepted for
        signature parity but gradients live on the optimizer facades here."""
        if norm_type != 2:
            raise NotImplementedError("only L2 global-norm clipping is supported")
        if not self.sync_gradients:
            return None
        buffers = [o.gradients for o in self._optimizers if o.gradients is not None]
        if not buffers:
            return None
        norm = optax.global_norm(buffers)
        factor = jnp.minimum(1.0, max_norm / (norm + 1e-6))
        for opt in self._optimizers:
            if opt.gradients is not None:
                opt.gradients = jax.tree_util.tree_map(
                    lambda g: g * factor, opt.gradients
                )
        return norm

    def clip_grad_value_(self, parameters=None, clip_value: float = 1.0):
        """ref :2272."""
        if not self.sync_gradients:
            return
        for opt in self._optimizers:
            if opt.gradients is not None:
                opt.gradients = jax.tree_util.tree_map(
                    lambda g: jnp.clip(g, -clip_value, clip_value), opt.gradients
                )

    # ------------------------------------------------- fused compiled path
    def train_step(
        self,
        loss_fn: Callable,
        has_aux: bool = False,
        max_grad_norm: float | None = None,
        donate: bool = True,
        contract=None,
        replication_threshold: int = 1 << 26,
    ) -> Callable:
        """Compile (TrainState, batch) -> (TrainState, metrics): forward,
        backward, 1/k accumulation, clip, optimizer update, loss-scale — one
        XLA program (replaces the eager chain in SURVEY.md §3.3).

        Gradient accumulation uses an in-state buffer: the optimizer applies
        every `gradient_accumulation_steps` calls (micro-step counter lives in
        the state; XLA `cond` gates the apply), so the Python loop stays a
        flat `for batch: state, m = step(state, batch)`.

        `contract` (an `analysis.CollectiveContract`) declares the step's
        expected collective structure; with `Accelerator(strict=...)` the
        lowered program is checked against it at trace time — plus a
        host-transfer scan and a replication audit of state leaves above
        `replication_threshold` bytes (default 64 MiB). Findings land in the
        telemetry registry as `analysis_findings_total{rule=...}`.
        """
        k = self.gradient_accumulation_steps
        dtype = self.compute_dtype
        max_grad_norm = (
            max_grad_norm if max_grad_norm is not None else self.gradient_clipping
        )
        use_scale = self.state.mixed_precision == PrecisionType.FP16
        use_fp8 = self.state.mixed_precision == PrecisionType.FP8
        if use_fp8:
            import inspect

            try:
                sig_params = inspect.signature(loss_fn).parameters
            except (TypeError, ValueError):
                sig_params = {}
            accepts_fp8 = "fp8_state" in sig_params or any(
                p.kind == inspect.Parameter.VAR_KEYWORD
                for p in sig_params.values()
            )
            if not accepts_fp8:
                raise ValueError(
                    "mixed_precision='fp8' needs a loss_fn that accepts an "
                    "fp8_state kwarg and returns (loss, new_fp8_state) — e.g. "
                    "models.llama.causal_lm_loss. fp8 never silently degrades "
                    "to full precision."
                )

        def step_fn(state: TrainState, *batch):
            if use_scale and state.loss_scale is None:
                raise ValueError(
                    "fp16 mixed precision needs a loss scale: create the state "
                    "with TrainState.create(use_loss_scale=True) or run it "
                    "through accelerator.prepare()."
                )
            if k > 1 and state.grad_accum is None:
                raise ValueError(
                    "gradient_accumulation_steps>1 needs TrainState.create("
                    "use_grad_accum_buffer=True)"
                )
            if use_fp8 and state.fp8_state is None:
                raise ValueError(
                    "mixed_precision='fp8' needs delayed-scaling state: create "
                    "it with TrainState.create(fp8_state=model.init_fp8_state("
                    "config)) — e.g. models.llama.init_fp8_state. fp8 never "
                    "silently degrades to full precision."
                )

            def compute_loss(params):
                # bf16 policy casts float inputs too (lax convs/dots require
                # matching dtypes). fp16 keeps inputs fp32: targets can
                # overflow fp16's range, and jnp promotion handles the mix.
                # fp8 runs the non-matmul compute in bf16; the fp8 casts
                # happen inside the model's fp8_dense calls.
                cast_batch = batch
                if dtype == jnp.bfloat16:
                    cast_batch = tuple(cast_floating(b, dtype) for b in batch)
                if use_fp8:
                    out = loss_fn(
                        cast_floating(params, dtype), *cast_batch,
                        fp8_state=state.fp8_state,
                    )
                    if has_aux:
                        loss, aux, new_fp8 = out
                    else:
                        loss, new_fp8 = out
                        aux = None
                    return loss, (loss, aux, new_fp8)
                out = loss_fn(cast_floating(params, dtype), *cast_batch)
                loss = out[0] if has_aux else out
                aux = out[1] if has_aux else None
                scaled = loss * state.loss_scale.scale if use_scale else loss
                return scaled, (loss, aux, None)

            grads, (loss, aux, new_fp8) = jax.grad(compute_loss, has_aux=True)(state.params)
            if use_fp8:
                # metas updated every micro-step (amax history is per-step
                # statistics, independent of the accumulation boundary)
                state = dataclasses.replace(state, fp8_state=new_fp8)
            # unscaling and the accumulation buffer are billed with the
            # clip and the update (`training.py`): the step's `optimizer` part
            with part("optimizer"):
                if use_scale:
                    grads = jax.tree_util.tree_map(
                        lambda g: g / state.loss_scale.scale, grads
                    )
                finite = jnp.isfinite(optax.global_norm(grads)) if use_scale else jnp.bool_(True)

            if k > 1:
                # overflowed micro-batches must not poison the buffer: their
                # contribution is zeroed (GradScaler-style skip per micro-step)
                with part("optimizer"):
                    accum = jax.tree_util.tree_map(
                        lambda a, g: a + jnp.where(finite, g, 0.0) / k,
                        state.grad_accum,
                        grads,
                    )
                micro = state.step + 1
                do_apply = micro % k == 0

                def apply(st):
                    g = accum
                    if max_grad_norm is not None:
                        g, _ = clip_by_global_norm(g, max_grad_norm)
                    new = st.apply_gradients(g)
                    return dataclasses.replace(
                        new,
                        grad_accum=jax.tree_util.tree_map(jnp.zeros_like, accum),
                    )

                def skip(st):
                    return dataclasses.replace(
                        st, step=st.step + 1, grad_accum=accum
                    )

                new_state = jax.lax.cond(do_apply, apply, skip, state)
            else:
                g = grads
                if max_grad_norm is not None:
                    g, _ = clip_by_global_norm(g, max_grad_norm)

                def apply(st):
                    return st.apply_gradients(g)

                def skip(st):
                    return dataclasses.replace(st, step=st.step + 1)

                new_state = jax.lax.cond(finite, apply, skip, state)

            if use_scale:
                new_state = dataclasses.replace(
                    new_state, loss_scale=state.loss_scale.update(finite)
                )
            metrics = {"loss": loss}
            if has_aux:
                metrics["aux"] = aux
            return new_state, metrics

        # each built step gets its own cost-table name: two steps (a
        # train and an eval fn) sharing "train_step" would overwrite
        # each other's FLOPs entry and merge their device-time samples
        # into one histogram — a silently wrong MFU
        self._cost_names_built += 1
        n = self._cost_names_built
        step = _CompiledTrainStep(
            step_fn, donate=donate, strict=self.strict, contract=contract,
            replication_threshold=replication_threshold,
            on_finding=self._note_analysis_finding,
            cost_table=self.cost_table,
            cost_name="train_step" if n == 1 else f"train_step_{n}",
        )
        step._on_dispatch = self._note_train_dispatch
        return step

    def _note_analysis_finding(self, finding) -> None:
        """Strict-mode findings surface as telemetry series (scrapeable and
        part of log_telemetry()'s multi-host aggregate)."""
        self.telemetry.counter(
            "analysis_findings_total", rule=finding.rule).inc()

    def _note_train_dispatch(self) -> None:
        """Per-dispatch telemetry heartbeat: counts the step and feeds the
        stall watchdog (a silent multi-host hang then dumps stacks instead
        of burning TPU hours)."""
        self._c_train_steps.inc()
        if self.stall_watchdog is not None:
            self.stall_watchdog.tick()

    def eval_step(self, eval_fn: Callable) -> Callable:
        """Compile an inference/eval function with the precision policy."""
        dtype = self.compute_dtype

        def step_fn(params, *batch):
            cast_batch = batch
            if dtype == jnp.bfloat16:
                cast_batch = tuple(cast_floating(b, dtype) for b in batch)
            return eval_fn(cast_floating(params, dtype), *cast_batch)

        return jax.jit(step_fn)

    # --------------------------------------------------------- collectives
    def gather(self, tensor):
        """ref :2299."""
        return ops.gather(tensor)

    def gather_for_metrics(self, input_data, use_gather_object: bool = False):
        """ref :2331-2403 — gather then drop the duplicated tail samples of
        the final uneven batch (tracked by the dataloader's `remainder`)."""
        try:
            recursively = bool(jax.tree_util.tree_leaves(input_data)) and all(
                isinstance(l, (jax.Array, np.ndarray))
                for l in jax.tree_util.tree_leaves(input_data)
            )
        except Exception:
            recursively = False
        if use_gather_object or not recursively:
            data = ops.gather_object(input_data)
            flattened = [x for sub in data for x in (sub if isinstance(sub, list) else [sub])]
            data = flattened
        else:
            data = self.gather(input_data)
        remainder = self.gradient_state.remainder
        if (
            self.gradient_state.end_of_dataloader
            and remainder is not None
            and remainder > 0
        ):
            layout = self.gradient_state.tail_layout

            def _truncate(x):
                if not hasattr(x, "__getitem__"):
                    return x
                if layout is not None and hasattr(x, "shape"):
                    hosts, padded, real = layout
                    if x.shape[0] == hosts * padded:
                        # gathered order is [host0: real+pad, host1: ...] —
                        # keep each host block's real rows, drop its pads
                        x = np.asarray(x)
                        blocks = x.reshape((hosts, padded) + x.shape[1:])
                        return blocks[:, :real].reshape((hosts * real,) + x.shape[1:])
                return x[:remainder]

            data = jax.tree_util.tree_map(_truncate, data) if recursively else data[:remainder]
        return data

    def reduce(self, tensor, reduction: str = "sum", scale: float = 1.0):
        """ref :2404."""
        return ops.reduce(tensor, reduction, scale)

    def pad_across_processes(self, tensor, dim: int = 0, pad_index: int = 0,
                             pad_first: bool = False):
        """ref :2440."""
        return ops.pad_across_processes(tensor, dim, pad_index, pad_first)

    def broadcast(self, tensor, from_process: int = 0):
        return ops.broadcast(tensor, from_process)

    # --------------------------------------------- early stop coordination
    def set_trigger(self) -> None:
        """ref :2127-2150."""
        self.flag_tensor = np.asarray([1.0], dtype=np.float32)

    def check_trigger(self) -> bool:
        """ref :2152-2184 — true if ANY host set the trigger."""
        local = self.flag_tensor if self.flag_tensor is not None else np.zeros(1, np.float32)
        total = ops.reduce(local, "sum")
        if float(np.asarray(total)[0]) >= 1:
            self.flag_tensor = None
            return True
        return False

    def context_attention(self, q, k, v, causal: bool = True,
                          window: int | None = None):
        """Sequence-parallel attention using the configured
        `ContextParallelPlugin.mode` (ring | ulysses) over this mesh.
        `window` applies Mistral-style sliding-window banding in either
        mode."""
        from .parallel import context_attention as _ca

        mode = (self.context_parallel_plugin.mode
                if self.context_parallel_plugin is not None else None)
        return _ca(q, k, v, causal=causal, mode=mode, mesh=self.mesh,
                   window=window)

    # --------------------------------------------------------- profiling
    def profile(self, logdir: str = "/tmp/accelerate_tpu_trace", **kwargs):
        """Trace XLA execution to TensorBoard/Perfetto (first-class here;
        the reference had no profiler — SURVEY.md §5)."""
        from .profiler import profile as _profile

        return _profile(logdir, **kwargs)

    def step_timer(self, flops_per_step: float = 0.0, tokens_per_step: int = 0,
                   fresh: bool = True, **kwargs):
        from .profiler import StepTimer

        # registry-backed by default: the timer's step/dispatch/stall
        # histograms surface on the Prometheus endpoint and in
        # log_telemetry()'s multi-host aggregate
        kwargs.setdefault("registry", self.telemetry)
        timer = StepTimer(flops_per_step=flops_per_step,
                          tokens_per_step=tokens_per_step, **kwargs)
        if fresh:
            # registry series are shared by name: a NEW timer must not
            # inherit a discarded one's samples (warmup-window pattern).
            # Pass fresh=False to deliberately continue the series.
            timer.reset()
        return timer

    @contextlib.contextmanager
    def join_uneven_inputs(self, joinables, even_batches: bool | None = None):
        """ref :1061-1146. Uneven inputs deadlock here only one way: hosts
        running different LOOP counts (every collective is global). The data
        layer's even_batches recycling already equalizes counts; this
        context's `even_batches` kwarg (ref semantics) temporarily overrides
        the flag on every prepared loader — so an even_batches=False loader
        iterated inside `join_uneven_inputs(..., even_batches=True)` pads to
        equal counts instead of desyncing the world."""
        if even_batches is None:
            yield
            return
        overridden = []
        seen: set[int] = set()

        def _walk(obj, depth=0):
            # prepared loaders nest (DataLoaderShard -> torch DataLoader ->
            # BatchSamplerShard): override every even_batches along the
            # chain — the sampler's flag is what decides iteration counts.
            # The seen-set keeps an object reachable twice (e.g. via a
            # re-prepared loader) from recording its overridden value as
            # "original", which would make the restore stick
            if obj is None or depth > 4 or id(obj) in seen:
                return
            seen.add(id(obj))
            if hasattr(obj, "even_batches"):
                overridden.append((obj, obj.even_batches))
                obj.even_batches = even_batches
            for attr in ("loader", "batch_sampler", "sampler"):
                _walk(getattr(obj, attr, None), depth + 1)

        for dl in self._dataloaders:
            _walk(dl)
        try:
            yield
        finally:
            for obj, old in overridden:
                obj.even_batches = old

    # ----------------------------------------------------------- lifecycle
    def free_memory(self, *objects):
        """ref :3150. Drop prepared references + device caches."""
        self._optimizers = []
        self._schedulers = []
        self._dataloaders = []
        self._models = []
        self._grad_fns = {}
        self.step = 0
        return release_memory(*objects)

    def clear(self, *objects):
        return self.free_memory(*objects)

    def unwrap_model(self, model, keep_fp32_wrapper: bool = True):
        """ref :2475 — no wrappers exist; returns the object unchanged."""
        return model

    @contextlib.contextmanager
    def autocast(self, autocast_handler=None):
        """ref :3293 — precision is a compile-time policy here; context kept
        for source compatibility."""
        yield

    def skip_first_batches(self, dataloader, num_batches: int = 0):
        """ref :3340."""
        return skip_first_batches(dataloader, num_batches)

    # ------------------------------------------------------------ trackers
    def init_trackers(self, project_name: str, config: dict | None = None,
                      init_kwargs: dict | None = None) -> None:
        """ref :2533."""
        from .tracking import filter_trackers

        self.trackers = filter_trackers(
            self.log_with, self.project_configuration.logging_dir, project_name,
            init_kwargs or {},
        )
        if config is not None:
            for tracker in self.trackers:
                tracker.store_init_configuration(config)

    def log(self, values: dict, step: int | None = None, log_kwargs: dict | None = None) -> None:
        """ref :2609."""
        self._c_logs.inc()
        if self.stall_watchdog is not None:
            # log boundaries are heartbeats too: eager-path loops that
            # never call the fused step still feed the watchdog
            self.stall_watchdog.tick()
        self._record_hbm_high_water()
        if self.is_main_process:
            for tracker in self.trackers:
                tracker.log(values, step=step, **((log_kwargs or {}).get(tracker.name, {})))

    def _record_hbm_high_water(self) -> None:
        """Sample HBM-in-use into a high-water gauge (log boundaries only —
        not per step). Backends without memory stats (CPU) record 0."""
        try:
            from .profiler import device_memory_stats

            stats = device_memory_stats()
        except Exception:
            return
        in_use = stats.get("bytes_in_use")
        if in_use is not None:
            self.telemetry.gauge("device_hbm_bytes_in_use_peak").set_max(
                float(in_use))

    def log_telemetry(self, step: int | None = None,
                      aggregate: bool = True) -> dict[str, float]:
        """Snapshot the telemetry registry and fan it out through the
        prepared trackers (the JSONLTracker backend writes one JSONL
        line). With `aggregate=True` on a multi-host world this is a
        COLLECTIVE (call on every process): counters sum globally, gauges
        reduce min/mean/max (per-host HBM high-water -> `__max`),
        histogram sketches merge for true global p50/p99, and each
        histogram carries `__slowest_host_mean` — the straggler view.
        Returns the flat dict that was logged."""
        self._record_hbm_high_water()
        if aggregate and self.num_processes > 1:
            from .telemetry.aggregate import aggregate_flat

            flat = aggregate_flat(self.telemetry)
        else:
            from .telemetry.export import snapshot_for_tracking

            flat = snapshot_for_tracking(self.telemetry)
        if self.is_main_process:
            for tracker in self.trackers:
                tracker.log(flat, step=step)
        return flat

    def get_tracker(self, name: str, unwrap: bool = False):
        """ref :2582."""
        for tracker in self.trackers:
            if tracker.name == name:
                return tracker.tracker if unwrap else tracker
        raise ValueError(f"tracker {name} not initialized; call init_trackers first")

    def end_training(self) -> None:
        """ref :2653."""
        from .checkpointing import wait_for_checkpoints

        try:
            wait_for_checkpoints()
        finally:
            # a failed background checkpoint must not leave trackers open or
            # peers hanging at the barrier
            for tracker in self.trackers:
                tracker.finish()
            if self.metrics_server is not None:
                self.metrics_server.stop()
                self.metrics_server = None
            if self.stall_watchdog is not None:
                self.stall_watchdog.stop()
                self.stall_watchdog = None
            if self._prev_transfer_guard is not None:
                # strict mode armed the process-global transfer guard;
                # hand back the value we found
                jax.config.update(
                    "jax_transfer_guard_device_to_host",
                    self._prev_transfer_guard)
                self._prev_transfer_guard = None
            self.wait_for_everyone()

    # --------------------------------------------------------- checkpoints
    def register_for_checkpointing(self, *objects) -> None:
        """ref :3256. Objects must expose state_dict/load_state_dict."""
        invalid = [o for o in objects if not (
            hasattr(o, "state_dict") and hasattr(o, "load_state_dict")
        )]
        if invalid:
            raise ValueError(
                f"Objects {invalid} lack state_dict/load_state_dict and cannot be "
                "registered for checkpointing"
            )
        self._custom_objects.extend(objects)

    def register_save_state_pre_hook(self, hook: Callable):
        from .hooks_registry import RemovableHandle

        handle = RemovableHandle(self._save_model_state_pre_hook)
        self._save_model_state_pre_hook[handle.id] = hook
        return handle

    def register_load_state_pre_hook(self, hook: Callable):
        from .hooks_registry import RemovableHandle

        handle = RemovableHandle(self._load_model_state_pre_hook)
        self._load_model_state_pre_hook[handle.id] = hook
        return handle

    def save_state(self, output_dir: str | None = None, state: TrainState | None = None,
                   async_save: bool = False, **save_model_kwargs) -> str:
        """ref :2830-2994 + checkpointing.py:51. `async_save=True` overlaps
        the array writes with subsequent steps (drain with
        `wait_for_checkpoints()`; `load_state`/`end_training` drain too)."""
        from .checkpointing import save_accelerator_state

        if output_dir is None:
            if (
                self.project_configuration.total_limit == 1
                and self.project_configuration.automatic_checkpoint_naming
            ):
                # with total_limit=1 the prune in _checkpoint_dir targets the
                # newest existing dir — the only one a previous async save
                # can still be committing (the single AsyncCheckpointer
                # serializes saves). Every process drains its own writer,
                # then a barrier keeps rank 0 from pruning before the other
                # hosts' drains have finished. Larger limits never prune the
                # newest dir, so they keep full async overlap.
                self.wait_for_checkpoints()
                self.wait_for_everyone()
            output_dir = self._checkpoint_dir(new=True)
        for hook in self._save_model_state_pre_hook.values():
            hook(self._models, None, output_dir)
        return save_accelerator_state(
            output_dir,
            train_states=[state] if state is not None else [],
            optimizers=self._optimizers,
            schedulers=self._schedulers,
            dataloaders=self._dataloaders,
            custom_objects=self._custom_objects,
            step=self.step,
            async_save=async_save,
        )

    def wait_for_checkpoints(self) -> int:
        """Drain in-flight async checkpoint saves."""
        from .checkpointing import wait_for_checkpoints

        return wait_for_checkpoints()

    def load_state(self, input_dir: str | None = None, state: TrainState | None = None,
                   **load_model_kwargs):
        """ref :2995-3127."""
        from .checkpointing import load_accelerator_state

        if input_dir is None:
            input_dir = self._checkpoint_dir(new=False)
        for hook in self._load_model_state_pre_hook.values():
            hook(self._models, input_dir)
        result = load_accelerator_state(
            input_dir,
            train_states=[state] if state is not None else [],
            optimizers=self._optimizers,
            schedulers=self._schedulers,
            dataloaders=self._dataloaders,
            custom_objects=self._custom_objects,
        )
        # resume the micro-step counter so accumulate() boundaries line up
        self.step = int(result.get("step", 0))
        return result

    def resume_latest(self, input_dir: str | None = None,
                      state: TrainState | None = None, **kwargs):
        """Preemption-tolerant restart: restore from the newest COMPLETE
        checkpoint (committed manifest, all files present) under
        `input_dir` (default: the project checkpoints dir). Torn saves —
        a crash at any byte offset of a prior save — are skipped. Returns
        the `load_state`-shaped result dict plus `checkpoint_dir` /
        `manifest`, or None when nothing committed exists (fresh start)."""
        from .checkpointing import resume_latest

        if input_dir is None:
            input_dir = os.path.join(
                self.project_configuration.project_dir or ".", "checkpoints")
        for hook in self._load_model_state_pre_hook.values():
            hook(self._models, input_dir)
        result = resume_latest(
            input_dir,
            train_states=[state] if state is not None else [],
            optimizers=self._optimizers,
            schedulers=self._schedulers,
            dataloaders=self._dataloaders,
            custom_objects=self._custom_objects,
            **kwargs,
        )
        if result is not None:
            self.step = int(result.get("step", 0))
        return result

    def _checkpoint_dir(self, new: bool) -> str:
        """Versioned dir resolution. On a shared filesystem, EVERY process
        must agree on the index: the main process lists/prunes and broadcasts
        its decision (independent listings race each other — a straggler can
        see one fewer checkpoint and write into the wrong version)."""
        from .utils.constants import CHECKPOINT_DIR_PREFIX

        base = os.path.join(self.project_configuration.project_dir or ".", "checkpoints")
        if not self.project_configuration.automatic_checkpoint_naming:
            return base
        idx = None
        if self.is_main_process:
            # any exception here MUST still reach the broadcast below, or
            # every other host hangs in the collective waiting for rank 0
            try:
                os.makedirs(base, exist_ok=True)
                existing = sorted(
                    int(d.rsplit("_", 1)[1])
                    for d in os.listdir(base)
                    if d.startswith(CHECKPOINT_DIR_PREFIX + "_")
                    and d.rsplit("_", 1)[1].isdigit()
                )
                if new:
                    idx = (existing[-1] + 1) if existing else 0
                    limit = self.project_configuration.total_limit
                    if limit is not None and len(existing) + 1 > limit:
                        import shutil

                        for old in existing[: len(existing) + 1 - limit]:
                            shutil.rmtree(
                                os.path.join(base, f"{CHECKPOINT_DIR_PREFIX}_{old}"),
                                ignore_errors=True,
                            )
                else:
                    idx = existing[-1] if existing else -1
            except Exception as e:
                idx = f"__error__:{type(e).__name__}: {e}"
        if self.num_processes > 1:
            (idx,) = ops.broadcast_object_list([idx])
        if isinstance(idx, str):
            raise RuntimeError(
                f"checkpoint dir resolution failed on the main process: "
                f"{idx.removeprefix('__error__:')}"
            )
        if idx is None or idx < 0:
            raise FileNotFoundError(f"no checkpoints under {base}")
        if new:
            self.project_configuration.iteration = idx
        return os.path.join(base, f"{CHECKPOINT_DIR_PREFIX}_{idx}")

    def save_model(self, params: Any, save_directory: str,
                   max_shard_size: str | int = "10GB", safe_serialization: bool = True):
        """ref :2691-2797 — portable safetensors export of a (possibly
        sharded) param pytree."""
        from .checkpointing import save_model as _save_model

        return _save_model(params, save_directory, max_shard_size, safe_serialization)

    def get_state_dict(self, model, unwrap: bool = True):
        """ref :3200 — with GSPMD there are no flattened/offloaded wrappers;
        gather shards to host for export."""
        if isinstance(model, TrainState):
            model = model.params
        return jax.tree_util.tree_map(lambda x: np.asarray(ops._to_local(x)), model)
