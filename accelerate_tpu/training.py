"""TrainState: the unit of training the fused path operates on.

The reference's `prepare()` returns wrapped *objects* (DDP module, optimizer,
scheduler) that coordinate eagerly per step (SURVEY.md §3.3). TPU-natively the
unit is one pytree carrying (params, opt_state, step, accumulation buffer,
loss scale) so the whole update — forward, backward, accumulate, clip,
optimizer, schedule — compiles into a single donated XLA program.
"""

from __future__ import annotations

import dataclasses
import os
import signal
import threading
from typing import Any, Callable

import jax
import jax.numpy as jnp
import optax

from .models.common import part


@jax.tree_util.register_dataclass
@dataclasses.dataclass
class DynamicLossScale:
    """fp16 dynamic loss scaling — replaces torch.cuda.amp.GradScaler
    (ref accelerator.py:455-479). bf16 never needs it; kept for fp16 parity."""

    scale: jax.Array
    growth_tracker: jax.Array
    growth_interval: int = dataclasses.field(default=2000, metadata={"static": True})
    growth_factor: float = dataclasses.field(default=2.0, metadata={"static": True})
    backoff_factor: float = dataclasses.field(default=0.5, metadata={"static": True})

    @classmethod
    def create(cls, init_scale: float = 2.0**16) -> "DynamicLossScale":
        return cls(
            scale=jnp.asarray(init_scale, jnp.float32),
            growth_tracker=jnp.asarray(0, jnp.int32),
        )

    def update(self, grads_finite: jax.Array) -> "DynamicLossScale":
        tracker = jnp.where(grads_finite, self.growth_tracker + 1, 0)
        grow = tracker >= self.growth_interval
        scale = jnp.where(
            grads_finite,
            jnp.where(grow, self.scale * self.growth_factor, self.scale),
            self.scale * self.backoff_factor,
        )
        return dataclasses.replace(
            self, scale=scale, growth_tracker=jnp.where(grow, 0, tracker)
        )


@jax.tree_util.register_dataclass
@dataclasses.dataclass
class TrainState:
    """Params + optimizer state + accumulation state as one pytree.

    `apply_fn`/`tx` are static (not traced). `grad_accum` exists only when
    gradient accumulation is driven per-micro-batch (the eager-compatible
    fused step); the scan-fused step needs no buffer.
    """

    step: jax.Array
    params: Any
    opt_state: Any
    grad_accum: Any
    loss_scale: DynamicLossScale | None
    apply_fn: Callable = dataclasses.field(metadata={"static": True})
    tx: optax.GradientTransformation = dataclasses.field(metadata={"static": True})
    # fp8 delayed-scaling metas (ops/fp8.py), threaded through the fused
    # step like optimizer state when mixed_precision="fp8"
    fp8_state: Any = None

    @classmethod
    def create(
        cls,
        *,
        apply_fn: Callable,
        params: Any,
        tx: optax.GradientTransformation,
        use_grad_accum_buffer: bool = False,
        use_loss_scale: bool = False,
        fp8_state: Any = None,
    ) -> "TrainState":
        return cls(
            step=jnp.zeros((), jnp.int32),
            params=params,
            opt_state=tx.init(params),
            grad_accum=(
                jax.tree_util.tree_map(jnp.zeros_like, params)
                if use_grad_accum_buffer
                else None
            ),
            loss_scale=DynamicLossScale.create() if use_loss_scale else None,
            apply_fn=apply_fn,
            tx=tx,
            fp8_state=fp8_state,
        )

    @part("optimizer")
    def apply_gradients(self, grads: Any) -> "TrainState":
        updates, new_opt_state = self.tx.update(grads, self.opt_state, self.params)
        return dataclasses.replace(
            self,
            step=self.step + 1,
            params=optax.apply_updates(self.params, updates),
            opt_state=new_opt_state,
        )


# ---------------------------------------------------------------------------
# goodput-grade resilient training loop (ISSUE 20)
# ---------------------------------------------------------------------------


@dataclasses.dataclass
class ResilienceReport:
    """What `run_resilient` lived through and what it cost."""

    state: Any                      # the final TrainState
    steps_completed: int            # global step index reached
    start_step: int                 # where this invocation (re)started
    resumes: int                    # in-process crash recoveries
    saves: int                      # checkpoints written
    preempted: bool                 # True when a drain signal ended the run
    goodput: float                  # StepTimer.goodput over the run
    taxonomy: dict                  # StepTimer.stall_taxonomy()
    checkpoint_dir: str
    last_commit_dir: str | None     # newest complete checkpoint at exit
    incidents: list                 # straggler reports raised during the run


def run_resilient(
    accelerator,
    state: "TrainState",
    step_fn: Callable,
    batch_fn: Callable,
    num_steps: int,
    checkpoint_dir: str,
    *,
    save_every: int = 0,
    keep_last_n: int = 2,
    timer: Any = None,
    max_resumes: int = 3,
    blocking_saves: bool = False,
    install_signal_handlers: bool = True,
    drain_signals: tuple = (signal.SIGTERM,),
    straggler_monitor: Any = None,
    poll_every: int = 0,
    restart_on_straggler: bool = False,
    on_step: Callable | None = None,
) -> ResilienceReport:
    """Preemption-tolerant training loop: step-overlapped checkpoints,
    SIGTERM drain-then-save, step-crash auto-resume from the last
    committed manifest, and the straggler closed loop — the goodput
    number stays honest because every save/stall is marked on `timer`.

    - `step_fn(state, batch) -> (state, metrics)` — the compiled step.
      Recompiles after an in-process resume are NOT paid (the jit cache
      survives); across a relaunch the persistent XLA compilation cache
      (`utils.environment.configure_compilation_cache`) pays them once.
    - `batch_fn(step_index) -> batch` must be deterministic in the step
      index — that is what makes the data position resumable (the host
      RNG streams restore too, for stochastic pipelines keyed on them).
    - `save_every > 0` checkpoints every N steps into
      `checkpoint_dir/step_<N>`, async by default (the device->host
      snapshot is the only in-loop cost; the write overlaps later
      steps), committed via manifest, pruned to `keep_last_n` (the
      newest complete commit is never deleted). `blocking_saves=True`
      is the measurement baseline: the full write blocks in-loop.
    - A drain signal (SIGTERM by default — the preemption notice) ends
      the loop at the next step boundary AFTER saving; crashes inside a
      step restore from the newest complete manifest and continue, at
      most `max_resumes` times, each leaving an incident bundle.
    - `straggler_monitor` (telemetry.StragglerMonitor) is polled every
      `poll_every` steps; `restart_on_straggler=True` wires its incident
      to the drain path — the single-job form of elastic restart.

    Returns a :class:`ResilienceReport`; `state` inside it is the final
    train state (also assigned through in place via the checkpoint
    restore on resume)."""
    from .checkpointing import latest_complete_checkpoint, prune_checkpoints
    from .profiler import StepTimer

    if timer is None:
        timer = StepTimer(warmup_steps=1, name="resilient_step")
    checkpoint_dir = os.path.abspath(os.path.expanduser(checkpoint_dir))
    os.makedirs(checkpoint_dir, exist_ok=True)

    resumed = accelerator.resume_latest(checkpoint_dir, state=state)
    start = int(resumed["step"]) if resumed is not None else 0
    last_commit = resumed["checkpoint_dir"] if resumed is not None else None

    drain = {"requested": False, "signum": None}

    def _request_drain(signum=None, frame=None):
        drain["requested"] = True
        drain["signum"] = signum

    if straggler_monitor is not None and restart_on_straggler \
            and straggler_monitor.on_straggler is None:
        straggler_monitor.on_straggler = lambda report: _request_drain()
    if straggler_monitor is not None and straggler_monitor.timer is None:
        straggler_monitor.timer = timer

    prev_handlers: dict = {}
    if install_signal_handlers \
            and threading.current_thread() is threading.main_thread():
        for sig in drain_signals:
            prev_handlers[sig] = signal.signal(sig, _request_drain)

    def _save(step_index: int, marked: bool) -> str:
        # accelerator.step is what save_accelerator_state persists as the
        # resume point — pin it to the loop's global step index
        accelerator.step = step_index
        target = os.path.join(checkpoint_dir, f"step_{step_index:08d}")
        if marked:
            kind = "checkpoint" if blocking_saves else "checkpoint_stage"
            with timer.overhead(kind):
                accelerator.save_state(target, state=state,
                                       async_save=not blocking_saves)
        else:
            accelerator.save_state(target, state=state,
                                   async_save=not blocking_saves)
        prune_checkpoints(checkpoint_dir, keep_last_n)
        return target

    resumes = saves = 0
    preempted = False
    incidents: list = []
    i = start
    try:
        while i < num_steps:
            if drain["requested"]:
                # drain-then-save: commit a resume point, then hand the
                # machine back — the relaunch continues from here
                _save(i, marked=False)
                accelerator.wait_for_checkpoints()
                saves += 1
                preempted = True
                break
            try:
                with timer.input_stall():
                    batch = batch_fn(i)
                with timer.dispatch():
                    state, metrics = step_fn(state, batch)
                timer.tick(state)
                if on_step is not None:
                    on_step(i, state, metrics)
            except Exception as exc:
                resumes += 1
                if resumes > max_resumes:
                    raise
                _write_crash_bundle(exc, accelerator)
                try:
                    # drain in-flight async saves so everything already
                    # enqueued publishes its manifest before we look for
                    # the newest complete commit
                    accelerator.wait_for_checkpoints()
                except Exception:
                    pass  # writer failure: sealed manifests were dropped
                restored = accelerator.resume_latest(checkpoint_dir,
                                                     state=state)
                if restored is None:
                    raise       # nothing committed yet: nothing to resume
                last_commit = restored["checkpoint_dir"]
                i = int(restored.get("step", 0))
                continue
            i += 1
            if save_every and i % save_every == 0 and i < num_steps:
                _save(i, marked=True)
                saves += 1
            if straggler_monitor is not None and poll_every \
                    and i % poll_every == 0:
                report = straggler_monitor.poll()
                if report is not None:
                    incidents.append(report)
        if not preempted and save_every and i > start:
            # final commit: un-marked on the timer — the goodput window
            # closed at the last tick, so marking post-window work would
            # subtract it without its wall time
            _save(i, marked=False)
            saves += 1
        accelerator.wait_for_checkpoints()
        if saves:
            # the periodic prunes ran before the async manifests published
            # (a not-yet-committed save is invisible to retention), so one
            # post-drain prune brings the directory down to keep_last_n
            prune_checkpoints(checkpoint_dir, keep_last_n=keep_last_n)
    finally:
        for sig, handler in prev_handlers.items():
            signal.signal(sig, handler)

    if saves:
        last_commit = latest_complete_checkpoint(checkpoint_dir) or last_commit
    goodput = timer.goodput
    return ResilienceReport(
        state=state,
        steps_completed=i,
        start_step=start,
        resumes=resumes,
        saves=saves,
        preempted=preempted,
        goodput=goodput if goodput == goodput else 0.0,
        taxonomy=timer.stall_taxonomy(),
        checkpoint_dir=checkpoint_dir,
        last_commit_dir=last_commit,
        incidents=incidents,
    )


def _write_crash_bundle(exc: BaseException, accelerator) -> str | None:
    """Best-effort incident bundle for a step-time crash (same location
    and format as the stall watchdog's)."""
    try:
        from .telemetry.watchdog import (build_exception_report,
                                         resolve_incident_dir,
                                         write_incident_bundle)

        base = resolve_incident_dir(None)
        if base is None:
            return None
        report = build_exception_report(exc, name="step-crash")
        report["kind"] = "step_crash"
        return write_incident_bundle(
            base, report, registry=getattr(accelerator, "telemetry", None),
            name="step-crash")
    except Exception:
        return None


def cast_floating(tree: Any, dtype) -> Any:
    """Cast floating leaves (the bf16 compute policy: fp32 master params cast
    at trace time — replaces torch autocast, ref accelerator.py:1356-1365)."""
    def _cast(x):
        if hasattr(x, "dtype") and jnp.issubdtype(x.dtype, jnp.floating):
            return x.astype(dtype)
        return x

    return jax.tree_util.tree_map(_cast, tree)


def global_norm(tree: Any) -> jax.Array:
    return optax.global_norm(tree)


@part("optimizer")
def clip_by_global_norm(tree: Any, max_norm: float) -> tuple[Any, jax.Array]:
    """Returns (clipped, pre-clip norm) — matches torch
    clip_grad_norm_'s return (ref accelerator.py:2221)."""
    norm = optax.global_norm(tree)
    factor = jnp.minimum(1.0, max_norm / (norm + 1e-6))
    return jax.tree_util.tree_map(lambda g: g * factor, tree), norm
