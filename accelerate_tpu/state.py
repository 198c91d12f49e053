"""Process/topology singletons.

TPU-native analogue of ref src/accelerate/state.py (1205 LoC):

- `PartialState` (ref state.py:111): in the reference this picks one of eight
  torch.distributed backends (smddp/xla/cncl/nccl/hccl/ccl/mpi/gloo,
  `_prepare_backend` ref state.py:708-760) and joins an NCCL/Gloo process
  group. Here there is exactly one backend — the JAX runtime: multi-host
  rendezvous via `jax.distributed.initialize` over DCN, collectives compiled
  by XLA over ICI. One *process per host* drives all local chips (vs. the
  reference's one process per accelerator).
- `AcceleratorState` (ref state.py:805): adds mixed precision + the resolved
  device mesh (where the reference promoted `distributed_type` to
  FSDP/DEEPSPEED/MEGATRON based on env, we resolve a `MeshConfig`).
- `GradientState` (ref state.py:1082): gradient-accumulation bookkeeping.

The reference's shared-dict singleton pattern (ref state.py:150,166) is kept:
all instances alias one state dict, `_reset_state` clears it (for tests).
"""

from __future__ import annotations

import logging
import os
import threading
from contextlib import contextmanager
from typing import Any, Callable, Iterator

import jax
import numpy as np

from .utils.constants import (
    ENV_COORDINATOR,
    ENV_CPU,
    ENV_DEBUG_MODE,
    ENV_FORCE_HOST_DEVICES,
    ENV_NUM_PROCESSES,
    ENV_PROCESS_ID,
    LEGACY_RANK_VARS,
    LEGACY_WORLD_VARS,
)
from .utils.dataclasses import (
    DistributedType,
    GradientAccumulationPlugin,
    MeshConfig,
    PrecisionType,
    resolve_mixed_precision,
)
from .utils.environment import get_int_from_env, parse_flag_from_env

logger = logging.getLogger(__name__)

_jax_distributed_initialized = False
_init_lock = threading.Lock()


def _maybe_init_jax_distributed(timeout_s: int | None = None) -> bool:
    """Join the multi-host world if the env protocol asks for one.

    Env protocol (ref state.py:215-237 `RANK/WORLD_SIZE/MASTER_ADDR/PORT`):
    ours is `ACCELERATE_TPU_COORDINATOR/NUM_PROCESSES/PROCESS_ID`, with the
    legacy names honoured as fallback. On Cloud TPU pods with no env set, JAX
    auto-discovers topology from the metadata server, so we also initialize
    when `JAX_COORDINATOR_ADDRESS` is present.
    """
    global _jax_distributed_initialized
    with _init_lock:
        if _jax_distributed_initialized:
            return True
        coordinator = os.environ.get(ENV_COORDINATOR) or os.environ.get(
            "JAX_COORDINATOR_ADDRESS"
        )
        num_processes = get_int_from_env((ENV_NUM_PROCESSES, *LEGACY_WORLD_VARS))
        process_id = get_int_from_env((ENV_PROCESS_ID, *LEGACY_RANK_VARS))
        if coordinator is None or num_processes is None or num_processes <= 1:
            return False
        kwargs: dict[str, Any] = dict(
            coordinator_address=coordinator,
            num_processes=num_processes,
            process_id=process_id,
        )
        if timeout_s is not None:
            kwargs["initialization_timeout"] = timeout_s
        jax.distributed.initialize(**kwargs)
        _jax_distributed_initialized = True
        return True


class PartialState:
    """Topology + process-control singleton (ref state.py:111).

    Usable before any model/optimizer exists, e.g. for `local_main_process_first`
    around dataset downloads.
    """

    _shared_state: dict[str, Any] = {}

    def __init__(self, cpu: bool = False, **kwargs: Any) -> None:
        self.__dict__ = self._shared_state
        if self.initialized:
            return
        timeout = kwargs.pop("timeout", None)
        timeout_s = int(timeout.total_seconds()) if timeout is not None else None
        host_devices = get_int_from_env((ENV_FORCE_HOST_DEVICES,))
        if host_devices:
            from .utils.environment import set_virtual_host_devices

            set_virtual_host_devices(host_devices)
        if cpu or host_devices or parse_flag_from_env(ENV_CPU):
            from .utils.environment import force_cpu_platform

            if not force_cpu_platform():
                logger.warning(
                    "CPU backend requested but a JAX backend is already "
                    "initialized; keeping the existing platform."
                )
        # persistent XLA compilation cache: configured here (the one choke
        # point every entry path crosses before compiling) so a relaunch
        # deserializes yesterday's executables instead of recompiling.
        # ACCELERATE_TPU_COMPILATION_CACHE overrides the dir or disables.
        from .utils.environment import configure_compilation_cache

        self.compilation_cache_dir = configure_compilation_cache()
        self.multi_host = _maybe_init_jax_distributed(timeout_s)
        self.debug = parse_flag_from_env(ENV_DEBUG_MODE)
        self._devices = list(jax.devices())
        self.backend = self._devices[0].platform  # 'tpu' | 'cpu' | 'gpu'
        if self.num_processes > 1:
            self.distributed_type = DistributedType.MULTI_HOST
        elif len(self._devices) > 1:
            self.distributed_type = DistributedType.JAX
        else:
            self.distributed_type = DistributedType.NO
        self._mesh = None
        logger.info(
            "PartialState: %d process(es), %d device(s) [%s], distributed_type=%s",
            self.num_processes,
            len(self._devices),
            self.backend,
            self.distributed_type,
        )

    # -- singleton plumbing (ref state.py:150-170) ---------------------------
    @property
    def initialized(self) -> bool:
        return bool(self._shared_state)

    @classmethod
    def _reset_state(cls) -> None:
        """Clear all singleton state (test use; ref testing.py:394-439)."""
        cls._shared_state.clear()
        AcceleratorState._shared_state.clear()
        GradientState._shared_state.clear()

    # -- topology ------------------------------------------------------------
    @property
    def device(self):
        """Default local device (ref `self.device`, a torch.device)."""
        return jax.local_devices()[0]

    @property
    def devices(self) -> list:
        return list(self._devices)

    @property
    def num_processes(self) -> int:
        """Host-process count. NOTE: the reference runs one process per
        accelerator; we run one per host and drive all local chips from it,
        so reference `num_processes` semantics for *data sharding* map to
        `dp_size` on the mesh, not this."""
        return jax.process_count()

    @property
    def process_index(self) -> int:
        return jax.process_index()

    @property
    def local_process_index(self) -> int:
        return 0  # one process per host

    @property
    def device_count(self) -> int:
        return len(self._devices)

    @property
    def local_device_count(self) -> int:
        return jax.local_device_count()

    @property
    def is_main_process(self) -> bool:
        return self.process_index == 0

    @property
    def is_local_main_process(self) -> bool:
        return True  # one process per host

    @property
    def is_last_process(self) -> bool:
        return self.process_index == self.num_processes - 1

    @property
    def use_distributed(self) -> bool:
        return self.distributed_type != DistributedType.NO

    # -- mesh ----------------------------------------------------------------
    @property
    def mesh(self):
        """Default 1-axis data mesh over all devices; AcceleratorState
        replaces this with the plugin-resolved mesh."""
        if self._mesh is None:
            self._mesh = MeshConfig.data_parallel().build(self._devices)
        return self._mesh

    def set_mesh(self, mesh) -> None:
        self._mesh = mesh

    # -- process control (ref state.py:345-678) ------------------------------
    def wait_for_everyone(self) -> None:
        """Cross-host barrier (ref state.py:345 -> xm.rendezvous /
        torch.distributed.barrier)."""
        if self.num_processes > 1:
            from jax.experimental import multihost_utils

            multihost_utils.sync_global_devices("accelerate_tpu.wait_for_everyone")

    @contextmanager
    def main_process_first(self) -> Iterator[None]:
        """Main process runs the body first, others wait (ref state.py:481)."""
        if not self.is_main_process:
            self.wait_for_everyone()
        yield
        if self.is_main_process:
            self.wait_for_everyone()

    @contextmanager
    def local_main_process_first(self) -> Iterator[None]:
        with self.main_process_first():
            yield

    @contextmanager
    def split_between_processes(
        self, inputs, apply_padding: bool = False
    ) -> Iterator[Any]:
        """Split a list/tuple/dict/array between host processes
        (ref state.py:390-479)."""
        if self.num_processes == 1:
            yield inputs
            return
        if isinstance(inputs, dict):
            lengths = {k: len(v) for k, v in inputs.items()}
            if len(set(lengths.values())) != 1:
                raise ValueError(
                    f"All dict values must share a length to be split, got {lengths}"
                )
            length = next(iter(lengths.values()))
        else:
            length = len(inputs)
        num_samples_per_process, remainder = divmod(length, self.num_processes)
        start = self.process_index * num_samples_per_process + min(
            self.process_index, remainder
        )
        end = start + num_samples_per_process + (1 if self.process_index < remainder else 0)
        if isinstance(inputs, dict):
            result = {k: v[start:end] for k, v in inputs.items()}
        else:
            result = inputs[start:end]
        if apply_padding and num_samples_per_process * self.num_processes != length:
            pad_to = num_samples_per_process + 1
            if isinstance(result, dict):
                result = {k: _pad_slice(v, pad_to) for k, v in result.items()}
            else:
                result = _pad_slice(result, pad_to)
        yield result

    def on_main_process(self, function: Callable) -> Callable:
        """Run only on global rank 0 (ref state.py:522)."""

        def wrapper(*args, **kwargs):
            if self.is_main_process:
                return function(*args, **kwargs)

        return wrapper

    def on_local_main_process(self, function: Callable) -> Callable:
        def wrapper(*args, **kwargs):
            if self.is_local_main_process:
                return function(*args, **kwargs)

        return wrapper

    def on_last_process(self, function: Callable) -> Callable:
        def wrapper(*args, **kwargs):
            if self.is_last_process:
                return function(*args, **kwargs)

        return wrapper

    def on_process(self, function: Callable, process_index: int = 0) -> Callable:
        def wrapper(*args, **kwargs):
            if self.process_index == process_index:
                return function(*args, **kwargs)

        return wrapper

    def print(self, *args: Any, **kwargs: Any) -> None:
        """Rank-0-only print (ref accelerator.py:1148)."""
        if self.is_main_process:
            print(*args, **kwargs)

    def destroy_process_group(self) -> None:
        global _jax_distributed_initialized
        if _jax_distributed_initialized:
            jax.distributed.shutdown()
            _jax_distributed_initialized = False

    def __repr__(self) -> str:
        return (
            f"PartialState(distributed_type={self.distributed_type}, "
            f"num_processes={self.num_processes}, process_index={self.process_index}, "
            f"devices={self.device_count}x{self.backend})"
        )


def _pad_slice(seq, pad_to: int):
    if hasattr(seq, "shape"):
        import jax.numpy as jnp

        if seq.shape[0] >= pad_to:
            return seq
        pad = [(0, pad_to - seq.shape[0])] + [(0, 0)] * (seq.ndim - 1)
        return jnp.pad(seq, pad)
    if len(seq) >= pad_to:
        return seq
    filler = seq[-1:] * (pad_to - len(seq)) if len(seq) else seq
    return seq + filler


class AcceleratorState:
    """PartialState + mixed precision + the resolved mesh (ref state.py:805).

    Where the reference promoted `distributed_type` based on
    `ACCELERATE_USE_{FSDP,DEEPSPEED,MEGATRON_LM}` env (ref state.py:892-910),
    we resolve every plugin into one `MeshConfig` and build the mesh once.
    """

    _shared_state: dict[str, Any] = {}

    def __init__(
        self,
        mixed_precision: str | None = None,
        cpu: bool = False,
        mesh_config: MeshConfig | None = None,
        **kwargs: Any,
    ) -> None:
        self.__dict__ = self._shared_state
        if self.initialized:
            if (
                mixed_precision is not None
                and PrecisionType(mixed_precision) != self.mixed_precision
            ):
                raise ValueError(
                    "AcceleratorState already initialized with "
                    f"mixed_precision={self.mixed_precision}; cannot switch to "
                    f"{mixed_precision}. Call Accelerator() once, or "
                    "PartialState._reset_state() in tests."
                )
            return
        self.partial_state = PartialState(cpu=cpu, **kwargs)
        self.mixed_precision = resolve_mixed_precision(mixed_precision)
        mesh_config = mesh_config or MeshConfig.from_env() or MeshConfig.data_parallel()
        self.mesh_config = mesh_config
        # an explicit MeshConfig.devices list (a sub-mesh of the host's
        # chips) wins over "every device of the process"
        self.mesh = mesh_config.build(
            None if mesh_config.devices is not None
            else self.partial_state.devices)
        self.partial_state.set_mesh(self.mesh)

    @property
    def initialized(self) -> bool:
        return bool(self._shared_state)

    @classmethod
    def _reset_state(cls) -> None:
        PartialState._reset_state()

    # mesh axis sizes --------------------------------------------------------
    def axis_size(self, name: str) -> int:
        return self.mesh.shape.get(name, 1)

    @property
    def dp_size(self) -> int:
        """Total batch-sharding degree (data * fsdp axes)."""
        from .utils.constants import BATCH_AXES

        size = 1
        for a in BATCH_AXES:
            size *= self.axis_size(a)
        return size

    def __getattr__(self, name: str):
        # delegate topology/process-control to PartialState (ref state.py:817)
        if name in ("partial_state", "_shared_state"):
            raise AttributeError(name)
        partial = self.__dict__.get("partial_state")
        if partial is None:
            raise AttributeError(
                f"AcceleratorState has no attribute {name!r} (not initialized?)"
            )
        return getattr(partial, name)

    def __repr__(self) -> str:
        return (
            f"AcceleratorState(mixed_precision={self.mixed_precision}, "
            f"mesh={dict(self.mesh.shape)}, {self.partial_state!r})"
        )


class GradientState:
    """Gradient-accumulation bookkeeping singleton (ref state.py:1082).

    Tracks whether this step is a sync boundary, end-of-dataloader, and the
    uneven-tail `remainder` used by `gather_for_metrics`
    (ref accelerator.py:2331-2403). The XLA `mark_step` graph-cut concern
    (ref state.py:1176-1185) does not exist here: each jitted call is already
    a complete compiled program.
    """

    _shared_state: dict[str, Any] = {}

    def __init__(self, plugin: GradientAccumulationPlugin | None = None) -> None:
        self.__dict__ = self._shared_state
        if not self.initialized:
            self.sync_gradients = True
            self.step = 0
            self.active_dataloader = None
            self.dataloader_references: list[Any] = [None]
            self.plugin = plugin or GradientAccumulationPlugin()
        if plugin is not None:
            self.plugin = plugin

    @property
    def initialized(self) -> bool:
        return bool(self._shared_state)

    @property
    def num_steps(self) -> int:
        return self.plugin.num_steps

    @property
    def adjust_scheduler(self) -> bool:
        return self.plugin.adjust_scheduler

    @property
    def sync_with_dataloader(self) -> bool:
        return self.plugin.sync_with_dataloader

    @property
    def end_of_dataloader(self) -> bool:
        if not self.in_dataloader:
            return False
        return getattr(self.active_dataloader, "end_of_dataloader", False)

    @property
    def remainder(self) -> int:
        if not self.in_dataloader:
            return -1
        return getattr(self.active_dataloader, "remainder", -1)

    @property
    def tail_layout(self):
        """(num_hosts, padded_per_host, real_per_host) of the final uneven
        batch, or None — lets gather_for_metrics drop pads per host block."""
        if not self.in_dataloader:
            return None
        return getattr(self.active_dataloader, "tail_layout", None)

    @property
    def in_dataloader(self) -> bool:
        return self.active_dataloader is not None

    def _set_sync_gradients(self, sync: bool) -> None:
        self.sync_gradients = sync

    def _add_dataloader(self, dataloader) -> None:
        """ref state.py:1187-1200."""
        self.active_dataloader = dataloader
        self.dataloader_references.append(dataloader)

    def _remove_dataloader(self, dataloader) -> None:
        # a loader generator may be finalized after _reset_state cleared the
        # shared dict — nothing to unregister then
        refs = self.__dict__.get("dataloader_references")
        if refs is None:
            return
        if dataloader in refs:
            refs.remove(dataloader)
        self.active_dataloader = refs[-1] if refs else None

    @classmethod
    def _reset_state(cls) -> None:
        cls._shared_state.clear()

    def __repr__(self) -> str:
        return (
            f"GradientState(step={self.step}, num_steps={self.num_steps}, "
            f"sync_gradients={self.sync_gradients}, in_dataloader={self.in_dataloader})"
        )


def is_initialized() -> bool:
    return AcceleratorState._shared_state != {}
