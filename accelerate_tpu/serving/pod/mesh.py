"""Pod layer 1 (SPMD): tensor-parallel serving over a device mesh.

One engine, many chips: the family forward and the paged KV pool run
under `NamedSharding` on a mesh with a single `"model"` axis, so
admit/prefill/decode execute tensor-parallel over ICI (the pjit/TPUv4
static-shapes recipe, arxiv 2204.06514) while the engine's host-side
machinery — scheduler, paged allocator, prefix radix tree, page tables —
is untouched: page indices are *data*, and data doesn't care how the
arrays holding it are laid out across chips.

Division of labor:

- params: `shard_params` plans each leaf with the repo's path-pattern
  rules (`sharding/rules.py` — the Megatron column/row layout the
  `match_partition_rules` pattern encodes) and places it;
- KV pool: sharded over the KV-heads dim when the head count divides the
  mesh axis (each chip holds its heads' pages — attention is
  head-parallel, so the pool never moves), replicated otherwise (GQA
  models whose few KV heads don't divide; correct, just not
  memory-scaled — `cache_state_shardings` is the one place that policy
  lives);
- per-slot state (tokens/keys/temps/lengths): replicated — a few dozen
  scalars per slot.

The engine pins these layouts as its programs' `out_shardings`
(`EngineConfig.mesh`): GSPMD would otherwise be free to choose a
different output sharding than the input's, and since an array's sharding
is part of the jit cache key, the cache layout would drift compile by
compile instead of hitting a fixed point — the compile-count-flat
discipline extends to "flat per mesh", not just "flat per shape".

Everything here runs identically on a real slice and on the forced-host
CPU mesh (`XLA_FLAGS=--xla_force_host_platform_device_count=N`) the
tier-1 tests use, where token-exactness against the single-device engine
is proven byte for byte.
"""

from __future__ import annotations

import dataclasses
from typing import Any

import jax
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec

from ...sharding.planner import plan_sharding, shard_pytree
from ...sharding.rules import ShardingRules, transformer_rules
from ...utils.constants import AXIS_MODEL

__all__ = [
    "tensor_mesh",
    "shard_params",
    "cache_state_shardings",
    "sharded_engine",
]


def tensor_mesh(num_devices: int | None = None, devices=None) -> Mesh:
    """A 1-D `("model",)` mesh over the first `num_devices` visible
    devices (None = all). The single axis is deliberate: serving decode
    is latency-bound, and tensor parallelism over ICI is the axis that
    cuts per-token latency — data/fsdp axes belong to training."""
    devices = list(devices if devices is not None else jax.devices())
    n = len(devices) if num_devices is None else int(num_devices)
    if n < 1 or n > len(devices):
        raise ValueError(
            f"num_devices={n} out of range (1..{len(devices)} visible)")
    return Mesh(np.array(devices[:n]), (AXIS_MODEL,))


def shard_params(params: Any, mesh: Mesh,
                 rules: ShardingRules | None = None) -> Any:
    """Place a family's params on the mesh under the transformer rule set
    (column-parallel qkv/up projections, row-parallel out/down — the
    Megatron TP layout as path-pattern specs). Axes absent from the mesh
    prune away, so the same call serves a `("model",)` serving mesh and a
    richer training mesh."""
    rules = rules if rules is not None else transformer_rules()
    return shard_pytree(params, plan_sharding(params, mesh, rules))


def cache_state_shardings(cache, mesh: Mesh):
    """(cache_shardings, replicated) for an engine's pool + slot state.

    The pool shards over the KV-heads dim (axis 2 of
    [L, pages+1, H, page_size, D]) when H divides the model axis — each
    chip owns its heads' pages outright, page gathers/scatters stay
    chip-local, and pool HBM scales 1/N. When H doesn't divide (tiny-GQA
    models on a wide mesh) the pool falls back to sharding over the PAGE
    dim (axis 1) when the page count (+1 trash page) divides the axis —
    each chip owns a stripe of whole pages, so pool HBM still scales 1/N
    and a big pool never replicates per chip; the per-step page gathers
    then cross chips (GSPMD inserts the movement), trading bandwidth for
    memory. Only when NEITHER dim divides does the pool replicate:
    correct, latency still scales with the sharded matmuls, memory
    doesn't — size `num_pages` so pages+1 divides the mesh if the head
    count can't. int8 pools shard their scale arrays identically (same
    leading dims).

    The specs deliberately omit trailing `None` entries
    (`P(None, None, "model")`, not `...,"model", None)`): GSPMD
    normalizes specs that way in its output shardings, and the engine
    pins outputs to exactly these objects — a cosmetically different
    spelling of the same sharding would still be a different jit cache
    key on the next step's inputs."""
    n = mesh.shape[AXIS_MODEL]
    rep = NamedSharding(mesh, PartitionSpec())
    num_heads = cache.k.shape[2]
    # one spec serves pool and scales in every branch: the sharded dim
    # (heads = axis 2, pages = axis 1) sits at the same index in the 5-D
    # pool and the 4-D scale array
    if num_heads % n == 0:
        kv = NamedSharding(mesh, PartitionSpec(None, None, AXIS_MODEL))
    elif cache.k.shape[1] % n == 0:
        kv = NamedSharding(mesh, PartitionSpec(None, AXIS_MODEL))
    else:
        kv = rep
    cache_sh = dataclasses.replace(
        cache, k=kv, v=kv, lengths=rep,
        k_scale=kv if cache.quantized else None,
        v_scale=kv if cache.quantized else None)
    return cache_sh, rep


def sharded_engine(family, config, params, engine_config=None,
                   mesh: Mesh | None = None,
                   tensor_parallel: int | None = None,
                   rules: ShardingRules | None = None, **engine_kwargs):
    """The layer-1 factory: params sharded by rule, engine built with
    `EngineConfig(mesh=...)` so its pool/state are placed and its
    programs' out_shardings pinned. `tensor_parallel=N` builds the mesh
    over the first N visible devices; pass `mesh` to control placement.
    Returns the ordinary `Engine` — submit/stream/cancel, the scheduler,
    prefix reuse, telemetry, and strict-mode audits (now against
    `pod_program_contracts`) all work unchanged."""
    from ..engine import Engine, EngineConfig

    if mesh is None:
        mesh = tensor_mesh(tensor_parallel)
    ec = engine_config or EngineConfig()
    ec = dataclasses.replace(ec, mesh=mesh)
    placed = shard_params(params, mesh, rules)
    return Engine(family, config, placed, ec, **engine_kwargs)
