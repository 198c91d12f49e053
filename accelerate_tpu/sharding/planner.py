"""Sharding planner: param pytree + mesh + rules -> NamedSharding plan.

This single component is the TPU re-target of the reference's entire
parallelism-wrapper layer (SURVEY.md §7 step 6):

- ZeRO-3 / FSDP FULL_SHARD  -> params sharded on the `fsdp` axis
- ZeRO-1/2 / SHARD_GRAD_OP  -> only optimizer state sharded (params replicated)
- Megatron TP               -> `model`-axis entries in the rule templates
- MoE expert parallel       -> `expert`-axis entries
- DDP                       -> no axes present; everything replicates

Where the reference wraps modules (`FSDP(module)` ref accelerator.py:1431,
`deepspeed.initialize` :1751), we emit `jax.sharding.NamedSharding` per leaf
and let GSPMD insert the collectives.
"""

from __future__ import annotations

import logging
from typing import Any, Callable

import jax
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec

from ..utils.constants import AXIS_FSDP, BATCH_AXES
from .rules import ShardingRules, SpecTemplate, transformer_rules

logger = logging.getLogger(__name__)


def _path_str(path) -> str:
    parts = []
    for p in path:
        if hasattr(p, "key"):
            parts.append(str(p.key))
        elif hasattr(p, "idx"):
            parts.append(str(p.idx))
        elif hasattr(p, "name"):
            parts.append(str(p.name))
        else:
            parts.append(str(p))
    return "/".join(parts)


def _axis_sizes(mesh: Mesh) -> dict[str, int]:
    return dict(mesh.shape)


def _prune_template(template: SpecTemplate, shape: tuple, mesh: Mesh) -> PartitionSpec:
    """Fit a spec template to a concrete shape on a concrete mesh: drop axes
    that aren't in the mesh, are size 1, or don't divide the dim. Templates
    shorter than the rank align to the *trailing* dims (leading batch/expert
    dims handled by explicit longer templates)."""
    sizes = _axis_sizes(mesh)
    rank = len(shape)
    entries: list = [None] * rank
    template = tuple(template)[:rank] if len(template) > rank else tuple(template)
    offset = rank - len(template)
    used: set[str] = set()
    for i, entry in enumerate(template):
        dim = offset + i
        if entry is None:
            continue
        axes = entry if isinstance(entry, tuple) else (entry,)
        kept = []
        group = 1
        for a in axes:
            if a in used or sizes.get(a, 1) == 1:
                continue
            if shape[dim] % (group * sizes[a]) != 0:
                continue
            kept.append(a)
            group *= sizes[a]
        for a in kept:
            used.add(a)
        if kept:
            entries[dim] = tuple(kept) if len(kept) > 1 else kept[0]
    return PartitionSpec(*entries)


def auto_fsdp_spec(shape: tuple, mesh: Mesh, axis: str = AXIS_FSDP) -> PartitionSpec:
    """ZeRO-style auto rule: shard the largest dim divisible by the fsdp axis
    (prefers later dims on ties — usually the output/feature dim)."""
    size = _axis_sizes(mesh).get(axis, 1)
    if size == 1 or not shape:
        return PartitionSpec()
    best_dim, best = -1, 0
    for dim, n in enumerate(shape):
        if n % size == 0 and n >= best:
            best, best_dim = n, dim
    if best_dim < 0:
        return PartitionSpec()
    entries = [None] * len(shape)
    entries[best_dim] = axis
    return PartitionSpec(*entries)


def plan_sharding(
    params: Any,
    mesh: Mesh,
    rules: ShardingRules | None = None,
    shard_params: bool = True,
) -> Any:
    """Return a pytree of `NamedSharding` matching `params` (arrays or
    ShapeDtypeStructs — pass `jax.eval_shape` output to plan without
    materializing, the meta-device trick of ref big_modeling.py:56-166).

    `shard_params=False` replicates parameters (ZeRO-1/2: only the optimizer
    state adopts the sharded plan — see `plan_optimizer_sharding`).
    """
    rules = rules if rules is not None else transformer_rules()

    def _plan(path, leaf):
        shape = tuple(leaf.shape)
        if not shard_params:
            return NamedSharding(mesh, PartitionSpec())
        nelems = int(np.prod(shape)) if shape else 1
        if nelems < rules.min_weight_size:
            return NamedSharding(mesh, PartitionSpec())
        template = rules.find(_path_str(path))
        if template is not None:
            spec = _prune_template(template, shape, mesh)
        elif rules.default_fsdp:
            spec = auto_fsdp_spec(shape, mesh)
        else:
            spec = PartitionSpec()
        # fall back to auto-fsdp if a matched rule pruned to fully-replicated
        if (
            template is not None
            and len(template) > 0
            and spec == PartitionSpec(*([None] * len(shape)))
            and rules.default_fsdp
        ):
            spec = auto_fsdp_spec(shape, mesh)
        return NamedSharding(mesh, spec)

    return jax.tree_util.tree_map_with_path(_plan, params)


def plan_optimizer_sharding(optimizer, opt_state: Any, param_plan: Any, mesh: Mesh) -> Any:
    """Shard optimizer state like its params (ZeRO-1/2/3 optimizer-state
    sharding, ref DeepSpeed engine).

    Uses `optax.tree_map_params` so param-shaped leaves (e.g. Adam mu/nu)
    adopt the param's sharding while step counters replicate.

    Block-quantized moments (`optimizers.adamw_8bit`) carry a
    ``[blocks, 256]`` payload that cannot adopt a param-shaped spec;
    they shard along the blocks dim on the fsdp axis instead whenever the
    plan wants sharding and the block count divides — composing 8-bit Adam
    with ZeRO instead of silently replicating (r4 weak-spot #5).
    """
    import optax

    from ..optimizers import _Quantized

    replicated = NamedSharding(mesh, PartitionSpec())
    is_quant = lambda x: isinstance(x, _Quantized)  # noqa: E731
    has_quant = any(
        is_quant(leaf)
        for leaf in jax.tree_util.tree_leaves(opt_state, is_leaf=is_quant)
    )

    # Quantized moments are handled as an OVERLAY on the tree_map_params
    # result, not an early return: a composed optimizer (e.g.
    # optax.chain(adamw_8bit, <transform with param-shaped state like
    # ema/trace>)) must keep ZeRO sharding for its non-quantized param-shaped
    # moments. Each _Quantized subtree is first masked to a single marker
    # leaf so the state zips structurally against the param plan, then the
    # markers are resolved to blocks-dim specs.
    class _QuantMarker:
        __slots__ = ("blocks",)

        def __init__(self, blocks: int):
            self.blocks = blocks

    quant_plan = None
    state_for_map = opt_state
    if has_quant:
        fsdp_size = _axis_sizes(mesh).get(AXIS_FSDP, 1)
        plan_wants_sharding = any(
            any(s is not None for s in ns.spec)
            for ns in jax.tree_util.tree_leaves(
                param_plan, is_leaf=lambda x: isinstance(x, NamedSharding)
            )
        )
        blocks_spec = (
            NamedSharding(mesh, PartitionSpec(AXIS_FSDP, None))
            if fsdp_size > 1
            else replicated
        )

        def quant_plan(blocks: int):
            if (
                plan_wants_sharding
                and fsdp_size > 1
                and blocks % fsdp_size == 0
            ):
                return _Quantized(q=blocks_spec, scale=blocks_spec)
            if plan_wants_sharding and fsdp_size > 1:
                logger.warning(
                    "adamw_8bit moment with %d blocks does not divide the "
                    "fsdp axis (%d); this moment replicates", blocks, fsdp_size,
                )
            return _Quantized(q=replicated, scale=replicated)

        state_for_map = jax.tree_util.tree_map(
            lambda n: _QuantMarker(int(n.q.shape[0])) if is_quant(n) else n,
            opt_state,
            is_leaf=is_quant,
        )

    def _map_param(leaf, sharding):
        if isinstance(leaf, _QuantMarker):
            return quant_plan(leaf.blocks)
        return sharding

    def _map_non_param(leaf):
        if isinstance(leaf, _QuantMarker):
            return quant_plan(leaf.blocks)
        return replicated

    try:
        return optax.tree_map_params(
            optimizer,
            _map_param,
            state_for_map,
            param_plan,
            transform_non_params=_map_non_param,
        )
    except Exception:
        # fallback: replicate non-quantized leaves; quantized moments keep
        # their blocks-dim specs (the 8-bit-Adam x ZeRO composition must not
        # silently degrade just because the surrounding transform's state
        # confused tree_map_params)
        logger.warning("optax.tree_map_params failed; replicating optimizer state")
        return jax.tree_util.tree_map(
            lambda n: quant_plan(int(n.q.shape[0])) if is_quant(n) else replicated,
            opt_state,
            is_leaf=is_quant,
        )


def count_replicated_quantized(opt_plan: Any) -> tuple[int, int]:
    """(#replicated, #total) block-quantized moment entries in an
    optimizer-sharding plan — the single source for the 8-bit-Adam x ZeRO
    composition warning (`Accelerator._warn_unsharded_quantized_moments`)."""
    from ..optimizers import _Quantized

    is_q = lambda x: isinstance(x, _Quantized)  # noqa: E731
    qplans = [
        n for n in jax.tree_util.tree_leaves(opt_plan, is_leaf=is_q)
        if is_q(n)
    ]
    replicated = [
        n for n in qplans if not any(s is not None for s in n.q.spec)
    ]
    return len(replicated), len(qplans)


def batch_spec(mesh: Mesh, batch_axes=BATCH_AXES, extra_dims: int = 0) -> PartitionSpec:
    """PartitionSpec for a batch: leading dim over the data-like axes."""
    axes = tuple(a for a in batch_axes if a in mesh.axis_names and mesh.shape[a] > 1)
    lead = axes if len(axes) > 1 else (axes[0] if axes else None)
    return PartitionSpec(lead, *([None] * extra_dims))


def batch_sharding(mesh: Mesh, batch_axes=BATCH_AXES) -> NamedSharding:
    return NamedSharding(mesh, batch_spec(mesh, batch_axes))


def shard_pytree(tree: Any, plan: Any) -> Any:
    """Place/reshard a pytree according to a plan (device_put handles both
    host arrays and resharding of existing jax.Arrays).

    All array leaves go through ONE batched `jax.device_put` call rather
    than one call per leaf: the single entry into jaxlib's
    batched_device_put is faster for large trees."""
    leaves, treedef = jax.tree_util.tree_flatten(tree)
    plan_leaves = treedef.flatten_up_to(plan)
    idx = [i for i, x in enumerate(leaves) if hasattr(x, "shape")]
    if idx:
        placed = jax.device_put([leaves[i] for i in idx],
                                [plan_leaves[i] for i in idx])
        for i, v in zip(idx, placed):
            leaves[i] = v
    return jax.tree_util.tree_unflatten(treedef, leaves)


def constrain(tree: Any, mesh: Mesh, spec: PartitionSpec) -> Any:
    """In-jit sharding constraint helper (GSPMD activation hints — how SP
    falls out for free, SURVEY.md §2.2 row SP)."""
    import jax.numpy as jnp  # noqa: F401
    from jax.lax import with_sharding_constraint

    return jax.tree_util.tree_map(
        lambda x: with_sharding_constraint(x, NamedSharding(mesh, spec)), tree
    )


def describe_plan(plan: Any, max_rows: int = 120) -> str:
    """Human-readable sharding table (debug aid; no reference equivalent)."""
    rows = []
    for path, sharding in jax.tree_util.tree_leaves_with_path(
        plan, is_leaf=lambda x: isinstance(x, NamedSharding)
    ):
        rows.append(f"  {_path_str(path):60s} {sharding.spec}")
        if len(rows) >= max_rows:
            rows.append("  ...")
            break
    return "\n".join(rows)
