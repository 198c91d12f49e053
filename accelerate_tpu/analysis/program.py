"""Program passes: audit lowered/compiled jax programs.

Three families, all returning the same `Finding` objects the source passes
emit so every surface (CLI JSON, telemetry, strict mode) renders them the
same way:

- `collective_counts` / `CollectiveContract`: count collectives per
  program and check them against a declared contract. Works on optimized
  HLO text (`.compile().as_text()` — where GSPMD-inserted collectives
  live), StableHLO text (`.lower().as_text()` — where shard_map-explicit
  collectives live), and jaxprs (primitive names).
- `find_host_transfers`: device_put / host callbacks / infeed-outfeed
  inside a traced program (ATP102).
- `audit_replication`: fully-replicated arrays above a size threshold on a
  multi-device mesh — the memory-blowup smell (ATP103).

jax is imported lazily inside functions: importing this module (e.g. via
the CLI) must not initialize a backend.
"""

from __future__ import annotations

import dataclasses
import re
from collections import Counter
from typing import Any, Iterable, Mapping

from .findings import AnalysisViolation, Finding

__all__ = [
    "CANONICAL_COLLECTIVES",
    "collective_counts",
    "CollectiveContract",
    "find_host_transfers",
    "audit_replication",
    "audit_compiled_step",
]

# Canonical collective names = the optimized-HLO spellings.
CANONICAL_COLLECTIVES = (
    "all-reduce", "all-gather", "reduce-scatter", "collective-permute",
    "all-to-all",
)

# jaxpr primitive -> canonical, by the names jax 0.9.0 gives them
# (`jax._src.lax.parallel`). Under `shard_map(check_vma=True)`, the
# default, `psum` traces as `psum_invariant` and an all-gather whose
# result is replicated as `all_gather_invariant`; with the check off the
# plain names appear. pmin/pmax lower to all-reduce too. `pshuffle` is a
# python wrapper over ppermute and `psum_scatter` traces as
# `reduce_scatter`, so neither needs a row.
_PRIM_TO_CANONICAL = {
    "psum": "all-reduce",
    "psum_invariant": "all-reduce",
    "unreduced_psum": "all-reduce",
    "pmin": "all-reduce",
    "pmax": "all-reduce",
    "all_gather": "all-gather",
    "all_gather_invariant": "all-gather",
    "all_gather_reduced": "all-gather",
    "reduce_scatter": "reduce-scatter",
    "unreduced_reduce_scatter": "reduce-scatter",
    "ppermute": "collective-permute",
    "all_to_all": "all-to-all",
    "ragged_all_to_all": "all-to-all",
}

# one regex covers optimized HLO (`all-reduce`), StableHLO
# (`stablehlo.all_reduce`), and HLO start/done async pairs are collapsed by
# only counting the `-start`-less spelling plus `-start` (never `-done`)
_HLO_RE = re.compile(
    r"\b(all-gather|reduce-scatter|all-reduce|collective-permute|all-to-all)"
    r"(-start|-done)?\b"
)
_STABLEHLO_RE = re.compile(
    r"\bstablehlo\.(all_gather|reduce_scatter|all_reduce|collective_permute"
    r"|all_to_all)\b"
)


def _is_jaxpr(obj: Any) -> bool:
    return hasattr(obj, "jaxpr") or hasattr(obj, "eqns")


def _as_text(obj: Any) -> str:
    """Program text from str | jax.stages.Lowered | jax.stages.Compiled."""
    if isinstance(obj, str):
        return obj
    if hasattr(obj, "as_text"):
        return obj.as_text()
    raise TypeError(
        f"expected HLO/StableHLO text, a Lowered/Compiled stage, or a "
        f"jaxpr; got {type(obj).__name__}"
    )


def _iter_jaxpr_eqns(jaxpr: Any):
    """Every eqn in a (closed) jaxpr including nested sub-jaxprs."""
    inner = getattr(jaxpr, "jaxpr", jaxpr)
    for eqn in getattr(inner, "eqns", []):
        yield eqn
        for v in eqn.params.values():
            stack = [v]
            while stack:
                item = stack.pop()
                if isinstance(item, (tuple, list)):
                    stack.extend(item)
                elif _is_jaxpr(item):
                    yield from _iter_jaxpr_eqns(item)


def collective_counts(obj: Any) -> Counter:
    """Counter of canonical collective names in a program.

    Accepts optimized-HLO text, StableHLO text, a Lowered/Compiled stage,
    or a (closed) jaxpr."""
    if _is_jaxpr(obj) and not isinstance(obj, str):
        counts: Counter = Counter()
        for eqn in _iter_jaxpr_eqns(obj):
            canon = _PRIM_TO_CANONICAL.get(getattr(eqn.primitive, "name", ""))
            if canon:
                counts[canon] += 1
        return counts
    text = _as_text(obj)
    counts = Counter()
    for m in _HLO_RE.finditer(text):
        if m.group(2) == "-done":
            continue  # async pair: count the -start, skip the -done
        counts[m.group(1)] += 1
    for m in _STABLEHLO_RE.finditer(text):
        counts[m.group(1).replace("_", "-")] += 1
    return counts


def _norm_items(mapping: Any) -> tuple[tuple[str, int], ...]:
    if mapping is None:
        return ()
    if isinstance(mapping, Mapping):
        items = mapping.items()
    else:
        items = tuple(mapping)
    return tuple(sorted((str(k), int(v)) for k, v in items))


def _norm_groups(groups: Any) -> tuple[tuple[str, ...], ...]:
    if groups is None:
        return ()
    out = []
    for g in groups:
        out.append((g,) if isinstance(g, str) else tuple(g))
    return tuple(out)


@dataclasses.dataclass(frozen=True)
class CollectiveContract:
    """Declared collective structure of ONE compiled program.

    - ``exact``: collective -> exact count (the version-pinned counts that
      used to live inline in tests).
    - ``at_least`` / ``at_most``: bounds, same shape as ``exact``.
    - ``require``: groups of alternatives — each group's summed count must
      be > 0 (e.g. ``("reduce-scatter", "all-to-all")``: XLA's CPU
      partitioner spells reduce-scatter as all-to-all + local reduce).
      A bare string is a one-element group.
    - ``forbid``: collectives that must not appear at all.

    - ``exhaustive``: when True, any collective the contract says nothing
      about is itself a violation ("an undeclared extra psum") — the
      strictest form, for programs whose full collective budget is known.

    ``check`` returns ATP101 findings; ``enforce`` raises
    `AnalysisViolation` on any.
    """

    name: str
    exact: Any = ()
    at_least: Any = ()
    at_most: Any = ()
    require: Any = ()
    forbid: tuple[str, ...] = ()
    exhaustive: bool = False

    def __post_init__(self):
        object.__setattr__(self, "exact", _norm_items(self.exact))
        object.__setattr__(self, "at_least", _norm_items(self.at_least))
        object.__setattr__(self, "at_most", _norm_items(self.at_most))
        object.__setattr__(self, "require", _norm_groups(self.require))
        object.__setattr__(self, "forbid", tuple(self.forbid))

    def check(self, obj: Any, counts: Counter | None = None) -> list[Finding]:
        counts = collective_counts(obj) if counts is None else counts
        problems: list[str] = []
        for coll, want in self.exact:
            got = counts.get(coll, 0)
            if got != want:
                problems.append(f"{coll}: expected exactly {want}, got {got}")
        for coll, want in self.at_least:
            if counts.get(coll, 0) < want:
                problems.append(
                    f"{coll}: expected >= {want}, got {counts.get(coll, 0)}")
        for coll, want in self.at_most:
            if counts.get(coll, 0) > want:
                problems.append(
                    f"{coll}: expected <= {want}, got {counts.get(coll, 0)}")
        for group in self.require:
            if sum(counts.get(c, 0) for c in group) == 0:
                problems.append(f"expected at least one of {'/'.join(group)}")
        for coll in self.forbid:
            if counts.get(coll, 0):
                problems.append(
                    f"{coll}: forbidden, got {counts.get(coll, 0)}")
        if self.exhaustive:
            declared = (
                {c for c, _ in self.exact} | {c for c, _ in self.at_least}
                | {c for c, _ in self.at_most} | set(self.forbid)
                | {c for g in self.require for c in g})
            for coll, got in sorted(counts.items()):
                if got and coll not in declared:
                    problems.append(f"{coll}: {got} undeclared by the contract")
        if not problems:
            return []
        detail = "; ".join(problems)
        return [Finding(
            rule="ATP101",
            message=(f"collective contract {self.name!r} violated: {detail} "
                     f"(program collectives: {dict(counts)})"),
            path=f"<program:{self.name}>",
            source=detail,
        )]

    def enforce(self, obj: Any, counts: Counter | None = None) -> None:
        findings = self.check(obj, counts=counts)
        if findings:
            raise AnalysisViolation(findings)


# ------------------------------------------------------------- ATP102 / 103

_TRANSFER_PRIMS = {
    "device_put", "pure_callback", "io_callback", "debug_callback",
    "callback", "infeed", "outfeed", "copy_to_host",
}
_TRANSFER_TEXT_RE = re.compile(
    r"(xla_python_cpu_callback|xla_ffi_python_cpu_callback"
    r"|xla_python_gpu_callback|CallbackToHost|annotate_device_placement"
    r"|stablehlo\.custom_call\s*@\s*Sharding_host"
    r"|\binfeed\b|\boutfeed\b)"
)


def find_host_transfers(obj: Any, name: str = "program") -> list[Finding]:
    """ATP102: host transfers / callbacks baked into a traced program.

    On a jaxpr this walks primitives (device_put, *_callback, infeed,
    outfeed); on HLO/StableHLO text it scans custom-call targets."""
    findings: list[Finding] = []
    if _is_jaxpr(obj) and not isinstance(obj, str):
        hits: Counter = Counter()
        for eqn in _iter_jaxpr_eqns(obj):
            pname = getattr(eqn.primitive, "name", "")
            if pname in _TRANSFER_PRIMS:
                hits[pname] += 1
        for pname, n in sorted(hits.items()):
            findings.append(Finding(
                rule="ATP102",
                message=(f"{n}x `{pname}` inside the traced program "
                         f"{name!r}: each execution round-trips the host, "
                         "serializing the device stream."),
                path=f"<program:{name}>", source=pname,
            ))
        return findings
    text = _as_text(obj)
    hits = Counter(m.group(1) for m in _TRANSFER_TEXT_RE.finditer(text))
    for target, n in sorted(hits.items()):
        findings.append(Finding(
            rule="ATP102",
            message=(f"{n}x host-transfer custom call `{target}` in compiled "
                     f"program {name!r}."),
            path=f"<program:{name}>", source=target,
        ))
    return findings


def _leaf_info(leaf: Any):
    """(nbytes, sharding) for jax.Array / ShapeDtypeStruct-likes."""
    sharding = getattr(leaf, "sharding", None)
    nbytes = getattr(leaf, "nbytes", None)
    if nbytes is None:
        shape = getattr(leaf, "shape", None)
        dtype = getattr(leaf, "dtype", None)
        if shape is None or dtype is None:
            return None, None
        n = 1
        for d in shape:
            n *= int(d)
        nbytes = n * getattr(dtype, "itemsize", 4)
    return int(nbytes), sharding


def audit_replication(tree: Any, threshold_bytes: int = 1 << 20,
                      name: str = "outputs") -> list[Finding]:
    """ATP103: fully-replicated leaves above `threshold_bytes` on a
    multi-device mesh. Replication is correct for small leaves (step
    counters, loss scales); a replicated multi-megabyte array on every
    device of a pod slice is the memory-blowup smell this flags."""
    import jax

    findings: list[Finding] = []
    leaves = jax.tree_util.tree_flatten_with_path(tree)[0]
    for path, leaf in leaves:
        nbytes, sharding = _leaf_info(leaf)
        if nbytes is None or sharding is None or nbytes <= threshold_bytes:
            continue
        spec = getattr(sharding, "spec", None)
        mesh = getattr(sharding, "mesh", None)
        if spec is None or mesh is None:
            continue
        if getattr(mesh, "size", 1) <= 1:
            continue
        if any(s is not None for s in spec):
            continue
        keystr = jax.tree_util.keystr(path)
        findings.append(Finding(
            rule="ATP103",
            message=(f"{name}{keystr} is fully replicated at "
                     f"{nbytes / 2**20:.1f} MiB on a {mesh.size}-device "
                     "mesh — every device holds a full copy. Shard it or "
                     "raise the audit threshold if intended."),
            path=f"<program:{name}>", source=f"{keystr}:{nbytes}",
        ))
    return findings


def audit_compiled_step(compiled: Any, state: Any = None,
                        contract: CollectiveContract | None = None,
                        replication_threshold: int = 1 << 20,
                        name: str = "train_step") -> list[Finding]:
    """The strict-mode bundle `_CompiledTrainStep` runs at trace time:
    contract check + transfer detector over the optimized HLO, plus the
    replication audit over the step's state layout (out == in is pinned,
    so the input layout IS the output layout)."""
    text = _as_text(compiled)
    findings: list[Finding] = []
    if contract is not None:
        findings += contract.check(text)
    findings += find_host_transfers(text, name=name)
    if state is not None:
        findings += audit_replication(
            state, threshold_bytes=replication_threshold, name=f"{name}.state")
    return findings
