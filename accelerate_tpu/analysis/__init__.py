"""accelerate_tpu.analysis — TPU hazard linter + program contract auditor.

Two pass families, one `Finding` currency:

- **Source passes** (`lint_text`/`lint_file`/`lint_paths`): AST rules
  ATP001-ATP008 over Python source — host syncs in traced code, untraced
  randomness, Python control flow on tracers, recompile hazards, donation
  aliasing. No jax import required; this is what `accelerate-tpu lint`
  and the tier-1 self-lint gate run.
- **Program passes** (`collective_counts`/`CollectiveContract`/
  `find_host_transfers`/`audit_replication`): ATP101-ATP103 over lowered
  or compiled jax programs. `contract_for`/`shard_map_contracts` expose
  the repo's per-jax-version contract table;
  `Accelerator(strict="warn"|"error")` runs these at trace time.

See docs/static-analysis.md for the rule catalog and suppression syntax.
"""

from .findings import (  # noqa: F401
    AnalysisViolation,
    Finding,
    Rule,
    RULES,
    apply_suppressions,
    baseline_payload,
    load_baseline,
    new_findings,
    parse_suppressions,
    save_baseline,
)
from .source import lint_source, lint_text  # noqa: F401
from .lifecycle import (  # noqa: F401
    PAIRING_TABLE,
    REQUEST_FSM,
    RequestFSM,
    ResourcePair,
    THREAD_ENTRIES,
    ThreadEntries,
    lint_lifecycle,
)
from .concurrency import (  # noqa: F401
    BLOCKING_CALLS,
    BlockingCall,
    lint_concurrency,
)
from .program import (  # noqa: F401
    CANONICAL_COLLECTIVES,
    CollectiveContract,
    audit_compiled_step,
    audit_replication,
    collective_counts,
    find_host_transfers,
)
from .contracts import (  # noqa: F401
    contract_for,
    serving_program_contracts,
    shard_map_contracts,
)
from .runner import (  # noqa: F401
    iter_python_files,
    lint_file,
    lint_paths,
    lint_target,
    render_human,
    render_json,
    resolve_target,
)

__all__ = [
    "AnalysisViolation",
    "Finding",
    "Rule",
    "RULES",
    "CANONICAL_COLLECTIVES",
    "CollectiveContract",
    "audit_compiled_step",
    "audit_replication",
    "collective_counts",
    "find_host_transfers",
    "contract_for",
    "serving_program_contracts",
    "shard_map_contracts",
    "PAIRING_TABLE",
    "REQUEST_FSM",
    "RequestFSM",
    "ResourcePair",
    "THREAD_ENTRIES",
    "ThreadEntries",
    "BLOCKING_CALLS",
    "BlockingCall",
    "lint_lifecycle",
    "lint_concurrency",
    "lint_source",
    "lint_text",
    "lint_file",
    "lint_paths",
    "lint_target",
    "iter_python_files",
    "render_human",
    "render_json",
    "resolve_target",
    "load_baseline",
    "save_baseline",
    "baseline_payload",
    "new_findings",
    "parse_suppressions",
    "apply_suppressions",
]
