"""`accelerate-tpu bench-diff` — the bench regression gate (ISSUE 11).

Compares two bench rows (bench.py's one-line JSON, or a BENCH_r*.json
capture file wrapping it under "parsed") metric by metric with relative
tolerances, so the r01-r05 trajectory becomes CHECKABLE instead of
write-only::

    accelerate-tpu bench-diff BENCH_r02.json new.json --tolerance 0.05
    accelerate-tpu bench-diff old.json new.json \
        --metric-tolerance ttft_p99_ms=0.25 --format json

Exit codes: 0 = no regression, 1 = at least one metric regressed beyond
its tolerance (or the headline degraded value -> error), 2 = malformed
input (unreadable JSON, a row violating the schema contract, bad args).

Only metrics with a KNOWN direction are compared (tokens/s up is good,
ttft_p99_ms up is bad); everything else — params, seq, wall_s, device —
is configuration, not performance, and comparing it would manufacture
false alarms. Phase rows (extra.serving / serving_prefix / server / pod,
schema v2) compare their "value" dicts; a phase that went value -> error
is itself a regression finding. jax-free on purpose: the gate must run
on CI boxes and laptops with no accelerator stack.

`benchmarks/regression.py` is the in-repo script form of the same gate.
"""

from __future__ import annotations

import argparse
import json
import sys

__all__ = ["MalformedRow", "load_row", "iter_comparable_metrics",
           "metric_direction", "compare_rows", "main",
           "register_subcommand"]


class MalformedRow(ValueError):
    """The row violates the bench schema contract (see bench.py)."""


# Metric direction by LEAF key (the last dotted path component).
# +1 = higher is better, -1 = lower is better. Anything unlisted is
# informational and never compared.
_HIGHER_IS_BETTER = {
    "value", "vs_baseline", "mfu", "goodput", "training",
    "tokens_per_sec", "cpu_smoke_tokens_per_sec",
    "tokens_per_sec_per_chip", "steps_per_sec",
    "prefix_hit_rate", "cached_token_fraction", "slo_attainment",
    "decode_mfu", "decode_hbm_bw_util", "hbm_bw_util",
    "train_mfu_measured",
    # speculative decoding (ISSUE 12): committed tokens per decode-role
    # step is the headline lever; the accept rate is its driver
    "tokens_per_decode_step", "spec_accept_rate",
    # hierarchical KV (ISSUE 16): prefix hits served from the host tier
    # are re-prefills avoided; dedup hits are whole prefills avoided;
    # the A/B row's chunk ratio is the headline (no-tier chunks over
    # with-tier chunks, >= 2x on the churn workload)
    "prefix_hits_host", "prefix_dedup_hits", "prefill_chunk_ratio",
    # resilient training (ISSUE 20): goodput of the run_resilient loop —
    # useful step time over wall, with the checkpoint/resume machinery on
    "resilient",
}
_LOWER_IS_BETTER = {
    "ttft_p50_ms", "ttft_p99_ms", "ttft_mean_ms",
    "per_token_p50_ms", "per_token_p99_ms", "per_token_mean_ms",
    "client_ttft_p50_ms", "client_ttft_p99_ms",
    "queue_wait_p50_ms", "queue_wait_p99_ms", "queue_wait_mean_ms",
    "host_dispatch_us", "host_dispatch_us_mean",
    "step_time_p50_s", "step_time_p99_s", "step_time_mean_s",
    "decode_device_time_mean_ms", "decode_device_time_p99_ms",
    "prefill_device_time_mean_ms", "prefill_device_time_p99_ms",
    "train_device_time_sampled_ms",
    "mxu_idle_fraction", "decode_mxu_idle_fraction",
    # hierarchical KV: PCIe round-trip cost per swapped-in prefix page
    "swap_in_p50_ms", "swap_in_p99_ms", "swap_in_mean_ms",
    # true multi-host pod (ISSUE 17): every replayed request re-pays its
    # prefill, every lost worker is an availability event, and recovery
    # latency is the time a stream stalls before its replay lands
    "pod_requests_replayed", "pod_workers_lost",
    "pod_recovery_latency_p50_ms", "pod_recovery_latency_p99_ms",
    "pod_recovery_latency_mean_ms",
    # pod distributed tracing (ISSUE 18): span-export lag bounds how
    # stale a merged fleet trace is; the tracing A/B overhead should
    # round to zero — a regression here is instrumentation on the hot
    # path
    "pod_span_export_lag_s", "pod_trace_overhead_pct",
    # resilient training (ISSUE 20): how long the loop BLOCKS on the
    # async checkpoint writer, and how long a preempted run takes to
    # find + restore the newest complete manifest
    "checkpoint_drain_p99_s", "checkpoint_drain_mean_s",
    "checkpoint_stage_mean_s", "resume_latency_s",
}


def metric_direction(key: str) -> int:
    """+1 (higher better), -1 (lower better), 0 (not compared) for a
    dotted metric path, classified by its leaf component."""
    leaf = key.rsplit(".", 1)[-1]
    if leaf in _HIGHER_IS_BETTER:
        return 1
    if leaf in _LOWER_IS_BETTER:
        return -1
    return 0


def load_row(path: str) -> dict:
    """One bench row from `path`: either the raw one-line JSON bench.py
    prints, or a BENCH_r*.json capture file (the row rides under
    "parsed"). Raises MalformedRow on unreadable/contract-violating
    input."""
    try:
        with open(path) as f:
            data = json.load(f)
    except (OSError, json.JSONDecodeError) as e:
        raise MalformedRow(f"{path}: {e}")
    if not isinstance(data, dict):
        raise MalformedRow(f"{path}: bench row must be a JSON object")
    if "parsed" in data and isinstance(data["parsed"], dict):
        data = data["parsed"]  # the driver's capture wrapper
    validate_row(data, path)
    return data


def validate_row(row: dict, label: str = "row") -> None:
    """The slice of the schema contract both v1 and v2 rows satisfy:
    non-null metric and unit, and at least one of value/error/skipped
    populated (v2 additionally guarantees EXACTLY one — enforced at the
    writer by bench._normalize_row; the reader accepts v1 history).
    Phase rows under extra.* are checked the same way when present."""
    if row.get("metric") is None or row.get("unit") is None:
        raise MalformedRow(f"{label}: null metric/unit")
    if all(row.get(k) is None for k in ("value", "error", "skipped")):
        raise MalformedRow(
            f"{label}: none of value/error/skipped populated")
    if row.get("schema_version", 1) >= 2:
        populated = [k for k in ("value", "error", "skipped")
                     if row.get(k) is not None]
        if len(populated) != 1:
            raise MalformedRow(
                f"{label}: schema v2 requires exactly one of "
                f"value/error/skipped, got {populated}")
    for phase, sub in (row.get("extra") or {}).items():
        if isinstance(sub, dict) and "metric" in sub:
            if sub.get("metric") is None or sub.get("unit") is None:
                raise MalformedRow(
                    f"{label}: phase row extra.{phase} has null "
                    "metric/unit")
            if all(sub.get(k) is None
                   for k in ("value", "error", "skipped")):
                raise MalformedRow(
                    f"{label}: phase row extra.{phase} has none of "
                    "value/error/skipped")


def _walk_numeric(obj, prefix: str):
    if isinstance(obj, bool):
        return
    if isinstance(obj, (int, float)):
        yield prefix, float(obj)
    elif isinstance(obj, dict):
        for k, v in obj.items():
            yield from _walk_numeric(v, f"{prefix}.{k}" if prefix else k)


def iter_comparable_metrics(row: dict):
    """(dotted_path, value) for every numeric metric with a known
    direction: the headline value and vs_baseline, extra.* scalars, and
    each phase row's "value" dict (flattened as extra.<phase>.<key>)."""
    for key in ("value", "vs_baseline"):
        v = row.get(key)
        if isinstance(v, (int, float)) and not isinstance(v, bool):
            yield key, float(v)
    for key, sub in (row.get("extra") or {}).items():
        if isinstance(sub, dict) and "metric" in sub:
            # schema-v2 phase row: the stats ride under "value"
            val = sub.get("value")
            if isinstance(val, dict):
                for path, v in _walk_numeric(val, f"extra.{key}"):
                    if metric_direction(path):
                        yield path, v
            continue
        for path, v in _walk_numeric(sub, f"extra.{key}"):
            if metric_direction(path):
                yield path, v


def _phase_states(row: dict) -> dict[str, str]:
    """extra phase name -> "value" | "error" | "skipped" (phase rows
    only)."""
    out = {}
    for key, sub in (row.get("extra") or {}).items():
        if isinstance(sub, dict) and "metric" in sub:
            out[key] = next((k for k in ("error", "skipped", "value")
                             if sub.get(k) is not None), "error")
    return out


def compare_rows(old: dict, new: dict, tolerance: float = 0.05,
                 overrides: dict[str, float] | None = None) -> dict:
    """Compare every shared, direction-known metric; returns the report::

        {"compared": N,
         "regressions":  [{key, old, new, change, tolerance}, ...],
         "improvements": [...same shape...],
         "degraded":     ["<headline or phase that went value->error>"]}

    `change` is the relative move in the GOOD direction (negative =
    worse). A metric regresses when it moves worse than its tolerance
    (per-key `overrides` by leaf or full path win over the global one).
    A headline or phase row that had a value in `old` but carries an
    error in `new` lands in "degraded" (counted with the regressions —
    losing the number IS a regression); `old` errors compare nothing."""
    overrides = overrides or {}
    old_metrics = dict(iter_comparable_metrics(old))
    new_metrics = dict(iter_comparable_metrics(new))
    regressions, improvements = [], []
    compared = 0
    for key in sorted(set(old_metrics) & set(new_metrics)):
        direction = metric_direction(key)
        o, n = old_metrics[key], new_metrics[key]
        if not (o == o and n == n) or o == 0.0:
            continue  # NaN or no meaningful relative baseline
        compared += 1
        tol = overrides.get(key,
                            overrides.get(key.rsplit(".", 1)[-1],
                                          tolerance))
        change = direction * (n - o) / abs(o)
        entry = {"key": key, "old": o, "new": n,
                 "change": round(change, 6), "tolerance": tol}
        if change < -tol:
            regressions.append(entry)
        elif change > tol:
            improvements.append(entry)
    degraded = []
    if old.get("value") is not None and new.get("value") is None \
            and new.get("skipped") is None:
        degraded.append("value (headline went value -> error)")
    old_phases, new_phases = _phase_states(old), _phase_states(new)
    for phase, state in sorted(old_phases.items()):
        if state == "value" and new_phases.get(phase) == "error":
            degraded.append(f"extra.{phase} (phase went value -> error)")
    return {"compared": compared, "regressions": regressions,
            "improvements": improvements, "degraded": degraded}


def _parse_overrides(pairs: list[str]) -> dict[str, float]:
    out = {}
    for pair in pairs or []:
        key, eq, val = pair.partition("=")
        if not eq:
            raise ValueError(f"bad --metric-tolerance {pair!r} "
                             "(want key=fraction)")
        try:
            out[key.strip()] = float(val)
        except ValueError:
            raise ValueError(
                f"--metric-tolerance {key!r}={val!r} is not a number")
    return out


def _add_args(p: argparse.ArgumentParser) -> None:
    p.add_argument("old", help="baseline row (bench JSON line or "
                               "BENCH_r*.json capture)")
    p.add_argument("new", help="candidate row to gate")
    p.add_argument("--tolerance", type=float, default=0.05,
                   help="default relative tolerance (fraction of the "
                        "old value; default 0.05)")
    p.add_argument("--metric-tolerance", action="append", default=[],
                   metavar="KEY=FRAC",
                   help="per-metric override, by leaf name or full "
                        "dotted path (repeatable), e.g. "
                        "ttft_p99_ms=0.25")
    p.add_argument("--format", choices=("text", "json"), default="text")


def _build_parser() -> argparse.ArgumentParser:
    p = argparse.ArgumentParser(
        "accelerate-tpu bench-diff",
        description="Compare two bench rows with per-metric tolerances; "
                    "exit 1 on regression, 2 on malformed input.")
    _add_args(p)
    return p


def main(argv: list[str] | None = None) -> int:
    try:
        args = _build_parser().parse_args(argv)
    except SystemExit as e:
        return 2 if e.code not in (0, None) else 0
    return run_diff(args)


def run_diff(args: argparse.Namespace) -> int:
    try:
        overrides = _parse_overrides(args.metric_tolerance)
        old = load_row(args.old)
        new = load_row(args.new)
    except (MalformedRow, ValueError) as e:
        print(f"bench-diff: {e}", file=sys.stderr)
        return 2
    report = compare_rows(old, new, tolerance=args.tolerance,
                          overrides=overrides)
    failed = bool(report["regressions"] or report["degraded"])
    if args.format == "json":
        print(json.dumps(dict(report, passed=not failed)))
        return 1 if failed else 0
    for entry in report["regressions"]:
        print(f"REGRESSION {entry['key']}: {entry['old']:g} -> "
              f"{entry['new']:g} ({entry['change']:+.1%}, tolerance "
              f"{entry['tolerance']:.0%})")
    for what in report["degraded"]:
        print(f"DEGRADED   {what}")
    for entry in report["improvements"]:
        print(f"improved   {entry['key']}: {entry['old']:g} -> "
              f"{entry['new']:g} ({entry['change']:+.1%})")
    verdict = "FAIL" if failed else "PASS"
    print(f"{verdict}: {report['compared']} metric(s) compared, "
          f"{len(report['regressions'])} regression(s), "
          f"{len(report['degraded'])} degraded row(s)")
    return 1 if failed else 0


def register_subcommand(subparsers) -> None:
    parser = subparsers.add_parser(
        "bench-diff",
        help="compare two bench rows; exit nonzero on perf regression",
        description="Gate a bench row against a baseline "
                    "(docs/benchmarking.md#regression-gate).")
    _add_args(parser)
    parser.set_defaults(func=run_diff)


if __name__ == "__main__":
    # `python -m accelerate_tpu.commands.bench_diff ...` must behave like
    # `accelerate-tpu bench-diff ...` (the lint `__main__`-guard lesson)
    from .accelerate_cli import main as cli_main

    sys.exit(cli_main(["bench-diff", *sys.argv[1:]]))
