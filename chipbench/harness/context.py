"""What a runner gets (`Context`), what a per-layer reader gets (`Run`),
and how the two become the result line."""

from __future__ import annotations

import dataclasses
import math
import sys
import time
from typing import Any

from . import device as device_mod
from .manifest import Cell


def log(msg: str) -> None:
    print(f"[chipbench] {msg}", flush=True)


@dataclasses.dataclass
class Check:
    """Numbers compared with the plain reference, each beside its limit.
    `correct` is the conjunction; every comparison is printed."""

    rows: list = dataclasses.field(default_factory=list)

    def compare(self, name: str, value: float, limit: float,
                at_least: bool = False) -> bool:
        value = float(value)
        ok = (math.isfinite(value)
              and (value >= limit if at_least else value <= limit))
        self.rows.append((name, value, limit, ok))
        log(f"check {name}: {value:.6g} {'>=' if at_least else '<='} "
            f"limit {limit:.6g} -> {'ok' if ok else 'NOT CORRECT'}")
        return ok

    @property
    def correct(self) -> bool:
        return bool(self.rows) and all(r[3] for r in self.rows)


@dataclasses.dataclass
class Context:
    cell: Cell
    seed: int
    seconds: float
    trace: bool
    started: float                  # perf_counter at process start
    require_chip: bool = True
    device: dict = dataclasses.field(default_factory=dict)
    compiles: Any = None

    def setup_seconds(self, window_open: float) -> float:
        """Process start to window open. The reference never runs inside
        it: runners run it once the window has closed."""
        return window_open - self.started


@dataclasses.dataclass
class Run:
    """Everything the window left behind, for metric readers. A reader
    takes what it needs and returns a number, or None when there is
    nothing to read."""

    cell: Cell
    device: dict
    peaks: dict | None
    window_s: float
    setup_s: float
    counters: dict                  # window deltas of counts
    samples: dict                   # name -> list of host-clock samples
    trace: Any = None               # trace_reduce.TraceSummary of a traced run
    end_to_end: dict = dataclasses.field(default_factory=dict)


def percentile(values, q: float) -> float | None:
    """The q-th percentile (0-100) by linear interpolation; None if empty."""
    vals = sorted(values)
    if not vals:
        return None
    pos = (len(vals) - 1) * q / 100.0
    lo = int(math.floor(pos))
    hi = min(lo + 1, len(vals) - 1)
    return vals[lo] + (vals[hi] - vals[lo]) * (pos - lo)


def open_context(cell_name: str, seed: int, seconds: float, trace: bool,
                 started: float | None = None, bench_dir: str | None = None,
                 require_chip: bool = True) -> Context:
    """Everything before the runner: find the cell, insist on the chip, fix
    the compile cache, forbid interpreted kernels."""
    started = time.perf_counter() if started is None else started
    cell = Cell(cell_name, bench_dir) if bench_dir else Cell(cell_name)
    if cell.root not in sys.path:
        sys.path.insert(0, cell.root)
    import accelerate_tpu  # noqa: F401  fails in a directory without the program
    from accelerate_tpu.ops.kernel_mode import require_compiled

    ctx = Context(cell=cell, seed=int(seed), seconds=float(seconds),
                  trace=bool(trace), started=started,
                  require_chip=require_chip)
    ctx.device = device_mod.describe(cell.chips, require_chip)
    if require_chip:
        cache = device_mod.configure_compile_cache(cell.root)
        log(f"compile cache: {cache}")
        require_compiled()
    ctx.compiles = device_mod.CompileCounter()
    log(f"cell {cell.name}: config {cell.entry['config']}, traffic "
        f"{cell.entry['traffic']} ({cell.kind}), device {ctx.device}, seed "
        f"{ctx.seed}, window {ctx.seconds:g} s, trace {int(ctx.trace)}")
    return ctx


def result_line(ctx: Context, run: Run, check: Check, attempted: int,
                failed: int, breakdown: dict | None) -> dict:
    """The contract's last line: end-to-end metrics untraced, per-layer
    metrics traced. A reader that returns None leaves its metric out."""
    cell = ctx.cell
    metrics: dict = {}
    if not ctx.trace:
        for m in cell.end_to_end():
            value = run.end_to_end.get(m["name"])
            if value is not None:
                metrics[m["name"]] = {"value": float(value),
                                      "unit": m["unit"]}
    else:
        for m in cell.per_layer():
            value = cell.layer_reader(m["name"]).read(run)
            if value is not None and math.isfinite(float(value)):
                metrics[m["name"]] = {"value": float(value),
                                      "unit": m["unit"]}
    dev = dict(ctx.device)
    # read when the window closed, before any reference ran
    dev["memory_peak_bytes"] = int(run.counters["memory_peak_bytes"])
    if ctx.trace and run.trace is not None:
        dev["busy_s"] = run.trace.busy_s
        dev["window_s"] = run.trace.window_s
    out = {"correct": check.correct, "attempted": int(attempted),
           "failed": int(failed), "metrics": metrics, "device": dev}
    if ctx.trace and breakdown:
        out["breakdown"] = breakdown
    return out
