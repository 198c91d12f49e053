"""The benchmark's one command.

    python3 chipbench/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

One new process per run, started from the root of a plain checkout. It
finds the cell by name in `BENCHMARK.json` and `chipbench/`, insists on
the chips the cell asks for (no TPU, an unknown device kind or an
interpreted kernel: exit 2 and no result line), makes weights and traffic
from `--seed`, warms exactly the cell's shapes, measures for `--seconds`,
checks the timed path's outputs against the configuration's plain
reference, and prints one JSON object as the last line of stdout. It has
no other mode: sweeps and controls are `chipbench/probe.py`'s.
"""

import time

_STARTED = time.perf_counter()

import argparse  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import sys  # noqa: E402
import traceback  # noqa: E402

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))


def run_cell(workload: str, seed: int, seconds: float, trace: bool,
             started: float | None = None, bench_dir: str | None = None,
             require_chip: bool = True, **runner_kwargs) -> dict:
    """One run, in this process; returns the result line as a dict."""
    from chipbench.harness.context import open_context

    ctx = open_context(workload, seed, seconds, trace, started=started,
                       bench_dir=bench_dir, require_chip=require_chip)
    return ctx.cell.runner().run(ctx, **runner_kwargs)


def main(argv=None) -> int:
    parser = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    parser.add_argument("--workload", required=True)
    parser.add_argument("--seed", type=int, required=True)
    parser.add_argument("--seconds", type=float, required=True)
    parser.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = parser.parse_args(argv)
    try:
        result = run_cell(args.workload, args.seed, args.seconds,
                          bool(args.trace), started=_STARTED)
    except Exception:  # noqa: BLE001 - the boundary: no result line, exit 2
        traceback.print_exc()
        sys.stderr.flush()
        return 2
    sys.stdout.flush()
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
