"""Sweeps and controls: a cell run with values of its files overridden, or
with the lower-precision control read beside the program. NOT the
benchmark's command, and its output is no result line: what it prints
last carries `"probe"` with every override, and its numbers stand under
`"readings"`, never under `"metrics"`.

    python3 chipbench/probe.py --workload <cell> --seed <n> --seconds <s>
        [--trace 0|1] [--control 1] [--set traffic.rate_per_s=2.5]
        [--set cell.engine.kv_dtype='"int8"'] ...
"""

import time

_STARTED = time.perf_counter()

import argparse  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import sys  # noqa: E402

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))


def probe(workload: str, seed: int, seconds: float, trace: bool = False,
          overrides=(), control: bool = False, started: float | None = None,
          bench_dir: str | None = None, require_chip: bool = True) -> dict:
    from chipbench.harness.context import open_context

    ctx = open_context(workload, seed, seconds, trace, started=started,
                       bench_dir=bench_dir, require_chip=require_chip)
    for item in overrides:  # "traffic.rate_per_s=2.5"
        path, value = item.split("=", 1)
        group, *keys = path.split(".")
        node = {"traffic": ctx.cell.traffic, "cell": ctx.cell.shape}[group]
        for key in keys[:-1]:
            node = node[key]
        node[keys[-1]] = json.loads(value)
    result = ctx.cell.runner().run(
        ctx, **({"with_control": True} if control else {}))
    result["readings"] = result.pop("metrics")
    result["probe"] = {"overrides": list(overrides), "control": bool(control)}
    return result


def main(argv=None) -> int:
    parser = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    parser.add_argument("--workload", required=True)
    parser.add_argument("--seed", type=int, required=True)
    parser.add_argument("--seconds", type=float, required=True)
    parser.add_argument("--trace", type=int, choices=(0, 1), default=0)
    parser.add_argument("--set", action="append", default=[],
                        metavar="GROUP.KEY=JSON", help="override one value "
                        "of the cell's traffic or cell file")
    parser.add_argument("--control", type=int, choices=(0, 1), default=0,
                        help="also read the lower-precision control")
    args = parser.parse_args(argv)
    print(json.dumps(probe(args.workload, args.seed, args.seconds,
                           bool(args.trace), args.set, bool(args.control),
                           started=_STARTED)), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
