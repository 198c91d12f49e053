"""Driver for the reference-benchmark table rows with real forensics.

Runs `big_model_inference.py` for each requested preset as a subprocess,
appending stdout JSON lines to `bench_results/<preset>.jsonl` and capturing
FULL stderr (not just the platform warning) into `bench_results/<preset>.err`
together with the exit code, phase timings, and the kill reason on timeout —
so a decode that dies leaves a diagnosis behind.

Run: python benchmarks/run_big_model_rows.py [preset ...]
     (default: the four reference rows, ref benchmarks/README.md:29-35)

Timeouts are generous: a streamed NeoX/OPT decode moves the full
stacked-layer bytes per token over the host->device link, so one token can
take minutes on a slow link. `--timeout` overrides.
"""

from __future__ import annotations

import argparse
import json
import os
import subprocess
import sys
import time

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
RESULTS = os.path.join(ROOT, "bench_results")

DEFAULT_ROWS = ["gptj-6b", "t0pp", "gpt-neox-20b", "opt-30b"]
# generous wall-clock ceilings per preset (load + compile + decode)
TIMEOUTS = {
    "gptj-6b": 3600,
    "t0pp": 5400,
    "gpt-neox-20b": 14400,
    "opt-30b": 18000,
}


def run_preset(preset: str, timeout: int | None, extra_args: list[str]) -> int:
    os.makedirs(RESULTS, exist_ok=True)
    out_path = os.path.join(RESULTS, f"{preset}.jsonl")
    err_path = os.path.join(RESULTS, f"{preset}.err")
    cmd = [sys.executable, os.path.join(ROOT, "benchmarks",
                                        "big_model_inference.py"),
           "--preset", preset, *extra_args]
    limit = timeout or TIMEOUTS.get(preset, 3600)
    t0 = time.time()
    with open(err_path, "w") as err:
        err.write(f"# cmd: {' '.join(cmd)}\n# started: {time.ctime()}\n")
        err.flush()
        try:
            proc = subprocess.run(
                cmd, stdout=subprocess.PIPE, stderr=err, text=True,
                timeout=limit,
            )
            rc, stdout = proc.returncode, proc.stdout
        except subprocess.TimeoutExpired as e:
            rc = -1
            stdout = (e.stdout or b"").decode() if isinstance(
                e.stdout, bytes) else (e.stdout or "")
            err.write(f"\n# KILLED: exceeded {limit}s wall clock\n")
        err.write(f"# finished: {time.ctime()} rc={rc} "
                  f"wall={time.time() - t0:.1f}s\n")
    lines = [ln for ln in stdout.splitlines() if ln.startswith("{")]
    with open(out_path, "a") as f:
        for ln in lines:
            f.write(ln + "\n")
    print(f"{preset}: rc={rc}, {len(lines)} row(s), "
          f"wall={time.time() - t0:.1f}s -> {out_path}")
    if rc != 0:
        tail = open(err_path).read().splitlines()[-8:]
        print("\n".join(f"  err| {ln}" for ln in tail))
    return rc


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("presets", nargs="*", default=DEFAULT_ROWS)
    ap.add_argument("--timeout", type=int, default=None)
    ap.add_argument("--new_tokens", type=int, default=None)
    args = ap.parse_args()
    extra = (["--new_tokens", str(args.new_tokens)]
             if args.new_tokens else [])
    rcs = [run_preset(p, args.timeout, extra) for p in args.presets]
    sys.exit(max((abs(rc) for rc in rcs), default=0))


if __name__ == "__main__":
    main()
