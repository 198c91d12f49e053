"""The command as the driver runs it, without a TPU: it must FAIL (no
result line, non-zero exit), never carry on on the CPU."""

import json
import os
import shutil
import subprocess
import sys

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))


def _cells():
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        return [w["name"] for w in json.load(f)["workloads"]]


def _drive(cwd, cell, extra_env=None):
    env = dict(os.environ, JAX_PLATFORMS="cpu", BENCH_RUN="7")
    env.pop("PYTHONPATH", None)
    env.update(extra_env or {})
    return subprocess.run(
        [sys.executable, "chipbench/run.py", "--workload", cell, "--seed",
         "2147483659", "--seconds", "1", "--trace", "0"],
        cwd=cwd, env=env, capture_output=True, text=True, timeout=300)


def _no_result_line(stdout: str) -> bool:
    for line in stdout.strip().splitlines()[-1:]:
        try:
            if "metrics" in json.loads(line):
                return False
        except ValueError:
            pass
    return True


@pytest.mark.parametrize("cell", _cells())
def test_without_a_tpu_the_command_fails_and_prints_no_result(cell):
    proc = _drive(ROOT, cell)
    assert proc.returncode != 0
    assert _no_result_line(proc.stdout)
    assert "no TPU" in proc.stderr


def test_in_a_directory_without_the_program_the_command_fails(tmp_path):
    shutil.copy(os.path.join(ROOT, "BENCHMARK.json"), tmp_path)
    shutil.copytree(os.path.join(ROOT, "chipbench"), tmp_path / "chipbench",
                    ignore=shutil.ignore_patterns(".work", "__pycache__"))
    proc = _drive(str(tmp_path), _cells()[0])
    assert proc.returncode != 0
    assert _no_result_line(proc.stdout)


def test_an_unknown_cell_fails():
    proc = _drive(ROOT, "no-such-cell")
    assert proc.returncode != 0 and _no_result_line(proc.stdout)
