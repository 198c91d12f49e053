"""chip_smoke.py, rehearsed on the CPU.

The script itself only ever runs at real sizes and only on a TPU. These
tests (a) drive its three phase functions in-process at the explicit
test-only `TINY` sizes, so that a wrong path, argument or control flow is
found here and not on chip time, and (b) run the script as the driver
does and check that without a TPU it FAILS: non-zero exit, `"ok": false`
in the last line, no phase carried out on another backend.
"""

import json
import os
import shutil
import subprocess
import sys

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


@pytest.fixture(scope="module")
def smoke():
    import importlib.util

    spec = importlib.util.spec_from_file_location(
        "chip_smoke", os.path.join(ROOT, "chip_smoke.py"))
    mod = importlib.util.module_from_spec(spec)
    sys.modules["chip_smoke"] = mod  # dataclasses resolves the module
    spec.loader.exec_module(mod)
    yield mod
    sys.modules.pop("chip_smoke", None)


@pytest.fixture(autouse=True)
def _fresh_state():
    from accelerate_tpu.state import PartialState

    PartialState._reset_state()
    yield
    PartialState._reset_state()


def test_train_phase_rehearsal_at_tiny_size(smoke, tmp_path, capsys):
    out = smoke.train_phase(smoke.TINY, str(tmp_path), expect_chip=False)
    assert len(out["losses"]) == smoke.TINY.train_steps
    assert out["losses"][-1] < out["losses"][0]
    text = capsys.readouterr().out
    assert "recompiles after warm-up = 0" in text
    assert "round trip bit-identical" in text
    assert "token loader implementation = " in text
    assert "depth cut 2 -> 2 layers" in text


def test_serve_phase_rehearsal_at_tiny_size(smoke, capsys):
    out = smoke.serve_phase(smoke.TINY, expect_chip=False)
    assert out["kernel_steps"] > 0
    assert out["logprob_gap_dense"] <= smoke.ENGINE_LOGPROB_TOL
    assert out["logprob_gap_f32"] <= smoke.F32_LOGPROB_TOL
    text = capsys.readouterr().out
    assert "POST /v1/completions -> 200" in text
    assert "{'admit': 1, 'prefill': 1, 'decode': 1}" in text
    assert "'paged_decode_attention': 'interpret'" in text  # the CPU says so


def test_multichip_phase_rehearsal_on_four_virtual_devices(smoke, capsys):
    smoke.multichip_phase(smoke.TINY, expect_chip=False)
    text = capsys.readouterr().out
    assert "mesh {'fsdp': 4} over 4 devices" in text
    assert "mesh {'data': 2, 'model': 2} over 4 devices" in text
    assert text.count("on devices [0, 1, 2, 3]") == 2
    assert "train: " not in text and "serve: " not in text  # no other phase


def test_real_sizes_are_the_published_qwen2_1p5b_widths(smoke):
    """Widths are never cut; only the training depth is (and says so)."""
    r = smoke.REAL
    assert (r.vocab_size, r.hidden_size, r.intermediate_size,
            r.num_attention_heads, r.num_key_value_heads, r.full_layers) == (
        151936, 1536, 8960, 12, 2, 28)
    cfg = smoke.model_config(r, r.full_layers)
    assert cfg.head_dim == 128 and cfg.tie_word_embeddings
    assert cfg.attention_bias and cfg.rope_theta == 1e6
    assert cfg.attention_backend == "auto"
    assert r.train_seq == 2048 and r.train_layers < r.full_layers


def _run_script(cwd, *args):
    from accelerate_tpu.test_utils import checkout_child_env

    env = checkout_child_env({"JAX_PLATFORMS": "cpu"})
    env.pop("PYTHONPATH")  # the script must find the package by itself
    return subprocess.run(
        [sys.executable, os.path.join(cwd, "chip_smoke.py"), *args],
        env=env, cwd=cwd, capture_output=True, text=True, timeout=300)


@pytest.mark.parametrize("args", [(), ("--multichip",)],
                         ids=["one-chip", "multichip"])
def test_script_without_a_tpu_exits_nonzero_with_ok_false(args):
    out = _run_script(ROOT, *args)
    assert out.returncode != 0, out.stdout[-800:]
    last = json.loads(out.stdout.strip().splitlines()[-1])
    assert last["ok"] is False
    assert last["device"]["platform"] == "cpu"
    assert "no TPU" in out.stdout
    # it failed AT ONCE: no phase ran on the CPU
    assert "train: step" not in out.stdout
    assert "serve: " not in out.stdout and "multichip: " not in out.stdout


def test_script_alone_in_a_directory_fails(tmp_path):
    """Without the rest of the repo beside it there is nothing to prove:
    non-zero exit, and never an `"ok": true` line."""
    shutil.copy(os.path.join(ROOT, "chip_smoke.py"), str(tmp_path))
    out = _run_script(str(tmp_path))
    assert out.returncode != 0
    assert '"ok": true' not in out.stdout
    assert json.loads(out.stdout.strip().splitlines()[-1])["ok"] is False
