"""SIGKILL mid-save -> auto-resume (ISSUE 20 acceptance).

A subprocess (tests/crash_resume_script.py) commits a complete async
checkpoint at step 4, then SIGKILLs itself while step 6's background
persist is in flight. The parent asserts the on-disk outcome of the
commit protocol — step 6 torn and invisible, step 4 the newest complete
manifest — then resumes IN-PROCESS from what the dead process left
behind and verifies the continued loss trajectory is identical to an
unfaulted run.
"""

from __future__ import annotations

import importlib.util
import os
import signal
import subprocess
import sys

import pytest

from accelerate_tpu import checkpointing as ckpt
from accelerate_tpu.test_utils import checkout_child_env

_SCRIPT = os.path.join(os.path.dirname(__file__), "crash_resume_script.py")


def _load_script_module():
    spec = importlib.util.spec_from_file_location("crash_resume_script",
                                                  _SCRIPT)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def test_sigkill_mid_save_resumes_loss_curve_exact(tmp_path):
    # the child imports accelerate_tpu from this checkout (not installed)
    env = checkout_child_env({"JAX_PLATFORMS": "cpu",
                              "CRASH_DIR": str(tmp_path)})
    out = subprocess.run([sys.executable, _SCRIPT], env=env,
                         capture_output=True, text=True, timeout=300)
    assert out.returncode == -signal.SIGKILL, (out.returncode, out.stderr)
    assert "ENQUEUED" in out.stdout  # died mid-persist, after the enqueue

    mod = _load_script_module()
    committed = os.path.join(str(tmp_path), f"step_{mod.COMMIT_STEP:08d}")
    torn = os.path.join(str(tmp_path), f"step_{mod.TORN_STEP:08d}")
    # the commit protocol's crash matrix: drained save complete, killed
    # save torn (bytes may exist — the manifest must not)
    assert ckpt.is_complete_checkpoint(committed)
    assert not ckpt.is_complete_checkpoint(torn)
    assert ckpt.latest_complete_checkpoint(str(tmp_path)) == \
        os.path.abspath(committed)

    # unfaulted reference trajectory, same deterministic toy loop
    ref_state = mod.make_state()
    reference = []
    for i in range(mod.NUM_STEPS):
        ref_state, metrics = mod.step_fn(ref_state, mod.batch_fn(i))
        reference.append(float(metrics["loss"]))

    # resume from the dead process's newest complete manifest
    state = mod.make_state()
    restored = ckpt.resume_latest(str(tmp_path), train_states=[state])
    assert restored is not None
    assert restored["step"] == mod.COMMIT_STEP
    assert restored["checkpoint_dir"] == os.path.abspath(committed)
    state = restored["train_states"][0]
    for i in range(mod.COMMIT_STEP, mod.NUM_STEPS):
        state, metrics = mod.step_fn(state, mod.batch_fn(i))
        assert float(metrics["loss"]) == pytest.approx(
            reference[i], abs=1e-7), i
