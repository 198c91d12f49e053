"""Profiler subsystem + debug-mode collective verification."""

import glob
import math
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from accelerate_tpu.profiler import (
    StepTimer,
    annotate,
    causal_lm_train_flops,
    device_memory_stats,
    peak_flops_per_chip,
    profile,
)


def test_profile_writes_trace(tmp_path):
    with profile(str(tmp_path)):
        with annotate("matmul-region"):
            x = jnp.ones((64, 64))
            jax.block_until_ready(x @ x)
    produced = glob.glob(str(tmp_path / "**" / "*"), recursive=True)
    assert any(os.path.isfile(p) for p in produced), produced


def test_step_timer_throughput():
    timer = StepTimer(tokens_per_step=100, warmup_steps=1)
    for _ in range(5):
        timer.tick()
    assert timer.steps_recorded == 3
    assert timer.steps_per_sec > 0
    assert timer.tokens_per_sec == pytest.approx(timer.steps_per_sec * 100)


def test_step_timer_warmup_excluded():
    timer = StepTimer(warmup_steps=10)
    for _ in range(3):
        timer.tick()
    assert timer.steps_recorded == 0
    assert math.isnan(timer.mean_step_time)


def test_step_timer_host_overhead_metrics():
    timer = StepTimer(warmup_steps=1)
    for _ in range(4):
        with timer.input_stall():
            pass
        with timer.dispatch():
            pass
        timer.tick()
    # warmup excluded: first iteration's readings (seen < warmup) dropped
    assert timer._dispatch_hist.count == 3
    assert timer._stall_hist.count == 3
    assert timer.host_dispatch_us >= 0
    assert timer.input_stall_us >= 0
    summary = timer.summary()
    assert "host_dispatch_us_mean" in summary
    assert "input_stall_us_mean" in summary


def test_step_timer_host_overhead_empty_is_nan():
    timer = StepTimer()
    assert math.isnan(timer.host_dispatch_us)
    assert math.isnan(timer.input_stall_us)
    assert "host_dispatch_us_mean" not in timer.summary()


def test_mfu_math():
    timer = StepTimer(flops_per_step=1e12, peak_flops=1e13, num_chips=1,
                      warmup_steps=0)
    timer._step_hist.record(0.5)  # 2e12 FLOPs/s achieved vs 1e13 peak
    assert timer.mfu() == pytest.approx(0.2)


def test_causal_lm_flops():
    base = causal_lm_train_flops(1_000_000, 512, attention=False)
    assert base == pytest.approx(6.0 * 1_000_000 * 512)
    with_attn = causal_lm_train_flops(
        1_000_000, 512, num_layers=4, hidden_size=64, seq_len=128
    )
    assert with_attn > base


def test_device_memory_stats_shape():
    stats = device_memory_stats()
    assert isinstance(stats, dict)  # CPU backend may legitimately be empty


def test_peak_flops_lookup_unknown_is_an_error():
    """No default peak: a device that is not a known TPU generation
    raises (it used to read 0.0, and bench.py assumed a v5e)."""
    from accelerate_tpu.utils.constants import tpu_peak_flops

    with pytest.raises(ValueError, match="no entry"):
        peak_flops_per_chip(jax.devices()[0])  # the CPU test device
    assert tpu_peak_flops("TPU v5 lite") == 197e12
    with pytest.raises(ValueError, match="no peak FLOP/s known"):
        tpu_peak_flops("TPU v99 imaginary")


def test_device_peaks_unknown_tpu_kind_is_an_error():
    """telemetry.cost.device_peaks: nominal placeholders for the CPU
    rehearsal only; a TPU of an unknown kind raises."""
    from accelerate_tpu.telemetry.cost import device_peaks

    class FakeTpu:
        platform = "tpu"
        device_kind = "TPU v99 imaginary"

    class V5e(FakeTpu):
        device_kind = "TPU v5 lite"

    assert device_peaks(jax.devices()[0])[2] is True
    assert device_peaks(V5e()) == (197e12, 0.82e12, False)
    with pytest.raises(ValueError, match="no peak FLOP/s known"):
        device_peaks(FakeTpu())


def test_debug_mode_verifies_collectives(monkeypatch):
    """ACCELERATE_TPU_DEBUG=1 pre-verifies operand skeletons; single-host
    worlds trivially agree, so this asserts the checked path stays silent."""
    from accelerate_tpu.state import PartialState
    from accelerate_tpu.utils import operations as ops

    PartialState._reset_state()
    monkeypatch.setenv("ACCELERATE_TPU_DEBUG", "1")
    state = PartialState()
    assert state.debug
    out = ops.gather(jnp.ones((2,)))
    np.testing.assert_allclose(np.asarray(out), 1.0)
    total = ops.reduce(jnp.asarray(3.0), "sum")
    assert float(np.asarray(total)) == 3.0


def _debug_mismatch_worker():
    import jax.numpy as jnp
    import pytest

    from accelerate_tpu.state import PartialState
    from accelerate_tpu.utils import operations as ops

    state = PartialState()
    # rank-dependent shape => debug mode must raise on every rank
    bad = jnp.ones((state.process_index + 1,))
    with pytest.raises(ops.DistributedOperationException):
        ops.gather(bad)


@pytest.mark.slow
def test_debug_mode_catches_cross_rank_mismatch():
    from accelerate_tpu.launchers import debug_launcher
    from accelerate_tpu.utils.environment import patch_environment

    with patch_environment(ACCELERATE_TPU_DEBUG="1"):
        debug_launcher(_debug_mismatch_worker, num_processes=2)
