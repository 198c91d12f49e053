"""Every kind of runner driven in this process on the CPU, at tiny sizes
that the fixture ADDS AS FILES to a temp copy of the benchmark (the proof
that a configuration, a traffic mix, a cell and a per-layer metric are added
without an edit to a file that is there). Kernels run interpreted here and
no number of these runs is a device metric; the device is named 'cpu' in
every result."""

import json
import os

import pytest

from chipbench.run import run_cell

CONTRACT_KEYS = {"correct", "attempted", "failed", "metrics", "device"}


def _run(bench, cell, seed, trace=False, **kw):
    return run_cell(cell, seed, 1.5, trace, bench_dir=bench,
                    require_chip=False, **kw)


@pytest.fixture(scope="module")
def train_plain(tiny_bench):
    return _run(tiny_bench, "tiny-train", 2**31 + 5, with_control=True)


@pytest.fixture(scope="module")
def train_traced(tiny_bench):
    return _run(tiny_bench, "tiny-train", 7, trace=True)


@pytest.fixture(scope="module")
def chat_plain(tiny_bench):
    return _run(tiny_bench, "tiny-chat", 12, with_control=True)


@pytest.fixture(scope="module")
def docqa_traced(tiny_bench):
    return _run(tiny_bench, "tiny-docqa", 8, trace=True)


def _reported(bench, cell, group):
    from chipbench.harness.manifest import Cell

    c = Cell(cell, bench)
    return [m["name"] for m in (c.end_to_end() if group == "end_to_end"
                                else c.per_layer())]


@pytest.mark.parametrize("fixture", ["train_plain", "chat_plain"])
def test_untraced_line_has_the_contracts_keys_and_end_to_end_metrics(
        fixture, request, tiny_bench):
    result = request.getfixturevalue(fixture)
    cell = {"train_plain": "tiny-train", "chat_plain": "tiny-chat"}[fixture]
    assert CONTRACT_KEYS <= set(result) and "breakdown" not in result
    json.dumps(result)
    assert result["correct"] is True and result["failed"] == 0
    assert result["attempted"] > 0
    assert set(result["metrics"]) == set(_reported(tiny_bench, cell,
                                                   "end_to_end"))
    for m in result["metrics"].values():
        assert set(m) == {"value", "unit"} and m["value"] > 0
    assert result["device"]["platform"] == "cpu"
    assert {"platform", "kind", "count", "memory_peak_bytes"} <= set(
        result["device"])


@pytest.mark.parametrize("fixture", ["train_traced", "docqa_traced"])
def test_traced_line_has_layer_metrics_and_breakdown(fixture, request,
                                                     tiny_bench):
    result = request.getfixturevalue(fixture)
    cell = {"train_traced": "tiny-train",
            "docqa_traced": "tiny-docqa"}[fixture]
    assert CONTRACT_KEYS <= set(result)
    assert result["correct"] is True
    assert {"busy_s", "window_s"} <= set(result["device"])
    assert set(result["breakdown"]) == {"device_ops", "idle_gaps"}
    reported = set(_reported(tiny_bench, cell, "per_layer"))
    assert set(result["metrics"]) <= reported
    # what needs a device trace finds nothing to read on the CPU and is
    # left out; what reads counts and host clocks is there
    host_read = {"tiny-train": {"train.dispatch_us",
                                "train.recompiles_in_window",
                                "input.stall_ms_per_step",
                                "tiny.steps_per_s"},
                 "tiny-docqa": {"engine.queue_wait_p50_ms",
                                "engine.slot_occupancy_share",
                                "engine.prefix_token_hit_share",
                                "engine.kv_pages_held_share"}}[cell]
    assert host_read <= set(result["metrics"])


def test_a_metric_added_as_a_file_is_read(train_traced):
    assert train_traced["metrics"]["tiny.steps_per_s"]["unit"] == "1/s"
    assert train_traced["metrics"]["train.recompiles_in_window"]["value"] == 0


def test_documents_come_from_the_prefix_cache(docqa_traced):
    share = docqa_traced["metrics"]["engine.prefix_token_hit_share"]["value"]
    assert share > 60.0


def test_open_loop_reports_generator_lateness_and_queue(chat_plain):
    assert chat_plain["generator_late_p99_ms"] >= 0
    assert len(chat_plain["queue_depth"]) == 2


def test_fp8_control_in_the_train_steps_place_is_not_correct(train_plain):
    assert train_plain["correct"] is True
    assert train_plain["control_correct"] is False


def test_fp8_control_in_the_engines_place_is_not_correct(chat_plain):
    assert chat_plain["correct"] is True
    assert chat_plain["control_correct"] is False


def test_the_programs_own_fp8_step_is_not_correct_and_a_probe_is_no_result(
        tiny_bench):
    """The program's own lower precision (scaled fp8 matmuls) in the timed
    path's place, through `probe.py`: rejected by the gradient probes, and
    what the probe prints cannot be taken for a result line."""
    from chipbench.probe import probe

    override = 'cell.trainer.mixed_precision="fp8"'
    out = probe("tiny-train", 9, 1.5, overrides=[override],
                bench_dir=tiny_bench, require_chip=False)
    assert out["correct"] is False
    assert "metrics" not in out and "setup_s" in out["readings"]
    assert out["probe"] == {"overrides": [override], "control": False}


def test_the_command_itself_takes_no_override():
    from chipbench import run

    for flag in (["--set", "traffic.rate_per_s=9"], ["--control", "1"]):
        with pytest.raises(SystemExit) as err:
            run.main(["--workload", "tiny-chat", "--seed", "1", "--seconds",
                      "1", "--trace", "0", *flag])
        assert err.value.code == 2


def test_the_control_rounds_a_copy_and_leaves_the_reference_plain(tiny_bench):
    import jax
    import jax.numpy as jnp
    import numpy as np

    from chipbench.harness.manifest import Cell

    cell = Cell("tiny-chat", tiny_bench)
    ref, low = cell.reference(), cell.control()
    assert ref.jnp is jnp and low is not ref and low.jnp is not jnp
    params = ref.make_params(cell.config, ref.seed_words(3))
    ids = jnp.arange(24, dtype=jnp.int32)[None, :] % cell.config["vocab_size"]
    with jax.default_matmul_precision("highest"):
        plain = np.asarray(ref.hidden_states(cell.config, params, ids))
        again = np.asarray(cell.reference().hidden_states(
            cell.config, params, ids))
        rounded = np.asarray(low.hidden_states(cell.config, params, ids))
    assert np.array_equal(plain, again)
    assert 1e-4 < np.abs(rounded - plain).max() < 0.5


def test_a_step_that_returns_its_state_unchanged_is_not_correct(tiny_bench):
    import jax

    class Unchanged:
        """The compiled step with the update left out: same loss, state
        handed back as it came."""

        def __init__(self, step):
            self._fn = jax.jit(lambda s, b: (s, step._step_fn(s, b)[1]))
            self._aot_compiles = 0

        def __call__(self, state, batch):
            return self._fn(state, batch)

        def _cache_size(self):
            return 0

    result = _run(tiny_bench, "tiny-train", 21, broken_step=Unchanged)
    assert result["correct"] is False
    assert CONTRACT_KEYS <= set(result)


def test_a_token_altered_where_it_is_produced_is_not_correct(tiny_bench):
    def alter(engine):
        note = engine.scheduler.note_token
        seen = {"n": 0}

        def note_token(slot, token, *a, **kw):
            seen["n"] += 1
            if seen["n"] % 5 == 0:
                token = (int(token) + 1) % engine.config.vocab_size
            return note(slot, token, *a, **kw)

        engine.scheduler.note_token = note_token

    result = _run(tiny_bench, "tiny-chat", 22, break_engine=alter)
    assert result["correct"] is False
    assert result["attempted"] > 0


def test_the_real_files_were_not_edited_by_adding_cells(tiny_bench):
    """Add-by-files: every file of the real benchmark is byte-identical in
    the copy that gained a configuration, three traffic mixes, three cells
    and a per-layer metric."""
    real = os.path.join(os.path.dirname(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__)))), "chipbench")
    checked = 0
    for base, dirs, files in os.walk(real):
        dirs[:] = [d for d in dirs if d not in (".work", "__pycache__")]
        for name in files:
            path = os.path.join(base, name)
            twin = os.path.join(tiny_bench, os.path.relpath(path, real))
            with open(path, "rb") as a, open(twin, "rb") as b:
                assert a.read() == b.read(), path
            checked += 1
    assert checked > 30
    added = set(os.listdir(os.path.join(tiny_bench, "cells"))) - set(
        os.listdir(os.path.join(real, "cells")))
    assert added == {"tiny-train.json", "tiny-chat.json", "tiny-docqa.json"}
