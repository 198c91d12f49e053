"""The engine's host pass read from a traced window: `harness/host_phases.py`
and the per-layer readers built on it, on a hand-made event list whose
answers are worked out below, on a few steps of the chat cell recorded on
the chip, and through a whole traced run of a tiny cell on the CPU."""

import importlib.util
import os
import types

import pytest

from chipbench.harness import host_phases, trace_reduce

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(os.path.dirname(HERE))
US = 1000.0  # the hand-made list is written in microseconds


def _spans(rows):
    return [[name, start * US, (end - start) * US] for name, start, end in rows]


# One device, three operations; two gaps: [1000, 1600] and [2600, 3000].
# The host: a decode step's tail (read, commit with a page release,
# bookkeeping), the caller's loop, a submit that admits (allocate + admit
# program), a prefill chunk that needs no read, a decode step, the
# caller's loop again, the next decode's dispatch.
HAND_MADE = {
    "devices": {"/device:TPU:0": {"modules": [], "ops": _spans([
        ("%fusion.1", 0, 1000), ("%fusion.2", 1600, 2600),
        ("%fusion.3", 3000, 4000)])}},
    "host": _spans([
        ("chipbench.engine_step", 90, 1310),
        ("serving.host_read", 100, 1050),
        ("serving.commit", 1050, 1250),
        ("serving.kv.release", 1100, 1150),
        ("serving.bookkeeping", 1250, 1300),
        ("chipbench.submit", 1340, 1500),
        ("serving.submit", 1350, 1500),
        ("serving.admit_pending", 1370, 1480),
        ("serving.kv.allocate", 1380, 1420),
        ("serving.admit", 1440, 1470),
        ("serving.schedule", 1500, 1510),
        ("serving.stage_inputs", 1510, 1560),
        ("serving.prefill", 1560, 1600),
        ("serving.commit", 1600, 1620),
        ("serving.bookkeeping", 1620, 1640),
        ("serving.schedule", 1650, 1660),
        ("serving.stage_inputs", 1660, 1700),
        ("serving.decode", 1700, 1750),
        ("serving.host_read", 1750, 2650),
        ("serving.commit", 2650, 2700),
        ("serving.bookkeeping", 2700, 2720),
        ("serving.schedule", 2900, 2910),
        ("serving.stage_inputs", 2910, 2950),
        ("serving.decode", 2950, 3000),
    ]),
}
WINDOW_S = 4000 * US / 1e9
# worked by hand from the list above, microseconds
COMMIT = 200 + 20 + 50            # the release nested in the first counts
ADMIT_PENDING = 110               # with its allocate and its admit dispatch
STAGE = (10 + 10 + 10) + (50 + 40 + 40) + (40 + 50 + 50)
BOOKKEEPING = 50 + 20 + 20
SUBMIT_SELF = 150 - 110
HOST = COMMIT + ADMIT_PENDING + STAGE + BOOKKEEPING + SUBMIT_SELF
WAIT = 950 + 900
IDLE = {"serving.host_read": 50 + 50, "serving.commit": 50 + 100 + 50,
        "serving.kv.release": 50, "serving.bookkeeping": 50 + 20,
        "serving.submit": 20 + 20, "serving.admit_pending": 10 + 20 + 10,
        "serving.kv.allocate": 40, "serving.admit": 30,
        "serving.schedule": 10 + 10, "serving.stage_inputs": 50 + 40,
        "serving.prefill": 40, "serving.decode": 50,
        host_phases.OUTSIDE: 50 + 180}
EXPECTED = {  # metric -> value on the hand-made list (3 engine steps)
    "engine.host_ms_per_step": HOST / 3 / 1e3,
    "engine.commit_ms_per_step": COMMIT / 3 / 1e3,
    "engine.stage_inputs_ms_per_step": STAGE / 3 / 1e3,
    "engine.admit_pending_ms_per_step": ADMIT_PENDING / 3 / 1e3,
    "engine.submit_ms_per_request": 0.150,
    "engine.kv_allocate_ms_per_admission": 0.040,
    "engine.host_read_wait_share": 100.0 * WAIT / 4000,
    "device.idle_outside_engine_share.serve": 100.0 * 230 / 1000,
}


def _reader(metric):
    path = os.path.join(ROOT, "chipbench", "layer_metrics", f"{metric}.py")
    spec = importlib.util.spec_from_file_location(
        "host_phase_metric_" + metric.replace(".", "_"), path)
    module = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(module)
    return module


def _run(events, window_s=WINDOW_S):
    summary = (None if events is None
               else trace_reduce.TraceSummary(events, window_s, chips=1))
    return types.SimpleNamespace(trace=summary)


def test_phases_are_disjoint_and_sum_to_the_whole():
    spans = host_phases.engine_spans(HAND_MADE)
    assert all(name.startswith("serving.") for name, _, _ in spans)
    assert host_phases.engine_steps(spans) == 3
    groups = host_phases.phase_times(spans)
    assert groups["serving.commit"] == pytest.approx(COMMIT * US)
    assert groups["serving.admit_pending"] == pytest.approx(ADMIT_PENDING * US)
    assert "serving.kv.release" not in groups  # counted in its container
    assert "serving.admit" not in groups
    assert groups["serving.submit"] == pytest.approx(SUBMIT_SELF * US)
    assert groups[host_phases.WAIT] == pytest.approx(WAIT * US)
    # the host pass leaves the wait out, and the named parts sum to it
    assert host_phases.host_pass_ns(groups) == pytest.approx(HOST * US)
    # by name, as `trace_reduce.self_times` has it, it is the same whole
    by_name = trace_reduce.self_times(spans)
    assert sum(by_name.values()) == pytest.approx((HOST + WAIT) * US)
    assert by_name["serving.kv.release"] == pytest.approx(50 * US)


def test_idle_is_split_by_overlap_and_sums_to_the_gaps():
    idle = host_phases.idle_by_phase(HAND_MADE)
    assert {k: v / US for k, v in idle.items()} == pytest.approx(IDLE)
    gaps = host_phases.device_gaps(HAND_MADE)
    assert [[a / US, b / US] for a, b in gaps] == [[1000, 1600], [2600, 3000]]
    assert sum(idle.values()) == pytest.approx(1000 * US)
    # the middle-of-the-gap rule gives each whole gap to one span
    middle = dict(trace_reduce.TraceSummary(HAND_MADE, WINDOW_S).idle_gaps())
    assert len(middle) == 2 and sum(middle.values()) == pytest.approx(1e-3)


def test_a_gap_under_no_engine_span_counts_as_outside():
    events = {"devices": {"/device:TPU:0": {"modules": [], "ops": _spans([
        ("%a", 0, 100), ("%b", 300, 400)])}},
        "host": _spans([("chipbench.wait_arrival", 120, 280),
                        ("serving.host_read", 10, 110)])}
    idle = host_phases.idle_by_phase(events)
    assert idle == pytest.approx({"serving.host_read": 10 * US,
                                  host_phases.OUTSIDE: 190 * US})


@pytest.mark.parametrize("metric", sorted(EXPECTED))
def test_reader_on_the_hand_made_list(metric):
    assert _reader(metric).read(_run(HAND_MADE)) == pytest.approx(
        EXPECTED[metric])


def test_the_phase_metrics_and_the_remainder_sum_to_the_host_pass():
    run = _run(HAND_MADE)
    parts = sum(_reader(m).read(run) for m in (
        "engine.commit_ms_per_step", "engine.stage_inputs_ms_per_step",
        "engine.admit_pending_ms_per_step"))
    remainder = (BOOKKEEPING + SUBMIT_SELF) / 3 / 1e3
    assert parts + remainder == pytest.approx(
        _reader("engine.host_ms_per_step").read(run))


@pytest.mark.parametrize("metric", sorted(EXPECTED))
def test_reader_returns_nothing_where_there_is_nothing_to_read(metric):
    """An untraced run; an engine from before the phases (the dispatch
    spans alone, as the parent commit has them); a window without a step:
    no number, and no exception."""
    read = _reader(metric).read
    assert read(_run(None)) is None
    before = dict(HAND_MADE, host=[e for e in HAND_MADE["host"] if e[0] in (
        "serving.decode", "serving.prefill", "serving.admit",
        "chipbench.engine_step", "chipbench.submit")])
    assert read(_run(before)) is None
    assert read(_run({"devices": {}, "host": []})) is None


def test_the_table_prints(capsys):
    text = host_phases.report(HAND_MADE)
    assert "3 engine steps" in text and host_phases.OUTSIDE in text
    assert "serving.kv.allocate" in text


# -- a few steps of the chat cell, recorded on the chip ------------------------


@pytest.fixture(scope="module")
def recorded():
    """0.25 s of `serve-qwen2-chat-steady` on a TPU v5 lite (PR 24's traced
    run, seed 3999999979): the event list `trace_reduce.py`'s `__main__`
    saves, cut to the events that lie whole between 150 ms and 400 ms of
    the trace: five decode steps, two submits that admit, their chunks."""
    return trace_reduce.load_events(
        os.path.join(HERE, "data", "serve_chat_steps.events.json.gz"))


def test_recorded_steps_and_phases(recorded):
    spans = host_phases.engine_spans(recorded)
    count = {}
    for name, _, _ in spans:
        count[name] = count.get(name, 0) + 1
    assert count == {
        "serving.commit": 10, "serving.bookkeeping": 8, "serving.schedule": 8,
        "serving.stage_inputs": 8, "serving.host_read": 7,
        "serving.decode": 6, "serving.prefill": 2, "serving.submit": 2,
        "serving.admit_pending": 2, "serving.kv.allocate": 2,
        "serving.admit": 2}
    assert host_phases.has_phases(spans)
    assert host_phases.engine_steps(spans) == 8
    groups = host_phases.phase_times(spans)
    # nanoseconds, computed once from the recorded list and kept
    assert groups == pytest.approx({
        "serving.admit_pending": 7012659.0, "serving.bookkeeping": 590610.0,
        "serving.commit": 1008061.0, "serving.decode": 3656510.0,
        "serving.host_read": 216987915.0, "serving.prefill": 1223690.0,
        "serving.schedule": 95110.0, "serving.stage_inputs": 1816570.0,
        "serving.submit": 123641.0})
    assert host_phases.host_pass_ns(groups) == pytest.approx(15526851.0)
    # grouped or by name, the spans cover the same time
    assert sum(groups.values()) == pytest.approx(
        sum(trace_reduce.self_times(spans).values()))
    # both submits admitted their request on the spot: 3.53 and 3.60 ms
    assert host_phases.median_ms(spans, "serving.submit") == pytest.approx(
        (3.5349 + 3.6014) / 2, abs=0.0001)
    assert host_phases.median_ms(spans, "serving.swap_in") is None


def test_recorded_idle_is_mostly_the_reads_own_latency(recorded):
    spans = host_phases.engine_spans(recorded)
    idle = host_phases.idle_by_phase(recorded, spans)
    gaps = host_phases.device_gaps(recorded)
    assert len(gaps) == 756
    assert sum(idle.values()) == pytest.approx(sum(b - a for a, b in gaps))
    assert sum(idle.values()) == pytest.approx(28150037.0)
    # the device stands idle while the host is still INSIDE its blocking
    # read (the result's way back), more than under all host work together
    assert idle["serving.host_read"] == pytest.approx(17222997.0)
    assert idle["serving.admit_pending"] == pytest.approx(6147800.0)
    assert idle[host_phases.OUTSIDE] == pytest.approx(3088732.0)
    # the middle rule of `TraceSummary.idle_gaps` rounds whole gaps
    middle = dict(trace_reduce.TraceSummary(recorded, 0.25).idle_gaps())
    assert middle["serving.host_read"] == pytest.approx(0.018028054)
    assert "chipbench.engine_step" not in middle


@pytest.mark.parametrize("metric", sorted(EXPECTED))
def test_reader_on_the_recorded_steps(metric, recorded):
    value = _reader(metric).read(_run(recorded, window_s=0.25))
    low, high = {
        "engine.host_ms_per_step": (1.9, 2.0),
        "engine.commit_ms_per_step": (0.12, 0.13),
        "engine.stage_inputs_ms_per_step": (0.84, 0.86),
        "engine.admit_pending_ms_per_step": (0.87, 0.88),
        "engine.submit_ms_per_request": (3.56, 3.58),
        "engine.kv_allocate_ms_per_admission": (0.14, 0.16),
        "engine.host_read_wait_share": (86.0, 87.5),
        "device.idle_outside_engine_share.serve": (10.9, 11.1),
    }[metric]
    assert low <= value <= high, value


# -- end to end: engine spans -> profiler -> extract -> readers, on the CPU ----


def test_a_traced_tiny_cell_reports_the_host_side_phase_metrics(tiny_bench):
    """The whole way once: the engine's live spans reach the profiler's
    host plane, `extract` keeps them, the readers find them through the
    manifest. There is no device plane on the CPU, so what splits the
    device's idle time is left out; the numbers are the CPU's and stand
    under `platform: cpu`."""
    from chipbench.run import run_cell

    result = run_cell("tiny-chat", 2**31 + 11, 1.5, True,
                      bench_dir=tiny_bench, require_chip=False)
    assert result["correct"] is True
    assert result["device"]["platform"] == "cpu"
    host_side = set(EXPECTED) - {"device.idle_outside_engine_share.serve"}
    assert host_side <= set(result["metrics"])
    m = {k: v["value"] for k, v in result["metrics"].items()}
    assert "device.idle_outside_engine_share.serve" not in m
    parts = (m["engine.commit_ms_per_step"]
             + m["engine.stage_inputs_ms_per_step"]
             + m["engine.admit_pending_ms_per_step"])
    assert 0 < parts <= m["engine.host_ms_per_step"] * (1 + 1e-9)
    assert 0 < m["engine.host_read_wait_share"] < 100
    assert all(v["unit"] in ("ms", "%") for k, v in result["metrics"].items()
               if k in host_side)
