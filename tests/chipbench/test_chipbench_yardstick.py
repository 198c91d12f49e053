"""The yardstick's arithmetic at hand-worked shapes: operations and bytes,
traffic generation, the reduction of a recorded chip trace."""

import os

import numpy as np
import pytest

from chipbench.harness import flops, trace_reduce, traffic
from chipbench.harness.context import percentile

HERE = os.path.dirname(os.path.abspath(__file__))
QWEN_D8 = dict(hidden_size=1536, intermediate_size=8960, num_hidden_layers=8,
               num_attention_heads=12, num_key_value_heads=2,
               vocab_size=151936)
PEAKS = {"bf16_flops": 197e12, "hbm_bytes_per_s": 819e9}


def test_train_flops_per_token_by_hand():
    # one layer: q and o 1536x1536 each, k and v 1536x256 each, three MLP
    # matrices 1536x8960 -> 46,792,704 parameters; head 1536x151936
    layer, head = flops.matmul_params(QWEN_D8)
    assert layer == 2 * 1536 * 1536 + 2 * 1536 * 256 + 3 * 1536 * 8960
    assert head == 1536 * 151936
    # forward: 2 ops a parameter, plus QK^T and PV (2 x 2 x 1536 ops a key)
    # over the mean causal context 1024.5, in 8 layers; x3 with the backward
    fwd = 2 * (8 * layer + head) + 8 * 4 * 1536 * 1024.5
    assert flops.train_flops_per_token(QWEN_D8, 2048) == pytest.approx(3 * fwd)
    assert 3.7e9 < 3 * fwd < 3.9e9


def test_paged_attention_cost_by_hand():
    # 32 slots, Hkv 2, G 6 (12 heads), D 128, bf16: slot i holds 10 * i
    # cached tokens and attends to them plus its own new token
    lengths = [10 * i for i in range(32)]
    ops, byts = flops.paged_attention_cost(lengths, 12, 2, 128)
    live = sum(n + 1 for n in lengths)
    assert byts == live * 2 * 2 * 128 * 2 + 32 * 2 * 12 * 128 * 2
    assert ops == live * 2 * 2 * 12 * 128
    t, bound = flops.roofline_seconds(ops, byts, PEAKS)
    assert bound == "memory" and t == pytest.approx(byts / 819e9)


def test_flash_attention_cost_by_hand():
    # batch 2, 12 heads over 2 KV heads, seq 2048, D 128: the causal half
    pairs = 2 * 12 * 2048 * 2049 / 2
    ops, byts = flops.flash_attention_cost(2, 2048, 12, 2, 128)
    assert ops == pytest.approx(4 * pairs * 128 * 3.5)
    q, kv = 2 * 2048 * 12 * 128, 2 * 2048 * 2 * 128
    assert byts == (2 * q + 2 * kv) * 2 + (4 * q + 4 * kv) * 2
    assert flops.roofline_seconds(ops, byts, PEAKS)[1] == "compute"


def test_every_seed_gets_the_same_sizes_and_arrivals_and_its_own_ids():
    mix = {"kind": "open_loop", "rate_per_s": 4.0, "fill_seconds": 1,
           "prompt_len": {"dist": "lognormal", "median": 256, "sigma": 1.0,
                          "min": 32, "max": 1536},
           "output_len": {"dist": "lognormal", "median": 96, "sigma": 0.8,
                          "min": 16, "max": 384}}
    a, _ = traffic.serve_plan(mix, 1000, 1, 200)
    b, _ = traffic.serve_plan(mix, 1000, 2**31 + 9, 200)
    for key in (lambda p: len(p.prompt), lambda p: p.max_new_tokens):
        assert list(map(key, a)) == list(map(key, b))
        assert list(map(key, a)) != sorted(map(key, a))  # shuffled once
    assert [p.due_s for p in a] == [p.due_s for p in b]
    other, _ = traffic.serve_plan(dict(mix, shape_seed=1), 1000, 1, 200)
    assert sorted(len(p.prompt) for p in other) == sorted(
        len(p.prompt) for p in a)
    assert [len(p.prompt) for p in other] != [len(p.prompt) for p in a]
    assert 32 <= min(len(p.prompt) for p in a) and max(
        len(p.prompt) for p in a) <= 1536
    assert not np.array_equal(a[0].prompt[:8], b[0].prompt[:8])
    # the mean gap of the exponential quantiles is the rate's, within 1%
    assert a[-1].due_s / 200 == pytest.approx(0.25, rel=0.02)


def test_documents_are_shared_by_zipf_and_stay_in_bounds():
    mix = {"kind": "closed_loop", "clients": 4, "cycle": 64,
           "documents": {"count": 16, "zipf_s": 1.0,
                         "len": {"dist": "uniform", "min": 2048, "max": 3584}},
           "prompt_len": {"dist": "uniform", "min": 32, "max": 128},
           "output_len": {"dist": "uniform", "min": 32, "max": 96}}
    plan, docs = traffic.serve_plan(mix, 1000, 5, 64)
    assert len(docs) == 16 and all(2048 <= len(d) <= 3584 for d in docs)
    counts = np.bincount([p.document for p in plan], minlength=16)
    assert list(counts) == list(traffic.zipf_counts(16, 1.0, 64))
    assert counts[0] == max(counts) and counts.sum() == 64
    for p in plan:
        assert np.array_equal(p.prompt[:len(docs[p.document])],
                              docs[p.document])
        assert len(p.prompt) + p.max_new_tokens <= 3584 + 128 + 96
        assert p.due_s is None


def test_train_corpus_rows_differ_and_are_zipfian():
    mix = {"batch": 2, "seq_len": 64, "corpus_batches": 16,
           "token_zipf_s": 1.0}
    a = traffic.train_corpus(mix, 5000, 3)
    assert a.shape == (32, 65) and a.dtype == np.int32
    assert len({row.tobytes() for row in a}) == 32
    assert 0 <= a.min() and a.max() < 5000
    top = np.bincount(a.reshape(-1), minlength=5000).max() / a.size
    assert top > 0.05  # the most frequent id of Zipf(1) over 5000: ~11%
    assert np.array_equal(a, traffic.train_corpus(mix, 5000, 3))


def test_percentile_interpolates():
    assert percentile([1, 2, 3, 4, 5], 50) == 3
    assert percentile(list(range(101)), 90) == 90
    assert percentile([], 90) is None


# -- the reduction, on three steps recorded on the chip (PR 23, cell 1) -------


@pytest.fixture(scope="module")
def recorded():
    return trace_reduce.load_events(
        os.path.join(HERE, "data", "train_d8_3steps.events.json.gz"))


def test_recorded_trace_busy_union(recorded):
    # three train steps of 2 x 2048 tokens on a TPU v5 lite: 6840 device
    # operations from 45,857,580 ns to 625,178,316 ns; the union of their
    # intervals, computed apart with a numpy sweep, is 576,257,956 ns
    window_s = (625178316.0 - 45857580.0) / 1e9
    s = trace_reduce.TraceSummary(recorded, window_s, chips=1)
    assert s.busy_s == pytest.approx(0.576257956, rel=1e-9)
    assert s.idle_share == pytest.approx(1 - 0.576257956 / window_s)
    assert 0.004 < s.idle_share < 0.007  # the fence of a loss read


def test_recorded_trace_program_and_kernel_time(recorded):
    s = trace_reduce.TraceSummary(recorded, 0.58, chips=1)
    calls = s.module_calls("step_fn")
    assert calls == pytest.approx([0.192098148, 0.192087298, 0.192104283])
    assert s.module_calls("no_such_program") == []
    # 4 flash-attention kernel calls a layer (forward, its recomputation,
    # two backward kernels) x 8 layers x 3 steps
    seconds, count = s.op_seconds("custom-call[tpu_custom_call]")
    assert count == 96
    assert seconds == pytest.approx(0.037717628, rel=1e-9)


def test_recorded_trace_gaps_are_named_by_the_host_span(recorded):
    s = trace_reduce.TraceSummary(recorded, 0.58, chips=1)
    gaps = dict(s.idle_gaps())
    # the one long gap (3.0 ms between steps 2 and 3) falls inside the
    # host's read of a loss
    assert max(gaps, key=gaps.get) == "chipbench.read_loss"
    assert gaps["chipbench.read_loss"] == pytest.approx(0.003, rel=0.1)
    top = s.top_ops(3)
    assert len(top) == 3 and top[0][1] >= top[1][1] >= top[2][1]
    assert all(len(name) < 140 for name, _ in top)


def test_self_time_does_not_count_a_loop_body_twice():
    events = [["while", 0.0, 100.0], ["body.a", 10.0, 30.0],
              ["body.b", 50.0, 40.0], ["after", 120.0, 5.0]]
    st = trace_reduce.self_times(events)
    assert st == {"while": 30.0, "body.a": 30.0, "body.b": 40.0, "after": 5.0}


def test_short_name_keeps_what_identifies_an_operation():
    hlo = ('%closed_call.8 = (bf16[24,2048,128]{2,1,0:T(8,128)(2,1)S(1)}, '
           'f32[24,2048,8]{2,1,0:T(8,128)}) custom-call(bf16[24,2048,128]'
           '{2,1,0} %bitcast.672), custom_call_target="tpu_custom_call"')
    assert trace_reduce.short_name(hlo) == (
        "%closed_call.8 custom-call[tpu_custom_call] "
        "(bf16[24,2048,128], f32[24,2048,8])")
    fused = ("%fusion.5 = bf16[8,16]{1,0:T(8,128)} fusion(bf16[8,16]{1,0} "
             "%p), kind=kLoop, calls=%fc")
    assert trace_reduce.short_name(fused) == "%fusion.5 fusion kLoop bf16[8,16]"


def test_stall_watch_tells_a_waiting_main_thread_from_a_process_not_run():
    """The main thread stands still (here: asleep, as in a device wait):
    the side thread keeps its beat, the collector took nothing."""
    import time

    from chipbench.harness.serve import StallWatch

    with StallWatch() as watch:
        start = time.perf_counter()
        time.sleep(0.8)
        end = time.perf_counter()
    seen = watch.during(start, end)
    assert seen["gc_s"] == 0
    assert seen["side_thread_silent_s"] < 0.6 < end - start


def test_machine_counters_are_seconds_where_linux_tells():
    from chipbench.harness.device import machine_counters

    first, second = machine_counters(), machine_counters()
    assert set(first) == set(second)
    assert all(second[k] >= first[k] >= 0 for k in first)


def test_stall_watch_writes_the_stacks_of_an_iteration_that_lasts(tmp_path):
    import time

    from chipbench.harness.serve import StallWatch

    with open(tmp_path / "stacks.txt", "w+") as out:
        with StallWatch(dump_to=out) as watch:
            watch.tick(time.perf_counter())
            time.sleep(1.4)  # the iteration that does not end
            watch.tick(None)
        out.seek(0)
        stacks = out.read()
    assert "most recent call first" in stacks
    assert "test_stall_watch_writes_the_stacks" in stacks
