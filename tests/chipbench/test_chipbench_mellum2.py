"""The configuration `mellum2-12b-a2.5b-d8` and its cell on the CPU: the
published widths are kept and the parameters are the issue's arithmetic,
the traffic and the engine are the issue's, the new operation and byte
counts give the hand-worked numbers, a tiny copy of the cell (ADDED AS
FILES to a temp copy of the benchmark, as `conftest.py` does for the Qwen
cells) runs through the `closed_loop` runner and is `correct`, the float8
control in the engine's place is not, and each new reader returns nothing
where there is nothing to read. Kernels run interpreted here; no number of
these runs is a device metric."""

import json
import os
import shutil
import types

import pytest

from chipbench.run import run_cell

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
CELL = "serve-mellum2-code-mixed-closed"
CONFIG = "mellum2-12b-a2.5b-d8"
PERIOD = ["sliding_attention"] * 3 + ["full_attention"]

# `config` of the catalog row "Mellum2-12B-A2.5B-Instruct" (model-configs
# guide), read from the model's own config.json
PUBLISHED = {
    "attention_bias": False, "head_dim": 128, "hidden_act": "silu",
    "hidden_size": 2304, "intermediate_size": 7168,
    "layer_types": PERIOD * 7, "mlp_layer_types": ["sparse"] * 28,
    "max_position_embeddings": 131072, "max_window_layers": 0,
    "model_type": "mellum", "moe_intermediate_size": 896,
    "norm_topk_prob": True, "num_attention_heads": 32, "num_experts": 64,
    "num_experts_per_tok": 8, "num_hidden_layers": 28,
    "num_key_value_heads": 4, "rms_norm_eps": 1e-06,
    "rope_parameters": {
        "full_attention": {
            "rope_type": "yarn", "rope_theta": 500000, "factor": 16,
            "original_max_position_embeddings": 8192, "beta_fast": 32,
            "beta_slow": 1, "attention_factor": 1.2772588722239782},
        "sliding_attention": {"rope_type": "default", "rope_theta": 500000}},
    "sliding_window": 1024, "tie_word_embeddings": False,
    "vocab_size": 98304, "use_sliding_window": True,
}
WIDTHS = ("hidden_size", "intermediate_size", "moe_intermediate_size",
          "head_dim", "num_experts_per_tok", "sliding_window")

# one period, so that the interpreted kernels stay affordable: s s s f
TINY = dict(vocab_size=512, hidden_size=64, intermediate_size=128,
            moe_intermediate_size=32, num_hidden_layers=4,
            layer_types=PERIOD, mlp_layer_types=["sparse"] * 4,
            num_attention_heads=8, num_key_value_heads=4, head_dim=128,
            num_experts=8, num_experts_per_tok=2, sliding_window=16,
            max_position_embeddings=256)


def _load(path):
    with open(path) as f:
        return json.load(f)


def _dump(obj, path):
    with open(path, "w") as f:
        json.dump(obj, f, indent=1)


@pytest.fixture(scope="module")
def mellum_bench(tmp_path_factory):
    """`chipbench/` inside a temp copy that also holds a tiny copy of the
    cell: a configuration, a traffic mix and a cell, all new files."""
    root = str(tmp_path_factory.mktemp("chipbench_mellum2"))
    bench = os.path.join(root, "chipbench")
    shutil.copytree(os.path.join(ROOT, "chipbench"), bench,
                    ignore=shutil.ignore_patterns(".work", "__pycache__"))
    cfg = _load(os.path.join(bench, "configs", f"{CONFIG}.json"))
    cfg.update(TINY)
    cfg["rope_parameters"]["full_attention"].update(
        original_max_position_embeddings=16, factor=4)
    _dump(cfg, os.path.join(bench, "configs", "tiny-mellum2.json"))
    tr = _load(os.path.join(bench, "traffic",
                            "code-mixed-lengths-closed.json"))
    # prompts past the window (16) and past the ring (4 pages of 8)
    tr.update(clients=2, cycle=64, fill_seconds=1,
              prompt_len={"dist": "lognormal", "median": 24, "sigma": 0.5,
                          "min": 4, "max": 44},
              output_len={"dist": "uniform", "min": 2, "max": 6})
    _dump(tr, os.path.join(bench, "traffic", "tiny-mellum2-code.json"))
    cell = _load(os.path.join(bench, "cells", f"{CELL}.json"))
    # on the CPU "auto" means the dense path: ask for the kernels
    # (interpreted)
    cell["engine"].update(num_slots=2, max_len=64, prefill_chunk=8,
                          page_size=8, num_pages=32, paged_attention=True)
    cell["check"].update(sample_requests=12, max_output=12)
    # limits of the TINY cell, set as the real cell's are: sound runs read
    # at most 0.0 and 0.0024 at this size on the CPU over six seeds, the
    # float8 control 0.0 and 0.0137 at the least (it fails by the
    # log-probability; the token gap is a median of 32 positions and these
    # answers have 2 to 6, so it reads 0 here whatever is served)
    cell["check"]["limits"].update(served_token_gap_max=0.005,
                                   served_logprob_gap_max=0.006)
    _dump(cell, os.path.join(bench, "cells", "tiny-mellum2-code.json"))
    m = _load(os.path.join(ROOT, "BENCHMARK.json"))
    m["configs"].append({"name": "tiny-mellum2", "source": "tests",
                         "reduced": [], "why": "CPU tests",
                         "file": "chipbench/configs/tiny-mellum2.json"})
    m["workloads"].append({"name": "tiny-mellum2-code",
                           "config": "tiny-mellum2",
                           "traffic": "tiny-mellum2-code", "why": "test",
                           "chips": 1})
    for metric in m["end_to_end"] + m["per_layer"]:
        if CELL in metric.get("workloads", ()):
            metric["workloads"].append("tiny-mellum2-code")
    _dump(m, os.path.join(root, "BENCHMARK.json"))
    return bench


def test_the_configuration_keeps_every_published_width():
    manifest = _load(os.path.join(ROOT, "BENCHMARK.json"))
    entry = {c["name"]: c for c in manifest["configs"]}[CONFIG]
    cfg = _load(os.path.join(ROOT, entry["file"]))
    reduced = {"num_hidden_layers", "layer_types", "mlp_layer_types",
               "max_position_embeddings"}
    assert set(entry["reduced"]) == set(cfg["reduced"]) == reduced
    assert entry["source"] == cfg["source"]
    assert not reduced & set(WIDTHS)
    for key, value in PUBLISHED.items():
        if key not in reduced:
            assert cfg[key] == value, key
    # cut in depth only: two whole periods, in the published order
    assert cfg["num_hidden_layers"] == 8
    assert cfg["layer_types"] == PUBLISHED["layer_types"][:8] == PERIOD * 2
    assert cfg["mlp_layer_types"] == PUBLISHED["mlp_layer_types"][:8]
    assert cfg["max_position_embeddings"] == 32768
    assert cfg["published"]["num_hidden_layers"] == 28
    assert cfg["published"]["max_position_embeddings"] == 131072
    assert set(cfg["assumed"]) >= reduced | {"qk_norm"}
    assert cfg["qk_norm"] is True and "stands_for" in cfg
    # the issue's arithmetic, in bf16 parameters
    h, D, H, Hkv, f, E, V = 2304, 128, 32, 4, 896, 64, 98304
    attention = 2 * h * H * D + 2 * h * Hkv * D
    assert attention == 21_233_664
    experts = E * 3 * h * f
    assert experts == 396_361_728
    layer = attention + 2 * D + experts + h * E + 2 * h
    assert layer == 417_747_712
    total = 8 * layer + 2 * V * h + h
    assert total == 3_794_968_832
    from chipbench.harness.manifest import Cell

    cell = Cell(CELL)
    assert cell.reference().param_count(cfg) == cfg["parameters"] == total
    # all 64 experts and the whole vocabulary are held here
    _, pcfg = cell.program_config()
    assert (pcfg.num_experts, pcfg.vocab_size, pcfg.num_hidden_layers,
            pcfg.head_dim, pcfg.sliding_window) == (64, 98304, 8, 128, 1024)
    assert pcfg.layer_types == tuple(PERIOD * 2) and pcfg.qk_norm
    assert pcfg.rope_of("full_attention") == PUBLISHED["rope_parameters"][
        "full_attention"]


def test_the_cell_is_the_issues_traffic_and_engine():
    from chipbench.harness.manifest import Cell

    cell = Cell(CELL)
    assert cell.chips == 1 and cell.kind == "closed_loop"
    assert cell.entry["traffic"] == "code-mixed-lengths-closed"
    tr = cell.traffic
    assert (tr["clients"], tr["shape_seed"], tr["cycle"], tr["fill_seconds"],
            tr["drain_seconds"]) == (48, 0, 2048, 20, 120)
    assert "documents" not in tr and "prime_documents" not in tr
    assert tr["prompt_len"] == {"dist": "lognormal", "median": 4096,
                                "sigma": 1.0, "min": 256, "max": 28672}
    assert tr["output_len"] == {"dist": "lognormal", "median": 384,
                                "sigma": 0.7, "min": 32, "max": 1536}
    engine = cell.shape["engine"]
    assert {k: engine[k] for k in (
        "num_slots", "max_len", "page_size", "num_pages", "cache_dtype",
        "prefix_cache", "paged_attention", "max_queue")} == {
        "num_slots": 48, "max_len": 32768, "page_size": 16,
        "num_pages": 24576, "cache_dtype": "bfloat16", "prefix_cache": False,
        "paged_attention": "auto", "max_queue": 512}
    assert engine["prefill_chunk"] in (256, 512, 1024, 2048)
    assert cell.shape["check"]["sample_requests"] == 4
    assert cell.shape["check"]["kernels_compiled"] == [
        "paged_decode_attention", "paged_decode_attention_window"]
    assert {m["name"] for m in cell.end_to_end()} == {
        "setup_s", "serve_out_tokens_per_s", "itl_p95_ms"}
    mine = {m["name"] for m in cell.per_layer()}
    assert {"kernel.mixed_paged_attention_roofline",
            "step.window_attention_decode_device_ms",
            "kernel.routed_expert_matmul_roofline",
            "step.routed_experts_decode_device_ms",
            "step.decode_device_ms", "step.prefill_chunk_device_ms",
            "device.idle_share.serve", "engine.host_ms_per_step"} <= mine
    assert not mine & {"kernel.paged_attention_roofline",
                       "kernel.moe_expert_matmul_roofline",
                       "engine.prefix_token_hit_share"}
    # the longest request fits a slot, with the chunk's slack
    from chipbench.harness import traffic

    prompts = traffic.quantiles(tr["prompt_len"], tr["cycle"])
    answers = traffic.quantiles(tr["output_len"], tr["cycle"])
    assert prompts.max() + answers.max() <= engine["max_len"]
    assert 0.02 < (prompts == 28672).mean() < 0.03


def test_mixed_window_costs_by_hand():
    """One slot under the window and one over it, 32 query heads over 4
    KV heads of 128, bf16."""
    from chipbench.harness import mixed_window_costs as costs

    assert costs.keys_seen(499, None) == 500
    assert costs.keys_seen(499, 1024) == 500
    assert costs.keys_seen(1023, 1024) == costs.keys_seen(5000, 1024) == 1024
    assert costs.keys_seen(5000, None) == 5001
    # a full layer: 500 + 5001 keys; a key is 2 x 2 x 32 x 128 operations
    # and 2 x 4 x 128 x 2 bytes; a slot's q and out 2 x 32 x 128 x 2 bytes
    ops, byts = costs.decode_attention_cost([499, 5000], 32, 4, 128)
    assert ops == 16384 * 5501 and byts == 2048 * 5501 + 2 * 16384
    # a sliding layer: 500 + 1024 keys
    ops, byts = costs.decode_attention_cost([499, 5000], 32, 4, 128, 1024)
    assert ops == 16384 * 1524 and byts == 2048 * 1524 + 2 * 16384
    cfg = _load(os.path.join(ROOT, "chipbench", "configs", f"{CONFIG}.json"))
    assert costs.layer_windows(cfg) == [1024, 1024, 1024, None] * 2


@pytest.fixture(scope="module")
def tiny_plain(mellum_bench):
    return run_cell("tiny-mellum2-code", 2**31 + 11, 1.5, False,
                    bench_dir=mellum_bench, require_chip=False,
                    with_control=True)


def test_the_tiny_cell_is_correct_and_the_fp8_control_is_not(tiny_plain):
    assert tiny_plain["correct"] is True and tiny_plain["failed"] == 0
    assert tiny_plain["attempted"] > 0
    assert tiny_plain["control_correct"] is False
    assert set(tiny_plain["metrics"]) == {
        "setup_s", "serve_out_tokens_per_s", "itl_p95_ms"}
    assert tiny_plain["device"]["platform"] == "cpu"


NEW_READERS = ["kernel.mixed_paged_attention_roofline",
               "step.window_attention_decode_device_ms",
               "kernel.routed_expert_matmul_roofline",
               "step.routed_experts_decode_device_ms"]


@pytest.mark.parametrize("metric", NEW_READERS)
@pytest.mark.parametrize("case", ["no-trace", "qwen-cell", "joyai-cell",
                                  "empty-trace"])
def test_a_new_reader_returns_nothing_where_there_is_nothing_to_read(
        metric, case):
    """No trace; a cell of another family (whose cell file names none of
    the new kernels, as the parent's program has none); a trace that holds
    no operation."""
    from chipbench.harness.manifest import Cell
    from chipbench.harness.trace_reduce import TraceSummary

    cell = Cell({"qwen-cell": "serve-qwen2-docqa-closed",
                 "joyai-cell": "serve-joyai-flash-docqa-long"}.get(case, CELL))
    trace = None if case == "no-trace" else TraceSummary(
        {"devices": {"/device:TPU:0": {"ops": [], "modules": []}},
         "host": []}, 4.0)
    run = types.SimpleNamespace(
        cell=cell, trace=trace, samples={"decode_lengths": [[5, 7]]},
        peaks={"bf16_flops": 197e12, "hbm_bytes_per_s": 819e9},
        counters={}, window_s=4.0)
    assert cell.layer_reader(metric).read(run) is None


def test_the_new_readers_on_a_hand_made_trace():
    """Two decode calls of 12 ms and one 30 ms chunk on a made-up device:
    each decode call holds 8 expert layers (a grouped product of 0.5 ms and
    its group layout of 0.1 ms), 2 full-layer kernels of 0.3 ms and 6
    window kernels of 0.1 ms; the chunk holds 24 grouped products of 1 ms;
    one more product and one more window kernel lie OUTSIDE any call and
    are not counted."""
    from chipbench.harness.manifest import Cell
    from chipbench.harness.trace_reduce import TraceSummary

    ms = 1e6
    ops, modules = [], []
    for call in range(2):
        t0 = call * 20 * ms
        modules.append(["jit_decode(1)", t0, 12 * ms])
        for i in range(8):
            ops.append([f"%ragged-dot-metadata.{i} custom-call"
                        "[tpu_custom_call]", t0 + i * ms, 0.1 * ms])
            ops.append([f"%ragged-dot-none.{i} custom-call[tpu_custom_call]",
                        t0 + (i + 0.2) * ms, 0.5 * ms])
        for i in range(2):
            ops.append([f"%paged_decode_attention.{i} custom-call"
                        "[tpu_custom_call]", t0 + (8 + i) * ms, 0.3 * ms])
        for i in range(6):
            ops.append([f"%paged_decode_attention_window.{i} custom-call"
                        "[tpu_custom_call]", t0 + (10 + 0.2 * i) * ms,
                        0.1 * ms])
    modules.append(["jit_prefill(2)", 100 * ms, 30 * ms])
    for i in range(24):
        ops.append([f"%ragged-dot-none.{i} custom-call[tpu_custom_call]",
                    100 * ms + i * ms, 1 * ms])
    ops.append(["%ragged-dot-none.99 custom-call[tpu_custom_call]", 200 * ms,
                7 * ms])
    ops.append(["%paged_decode_attention_window.99 custom-call"
                "[tpu_custom_call]", 210 * ms, 7 * ms])
    cell = Cell(CELL)
    peaks = {"bf16_flops": 197e12, "hbm_bytes_per_s": 819e9}
    run = types.SimpleNamespace(
        cell=cell, peaks=peaks, counters={}, window_s=0.3,
        samples={"decode_lengths": [[5000] * 40, [5000] * 40]},
        trace=TraceSummary({"devices": {"/device:TPU:0": {
            "ops": ops, "modules": modules}}, "host": []}, 0.3))
    read = lambda name: cell.layer_reader(name).read(run)  # noqa: E731
    assert read("step.routed_experts_decode_device_ms") == pytest.approx(
        8 * 0.6)
    assert read("step.window_attention_decode_device_ms") == pytest.approx(
        6 * 0.1)
    # a chunk's least time: 8 layers x 64 x 3 x 2304 x 896 x 2 B = 792.7 MB
    # over 819 GB/s (memory-bound: 50.7 GFLOP a layer are 0.26 ms); the
    # products took 24 ms
    weights = 64 * 3 * 2304 * 896 * 2
    assert read("kernel.routed_expert_matmul_roofline") == pytest.approx(
        100 * 8 * weights / 819e9 / 24e-3)
    # a decode call's least time: 40 slots; a full layer reads 5001 keys a
    # slot, a sliding layer 1024, 2048 B a key, + q and out (memory-bound);
    # the kernels took 2 x (2 x 0.3 + 6 x 0.1) ms
    full = 40 * (5001 * 2048 + 16384)
    sliding = 40 * (1024 * 2048 + 16384)
    assert read("kernel.mixed_paged_attention_roofline") == pytest.approx(
        100 * 2 * (2 * full + 6 * sliding) / 819e9 / 2.4e-3)
