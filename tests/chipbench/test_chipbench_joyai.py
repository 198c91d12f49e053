"""The configuration `joyai-llm-flash-d5` and its cell on the CPU: the
published widths are kept, the new operation and byte counts give the
hand-worked numbers, a tiny copy of the cell (ADDED AS FILES to a temp copy
of the benchmark, as `conftest.py` does for the Qwen cells) runs through
the `closed_loop` runner and is `correct`, the float8 control in the
engine's place is not, and each new reader returns nothing where there is
nothing to read. Kernels run interpreted here; no number of these runs is
a device metric."""

import json
import os
import shutil
import types

import pytest

from chipbench.run import run_cell

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
CELL = "serve-joyai-flash-docqa-long"

# `config` of the catalog row "JoyAI-LLM-Flash" (model-configs guide),
# read from the model's own config.json
PUBLISHED = {
    "attention_bias": False, "ep_size": 1, "first_k_dense_replace": 1,
    "head_dim": 64, "hidden_act": "silu", "hidden_size": 2048,
    "intermediate_size": 7168, "kv_lora_rank": 512,
    "max_position_embeddings": 131072, "model_type": "joyai_llm_flash",
    "moe_intermediate_size": 768, "moe_layer_freq": 1, "n_group": 1,
    "n_routed_experts": 256, "n_shared_experts": 1, "norm_topk_prob": True,
    "num_attention_heads": 32, "num_experts_per_tok": 8,
    "num_hidden_layers": 40, "num_key_value_heads": 32,
    "num_nextn_predict_layers": 1, "q_lora_rank": 1536, "qk_head_dim": 192,
    "qk_nope_head_dim": 128, "qk_rope_head_dim": 64, "rms_norm_eps": 1e-06,
    "rope_interleave": True, "rope_scaling": None, "rope_theta": 32000000,
    "routed_scaling_factor": 2.5, "scoring_func": "sigmoid",
    "tie_word_embeddings": False, "topk_group": 1, "topk_method": "noaux_tc",
    "v_head_dim": 128, "vocab_size": 129280,
}
WIDTHS = ("hidden_size", "intermediate_size", "moe_intermediate_size",
          "q_lora_rank", "kv_lora_rank", "qk_nope_head_dim",
          "qk_rope_head_dim", "qk_head_dim", "v_head_dim", "head_dim",
          "num_experts_per_tok")

TINY = dict(vocab_size=512, hidden_size=64, intermediate_size=128,
            moe_intermediate_size=32, num_hidden_layers=3,
            num_attention_heads=4, num_key_value_heads=4, q_lora_rank=48,
            kv_lora_rank=128, qk_nope_head_dim=16, qk_rope_head_dim=8,
            qk_head_dim=24, v_head_dim=16, head_dim=8, n_routed_experts=8,
            num_experts_per_tok=2, max_position_embeddings=256)


def _load(path):
    with open(path) as f:
        return json.load(f)


def _dump(obj, path):
    with open(path, "w") as f:
        json.dump(obj, f, indent=1)


@pytest.fixture(scope="module")
def joyai_bench(tmp_path_factory):
    """`chipbench/` inside a temp copy that also holds a tiny copy of the
    cell: a configuration, a traffic mix and a cell, all new files."""
    root = str(tmp_path_factory.mktemp("chipbench_joyai"))
    bench = os.path.join(root, "chipbench")
    shutil.copytree(os.path.join(ROOT, "chipbench"), bench,
                    ignore=shutil.ignore_patterns(".work", "__pycache__"))
    cfg = _load(os.path.join(bench, "configs", "joyai-llm-flash-d5.json"))
    cfg.update(TINY)
    _dump(cfg, os.path.join(bench, "configs", "tiny-joyai.json"))
    tr = _load(os.path.join(bench, "traffic", "docqa-16docs-long-closed.json"))
    tr.update(clients=2, cycle=64, fill_seconds=1,
              documents={"count": 3, "zipf_s": 1.0,
                         "len": {"dist": "uniform", "min": 24, "max": 40}},
              prompt_len={"dist": "uniform", "min": 3, "max": 12},
              output_len={"dist": "uniform", "min": 2, "max": 6})
    _dump(tr, os.path.join(bench, "traffic", "tiny-joyai-docqa.json"))
    cell = _load(os.path.join(bench, "cells", f"{CELL}.json"))
    # on the CPU "auto" means the dense path: ask for the kernel (interpreted)
    cell["engine"].update(num_slots=2, max_len=64, prefill_chunk=8,
                          page_size=8, num_pages=64, paged_attention=True)
    cell["check"].update(sample_requests=12, max_output=12)
    # limits of the TINY cell, set as the real cell's are: over six seeds
    # sound runs read at most 0.0007 and 0.0019 at this size on the CPU,
    # the float8 control 0.0 and 0.017 at the least (it fails by the
    # log-probability; the token gap is a median of 32 positions and these
    # answers have 2 to 6, so it reads 0 here whatever is served)
    cell["check"]["limits"].update(served_token_gap_max=0.005,
                                   served_logprob_gap_max=0.006)
    _dump(cell, os.path.join(bench, "cells", "tiny-joyai-docqa.json"))
    m = _load(os.path.join(ROOT, "BENCHMARK.json"))
    m["configs"].append({"name": "tiny-joyai", "source": "tests",
                         "reduced": [], "why": "CPU tests",
                         "file": "chipbench/configs/tiny-joyai.json"})
    m["workloads"].append({"name": "tiny-joyai-docqa", "config": "tiny-joyai",
                           "traffic": "tiny-joyai-docqa", "why": "test",
                           "chips": 1})
    for metric in m["end_to_end"] + m["per_layer"]:
        if CELL in metric.get("workloads", ()):
            metric["workloads"].append("tiny-joyai-docqa")
    _dump(m, os.path.join(root, "BENCHMARK.json"))
    return bench


def test_the_configuration_keeps_every_published_width():
    manifest = _load(os.path.join(ROOT, "BENCHMARK.json"))
    entry = {c["name"]: c for c in manifest["configs"]}["joyai-llm-flash-d5"]
    cfg = _load(os.path.join(ROOT, entry["file"]))
    reduced = {"num_hidden_layers", "num_nextn_predict_layers",
               "max_position_embeddings"}
    assert set(entry["reduced"]) == set(cfg["reduced"]) == reduced
    assert entry["source"] == cfg["source"]
    assert not reduced & set(WIDTHS)
    for key, value in PUBLISHED.items():
        if key not in reduced:
            assert cfg[key] == value, key
    assert cfg["num_hidden_layers"] == 5
    assert cfg["num_nextn_predict_layers"] == 0
    assert cfg["published"] == {k: PUBLISHED[k] for k in reduced}
    assert set(cfg["assumed"]) >= reduced
    # all 256 experts and the whole vocabulary are held here, 1 dense + 4
    # expert layers; the parameter count is the reference's own
    from chipbench.harness.manifest import Cell

    cell = Cell(CELL)
    assert cell.reference().param_count(cfg) == cfg["parameters"] \
        == 5_558_141_952
    _, pcfg = cell.program_config()
    assert (pcfg.n_routed_experts, pcfg.vocab_size, pcfg.num_hidden_layers,
            pcfg.first_k_dense_replace) == (256, 129280, 5, 1)
    assert pcfg.latent_width == 576 and pcfg.latent_row_width == 640


def test_the_cell_is_the_issues_traffic_and_engine():
    from chipbench.harness.manifest import Cell

    cell = Cell(CELL)
    tr, eng = cell.traffic, cell.shape["engine"]
    assert cell.chips == 1 and tr["kind"] == "closed_loop"
    assert (tr["clients"], tr["cycle"], tr["fill_seconds"],
            tr["drain_seconds"], tr["shape_seed"]) == (16, 2048, 6, 60, 0)
    assert tr["prime_documents"] is True
    assert tr["documents"] == {"count": 16, "zipf_s": 1.0, "len": {
        "dist": "uniform", "min": 8192, "max": 16384}}
    assert tr["prompt_len"] == {"dist": "uniform", "min": 128, "max": 1024}
    assert tr["output_len"] == {"dist": "uniform", "min": 32, "max": 96}
    assert (eng["num_slots"], eng["max_len"], eng["page_size"],
            eng["num_pages"]) == (16, 17920, 16, 17920)
    assert eng["num_pages"] == eng["num_slots"] * eng["max_len"] // 16
    assert eng["prefill_chunk"] in (256, 512, 1024)
    assert eng["paged_attention"] == "auto" and eng["prefix_cache"] is True
    assert {m["name"] for m in cell.end_to_end()} == {
        "setup_s", "serve_out_tokens_per_s", "itl_p95_ms"}
    names = {m["name"] for m in cell.per_layer()}
    assert {"kernel.latent_paged_attention_roofline",
            "kernel.moe_expert_matmul_roofline", "step.moe_decode_device_ms",
            "step.decode_device_ms", "step.prefill_chunk_device_ms"} <= names
    assert "kernel.paged_attention_roofline" not in names


def test_latent_attention_cost_by_hand():
    from chipbench.harness import latent_moe_costs as costs

    # two slots, 3 and 0 cached tokens, 32 heads over a 576-wide row whose
    # first 512 lanes are the value: contexts 4 and 1
    ops, byts = costs.latent_attention_cost([3, 0], 32, 576, 512)
    assert ops == 2 * (576 + 512) * 32 * (4 + 1) == 348_160
    # rows once: 5 x 576 x 2 B; q in and o out: 2 slots x 32 x 1088 x 2 B
    assert byts == 5 * 1152 + 2 * 32 * 1088 * 2 == 145_024
    # the cell's reckoning: 16 slots at 12,900 tokens read 238 MB a layer
    _, byts = costs.latent_attention_cost([12_900] * 16, 32, 576, 512)
    assert abs(byts - 16 * 12_901 * 1152) == 16 * 32 * 1088 * 2


def test_expert_products_cost_by_hand():
    from chipbench.harness import latent_moe_costs as costs

    # a 512-token chunk, 8 experts a token, all 256 experts touched
    ops, byts = costs.expert_products_cost(512 * 8, 256, 2048, 768)
    assert ops == 2 * 3 * 2048 * 768 * 4096 == 38_654_705_664
    assert byts == 256 * 3 * 2048 * 768 * 2 == 2_415_919_104
    # a decode step that touches 102 experts reads 102 of them
    assert costs.expert_products_cost(128, 102, 2048, 768)[1] \
        == 102 * 9_437_184


@pytest.mark.parametrize("case", ["one-token-cannot-carry-it",
                                  "most-of-a-window-does", "numpy"])
def test_the_served_tokens_gap_is_a_running_median(case):
    """`position_gaps` judges a position by the median gap of it and the 31
    before it: one flipped token (of 30% that flip somewhere, each moving
    its logits as far as float8 moves all) reads 0, a fault on most tokens
    reads what it is; the first 31 positions are judged inside later
    windows."""
    import numpy as np
    from chipbench.harness.manifest import Cell

    ref = Cell(CELL).reference()
    w = ref.GAP_WINDOW
    assert w == 32
    if case == "one-token-cannot-carry-it":
        x = np.zeros(64, np.float32)
        x[[3, 11, 19, 20, 33, 34, 35, 50]] = 1.5
        want = np.zeros(64, np.float32)
    elif case == "most-of-a-window-does":
        x = np.where(np.arange(64) % 3 == 0, 0.0, 0.4).astype(np.float32)
        want = np.where(np.arange(64) >= w - 1, 0.4, 0.0)
    else:
        x = np.random.default_rng(3).gamma(0.3, 0.5, 96).astype(np.float32)
        want = np.array([0.0] * (w - 1) + [
            np.sort(x[j - w + 1:j + 1])[w // 2] for j in range(w - 1, 96)])
    assert np.allclose(np.asarray(ref._running_median(x, w)), want)


def test_position_gaps_against_the_plain_logits():
    """Gaps: the running median of `best - taken` from `logits`; first
    choices and log-probabilities: one position each."""
    import jax
    import jax.numpy as jnp
    import numpy as np
    from chipbench.harness.manifest import Cell

    cell = Cell(CELL)
    ref, cfg = cell.reference(), dict(cell.config, **TINY)
    params = ref.make_params(cfg, ref.seed_words(2**31 + 5))
    ids = np.random.default_rng(1).integers(0, 512, (80,)).astype(np.int32)
    first, count = 20, 48
    with jax.default_matmul_precision("highest"):
        full = np.asarray(ref.logits(cfg, params, jnp.asarray(ids)))
        rows = full[first - 1:first - 1 + count]
        tokens = np.argsort(rows, -1)[:, -3]         # each position's third
        gaps, firsts, logps = ref.position_gaps(
            cfg, params, jnp.asarray(ids), jnp.int32(first),
            jnp.asarray(tokens))
    took = rows[np.arange(count), tokens]
    point = rows.max(-1) - took
    assert point.min() > 0
    w = ref.GAP_WINDOW
    assert np.allclose(np.asarray(gaps)[:w - 1], 0)
    assert np.allclose(np.asarray(gaps)[w - 1:], [
        np.sort(point[j - w + 1:j + 1])[w // 2] for j in range(w - 1, count)],
        atol=1e-5)
    assert np.array_equal(np.asarray(firsts), rows.argmax(-1))
    lse = np.log(np.exp(rows - rows.max(-1, keepdims=True)).sum(-1)) \
        + rows.max(-1)
    assert np.allclose(np.asarray(logps), took - lse, atol=1e-4)


@pytest.fixture(scope="module")
def tiny_plain(joyai_bench):
    return run_cell("tiny-joyai-docqa", 2**31 + 11, 1.5, False,
                    bench_dir=joyai_bench, require_chip=False,
                    with_control=True)


@pytest.fixture(scope="module")
def tiny_traced(joyai_bench):
    return run_cell("tiny-joyai-docqa", 9, 1.5, True, bench_dir=joyai_bench,
                    require_chip=False)


def test_the_tiny_cell_is_correct_and_the_fp8_control_is_not(tiny_plain):
    assert tiny_plain["correct"] is True and tiny_plain["failed"] == 0
    assert tiny_plain["attempted"] > 0
    assert tiny_plain["control_correct"] is False
    assert set(tiny_plain["metrics"]) == {
        "setup_s", "serve_out_tokens_per_s", "itl_p95_ms"}
    assert tiny_plain["device"]["platform"] == "cpu"


def test_the_traced_tiny_cell_reads_host_metrics_and_leaves_out_the_rest(
        tiny_traced):
    assert tiny_traced["correct"] is True
    got = set(tiny_traced["metrics"])
    # (`engine.ttft_mean_ms` needs a first token inside the traced 1.5 s,
    # which a loaded box does not always give)
    assert {"engine.slot_occupancy_share",
            "engine.kv_pages_held_share"} <= got
    # nothing ran on a device here: the three new readers find no device
    # operation to read and leave their metrics out
    assert not got & {"kernel.latent_paged_attention_roofline",
                      "kernel.moe_expert_matmul_roofline",
                      "step.moe_decode_device_ms"}


@pytest.mark.parametrize("metric", [
    "kernel.latent_paged_attention_roofline",
    "kernel.moe_expert_matmul_roofline", "step.moe_decode_device_ms"])
@pytest.mark.parametrize("case", ["no-trace", "qwen-cell", "empty-trace"])
def test_a_new_reader_returns_nothing_where_there_is_nothing_to_read(
        metric, case):
    """No trace; a cell of another family (whose cell file names none of
    the new kernels, as the parent's program has none); a trace that holds
    no operation."""
    from chipbench.harness.manifest import Cell
    from chipbench.harness.trace_reduce import TraceSummary

    cell = Cell("serve-qwen2-docqa-closed" if case == "qwen-cell" else CELL)
    trace = None if case == "no-trace" else TraceSummary(
        {"devices": {"/device:TPU:0": {"ops": [], "modules": []}},
         "host": []}, 4.0)
    run = types.SimpleNamespace(
        cell=cell, trace=trace, samples={"decode_lengths": [[5, 7]]},
        peaks={"bf16_flops": 197e12, "hbm_bytes_per_s": 819e9},
        counters={}, window_s=4.0)
    assert cell.layer_reader(metric).read(run) is None


def test_the_new_readers_on_a_hand_made_trace():
    """Two decode calls of 10 ms and one 50 ms chunk on a made-up device:
    each decode call holds 4 expert layers (a grouped product of 0.5 ms and
    its group layout of 0.1 ms; the router's sort is not the layer's to
    name) and 5 latent kernels of 0.4 ms; the chunk holds 12 grouped
    products of 2 ms; one more product lies OUTSIDE any chunk and is not
    counted."""
    from chipbench.harness.manifest import Cell
    from chipbench.harness.trace_reduce import TraceSummary

    ms = 1e6
    ops, modules = [], []
    for call in range(2):
        t0 = call * 20 * ms
        modules.append(["jit_decode(1)", t0, 10 * ms])
        for i in range(4):
            ops.append([f"%sort.{i} sort (f32[16,256], s32[16,256])",
                        t0 + i * 2 * ms, 0.2 * ms])
            ops.append([f"%ragged-dot-metadata.{i} custom-call"
                        "[tpu_custom_call]", t0 + (i * 2 + 0.2) * ms,
                        0.1 * ms])
            ops.append([f"%ragged-dot-none.{i} custom-call[tpu_custom_call]",
                        t0 + (i * 2 + 0.3) * ms, 0.5 * ms])
        for i in range(5):
            ops.append([f"%latent_paged_decode_attention.{i} custom-call"
                        "[tpu_custom_call] bf16[16,32,512]",
                        t0 + 8 * ms + i * 0.4 * ms, 0.4 * ms])
    modules.append(["jit_prefill(2)", 100 * ms, 50 * ms])
    for i in range(12):
        ops.append([f"%ragged-dot-none.{i} custom-call[tpu_custom_call]",
                    100 * ms + i * 3 * ms, 2 * ms])
    ops.append(["%ragged-dot-none.99 custom-call[tpu_custom_call]", 200 * ms,
                7 * ms])
    cell = Cell(CELL)
    peaks = {"bf16_flops": 197e12, "hbm_bytes_per_s": 819e9}
    run = types.SimpleNamespace(
        cell=cell, peaks=peaks, counters={}, window_s=0.3,
        samples={"decode_lengths": [[12_000] * 16, [12_000] * 16]},
        trace=TraceSummary({"devices": {"/device:TPU:0": {
            "ops": ops, "modules": modules}}, "host": []}, 0.3))
    read = lambda name: cell.layer_reader(name).read(run)  # noqa: E731
    assert read("step.moe_decode_device_ms") == pytest.approx(4 * 0.6)
    # a chunk's least time: 4 layers x 2.416 GB over 819 GB/s = 11.80 ms
    # (memory-bound: 38.7 GFLOP a layer are 0.2 ms); the products took 24
    assert read("kernel.moe_expert_matmul_roofline") == pytest.approx(
        100 * 4 * 2_415_919_104 / 819e9 / 24e-3)
    # a decode call's least time: 5 layers x (16 x 12,001 rows x 1,152 B +
    # 16 x 32 x 1,088 x 2 B) over 819 GB/s; the kernels took 2 x 5 x 0.4 ms
    byts = 16 * 12_001 * 1152 + 16 * 32 * 1088 * 2
    assert read("kernel.latent_paged_attention_roofline") == pytest.approx(
        100 * 2 * 5 * byts / 819e9 / 4e-3)
