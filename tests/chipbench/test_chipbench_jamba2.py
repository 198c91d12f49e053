"""The configuration `jamba2-3b` and its cell on the CPU: every published
key is kept and the parameters are the issue's arithmetic, the traffic and
the engine are the issue's, the new byte counts give the hand-worked
numbers, the plain reference agrees with the program's forward on seeded
weights, a tiny copy of the cell (ADDED AS FILES to a temp copy of the
benchmark, as `conftest.py` does for the Qwen cells) runs through the
`closed_loop` runner and is `correct`, the float8 control in the engine's
place is not, an engine that serves WITHOUT the inner norms or with the
convolution one tap short is not, and each new reader returns nothing where
there is nothing to read. Kernels run interpreted here; no number of these
runs is a device metric."""

import json
import os
import shutil
import types

import numpy as np
import pytest

from chipbench.run import run_cell

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
CELL = "serve-jamba2-3b-reason-256-closed"
CONFIG = "jamba2-3b"
TRAFFIC = "unshared-512-in-1k-out-256-closed"

# `config` of the catalog row "AI21-Jamba2-3B" (model-configs guide), read
# from the model's own config.json
PUBLISHED = {
    "attn_layer_offset": 7, "attn_layer_period": 14,
    "expert_layer_offset": 1, "expert_layer_period": 2, "hidden_act": "silu",
    "hidden_size": 2560, "intermediate_size": 8192, "mamba_conv_bias": True,
    "mamba_d_conv": 4, "mamba_d_state": 16, "mamba_dt_rank": 160,
    "mamba_expand": 2, "mamba_proj_bias": False,
    "max_position_embeddings": 262144, "model_type": "jamba",
    "num_attention_heads": 20, "num_experts": 1, "num_experts_per_tok": 1,
    "num_hidden_layers": 28, "num_key_value_heads": 1,
    "num_logits_to_keep": 1, "rms_norm_eps": 1e-06, "sliding_window": None,
    "tie_word_embeddings": True, "use_mamba_kernels": True,
    "vocab_size": 65536,
}

# one 128-wide KV head (the live-pages kernel's width), 512 channels
TINY = dict(vocab_size=512, hidden_size=256, intermediate_size=256,
            num_hidden_layers=4, num_attention_heads=2,
            num_key_value_heads=1, attn_layer_period=2, attn_layer_offset=1,
            mamba_dt_rank=16, max_position_embeddings=512)

# limits of the TINY cell, set as the real cell's are, from readings on the
# CPU at this size (seed 2**31 + 11; `fill_seconds` 0, so the window opens
# with the first submissions; the interpreted kernels serve 2 requests of
# 32-40 tokens in 2 s, more on a quicker machine). The tiny model's logits
# are mostly its tied embedding's (it repeats its last token by a wide
# margin), so the token gap reads 0 in every run, sound or not, and each
# control fails by the LOG-PROBABILITY limit: sound 0.0054; the float8
# control 0.046, the engine without its inner norms 0.188, with the
# convolution one tap short 0.130.
LIMITS = dict(served_token_gap_max=0.01, served_logprob_gap_max=0.02)
WINDOW_S = 2.0


def _load(path):
    with open(path) as f:
        return json.load(f)


def _dump(obj, path):
    with open(path, "w") as f:
        json.dump(obj, f, indent=1)


@pytest.fixture(scope="module")
def jamba_bench(tmp_path_factory):
    """`chipbench/` inside a temp copy that also holds a tiny copy of the
    cell: a configuration, a traffic mix and a cell, all new files."""
    root = str(tmp_path_factory.mktemp("chipbench_jamba2"))
    bench = os.path.join(root, "chipbench")
    shutil.copytree(os.path.join(ROOT, "chipbench"), bench,
                    ignore=shutil.ignore_patterns(".work", "__pycache__"))
    cfg = _load(os.path.join(bench, "configs", f"{CONFIG}.json"))
    cfg.update(TINY)
    _dump(cfg, os.path.join(bench, "configs", "tiny-jamba2.json"))
    tr = _load(os.path.join(bench, "traffic", f"{TRAFFIC}.json"))
    # prompts of three to six chunks with a padded last one, answers of 32+
    # tokens (PERF.md section 7: a check over a handful of tokens reads what
    # one token does)
    tr.update(clients=2, cycle=64, fill_seconds=0,
              prompt_len={"dist": "uniform", "min": 40, "max": 90},
              output_len={"dist": "uniform", "min": 32, "max": 40})
    _dump(tr, os.path.join(bench, "traffic", "tiny-jamba2.json"))
    cell = _load(os.path.join(bench, "cells", f"{CELL}.json"))
    # on the CPU "auto" means the dense path: ask for the kernels
    # (interpreted)
    cell["engine"].update(num_slots=2, max_len=160, prefill_chunk=16,
                          num_pages=24, paged_attention=True)
    cell["check"].update(sample_requests=12, max_output=40)
    cell["check"]["limits"].update(LIMITS)
    _dump(cell, os.path.join(bench, "cells", "tiny-jamba2.json"))
    m = _load(os.path.join(ROOT, "BENCHMARK.json"))
    m["configs"].append({"name": "tiny-jamba2", "source": "tests",
                         "reduced": [], "why": "CPU tests",
                         "file": "chipbench/configs/tiny-jamba2.json"})
    m["workloads"].append({"name": "tiny-jamba2", "config": "tiny-jamba2",
                           "traffic": "tiny-jamba2", "why": "test",
                           "chips": 1})
    for metric in m["end_to_end"] + m["per_layer"]:
        if CELL in metric.get("workloads", ()):
            metric["workloads"].append("tiny-jamba2")
    _dump(m, os.path.join(root, "BENCHMARK.json"))
    return bench


def test_the_configuration_keeps_every_published_key_and_cuts_nothing():
    manifest = _load(os.path.join(ROOT, "BENCHMARK.json"))
    entry = {c["name"]: c for c in manifest["configs"]}[CONFIG]
    cfg = _load(os.path.join(ROOT, entry["file"]))
    assert entry["reduced"] == cfg["reduced"] == [] and cfg["published"] == {}
    assert entry["source"] == cfg["source"] == (
        "https://huggingface.co/ai21labs/AI21-Jamba2-3B/blob/main/"
        "config.json")
    for key, value in PUBLISHED.items():
        assert cfg[key] == value, key
    assert set(cfg["assumed"]) == {"layer_order", "no_positions",
                                   "seeded_values", "state_dtype"}
    assert "WHOLE model" in cfg["stands_for"]
    assert "Nothing was cut" in cfg["stands_for"]
    # the issue's arithmetic, in bf16 parameters
    h, d, n, r, f, V = 2560, 5120, 16, 160, 8192, 65536
    mamba = (h * 2 * d + d * h + d * (r + 2 * n) + (r * d + d) + n * d
             + (4 * d + d) + d + (r + 2 * n))
    attn = 2 * h * h + 2 * h * 128
    mlp = 3 * h * f
    assert (mamba, attn, mlp) == (41_241_792, 13_762_560, 62_914_560)
    total = (26 * (mamba + mlp + 2 * h) + 2 * (attn + mlp + 2 * h)
             + V * h + h)
    assert total == 3_029_337_472 and 6.05e9 < 2 * total < 6.07e9
    from chipbench.harness.manifest import Cell

    cell = Cell(CELL)
    assert cell.reference().param_count(cfg) == cfg["parameters"] == total
    _, pcfg = cell.program_config()
    assert (pcfg.vocab_size, pcfg.num_hidden_layers, pcfg.attention_layers,
            len(pcfg.mamba_layers), pcfg.d_inner, pcfg.head_dim,
            pcfg.state_dtype, pcfg.use_inner_norms,
            pcfg.conv_taps_skipped) == (
        65536, 28, (7, 21), 26, 5120, 128, "float32", True, 0)


def test_the_cell_is_the_issues_traffic_and_engine():
    from accelerate_tpu.models import jamba
    from chipbench.harness import traffic
    from chipbench.harness.manifest import Cell

    cell = Cell(CELL)
    assert cell.chips == 1 and cell.kind == "closed_loop"
    assert cell.entry["traffic"] == TRAFFIC
    tr = cell.traffic
    assert (tr["clients"], tr["shape_seed"], tr["cycle"], tr["fill_seconds"],
            tr["drain_seconds"]) == (256, 0, 2048, 45, 90)
    assert "documents" not in tr
    assert tr["prompt_len"] == {"dist": "lognormal", "median": 512,
                                "sigma": 0.8, "min": 64, "max": 2048}
    assert tr["output_len"] == {"dist": "lognormal", "median": 1024,
                                "sigma": 0.6, "min": 128, "max": 2048}
    engine = cell.shape["engine"]
    assert engine == {
        "num_slots": 256, "max_len": 4096, "prefill_chunk": 512,
        "num_pages": 65536, "cache_dtype": "bfloat16", "prefix_cache": False,
        "paged_attention": "auto", "max_queue": 512}
    assert cell.shape["check"]["kernels_compiled"] == [
        "ssm_decode_step", "ssm_chunk_scan", "paged_decode_attention"]
    assert cell.shape["kernels"] == {
        "ssm_decode": "ssm_decode_step", "ssm_chunk": "ssm_chunk_scan",
        "paged_attention": "paged_decode_attention"}
    assert {m["name"] for m in cell.end_to_end()} == {
        "setup_s", "serve_out_tokens_per_s", "itl_p95_ms"}
    mine = {m["name"] for m in cell.per_layer()}
    assert {"kernel.ssm_decode_roofline", "step.ssm_scan_prefill_device_ms",
            "step.decode_device_ms", "step.prefill_chunk_device_ms",
            "device.idle_share.serve", "engine.kv_pages_held_share",
            "engine.host_ms_per_step", "engine.kv_allocate_ms_per_admission",
            "engine.submit_ms_per_request",
            "step.prefill_attention_device_ms", "step.decode_ffn_device_ms",
            "step.decode_attention_device_ms",
            "device.unscoped_busy_share.serve"} <= mine
    assert not mine & {"kernel.paged_attention_roofline",
                       "engine.prefix_token_hit_share",
                       "kernel.retention_decode_roofline"}
    # every request fits a slot and the pool never refuses an admitted one
    prompts = traffic.quantiles(tr["prompt_len"], tr["cycle"])
    answers = traffic.quantiles(tr["output_len"], tr["cycle"])
    assert prompts.max() + answers.max() <= engine["max_len"]
    assert 650 < prompts.mean() < 760 and 1100 < answers.mean() < 1200
    assert 256 * ((4096 + 512) // 16) > engine["num_pages"] >= 256 * (
        4096 // 16)
    # the pools: 257 entries of 10.12 MB, 65,537 pages of 1,024 B a token
    _, pcfg = cell.program_config()
    pages, state = jamba.cache_spec(pcfg)
    entry = 26 * (16 + 3) * 5120 * 4
    assert entry == 10_117_120 and 2.59e9 < 257 * entry < 2.61e9
    assert 2 * 2 * 128 * 2 == 1024
    pool = 65537 * 16 * 1024
    assert 8e9 < 257 * entry + pool + 2 * cell.config["parameters"] < 15.5e9


def test_scan_costs_by_hand():
    """A lane and Mamba layer: 5120 x 16 float32 numbers of state read and
    written, three rows of 5120 float32 in and out, two of 16."""
    from chipbench.harness import ssm_costs as costs
    from chipbench.harness.manifest import Cell

    cfg = Cell(CELL).config
    assert costs.state_elements(cfg) == (81_920, 15_360)
    assert costs.mamba_layers(cfg) == 26
    ops, byts = costs.decode_scan_cost(1, cfg)
    assert ops == 6 * 81_920
    assert byts == 2 * 327_680 + 3 * 5120 * 4 + 2 * 16 * 4
    assert costs.decode_scan_cost(256, cfg) == (256 * ops, 256 * byts)
    _, with_window = costs.decode_scan_cost(1, cfg, window_in_kernel=True)
    assert with_window == byts + 2 * 61_440
    assert ops / 197e12 < 0.01 * byts / 819e9       # memory-bound, far
    # a whole step at 256 lanes: 4.77 GB, 5.83 ms of the HBM peak
    assert 5.8e-3 < 26 * 256 * byts / 819e9 < 5.9e-3


def test_the_program_agrees_with_the_reference_on_seeded_weights():
    """The family's own forward (no cache) against the reference's logits,
    float32, the harness's seeded weights; and the seeded values are the
    configuration's `assumed` ones."""
    import jax
    import jax.numpy as jnp

    from accelerate_tpu.models import jamba
    from chipbench.harness.manifest import Cell

    ref = Cell(CELL).reference()
    cfg = dict(_load(os.path.join(ROOT, "chipbench", "configs",
                                  f"{CONFIG}.json")), **TINY)
    params = ref.make_params(cfg, ref.seed_words(2**31 + 5))
    m = params["layers"][0]["mamba"]
    np.testing.assert_allclose(np.asarray(m["A_log"])[:, 7],
                               np.log(np.arange(1, 17)), rtol=1e-6)
    dt0 = np.asarray(jax.nn.softplus(m["dt_proj"]["bias"]))
    np.testing.assert_allclose(dt0[[0, -1]], [1e-3, 1e-1], rtol=1e-4)
    assert float(np.abs(np.asarray(m["conv"]["kernel"])).max()) <= 0.5
    assert "attn" in params["layers"][1] and "mamba" not in params["layers"][1]
    pcfg = jamba.JambaConfig(**{k: cfg[k] for k in cfg["program"]["copy"]})
    ids = np.random.default_rng(0).integers(0, 512, (70,)).astype(np.int32)
    with jax.default_matmul_precision("highest"):
        want = ref.logits(cfg, params, jnp.asarray(ids))
        got = jamba.forward(pcfg, params, jnp.asarray(ids)[None])[0]
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=2e-3, atol=2e-4)
    # the program's own initialiser builds the same tree
    own = jamba.init_params(pcfg, jax.random.key(0))
    assert jax.tree.structure(own) == jax.tree.structure(params)
    assert [x.shape for x in jax.tree.leaves(own)] == [
        x.shape for x in jax.tree.leaves(params)]


@pytest.fixture(scope="module")
def tiny_plain(jamba_bench):
    return run_cell("tiny-jamba2", 2**31 + 11, WINDOW_S, False,
                    bench_dir=jamba_bench, require_chip=False,
                    with_control=True)


def test_the_tiny_cell_is_correct_and_the_fp8_control_is_not(tiny_plain):
    assert tiny_plain["correct"] is True and tiny_plain["failed"] == 0
    assert tiny_plain["attempted"] > 0
    assert tiny_plain["control_correct"] is False
    assert set(tiny_plain["metrics"]) == {
        "setup_s", "serve_out_tokens_per_s", "itl_p95_ms"}
    assert tiny_plain["device"]["platform"] == "cpu"


@pytest.mark.parametrize("other", [dict(use_inner_norms=False),
                                   dict(conv_taps_skipped=1)],
                         ids=["inner-norms-off", "one-tap-short"])
def test_an_engine_that_serves_another_model_is_not_correct(jamba_bench,
                                                            other):
    """The engine serving without the three inner norms, or with the
    convolution's oldest tap left out, under the cell's reference and
    limits: what `probe.py --set cell.program_config_extra...` does on the
    chip."""
    cell = _load(os.path.join(jamba_bench, "cells", "tiny-jamba2.json"))
    name = "tiny-jamba2-" + "-".join(other)
    cell["program_config_extra"] = other
    _dump(cell, os.path.join(jamba_bench, "cells", f"{name}.json"))
    root = os.path.dirname(jamba_bench)
    m = _load(os.path.join(root, "BENCHMARK.json"))
    m["workloads"].append({"name": name, "config": "tiny-jamba2",
                           "traffic": "tiny-jamba2", "why": "test",
                           "chips": 1})
    for metric in m["end_to_end"] + m["per_layer"]:
        if "tiny-jamba2" in metric.get("workloads", ()):
            metric["workloads"].append(name)
    _dump(m, os.path.join(root, "BENCHMARK.json"))
    out = run_cell(name, 2**31 + 11, WINDOW_S, False,
                   bench_dir=jamba_bench, require_chip=False)
    assert out["attempted"] > 0 and out["failed"] == 0
    assert out["correct"] is False


def test_a_traced_tiny_cell_reports_the_by_part_metrics(jamba_bench):
    """A traced run on the CPU holds no device plane, so every device
    metric is left out and the host's are there; the page group answers the
    harness's page questions."""
    from accelerate_tpu.telemetry.trace import configure_tracing

    try:
        out = run_cell("tiny-jamba2", 5, 1.0, True, bench_dir=jamba_bench,
                       require_chip=False)
    finally:
        configure_tracing(False)   # the runner turns it on for the process
    assert out["correct"] is True
    got = out["metrics"]
    assert 0 < got["engine.kv_pages_held_share"]["value"] <= 100
    assert got["engine.slot_occupancy_share"]["value"] > 0
    assert "kernel.ssm_decode_roofline" not in got
    assert "step.ssm_scan_prefill_device_ms" not in got


NEW_READERS = ["kernel.ssm_decode_roofline",
               "step.ssm_scan_prefill_device_ms"]


@pytest.mark.parametrize("metric", NEW_READERS)
@pytest.mark.parametrize("case", ["no-trace", "qwen-cell", "brumby-cell",
                                  "empty-trace"])
def test_a_new_reader_returns_nothing_where_there_is_nothing_to_read(
        metric, case):
    """No trace; a cell of another family (as the parent's program, which
    has no scan); a trace that holds no operation."""
    from chipbench.harness.manifest import Cell
    from chipbench.harness.trace_reduce import TraceSummary

    cell = Cell({"qwen-cell": "serve-qwen2-docqa-closed",
                 "brumby-cell": "serve-brumby-8k-in-1k-out-closed"}.get(
                     case, CELL))
    trace = None if case == "no-trace" else TraceSummary(
        {"devices": {"/device:TPU:0": {"ops": [], "modules": []}},
         "host": []}, 4.0)
    run = types.SimpleNamespace(
        cell=cell, trace=trace, samples={"decode_lengths": [[5, 7]]},
        peaks={"bf16_flops": 197e12, "hbm_bytes_per_s": 819e9},
        counters={}, window_s=4.0)
    assert cell.layer_reader(metric).read(run) is None


def test_both_readers_on_a_hand_made_trace():
    """Two decode calls on a made-up device, each 26 scans of 0.3 ms with
    256 and then 192 live lanes, and a prefill call of 26 chunk scans of
    0.15 ms; a decode kernel outside any call and one inside `jit_prefill`
    are not counted. A lane and layer is 716.9 KB at 819 GB/s = 0.875 us:
    256 lanes are 0.224 ms of a 0.3 ms kernel."""
    from chipbench.harness import ssm_costs as costs
    from chipbench.harness.manifest import Cell
    from chipbench.harness.trace_reduce import TraceSummary

    ms = 1e6
    ops, modules = [], []
    for call in range(2):
        t0 = call * 40 * ms
        modules.append(["jit_decode(1)", t0, 30 * ms])
        for i in range(26):
            ops.append([f"%ssm_decode_step.{i} custom-call[tpu_custom_call]",
                        t0 + i * ms, 0.3 * ms])
    modules.append(["jit_prefill(2)", 100 * ms, 40 * ms])
    for i in range(26):
        ops.append([f"%ssm_chunk_scan.{i} custom-call[tpu_custom_call]",
                    (101 + i) * ms, 0.15 * ms])
    ops.append(["%ssm_decode_step.99 custom-call[tpu_custom_call]",
                130 * ms, 5 * ms])
    ops.append(["%ssm_decode_step.98 custom-call[tpu_custom_call]",
                200 * ms, 7 * ms])
    cell = Cell(CELL)
    peaks = {"bf16_flops": 197e12, "hbm_bytes_per_s": 819e9}
    run = types.SimpleNamespace(
        cell=cell, peaks=peaks, counters={}, window_s=0.3,
        samples={"decode_lengths": [[900] * 256, [900] * 192]},
        trace=TraceSummary({"devices": {"/device:TPU:0": {
            "ops": ops, "modules": modules}}, "host": []}, 0.3))
    lane = costs.decode_scan_cost(1, cell.config)[1] / 819e9
    assert lane == pytest.approx(0.8754e-6, rel=1e-3)
    want = 100.0 * (224 * lane * 26 * 2) / (52 * 0.3e-3)
    got = cell.layer_reader("kernel.ssm_decode_roofline").read(run)
    assert got == pytest.approx(want) and 60 < got < 70
    chunk = cell.layer_reader("step.ssm_scan_prefill_device_ms").read(run)
    assert chunk == pytest.approx(26 * 0.15)
