"""The configuration `brumby-14b-base-d8` and its cell on the CPU: the
published widths are kept and the parameters are the issue's arithmetic,
the traffic and the engine are the issue's, the new operation and byte
counts give the hand-worked numbers, the plain reference's `[positions,
positions]` form agrees with the recurrence it never writes, a tiny copy of
the cell (ADDED AS FILES to a temp copy of the benchmark, as `conftest.py`
does for the Qwen cells) runs through the `closed_loop` runner and is
`correct`, the float8 control in the engine's place is not, an engine that
serves WITHOUT gates or with degree 1 is not, and each new reader returns
nothing where there is nothing to read. Kernels run interpreted here; no
number of these runs is a device metric."""

import dataclasses
import json
import os
import shutil
import types

import numpy as np
import pytest

from chipbench.run import run_cell

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
CELL = "serve-brumby-8k-in-1k-out-closed"
CONFIG = "brumby-14b-base-d8"

# `config` of the catalog row "Brumby-14B-Base" (model-configs guide), read
# from the model's own config.json
PUBLISHED = {
    "attention_bias": False, "head_dim": 128, "hidden_act": "silu",
    "hidden_size": 5120, "intermediate_size": 17408,
    "max_position_embeddings": 32768, "max_window_layers": 40,
    "model_type": "brumby", "num_attention_heads": 40,
    "num_hidden_layers": 40, "num_key_value_heads": 8, "rms_norm_eps": 1e-06,
    "rope_scaling": None, "rope_theta": 1000000, "sliding_window": None,
    "tie_word_embeddings": False, "use_sliding_window": False,
    "vocab_size": 151936,
}
WIDTHS = ("hidden_size", "intermediate_size", "head_dim")

# 16-lane heads: the interpreted kernels walk 9 rows of `phi`, not 65
TINY = dict(vocab_size=512, hidden_size=64, intermediate_size=128,
            num_hidden_layers=2, num_attention_heads=4,
            num_key_value_heads=2, head_dim=16,
            max_position_embeddings=256, rope_theta=10000.0)

# limits of the TINY cell, set as the real cell's are, from readings on the
# CPU at this size (seed 2**31 + 11; `fill_seconds` 0, so the window opens
# with the first submissions; ~50 requests of 32-40 tokens in 2 s, fewer on
# a loaded machine): sound runs read a token gap of 0.0006-0.0007 and a
# log-probability gap of 0.0024-0.0035; the float8 control 0.036 and
# 0.030, the engine without gates 0.044 and 0.069, with degree 1 1.00 and
# 1.01.
LIMITS = dict(served_token_gap_max=0.015, served_logprob_gap_max=0.015)
WINDOW_S = 2.0


def _load(path):
    with open(path) as f:
        return json.load(f)


def _dump(obj, path):
    with open(path, "w") as f:
        json.dump(obj, f, indent=1)


@pytest.fixture(scope="module")
def brumby_bench(tmp_path_factory):
    """`chipbench/` inside a temp copy that also holds a tiny copy of the
    cell: a configuration, a traffic mix and a cell, all new files."""
    root = str(tmp_path_factory.mktemp("chipbench_brumby"))
    bench = os.path.join(root, "chipbench")
    shutil.copytree(os.path.join(ROOT, "chipbench"), bench,
                    ignore=shutil.ignore_patterns(".work", "__pycache__"))
    cfg = _load(os.path.join(bench, "configs", f"{CONFIG}.json"))
    cfg.update(TINY)
    _dump(cfg, os.path.join(bench, "configs", "tiny-brumby.json"))
    tr = _load(os.path.join(bench, "traffic",
                            "unshared-8k-in-1k-out-closed.json"))
    # prompts of three to six chunks with a padded last one, answers of 32+
    # tokens (PERF.md section 7: a check over a handful of tokens reads what
    # one token does)
    tr.update(clients=2, cycle=64, fill_seconds=0,
              prompt_len={"dist": "uniform", "min": 40, "max": 90},
              output_len={"dist": "uniform", "min": 32, "max": 40})
    _dump(tr, os.path.join(bench, "traffic", "tiny-brumby.json"))
    cell = _load(os.path.join(bench, "cells", f"{CELL}.json"))
    # on the CPU "auto" means the dense path: ask for the kernels
    # (interpreted)
    cell["engine"].update(num_slots=2, max_len=160, prefill_chunk=16,
                          num_pages=2, paged_attention=True)
    cell["check"].update(sample_requests=12, max_output=40)
    cell["check"]["limits"].update(LIMITS)
    _dump(cell, os.path.join(bench, "cells", "tiny-brumby.json"))
    m = _load(os.path.join(ROOT, "BENCHMARK.json"))
    m["configs"].append({"name": "tiny-brumby", "source": "tests",
                         "reduced": [], "why": "CPU tests",
                         "file": "chipbench/configs/tiny-brumby.json"})
    m["workloads"].append({"name": "tiny-brumby", "config": "tiny-brumby",
                           "traffic": "tiny-brumby", "why": "test",
                           "chips": 1})
    for metric in m["end_to_end"] + m["per_layer"]:
        if CELL in metric.get("workloads", ()):
            metric["workloads"].append("tiny-brumby")
    _dump(m, os.path.join(root, "BENCHMARK.json"))
    return bench


def test_the_configuration_keeps_every_published_width():
    manifest = _load(os.path.join(ROOT, "BENCHMARK.json"))
    entry = {c["name"]: c for c in manifest["configs"]}[CONFIG]
    cfg = _load(os.path.join(ROOT, entry["file"]))
    reduced = {"num_hidden_layers"}
    assert set(entry["reduced"]) == set(cfg["reduced"]) == reduced
    assert entry["source"] == cfg["source"] == (
        "https://huggingface.co/manifestai/Brumby-14B-Base/blob/main/"
        "config.json")
    assert not reduced & set(WIDTHS)
    for key, value in PUBLISHED.items():
        if key not in reduced:
            assert cfg[key] == value, key
    assert cfg["num_hidden_layers"] == 8
    assert cfg["published"] == {"num_hidden_layers": 40}
    assert set(cfg["assumed"]) >= reduced | {
        "retention_degree", "use_gate", "gate_bias", "qk_norm",
        "rotary_pairs", "retention_eps", "state_form", "state_dtype"}
    assert (cfg["retention_degree"], cfg["use_gate"]) == (2, True)
    assert "stands_for" in cfg and "qk_norm" not in cfg
    # the issue's arithmetic, in bf16 parameters
    h, D, H, G, f, V = 5120, 128, 40, 8, 17408, 151936
    layer = (2 * h * H * D + 2 * h * G * D + 3 * h * f + (h * G + G)
             + 2 * h + 2 * D)
    assert layer == 330_352_904
    total = 8 * layer + 2 * V * h + h
    assert total == 4_198_652_992 and 8.39e9 < 2 * total < 8.41e9
    assert 29.4e9 < 2 * (40 * layer + 2 * V * h + h) < 29.6e9
    from chipbench.harness.manifest import Cell

    cell = Cell(CELL)
    assert cell.reference().param_count(cfg) == cfg["parameters"] == total
    _, pcfg = cell.program_config()
    assert (pcfg.vocab_size, pcfg.num_hidden_layers, pcfg.head_dim,
            pcfg.num_attention_heads, pcfg.num_key_value_heads,
            pcfg.retention_degree, pcfg.use_gate, pcfg.state_dtype) == (
        151936, 8, 128, 40, 8, 2, True, "float32")
    assert pcfg.rope_theta == 1e6


def test_the_cell_is_the_issues_traffic_and_engine():
    from accelerate_tpu.models import brumby
    from chipbench.harness import traffic
    from chipbench.harness.manifest import Cell

    cell = Cell(CELL)
    assert cell.chips == 1 and cell.kind == "closed_loop"
    assert cell.entry["traffic"] == "unshared-8k-in-1k-out-closed"
    tr = cell.traffic
    assert (tr["clients"], tr["shape_seed"], tr["cycle"], tr["fill_seconds"],
            tr["drain_seconds"]) == (16, 0, 2048, 30, 120)
    assert "documents" not in tr
    assert tr["prompt_len"] == {"dist": "lognormal", "median": 8192,
                                "sigma": 0.5, "min": 2048, "max": 24576}
    assert tr["output_len"] == {"dist": "lognormal", "median": 1024,
                                "sigma": 0.5, "min": 256, "max": 2048}
    engine = cell.shape["engine"]
    assert engine == {
        "num_slots": 16, "max_len": 26624, "prefill_chunk": 512,
        "num_pages": 16, "cache_dtype": "bfloat16", "prefix_cache": False,
        "paged_attention": "auto", "max_queue": 512}
    assert cell.shape["check"]["kernels_compiled"] == [
        "retention_decode_step", "retention_chunk"]
    assert {m["name"] for m in cell.end_to_end()} == {
        "setup_s", "serve_out_tokens_per_s", "itl_p95_ms"}
    mine = {m["name"] for m in cell.per_layer()}
    assert {"kernel.retention_decode_roofline",
            "kernel.retention_chunk_roofline", "step.decode_device_ms",
            "step.prefill_chunk_device_ms", "device.idle_share.serve",
            "engine.kv_pages_held_share", "engine.host_ms_per_step",
            "engine.kv_allocate_ms_per_admission",
            "step.prefill_attention_device_ms", "step.decode_ffn_device_ms",
            "device.unscoped_busy_share.serve"} <= mine
    assert not mine & {"kernel.paged_attention_roofline",
                       "engine.prefix_token_hit_share",
                       "kernel.routed_expert_matmul_roofline"}
    # every request fits a slot, and the mix is the issue's long-in,
    # long-out one
    prompts = traffic.quantiles(tr["prompt_len"], tr["cycle"])
    answers = traffic.quantiles(tr["output_len"], tr["cycle"])
    assert prompts.max() + answers.max() <= engine["max_len"]
    assert 9000 < prompts.mean() < 9400 and 1100 < answers.mean() < 1160
    # the pool: 16 entries and the spare, 274.99 MB each, as the file says
    _, pcfg = cell.program_config()
    spec = brumby.cache_spec(pcfg)
    entry = 8 * 8 * (spec.state_rows * 128 + 72 * 128) * 4
    assert spec.state_rows == 8320 and entry == 274_989_056
    assert 4.67e9 < 17 * entry < 4.68e9
    assert 12e9 < 17 * entry + 2 * cell.config["parameters"] < 15.5e9


def test_retention_costs_by_hand():
    """40 query heads over 8 KV heads of 128: a head's state has 128 x 129
    / 2 = 8,256 rows of 128 (and its normaliser 8,256 numbers), float32."""
    from chipbench.harness import retention_costs as costs

    assert costs.state_rows(128) == 8256 and costs.state_rows(128, 1) == 128
    entries = 8 * 8256 * 129
    # a decode step of one lane: the state read and written, 3 operations
    # an entry for the update and 2 an entry and query head for the answer
    ops, byts = costs.decode_step_cost(1, 40, 8, 128)
    assert ops == 3 * entries + 2 * 40 * 8256 * 129
    assert byts == 2 * entries * 4 + (80 + 16) * 128 * 2 + 32
    assert 68.1e6 < byts < 68.3e6              # 67.6 MB of S, the rest z, io
    ops16, byts16 = costs.decode_step_cost(16, 40, 8, 128)
    assert (ops16, byts16) == (16 * ops, 16 * byts)
    assert ops / 197e12 < 0.01 * byts / 819e9     # memory-bound, far
    # a chunk of 512 rows: the causal half twice a query head, phi(Q) S_0 a
    # query head, the update a KV head
    ops, byts = costs.chunk_cost(512, 40, 8, 128)
    inside = 2 * 2 * (512 * 513 / 2) * 128 * 40
    across = 2 * 512 * 8256 * 129 * 40
    update = 2 * 512 * 8256 * 129 * 8
    assert ops == inside + across + update
    assert 55e9 < ops < 55.1e9        # 0.28 ms of the bf16 peak a layer
    assert byts == 2 * entries * 4 + 512 * 96 * 128 * 2
    assert ops / 197e12 > 2 * byts / 819e9                  # compute-bound


def test_the_reference_writes_no_state_and_agrees_with_the_recurrence():
    """The reference's `[positions, positions]` form against a recurrence
    written here from the issue's equations (a state of 8,256 rows built
    from the upper triangle of k k^T, which neither the reference nor the
    program lays out that way)."""
    import jax
    import jax.numpy as jnp

    from chipbench.harness.manifest import Cell

    ref = Cell(CELL).reference()
    cfg = dict(_load(os.path.join(ROOT, "chipbench", "configs",
                                  f"{CONFIG}.json")), **TINY)
    cfg.update(num_hidden_layers=1)
    params = ref.make_params(cfg, ref.seed_words(3))
    a = params["layers"][0]["attn"]
    assert np.allclose(np.asarray(a["gate_proj"]["bias"]), [4.0, 9.0])
    x = jax.random.normal(jax.random.key(0), (24, 64))
    with jax.default_matmul_precision("highest"):
        got = ref._retention(cfg, a, x, rows_per_block=8)
        # the same numbers, one token at a time over a fixed-size state
        T, H, G, D = 24, 4, 2, 16
        q = ref._rope(ref._rms_norm((x @ a["q_proj"]["kernel"]).reshape(
            T, H, D), a["q_norm"]["scale"], 1e-6), cfg["rope_theta"])
        k = ref._rope(ref._rms_norm((x @ a["k_proj"]["kernel"]).reshape(
            T, G, D), a["k_norm"]["scale"], 1e-6), cfg["rope_theta"])
        v = (x @ a["v_proj"]["kernel"]).reshape(T, G, D)
        g = jax.nn.sigmoid(x @ a["gate_proj"]["kernel"]
                           + a["gate_proj"]["bias"])
        iu = np.triu_indices(D)
        c = np.where(iu[0] == iu[1], 1.0, np.sqrt(2.0))
        phi = lambda y: c * (y[..., :, None] * y[..., None, :])[  # noqa: E731
            ..., iu[0], iu[1]]
        assert phi(k[0]).shape == (G, D * (D + 1) // 2)
        S = jnp.zeros((G, D * (D + 1) // 2, D))
        z = jnp.zeros((G, D * (D + 1) // 2))
        out = []
        for t in range(T):
            S = g[t][:, None, None] * S + phi(k[t])[:, :, None] * v[t][:, None]
            z = g[t][:, None] * z + phi(k[t])
            pq = phi(q[t].reshape(G, H // G, D))
            out.append(jnp.einsum("ghD,gDv->ghv", pq, S) / (
                jnp.einsum("ghD,gD->gh", pq, z)[..., None] + 1e-6))
        want = jnp.stack(out).reshape(T, H * D) @ a["o_proj"]["kernel"]
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=2e-4, atol=2e-5)


def test_the_program_agrees_with_the_reference_on_seeded_weights():
    """The family's own forward (no cache) against the reference's logits,
    float32, the harness's seeded weights."""
    import jax
    import jax.numpy as jnp

    from accelerate_tpu.models import brumby
    from chipbench.harness.manifest import Cell

    ref = Cell(CELL).reference()
    cfg = dict(_load(os.path.join(ROOT, "chipbench", "configs",
                                  f"{CONFIG}.json")), **TINY)
    params = ref.make_params(cfg, ref.seed_words(2**31 + 5))
    pcfg = brumby.BrumbyConfig(**{k: cfg[k] for k in cfg["program"]["copy"]})
    ids = np.random.default_rng(0).integers(0, 512, (70,)).astype(np.int32)
    with jax.default_matmul_precision("highest"):
        want = ref.logits(cfg, params, jnp.asarray(ids))
        got = brumby.forward(pcfg, params, jnp.asarray(ids)[None])[0]
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=2e-3, atol=2e-4)


@pytest.fixture(scope="module")
def tiny_plain(brumby_bench):
    return run_cell("tiny-brumby", 2**31 + 11, WINDOW_S, False,
                    bench_dir=brumby_bench, require_chip=False,
                    with_control=True)


def test_the_tiny_cell_is_correct_and_the_fp8_control_is_not(tiny_plain):
    assert tiny_plain["correct"] is True and tiny_plain["failed"] == 0
    assert tiny_plain["attempted"] > 0
    assert tiny_plain["control_correct"] is False
    assert set(tiny_plain["metrics"]) == {
        "setup_s", "serve_out_tokens_per_s", "itl_p95_ms"}
    assert tiny_plain["device"]["platform"] == "cpu"


@pytest.mark.parametrize("other", [dict(use_gate=False),
                                   dict(retention_degree=1)],
                         ids=["gates-off", "degree-1"])
def test_an_engine_that_serves_another_model_is_not_correct(brumby_bench,
                                                            other):
    """The engine serving with every gate at 1 (nothing is forgotten), or
    with the first power in the second's place, under the cell's reference
    and limits: what `probe.py --set cell.program_config_extra...` does on
    the chip."""
    cell = _load(os.path.join(brumby_bench, "cells", "tiny-brumby.json"))
    name = "tiny-brumby-" + "-".join(other)
    cell["program_config_extra"] = other
    _dump(cell, os.path.join(brumby_bench, "cells", f"{name}.json"))
    root = os.path.dirname(brumby_bench)
    m = _load(os.path.join(root, "BENCHMARK.json"))
    m["workloads"].append({"name": name, "config": "tiny-brumby",
                           "traffic": "tiny-brumby", "why": "test",
                           "chips": 1})
    for metric in m["end_to_end"] + m["per_layer"]:
        if "tiny-brumby" in metric.get("workloads", ()):
            metric["workloads"].append(name)
    _dump(m, os.path.join(root, "BENCHMARK.json"))
    out = run_cell(name, 2**31 + 11, WINDOW_S, False,
                   bench_dir=brumby_bench, require_chip=False)
    assert out["attempted"] > 0 and out["failed"] == 0
    assert out["correct"] is False


def test_a_traced_tiny_cell_reports_the_by_part_metrics(brumby_bench):
    """A traced run on the CPU holds no device plane, so every device
    metric is left out and the host's are there; the state pool answers the
    harness's page questions with entries."""
    from accelerate_tpu.telemetry.trace import configure_tracing

    try:
        out = run_cell("tiny-brumby", 5, 1.0, True, bench_dir=brumby_bench,
                       require_chip=False)
    finally:
        configure_tracing(False)   # the runner turns it on for the process
    assert out["correct"] is True
    got = out["metrics"]
    assert 0 < got["engine.kv_pages_held_share"]["value"] <= 100
    assert got["engine.slot_occupancy_share"]["value"] > 0
    assert "kernel.retention_decode_roofline" not in got
    assert "kernel.retention_chunk_roofline" not in got


NEW_READERS = ["kernel.retention_decode_roofline",
               "kernel.retention_chunk_roofline"]


@pytest.mark.parametrize("metric", NEW_READERS)
@pytest.mark.parametrize("case", ["no-trace", "qwen-cell", "keye-cell",
                                  "empty-trace"])
def test_a_new_reader_returns_nothing_where_there_is_nothing_to_read(
        metric, case):
    """No trace; a cell of another family (as the parent's program, which
    has no retention); a trace that holds no operation."""
    from chipbench.harness.manifest import Cell
    from chipbench.harness.trace_reduce import TraceSummary

    cell = Cell({"qwen-cell": "serve-qwen2-docqa-closed",
                 "keye-cell": "serve-keye-vl2-docqa-32k-closed"}.get(
                     case, CELL))
    trace = None if case == "no-trace" else TraceSummary(
        {"devices": {"/device:TPU:0": {"ops": [], "modules": []}},
         "host": []}, 4.0)
    run = types.SimpleNamespace(
        cell=cell, trace=trace, samples={"decode_lengths": [[5, 7]]},
        peaks={"bf16_flops": 197e12, "hbm_bytes_per_s": 819e9},
        counters={}, window_s=4.0)
    assert cell.layer_reader(metric).read(run) is None


def test_the_decode_reader_on_a_hand_made_trace():
    """Two decode calls on a made-up device, each 8 layers of a retention
    kernel of 1.6 ms with 16 and then 12 live lanes; a kernel outside any
    call and one inside `jit_prefill` are not counted. A lane and layer is
    68.2 MB at 819 GB/s = 83.3 us: 16 lanes are 1.333 ms of a 1.6 ms
    kernel."""
    from chipbench.harness import retention_costs as costs
    from chipbench.harness.manifest import Cell
    from chipbench.harness.trace_reduce import TraceSummary

    ms = 1e6
    ops, modules = [], []
    for call in range(2):
        t0 = call * 40 * ms
        modules.append(["jit_decode(1)", t0, 30 * ms])
        for i in range(8):
            ops.append([f"%retention_decode_step.{i} custom-call"
                        "[tpu_custom_call]", t0 + 3 * i * ms, 1.6 * ms])
    modules.append(["jit_prefill(2)", 100 * ms, 30 * ms])
    ops.append(["%retention_decode_step.9 custom-call[tpu_custom_call]",
                101 * ms, 5 * ms])
    ops.append(["%retention_decode_step.99 custom-call[tpu_custom_call]",
                200 * ms, 7 * ms])
    cell = Cell(CELL)
    peaks = {"bf16_flops": 197e12, "hbm_bytes_per_s": 819e9}
    run = types.SimpleNamespace(
        cell=cell, peaks=peaks, counters={}, window_s=0.3,
        samples={"decode_lengths": [[9000] * 16, [9000] * 12]},
        trace=TraceSummary({"devices": {"/device:TPU:0": {
            "ops": ops, "modules": modules}}, "host": []}, 0.3))
    lane = costs.decode_step_cost(1, 40, 8, 128)[1] / 819e9
    assert lane == pytest.approx(83.3e-6, rel=2e-3)
    want = 100.0 * (14 * lane * 8 * 2) / (16 * 1.6e-3)
    got = cell.layer_reader("kernel.retention_decode_roofline").read(run)
    assert got == pytest.approx(want) and 70 < got < 75


def test_dataclasses_replace_keeps_the_controls_off_the_served_config():
    """The controls are options of the program's configuration class, off
    in the configuration the cell runs."""
    from accelerate_tpu.models import brumby

    cfg = brumby.BrumbyConfig.tiny()
    assert (cfg.retention_degree, cfg.use_gate) == (2, True)
    other = dataclasses.replace(cfg, retention_degree=1, use_gate=False)
    assert brumby.cache_spec(other).state_rows == 128
    assert brumby.cache_spec(cfg).state_rows == 65 * 128
