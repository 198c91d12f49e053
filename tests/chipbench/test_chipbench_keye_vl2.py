"""The configuration `keye-vl2-30b-a3b-d6` and its cell on the CPU: the
published widths are kept and the parameters are the issue's arithmetic,
the traffic and the engine are the issue's, the new operation and byte
counts give the hand-worked numbers, a tiny copy of the cell (ADDED AS
FILES to a temp copy of the benchmark, as `conftest.py` does for the Qwen
cells) runs through the `closed_loop` runner with documents primed and is
`correct`, the float8 control in the engine's place is not, the engine
with selection OFF is not, and each new reader returns nothing where
there is nothing to read. Kernels run interpreted here; no number of
these runs is a device metric."""

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
CELL = "serve-keye-vl2-docqa-32k-closed"
CONFIG = "keye-vl2-30b-a3b-d6"

# `config` of the catalog row "Keye-VL-2.0-30B-A3B" (model-configs guide),
# read from the model's own config.json
PUBLISHED = {
    "attention_bias": False, "decoder_sparse_step": 1, "head_dim": 128,
    "hidden_act": "silu", "hidden_size": 2048, "intermediate_size": 6144,
    "max_position_embeddings": 262144, "max_window_layers": 48,
    "mlp_only_layers": [], "model_type": "KeyeVL2",
    "moe_intermediate_size": 768, "norm_topk_prob": True,
    "num_attention_heads": 32, "num_experts": 128, "num_experts_per_tok": 8,
    "num_hidden_layers": 48, "num_key_value_heads": 4,
    "num_local_experts": 128, "rms_norm_eps": 1e-06,
    "rope_scaling": {"mrope_section": [16, 24, 24], "rope_type": "default",
                     "type": "default"},
    "rope_theta": 10000000,
    "sa_config": {"indexer_head_dim": 64, "indexer_num_heads": 16,
                  "indexer_num_kv_heads": 1, "kv_chunk_size": 512,
                  "q_chunk_size": 512, "topk": 2048},
    "sliding_window": None, "tie_word_embeddings": False,
    "use_sliding_window": False, "vocab_size": 151936,
}
WIDTHS = ("hidden_size", "intermediate_size", "moe_intermediate_size",
          "head_dim", "num_experts_per_tok", "sa_config")

TINY = dict(vocab_size=512, hidden_size=64, intermediate_size=128,
            moe_intermediate_size=32, num_hidden_layers=2,
            mlp_layer_types=["sparse"] * 2, num_attention_heads=4,
            num_key_value_heads=2, head_dim=128, num_experts=8,
            num_experts_per_tok=2, num_local_experts=8,
            max_position_embeddings=256, rope_theta=10000.0,
            sa_config={"indexer_head_dim": 64, "indexer_num_heads": 4,
                       "indexer_num_kv_heads": 1, "kv_chunk_size": 8,
                       "q_chunk_size": 8, "topk": 16})


def _load(path):
    with open(path) as f:
        return json.load(f)


def _dump(obj, path):
    with open(path, "w") as f:
        json.dump(obj, f, indent=1)


@pytest.fixture(scope="module")
def keye_bench(tmp_path_factory):
    """`chipbench/` inside a temp copy that also holds a tiny copy of the
    cell: a configuration, a traffic mix and a cell, all new files."""
    root = str(tmp_path_factory.mktemp("chipbench_keye_vl2"))
    bench = os.path.join(root, "chipbench")
    shutil.copytree(os.path.join(ROOT, "chipbench"), bench,
                    ignore=shutil.ignore_patterns(".work", "__pycache__"))
    cfg = _load(os.path.join(bench, "configs", f"{CONFIG}.json"))
    cfg.update(TINY)
    _dump(cfg, os.path.join(bench, "configs", "tiny-keye.json"))
    tr = _load(os.path.join(bench, "traffic", "docqa-8docs-32k-closed.json"))
    # documents three to four times `topk` (16): the selection bites in
    # every question and every answer; answers as long as the window of
    # the token gap's median
    tr.update(clients=2, cycle=64, fill_seconds=0,
              documents={"count": 2, "zipf_s": 1.0,
                         "len": {"dist": "uniform", "min": 48, "max": 64}},
              prompt_len={"dist": "uniform", "min": 4, "max": 20},
              output_len={"dist": "uniform", "min": 32, "max": 40})
    _dump(tr, os.path.join(bench, "traffic", "tiny-keye-docqa.json"))
    cell = _load(os.path.join(bench, "cells", f"{CELL}.json"))
    # on the CPU "auto" means the dense path: ask for the kernels
    # (interpreted)
    cell["engine"].update(num_slots=2, max_len=128, prefill_chunk=16,
                          page_size=16, num_pages=40, paged_attention=True)
    cell["check"].update(sample_requests=12, max_output=40)
    cell["check"]["limits"].update(LIMITS)
    _dump(cell, os.path.join(bench, "cells", "tiny-keye-docqa.json"))
    m = _load(os.path.join(ROOT, "BENCHMARK.json"))
    m["configs"].append({"name": "tiny-keye", "source": "tests",
                         "reduced": [], "why": "CPU tests",
                         "file": "chipbench/configs/tiny-keye.json"})
    m["workloads"].append({"name": "tiny-keye-docqa", "config": "tiny-keye",
                           "traffic": "tiny-keye-docqa", "why": "test",
                           "chips": 1})
    for metric in m["end_to_end"] + m["per_layer"]:
        if CELL in metric.get("workloads", ()):
            metric["workloads"].append("tiny-keye-docqa")
    _dump(m, os.path.join(root, "BENCHMARK.json"))
    return bench


# limits of the TINY cell, set as the real cell's are: the token gap, a
# median of 32 positions, decides, so the answers have 32 to 40 tokens. The
# window opens with the first submissions (`fill_seconds` 0), so the plan's
# first two requests are measured however slow the machine is, and the run
# waits for them: on those two alone the sound run reads 0, the float8
# control 0.046, the engine with selection off 0.200, with half of `topk`
# 0.195 (0, 0.035, 0.205, 0.210 on the next two, which a faster machine
# adds). The log-probability is one position each: at `topk` 16 one flipped
# selection moves a sixteenth of a query's attention and bfloat16 flips
# some, so sound runs read 0.09-0.26 and the controls 0.25-0.52; its limit
# holds against a gross fault only, as the real cell's does.
LIMITS = dict(served_token_gap_max=0.01, served_logprob_gap_max=1.0)
WINDOW_S = 2.0


def test_the_configuration_keeps_every_published_width():
    manifest = _load(os.path.join(ROOT, "BENCHMARK.json"))
    entry = {c["name"]: c for c in manifest["configs"]}[CONFIG]
    cfg = _load(os.path.join(ROOT, entry["file"]))
    reduced = {"num_hidden_layers", "max_position_embeddings"}
    assert set(entry["reduced"]) == set(cfg["reduced"]) == reduced
    assert entry["source"] == cfg["source"] == (
        "https://huggingface.co/Kwai-Keye/Keye-VL-2.0-30B-A3B/blob/main/"
        "config.json")
    assert not reduced & set(WIDTHS)
    for key, value in PUBLISHED.items():
        if key not in reduced:
            assert cfg[key] == value, key
    assert cfg["num_hidden_layers"] == 6
    assert cfg["max_position_embeddings"] == 49152
    assert cfg["published"] == {"num_hidden_layers": 48,
                                "max_position_embeddings": 262144}
    assert cfg["mlp_layer_types"] == ["sparse"] * 6
    assert set(cfg["assumed"]) >= reduced | {
        "qk_norm", "mlp_layer_types", "indexer_inputs",
        "indexer_norm_rotation_scale", "topk_counts_tokens", "selection",
        "vision_tower"}
    assert cfg["qk_norm"] is True and "stands_for" in cfg
    # the issue's arithmetic, in bf16 parameters
    h, D, H, Hkv, f, E, V = 2048, 128, 32, 4, 768, 128, 151936
    attention = 2 * h * H * D + 2 * h * Hkv * D
    assert attention == 18_874_368
    indexer = h * 16 * 64 + h * 64 + h * 16
    assert indexer == 2_260_992
    experts = E * 3 * h * f
    assert experts == 603_979_776
    # norms: two of h, q's and k's of D, the index key's scale and bias
    layer = attention + indexer + h * E + experts + 2 * h + 2 * D + 2 * 64
    assert 625.3e6 < layer < 625.5e6
    total = 6 * layer + 2 * V * h + h
    assert total == 4_374_622_464 and 8.74e9 < 2 * total < 8.76e9
    from chipbench.harness.manifest import Cell

    cell = Cell(CELL)
    assert cell.reference().param_count(cfg) == cfg["parameters"] == total
    # all 128 experts and the whole vocabulary are held here
    _, pcfg = cell.program_config()
    assert (pcfg.num_experts, pcfg.vocab_size, pcfg.num_hidden_layers,
            pcfg.head_dim, pcfg.topk) == (128, 151936, 6, 128, 2048)
    assert pcfg.indexer == PUBLISHED["sa_config"]
    assert pcfg.mrope_section == (16, 24, 24) and pcfg.qk_norm
    assert pcfg.rope_theta == 1e7


def test_the_cell_is_the_issues_traffic_and_engine():
    from chipbench.harness import traffic
    from chipbench.harness.manifest import Cell

    cell = Cell(CELL)
    assert cell.chips == 1 and cell.kind == "closed_loop"
    assert cell.entry["traffic"] == "docqa-8docs-32k-closed"
    tr = cell.traffic
    assert (tr["clients"], tr["shape_seed"], tr["cycle"], tr["fill_seconds"],
            tr["drain_seconds"], tr["prime_documents"]) == (
        16, 0, 2048, 6, 60, True)
    assert tr["documents"] == {"count": 8, "zipf_s": 1.0, "len": {
        "dist": "uniform", "min": 24576, "max": 40960}}
    assert tr["prompt_len"] == {"dist": "uniform", "min": 128, "max": 1024}
    assert tr["output_len"] == {"dist": "uniform", "min": 32, "max": 96}
    engine = cell.shape["engine"]
    assert engine == {
        "num_slots": 16, "max_len": 43008, "prefill_chunk": 512,
        "page_size": 16, "num_pages": 21504, "cache_dtype": "bfloat16",
        "prefix_cache": True, "paged_attention": "auto", "max_queue": 512}
    assert cell.shape["check"]["kernels_compiled"] == [
        "indexer_paged_scores", "sparse_topk_select",
        "sparse_paged_decode_attention"]
    assert {m["name"] for m in cell.end_to_end()} == {
        "setup_s", "serve_out_tokens_per_s", "itl_p95_ms"}
    mine = {m["name"] for m in cell.per_layer()}
    assert {"kernel.sparse_paged_attention_roofline",
            "step.sparse_select_decode_device_ms",
            "kernel.routed_expert_matmul_roofline",
            "step.routed_experts_decode_device_ms",
            "engine.prefix_token_hit_share", "step.decode_device_ms",
            "step.prefill_chunk_device_ms", "device.idle_share.serve",
            "engine.host_ms_per_step"} <= mine
    assert not mine & {"kernel.paged_attention_roofline",
                       "kernel.mixed_paged_attention_roofline",
                       "kernel.moe_expert_matmul_roofline"}
    # the pool holds the 8 primed documents (their pages, and the page of
    # the priming question) and 16 sessions' own pages at their largest
    docs = traffic.quantiles(tr["documents"]["len"], 8)
    assert docs.sum() == 262144 and (docs % 16 == 0).all()
    questions = traffic.quantiles(tr["prompt_len"], tr["cycle"])
    answers = traffic.quantiles(tr["output_len"], tr["cycle"])
    assert docs.max() + questions.max() + answers.max() <= engine["max_len"]
    own = -(-(questions.max() + answers.max() + engine["prefill_chunk"])
            // 16)
    held = docs.sum() // 16 + 8 + 16 * own
    assert held == 18024 and engine["num_pages"] - held == 3480
    # every decode step and chunk attends over 24k-42k cached positions
    assert docs.min() + questions.min() > 12 * 2048


def test_the_pool_never_evicts_a_documents_page():
    """The cell's admissions through the REAL allocator and prefix index
    (no model): the documents primed, then the plan in its order over 16
    slots, one chunk and one decode step a round. The order of documents
    and every length come from `shape_seed`, so they are the same in every
    run: over the 420 requests a run of 50 s reaches at most, finished
    questions' pages are evicted by the thousand and NO request reuses less
    than its whole document. (The same walk loses a document's tail pages
    from 19,000 pages down.)"""
    from accelerate_tpu.serving.cache import PagedAllocator
    from chipbench.harness import traffic
    from chipbench.harness.manifest import Cell

    cell = Cell(CELL)
    tr, eng = cell.traffic, cell.shape["engine"]
    chunk = eng["prefill_chunk"]
    plan, docs = traffic.serve_plan(tr, cell.config["vocab_size"], 7,
                                    tr["cycle"])
    alloc = PagedAllocator(eng["page_size"], eng["num_pages"],
                           pad_slack=chunk, prefix_cache=True)

    def admit(slot, prompt, max_new):
        slot.request = types.SimpleNamespace(
            prompt=prompt, prompt_len=len(prompt), max_new_tokens=max_new)
        slot.alloc = alloc.allocate(slot.request)
        return slot.alloc

    slots = [types.SimpleNamespace(index=i, alloc=None, request=None,
                                   prompt_done=0, tokens=0)
             for i in range(eng["num_slots"])]
    for doc in docs:                        # set-up: each document once
        s = slots[0]
        assert admit(s, np.concatenate([doc, np.arange(16)]), 4)
        s.prompt_done = s.request.prompt_len
        alloc.release(s, finished=True)
        s.request = None
    nxt, reused_less = 0, []
    while nxt < 420 or any(s.request is not None for s in slots):
        for s in slots:
            if s.request is None and nxt < 420:
                p = plan[nxt]
                if admit(s, p.prompt, p.max_new_tokens) is None:
                    s.request = None
                    break
                s.prompt_done, s.tokens = s.alloc.reused_len, 0
                if s.alloc.reused_len < len(docs[p.document]):
                    reused_less.append((nxt, p.document, s.alloc.reused_len))
                nxt += 1
        filling = [s for s in slots if s.request is not None
                   and s.prompt_done < s.request.prompt_len]
        if filling:                         # the round's one chunk
            s = filling[0]
            s.prompt_done = min(s.prompt_done + chunk, s.request.prompt_len)
        for s in slots:                     # the round's decode step
            if s.request is not None and s not in filling:
                s.tokens += 1
                if s.tokens >= s.request.max_new_tokens:
                    alloc.release(s, finished=True)
                    s.request = None
    assert alloc.evictions > 8000 and reused_less == []


def test_sparse_attention_costs_by_hand():
    """One slot under `topk` and one far over it; 16 index heads of 64, 32
    query heads over 4 KV heads of 128, bf16."""
    from chipbench.harness import sparse_attention_costs as costs

    assert costs.keys_selected(499, 2048) == 500
    assert costs.keys_selected(2047, 2048) == 2048
    assert costs.keys_selected(30000, 2048) == 2048
    # the indexer: a cached key is 2 x 16 x 64 operations and 64 x 2 bytes
    ops, byts = costs.indexer_score_cost([499, 30000], 16, 64)
    assert ops == 2048 * 30499 and byts == 128 * 30499
    # attention: 500 + 2048 keys; a key is 2 x 2 x 32 x 128 operations and
    # 2 x 4 x 128 x 2 bytes; a slot's q and out 2 x 32 x 128 x 2 bytes
    ops, byts = costs.sparse_attention_cost([499, 30000], 2048, 32, 4, 128)
    assert ops == 16384 * 2548 and byts == 2048 * 2548 + 2 * 16384
    # at 32k of context the sparse read is a sixth of the dense one
    dense = 32768 * 2048
    sparse = costs.indexer_score_cost([32768], 16, 64)[1] + (
        costs.sparse_attention_cost([32768], 2048, 32, 4, 128)[1])
    assert 7 < dense / sparse < 8.1


@pytest.fixture(scope="module")
def tiny_plain(keye_bench):
    return run_cell("tiny-keye-docqa", 2**31 + 11, WINDOW_S, False,
                    bench_dir=keye_bench, require_chip=False,
                    with_control=True)


def test_the_tiny_cell_is_correct_and_the_fp8_control_is_not(tiny_plain):
    assert tiny_plain["correct"] is True and tiny_plain["failed"] == 0
    assert tiny_plain["attempted"] > 0
    assert tiny_plain["control_correct"] is False
    assert set(tiny_plain["metrics"]) == {
        "setup_s", "serve_out_tokens_per_s", "itl_p95_ms"}
    assert tiny_plain["device"]["platform"] == "cpu"


@pytest.mark.parametrize("topk", [4096, 8], ids=["selection-off", "half"])
def test_an_engine_with_another_selection_is_not_correct(keye_bench, topk):
    """The engine serving with selection OFF (every key attended), or with
    half of `topk`, under the cell's reference and limits."""

    def other_selection(engine):
        sa = dict(engine.config.indexer, topk=topk)
        engine.config = dataclasses.replace(engine.config, sa_config=sa)
        engine._build_programs()

    out = run_cell("tiny-keye-docqa", 2**31 + 11, WINDOW_S, False,
                   bench_dir=keye_bench, require_chip=False,
                   break_engine=other_selection)
    assert out["attempted"] > 0 and out["failed"] == 0
    assert out["correct"] is False


NEW_READERS = ["kernel.sparse_paged_attention_roofline",
               "step.sparse_select_decode_device_ms"]


@pytest.mark.parametrize("metric", NEW_READERS)
@pytest.mark.parametrize("case", ["no-trace", "qwen-cell", "mellum-cell",
                                  "empty-trace"])
def test_a_new_reader_returns_nothing_where_there_is_nothing_to_read(
        metric, case):
    """No trace; a cell of another family (whose cell file names none of
    the new kernels, as the parent's program has none); a trace that holds
    no operation."""
    from chipbench.harness.manifest import Cell
    from chipbench.harness.trace_reduce import TraceSummary

    cell = Cell({"qwen-cell": "serve-qwen2-docqa-closed",
                 "mellum-cell": "serve-mellum2-code-mixed-closed"}.get(
                     case, CELL))
    trace = None if case == "no-trace" else TraceSummary(
        {"devices": {"/device:TPU:0": {"ops": [], "modules": []}},
         "host": []}, 4.0)
    run = types.SimpleNamespace(
        cell=cell, trace=trace, samples={"decode_lengths": [[5, 7]]},
        peaks={"bf16_flops": 197e12, "hbm_bytes_per_s": 819e9},
        counters={}, window_s=4.0)
    assert cell.layer_reader(metric).read(run) is None


def test_the_new_readers_on_a_hand_made_trace():
    """Two decode calls of 30 ms on a made-up device: each holds 6 layers
    of an indexer-score kernel of 0.9 ms, a selection loop of 0.4 ms, glue
    with no name and a sparse attention kernel of 1.5 ms that starts 2 ms
    after the score kernel did: a layer's span is 2 ms to the attention
    kernel's start and 3.5 ms to its end, whatever ran between. A third
    call is cut by the trace's edge after a score kernel: left out. One
    more of each kernel lies OUTSIDE any call, and a chunk's loops lie in
    `jit_prefill`: not counted."""
    from chipbench.harness.manifest import Cell
    from chipbench.harness.trace_reduce import TraceSummary

    ms = 1e6
    ops, modules = [], []
    for call in range(2):
        t0 = call * 40 * ms
        modules.append(["jit_decode(1)", t0, 30 * ms])
        for i in range(6):
            at = t0 + 4 * i * ms
            ops.append([f"%indexer_paged_scores.{i} custom-call"
                        "[tpu_custom_call]", at, 0.9 * ms])
            ops.append([f"%while.{i} while", at + 1 * ms, 0.4 * ms])
            ops.append([f"%sort.{i} sort", at + 1.5 * ms, 0.2 * ms])
            ops.append([f"%sparse_paged_decode_attention.{i} custom-call"
                        "[tpu_custom_call]", at + 2 * ms, 1.5 * ms])
        ops.append(["%while.77 while", t0 + 25 * ms, 0.1 * ms])
    modules.append(["jit_decode(1)", 80 * ms, 1 * ms])
    ops.append(["%indexer_paged_scores.0 custom-call[tpu_custom_call]",
                80 * ms, 0.9 * ms])
    modules.append(["jit_prefill(2)", 100 * ms, 30 * ms])
    ops.append(["%while.5 while", 101 * ms, 9 * ms])
    for name in ("%indexer_paged_scores.99 custom-call[tpu_custom_call]",
                 "%while.99 while",
                 "%sparse_paged_decode_attention.99 custom-call"
                 "[tpu_custom_call]"):
        ops.append([name, 200 * ms, 7 * ms])
    cell = Cell(CELL)
    peaks = {"bf16_flops": 197e12, "hbm_bytes_per_s": 819e9}
    run = types.SimpleNamespace(
        cell=cell, peaks=peaks, counters={}, window_s=0.3,
        samples={"decode_lengths": [[30000] * 16, [30000] * 16]},
        trace=TraceSummary({"devices": {"/device:TPU:0": {
            "ops": ops, "modules": modules}}, "host": []}, 0.3))
    read = lambda name: cell.layer_reader(name).read(run)  # noqa: E731
    assert read("step.sparse_select_decode_device_ms") == pytest.approx(
        6 * 2.0)
    # a decode call's least time, 6 layers x 16 slots, both pieces
    # memory-bound: 30000 index keys of 128 B (the products are 2048
    # operations a key: 0.3 us against 4.7 us), then 2048 selected keys of
    # 2048 B and q and out; over 2 calls x 6 layers x 3.5 ms
    index = 16 * 30000 * 128
    attend = 16 * (2048 * 2048 + 16384)
    assert read("kernel.sparse_paged_attention_roofline") == pytest.approx(
        100 * 2 * 6 * (index + attend) / 819e9 / (2 * 6 * 3.5e-3))
