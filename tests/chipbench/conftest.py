"""Fixtures of the benchmark's CPU tests: a throw-away copy of the
benchmark with tiny cells ADDED AS FILES (no edit to a file that is
there), which is also the proof that the harness is driven by data."""

import json
import os
import shutil

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

TINY_CONFIG = dict(vocab_size=512, hidden_size=64, intermediate_size=128,
                   num_hidden_layers=2, num_attention_heads=4,
                   num_key_value_heads=2, max_position_embeddings=256)


def _load(path):
    with open(path) as f:
        return json.load(f)


def _dump(obj, path):
    with open(path, "w") as f:
        json.dump(obj, f, indent=1)


def add_tiny_cells(root: str) -> None:
    """What a later PR would do: new files, new manifest entries."""
    bench = os.path.join(root, "chipbench")
    cfg = _load(os.path.join(bench, "configs", "qwen2-1.5b-d8.json"))
    cfg.update(TINY_CONFIG)
    _dump(cfg, os.path.join(bench, "configs", "tiny.json"))

    tr = _load(os.path.join(bench, "traffic", "packed-2x2048.json"))
    tr.update(batch=2, seq_len=32, corpus_batches=64, warm_steps=5,
              log_loss_every=2)
    _dump(tr, os.path.join(bench, "traffic", "tiny-train.json"))
    cell = _load(os.path.join(bench, "cells", "train-qwen2-d8-seq2048.json"))
    # limits of the TINY cell, set as the real cell's are: above what sound
    # runs read on the CPU at this size (gradient probes 0.011-0.015) and
    # below the fp8 control (0.12); the others are held against the broken
    # step (a state handed back unchanged reads 1.0 on the last three)
    cell["check"]["limits"].update(loss_rel_gap=[1e-3, 1e-3, 1e-3],
                                   grad_norm_worst_leaf_gap=0.02,
                                   grad_probe_worst_leaf_gap=0.04,
                                   param_change_worst_leaf_gap=0.05,
                                   # a loaded box makes a dozen steps in
                                   # the tests' 1.5 s window, not hundreds
                                   loss_fall_at_least=0.02)
    _dump(cell, os.path.join(bench, "cells", "tiny-train.json"))

    tr = _load(os.path.join(bench, "traffic", "chat-unshared-poisson.json"))
    tr.update(rate_per_s=20.0, fill_seconds=1, drain_seconds=120,
              prompt_len={"dist": "lognormal", "median": 12, "sigma": 0.6,
                          "min": 3, "max": 40},
              output_len={"dist": "lognormal", "median": 6, "sigma": 0.5,
                          "min": 2, "max": 12})
    _dump(tr, os.path.join(bench, "traffic", "tiny-chat.json"))
    tr = _load(os.path.join(bench, "traffic", "docqa-16docs-closed.json"))
    tr.update(clients=2, cycle=64, fill_seconds=1,
              documents={"count": 3, "zipf_s": 1.0,
                         "len": {"dist": "uniform", "min": 24, "max": 40}},
              prompt_len={"dist": "uniform", "min": 3, "max": 8},
              output_len={"dist": "uniform", "min": 2, "max": 6})
    _dump(tr, os.path.join(bench, "traffic", "tiny-docqa.json"))
    cell = _load(os.path.join(bench, "cells", "serve-qwen2-chat-steady.json"))
    # on the CPU "auto" means the dense path: ask for the kernel (interpreted)
    cell["engine"].update(num_slots=3, max_len=64, prefill_chunk=8,
                          page_size=8, num_pages=64, paged_attention=True)
    cell["check"].update(sample_requests=12, max_output=12)
    # sound runs read 0 and 0.002 at this size, the fp8 control 0 and 0.03
    cell["check"]["limits"].update(served_token_gap_max=0.01,
                                   served_logprob_gap_max=0.01)
    _dump(cell, os.path.join(bench, "cells", "tiny-chat.json"))
    cell["engine"].update(num_slots=2)
    _dump(cell, os.path.join(bench, "cells", "tiny-docqa.json"))

    # one more per-layer metric, as a file of its own
    with open(os.path.join(bench, "layer_metrics", "tiny.steps_per_s.py"),
              "w") as f:
        f.write("def read(run):\n"
                "    steps = run.counters.get('steps')\n"
                "    return steps / run.window_s if steps else None\n")

    m = _load(os.path.join(root, "BENCHMARK.json"))
    m["configs"].append({"name": "tiny", "source": "tests", "reduced": [],
                         "file": "chipbench/configs/tiny.json",
                         "why": "CPU tests"})
    for name in ("tiny-train", "tiny-chat", "tiny-docqa"):
        m["workloads"].append({"name": name, "config": "tiny",
                               "traffic": name, "why": "test", "chips": 1})
    for metric in m["end_to_end"] + m["per_layer"]:
        if "workloads" in metric:
            kinds = {w.split("-")[0] for w in metric["workloads"]}
            metric["workloads"] += [
                n for n in ("tiny-train", "tiny-chat", "tiny-docqa")
                if ("train" in kinds) == n.startswith("tiny-train")
                and ("train" in kinds or "serve" in kinds)]
    m["per_layer"].append({"name": "tiny.steps_per_s", "unit": "1/s",
                           "better": "higher", "source": "host_clock",
                           "layer": "Training facade",
                           "moves": "train_tokens_per_s_per_chip",
                           "workloads": ["tiny-train"]})
    _dump(m, os.path.join(root, "BENCHMARK.json"))


@pytest.fixture(scope="session")
def tiny_bench(tmp_path_factory):
    """Path of `chipbench/` inside a temp copy that also holds tiny cells."""
    root = str(tmp_path_factory.mktemp("chipbench_copy"))
    shutil.copytree(os.path.join(ROOT, "chipbench"),
                    os.path.join(root, "chipbench"),
                    ignore=shutil.ignore_patterns(".work", "__pycache__"))
    shutil.copy(os.path.join(ROOT, "BENCHMARK.json"), root)
    add_tiny_cells(root)
    return os.path.join(root, "chipbench")
