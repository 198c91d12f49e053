"""`BENCHMARK.json` against its contract and against the files it names."""

import json
import os
import re

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.\-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.\-]{1,16}$")
WIDTHS = re.compile(r"(hidden_size|intermediate|latent|state_size|proj|"
                    r"_dim$|_rank$|expand|experts_per_tok|head_size)")


@pytest.fixture(scope="module")
def manifest():
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        return json.load(f)


def _cells(manifest):
    return {w["name"]: w for w in manifest["workloads"]}


def test_keys_are_exactly_the_contracts(manifest):
    assert set(manifest) == {"command", "paths", "run_seconds", "configs",
                             "workloads", "end_to_end", "per_layer"}
    assert manifest["command"] == ["python3", "chipbench/run.py"]
    assert 1 <= manifest["run_seconds"] <= 51
    assert os.path.getsize(os.path.join(ROOT, "BENCHMARK.json")) < 64 * 1024


def test_names_and_units(manifest):
    names = []
    for group in ("configs", "workloads", "end_to_end", "per_layer"):
        for entry in manifest[group]:
            assert NAME.match(entry["name"]), entry["name"]
            names.append((group in ("end_to_end", "per_layer"), entry["name"]))
    assert len(names) == len(set(names))
    for w in manifest["workloads"]:
        assert NAME.match(w["config"]) and NAME.match(w["traffic"])
        assert w["chips"] in (1, 4) and 1 <= len(w["why"]) <= 200
    for m in manifest["end_to_end"] + manifest["per_layer"]:
        assert UNIT.match(m["unit"]), m["unit"]
        assert m["better"] in ("lower", "higher")
        assert m["source"] in ("device_trace", "program_span",
                               "program_counter", "host_clock")


def test_entries_carry_only_the_contracts_keys(manifest):
    for m in manifest["end_to_end"]:
        assert set(m) - {"workloads"} == {"name", "unit", "better", "bound",
                                          "source"}
        assert m["source"] in ("host_clock", "device_trace")
        assert 0.01 <= m["bound"] <= 0.1
    for m in manifest["per_layer"]:
        assert set(m) - {"workloads"} == {"name", "unit", "better", "source",
                                          "layer", "moves"}
    for c in manifest["configs"]:
        assert set(c) == {"name", "source", "file", "reduced", "why"}
    for w in manifest["workloads"]:
        assert set(w) == {"name", "config", "traffic", "chips", "why"}


def test_setup_s_is_an_end_to_end_metric_of_every_cell(manifest):
    setup = [m for m in manifest["end_to_end"] if m["name"] == "setup_s"]
    assert len(setup) == 1 and "workloads" not in setup[0]
    assert setup[0]["bound"] <= 0.1


def test_every_cell_reports_a_second_end_to_end_and_a_layer_metric(manifest):
    from chipbench.harness.manifest import Cell

    for name in _cells(manifest):
        cell = Cell(name)
        assert len(cell.end_to_end()) >= 2, name
        assert len(cell.per_layer()) >= 1, name


def test_every_layer_metric_moves_a_metric_its_cells_report(manifest):
    e2e = {m["name"]: m for m in manifest["end_to_end"]}
    cells = _cells(manifest)
    for m in manifest["per_layer"]:
        target = e2e[m["moves"]]
        reporters = set(target.get("workloads", cells))
        for cell in m.get("workloads", reporters):
            assert cell in cells and cell in reporters, (m["name"], cell)


def test_roofline_metrics_are_named_and_united_as_such(manifest):
    for m in manifest["per_layer"]:
        if "roofline" in m["name"] or "flops_util" in m["name"]:
            assert m["unit"] == "%"
        if "roofline" in m["name"]:
            assert m["name"].endswith("_roofline")


def test_every_metric_configuration_cell_and_traffic_has_its_file(manifest):
    bench = os.path.join(ROOT, "chipbench")
    for m in manifest["per_layer"]:
        assert os.path.isfile(os.path.join(bench, "layer_metrics",
                                           m["name"] + ".py")), m["name"]
    files = [c["file"] for c in manifest["configs"]]
    assert len(files) == len(set(files))
    used = {w["config"] for w in manifest["workloads"]}
    for c in manifest["configs"]:
        assert c["name"] in used
        assert c["file"].startswith("chipbench/")
        assert os.path.isfile(os.path.join(ROOT, c["file"]))
    for w in manifest["workloads"]:
        assert os.path.isfile(os.path.join(bench, "cells", w["name"] + ".json"))
        traffic = os.path.join(bench, "traffic", w["traffic"] + ".json")
        with open(traffic) as f:
            kind = json.load(f)["kind"]
        assert os.path.isfile(os.path.join(bench, "harness", "runners",
                                           kind + ".py"))


def test_four_chip_cells_stay_within_a_quarter(manifest):
    four = [w for w in manifest["workloads"] if w["chips"] == 4]
    assert len(four) <= max(1, len(manifest["workloads"]) // 4)


@pytest.mark.parametrize("name", ["qwen2-1.5b", "qwen2-1.5b-d8"])
def test_configurations_keep_the_published_widths(manifest, name):
    entry = {c["name"]: c for c in manifest["configs"]}.get(name)
    if entry is None:
        pytest.skip(f"{name} is not in the manifest")
    with open(os.path.join(ROOT, entry["file"])) as f:
        cfg = json.load(f)
    published = dict(hidden_size=1536, intermediate_size=8960,
                     num_attention_heads=12, num_key_value_heads=2,
                     vocab_size=151936, rope_theta=1000000.0,
                     rms_norm_eps=1e-06, tie_word_embeddings=True,
                     hidden_act="silu", num_hidden_layers=28)
    changed = {k for k, v in published.items() if cfg[k] != v}
    assert changed <= set(entry["reduced"])
    assert not any(WIDTHS.search(k) for k in entry["reduced"])
    assert sorted(cfg["reduced"]) == sorted(entry["reduced"])
    for key in ("source", "assumed", "stands_for", "reference", "program"):
        assert key in cfg
    assert entry["source"] == cfg["source"]


def test_the_harness_names_no_cell_configuration_or_metric(manifest):
    """Driven by data: no harness file may mention one by name."""
    names = ([w["name"] for w in manifest["workloads"]]
             + [c["name"] for c in manifest["configs"]]
             + [m["name"] for m in manifest["per_layer"]]
             + [w["traffic"] for w in manifest["workloads"]])
    harness = os.path.join(ROOT, "chipbench", "harness")
    sources = [os.path.join(ROOT, "chipbench", "run.py")]
    for base, _, files in os.walk(harness):
        sources += [os.path.join(base, f) for f in files if f.endswith(".py")]
    for path in sources:
        with open(path) as f:
            text = f.read()
        for name in names:
            assert name not in text, (path, name)
