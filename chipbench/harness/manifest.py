"""Find a cell and everything it names, by name, in files.

`BENCHMARK.json` (root of the checkout) lists configurations, cells and
metrics. What belongs to ONE of them sits in a file of its own under
`chipbench/`, found by the name in the manifest:

    cells/<cell>.json            engine or trainer shape, check limits
    configs/<config>.json        the model configuration as it is run
    references/<name>.py         the configuration's plain reference
    references/lower_precision.py  the controls' rounding, outside it
    traffic/<traffic>.json       parameters of one traffic mix
    layer_metrics/<metric>.py    `read(run)` for one per-layer metric
    harness/runners/<kind>.py    `run(ctx)` for one kind of traffic

Nothing here, or in the runners, knows a cell, a configuration or a
metric by name: a later PR adds files and manifest entries only.
"""

from __future__ import annotations

import importlib.util
import json
import os

BENCH_DIR = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _read_json(path: str) -> dict:
    with open(path) as f:
        return json.load(f)


def _import_file(path: str, name: str):
    spec = importlib.util.spec_from_file_location(name, path)
    if spec is None or spec.loader is None:
        raise FileNotFoundError(path)
    module = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(module)
    return module


class Cell:
    """One entry of `workloads` with its files loaded."""

    def __init__(self, name: str, bench_dir: str = BENCH_DIR):
        self.bench_dir = bench_dir
        self.root = os.path.dirname(bench_dir)
        self.manifest = _read_json(os.path.join(self.root, "BENCHMARK.json"))
        entries = {w["name"]: w for w in self.manifest["workloads"]}
        if name not in entries:
            raise KeyError(f"no cell {name!r} in BENCHMARK.json; cells: "
                           f"{sorted(entries)}")
        self.name = name
        self.entry = entries[name]
        self.chips = int(self.entry["chips"])
        self.shape = _read_json(
            os.path.join(bench_dir, "cells", f"{name}.json"))
        configs = {c["name"]: c for c in self.manifest["configs"]}
        self.config_entry = configs[self.entry["config"]]
        self.config = _read_json(
            os.path.join(self.root, self.config_entry["file"]))
        self.traffic = _read_json(os.path.join(
            bench_dir, "traffic", f"{self.entry['traffic']}.json"))
        self.kind = self.traffic["kind"]

    # -- which metrics this cell reports -----------------------------------

    def _mine(self, entry: dict, end_to_end: set | None = None) -> bool:
        if "workloads" in entry:
            return self.name in entry["workloads"]
        return end_to_end is None or entry["moves"] in end_to_end

    def end_to_end(self) -> list[dict]:
        return [m for m in self.manifest["end_to_end"] if self._mine(m)]

    def per_layer(self) -> list[dict]:
        names = {m["name"] for m in self.end_to_end()}
        return [m for m in self.manifest["per_layer"]
                if self._mine(m, names)]

    # -- code found by name ---------------------------------------------------

    def _reference_path(self) -> str:
        return os.path.join(self.bench_dir, "references",
                            f"{self.config['reference']}.py")

    def reference(self):
        name = self.config["reference"]
        return _import_file(self._reference_path(),
                            f"chipbench_reference_{name.replace('-', '_')}")

    def control(self):
        """The reference once more, its matrix products in the precision
        below the configuration's: what a control puts in the program's
        place. No benchmark run loads it."""
        lower = _import_file(
            os.path.join(self.bench_dir, "references", "lower_precision.py"),
            "chipbench_lower_precision")
        return lower.rounded_copy(self._reference_path())

    def runner(self):
        return _import_file(
            os.path.join(self.bench_dir, "harness", "runners",
                         f"{self.kind}.py"),
            f"chipbench_runner_{self.kind}")

    def layer_reader(self, metric: str):
        path = os.path.join(self.bench_dir, "layer_metrics", f"{metric}.py")
        return _import_file(
            path, "chipbench_metric_" + metric.replace(".", "_").replace(
                "-", "_"))

    def work_dir(self) -> str:
        """Scratch inside the checkout (git-ignored), one per cell."""
        path = os.path.join(self.bench_dir, ".work", self.name)
        os.makedirs(path, exist_ok=True)
        return path

    def program_config(self):
        """The program's own configuration object, built from the file's
        `program` group: its module, its class, the keys copied by name
        from the configuration and the extra keyword arguments."""
        import importlib

        spec = self.config["program"]
        module = importlib.import_module(spec["family"])
        kwargs = {k: self.config[k] for k in spec["copy"]}
        kwargs.update(spec.get("extra", {}))
        kwargs.update(self.shape.get("program_config_extra", {}))
        return module, getattr(module, spec["config_class"])(**kwargs)
