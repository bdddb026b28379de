"""The benchmark's manifest: `BENCHMARK.json` and the data files it names.

Whatever belongs to one configuration, one traffic mix or one per-layer
metric is a file of its own, found by the name in `BENCHMARK.json`:
`configs/<config>.json`, `traffic/<traffic>.json`, `metrics/<metric>.json`.
The code such a file calls for is found by the name it gives, too: the
input module a configuration names, `inputs/<inputs_kind>.py` (one that
names none gets `train_test_csv`); its generator, `generators/<kind>.py`;
its check, `checks/<kind>.py`; the loop a mix names, `loops/<loop>.py`;
the reader a metric names, `readers/<reader>.py`. Adding a cell, a mix, a
metric or a deployment of another job family is adding files and
entries; no file here is edited.
"""

from __future__ import annotations

import json
import os
from dataclasses import dataclass
from typing import Callable, Dict, List

from chipbench import generate

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(HERE)
DEFAULT_INPUTS = "train_test_csv"    # a train file and the mix's test files


def _load(path: str) -> Dict:
    with open(path) as fh:
        return json.load(fh)


@dataclass
class Cell:
    name: str
    chips: int
    config: Dict
    traffic: Dict
    end_to_end: List[Dict]          # BENCHMARK.json entries, this cell's
    per_layer: List[Dict]
    bench_dir: str = HERE           # where generators/, checks/, ... are


class Manifest:
    """`BENCHMARK.json` at `root`, with the benchmark's files under
    `bench_dir` (a test points both at a temporary copy)."""

    def __init__(self, root: str = ROOT, bench_dir: str = HERE):
        self.root, self.bench_dir = root, bench_dir
        self.doc = _load(os.path.join(root, "BENCHMARK.json"))

    def path(self, kind: str, name: str, ext: str = ".json") -> str:
        return os.path.join(self.bench_dir, kind, name + ext)

    def config_file(self, name: str) -> str:
        for c in self.doc["configs"]:
            if c["name"] == name:
                return os.path.join(self.root, c["file"])
        raise KeyError(f"BENCHMARK.json has no configuration {name!r}")

    def metric(self, name: str) -> Dict:
        return _load(self.path("metrics", name))

    def module(self, folder: str, name: str):
        """`<folder>/<name>.py` of the benchmark: `inputs/`, `generators/`,
        `loops/`, `checks/` and `readers/` are found this way."""
        return generate.load_module(self.bench_dir, folder, name)

    def inputs(self, config: Dict):
        """The module that makes what the configuration's job reads: its
        `Inputs(cell, seed, work)` writes every input file from the seed
        and gives `n_files`, `out_suffix`, `argv(file_no, out)` and
        `warmup_argv(out)`."""
        return self.module("inputs", config.get("inputs_kind", DEFAULT_INPUTS))

    def reader(self, name: str) -> Callable:
        """The `read(ctx, params)` of `readers/<name>.py`."""
        return self.module("readers", name).read

    def _applies(self, metric: Dict, cell: str, reported: List[str]) -> bool:
        if "workloads" in metric:
            return cell in metric["workloads"]
        return metric.get("moves") in reported or "moves" not in metric

    def cell(self, name: str) -> Cell:
        for w in self.doc["workloads"]:
            if w["name"] == name:
                break
        else:
            names = ", ".join(w["name"] for w in self.doc["workloads"])
            raise KeyError(f"no workload {name!r}; BENCHMARK.json has: {names}")
        e2e = [m for m in self.doc["end_to_end"]
               if name in m.get("workloads", [name])]
        reported = [m["name"] for m in e2e]
        layers = [m for m in self.doc["per_layer"]
                  if self._applies(m, name, reported)]
        return Cell(name, int(w["chips"]), _load(self.config_file(w["config"])),
                    _load(self.path("traffic", w["traffic"])), e2e, layers,
                    self.bench_dir)
