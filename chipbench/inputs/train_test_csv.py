"""The inputs of a job that reads a train file and a test file, such as
`nearestNeighbor`: what a configuration gets that names no input module.
Found by `inputs_kind` in the configuration's file.

Everything one run feeds the program is made from the seed: the train
file by the configuration's generator (`generator.kind`) through the
general writer `generate.make_csv`, the mix's test files by the same
(`files_per_seed`, `rows_per_file`), the schema and the properties. A job
of the window reads the train file and the next of the test files and
writes a line for each test row.
"""

from __future__ import annotations

import json
import os
import shutil
from typing import List

from chipbench import generate


class Inputs:
    """Everything one run feeds the program, made from the seed: the train
    file by the configuration's generator, the mix's test files by the
    same, the schema and the properties."""

    out_suffix = ".csv"

    def __init__(self, cell, seed: int, work: str):
        cfg, mix = cell.config, cell.traffic
        gen = cfg["generator"]
        fields = generate.feature_fields(cfg["schema"])
        self.classes = list(gen["classes"])
        self.prefix = gen["id_prefix"]
        self.work = work
        self.train_path = os.path.join(work, "train.csv")
        self.train = generate.make_csv(
            self.train_path, seed, 0, int(cfg["train_rows"]), gen, fields, 0,
            cell.bench_dir)
        self.tests: List[generate.Rows] = []
        self.test_paths: List[str] = []
        for j, rows in enumerate(generate.file_rows(mix)):
            path = os.path.join(work, f"test_{j:02d}.csv")
            self.tests.append(generate.make_csv(
                path, seed, 1 + j, rows, gen, fields,
                int(gen["test_id_start"]) + j * int(gen["test_id_stride"]),
                cell.bench_dir))
            self.test_paths.append(path)
        self.n_files = len(self.test_paths)
        self.schema_path = os.path.join(work, "schema.json")
        with open(self.schema_path, "w") as fh:
            json.dump(cfg["schema"], fh)
        self.props_path = os.path.join(work, "job.properties")
        with open(self.props_path, "w") as fh:
            for key, val in cfg["properties"].items():
                fh.write(f"{key}={val.format(schema=self.schema_path)}\n")
        self.job = cfg["job"]
        self.input_slots = list(cfg["inputs"])

    def argv(self, file_no: int, out: str) -> List[str]:
        return self._argv(self.test_paths[file_no], out)

    def warmup_argv(self, out: str) -> List[str]:
        """The warm-up job's arguments: test file 0 under another name, so
        that what the program caches beside a test file (its columnar
        sidecar) does not make the window's first job differ from the
        rest, while the two outputs stay comparable byte for byte."""
        twin = os.path.join(self.work, "test_warmup.csv")
        shutil.copyfile(self.test_paths[0], twin)
        return self._argv(twin, out)

    def _argv(self, test_path: str, out: str) -> List[str]:
        paths = [slot.format(train=self.train_path, test=test_path)
                 for slot in self.input_slots]
        return [self.job, "--conf", self.props_path, *paths, out]
