"""Shared by the chipbench tests: a throw-away copy of the benchmark cut
to a size a CPU test can hold. Nothing here describes a TPU topology or
loads libtpu; every test runs on the CPU backend `tests/conftest.py` pins.
"""

import contextlib
import json
import os
import shutil
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

from chipbench import manifest  # noqa: E402

CPU_DEVICE = {"platform": "cpu", "kind": "cpu", "count": 1}
CONFIGS = ("knn-elearn", "knn-elearn-ccw")
#: a cell of each configuration (in the small copy every mix is cut alike)
CELL_OF = {"knn-elearn": "knn-elearn.bulk",
           "knn-elearn-ccw": "knn-elearn-ccw.adhoc"}


def small_copy(tmp: str, train_rows: int = 2048, test_rows: int = 256,
               files: int = 2, sample_rows: int = 256) -> manifest.Manifest:
    """A copy of BENCHMARK.json and chipbench/ under `tmp`, with every
    configuration and traffic file cut down; returns its manifest."""
    bench = os.path.join(tmp, "chipbench")
    shutil.copytree(os.path.join(ROOT, "chipbench"), bench,
                    ignore=shutil.ignore_patterns("__pycache__"))
    shutil.copy(os.path.join(ROOT, "BENCHMARK.json"), tmp)
    for name in os.listdir(os.path.join(bench, "configs")):
        path = os.path.join(bench, "configs", name)
        with open(path) as fh:
            doc = json.load(fh)
        doc["train_rows"] = train_rows
        doc["check"]["sample_rows"] = sample_rows
        with open(path, "w") as fh:
            json.dump(doc, fh)
    for name in os.listdir(os.path.join(bench, "traffic")):
        path = os.path.join(bench, "traffic", name)
        with open(path) as fh:
            doc = json.load(fh)
        doc.update(rows_per_file=test_rows, files_per_seed=files)
        with open(path, "w") as fh:
            json.dump(doc, fh)
    return manifest.Manifest(tmp, bench)


def file_stamps(folder: str) -> dict:
    """mtime and size of every file under `folder`: what a test that adds
    files holds the files that were there to."""
    out = {}
    for sub, _dirs, files in os.walk(folder):
        for f in files:
            p = os.path.join(sub, f)
            out[p] = os.path.getmtime(p), os.path.getsize(p)
    return out


@contextlib.contextmanager
def compile_cache_off():
    """The persistent compile cache off while jobs run in a test, and the
    worker's cache directory put back afterwards (the jobs' own device rule
    places it): what this worker would otherwise write into the checkout's
    `.jax_cache`, now or in a later test file, is what
    `tests/test_devices.py` watches for in another worker."""
    import jax
    from jax.experimental.compilation_cache import compilation_cache

    was = (jax.config.jax_enable_compilation_cache,
           jax.config.jax_compilation_cache_dir)
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    try:
        yield
    finally:
        jax.config.update("jax_enable_compilation_cache", was[0])
        jax.config.update("jax_compilation_cache_dir", was[1])
        compilation_cache.reset_cache()
