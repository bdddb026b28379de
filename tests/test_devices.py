"""The device policy (avenir_tpu.utils.devices): one rule at every process
entry, a compile cache placed from outside, a native parser that is never
loaded from another build, launchers that state their children's platform.
"""

import json
import os
import shutil
import subprocess
import sys

import pytest

import avenir_tpu.utils.devices as devices
from avenir_tpu.data import churn_schema, generate_churn

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _churn(tmp_path, rows=300):
    schema = str(tmp_path / "churn.json")
    churn_schema().save(schema)
    csv = str(tmp_path / "churn.csv")
    with open(csv, "w") as fh:
        fh.write(generate_churn(rows, seed=3, as_csv=True))
    conf = str(tmp_path / "nb.properties")
    with open(conf, "w") as fh:
        fh.write(f"bad.feature.schema.file.path={schema}\n")
    return schema, csv, conf


# ---------------------------------------------------------- the device rule
def test_explicit_cpu_passes_without_a_probe_subprocess(monkeypatch):
    def no_subprocess(*a, **k):
        raise AssertionError("the device rule must not start a process")

    monkeypatch.setattr(subprocess, "Popen", no_subprocess)
    # conftest pinned the platform to cpu: that is having asked for it
    assert devices.requested_platform() == "cpu"
    assert devices.require_backend() == "cpu"
    assert not hasattr(devices, "subprocess")


def test_accelerator_expected_and_cpu_found_is_an_error(monkeypatch):
    monkeypatch.setattr(devices, "requested_platform", lambda: "")
    with pytest.raises(devices.DeviceUnavailable, match="JAX_PLATFORMS=cpu"):
        devices.require_backend()


def test_cli_without_accelerator_exits_nonzero(tmp_path):
    """JAX_PLATFORMS unset and an accelerator runtime that cannot start:
    JAX logs the failure and carries on with the CPU; the CLI must not."""
    _schema, csv, conf = _churn(tmp_path)
    env = {k: v for k, v in os.environ.items() if k != "JAX_PLATFORMS"}
    env.update(TPU_LIBRARY_PATH="/nonexistent/libtpu.so",
               PYTHONPATH=REPO + os.pathsep + env.get("PYTHONPATH", ""))
    out = str(tmp_path / "model.csv")
    proc = subprocess.run(
        [sys.executable, "-m", "avenir_tpu", "bayesianDistr", "--conf",
         conf, csv, out],
        capture_output=True, text=True, timeout=240, env=env, cwd=REPO)
    assert proc.returncode != 0
    assert "no accelerator" in proc.stderr
    assert "running on CPU" not in proc.stderr
    assert not os.path.exists(out)


def test_accelerator_requires_the_native_parser(monkeypatch):
    import jax
    import avenir_tpu.native.ingest as ingest

    monkeypatch.setattr(devices, "requested_platform", lambda: "tpu")
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    monkeypatch.setattr(ingest, "_lib", None)
    monkeypatch.setattr(ingest, "_build_error", "g++ exited 1: boom")
    with pytest.raises(RuntimeError, match="native CSV parser.*boom"):
        devices.require_backend()


# -------------------------------------------------------- cache placement
def _record_updates(monkeypatch):
    import jax

    updates = []
    monkeypatch.setattr(jax.config, "update",
                        lambda key, val: updates.append((key, val)))
    return updates


def test_cache_env_set_means_nothing_set_in_code(monkeypatch, tmp_path):
    updates = _record_updates(monkeypatch)
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(tmp_path))
    assert devices.place_compile_cache() == str(tmp_path)
    assert updates == []


def test_cache_env_unset_means_checkout_jax_cache(monkeypatch):
    updates = _record_updates(monkeypatch)
    monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
    want = os.path.join(REPO, ".jax_cache")
    assert devices.place_compile_cache() == want
    assert updates == [("jax_compilation_cache_dir", want)]


def test_one_cache_site_in_the_package():
    hits = []
    for dirpath, _dirs, files in os.walk(os.path.join(REPO, "avenir_tpu")):
        for name in files:
            if name.endswith(".py"):
                path = os.path.join(dirpath, name)
                with open(path) as fh:
                    n = fh.read().count("jax_compilation_cache_dir")
                if n:
                    hits.append((os.path.relpath(path, REPO), n))
    assert hits == [(os.path.join("avenir_tpu", "utils", "devices.py"), 1)]


# ------------------------------------------------- native-build provenance
needs_gxx = pytest.mark.skipif(
    shutil.which("g++") is None,
    reason="g++ unavailable; native ingest not built")


@pytest.fixture
def native_copy(tmp_path, monkeypatch):
    """The ingest module pointed at a private copy of its source."""
    import avenir_tpu.native.ingest as ingest

    src = str(tmp_path / "csv_ingest.cpp")
    shutil.copy(ingest._SRC, src)
    monkeypatch.setattr(ingest, "_DIR", str(tmp_path))
    monkeypatch.setattr(ingest, "_SRC", src)
    monkeypatch.setattr(ingest, "_lib", None)
    monkeypatch.setattr(ingest, "_build_error", None)
    return ingest


@needs_gxx
def test_stale_or_foreign_library_is_never_loaded(native_copy, tmp_path):
    ingest = native_copy
    # what the working tree used to carry: a library under the old fixed
    # name, built elsewhere — garbage here, so loading it would fail
    (tmp_path / "libcsv_ingest.so").write_bytes(b"not an ELF file")
    assert ingest.native_available()
    built = ingest._lib_path()
    assert os.path.exists(built) and built != str(
        tmp_path / "libcsv_ingest.so")

    # other source -> other name (stale); other CPU -> other name (foreign)
    with open(ingest._SRC, "a") as fh:
        fh.write("\n// edited\n")
    edited = ingest._lib_path()
    assert edited != built and not os.path.exists(edited)


@needs_gxx
def test_foreign_cpu_changes_the_library_name(native_copy, monkeypatch):
    ingest = native_copy
    here = ingest._lib_path()
    monkeypatch.setattr(ingest, "_host_cpu", lambda: "flags : another cpu")
    assert ingest._lib_path() != here


@needs_gxx
def test_failed_build_raises_on_the_accelerator_path(native_copy):
    ingest = native_copy
    with open(ingest._SRC, "w") as fh:
        fh.write("this is not C++\n")
    assert not ingest.native_available()        # the CPU path degrades
    with pytest.raises(RuntimeError, match="g\\+\\+ exited"):
        ingest.require_native()                 # the accelerator path stops


# ------------------------------------------ launchers' platform statement
def test_children_platform_is_stated_when_cpu_was_asked_for():
    env = devices.cpu_children_env({"JAX_PLATFORMS": "cpu"}, "--shard")
    assert env["JAX_PLATFORMS"] == "cpu"
    # nothing in the environment, but this process was pinned to the cpu:
    # the launcher writes the platform down instead of leaving it implied
    assert devices.cpu_children_env({}, "--shard") == {"JAX_PLATFORMS": "cpu"}


def test_children_on_an_accelerator_are_refused(monkeypatch):
    with pytest.raises(devices.DeviceUnavailable, match="fleet --hosts"):
        devices.cpu_children_env({"JAX_PLATFORMS": "tpu"}, "fleet --hosts")
    monkeypatch.setattr(devices, "requested_platform", lambda: "")
    with pytest.raises(devices.DeviceUnavailable, match="--shard"):
        devices.cpu_children_env({}, "--shard")


def _no_popen(monkeypatch):
    def refuse(*a, **k):
        raise AssertionError("a child was started")

    monkeypatch.setattr(subprocess, "Popen", refuse)


def test_shard_refuses_before_it_plans_or_spawns(tmp_path, monkeypatch):
    from avenir_tpu.dist import run_sharded

    _schema, csv, conf = _churn(tmp_path)
    monkeypatch.delenv("JAX_PLATFORMS", raising=False)
    monkeypatch.setattr(devices, "requested_platform", lambda: "")
    _no_popen(monkeypatch)
    root = str(tmp_path / "shard")
    with pytest.raises(devices.DeviceUnavailable, match="--shard"):
        run_sharded("bayesianDistr", conf, [csv], str(tmp_path / "out"),
                    procs=2, shard_root=root)
    assert not os.path.exists(os.path.join(root, "plan.json"))


def test_fleet_refuses_before_it_spawns(tmp_path, monkeypatch):
    from avenir_tpu.net.fleet import Fleet

    _no_popen(monkeypatch)
    fleet = Fleet(str(tmp_path / "fleet"), hosts=2,
                  env={"JAX_PLATFORMS": "tpu"})
    with pytest.raises(devices.DeviceUnavailable, match="fleet --hosts"):
        fleet.start()


# ----------------------------------------------------------- chip_smoke.py
def test_chip_smoke_dry_run_cpu_end_to_end(tmp_path):
    """The whole smoke at toy sizes: every phase, the served artefacts
    byte-identical, the placed compile cache found by a fresh process —
    and a summary that cannot be read as a pass on the chip."""
    cache = tmp_path / "cache"
    env = dict(os.environ, JAX_COMPILATION_CACHE_DIR=str(cache))
    proc = subprocess.run(
        [sys.executable, os.path.join(REPO, "chip_smoke.py"),
         "--dry-run-cpu"],
        capture_output=True, text=True, timeout=600, env=env, cwd=REPO)
    assert proc.returncode == 0, proc.stdout[-1500:] + proc.stderr[-2500:]
    lines = proc.stdout.strip().splitlines()
    # the last line is the verdict and nothing else; the summary precedes it
    verdict, summary = json.loads(lines[-1]), json.loads(lines[-2])
    assert list(verdict) == ["ok", "device"] and verdict["ok"] is False
    assert list(verdict["device"]) == ["platform", "kind", "count"]
    assert verdict["device"] == summary["device"]
    assert isinstance(verdict["device"]["count"], int)
    assert summary["chip"] is False and summary["ok"] is False
    assert summary["dry_run_ok"] is True
    assert summary["device"]["platform"] == "cpu"
    assert list(summary)[-1] == "claim" and summary["claim"] is None
    assert summary["compile_cache_dir"] == str(cache)
    phases = summary["phases"]
    assert list(phases) == ["device", "data", "nb", "knn_cold", "knn_warm",
                            "knn_variants", "kernels", "server"]
    for name, row in phases.items():
        if name != "data":
            assert row["platform"] == "cpu" and row["parser"] == "native"
            assert row["xla_compiles"] >= 0 and row["wall_s"] > 0
    assert phases["nb"]["counts_equal_host_oracle"] is True
    assert phases["nb"]["blocks"] > 1
    assert phases["kernels"]["interpreted"] is True
    assert phases["server"]["jobs_byte_identical"] == [
        "bayesianDistr", "nearestNeighbor"]
    # the cache: env set -> that directory fills, and no phase names
    # another (what <checkout>/.jax_cache holds is its neighbours' doing:
    # any test that has placed the default cache in its worker writes there)
    assert phases["knn_warm"]["compile_cache_hits"] > 0
    assert os.listdir(cache)
    assert [row["compile_cache_dir"] for row in phases.values()
            if "compile_cache_dir" in row] == [str(cache)]


def test_chip_smoke_without_a_chip_fails_and_prints_no_result(tmp_path):
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    proc = subprocess.run(
        [sys.executable, os.path.join(REPO, "chip_smoke.py")],
        capture_output=True, text=True, timeout=240, env=env, cwd=REPO)
    assert proc.returncode != 0
    assert "not on a TPU" in proc.stderr
    assert '"ok"' not in proc.stdout


# ------------------------------------------------- what a process reports
def test_pallas_available_does_not_swallow_a_backend_failure(monkeypatch):
    import jax
    from avenir_tpu.ops import pallas_knn

    def broken():
        raise RuntimeError("Unable to initialize backend 'tpu'")

    monkeypatch.setattr(jax, "default_backend", broken)
    with pytest.raises(RuntimeError, match="Unable to initialize"):
        pallas_knn.pallas_available()


def test_device_report_counts_this_process_compiles():
    import jax
    import jax.numpy as jnp

    devices.require_backend()
    before = devices.device_report()
    jax.jit(lambda x: x * 3 + 1)(jnp.arange(7)).block_until_ready()
    after = devices.device_report()
    assert after["xla_compiles"] > before["xla_compiles"]
    assert after["compile_s"] >= before["compile_s"]
    assert (after["platform"], after["device_kind"]) == ("cpu", "cpu")
    assert after["device_count"] == len(jax.devices())
    assert after["parser"] in ("native", "python")


def test_healthz_names_the_device(tmp_path):
    import urllib.request

    from avenir_tpu.net.listener import NetListener
    from avenir_tpu.server import JobServer

    server = JobServer(workers=1, state_root=str(tmp_path / "state"))
    server.start()
    try:
        with NetListener(server, port=0) as listener:
            with urllib.request.urlopen(
                    listener.address + "/healthz", timeout=30) as resp:
                health = json.loads(resp.read())
    finally:
        server.shutdown()
    assert health["status"] == "serving"
    assert health["device"]["platform"] == "cpu"
    assert set(health["device"]) >= {"device_kind", "device_count",
                                     "xla_compiles", "parser"}


def test_chip_smoke_parent_never_imports_jax():
    """A parent that has touched JAX holds the chip its children need."""
    code = ("import sys; sys.path.insert(0, %r); import chip_smoke; "
            "assert 'jax' not in sys.modules; "
            "assert 'avenir_tpu' not in sys.modules" % REPO)
    proc = subprocess.run([sys.executable, "-c", code], capture_output=True,
                          text=True, timeout=60)
    assert proc.returncode == 0, proc.stderr[-800:]


def test_pallas_kernel_cache_key_does_not_depend_on_the_caller():
    """A kernel's Mosaic payload is part of its compile-cache key. With
    JAX's default (full tracebacks in locations) it carries the frames of
    whoever called, so the CLI and the server compiled the same kernel
    twice on the chip; the entry rule keeps the innermost frame only."""
    import base64
    import functools
    import hashlib
    import re

    import jax
    import jax.numpy as jnp
    from jax import export

    from avenir_tpu.ops import pallas_knn

    devices.require_backend()

    def payload():
        pallas_knn.knn_topk_pallas.clear_cache()      # trace again
        fn = functools.partial(pallas_knn.knn_topk_pallas, k=5, block_q=256,
                               block_t=512, metric="manhattan")
        text = export.export(jax.jit(fn), platforms=["tpu"])(
            jax.ShapeDtypeStruct((256, 6), jnp.float32),
            jax.ShapeDtypeStruct((1024, 6), jnp.float32)).mlir_module()
        body = re.search(r'\\22body\\22: \\22([A-Za-z0-9+/=]+)', text)
        return hashlib.sha256(base64.b64decode(body.group(1))).hexdigest()

    def from_deeper(n):
        return payload() if n == 0 else from_deeper(n - 1)

    assert payload() == from_deeper(3)
