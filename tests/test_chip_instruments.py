"""The chip-side instruments (bench.py, tools/tpu_kernel_check.py,
__graft_entry__.py) after the measurement bank: one process, a named
device, and a non-zero exit without a chip — never a skip, a default or an
error object printed with exit 0."""

import os
import sys

import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, REPO)
sys.path.insert(0, os.path.join(REPO, "tools"))

import bench  # noqa: E402


def test_bench_refuses_the_cpu(capsys):
    with pytest.raises(SystemExit) as exc:
        bench.main(["sanity"])
    assert exc.value.code not in (0, None)
    assert "accelerator" in str(exc.value.code)
    assert capsys.readouterr().out == ""        # no JSON line, no error object


def test_bench_rejects_an_unknown_section():
    with pytest.raises(SystemExit) as exc:
        bench.main(["no_such_section"])
    assert "unknown section" in str(exc.value.code)


def test_bench_has_no_default_peak_for_an_unknown_device():
    results = {"sanity": {"values": {"device_kind": "TPU v99",
                                     "platform": "tpu"}, "s": 0.1}}
    with pytest.raises(RuntimeError, match="no peak FLOP/s.*TPU v99"):
        bench._assemble(results)


def test_bench_assembles_a_partial_run_with_its_device():
    results = {"sanity": {"values": {"device_kind": "TPU v5 lite",
                                     "platform": "tpu"}, "s": 0.1},
               "anchor": {"values": {"nb_node_rps": 1e6,
                                     "pair_node_pps": 1e8}, "s": 0.2}}
    line = bench._json_safe(bench._assemble(results))
    assert line["device"] == {"platform": "tpu", "kind": "TPU v5 lite"}
    assert line["nb_rows_per_sec"] is None       # section not run: null
    assert line["section_seconds"] == {"sanity": 0.1, "anchor": 0.2}
    assert not hasattr(bench, "drain") and not hasattr(bench, "BANK_PATH")


def test_bench_sections_run_in_this_process():
    """No section starts a process: a child could not have the chip the
    bench's own process holds."""
    with open(os.path.join(REPO, "bench.py")) as fh:
        src = fh.read()
    assert "subprocess" not in src


def test_kernel_check_without_a_tpu_is_an_error_not_a_skip(capsys):
    import tpu_kernel_check

    with pytest.raises(SystemExit) as exc:
        tpu_kernel_check.main()
    assert "compiles for a TPU" in str(exc.value.code)
    assert "skipped" not in capsys.readouterr().out


def test_dryrun_body_needs_its_cpu_devices():
    import __graft_entry__ as graft

    with pytest.raises(RuntimeError, match="need 64 CPU devices"):
        graft._dryrun_multichip_impl(64)


def test_bench_scaling_pins_the_cpu_for_itself_and_its_children():
    """bench_scaling is a CPU instrument: its parent says so in the
    environment its fleets, shard workers and solo children inherit."""
    import subprocess

    code = (
        "import os, sys\n"
        "os.environ.pop('JAX_PLATFORMS', None)\n"
        "import bench_scaling\n"
        "import avenir_tpu.parallel.scaling as sc\n"
        "def stop(devices, **kw):\n"
        "    import jax\n"
        "    print(os.environ['JAX_PLATFORMS'], len(devices),\n"
        "          devices[0].platform, jax.default_backend())\n"
        "    sys.exit(0)\n"
        "sc.measure_scaling = stop\n"
        "bench_scaling.main(4, quick=True)\n")
    proc = subprocess.run([sys.executable, "-c", code], cwd=REPO,
                          capture_output=True, text=True, timeout=240)
    assert proc.returncode == 0, proc.stderr[-1500:]
    assert proc.stdout.split() == ["cpu", "4", "cpu", "cpu"]
