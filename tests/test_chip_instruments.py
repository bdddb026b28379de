"""The chip-side instruments beside the benchmark
(tools/tpu_kernel_check.py, __graft_entry__.py): a named device, and a
non-zero exit without a chip, never a skip, a default or an error object
printed with exit 0. The benchmark's own refusals are held by
tests/chipbench/, chip_smoke.py's by tests/test_devices.py."""

import os
import sys

import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, REPO)
sys.path.insert(0, os.path.join(REPO, "tools"))


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
