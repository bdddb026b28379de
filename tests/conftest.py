"""Test harness: run everything on a virtual 8-device CPU mesh.

The TPU analog of "multi-node without a real cluster" (SURVEY §4): tests
assert that mesh-sharded results equal single-device results on 8 virtual
CPU devices. Must configure the platform before any JAX backend init.
"""

import os

# 8 virtual CPU devices; must be in place before the CPU client is created.
_flag = "--xla_force_host_platform_device_count=8"
if _flag not in os.environ.get("XLA_FLAGS", ""):
    os.environ["XLA_FLAGS"] = (os.environ.get("XLA_FLAGS", "") + " " + _flag).strip()

import jax  # noqa: E402

jax.config.update("jax_platforms", "cpu")

import numpy as np  # noqa: E402
import pytest  # noqa: E402


@pytest.fixture(scope="session")
def mesh8():
    from avenir_tpu.parallel import data_mesh

    assert len(jax.devices()) == 8, "expected 8 virtual CPU devices"
    return data_mesh()


@pytest.fixture(scope="session")
def rng():
    return np.random.default_rng(42)


# --------------------------------------------------------------------------
# skip triage: the tier-1 gate tolerates SKIPS only for the frozen
# environment gates below (an unmounted /root/reference tree, a host
# without the native toolchain). Any OTHER skip reason is converted into
# a test FAILURE on the spot: a skip is a silent hole in the gate, so
# adding one is an explicit, reviewed decision — extend this allowlist
# in the same PR that adds the skip, with the environment gate named.
# --------------------------------------------------------------------------
_SKIP_REASON_ALLOWLIST = (
    "reference tree not mounted",           # tests/test_core.py,
                                            # test_reference_configs.py,
                                            # test_runner.py: /root/reference
    "reference checkout not present",       # tests/test_core.py: same tree
    "g++ unavailable; native ingest not built",   # test_native_ingest.py
    "native encoder unavailable",           # tests/test_bitset.py
        "no native lib",                        # test_native_ingest.py
    # test_stream_jobs.py, test_shared_scan.py: a jax whose jitted
    # functions have no `_cache_size()` (`jit_cache_size` returns -1)
    "this jax does not expose a jitted function's cache size",
)


@pytest.hookimpl(hookwrapper=True)
def pytest_runtest_makereport(item, call):
    outcome = yield
    report = outcome.get_result()
    if not report.skipped or getattr(report, "wasxfail", None):
        return
    longrepr = report.longrepr
    reason = longrepr[2] if isinstance(longrepr, tuple) else str(longrepr)
    if any(allowed in reason for allowed in _SKIP_REASON_ALLOWLIST):
        return
    report.outcome = "failed"
    report.longrepr = (
        f"UNEXPECTED SKIP: {reason!r} is not on the frozen skip-reason "
        f"allowlist (tests/conftest.py _SKIP_REASON_ALLOWLIST). Skips "
        f"are holes in the tier-1 gate: either make the test run, or "
        f"add the reason to the allowlist in the same change, naming "
        f"the environment gate that justifies it.")
