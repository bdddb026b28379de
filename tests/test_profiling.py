"""trace / RunningStats / Histogram percentile utilities."""

import math

import numpy as np
import pytest

from avenir_tpu.utils.profiling import RunningStats, trace
from avenir_tpu.utils.sampling import Histogram


def test_trace_writes_profile(tmp_path):
    import jax
    import jax.numpy as jnp

    d = str(tmp_path / "trace")
    with trace(d):
        jax.block_until_ready(jnp.ones((128, 128)) @ jnp.ones((128, 128)))
    import os
    found = [f for _, _, fs in os.walk(d) for f in fs]
    assert found, "no trace files written"


def test_trace_records_span_with_device_trace_dir(tmp_path):
    """profiling.trace() feeds the avenir-trace recorder: the region
    shows up as one span whose attrs carry the device trace dir and
    whether the jax profiler actually started."""
    from avenir_tpu.obs import trace as obs_trace

    d = str(tmp_path / "trace")
    with obs_trace.capture() as rec:
        with trace(d):
            pass
    spans = [sp for sp in rec.spans() if sp.name == "jax.profiler.trace"]
    assert len(spans) == 1
    assert spans[0].attrs["log_dir"] == d
    assert spans[0].attrs["started"] in (True, False)


def test_running_stats_matches_numpy():
    rng = np.random.default_rng(0)
    x = rng.normal(3.0, 2.0, 1000)
    rs = RunningStats().add_array(x)
    assert rs.mean == pytest.approx(x.mean(), rel=1e-9)
    assert rs.std == pytest.approx(x.std(ddof=1), rel=1e-9)
    assert rs.min_val == x.min() and rs.max_val == x.max()


def test_running_stats_merge_is_exact():
    rng = np.random.default_rng(1)
    x = rng.normal(size=1000)
    whole = RunningStats().add_array(x)
    a = RunningStats().add_array(x[:300])
    b = RunningStats().add_array(x[300:])
    merged = a.merge(b)
    assert merged.count == whole.count
    assert merged.mean == pytest.approx(whole.mean, rel=1e-12)
    assert merged.variance == pytest.approx(whole.variance, rel=1e-9)


def test_running_stats_scalar_adds():
    rs = RunningStats().add(1.0, 2.0, 3.0)
    assert rs.mean == 2.0
    assert rs.variance == pytest.approx(1.0)
    assert math.isinf(RunningStats().min_val)


def test_histogram_percentile_and_cum():
    h = Histogram.uninitialized(0.0, 10.0, 1.0)
    h.add(np.repeat(np.arange(10), 10))  # uniform over 0..9
    assert h.percentile(50) == pytest.approx(4.0, abs=1.0)
    assert h.percentile(100) == pytest.approx(9.0, abs=1.0)
    assert h.cum_distr()[-1] == pytest.approx(1.0)
    assert h.cum_value(9.0) == pytest.approx(1.0)
    with pytest.raises(ValueError):
        h.percentile(150)
