"""``trace_reduce.py`` on the small device trace recorded beside it
(``record_fixture.py``, one v5e: three launches of one jitted program 50 ms
apart in a 0.4 s window)."""

import json

import pytest
from conftest import HERE
from harness import trace_reduce

FIXTURE = HERE / "fixture.xplane.pb"
FACTS = json.loads((HERE / "fixture.json").read_text())


def test_union_merges_overlaps():
    assert trace_reduce.union([(5, 7), (0, 2), (1, 3), (7, 8)]) \
        == [(0, 3), (5, 8)]


@pytest.mark.skipif(not FIXTURE.is_file(), reason="no recorded trace")
def test_reduction_of_the_recorded_trace():
    out = trace_reduce.reduce(str(FIXTURE), FACTS["window_s"])
    assert out["devices"] == 1
    assert 0 < out["busy_s"] < 0.5 * out["window_s"]
    assert out["device_ops"] and len(out["device_ops"]) <= 10
    assert all(s > 0 for _n, s in out["device_ops"])
    assert out["device_ops"][0][0] == "jit__lambda:%fusion"
    assert out["idle_gaps"][0][0] == "no host span"      # the host slept
    # ops on one device: no more time in them than the device was busy
    assert sum(s for _n, s in out["device_ops"]) <= out["busy_s"] * 1.001 \
        or len(out["device_ops"]) == 10
    # the 50 ms sleeps between launches are the long gaps
    assert len(out["idle_gaps"]) >= FACTS["launches"]
    assert out["idle_gaps"][0][1] >= 0.05
    assert out["busy_s"] + sum(s for _n, s in out["idle_gaps"]) \
        <= out["window_s"] * 1.001


def test_no_device_plane_gives_nothing_to_read(tmp_path):
    """A CPU trace has no device plane: busy_s is None, never 0."""
    import jax
    import jax.numpy as jnp
    jax.profiler.start_trace(str(tmp_path))
    jnp.ones(8).sum().block_until_ready()
    jax.profiler.stop_trace()
    out = trace_reduce.reduce(trace_reduce.find_xplane(str(tmp_path)), 1.0)
    assert out["busy_s"] is None and out["idle_gaps"] == []
