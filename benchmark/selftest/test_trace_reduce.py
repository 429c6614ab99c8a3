"""``trace_reduce.py`` on the small device trace recorded beside it
(``record_fixture.py``, one v5e: three launches of one jitted program 50 ms
apart in a 0.4 s window), on traces of several device planes written out
as text, and the readers that take their numbers from the reduction."""

import json

import pytest
from conftest import HERE
from harness import trace_reduce
from run import read_per_layer

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
    assert out["busy_s_by_device"] == [["/device:TPU:0", out["busy_s"]]]
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


def written_trace(tmp_path, planes: list) -> str:
    """An ``.xplane.pb`` with one device plane for each list of (start,
    end) seconds in which an operation ran there."""
    from jax.profiler import ProfileData
    text = ""
    for i, ops in enumerate(planes):
        events = " ".join(
            f"events {{ metadata_id: 1 offset_ps: {round(a * 1e12)} "
            f"duration_ps: {round((b - a) * 1e12)} }}" for a, b in ops)
        text += (f'planes {{ name: "/device:TPU:{i}" lines {{ name: '
                 f'"XLA Ops" timestamp_ns: 0 {events} }} event_metadata '
                 f'{{ key: 1 value {{ id: 1 name: "%fusion.1 = f32[8] '
                 f'fusion()" }} }} }} ')
    path = tmp_path / f"{len(planes)}.xplane.pb"
    path.write_bytes(ProfileData.text_proto_to_serialized_xspace(text))
    return str(path)


def test_several_planes_are_named_and_idle_is_what_all_have_in_common(
        tmp_path):
    one = [(0.0, 0.1), (0.3, 0.4)]
    out = trace_reduce.reduce(
        written_trace(tmp_path, [one, [(0.05, 0.2)], [], [(0.38, 0.45)]]),
        0.5)
    assert out["devices"] == 4
    by = dict(out["busy_s_by_device"])
    assert list(by) == [f"/device:TPU:{i}" for i in range(4)]
    assert [round(v, 9) for v in by.values()] == [0.2, 0.15, 0.0, 0.07]
    assert out["busy_s"] == pytest.approx(0.105)
    # nothing ran anywhere in 0.2-0.3 and 0.45-0.5; on the first plane alone
    # it would be 0.1-0.3 and 0.4-0.5
    assert [round(s, 9) for _n, s in out["idle_gaps"]] == [0.1, 0.05]
    # one plane: the gaps are that plane's own, as before
    alone = trace_reduce.reduce(written_trace(tmp_path, [one]), 0.5)
    assert [round(s, 9) for _n, s in alone["idle_gaps"]] == [0.2, 0.1]


def test_the_roofline_divides_by_the_chips_that_were_traced(tmp_path):
    """The least bytes are those of all chips and ``busy_s`` is the mean a
    chip: on one plane the share is bytes / peak / busy, on four planes as
    busy each a quarter of it."""
    ops = [(0.0, 0.1), (0.3, 0.4)]
    run = {"trace_least_bytes": 819e9 * 0.05,
           "peaks": {"hbm_bytes_per_s": 819e9}}
    shares = {}
    for n in (1, 4):
        run["trace"] = trace_reduce.reduce(
            written_trace(tmp_path, [ops] * n), 0.5)
        assert run["trace"]["busy_s"] == pytest.approx(0.2)
        shares[n] = read_per_layer("device_program_roofline", run)
    assert shares[1] == pytest.approx(100 * 0.05 / 0.2, rel=1e-12)
    assert shares[4] == pytest.approx(shares[1] / 4, rel=1e-12)
