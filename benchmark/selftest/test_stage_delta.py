"""``readers/stage_delta.py`` on a hand-made ``run``: the window, since the
start, a missing block, a mean a span."""

import importlib.util

import pytest
from conftest import BENCH


def reader():
    spec = importlib.util.spec_from_file_location(
        "stage_delta", BENCH / "readers" / "stage_delta.py")
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod.read


def row(count, wall_s, cpu_s):
    return {"count": count, "wall_s": wall_s, "cpu_s": cpu_s}


RUN = {
    "device_before": {"stages": {
        "http.request": row(100, 20.0, 1.0),
        "scheduler.run": row(90, 9.0, 2.0),
        "grid.build": row(1, 35.0, 30.0)}},
    "device_after": {"stages": {
        "http.request": row(300, 70.0, 3.0),
        "scheduler.run": row(290, 31.0, 6.0),
        "grid.build": row(1, 35.0, 30.0),
        "gc.pause": row(2, 0.9, 0.9)}},
}


def test_the_window_is_the_change_between_the_two_documents():
    read = reader()
    assert read(RUN, ["http.request"], "wall_s") == pytest.approx(50.0)
    assert read(RUN, ["http.request"], "count") == 200
    # a span that first ran inside the window counts from zero
    assert read(RUN, ["gc.pause"], "wall_s", scale=1000.0) == \
        pytest.approx(900.0)
    # one that did not run at all reads 0, not nothing
    assert read(RUN, ["grid.build", "never.ran"], "wall_s") == 0.0


def test_per_gives_a_mean_over_that_spans_count():
    read = reader()
    got = read(RUN, ["http.request", "scheduler.run"], "cpu_s",
               per="http.request", scale=1000.0)
    assert got == pytest.approx(1000.0 * (2.0 + 4.0) / 200)
    assert read(RUN, ["gc.pause"], "wall_s", per="never.ran") is None


def test_since_start_reads_the_closing_document_alone():
    read = reader()
    assert read(RUN, ["grid.build"], "wall_s", since="start") == 35.0
    assert read({"device_after": RUN["device_after"]}, ["grid.build"],
                "wall_s", since="start") == 35.0
    with pytest.raises(ValueError):
        read(RUN, ["grid.build"], "wall_s", since="yesterday")


@pytest.mark.parametrize("run", [
    {}, {"device_before": None, "device_after": None},
    {"device_before": {"compile": {}}, "device_after": {"compile": {}}},
    {"device_before": {"compile": {}}, "device_after": RUN["device_after"]}])
def test_a_program_without_the_stage_clock_is_left_out(run):
    assert reader()(run, ["http.encode"], "wall_s", per="http.encode") is None
