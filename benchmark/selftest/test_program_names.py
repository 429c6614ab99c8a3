"""What ``run.py``'s ``device_dispatches`` reads of the program's names, held
here until a tier-1 test holds it (``tests/test_benchmark_contract.py`` pins
the ``devicestore.*`` family only; a ``benchmark`` PR may not touch it): the
mesh fabric's programs are ``meshgrid.*`` through ``devicewatch.jit``, none of
them stacked, and the helpers that answer no request are there under exactly
the names ``run.py`` leaves out."""

import re

import pytest
import run
from conftest import ROOT

NAMED = re.compile(r"""devicewatch\.jit,?[^)]*?program=["']([\w.]+)["']""",
                   re.S)
SOURCES = {"devicestore.": ROOT / "filodb_tpu" / "memstore" / "devicestore.py",
           "meshgrid.": ROOT / "filodb_tpu" / "parallel" / "meshgrid.py"}


def programs(family: str) -> list:
    if not SOURCES[family].is_file():
        pytest.skip("the program is not in this checkout")
    return NAMED.findall(SOURCES[family].read_text())


def test_the_families_are_the_ones_with_a_source():
    assert set(run.SERVING_FAMILIES) == set(SOURCES)


@pytest.mark.parametrize("family", sorted(SOURCES))
def test_every_program_of_a_family_is_named_for_it(family):
    names = programs(family)
    assert len(names) >= 7, names
    assert all(n.startswith(family) for n in names), names
    helpers = [h for h in run.HELPERS if h.startswith(family)]
    assert len(helpers) == 1 and names.count(helpers[0]) == 1, names
    serving = [n for n in names if n not in run.HELPERS]
    stacked = [n for n in serving if run.STACKED in n]
    if family == "meshgrid.":
        # a fused launch answers one request: none is counted by members
        assert not stacked
        assert {"meshgrid.fused", "meshgrid.grouped",
                "meshgrid.quantile"} <= set(serving)
    else:
        assert sorted(stacked) == ["devicestore.grouped_batch",
                                   "devicestore.series_batch"]
