import pytest
from harness import peaks


def test_v5e_is_in_the_table():
    p = peaks.peaks_for("TPU v5 lite")
    assert p["hbm_bytes_per_s"] == 819e9


@pytest.mark.parametrize("kind", ["cpu", "TPU v4", "", "TPU v5e", "NVIDIA H100"])
def test_an_unknown_device_kind_is_refused(kind):
    with pytest.raises(peaks.UnknownDevice):
        peaks.peaks_for(kind)
