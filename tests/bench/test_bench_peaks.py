"""The table of peaks: the v5e's published numbers, and a refusal for a
device the table does not know."""

import pytest

from peaks import UnknownDevice, peaks_for


def test_v5e_peaks_are_the_published_ones():
    p = peaks_for("TPU v5 lite")
    assert p["bf16_flops"] == 197e12
    assert p["int8_ops"] == 393e12
    assert p["hbm_bytes_per_s"] == 819e9


@pytest.mark.parametrize("kind", ["TPU v4", "cpu", ""])
def test_unknown_device_is_refused(kind):
    with pytest.raises(UnknownDevice):
        peaks_for(kind)
