"""The table of published peaks."""

import pytest

import _benchpath  # noqa: F401
from benchlib import peaks


def test_v5e_peaks_with_their_source():
    p = peaks.peaks_for("TPU v5 lite")
    assert p["bf16_flops"] == 197e12
    assert p["hbm_bytes_per_s"] == 819e9
    assert "TPU v5e" in p["source"]


def test_unknown_device_kind_raises():
    with pytest.raises(KeyError, match="no published peaks"):
        peaks.peaks_for("cpu")
