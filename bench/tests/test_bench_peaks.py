"""The peaks table (bench/peaks.json) is keyed by device_kind and refuses a
kind it does not know."""
import pytest

import benchpath  # noqa: F401
import peaks


def test_v5e_peaks_and_source():
    p = peaks.peaks("TPU v5 lite")
    assert p["bf16_flops_per_s"] == 197e12
    assert p["hbm_bytes_per_s"] == 819e9
    assert "TPU v5e" in p["source"]


@pytest.mark.parametrize("kind", ["cpu", "TPU v4", "tpu v5 lite", ""])
def test_unknown_device_kind_raises(kind):
    with pytest.raises(KeyError, match="no peaks for device kind"):
        peaks.peaks(kind)
