"""The least time of a step's required work, against counts made by hand.
The work counts themselves are the family's (``test_family_mistral.py``)."""
import pytest

from bench import work


def test_least_seconds_names_its_bound():
    peaks = {"bf16_flops_per_s": 100.0, "hbm_bytes_per_s": 10.0}
    assert work.least_seconds(200, 10, peaks) == (2.0, "compute")
    assert work.least_seconds(100, 30, peaks) == pytest.approx((3.0, "memory"))
