"""Percentile and rate arithmetic of the benchmark."""
import sys
from pathlib import Path

import numpy as np
import pytest

sys.path.insert(0, str(Path(__file__).resolve().parents[1]))

from harness import stats  # noqa: E402


@pytest.mark.parametrize("q", [50, 90, 95, 99])
def test_percentile_matches_numpy_on_finite_values(q):
    vals = list(np.random.default_rng(q).exponential(1.0, 257))
    assert stats.percentile(vals, q) == pytest.approx(np.percentile(vals, q))


def test_every_request_counts():
    vals = [1.0] * 95 + [100.0] * 5
    assert stats.percentile(vals, 95) > 1.0
    assert stats.percentile(vals[:95], 95) == 1.0


def test_percentile_of_one_value():
    assert stats.percentile([3.5], 95) == 3.5


def test_percentile_of_nothing_is_an_error():
    with pytest.raises(ValueError):
        stats.percentile([], 50)


def test_rate():
    assert stats.rate(400, 40.0) == 10.0
    with pytest.raises(ValueError):
        stats.rate(1, 0.0)
