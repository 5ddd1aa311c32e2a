"""The calibrated threshold accepts about half of the steps under
soft best-of-n selection.

    python3 -m pytest bench/tests/test_calibrate.py
"""
import numpy as np

from bench.calibrate import accept_share, median_selected


def test_share_follows_the_selection_probabilities():
    tilted = np.array([[0.0, 1.0], [0.0, 1.0]])
    p_high = 1.0 / (1.0 + np.exp(-20.0))
    assert np.isclose(accept_share(tilted, 20.0, 0.5), p_high)
    assert np.isclose(accept_share(tilted, 0.0, 0.5), 0.5)
    assert np.isclose(accept_share(tilted, 20.0, -1.0), 1.0)


def test_median_selected_accepts_half():
    rng = np.random.default_rng(3)
    tilted = rng.normal(size=(400, 4)) * 0.1
    u = median_selected(tilted, 20.0)
    assert accept_share(tilted, 20.0, u) >= 0.5
    above = tilted[tilted > u].min()
    assert accept_share(tilted, 20.0, above) < 0.5
    # the selected candidate is biased high: u lies above the plain median
    assert u > np.median(tilted)
