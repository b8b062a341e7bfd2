"""``tools/sets.py``: the spread it reports is the distance between the
quartiles over the median."""

import pytest

from benchmark.tools import sets


def test_spread_of_six():
    # quartiles by linear interpolation: 2.25 and 4.75 around a median of 3.5
    assert sets.spread([6, 1, 4, 2, 5, 3]) == pytest.approx(2.5 / 3.5)
    assert sets.spread([7.0] * 6) == 0.0
