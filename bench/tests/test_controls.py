"""Each cell's control comes out not correct against the cell's limits.

The control is the reference with one guarantee of the configuration
broken (``bench/limits/<cell>.json`` names it), computed as
``bench/calibrate.py`` computes it; at the cells' own sizes, on one seed
here (the limits were set from three or more, see ``PERF.md``).
"""
import pytest

import calibrate
import harness
from cells import ALL, load


@pytest.mark.parametrize("cell", ALL)
def test_control_fails_the_limits(cell):
    c = load(cell)
    fn = (calibrate.collective_control if c.traffic["driver"] == "collective"
          else calibrate.simulator_control)
    ok, numbers = harness.checked(fn(c, 4242), c.limits["limits"])
    assert not ok, numbers
