"""The reference's open-loop draws are the simulator's own: the same
seed gives the very same packets (src, dst, generation cycle)."""
import numpy as np
import pytest

import harness
from cells import tiny

SEEDS = [1, 42, 9_999, 2**31 + 11, 3 * 2**32 + 7]


def _same(got, want):
    for field in ("src", "dst", "gen"):
        a, b = getattr(got, field), getattr(want, field)
        assert a.dtype == b.dtype and np.array_equal(a, b), field


@pytest.mark.parametrize("seed", SEEDS)
def test_adversarial_draw_is_the_programs(seed):
    from repro.core.dragonfly import DragonflyConfig
    from repro.sim.traffic import adversarial_same_group
    cell = tiny("df2064.adversarial.adaptive")
    fabric, tr, t = (cell.config["fabric"], cell.traffic,
                     cell.config["terminals"])
    prm = fabric["params"]
    cfg = DragonflyConfig(
        group_size=prm["group_size"],
        terminals_per_switch=prm["terminals_per_switch"],
        global_ports_per_switch=prm["global_ports_per_switch"],
        num_groups=prm["num_groups"])
    mix = harness.load_module("reference/mixes", tr["reference"])
    for load in tr["loads"]:
        want = adversarial_same_group(cfg, offered=load,
                                      cycles=tr["cycles"], terminals=t,
                                      seed=seed)
        got = mix.packets(fabric, tr, t, load, seed)
        assert got.src.size > 0
        assert np.all(got.dst // prm["group_size"]
                      == (got.src // prm["group_size"] + 1)
                      % prm["num_groups"])
        _same(got, want)


@pytest.mark.parametrize("seed", SEEDS[:3])
def test_uniform_draw_is_the_programs(seed):
    from repro.sim.traffic import uniform
    cell = tiny("df2064.uniform.minimal")
    fabric, tr, t = (cell.config["fabric"], cell.traffic,
                     cell.config["terminals"])
    mix = harness.load_module("reference/mixes", tr["reference"])
    n = fabric["params"]["group_size"] * fabric["params"]["num_groups"]
    for load in tr["loads"]:
        _same(mix.packets(fabric, tr, t, load, seed),
              uniform(n, offered=load, cycles=tr["cycles"], terminals=t,
                      seed=seed))
