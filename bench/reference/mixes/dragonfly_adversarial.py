"""The Dragonfly adversary (Kim et al., ISCA 2008): every switch of
group g sends only to group g + 1 mod G, so minimal routing funnels a
whole group's traffic through one global link.

The draw is the simulator's own (``sim.traffic.adversarial_same_group``),
repeated here as a frozen contract so that the reference never reads a
packet the program made: Poisson(load * terminals) arrivals per switch
and cycle, then, from the same stream, a destination switch uniform
over the a switches of the next group (switch index ``group * a +
local``).
"""
from __future__ import annotations

import numpy as np

from reference import traffic as ref_traffic


def packets(fabric: dict, traffic: dict, terminals: int, load: float,
            seed: int) -> ref_traffic.Packets:
    if fabric["kind"] != "dragonfly":
        raise ValueError(f"the adversarial mix needs a Dragonfly, not a "
                         f"{fabric['kind']} fabric")
    a = int(fabric["params"]["group_size"])
    g = int(fabric["params"]["num_groups"])
    rng = np.random.default_rng(seed)
    src, gen = ref_traffic.poisson_arrivals(rng, a * g, load * terminals,
                                            traffic["cycles"])
    peer_group = (src // a + 1) % g
    dst = peer_group * a + rng.integers(0, a, size=src.size)
    return ref_traffic.Packets(src, dst, gen, None)
