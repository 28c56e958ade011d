"""Uniform random destinations on any fabric: the simulator's own
uniform draw (``reference.traffic.uniform``), Poisson arrivals per
switch and cycle at ``load * terminals``."""
from __future__ import annotations

from reference import fabric as ref_fabric
from reference import traffic as ref_traffic


def packets(fabric: dict, traffic: dict, terminals: int, load: float,
            seed: int) -> ref_traffic.Packets:
    return ref_traffic.uniform(
        ref_fabric.num_switches(fabric), offered=load,
        cycles=traffic["cycles"], terminals=terminals, seed=seed)
