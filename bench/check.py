"""The comparisons that decide ``correct`` for simulator cells.

For each grid point compared, the reference (``bench/reference``) runs
the same deployment on the same packets, regenerated from the point's
seed, with its own random stream for tie-breaks.  Open-loop traffic can
agree only in distribution, so its numbers are relative gaps, worst over
the points compared; a collective replay is deterministic (every phase a
contention-free 1-factor), so its numbers are exact differences.

The reference's packets come from the builder the traffic file names
(``"reference": "<name>"``): ``bench/reference/mixes/<name>.py``, whose
``packets(fabric, traffic, terminals, load, seed)`` takes the
configuration's ``fabric`` entry and the whole traffic file.  A new mix
brings its own builder; nothing here knows a pattern or a fabric.

* ``generated``   |packets generated - reference|, worst point (exact)
* ``accepted``    |accepted - ref| / ref, worst point
* ``latency``     |mean latency - ref| / ref, worst point
* ``links``       open loop: |mean link utilization - ref| / ref;
                  replay: largest difference of the sorted per-link
                  traversal counts (exact)
* ``completion``  |completion cycles - ref| (replay, exact)
* ``phases``      largest |phase cycles - ref| (replay, exact)
"""
from __future__ import annotations

import numpy as np

import harness
from reference import fabric as ref_fabric
from reference import netsim


def _rel(a: float, b: float) -> float:
    return abs(a - b) / abs(b) if b else abs(a - b)


class Reference:
    """The reference deployment of one cell, built once per run."""

    def __init__(self, config: dict, traffic: dict, **control):
        self.config = config
        self.traffic = traffic
        self.fabric = ref_fabric.build(config["fabric"])
        self.control = control
        if "reference" not in traffic:
            raise harness.Refused("the traffic file names no reference "
                                  "builder (its \"reference\" key)")
        self.mix = harness.load_module("reference/mixes",
                                       traffic["reference"])

    @property
    def replay(self) -> bool:
        return self.traffic["traffic"]["pattern"] == "workload"

    def packets(self, load: float, seed: int):
        return self.mix.packets(self.config["fabric"], self.traffic,
                                self.config["terminals"], load, seed)

    def simulate(self, load: float, seed: int, rng_seed: int) -> dict:
        routing = self.traffic["routing"]
        kw = dict(routing.get("params", {}))
        kw.update(self.config.get("engine", {}))
        kw.update(self.control)
        return netsim.simulate(
            self.fabric, self.packets(load, seed),
            terminals=self.config["terminals"],
            cycles=self.traffic.get("cycles"),
            warmup=self.traffic.get("warmup") or 0,
            policy=routing["policy"], seed=rng_seed, **kw)


def point_gaps(got, want: dict, replay: bool) -> dict:
    """The numbers of one grid point; ``got`` is the program's RunStats
    (or anything with the same attributes), ``want`` the reference's."""
    out = {"generated": abs(got.packets_generated
                            - want["packets_generated"]),
           "latency": _rel(got.latency_mean, want["latency_mean"]),
           "accepted": _rel(got.accepted, want["accepted"])}
    if replay:
        a = np.sort(np.asarray(got.link_loads))
        b = np.sort(np.asarray(want["link_loads"]))
        out["links"] = (float(np.abs(a - b).max()) if a.shape == b.shape
                        else float("inf"))
        out["completion"] = abs((got.completion_cycles or 0)
                                - want["completion_cycles"])
        pa = list(got.phase_cycles or ())
        pb = list(want["phase_cycles"])
        out["phases"] = (max((abs(x - y) for x, y in zip(pa, pb)),
                             default=0) if len(pa) == len(pb)
                         else float("inf"))
    else:
        out["links"] = _rel(got.link_util_mean, want["link_util_mean"])
    return out


def worst(gaps: list[dict]) -> dict:
    keys = set().union(*gaps) if gaps else set()
    return {k: max(g[k] for g in gaps) for k in sorted(keys)}
