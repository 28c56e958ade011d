"""The dimension-order all-to-all of a HyperX of Circle CINs, replayed
phase by phase (``reference.traffic.a2a_replay``); load and seed do not
change it."""
from __future__ import annotations

from reference import traffic as ref_traffic


def packets(fabric: dict, traffic: dict, terminals: int, load: float,
            seed: int) -> ref_traffic.Packets:
    prm = traffic["traffic"]["params"]
    if fabric["kind"] != "hyperx" or prm.get("collective") != "all_to_all":
        raise ValueError(f"no reference replay of {prm} on a "
                         f"{fabric['kind']} fabric")
    return ref_traffic.a2a_replay(fabric["params"]["dims"],
                                  int(prm["message_size"]))
