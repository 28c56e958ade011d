"""Cells at a size a test run holds, built from the real cells' files."""
import copy
import tempfile

import jax

import harness

ALL = [w["name"] for w in harness.benchmark()["workloads"]]


def load(name: str) -> harness.Cell:
    return harness.load_cell(name)


def tiny(name: str, **limits) -> harness.Cell:
    cell = copy.deepcopy(load(name))
    cfg, tr = cell.config, cell.traffic
    if cfg.get("fabric", {}).get("kind") == "hyperx":
        cfg["fabric"]["params"].update(dims=[6, 4], terminals=3)
        cfg["terminals"] = 3
        if tr["traffic"]["pattern"] == "workload":
            tr["traffic"]["params"]["message_size"] = 8
        else:
            tr.update(loads=[0.2, 0.4, 0.6], cycles=200, warmup=50)
    elif cfg.get("fabric", {}).get("kind") == "dragonfly":
        cfg["fabric"]["params"].update(
            group_size=4, terminals_per_switch=2, global_ports_per_switch=2,
            num_groups=9)
        cfg["terminals"] = 2
    else:
        cfg["bucket_mib"] = 0.25
    cell.limits["limits"].update(limits)
    return cell


def run(cell, seed: int = 3, seconds: float = 0.5) -> dict:
    from repro.obs import telemetry
    jax.clear_caches()
    telemetry.clear_caches(memory=True)
    driver = harness.load_module("drivers", cell.traffic["driver"])
    return driver.run(cell, jax.devices()[:cell.chips], seed=seed,
                      seconds=seconds, trace=False,
                      start=harness.process_start(),
                      trace_dir=tempfile.mkdtemp())
