"""Simulator cells: whole (load x seed) grids through the public call
``repro.studies.Study(spec, backend="jax").run()``, with no store.

Set-up builds the experiment from the configuration and traffic files
and runs one grid (seeds ``seed .. seed + s - 1`` for ``s`` seeds per
grid), which acquires the compiled program from the disk cache or
compiles it.  The window then runs grids back to back, grid k on seeds
``seed + s*k ..``, so traffic generation, packing and the statistics
rebuild are fresh work each time; it ends with the grid that crosses
``seconds``.  Every window grid must find its program in memory: one
that compiled counts its points as failed.

A traced run's window is one grid: the profiler records every device
operation of every simulated cycle, and one grid already holds its
program launch and the host work around it.

``correct`` compares every point of one window grid, drawn from the
seed, with the reference (``bench/check.py``).
"""
from __future__ import annotations

import time

import numpy as np

import check
import harness
from stepbytes import step_bytes


def experiment(cell, seeds):
    from repro.studies import ExperimentSpec
    cfg, tr = cell.config, cell.traffic
    return ExperimentSpec(
        fabric=cfg["fabric"], traffic=tr["traffic"], routing=tr["routing"],
        sweep={"loads": tr["loads"], "seeds": list(seeds),
               "cycles": tr.get("cycles"), "warmup": tr.get("warmup")},
        terminals=cfg["terminals"], engine=cfg.get("engine", {}),
        name=cell.name)


def grid_seeds(seed: int, k: int, per: int) -> list[int]:
    return [seed + per * k + j for j in range(per)]


def _record(result, wall_s: float) -> dict:
    stats = [r.stats for r in result.results]
    timing = stats[0].timing
    return {"wall_s": wall_s, "execute_s": timing["execute_s"],
            "compile_s": timing["compile_s"],
            "cached": timing["compile_cached"],
            "copies": len(stats), "switches": stats[0].num_switches,
            "cycles": max(s.cycles for s in stats),
            "switch_cycles": sum(s.num_switches * s.cycles for s in stats)}


def run(cell, devices, *, seed: int, seconds: float, trace: bool,
        start: float, trace_dir: str, trace_out: str | None = None
        ) -> dict:
    from repro.studies import Study
    per = int(cell.traffic["seeds_per_grid"])
    spec = experiment(cell, grid_seeds(seed, 0, per))
    with harness.span("setup"):
        spec.fabric.resolve_topology()
        warm = _record(Study(spec, backend="jax").run(), 0.0)
    harness.log(f"warm grid: compile_cached={warm['cached']} "
                f"compile_s={warm['compile_s']} "
                f"execute_s={warm['execute_s']}")

    grids, results = [], []
    prof = harness.Profiler(trace, trace_dir, trace_out)
    with prof:
        setup_s = time.time() - start
        with harness.span("window"):
            t0 = time.perf_counter()
            k = 1
            while True:
                g_spec = spec.with_sweep(seeds=grid_seeds(seed, k, per))
                g0 = time.perf_counter()
                with harness.span("grid"):
                    res = Study(g_spec, backend="jax").run()
                t1 = time.perf_counter()
                grids.append(_record(res, t1 - g0))
                results.append(res)
                k += 1
                if trace or t1 - t0 >= seconds:
                    break
    window_s = t1 - t0
    dev = harness.device_block(devices)
    compiled = [g for g in grids if g["cached"] != "memory"]
    for g in compiled:
        harness.log(f"a window grid acquired its program from "
                    f"{g['cached'] or 'a fresh compile'} "
                    f"(compile_s={g['compile_s']})")
    harness.log(f"window: {len(grids)} grids in {window_s:.3f}s")
    for k, g in enumerate(grids):
        harness.log(f"grid {k}: wall_s={g['wall_s']:.4f} "
                    f"execute_s={g['execute_s']:.4f} cached={g['cached']}")

    # correct: every point of one window grid, drawn from the seed.
    pick = int(np.random.default_rng(seed).integers(len(results)))
    ref = check.Reference(cell.config, cell.traffic)
    with harness.span("check"):
        gaps = [check.point_gaps(
                    r.stats, ref.simulate(r.load, r.seed,
                                          rng_seed=seed + 7919 * i),
                    ref.replay)
                for i, r in enumerate(results[pick].results)]
    ok, numbers = harness.checked(check.worst(gaps), cell.limits["limits"])

    attempted = sum(g["copies"] for g in grids)
    failed = sum(g["copies"] for g in compiled)
    out = {"correct": bool(ok) and not compiled, "attempted": attempted,
           "failed": failed}
    if not trace:
        out["metrics"] = {
            "setup_s": {"value": setup_s, "unit": "s"},
            "switch_cycles_per_s": {
                "value": sum(g["switch_cycles"] for g in grids) / window_s,
                "unit": "switch-cycles/s"}}
        out["device"] = dev
    else:
        from tracereduce import reduce_trace
        summary = reduce_trace(prof.path(), num_devices=len(devices))
        fab = ref.fabric
        policy = cell.traffic["routing"]["policy"]
        shape = {"copies": grids[0]["copies"], "switches": fab.num_switches,
                 "ports": fab.num_ports,
                 "vcs": fab.diameter * (1 if policy == "minimal" else 2),
                 "capacity": cell.config.get("engine", {}).get(
                     "queue_capacity", 4),
                 "terminals": cell.config["terminals"]}
        ctx = {"summary": summary, "grids": grids, "warm": warm,
               "peaks": harness.peaks(dev["kind"]),
               "step_bytes": step_bytes(**shape),
               "cycles": sum(g["cycles"] for g in grids)}
        out["metrics"] = harness.read_metrics(cell, ctx)
        if summary is not None:
            dev["busy_s"] = summary.busy_s
            dev["window_s"] = summary.window_s
            out["breakdown"] = summary.breakdown()
        out["device"] = dev
    out["check"] = numbers
    return out
