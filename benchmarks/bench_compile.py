"""Compile economics of the compiled engine (repro.sim.xengine).

Three measurements, appended to ``benchmarks/BENCH_sim.json`` (run this
module after ``bench_simulation``, as ``benchmarks/run.py`` does):

* ``compile_cache`` block — the cold/disk/memory split for a bundled
  spec, in one process: the numpy oracle wall time vs (a) a **cold**
  run that must compile, (b) a **disk-restored** run after the
  in-process LRU is cleared, which deserializes the executable from the
  persistent cache (`docs/compile_cache.md`) exactly as a fresh process
  would, and (c) a seed re-run that reuses the bucketed program from
  memory.  The headline number is ``speedup_vs_numpy_with_compile``,
  measured on the disk-restored run: the compile tax is paid once per
  cache directory, so a fresh process keeps the compiled engine's win.
  Everything runs in this process because an accelerator belongs to one
  process at a time: a child started after this process touched JAX
  could not open it.
* ``xl_scale`` block — a 1040-switch Dragonfly (a=16, p=8, h=8, g=65;
  8320 terminals) pushed through the *cycle* engine (int16 state diet +
  shape bucketing), recording cycles/sec, cold-vs-warm wall time, and
  cost per grid point.  Beyond this scale the ``backend="auto"`` ladder
  still escalates to the flow tier (``bench_flow.py``).

The compile-cache runs use a throwaway ``JAX_COMPILATION_CACHE_DIR``, so
the block also doubles as an end-to-end check that serialized
executables restore (the CI ``cache-smoke`` lane checks it across
processes every push).
"""
from __future__ import annotations

import json
import os
import tempfile
import time

from repro import sim, studies
from repro.core.dragonfly import DragonflyConfig
from repro.obs.telemetry import cache_stats, clear_caches, disk_cache_entries
from repro.sim import xengine
from repro.sim.topology import dragonfly_topology

from .common import quick, row

_ARTIFACT = os.path.join(os.path.dirname(__file__), "BENCH_sim.json")


def _timed_study(exp) -> dict:
    """Run ``exp`` through the compiled Study backend and report the
    study wall time + the engine's own telemetry."""
    t0 = time.perf_counter()
    out = studies.Study(exp, backend="jax").run()
    wall = time.perf_counter() - t0
    # One experiment -> one batched program -> one shared timing dict.
    t = out.results[0].provenance["timings"]
    return {
        "study_wall_s": round(wall, 4),
        "compile_s": t["compile_s"],
        "compile_cached": t["compile_cached"],
        "points": len(out.results),
        "cache_entries": len(disk_cache_entries()),
        "cache_stats": cache_stats(),
    }


def _speed_spec() -> studies.ExperimentSpec:
    """The bundled cin16_saturation uniform/minimal experiment, widened
    to a realistic 8-seed confidence sweep.  Like ``bench_simulation``'s
    headline speed row, this workload is identical in quick and full
    modes so the recorded cold/warm/disk trajectory is comparable run
    over run (and big enough that the numpy oracle's wall time is the
    thing being beaten, not process noise)."""
    [exp] = [e for e in studies.load_specs(
                 studies.bundled_spec_path("cin16_saturation"))
             if e.traffic.pattern == "uniform"
             and e.routing.policy == "minimal"]
    return exp.with_sweep(seeds=tuple(range(23, 31)))


def compile_cache_rows(out: list, blocks: dict) -> None:
    exp = _speed_spec()

    t0 = time.perf_counter()
    studies.Study(exp, backend="numpy").run()
    numpy_s = time.perf_counter() - t0

    saved = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    os.environ["JAX_COMPILATION_CACHE_DIR"] = tempfile.mkdtemp(
        prefix="lacin-bench-cache-")
    try:
        clear_caches(memory=True)
        cold = _timed_study(exp)
        # Drop the in-process layer: the next acquisition is the one a
        # fresh process would make, from disk.
        clear_caches(memory=True)
        restored = _timed_study(exp)
        # A seed re-run lands in the same shape bucket: nothing compiles.
        rerun_exp = exp.with_sweep(
            seeds=tuple(s + 100 for s in exp.sweep.seeds))
        rerun = _timed_study(rerun_exp)
    finally:
        if saved is None:
            os.environ.pop("JAX_COMPILATION_CACHE_DIR", None)
        else:
            os.environ["JAX_COMPILATION_CACHE_DIR"] = saved
    rerun_s = rerun["study_wall_s"]

    blocks["compile_cache"] = {
        "workload": (f"cin16/uniform/minimal {len(exp.sweep.loads)} loads"
                     f" x {len(exp.sweep.seeds)} seeds x"
                     f" {exp.sweep.cycles} cycles (bundled spec, 8-seed"
                     f" sweep)"),
        "numpy_s": round(numpy_s, 4),
        "cold": cold,
        "disk_restored": restored,
        "seed_rerun": rerun,
        "speedup_vs_numpy": round(numpy_s / rerun_s, 2),
        "speedup_vs_numpy_with_compile":
            round(numpy_s / restored["study_wall_s"], 2),
        "speedup_vs_numpy_cold": round(numpy_s / cold["study_wall_s"], 2),
    }
    out.append(row("compile/cache/cold", cold["study_wall_s"] * 1e6,
                   f"compile_cached={cold['compile_cached']} "
                   f"compile={cold['compile_s']}s "
                   f"entries={cold['cache_entries']}"))
    out.append(row("compile/cache/disk_restored",
                   restored["study_wall_s"] * 1e6,
                   f"compile_cached={restored['compile_cached']} "
                   f"speedup_vs_numpy_with_compile="
                   f"{numpy_s / restored['study_wall_s']:.1f}x "
                   f"(cold={numpy_s / cold['study_wall_s']:.1f}x)"))
    out.append(row("compile/cache/seed_rerun", rerun_s * 1e6,
                   f"compile_cached={rerun['compile_cached']} "
                   f"compile_s={rerun['compile_s']} (bucketed program "
                   f"reused across seeds; steady speedup="
                   f"{numpy_s / rerun_s:.1f}x)"))


def xl_scale_rows(out: list, blocks: dict) -> None:
    cycles = 64 if quick() else 256
    cfg = DragonflyConfig(group_size=16, terminals_per_switch=8,
                          global_ports_per_switch=8, num_groups=65)
    topo = dragonfly_topology(cfg)

    def tf(load, seed):
        return sim.uniform(topo.num_switches, offered=load, cycles=cycles,
                           terminals=cfg.terminals_per_switch, seed=seed)

    def run():
        return xengine.sweep(topo, "minimal", tf, [0.05], seeds=(0,),
                             terminals=cfg.terminals_per_switch,
                             cycles=cycles, warmup=cycles // 4)

    t0 = time.perf_counter()
    grid = run()
    cold_s = time.perf_counter() - t0
    cold_stats = grid[0][0]
    t0 = time.perf_counter()
    warm_stats = run()[0][0]
    warm_s = time.perf_counter() - t0

    blocks["xl_scale"] = {
        "fabric": (f"dragonfly a={cfg.group_size} "
                   f"p={cfg.terminals_per_switch} "
                   f"h={cfg.global_ports_per_switch} g={cfg.num_groups}"),
        "switches": topo.num_switches,
        "terminals": topo.num_switches * cfg.terminals_per_switch,
        "cycles": cycles,
        "cold_wall_s": round(cold_s, 4),
        "warm_wall_s": round(warm_s, 4),
        "compile_s": cold_stats.timing["compile_s"],
        "execute_s": cold_stats.timing["execute_s"],
        "cold_compile_cached": cold_stats.timing["compile_cached"],
        "cycles_per_sec": round(cycles / warm_s, 1),
        "cost_per_point_s": round(warm_s, 4),
        "packets_delivered": int(warm_stats.packets_delivered),
    }
    assert topo.num_switches >= 1024
    assert warm_stats.packets_delivered > 0
    out.append(row(f"compile/xl_scale/dragonfly{topo.num_switches}",
                   cold_s * 1e6,
                   f"cycle engine at {topo.num_switches} switches: "
                   f"cold={cold_s:.1f}s warm={warm_s:.2f}s "
                   f"({cycles / warm_s:.0f} cyc/s) "
                   f"delivered={int(warm_stats.packets_delivered)}"))


def rows():
    out: list = []
    blocks: dict = {}
    compile_cache_rows(out, blocks)
    xl_scale_rows(out, blocks)
    if os.path.exists(_ARTIFACT):
        with open(_ARTIFACT) as f:
            payload = json.load(f)
        payload.update(blocks)
        with open(_ARTIFACT, "w") as f:
            json.dump(payload, f, indent=2, sort_keys=True)
            f.write("\n")
    return out


def main():
    from .common import emit
    emit(rows())


if __name__ == "__main__":
    main()
