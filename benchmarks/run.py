"""Benchmark harness: one module per paper table/figure.

Prints ``name,us_per_call,derived`` CSV rows.

``--quick`` (the CI configuration) drops all ``time_us`` timings to a
single repeat with no warmup, and modules that opt in via
``common.quick()`` additionally shrink their workloads (the simulator
module shortens its sweeps; the multi-device collective subprocesses run
at full size either way).  The simulator module drives every sweep
through :mod:`repro.studies` (the bundled spec files, shrunk via
``ExperimentSpec.with_sweep`` in quick mode) and writes the unified
result records to the ``benchmarks/BENCH_sim.json`` artifact, so the
latency/throughput trajectory it records per run is exactly what
``python -m repro.studies run cin16_saturation`` (etc.) reproduces.
"""
from __future__ import annotations

import json
import os
import sys
import time
import traceback


MODULES = [
    "bench_port_matrices",   # Figure 2
    "bench_table1",          # Table 1
    "bench_layout",          # §4 wire length + crossings
    "bench_routing",         # §3 + Algorithm 2
    "bench_hyperx",          # §5 + Figure 4
    "bench_dragonfly",       # Figure 3 + §5
    "bench_simulation",      # §1/§2 link loads + step schedules
    "bench_flow",            # flow-model scale tiers (after _simulation: appends to its artifact)
    "bench_faults",          # degraded-fabric survivability (after _simulation: appends to its artifact)
    "bench_collective_replay",  # schedule -> simulator replay (after _simulation: appends to its artifact)
    "bench_workload",        # extracted-step replay + serving SLOs (after _simulation: appends to its artifact)
    "bench_compile",         # compile cache cold/warm/disk split + 1040-switch xl point (appends to the artifact)
    "bench_collectives",     # §2 refs [8,9]: LACIN collectives vs XLA
]


def _stamp_environment(block_wall_s: dict[str, float]) -> None:
    """Merge an environment/provenance block into the BENCH_sim.json
    artifact: host + library versions, per-module wall time, and the
    simulator's measured xengine compile-vs-execute split — the context
    that makes a recorded trajectory comparable run over run."""
    artifact = os.path.join(os.path.dirname(__file__), "BENCH_sim.json")
    if not os.path.exists(artifact):
        return
    from repro.obs.telemetry import provenance
    with open(artifact) as f:
        payload = json.load(f)
    env = provenance()
    env["block_wall_s"] = block_wall_s
    speed = payload.get("sim_speed", {})
    env["xengine"] = {
        "compile_s": speed.get("jax_compile_s"),
        "execute_s": speed.get("jax_execute_s"),
        "cold_s": speed.get("jax_cold_s"),
        "steady_s": speed.get("jax_steady_s"),
    }
    payload["environment"] = env
    with open(artifact, "w") as f:
        json.dump(payload, f, indent=2, sort_keys=True)
        f.write("\n")


def main() -> None:
    if "--quick" in sys.argv[1:]:
        os.environ["REPRO_BENCH_QUICK"] = "1"
    print("name,us_per_call,derived")
    failures = 0
    block_wall_s: dict[str, float] = {}
    for name in MODULES:
        t0 = time.perf_counter()
        try:
            mod = __import__(f"benchmarks.{name}", fromlist=["rows"])
            from benchmarks.common import emit
            emit(mod.rows())
        except Exception as e:  # noqa: BLE001
            failures += 1
            print(f"{name},0,ERROR {type(e).__name__}: {e}")
            traceback.print_exc(file=sys.stderr)
        block_wall_s[name] = round(time.perf_counter() - t0, 3)
    try:
        _stamp_environment(block_wall_s)
    except Exception as e:  # noqa: BLE001
        failures += 1
        print(f"environment,0,ERROR {type(e).__name__}: {e}")
        traceback.print_exc(file=sys.stderr)
    if failures:
        raise SystemExit(1)


if __name__ == "__main__":
    main()
