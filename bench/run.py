"""Run one benchmark cell once and print its result line.

    python bench/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

The cell is an entry of ``BENCHMARK.json``'s ``workloads``; its traffic
file names the driver (``bench/drivers/<driver>.py``) that runs it.
Set-up (``setup_s``) runs from this process's start to the start of the
measured window; the window lasts ``--seconds`` or a little more (it ends
with the unit of work that crosses it).  With ``--trace 1`` the window
runs under the profiler and the result holds the cell's per-layer
metrics instead of its end-to-end ones.

Without a TPU, or with fewer chips than the cell asks for, the run exits
with status 2 and prints no result.  The last line of standard output is
one JSON object: ``correct``, ``attempted``, ``failed``, ``metrics``,
``device``, ``breakdown`` (traced runs) and, last, ``check``: each number
that decided ``correct`` beside its limit.  Progress goes to standard
error, which ends with the same numbers and limits.
"""
from __future__ import annotations

import argparse
import json
import os
import shutil
import sys
import tempfile


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    ap.add_argument("--trace-out", help="also keep the traced window's "
                    ".xplane.pb in this directory")
    args = ap.parse_args(argv)

    bench = os.path.dirname(os.path.abspath(__file__))
    root = os.path.dirname(bench)
    # One fixed compile-cache directory inside the checkout, for JAX and
    # for the simulator's own executable cache, whatever the host sets.
    os.environ["JAX_COMPILATION_CACHE_DIR"] = os.path.join(root, ".jax_cache")
    # The TPU runtime would otherwise write its logs to a fixed /tmp path.
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    sys.path[:0] = [bench, os.path.join(root, "src")]
    import harness

    start = harness.process_start()
    try:
        cell = harness.load_cell(args.workload, root)
        devices = harness.device_gate(cell.chips)
        import jax
        jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
        driver = harness.load_module("drivers", cell.traffic["driver"])
    except harness.Refused as e:
        harness.log(f"refused: {e}")
        return 2
    trace_dir = tempfile.mkdtemp(prefix="bench-trace-")
    try:
        result = driver.run(cell, devices, seed=args.seed,
                            seconds=args.seconds, trace=bool(args.trace),
                            start=start, trace_dir=trace_dir,
                            trace_out=args.trace_out)
    finally:
        shutil.rmtree(trace_dir, ignore_errors=True)
    harness.print_check(result["check"])
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
