"""Readings of a cell's control, from which its limits are set.

    python bench/calibrate.py --workload <cell> --seeds 11,12,13 [--out f]

The control is the reference put in the program's place with one
guarantee of the configuration broken, as ``bench/limits/<cell>.json``
names it: for the simulator cells a queue capacity or a phase barrier,
for the collective cell the program's all-reduce run in bfloat16
instead of the configuration's float32, on the cell's chips.  For each seed the control computes what the
window's first grid (or call) computes and is compared with the
reference exactly as ``correct`` compares the program; each line printed
is one seed's numbers.  The program's own readings are the ``check``
numbers of ordinary runs (``bench/run.py``).
"""
from __future__ import annotations

import argparse
import json
import os
import sys
from types import SimpleNamespace


def simulator_control(cell, seed: int) -> dict:
    import check
    from drivers.study import grid_seeds
    ref = check.Reference(cell.config, cell.traffic)
    ctl = check.Reference(cell.config, cell.traffic,
                          **cell.limits["control"])
    per = int(cell.traffic["seeds_per_grid"])
    gaps = []
    for i, (load, s) in enumerate(
            (load, s) for load in cell.traffic["loads"]
            for s in grid_seeds(seed, 1, per)):
        got = SimpleNamespace(**ctl.simulate(load, s, rng_seed=seed + i))
        want = ref.simulate(load, s, rng_seed=seed + 7919 * i)
        gaps.append(check.point_gaps(got, want, ref.replay))
    return check.worst(gaps)


def collective_control(cell, seed: int) -> dict:
    """The program's own all-reduce with its bfloat16 path switched on:
    the driver's float32 buckets, cast to bfloat16, all-reduced on the
    cell's mesh, against the float64 sum of the float32 buckets."""
    import jax
    import jax.numpy as jnp
    import numpy as np
    from jax.sharding import Mesh
    from repro.fabric import LacinCollectives
    from drivers.collective import MIB, all_reduce_fn, buckets, \
        reference_gap
    devices = jax.devices()[:int(cell.chips)]
    n = len(devices)
    elems = int(float(cell.config["bucket_mib"]) * MIB) // 4
    mesh = Mesh(np.array(devices), ("x",))
    f = all_reduce_fn(mesh, LacinCollectives(
        mesh=mesh, instance=cell.config["instance"]))
    x = buckets(mesh, n, elems, int(cell.traffic["rotation"]), seed)[0]
    out = f(x.astype(jnp.bfloat16))
    return {"sum_error": reference_gap(np.asarray(out.astype(jnp.float32)),
                                       np.asarray(x))}


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--out")
    args = ap.parse_args(argv)
    bench = os.path.dirname(os.path.abspath(__file__))
    sys.path[:0] = [bench, os.path.join(os.path.dirname(bench), "src")]
    import harness
    cell = harness.load_cell(args.workload)
    fn = (collective_control if cell.traffic["driver"] == "collective"
          else simulator_control)
    lines = []
    for seed in (int(s) for s in args.seeds.split(",")):
        numbers = fn(cell, seed)
        ok, checked_ = harness.checked(numbers, cell.limits["limits"])
        lines.append({"workload": cell.name, "seed": seed,
                      "control": cell.limits["control"],
                      "correct": ok, "numbers": numbers})
        print(json.dumps(lines[-1]), flush=True)
    if args.out:
        with open(args.out, "a") as f:
            f.writelines(json.dumps(x) + "\n" for x in lines)
    return 0


if __name__ == "__main__":
    sys.exit(main())
