"""Command-line driver: ``python -m repro.workload <command>``.

Commands:

* ``extract`` — compile a training step (``--step moe | dp | pipeline``)
  for ``--devices`` host devices in a subprocess (XLA_FLAGS is set
  *before* the child imports jax), lower its collective sequence onto a
  CIN fabric of the same size, and write the resulting
  :class:`~repro.sim.workloads.Workload` as JSON.
* ``replay`` — replay an extracted workload JSON on a fabric through
  the cycle engines.  ``--backend both`` runs the numpy oracle *and*
  the compiled engine, asserts ``measured >= ideal`` (the
  contention-free bound) and exact cross-engine agreement.
* ``slo`` — run :meth:`repro.studies.Study.slo_capacity` on a serving
  study spec: the largest arrival-rate scale whose latency percentile
  still meets the SLO.

Examples::

    python -m repro.workload extract --step moe --devices 8 \\
        --bytes-per-packet 256 -o moe8.workload.json
    python -m repro.workload replay moe8.workload.json --backend both
    python -m repro.workload slo serving_slo \\
        --experiment cin-xor-16/serving-poisson-r0.05/minimal
"""
from __future__ import annotations

import argparse
import json
import os
import subprocess
import sys

_STEPS = ("moe", "dp", "pipeline")

#: Child source for ``extract``: runs in a subprocess whose XLA_FLAGS
#: already request the device count, prints the workload dict as the
#: last stdout line.
_EXTRACT_CHILD = r"""
import json, sys
args = json.loads(sys.argv[1])
from repro.workload import (dp_step_hlo, moe_step_hlo, pipeline_step_hlo,
                            workload_from_hlo)
step = {"moe": moe_step_hlo, "dp": dp_step_hlo,
        "pipeline": pipeline_step_hlo}[args["step"]]
hlo = step(args["devices"], **args["step_kw"])
w = workload_from_hlo(hlo, (args["instance"], args["n"]),
                      bytes_per_packet=args["bytes_per_packet"],
                      strict=args["strict"], name=args["name"])
print(json.dumps(w.to_dict()))
"""


def _src_path() -> str:
    import repro
    # repro is a namespace package (no __init__.py): locate it via
    # __path__, whose single entry is <src>/repro.
    return os.path.dirname(os.path.abspath(next(iter(repro.__path__))))


def cmd_extract(args) -> int:
    payload = {
        "step": args.step, "devices": args.devices,
        "instance": args.fabric, "n": args.n or args.devices,
        "bytes_per_packet": args.bytes_per_packet,
        "strict": not args.lenient, "name": args.name,
        "step_kw": ({"dp": args.dp} if args.step == "moe" and args.dp > 1
                    else {}),
    }
    # The child emulates the mesh on host devices (HLO extraction only), so
    # it never needs, or contends for, an accelerator.
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    env["XLA_FLAGS"] = (env.get("XLA_FLAGS", "") + " --xla_force_host_"
                        f"platform_device_count={args.devices}").strip()
    env["PYTHONPATH"] = os.pathsep.join(
        p for p in (_src_path(), env.get("PYTHONPATH")) if p)
    proc = subprocess.run(
        [sys.executable, "-c", _EXTRACT_CHILD, json.dumps(payload)],
        env=env, capture_output=True, text=True)
    if proc.returncode != 0:
        sys.stderr.write(proc.stderr)
        raise SystemExit(f"extract subprocess failed "
                         f"(exit {proc.returncode})")
    line = [l for l in proc.stdout.splitlines() if l.strip()][-1]
    wd = json.loads(line)
    out = args.out or f"{args.step}{args.devices}.workload.json"
    with open(out, "w") as f:
        json.dump(wd, f, indent=2, sort_keys=True)
        f.write("\n")
    total = sum(len(p["src"]) * p["messages"] for p in wd["phases"])
    print(f"wrote {out}: workload {wd['name']!r}, "
          f"{wd['num_switches']} switches, {len(wd['phases'])} phases, "
          f"{total} packets")
    return 0


def cmd_replay(args) -> int:
    from repro.fabric import make_fabric
    from repro.sim.workloads import Workload, replay
    with open(args.workload) as f:
        w = Workload.from_dict(json.load(f))
    fab = make_fabric(args.fabric, args.n or w.num_switches)
    topo = fab.sim_topology()
    backends = ["numpy", "jax"] if args.backend == "both" else [args.backend]
    runs = {}
    for be in backends:
        stats = replay(topo, args.routing, w, backend=be)
        runs[be] = stats
        ratio = (stats.completion_cycles / stats.ideal_cycles
                 if stats.ideal_cycles else float("nan"))
        print(f"{be}: completion={stats.completion_cycles} "
              f"ideal={stats.ideal_cycles} ratio={ratio:.3f}")
        if stats.completion_cycles < stats.ideal_cycles:
            raise SystemExit(
                f"{be}: measured completion {stats.completion_cycles} "
                f"below the contention-free bound {stats.ideal_cycles} — "
                f"the replay undercounted wire time")
    if args.backend == "both":
        a, b = runs["numpy"], runs["jax"]
        if (a.completion_cycles != b.completion_cycles
                or a.phase_cycles != b.phase_cycles):
            raise SystemExit(
                f"cross-engine replay mismatch: numpy "
                f"completion={a.completion_cycles} "
                f"phases={list(a.phase_cycles or ())} vs jax "
                f"completion={b.completion_cycles} "
                f"phases={list(b.phase_cycles or ())}")
        print("cross-engine replay agrees exactly")
    return 0


def cmd_slo(args) -> int:
    from repro.studies import Study, resolve_spec_source
    spec = resolve_spec_source(args.spec)
    study = Study(spec, backend=args.backend)
    cap = study.slo_capacity(args.experiment, percentile=args.percentile,
                             lo=args.lo, hi=args.hi, tol=args.tol)
    print(f"experiment: {cap['experiment']}")
    print(f"slo: p{cap['percentile']:g} <= {cap['slo']} cycles")
    for load, att in cap["probes"]:
        print(f"  probe load={load}: attainment={att}")
    print(f"capacity: {cap['capacity']}")
    return 0


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(
        prog="python -m repro.workload",
        description=__doc__.splitlines()[0])
    sub = ap.add_subparsers(dest="cmd", required=True)

    ex = sub.add_parser("extract",
                        help="compile a training step and lower it to a "
                             "replayable workload JSON")
    ex.add_argument("--step", choices=list(_STEPS), required=True)
    ex.add_argument("--devices", type=int, required=True,
                    help="host device count (XLA_FLAGS is set for you)")
    ex.add_argument("--dp", type=int, default=1,
                    help="data-parallel axis size for --step moe")
    ex.add_argument("--fabric", default="xor",
                    help="CIN instance to lower onto (default: xor)")
    ex.add_argument("--n", type=int, default=None,
                    help="fabric switch count (default: --devices)")
    ex.add_argument("--bytes-per-packet", type=int, default=8192,
                    help="simulated link payload per cycle")
    ex.add_argument("--lenient", action="store_true",
                    help="skip (rather than fail on) collectives whose "
                         "replica group size mismatches the fabric")
    ex.add_argument("--name", default=None)
    ex.add_argument("-o", "--out", default=None,
                    help="output path (default: "
                         "<step><devices>.workload.json)")
    ex.set_defaults(fn=cmd_extract)

    rp = sub.add_parser("replay",
                        help="replay an extracted workload on the cycle "
                             "engines")
    rp.add_argument("workload", help="workload JSON from extract")
    rp.add_argument("--fabric", default="xor")
    rp.add_argument("--n", type=int, default=None,
                    help="fabric switch count (default: the workload's)")
    rp.add_argument("--routing", default="minimal")
    rp.add_argument("--backend", default="both",
                    choices=["numpy", "jax", "both"])
    rp.set_defaults(fn=cmd_replay)

    sl = sub.add_parser("slo", help="SLO capacity search on a serving spec")
    sl.add_argument("spec", help="spec file path or bundled spec name")
    sl.add_argument("--experiment", default=None,
                    help="experiment name (required unless the spec holds "
                         "exactly one)")
    sl.add_argument("--backend", default=None,
                    help="auto | jax | numpy | flow")
    sl.add_argument("--percentile", type=float, default=99.0)
    sl.add_argument("--lo", type=float, default=0.05)
    sl.add_argument("--hi", type=float, default=2.0)
    sl.add_argument("--tol", type=float, default=0.01)
    sl.set_defaults(fn=cmd_slo)

    args = ap.parse_args(argv)
    return args.fn(args)


if __name__ == "__main__":
    sys.exit(main())
