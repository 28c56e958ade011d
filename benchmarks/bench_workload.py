"""Real-workload benchmarks: extraction, replay, and serving SLOs.

Three measurements of the :mod:`repro.workload` subsystem:

* **Extraction** — wall time to compile an 8-device MoE training step
  (a subprocess, since XLA_FLAGS must precede jax imports) and lower
  its collective sequence into a phased workload.
* **Replay** — the extracted workload through the numpy oracle and the
  compiled engine: completion vs the contention-free bound, exact
  cross-engine agreement, per-backend wall time.
* **Serving** — the bundled ``serving_slo`` spec at cycle (numpy) and
  flow fidelity: request-latency p50/p99, SLO attainment, per-tier
  wall time, plus an ``slo_capacity`` bisection on the CIN-16 Poisson
  experiment.

Results land in a ``workload`` block of ``benchmarks/BENCH_sim.json``
(appended to the artifact ``bench_simulation`` writes — run after it,
as ``benchmarks/run.py`` does).  Quick mode (CI) shrinks the MoE step
to 4 devices and skips the capacity bisection.
"""
from __future__ import annotations

import json
import os
import subprocess
import sys
import time

from repro.fabric import make_fabric
from repro.sim.workloads import Workload, replay

from .common import quick, row

_ARTIFACT = os.path.join(os.path.dirname(__file__), "BENCH_sim.json")
_REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

BYTES_PER_PACKET = 256
SLO_EXPERIMENT = "cin-xor-16/serving-poisson-r0.05/minimal"

_EXTRACT_CHILD = """
import json, sys
devices = int(sys.argv[1])
from repro.workload import moe_step_hlo, workload_from_hlo
hlo = moe_step_hlo(devices, d_model=32, d_ff=16, batch=4, seq=8)
w = workload_from_hlo(hlo, ("xor", devices), bytes_per_packet=%d)
print("RESULT " + json.dumps(w.to_dict()))
""" % BYTES_PER_PACKET


def _extract(devices: int) -> tuple[dict, float]:
    # Extraction emulates the mesh on host devices and times no chip; an
    # accelerator this process may hold stays out of the child's reach.
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               XLA_FLAGS=f"--xla_force_host_platform_device_count={devices}",
               PYTHONPATH="src")
    t0 = time.perf_counter()
    res = subprocess.run(
        [sys.executable, "-c", _EXTRACT_CHILD, str(devices)], env=env,
        capture_output=True, text=True, timeout=600, cwd=_REPO)
    extract_s = time.perf_counter() - t0
    if res.returncode != 0:
        raise RuntimeError(f"extraction failed: {res.stderr[-2000:]}")
    line = [l for l in res.stdout.splitlines()
            if l.startswith("RESULT ")][-1]
    return json.loads(line[len("RESULT "):]), extract_s


def _replay_block(wd: dict) -> dict:
    w = Workload.from_dict(wd)
    topo = make_fabric("xor", w.num_switches).sim_topology()
    out = {}
    for backend in ("numpy", "jax"):
        t0 = time.perf_counter()
        stats = replay(topo, "minimal", w, backend=backend)
        out[backend] = {
            "completion_cycles": int(stats.completion_cycles),
            "ideal_cycles": int(stats.ideal_cycles),
            "replay_s": round(time.perf_counter() - t0, 4),
        }
        assert stats.completion_cycles >= stats.ideal_cycles, backend
    out["agree"] = (out["numpy"]["completion_cycles"]
                    == out["jax"]["completion_cycles"])
    assert out["agree"], f"cross-engine replay mismatch: {out}"
    return out


def _serving_block() -> dict:
    from repro.studies import Study, bundled_spec_path
    spec = bundled_spec_path("serving_slo")
    tiers = {}
    for backend in ("numpy", "flow"):
        t0 = time.perf_counter()
        result = Study(spec, backend=backend).run()
        wall = time.perf_counter() - t0
        rows_ = {}
        for r in result.results:
            e = rows_.setdefault(r.experiment, {
                "requests": 0, "p50": 0.0, "p99": 0.0, "attainment": 1.0})
            e["requests"] += r.request_count or 0
            e["p50"] = max(e["p50"], r.request_latency_p50 or 0.0)
            e["p99"] = max(e["p99"], r.request_latency_p99 or 0.0)
            if r.slo_attainment is not None:
                e["attainment"] = min(e["attainment"], r.slo_attainment)
        tiers[backend] = {"wall_s": round(wall, 4), "experiments": rows_}
    block = {"spec": "serving_slo", "tiers": tiers}
    if not quick():
        study = Study(spec, backend="numpy")
        block["slo_capacity"] = study.slo_capacity(
            SLO_EXPERIMENT, percentile=99.0, lo=0.1, hi=2.0, tol=0.1)
    return block


def rows():
    devices = 4 if quick() else 8
    wd, extract_s = _extract(devices)
    packets = sum(len(p["src"]) * p["messages"] for p in wd["phases"])
    replay_b = _replay_block(wd)
    serving = _serving_block()
    block = {
        "quick": quick(),
        "extract": {
            "step": "moe", "devices": devices,
            "bytes_per_packet": BYTES_PER_PACKET,
            "phases": len(wd["phases"]), "packets": packets,
            "extract_s": round(extract_s, 3),
        },
        "replay": replay_b,
        "serving": serving,
    }
    payload = {}
    if os.path.exists(_ARTIFACT):
        with open(_ARTIFACT) as f:
            payload = json.load(f)
    payload["workload"] = block
    with open(_ARTIFACT, "w") as f:
        json.dump(payload, f, indent=2, sort_keys=True)
        f.write("\n")

    out = [row(f"sim/workload/extract/moe{devices}", extract_s * 1e6,
               f"phases={len(wd['phases'])} packets={packets}")]
    for backend in ("numpy", "jax"):
        b = replay_b[backend]
        out.append(row(
            f"sim/workload/replay/{backend}", b["replay_s"] * 1e6,
            f"completion={b['completion_cycles']} "
            f"ideal={b['ideal_cycles']}"))
    for backend, tier in serving["tiers"].items():
        for name, e in sorted(tier["experiments"].items()):
            out.append(row(
                f"sim/workload/serving/{backend}/{name}", 0.0,
                f"requests={e['requests']} p99={e['p99']} "
                f"att={e['attainment']}"))
    if "slo_capacity" in serving:
        cap = serving["slo_capacity"]
        out.append(row("sim/workload/slo_capacity", 0.0,
                       f"exp={cap['experiment']} capacity={cap['capacity']}"))
    return out


def main():
    from .common import emit
    emit(rows())


if __name__ == "__main__":
    main()
