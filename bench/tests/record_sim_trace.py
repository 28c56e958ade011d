"""Record the small simulator trace that ``test_scopes.py`` reads.

    python bench/tests/record_sim_trace.py <out_dir>

Run on a TPU host.  A CIN-16 (xor) study, adaptive routing, 2 loads x 1
seed, 32 cycles: one ``Study.run`` acquires the program outside the
trace, then a second, on another seed, runs under the profiler inside
the harness's ``window`` and ``grid`` spans, as a traced benchmark
window does.  Writes ``<out_dir>/cin16.xplane.pb.gz`` (the trace holds
the program's HLO, so it is kept compressed) and
``<out_dir>/cin16.scopes.json``: the scope map
(``repro.obs.telemetry.scope_maps``) of the traced program, cut to the
instructions the trace shows.  Prints the module names of the trace and
of the map, and the reduction (``bench/scopereduce.py``) of the trace.
"""
from __future__ import annotations

import glob
import gzip
import json
import os
import shutil
import sys
import tempfile

HERE = os.path.dirname(os.path.abspath(__file__))
BENCH = os.path.dirname(HERE)
sys.path[:0] = [BENCH, os.path.join(os.path.dirname(BENCH), "src")]


def spec(seed: int):
    from repro.studies import ExperimentSpec
    return ExperimentSpec(
        fabric={"kind": "cin", "params": {"instance": "xor", "n": 16}},
        traffic={"pattern": "uniform"}, routing={"policy": "adaptive"},
        sweep={"loads": [0.3, 0.6], "seeds": [seed], "cycles": 32,
               "warmup": 8},
        terminals=4, name="cin16")


def main(out_dir: str) -> int:
    os.environ["JAX_COMPILATION_CACHE_DIR"] = ""
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    import jax
    from jax.profiler import ProfileData
    from repro.obs import telemetry
    from repro.studies import Study
    from scopereduce import reduce_scopes

    dev = jax.devices()[0]
    print(f"device: {dev.platform} {dev.device_kind} {len(jax.devices())}")
    if dev.platform != "tpu":
        return 1
    Study(spec(1), backend="jax").run()
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    tmp = tempfile.mkdtemp()
    jax.profiler.start_trace(tmp, profiler_options=opts)
    with jax.profiler.TraceAnnotation("window"):
        with jax.profiler.TraceAnnotation("grid"):
            res = Study(spec(2), backend="jax").run()
    jax.profiler.stop_trace()
    path = glob.glob(os.path.join(tmp, "**", "*.xplane.pb"),
                     recursive=True)[0]
    os.makedirs(out_dir, exist_ok=True)
    with open(path, "rb") as src, gzip.open(
            os.path.join(out_dir, "cin16.xplane.pb.gz"), "wb", 9) as dst:
        shutil.copyfileobj(src, dst)
    print(f"trace: {os.path.getsize(path)} bytes")
    print(f"timing: {res.results[0].stats.timing}")

    shown, modules = set(), set()
    for plane in ProfileData.from_file(path).planes:
        if not plane.name.startswith("/device:TPU:"):
            continue
        for line in plane.lines:
            for ev in line.events:
                if line.name == "XLA Ops":
                    shown.add(ev.name.split(" = ", 1)[0])
                elif line.name == "XLA Modules":
                    modules.add(ev.name)
    maps = telemetry.scope_maps()
    print(f"trace modules: {sorted(modules)}")
    print(f"scope map keys: {sorted(maps)}")
    cut = {key: {k: v for k, v in m.items() if k in shown}
           for key, m in maps.items()}
    with open(os.path.join(out_dir, "cin16.scopes.json"), "w") as f:
        json.dump(cut, f, indent=0, sort_keys=True)
    s = reduce_scopes(path, maps)
    print(f"clock_offset_ms: {s.clock_offset_ms}")
    print(f"leaf_s: {s.leaf_s}")
    print(f"scope_s: {s.scope_s}")
    print(f"span_s: {s.span_s}")
    print(f"breakdown: {json.dumps(s.breakdown(20))}")
    shutil.rmtree(tmp, ignore_errors=True)
    return 0


if __name__ == "__main__":
    sys.exit(main(sys.argv[1]))
