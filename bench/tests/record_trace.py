"""Record the small device trace that ``test_trace.py`` reads.

    python bench/tests/record_trace.py <out_dir>

Run on a TPU host.  It traces a few calls of a small jitted program, each
inside the harness's own host spans (``setup`` once, then ``grid``
around each call, with host-only sleeps between them so the trace holds
idle gaps), copies the ``.xplane.pb`` to ``<out_dir>/small.xplane.pb``
and prints every plane and line with a few events, so that the layout
the reduction relies on can be read.
"""
from __future__ import annotations

import glob
import os
import shutil
import sys
import tempfile
import time


def main(out_dir: str) -> int:
    import jax
    import jax.numpy as jnp
    from jax.profiler import ProfileData

    dev = jax.devices()[0]
    print(f"device: {dev.platform} {dev.device_kind} {len(jax.devices())}")
    if dev.platform != "tpu":
        return 1
    f = jax.jit(lambda x: jnp.tanh(x @ x) + 1.0)
    x = jnp.ones((1024, 1024), jnp.float32)
    f(x).block_until_ready()
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    tmp = tempfile.mkdtemp()
    jax.profiler.start_trace(tmp, profiler_options=opts)
    with jax.profiler.TraceAnnotation("setup"):
        time.sleep(0.003)
    for _ in range(4):
        with jax.profiler.TraceAnnotation("grid"):
            y = f(x)
            for _ in range(3):
                y = f(y)
            y.block_until_ready()
            time.sleep(0.002)
        time.sleep(0.001)
    jax.profiler.stop_trace()
    path = glob.glob(os.path.join(tmp, "**", "*.xplane.pb"), recursive=True)[0]
    os.makedirs(out_dir, exist_ok=True)
    shutil.copy(path, os.path.join(out_dir, "small.xplane.pb"))
    print(f"trace: {os.path.getsize(path)} bytes")
    for plane in ProfileData.from_file(path).planes:
        print(f"PLANE {plane.name!r}")
        for line in plane.lines:
            events = list(line.events)
            print(f"  LINE {line.name!r}: {len(events)} events")
            for ev in events[:4]:
                print(f"    {ev.name!r} start={ev.start_ns} "
                      f"dur={ev.duration_ns} stats={dict(ev.stats)}")
    shutil.rmtree(tmp, ignore_errors=True)
    return 0


if __name__ == "__main__":
    sys.exit(main(sys.argv[1]))
