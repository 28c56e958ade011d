"""Paper §2 refs [8,9] applied: LACIN-scheduled collectives vs XLA's.

Runs in a subprocess with 8 host devices (the bench harness itself keeps
the default single-device environment).  Measures wall time of the XOR /
Circle / cyclic(anisoport) ppermute schedules against lax.psum /
lax.all_to_all for a few payload sizes, and counts the collective-permute
steps in the compiled HLO (must be N-1 per matching schedule).
"""
from __future__ import annotations

import json
import os
import subprocess
import sys

from .common import row

_CHILD = r"""
import json, time
import numpy as np, jax, jax.numpy as jnp
from jax.sharding import Mesh, PartitionSpec as P
from jax import shard_map
from repro.core import all_reduce_lacin, all_to_all_lacin

devs = jax.devices(); n = len(devs)
mesh = Mesh(np.array(devs), ("x",))
out = []

def timeit(fn, *args):
    fn(*args)[0].block_until_ready() if isinstance(fn(*args), tuple) else None
    r = fn(*args); jax.block_until_ready(r)
    best = 1e9
    for _ in range(10):
        t0 = time.perf_counter(); jax.block_until_ready(fn(*args))
        best = min(best, time.perf_counter() - t0)
    return best * 1e6

for size in (1 << 16, 1 << 20, 1 << 22):
    x = jnp.arange(n * size, dtype=jnp.float32).reshape(n, size)
    for inst in ("xor", "circle", "cyclic"):
        f = jax.jit(shard_map(
            lambda xl, inst=inst: all_reduce_lacin(xl[0], "x", axis_size=n,
                                                   instance=inst)[None],
            mesh=mesh, in_specs=P("x"), out_specs=P("x")))
        us = timeit(f, x)
        out.append((f"collective/all_reduce/{inst}/{4*size}B", us, "lacin"))
    f = jax.jit(shard_map(lambda xl: jax.lax.psum(xl[0], "x")[None],
                          mesh=mesh, in_specs=P("x"), out_specs=P("x")))
    us = timeit(f, x)
    out.append((f"collective/all_reduce/xla_psum/{4*size}B", us, "xla"))

# hierarchical schedules on a HyperX/Dragonfly-shaped (2, 4) mesh:
# dimension-order grid all-to-all and two-level all-reduce
from repro.fabric import LacinCollectives
mesh2d = Mesh(np.array(devs).reshape(2, 4), ("g", "l"))
coll = LacinCollectives(mesh=mesh2d)
for size in (1 << 16, 1 << 20):
    x = jnp.arange(n * size, dtype=jnp.float32).reshape(n, size)
    f = jax.jit(shard_map(
        lambda xl: coll.all_reduce_two_level(xl[0], "l", "g")[None],
        mesh=mesh2d, in_specs=P(("g", "l")), out_specs=P(("g", "l"))))
    out.append((f"collective/two_level_all_reduce/2x4/{4*size}B",
                timeit(f, x), "local RS -> global AR -> local AG"))
    xa = jnp.arange(n * n * (size // n), dtype=jnp.float32).reshape(
        n, n, size // n)
    f = jax.jit(shard_map(
        lambda xl: coll.all_to_all_grid(xl[0], ("g", "l"))[None],
        mesh=mesh2d, in_specs=P(("g", "l")), out_specs=P(("g", "l"))))
    out.append((f"collective/grid_a2a/2x4/{4*size}B",
                timeit(f, xa), "per-dimension LACIN schedules, composed"))

# step counts in HLO: N-1 ppermutes per matching collective chain
import re
def count_cp(inst):
    f = jax.jit(shard_map(
        lambda xl: all_to_all_lacin(xl[0], "x", axis_size=n,
                                    instance=inst)[None],
        mesh=mesh, in_specs=P("x"), out_specs=P("x")))
    txt = f.lower(jax.ShapeDtypeStruct((n, n, 64), jnp.float32)).compile().as_text()
    # match op instances only — the bare name also appears in metadata
    return len(re.findall(r"collective-permute\(", txt))
for inst in ("xor", "circle"):
    out.append((f"collective/a2a_steps_hlo/{inst}", float(count_cp(inst)),
                f"expect {n-1}"))
print(json.dumps(out))
"""


def rows():
    # Host-device emulation: the child measures HLO and CPU collectives,
    # never an accelerator this process may already hold.
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               XLA_FLAGS="--xla_force_host_platform_device_count=8",
               PYTHONPATH=os.environ.get("PYTHONPATH", "src"))
    res = subprocess.run([sys.executable, "-c", _CHILD], env=env,
                         capture_output=True, text=True, timeout=600)
    if res.returncode != 0:
        return [row("collective/subprocess", 0.0,
                    f"FAILED: {res.stderr[-300:]}")]
    data = json.loads(res.stdout.strip().splitlines()[-1])
    return [row(name, us, derived) for name, us, derived in data]


def main():
    from .common import emit
    emit(rows())


if __name__ == "__main__":
    main()
