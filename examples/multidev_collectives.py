import os
os.environ.setdefault("XLA_FLAGS", "--xla_force_host_platform_device_count=8")
"""Multi-device demo: LACIN-scheduled collectives + explicit-DP training.

    PYTHONPATH=src python examples/multidev_collectives.py

Runs on 8 host devices: (1) compares the XOR/Circle/cyclic step schedules
against lax.psum on an all-reduce; (2) trains a tiny LM where the gradient
all-reduce is the paper's 1-factor schedule (optionally int8-compressed).
"""
import time

import numpy as np

import jax
import jax.numpy as jnp
from jax import shard_map
from jax.sharding import Mesh, PartitionSpec as P

from repro.core import make_schedule
from repro.fabric import LacinCollectives


def bench_allreduce(mesh, n):
    x = jax.random.normal(jax.random.PRNGKey(0), (n, 1 << 20))
    rows = []
    for inst in ("xor", "circle", "cyclic"):
        coll = LacinCollectives(mesh=mesh, instance=inst)
        f = jax.jit(shard_map(
            lambda xl, c=coll: c.all_reduce(xl[0], "x")[None],
            mesh=mesh, in_specs=P("x"), out_specs=P("x")))
        jax.block_until_ready(f(x))
        t0 = time.perf_counter()
        for _ in range(5):
            jax.block_until_ready(f(x))
        rows.append((inst, (time.perf_counter() - t0) / 5 * 1e3))
    f = jax.jit(shard_map(lambda xl: jax.lax.psum(xl[0], "x")[None],
                          mesh=mesh, in_specs=P("x"), out_specs=P("x")))
    jax.block_until_ready(f(x))
    t0 = time.perf_counter()
    for _ in range(5):
        jax.block_until_ready(f(x))
    rows.append(("xla_psum", (time.perf_counter() - t0) / 5 * 1e3))
    return rows


def main():
    devs = jax.devices()
    n = len(devs)
    mesh = Mesh(np.array(devs), ("x",))
    print(f"devices: {n}")

    s = make_schedule("auto", n)
    print(f"schedule: {s.instance}, {s.num_steps} steps, "
          f"matching/step={s.is_matching_per_step()}")

    print("\nall-reduce of 4 MiB x 8 shards:")
    for name, ms in bench_allreduce(mesh, n):
        print(f"  {name:9s} {ms:7.2f} ms")

    # hierarchical: two-level Dragonfly-style all-reduce on a (2, 4) mesh
    if n == 8:
        import jax.numpy as jnp
        mesh2 = Mesh(np.array(devs).reshape(2, 4), ("g", "l"))
        coll = LacinCollectives(mesh=mesh2)
        x = jnp.ones((n, 1 << 10))
        y = shard_map(lambda xl: coll.all_reduce_two_level(xl[0], "l", "g")[None],
                      mesh=mesh2, in_specs=P(("g", "l")),
                      out_specs=P(("g", "l")))(x)
        print(f"\ntwo-level all-reduce on (g=2, l=4): sum={float(y[0,0]):.0f} "
              f"(expect {n})")

    print("\nexplicit-DP training with LACIN gradient all-reduce:")
    from repro.models import get_config
    from repro.optim import OptConfig
    from repro.runtime.manual_dp import make_manual_dp_train_step
    from repro.runtime.trainer import init_train_state

    cfg = get_config("lacin-demo").reduced()
    rng = np.random.default_rng(0)
    tok = jnp.asarray(rng.integers(0, cfg.vocab_size, (n * 2, 32)), jnp.int32)
    batch = {"tokens": tok, "labels": tok}
    for compress in (False, True):
        step = make_manual_dp_train_step(
            cfg, mesh, OptConfig(lr=1e-3), axis_name="x", compress=compress)
        # fresh state per run: the step donates its input buffers
        st = init_train_state(jax.random.PRNGKey(0), cfg)
        losses = []
        for _ in range(5):
            st, m = step(st, batch)
            losses.append(float(m["loss"]))
        tag = "int8-compressed" if compress else "fp32"
        print(f"  {tag:16s} losses: " + " ".join(f"{l:.3f}" for l in losses))
    print("done.")


if __name__ == "__main__":
    main()
