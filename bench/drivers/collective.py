"""Collective cells: back-to-back LACIN all-reduces of gradient buckets
on a mesh of the cell's chips, through ``LacinCollectives(mesh)`` inside
``jax.shard_map``.

Set-up makes ``rotation`` buckets of ``bucket_mib`` MiB of float32 per
chip on the devices, in one jitted call from the seed, and runs one warm
call of each.  The window issues calls back to back, call i on bucket
``i % rotation``, with at most ``in_flight`` calls outstanding, and
stops its clock when the call that crosses ``seconds`` has completed.
A traced run's window lasts at most ``TRACED_S`` seconds.

``correct`` compares, on every chip, the outputs of a sample of the
window's calls drawn from the seed (a uniform sample of ``sample``
calls, kept as the window runs, and the last call)
with the float64 sum of the four chips' buckets.  The number compared is
the largest error over all elements, as a share of the reference sum's
root mean square.
"""
from __future__ import annotations

import time

import numpy as np

import harness

MIB = 1 << 20
TRACED_S = 1.0


def buckets(mesh, n: int, elems: int, rotation: int, seed: int):
    """``rotation`` arrays of shape (n, elems), row i on chip i."""
    import jax
    from jax.sharding import NamedSharding, PartitionSpec as P
    shard = NamedSharding(mesh, P("x"))

    @jax.jit
    def make(key):
        keys = jax.random.split(key, rotation)
        return tuple(jax.lax.with_sharding_constraint(
            jax.random.normal(k, (n, elems), np.float32), shard)
            for k in keys)

    return make(jax.random.key(seed))


def all_reduce_fn(mesh, coll):
    import jax
    from jax.sharding import PartitionSpec as P

    def local(b):
        return coll.all_reduce(b[0], "x")[None]

    return jax.jit(jax.shard_map(local, mesh=mesh, in_specs=P("x"),
                                 out_specs=P("x")))


def reference_gap(outputs, inputs, *, dtype=np.float64) -> float:
    """Largest |output - sum of the buckets| over every chip's output,
    as a share of the sum's root mean square; the sum is taken in
    ``dtype`` (float64 for the reference, lower for the control)."""
    want = np.asarray(inputs).astype(dtype).sum(axis=0, dtype=dtype)
    want = want.astype(np.float64)
    rms = float(np.sqrt(np.mean(want * want)))
    got = np.asarray(outputs, dtype=np.float64)
    return float(np.max(np.abs(got - want[None, :]))) / rms


def run(cell, devices, *, seed: int, seconds: float, trace: bool,
        start: float, trace_dir: str, trace_out: str | None = None
        ) -> dict:
    import jax
    from jax.sharding import Mesh
    from repro.fabric import LacinCollectives

    cfg, tr = cell.config, cell.traffic
    n = len(devices)
    elems = int(float(cfg["bucket_mib"]) * MIB) // 4
    rotation = int(tr["rotation"])
    depth = int(tr["in_flight"])
    with harness.span("setup"):
        mesh = Mesh(np.array(devices), ("x",))
        coll = LacinCollectives(mesh=mesh, instance=cfg["instance"])
        f = all_reduce_fn(mesh, coll)
        bufs = buckets(mesh, n, elems, rotation, seed)
        jax.block_until_ready([f(b) for b in bufs])

    rng = np.random.default_rng(seed)
    sample = int(tr["sample"])
    kept = {}
    limit = min(seconds, TRACED_S) if trace else seconds
    prof = harness.Profiler(trace, trace_dir, trace_out)
    with prof:
        setup_s = time.time() - start
        with harness.span("window"):
            pending = []
            t0 = time.perf_counter()
            i = 0
            while True:
                with harness.span("allreduce"):
                    out = f(bufs[i % rotation])
                pending.append(out)
                # Reservoir sampling: every call is kept with the same
                # chance, at most ``sample`` outputs held at a time.
                if i < sample:
                    kept[i] = out
                else:
                    j = int(rng.integers(i + 1))
                    if j < sample:
                        del kept[sorted(kept)[j]]
                        kept[i] = out
                if len(pending) > depth:
                    pending.pop(0).block_until_ready()
                i += 1
                if time.perf_counter() - t0 >= limit:
                    break
            jax.block_until_ready(pending)
            t1 = time.perf_counter()
    kept[i - 1] = out
    calls = i
    window_s = t1 - t0
    dev = harness.device_block(devices)
    harness.log(f"window: {calls} calls in {window_s:.3f}s, "
                f"{len(kept)} outputs kept for the check")

    with harness.span("check"):
        inputs = [np.asarray(b) for b in bufs]
        gap = max(reference_gap(np.asarray(o), inputs[j % rotation])
                  for j, o in kept.items())
    ok, numbers = harness.checked({"sum_error": gap}, cell.limits["limits"])

    bucket_bytes = elems * 4
    out = {"correct": bool(ok), "attempted": calls, "failed": 0}
    if not trace:
        out["metrics"] = {
            "setup_s": {"value": setup_s, "unit": "s"},
            "allreduce_gbps": {"value": bucket_bytes * calls / window_s
                               / 1e9, "unit": "GB/s"}}
    else:
        from tracereduce import reduce_trace
        summary = reduce_trace(prof.path(), num_devices=n)
        ctx = {"summary": summary, "calls": calls, "chips": n,
               "bucket_bytes": bucket_bytes,
               "peaks": harness.peaks(dev["kind"])}
        out["metrics"] = harness.read_metrics(cell, ctx)
        if summary is not None:
            dev["busy_s"] = summary.busy_s
            dev["window_s"] = summary.window_s
            out["breakdown"] = summary.breakdown()
    out["device"] = dev
    out["check"] = numbers
    return out
