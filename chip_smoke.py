"""Bring-up smoke run of the compiled simulator on a TPU.

    python chip_smoke.py             # one chip: the five phases below
    python chip_smoke.py --chips 4   # four chips: sharded sweep + collectives

One process drives everything (a chip belongs to one process at a time).
The one-chip run, in order:

1. device gate: JAX's first device must be a TPU, or the run exits 1;
2. exactness at full fabric size: a drained one-shot all-to-all on the
   1040-switch Dragonfly (a16 p8 h8 g65) delivers every packet, and its
   link loads equal the closed form ``core.dragonfly_link_loads``;
3. the main path: ``studies.Study(spec, backend="jax").run()`` on that
   fabric (uniform, minimal, 4 loads x 2 seeds, 512 cycles), then again
   after the in-process cache is cleared -- every record identical and
   the program restored from the persistent cache on disk;
4. the numpy oracle as reference: the bundled ``collective_replay`` and
   ``cin16_saturation`` specs on both engines;
5. the flow tier: the jitted max-min solver against the numpy core on a
   1k-switch incidence.

``--chips 4`` runs only what exists across chips: the Dragonfly-1040
grid sharded over four chips against the one-chip program, and the LACIN
collectives on a four-chip mesh against their ``lax`` references.

Every phase prints its checks; the times printed are smoke figures, not
benchmark results.  The last line is ``{"ok": true, "device": {...}}``
only when every phase passed; any failure exits non-zero without it.
"""
from __future__ import annotations

import argparse
import dataclasses
import json
import os
import sys
import time
import traceback

_ROOT = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, os.path.join(_ROOT, "src"))

#: The largest deployment the cycle tier reaches today: 1040 switches,
#: 8320 terminals (``benchmarks/bench_compile.py``, ``xl_scale``).
DRAGONFLY_1040 = {"group_size": 16, "terminals_per_switch": 8,
                  "global_ports_per_switch": 8, "num_groups": 65}
LOADS = (0.1, 0.3, 0.5, 0.7)
SEEDS = (0, 1)
CYCLES = 512
#: Max-min rates: the tolerance ``tests/test_flow.py`` holds the jitted
#: solver to against the numpy core.
FLOW_ATOL = 1e-9
#: accepted <= offered, up to this many standard deviations of the
#: window's arrival count (Bernoulli arrivals: sigma/mean is
#: 1/sqrt(expected packets in the window)).
ACCEPTED_SIGMAS = 5.0


class CheckFailed(AssertionError):
    pass


def log(msg: str) -> None:
    print(msg, flush=True)


def check(ok: bool, what: str) -> None:
    log(f"  [{'ok' if ok else 'FAIL'}] {what}")
    if not ok:
        raise CheckFailed(what)


def device_gate(chips: int):
    import jax
    devs = jax.devices()
    dev = devs[0]
    log(f"device: platform={dev.platform} kind={dev.device_kind} "
        f"count={len(devs)}")
    if dev.platform != "tpu":
        log("device gate: no TPU visible; this run never falls back to "
            "another backend")
        return None
    if len(devs) < chips:
        log(f"device gate: {chips} chips asked for, {len(devs)} visible")
        return None
    return dev


def _runstats_fields_equal(a, b) -> list[str]:
    """Names of RunStats result fields (not timing/trace) that differ."""
    import numpy as np
    from repro.sim.metrics import RunStats
    bad = []
    for f in dataclasses.fields(RunStats):
        if not f.compare:
            continue
        x, y = getattr(a, f.name), getattr(b, f.name)
        if isinstance(x, np.ndarray) or isinstance(y, np.ndarray):
            same = np.array_equal(np.asarray(x), np.asarray(y))
        else:
            same = x == y
        if not same:
            bad.append(f.name)
    return bad


# ---------------------------------------------------------------------------
# One-chip phases.
# ---------------------------------------------------------------------------

def phase_exactness(params: dict) -> None:
    """Drained one-shot all-to-all: every packet delivered, and the link
    loads equal the closed form link for link."""
    import numpy as np
    from repro import sim
    from repro.core import DragonflyConfig, dragonfly_link_loads
    from repro.sim.link import LinkTable
    cfg = DragonflyConfig(**params)
    topo = sim.dragonfly_topology(cfg)
    n = topo.num_switches
    t0 = time.perf_counter()
    stats = sim.simulate(topo, sim.MinimalPolicy(),
                         sim.one_shot_all_to_all(
                             n, terminals=cfg.terminals_per_switch),
                         backend="jax", drain=True)
    wall = time.perf_counter() - t0
    t = stats.timing
    log(f"  smoke figures: wall={wall:.3f}s compile_s={t['compile_s']} "
        f"execute_s={t['execute_s']}")
    check(stats.packets_delivered == stats.packets_generated == n * (n - 1),
          f"{n} switches: delivered {stats.packets_delivered} == "
          f"{n}*{n - 1}")
    check(stats.in_flight_at_end == 0, "drained: nothing left in flight")

    table = LinkTable(topo, 1)
    loads = np.asarray(stats.link_loads)
    check(int(loads[~table.wired].sum()) == 0, "no load on unwired ports")
    used = np.flatnonzero(loads > 0)
    s, d = table.endpoints(used)
    got: dict[tuple[int, int], int] = {}
    for a, b, c in zip(s.tolist(), d.tolist(), loads[used].tolist()):
        got[(a, b)] = got.get((a, b), 0) + int(c)
    cf = dragonfly_link_loads(cfg)
    a = cfg.group_size
    want: dict[tuple[int, int], int] = {}
    for (grp, s_, t_), v in cf["local"].items():
        key = (grp * a + s_, grp * a + t_)
        want[key] = want.get(key, 0) + v
    for (ga, gb), v in cf["global"].items():
        sa, _ = cfg.global_port_owner(ga, gb)
        sb, _ = cfg.global_port_owner(gb, ga)
        key = (ga * a + sa, gb * a + sb)
        want[key] = want.get(key, 0) + v
    check(got == want, f"link loads equal the closed form on all "
          f"{len(want)} loaded directed links")


def study_spec(params: dict, loads, seeds, cycles: int):
    from repro.studies import ExperimentSpec
    return ExperimentSpec(
        fabric={"kind": "dragonfly", "params": dict(params)},
        traffic={"pattern": "uniform"}, routing={"policy": "minimal"},
        sweep={"loads": list(loads), "seeds": list(seeds),
               "cycles": cycles},
        terminals=params["terminals_per_switch"])


def _records(result) -> list[dict]:
    return [{k: v for k, v in r.record().items() if k != "provenance"}
            for r in result.results]


def phase_study(spec) -> None:
    """The user's path, twice: a fresh run, then a rerun whose program
    comes back from the persistent cache on disk."""
    from repro import studies
    from repro.obs import telemetry
    log(f"  compile cache: {telemetry.cache_dir()}")
    telemetry.reset_cache_stats()
    first = studies.Study(spec, backend="jax").run()
    t = first.results[0].provenance["timings"]
    n = spec.fabric.num_switches
    copies = len(first.results)
    cycles = spec.sweep.cycles
    log(f"  smoke figures (run 1): compile_cached={t['compile_cached']} "
        f"compile_s={t['compile_s']} execute_s={t['execute_s']} "
        f"-> {cycles / t['execute_s']:.1f} simulated cycles/s for "
        f"{copies} copies, "
        f"{copies * n * cycles / t['execute_s']:.4g} switch-cycles/s")
    log(f"  device: {first.results[0].provenance['device']}")
    for r in first.results:
        check(r.packets_delivered + r.in_flight_at_end
              <= r.packets_generated and r.in_flight_at_end >= 0,
              f"load={r.load} seed={r.seed}: delivered "
              f"{r.packets_delivered} + in flight {r.in_flight_at_end} <= "
              f"generated {r.packets_generated}")
        expected = r.offered * r.num_switches * r.terminals * (
            r.cycles - r.warmup)
        bound = r.offered * (1 + ACCEPTED_SIGMAS / expected ** 0.5)
        check(r.accepted <= bound,
              f"load={r.load} seed={r.seed}: accepted {r.accepted} <= "
              f"offered {r.offered} (+{ACCEPTED_SIGMAS:g} sigma: {bound:.6f})")

    telemetry.clear_caches(memory=True)
    second = studies.Study(spec, backend="jax").run()
    t2 = second.results[0].provenance["timings"]
    stats = telemetry.cache_stats()
    log(f"  smoke figures (run 2): compile_cached={t2['compile_cached']} "
        f"compile_s={t2['compile_s']} execute_s={t2['execute_s']}")
    log(f"  cache stats: {stats}")
    check(all(r.provenance["timings"]["compile_cached"] == "disk"
              for r in second.results),
          "rerun after clearing memory: compile_cached == 'disk'")
    check(stats["disk_errors"] == 0, "disk_errors == 0")
    check(_records(first) == _records(second),
          f"all {copies} records identical across the two runs")


def phase_oracle() -> None:
    """Two bundled specs on the chip and on the numpy oracle."""
    from repro import studies
    path = studies.bundled_spec_path("collective_replay")
    jx = studies.Study(path, backend="jax").run()
    np_ = studies.Study(path, backend="numpy").run()
    rj, rn = jx.replay_points(), np_.replay_points()
    for exp in jx.experiments:
        kind, policy = exp.fabric.kind, exp.routing.policy
        a, b = rj[exp.name], rn[exp.name]
        log(f"  {exp.name}: jax {a} numpy {b}")
        if policy != "minimal":
            # Adaptive choices draw on each engine's own RNG stream.
            check(a["measured"] >= a["ideal"] and b["measured"] >= b["ideal"],
                  f"{kind} {policy} replay: no faster than the ideal "
                  f"{a['ideal']} cycles on either engine")
        elif kind in ("cin", "hyperx"):
            check(a["measured"] == a["ideal"] and b["measured"] == b["ideal"],
                  f"{kind} minimal replay completes at the ideal "
                  f"{a['ideal']} cycles on both engines")
        else:
            check(a["measured"] == b["measured"],
                  f"{kind} minimal replay: equal completion on both "
                  f"engines ({a['measured']})")

    path = studies.bundled_spec_path("cin16_saturation")
    kj = studies.Study(path, backend="jax").run().saturation_points()
    kn = studies.Study(path, backend="numpy").run().saturation_points()
    log(f"  cin16 knees: jax {kj} numpy {kn}")
    check(kj == kn, "cin16_saturation knees agree")


def flow_problem():
    """The 1k-switch Dragonfly incidence of ``benchmarks/bench_flow.py``
    (uniform demand at its timed point), solved by the numpy core."""
    from repro.core import DragonflyConfig
    from repro.flow import FlowParams, pattern_demands, solve_flows
    from repro.sim import dragonfly_topology
    topo = dragonfly_topology(DragonflyConfig(
        group_size=16, terminals_per_switch=16, global_ports_per_switch=8,
        num_groups=64))
    params = FlowParams(solver="numpy")
    src, dst, rate = pattern_demands(topo, "uniform", 0.6, 16, params, None)
    return solve_flows(topo, "minimal", src, dst, rate, params=params)


def phase_flow(sol) -> None:
    import numpy as np
    from repro.flow import maxmin_rates
    p = sol.problem
    log(f"  incidence: {p.link_ids.size} entries, {p.demand.size} flows")
    t0 = time.perf_counter()
    rj = maxmin_rates(p.demand, p.link_ids, p.flow_ptr, sol.capacity,
                      max_iters=sol.params.max_iters, solver="jax")
    log(f"  smoke figure: jax solve {time.perf_counter() - t0:.3f}s "
        f"(compile included)")
    err = float(np.max(np.abs(rj - sol.rates)))
    check(np.isfinite(rj).all() and err <= FLOW_ATOL,
          f"jax rates match numpy: max |diff| {err:.3g} <= {FLOW_ATOL}")


# ---------------------------------------------------------------------------
# Four-chip phases.
# ---------------------------------------------------------------------------

def phase_sharded_sweep(params: dict, loads, seeds, cycles: int,
                        ndev: int) -> None:
    """The grid sharded over ``ndev`` chips, field for field against the
    one-chip program, with one block of copies on each chip."""
    import jax
    import numpy as np
    from repro import sim
    from repro.core import DragonflyConfig
    from repro.sim import xengine
    cfg = DragonflyConfig(**params)
    topo = sim.dragonfly_topology(cfg)
    term = cfg.terminals_per_switch

    def tf(load, seed):
        return sim.uniform(topo.num_switches, offered=load, cycles=cycles,
                           terminals=term, seed=seed)

    def run(devices):
        return xengine.sweep(topo, "minimal", tf, loads, seeds=seeds,
                             terminals=term, cycles=cycles, devices=devices)

    one = run(None)
    log(f"  one chip: {one[0][0].timing}")
    # Keep the sharded program's device outputs to see where they live.
    seen = {}
    timed = xengine.timed_compiled

    def spy(fn, static_arg, *args, **kw):
        out, timing = timed(fn, static_arg, *args, **kw)
        seen["out"] = out
        return out, timing

    xengine.timed_compiled = spy
    try:
        many = run(ndev)
    finally:
        xengine.timed_compiled = timed
    log(f"  {ndev} chips: {many[0][0].timing}")
    shards = seen["out"]["delivered_total"].addressable_shards
    devs = {s.device for s in shards}
    check(len(devs) == ndev and all(s.data.shape[0] == 1 for s in shards),
          f"one block of copies on each of {ndev} chips")
    per_chip = [int(np.asarray(s.data).sum()) for s in shards]
    check(all(c > 0 for c in per_chip),
          f"every chip delivered packets: {per_chip}")
    bad = [(i, j, _runstats_fields_equal(a, b))
           for i, (ra, rb) in enumerate(zip(one, many))
           for j, (a, b) in enumerate(zip(ra, rb))]
    bad = [x for x in bad if x[2]]
    check(not bad, f"every RunStats field identical over "
          f"{len(loads) * len(seeds)} points{'' if not bad else f': {bad}'}")
    for d in jax.devices()[:ndev]:
        ms = d.memory_stats() or {}
        log(f"  {d}: bytes_in_use={ms.get('bytes_in_use')} "
            f"peak_bytes_in_use={ms.get('peak_bytes_in_use')}")


def phase_collectives(ndev: int) -> None:
    """LACIN collectives on an ``ndev``-chip mesh against ``lax``."""
    import jax
    import jax.numpy as jnp
    import numpy as np
    from jax import lax, shard_map
    from jax.sharding import Mesh, PartitionSpec as P
    from repro.fabric import LacinCollectives
    mesh = Mesh(np.array(jax.devices()[:ndev]), ("x",))
    coll = LacinCollectives(mesh=mesh)
    n = ndev

    def sm(f, x):
        return shard_map(lambda xl: f(xl[0])[None], mesh=mesh,
                         in_specs=P("x"), out_specs=P("x"))(x)

    rng = np.random.default_rng(0)
    x = jax.random.normal(jax.random.PRNGKey(0), (n, n, 3, 2))
    got = sm(lambda xl: coll.all_to_all(xl, "x"), x)
    ref = sm(lambda xl: lax.all_to_all(xl[:, None], "x", split_axis=0,
                                       concat_axis=0).reshape(n, 3, 2), x)
    check(bool(jnp.array_equal(got, ref)), "all_to_all bit-identical")

    xs = jax.random.normal(jax.random.PRNGKey(1), (n, 4, 3))
    got = sm(lambda xl: coll.all_gather(xl, "x"), xs)
    ref = sm(lambda xl: lax.all_gather(xl, "x"), xs)
    check(bool(jnp.array_equal(got, ref)), "all_gather bit-identical")

    # Integer-valued floats: every summation order gives the same sum.
    xr = jnp.asarray(rng.integers(-8, 8, (n, n, 5)), jnp.float32)
    got = sm(lambda xl: coll.reduce_scatter(xl, "x"), xr)
    ref = sm(lambda xl: lax.psum(xl, "x")[lax.axis_index("x")], xr)
    check(bool(jnp.array_equal(got, ref)), "reduce_scatter exact")

    xa = jnp.asarray(rng.integers(-8, 8, (n, 6, 3)), jnp.float32)
    got = sm(lambda xl: coll.all_reduce(xl, "x"), xa)
    ref = sm(lambda xl: lax.psum(xl, "x"), xa)
    check(bool(jnp.array_equal(got, ref)), "all_reduce exact")


# ---------------------------------------------------------------------------

def run_phases(phases) -> bool:
    ok = True
    for name, fn in phases:
        log(f"phase: {name}")
        t0 = time.perf_counter()
        try:
            fn()
        except Exception:   # report every phase, then fail the run
            ok = False
            traceback.print_exc()
            log(f"phase {name}: FAILED")
        log(f"phase {name}: {time.perf_counter() - t0:.1f}s wall")
    return ok


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1)
    args = ap.parse_args(argv)

    dev = device_gate(args.chips)
    if dev is None:
        return 1
    import repro  # noqa: F401  (fails here when the package is absent)

    if args.chips == 1:
        phases = [
            ("exactness", lambda: phase_exactness(DRAGONFLY_1040)),
            ("study", lambda: phase_study(
                study_spec(DRAGONFLY_1040, LOADS, SEEDS, CYCLES))),
            ("oracle", phase_oracle),
            ("flow", lambda: phase_flow(flow_problem())),
        ]
    else:
        phases = [
            ("sharded_sweep", lambda: phase_sharded_sweep(
                DRAGONFLY_1040, LOADS, SEEDS, CYCLES, args.chips)),
            ("collectives", lambda: phase_collectives(args.chips)),
        ]
    if not run_phases(phases):
        return 1
    import jax
    print(json.dumps({"ok": True, "device": {
        "platform": dev.platform, "kind": dev.device_kind,
        "count": len(jax.devices())}}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
