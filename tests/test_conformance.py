"""Cross-engine / cross-program conformance suite.

The compiled engine earns its keep only if the same physics falls out of
every way of running it.  This suite pins the two contracts the
persistent-compile-cache + shape-bucketing + sharding rebuild rests on:

* **Engines.**  The numpy oracle and the compiled jax engine agree
  *exactly* on drained minimal workloads — delivered counts and per-link
  load totals, where unique minimal paths make the traversal multiset
  arbitration-independent — across registry instances and workload
  shapes: open-loop drains, collective replays, serving request fans,
  and degraded (failure-masked) fabrics.
* **Programs.**  Within the jax engine, every program variant must be
  *bit-identical* to the exact-shape, freshly-compiled, single-device
  reference: the bucket-padded program (:func:`xengine._bucket_count`
  shape bucketing), the executable restored from the persistent disk
  cache (``repro.obs.telemetry``), and — in a subprocess with forced
  host devices — the ``shard_map``-sharded program.  Bit-identical means
  every :class:`RunStats` field, not statistics within tolerance: the
  per-copy RNG keying guarantees padding and sharding never perturb a
  single arbitration draw.  The program that carries each packet's
  destination in its queue's packed word and the one that gathers it
  from the packet table (the layout of fabrics too large for the field)
  agree bit for bit too.
"""
import dataclasses
import os
import subprocess
import sys
import textwrap

import jax
import numpy as np
import pytest
from hypothesis import given, settings, strategies as st

import repro
import repro.fabric.mirror  # noqa: F401  (registers the mirror instance)
from repro import sim
from repro.core.dragonfly import DragonflyConfig
from repro.fabric import make_fabric
from repro.faults import FailureSpec
from repro.sim import xengine
from repro.sim.metrics import RunStats
from repro.workload import ArrivalSpec, serving_traffic

INSTANCES = [("swap", 8), ("circle", 9), ("xor", 8), ("mirror", 9)]

#: RunStats fields excluded from bit-identity: both are run *metadata*
#: (wall-clock timings, sampled observability series), not simulation
#: results, and both are declared compare=False on the dataclass.
_META_FIELDS = {"timing", "trace"}


def _assert_bit_identical(a: RunStats, b: RunStats) -> None:
    for f in dataclasses.fields(RunStats):
        if f.name in _META_FIELDS:
            continue
        x, y = getattr(a, f.name), getattr(b, f.name)
        if isinstance(x, np.ndarray) or isinstance(y, np.ndarray):
            assert np.array_equal(np.asarray(x), np.asarray(y)), f.name
        else:
            assert x == y, (f.name, x, y)


def _assert_grids_bit_identical(ga, gb) -> None:
    assert len(ga) == len(gb)
    for row_a, row_b in zip(ga, gb):
        assert len(row_a) == len(row_b)
        for a, b in zip(row_a, row_b):
            _assert_bit_identical(a, b)


# ---------------------------------------------------------------------------
# Engines: numpy oracle vs compiled engine, exact on drained minimal
# workloads, across registry instances x workload shapes.
# ---------------------------------------------------------------------------

def _drained_scenario(kind: str, inst: str, n: int):
    """(traffic, failures) for one drained minimal workload shape."""
    if kind == "open_loop":
        return sim.one_shot_all_to_all(n), None
    if kind == "serving":
        return serving_traffic(ArrivalSpec(rate=0.03, seed=1), n,
                               cycles=60, terminals=4,
                               packets_per_request=2,
                               slo=40.0, seed=7), None
    if kind == "degraded":
        return (sim.one_shot_all_to_all(n),
                FailureSpec(link_fraction=0.08, seed=3))
    raise AssertionError(kind)


@pytest.mark.parametrize("inst,n", INSTANCES)
@pytest.mark.parametrize("kind", ["open_loop", "serving", "degraded"])
def test_engines_agree_exactly_on_drained_minimal(kind, inst, n):
    topo = sim.cin_topology(inst, n)
    traffic, failures = _drained_scenario(kind, inst, n)
    kw = dict(terminals=4, drain=True, seed=5, failures=failures)
    s_np = sim.simulate(topo, sim.MinimalPolicy(), traffic,
                        backend="numpy", **kw)
    s_jx = sim.simulate(topo, sim.MinimalPolicy(), traffic,
                        backend="jax", **kw)
    assert s_jx.packets_generated == s_np.packets_generated
    assert s_jx.packets_delivered == s_np.packets_delivered
    assert s_jx.packets_delivered > 0
    assert np.array_equal(np.asarray(s_jx.link_loads),
                          np.asarray(s_np.link_loads))
    if kind == "serving":
        # Request accounting (completed-request count) is also
        # arbitration-independent under drain: every packet delivers.
        assert s_jx.request_count == s_np.request_count


@pytest.mark.parametrize("inst,n", [("xor", 8), ("circle", 9)])
def test_engines_agree_on_collective_replay(inst, n):
    fab = make_fabric(inst, n)
    s_np = fab.replay("all_to_all", message_size=2, backend="numpy")
    s_jx = fab.replay("all_to_all", message_size=2, backend="jax")
    assert s_jx.packets_delivered == s_np.packets_delivered
    # LACIN 1-factor schedules are contention-free, so phase completion
    # is deterministic and both engines must land on the ideal bound.
    assert (s_jx.completion_cycles == s_np.completion_cycles
            == s_np.ideal_cycles)
    assert s_jx.phase_cycles == s_np.phase_cycles
    assert np.array_equal(np.asarray(s_jx.link_loads),
                          np.asarray(s_np.link_loads))


# ---------------------------------------------------------------------------
# Programs: bucketed == exact, bit for bit.
# ---------------------------------------------------------------------------

def _sweep(**kw):
    """An open-loop sweep whose grid (9 copies), horizon (90 cycles) and
    packet count all land strictly inside bucket boundaries, so the
    bucketed program genuinely pads every axis."""
    topo = sim.cin_topology("xor", 16)

    def tf(load, seed):
        return sim.uniform(16, offered=load, cycles=90, terminals=2,
                           seed=seed)

    return xengine.sweep(topo, "minimal", tf, [0.25, 0.55, 0.85],
                         seeds=(0, 1, 2), terminals=2, cycles=90,
                         warmup=20, **kw)


def test_bucketed_sweep_bit_identical_to_exact():
    _assert_grids_bit_identical(_sweep(bucket=False), _sweep())


def test_bucketed_drain_bit_identical_to_exact():
    topo = sim.cin_topology("circle", 9)
    tr = sim.one_shot_all_to_all(9)
    exact = xengine.simulate_jax(topo, sim.MinimalPolicy(), tr,
                                 terminals=4, bucket=False)
    bucketed = xengine.simulate_jax(topo, sim.MinimalPolicy(), tr,
                                    terminals=4)
    _assert_bit_identical(exact, bucketed)


def test_bucketed_replay_bit_identical_to_exact():
    fab = make_fabric("xor", 8)
    a = fab.replay("all_to_all", message_size=2, backend="jax",
                   bucket=False)
    b = fab.replay("all_to_all", message_size=2, backend="jax")
    _assert_bit_identical(a, b)


@settings(max_examples=6, deadline=None)
@given(points=st.integers(1, 5), cycles=st.integers(40, 88))
def test_bucketing_invariance_property(points, cycles):
    """Any grid width x any horizon: padding the batch, the packet axis,
    and the cycle loop never changes a single statistic."""
    topo = sim.cin_topology("xor", 8)

    def tf(load, seed):
        return sim.uniform(8, offered=load, cycles=cycles, terminals=2,
                           seed=seed)

    loads = [round(0.2 + 0.15 * i, 2) for i in range(points)]
    kw = dict(seeds=(0,), terminals=2, cycles=cycles, warmup=cycles // 4)
    _assert_grids_bit_identical(
        xengine.sweep(topo, "minimal", tf, loads, bucket=False, **kw),
        xengine.sweep(topo, "minimal", tf, loads, **kw))


# ---------------------------------------------------------------------------
# Programs: destination in the packed word == destination gathered by
# packet id, bit for bit.
# ---------------------------------------------------------------------------

_REAL_WORD_LAYOUT = xengine._word_layout


def _layout_without_dst(n, vcs):
    """The word of a fabric too large for a ``dst`` field, at ``vcs``."""
    return _REAL_WORD_LAYOUT(1 << 16, vcs)


def _dragonfly_grid(policy):
    topo = sim.dragonfly_topology(DragonflyConfig(
        group_size=4, terminals_per_switch=2, global_ports_per_switch=2,
        num_groups=6))

    def tf(load, seed):
        return sim.uniform(topo.num_switches, offered=load, cycles=96,
                           terminals=2, seed=seed)

    grid = xengine.sweep(topo, policy, tf, [0.3, 0.9], seeds=(0, 1),
                         terminals=2, cycles=96, warmup=24)
    return [s for row in grid for s in row]


_WORD_SCENARIOS = {
    "minimal": lambda: _dragonfly_grid("minimal"),
    "valiant": lambda: _dragonfly_grid("valiant"),
    "adaptive": lambda: _dragonfly_grid("adaptive"),
    "degraded": lambda: [sim.simulate(
        sim.cin_topology("circle", 9), sim.make_policy("adaptive"),
        sim.uniform(9, offered=0.6, cycles=100, terminals=4, seed=3),
        terminals=4, cycles=100, seed=2, backend="jax",
        failures=FailureSpec(link_fraction=0.08, seed=3))],
    "replay": lambda: [make_fabric("xor", 8).replay(
        "all_to_all", message_size=2, backend="jax")],
    "serving": lambda: [sim.simulate(
        sim.cin_topology("circle", 9), sim.MinimalPolicy(),
        _drained_scenario("serving", "circle", 9)[0], terminals=4,
        drain=True, seed=5, backend="jax")],
}


@pytest.mark.parametrize("scenario", list(_WORD_SCENARIOS))
def test_dst_in_word_bit_identical_to_gathered_dst(scenario, tmp_path,
                                                   monkeypatch):
    run = _WORD_SCENARIOS[scenario]
    in_word = run()
    assert all(s.timing["dst_in_word"] == 1 for s in in_word)
    # The program cache keys on the jitted function and the spec, which
    # the word layout does not change: trace the step again through a
    # jit of its own, with a disk layer of its own.
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(tmp_path))
    monkeypatch.setattr(xengine, "_word_layout", _layout_without_dst)
    monkeypatch.setattr(xengine, "_run_flat", jax.jit(
        lambda spec, *args: xengine._run_loop(spec, *args),
        static_argnums=0))
    gathered = run()
    monkeypatch.undo()
    assert all(s.timing["dst_in_word"] == 0 for s in gathered)
    assert gathered[0].timing["compile_cached"] is False
    assert len(in_word) == len(gathered)
    for a, b in zip(in_word, gathered):
        _assert_bit_identical(a, b)
    assert sum(s.packets_delivered for s in in_word) > 0


# ---------------------------------------------------------------------------
# Programs: disk-restored executable == freshly compiled, bit for bit.
# ---------------------------------------------------------------------------

def test_disk_restored_executable_bit_identical(tmp_path, monkeypatch):
    from repro.obs import telemetry
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(tmp_path))
    telemetry.clear_caches(memory=True)
    fresh = _sweep()
    assert fresh[0][0].timing["compile_cached"] is False
    assert telemetry.disk_cache_entries(), "compile did not persist"
    # Drop the in-process layer: the rerun must come back from disk and
    # reproduce every statistic byte for byte.
    telemetry.clear_caches(memory=True)
    restored = _sweep()
    assert restored[0][0].timing["compile_cached"] == "disk"
    _assert_grids_bit_identical(fresh, restored)


# ---------------------------------------------------------------------------
# Programs: device-sharded == single-device, bit for bit (subprocess —
# CPU devices are fixed by XLA_FLAGS before jax initializes).
# ---------------------------------------------------------------------------

_SHARD_SCRIPT = textwrap.dedent("""
    import dataclasses
    import numpy as np
    from repro import sim
    from repro.sim import xengine
    from repro.sim.metrics import RunStats

    topo = sim.cin_topology("xor", 16)

    def tf(load, seed):
        return sim.uniform(16, offered=load, cycles=80, terminals=2,
                           seed=seed)

    kw = dict(seeds=(0, 1), terminals=2, cycles=80, warmup=20)
    ref = xengine.sweep(topo, "minimal", tf, [0.3, 0.7], **kw)
    shr = xengine.sweep(topo, "minimal", tf, [0.3, 0.7], devices=2, **kw)
    for row_r, row_s in zip(ref, shr):
        for r, s in zip(row_r, row_s):
            for f in dataclasses.fields(RunStats):
                if f.name in ("timing", "trace"):
                    continue
                x, y = getattr(r, f.name), getattr(s, f.name)
                if isinstance(x, np.ndarray) or isinstance(y, np.ndarray):
                    assert np.array_equal(np.asarray(x),
                                          np.asarray(y)), f.name
                else:
                    assert x == y, (f.name, x, y)
    drain = sim.one_shot_all_to_all(16)
    a = xengine.simulate_jax(topo, sim.MinimalPolicy(), drain, terminals=4)
    b = xengine.simulate_jax(topo, sim.MinimalPolicy(), drain, terminals=4,
                             devices=2)
    assert a.packets_delivered == b.packets_delivered
    assert np.array_equal(np.asarray(a.link_loads),
                          np.asarray(b.link_loads))
    assert a.latency_mean == b.latency_mean
    print("SHARD-CONFORMANCE-OK")
""")


def test_sharded_program_bit_identical(tmp_path):
    src = os.path.dirname(os.path.abspath(list(repro.__path__)[0]))
    env = dict(os.environ,
               XLA_FLAGS="--xla_force_host_platform_device_count=2",
               JAX_COMPILATION_CACHE_DIR=str(tmp_path),
               PYTHONPATH=os.pathsep.join(
                   [src, os.environ.get("PYTHONPATH", "")]))
    proc = subprocess.run([sys.executable, "-c", _SHARD_SCRIPT], env=env,
                          capture_output=True, text=True, timeout=900)
    assert proc.returncode == 0, proc.stderr[-4000:]
    assert "SHARD-CONFORMANCE-OK" in proc.stdout


def test_devices_validation():
    topo = sim.cin_topology("xor", 8)
    tr = sim.one_shot_all_to_all(8)
    with pytest.raises(ValueError, match="devices"):
        xengine.simulate_jax(topo, sim.MinimalPolicy(), tr, terminals=4,
                             devices=0)
    too_many = jax.local_device_count() + 1
    with pytest.raises(ValueError, match="visible"):
        xengine.simulate_jax(topo, sim.MinimalPolicy(), tr, terminals=4,
                             devices=too_many)
