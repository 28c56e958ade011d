"""Compiled engine (repro.sim.xengine) vs the numpy oracle.

Two tiers of agreement:

* **Exact** — properties arbitration order cannot change: delivered
  packet counts of drained (closed) workloads, and per-link load totals
  under minimal routing (the minimal path of every packet is unique, so
  the drained traversal multiset is engine-independent).
* **Statistical** — open-loop sweeps driven by the *same* traffic object
  through both engines: accepted throughput, delivered counts, mean
  latency, and the latency histogram mass agree within seed-matched
  tolerances (the engines draw arbitration tie-breaks from different RNG
  streams).
"""
import jax
import numpy as np
import pytest
from jax.extend.core import Var

import repro.fabric.mirror  # noqa: F401  (registers the mirror instance)
from repro import sim
from repro.core.dragonfly import DragonflyConfig
from repro.core.hyperx import HyperXConfig
from repro.core.simulate import cin_link_loads
from repro.fabric import make_fabric
from repro.sim import xengine

CYCLES = 240
WARMUP = 60
T = 6


def _both(topo, policy_name, traffic, *, terminals=T, cycles=CYCLES,
          warmup=WARMUP, seed=3, **kw):
    """Run one traffic object through both engines."""
    s_np = sim.simulate(topo, sim.make_policy(policy_name), traffic,
                        terminals=terminals, cycles=cycles, warmup=warmup,
                        seed=seed, backend="numpy", **kw)
    s_jx = sim.simulate(topo, sim.make_policy(policy_name), traffic,
                        terminals=terminals, cycles=cycles, warmup=warmup,
                        seed=seed, backend="jax", **kw)
    return s_np, s_jx


def _assert_statistical_match(s_np, s_jx, rtol=0.12):
    assert s_jx.packets_generated == s_np.packets_generated
    assert s_jx.packets_delivered == pytest.approx(
        s_np.packets_delivered, rel=rtol, abs=25)
    assert s_jx.accepted == pytest.approx(s_np.accepted, rel=rtol, abs=0.02)
    if s_np.latency_mean > 0:
        assert s_jx.latency_mean == pytest.approx(
            s_np.latency_mean, rel=0.25, abs=2.0)
    # Same histogram support scale: total mass within tolerance.
    assert s_jx.latency_histogram.sum() == pytest.approx(
        s_np.latency_histogram.sum(), rel=rtol, abs=25)
    # Conservation: link-load totals count the same flows modulo detour
    # randomness.
    assert s_jx.link_loads.sum() == pytest.approx(
        s_np.link_loads.sum(), rel=rtol, abs=50)


# ---------------------------------------------------------------------------
# Exact agreement on drained minimal workloads (every instance).
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("inst,n", [("swap", 8), ("circle", 8),
                                    ("circle", 9), ("mirror", 9),
                                    ("xor", 16)])
def test_one_shot_a2a_exactly_matches_oracle(inst, n):
    topo = sim.cin_topology(inst, n)
    tr = sim.one_shot_all_to_all(n)
    s_jx = xengine.simulate_jax(topo, sim.MinimalPolicy(), tr, terminals=4)
    eng = sim.Engine(topo, sim.MinimalPolicy(), tr, terminals=4)
    s_np = eng.run()
    assert s_jx.packets_delivered == s_np.packets_delivered == n * (n - 1)
    assert np.array_equal(s_jx.link_loads, s_np.link_loads)
    assert eng.load.by_switch_pair() == cin_link_loads(inst, n)


def test_one_shot_a2a_exact_on_compositions():
    hx = make_fabric(HyperXConfig(dims=(4, 4), terminals=4)).sim_topology()
    tr = sim.one_shot_all_to_all(16)
    s_jx = xengine.simulate_jax(hx, sim.MinimalPolicy(), tr, terminals=4)
    eng = sim.Engine(hx, sim.MinimalPolicy(), tr, terminals=4)
    s_np = eng.run()
    assert s_jx.packets_delivered == s_np.packets_delivered
    assert np.array_equal(s_jx.link_loads, s_np.link_loads)

    cfg = DragonflyConfig(group_size=4, terminals_per_switch=2,
                          global_ports_per_switch=2, num_groups=6)
    dtopo = make_fabric(cfg).sim_topology()
    tr = sim.one_shot_all_to_all(cfg.switches)
    s_jx = xengine.simulate_jax(dtopo, sim.MinimalPolicy(), tr, terminals=4)
    eng = sim.Engine(dtopo, sim.MinimalPolicy(), tr, terminals=4)
    s_np = eng.run()
    assert s_jx.packets_delivered == s_np.packets_delivered
    assert np.array_equal(s_jx.link_loads, s_np.link_loads)


def test_drain_mode_deadlock_freedom_nonminimal():
    """Closed Valiant workload must fully drain on the compiled engine —
    the distance-class VC ladder argument holds there too."""
    topo = sim.cin_topology("xor", 16)
    tr = sim.one_shot_all_to_all(16)
    s = xengine.simulate_jax(topo, sim.ValiantPolicy(), tr, terminals=4,
                             max_cycles=20_000)
    assert s.packets_delivered == s.packets_generated == 240


# ---------------------------------------------------------------------------
# Statistical agreement: instances x policies (uniform traffic).
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("inst,n", [("swap", 8), ("circle", 9),
                                    ("mirror", 9), ("xor", 8)])
@pytest.mark.parametrize("policy", ["minimal", "valiant", "adaptive"])
def test_uniform_equivalence_instances_policies(inst, n, policy):
    topo = sim.cin_topology(inst, n)
    tr = sim.uniform(n, offered=0.5, cycles=CYCLES, terminals=T, seed=5)
    s_np, s_jx = _both(topo, policy, tr)
    _assert_statistical_match(s_np, s_jx)


# ---------------------------------------------------------------------------
# Statistical agreement: traffic patterns.
# ---------------------------------------------------------------------------

def test_permutation_equivalence():
    topo = sim.cin_topology("xor", 16)
    tr = sim.permutation(16, offered=0.6, cycles=CYCLES, terminals=T, seed=6)
    s_np, s_jx = _both(topo, "minimal", tr)
    _assert_statistical_match(s_np, s_jx)


def test_hotspot_equivalence():
    topo = sim.cin_topology("xor", 16)
    tr = sim.hotspot(16, offered=0.3, cycles=CYCLES, terminals=T,
                     hot_fraction=0.9, seed=7)
    s_np, s_jx = _both(topo, "valiant", tr)
    _assert_statistical_match(s_np, s_jx)


def test_adversarial_equivalence_on_dragonfly():
    cfg = DragonflyConfig(group_size=4, terminals_per_switch=2,
                          global_ports_per_switch=2, num_groups=8)
    topo = make_fabric(cfg).sim_topology()
    for policy in ("minimal", "valiant"):
        tr = sim.adversarial_same_group(cfg, offered=0.3, cycles=400,
                                        terminals=2, seed=8)
        s_np, s_jx = _both(topo, policy, tr, terminals=2, cycles=400,
                           warmup=100)
        _assert_statistical_match(s_np, s_jx)
    # and the §3 story survives the backend: valiant >> minimal here
    tr = sim.adversarial_same_group(cfg, offered=0.3, cycles=400,
                                    terminals=2, seed=8)
    s_min = sim.simulate(topo, sim.MinimalPolicy(), tr, terminals=2,
                         cycles=400, warmup=100, backend="jax")
    s_val = sim.simulate(topo, sim.ValiantPolicy(), tr, terminals=2,
                         cycles=400, warmup=100, backend="jax")
    assert s_val.accepted > 1.5 * s_min.accepted


# ---------------------------------------------------------------------------
# Batched sweeps.
# ---------------------------------------------------------------------------

def test_batched_sweep_matches_pointwise_runs():
    """One compiled (loads x seeds) program reports the same statistics
    as running its points separately (identical traffic per point; the
    shared arbitration key differs, hence statistical tolerance)."""
    topo = sim.cin_topology("xor", 16)

    def tf(load, seed):
        return sim.uniform(16, offered=load, cycles=CYCLES, terminals=T,
                           seed=seed)

    loads, seeds = [0.3, 0.8], (1, 2)
    grid = xengine.sweep(topo, "minimal", tf, loads, seeds=seeds,
                         terminals=T, cycles=CYCLES, warmup=WARMUP)
    assert len(grid) == len(loads) and len(grid[0]) == len(seeds)
    for li, load in enumerate(loads):
        for si, seed in enumerate(seeds):
            ref = sim.simulate(topo, sim.MinimalPolicy(), tf(load, seed),
                               terminals=T, cycles=CYCLES, warmup=WARMUP,
                               backend="numpy", seed=seed)
            got = grid[li][si]
            assert got.offered == load
            assert got.accepted == pytest.approx(ref.accepted, rel=0.12,
                                                 abs=0.02)


def test_fabric_sim_sweep_backends_agree():
    """The deprecated Fabric.sim_sweep shim still works on both backends
    (it routes through repro.studies.Study internally)."""
    fab = make_fabric("xor", 16)

    def tf(load, seed):
        return sim.uniform(16, offered=load, cycles=CYCLES, terminals=T,
                           seed=seed)

    kw = dict(seeds=(4,), terminals=T, cycles=CYCLES, warmup=WARMUP)
    from repro.fabric import LacinDeprecationWarning
    with pytest.warns(LacinDeprecationWarning):
        jx = fab.sim_sweep("minimal", tf, [0.4, 0.8], backend="jax", **kw)
    with pytest.warns(LacinDeprecationWarning):
        np_ = fab.sim_sweep("minimal", tf, [0.4, 0.8], backend="numpy", **kw)
    for row_jx, row_np in zip(jx, np_):
        assert row_jx[0].accepted == pytest.approx(row_np[0].accepted,
                                                   rel=0.12, abs=0.02)


def test_sweep_derives_shared_horizon_from_traffic():
    """cycles=None on a batched sweep: the shared horizon is the max
    generation window over the grid (no ValueError, no explicit cycles)."""
    topo = sim.cin_topology("xor", 8)

    def tf(load):
        return sim.uniform(8, offered=load, cycles=100 + int(load * 100),
                           terminals=2, seed=0)

    with pytest.warns(UserWarning, match="shared horizon"):
        grid = xengine.sweep(topo, "minimal", tf, [0.1, 0.9], terminals=2)
    assert [row[0].cycles for row in grid] == [190, 190]
    assert [row[0].warmup for row in grid] == [190 // 4] * 2
    # sanity: the derived-horizon run matches the same sweep pinned
    # explicitly to that horizon
    pinned = xengine.sweep(topo, "minimal", tf, [0.1, 0.9], terminals=2,
                           cycles=190)
    for a, b in zip(grid, pinned):
        assert a[0].accepted == b[0].accepted


def test_saturation_sweep_backend_switch():
    topo = sim.cin_topology("xor", 8)

    def tf(load):
        return sim.uniform(8, offered=load, cycles=CYCLES, terminals=4,
                           seed=9)

    from repro.fabric import LacinDeprecationWarning
    with pytest.warns(LacinDeprecationWarning):
        stats = sim.saturation_sweep(topo, sim.MinimalPolicy, tf, [0.2, 0.6],
                                     terminals=4, cycles=CYCLES,
                                     warmup=WARMUP, backend="jax")
    assert [s.offered for s in stats] == [0.2, 0.6]
    assert all(0 < s.accepted <= 1.2 for s in stats)


# ---------------------------------------------------------------------------
# Engine construction memoization (satellite).
# ---------------------------------------------------------------------------

def test_link_table_memoized_per_topology_and_vcs():
    topo = sim.cin_topology("xor", 8)
    tr = sim.uniform(8, offered=0.2, cycles=50, terminals=2, seed=0)
    e1 = sim.Engine(topo, sim.MinimalPolicy(), tr, terminals=2)
    e2 = sim.Engine(topo, sim.MinimalPolicy(), tr, terminals=2)
    assert e1.links is e2.links
    e3 = sim.Engine(topo, sim.ValiantPolicy(), tr, terminals=2)
    assert e3.links is not e1.links          # different VC count
    assert e3.num_vcs != e1.num_vcs


def test_minimal_port_table_matches_routing():
    topo = sim.cin_topology("circle", 9)
    tbl = topo.minimal_port_table()
    assert tbl is topo.minimal_port_table()  # cached
    rng = np.random.default_rng(0)
    cur = rng.integers(0, 9, 64)
    tgt = rng.integers(0, 9, 64)
    off = cur != tgt
    assert np.array_equal(tbl[cur[off], tgt[off]],
                          topo.minimal_port(cur[off], tgt[off]))


def test_engine_pressure_updates_every_cycle_when_blocked():
    """The EWMA congestion signal decays/updates on every step path,
    including fully-blocked cycles (regression for the early-return
    skip)."""
    topo = sim.cin_topology("xor", 4)
    tr = sim.uniform(4, offered=0.9, cycles=60, terminals=8, seed=1)
    eng = sim.Engine(topo, sim.MinimalPolicy(), tr, terminals=8,
                     queue_capacity=1, seed=1)
    pressures = []
    for _ in range(60):
        eng.step()
        pressures.append(eng.pressure.copy())
    # pressure must keep moving cycle-over-cycle (no frozen stale reads)
    diffs = [np.abs(a - b).sum() for a, b in zip(pressures, pressures[1:])]
    assert np.count_nonzero(diffs) >= len(diffs) // 2


# ---------------------------------------------------------------------------
# The queue's packed attribute word: layout and what the step gathers.
# ---------------------------------------------------------------------------

def _smallest_n_without_dst(vcs):
    """The fewest switches whose packed word has no room for ``dst``."""
    return next(n for n in (2 ** k + 1 for k in range(31))
                if xengine._word_layout(n, vcs)[1] == 0)


def test_word_layout_boundaries():
    # dragonfly-2064 at its 3 VCs: 12 + 12 + 1 + 2 bits.
    assert xengine._word_layout(2064, 3) == (2, 12)
    assert xengine._word_layout(4096, 3) == (2, 12)
    assert xengine._word_layout(96, 4) == (3, 7)        # hyperx-12x8
    assert _smallest_n_without_dst(3) == 2 ** 14 + 1
    for vcs in range(1, 129):
        n_fb = _smallest_n_without_dst(vcs)
        hop_bits, id_bits = xengine._word_layout(n_fb - 1, vcs)
        assert 2 * id_bits + 1 + hop_bits <= 31
        assert (1 << hop_bits) - 1 >= min(vcs, xengine._MAX_HOPS)
        assert xengine._word_layout(n_fb, vcs) == (7, 0)


@pytest.mark.parametrize("vcs", range(1, 9))
def test_word_round_trip_and_vc_class(vcs):
    n_fb = _smallest_n_without_dst(vcs)
    hops = np.arange(201, dtype=np.int32)
    today = np.minimum(np.minimum(hops, 127) - 1, vcs - 1)
    for n in (2064, 4096, n_fb - 1, n_fb):
        layout = xengine._word_layout(n, vcs)
        # n - 1 sets the top bit of a bits(n - 1)-wide field.
        ids = np.array([0, 1, n // 2, n - 1], np.int32)
        d, m, ph, h = (a.ravel() for a in np.meshgrid(
            ids, ids, np.array([0, 1], np.int32), hops, indexing="ij"))
        word = np.asarray(xengine._pack_attr(layout, d, m, ph, h))
        assert (word >= 0).all()
        dst, mid, phase, h_out = (None if a is None else np.asarray(a)
                                  for a in xengine._unpack_attr(layout, word))
        if layout[1]:
            assert np.array_equal(dst, d)
        else:
            assert dst is None and n == n_fb
        assert np.array_equal(mid, m)
        assert np.array_equal(phase, ph)
        vc = np.minimum(h_out - 1, vcs - 1).reshape(-1, hops.size)
        assert (vc == today).all()
        # The step's own progression: inject at 1 hop, repack +1 a hop.
        w = xengine._pack_attr(layout, ids, ids, ids % 2,
                               np.ones_like(ids))
        for true_hops in range(1, 201):
            _, _, _, h_now = xengine._unpack_attr(layout, w)
            assert (np.minimum(np.asarray(h_now) - 1, vcs - 1)
                    == min(min(true_hops, 127) - 1, vcs - 1)).all()
            w = xengine._pack_attr(layout, ids, ids, ids % 2, h_now + 1)


def _dst_gather_lengths(jaxpr, tracked):
    """Index counts of every gather from a var in ``tracked``, following
    the vars into nested jaxprs by position."""
    lengths = []
    for eqn in jaxpr.eqns:
        if eqn.primitive.name == "gather" and eqn.invars[0] in tracked:
            lengths.append(eqn.invars[1].aval.shape[0])
        inner = eqn.params.get("jaxpr")
        if inner is not None:
            inner = getattr(inner, "jaxpr", inner)
            sub = {iv for ov, iv in zip(eqn.invars, inner.invars)
                   if isinstance(ov, Var) and ov in tracked}
            if sub:
                lengths += _dst_gather_lengths(inner, sub)
    return lengths


@pytest.mark.parametrize("policy", ["minimal", "adaptive"])
@pytest.mark.parametrize("dst_in_word", [1, 0])
def test_step_gathers_dst_only_on_terminal_lanes(policy, dst_in_word,
                                                 monkeypatch):
    """With the destination in the packed word, the step reads
    ``pkt["dst"]`` only at injection (NT terminal lanes), never per queue
    lane (Q); the program without the field still shows the (Q,) gather,
    which is what this check would find if it came back."""
    topo = sim.hyperx_topology(HyperXConfig(dims=(4, 3), terminals=2,
                                            instance="circle"))
    if not dst_in_word:
        real = xengine._word_layout
        monkeypatch.setattr(xengine, "_word_layout",
                            lambda n, vcs: real(1 << 16, vcs))
    captured = {}

    class _Captured(Exception):
        pass

    def capture(fn, spec, *args, **kw):
        captured.update(spec=spec, args=args)
        raise _Captured

    monkeypatch.setattr(xengine, "timed_compiled", capture)
    with pytest.raises(_Captured):
        xengine.sweep(topo, policy,
                      lambda load, seed: sim.uniform(12, offered=load,
                                                     cycles=40, terminals=2,
                                                     seed=seed),
                      [0.3, 0.6], seeds=(0,), terminals=2, cycles=40)
    spec, (tables, pkt, key, warmup) = captured["spec"], captured["args"]
    rest = {k: a for k, a in pkt.items() if k != "dst"}

    def step(dst, rest, state):
        return xengine._step(spec, tables, dict(rest, dst=dst), key,
                             warmup, state)

    closed = jax.make_jaxpr(step)(pkt["dst"], rest,
                                  xengine._init_state(spec, pkt))
    lengths = _dst_gather_lengths(closed.jaxpr, {closed.jaxpr.invars[0]})
    b = pkt["blk_start"].shape[0] // spec.n
    nt = b * spec.n * spec.terminals
    q = b * spec.n * spec.ports * spec.vcs
    assert nt in lengths
    if dst_in_word:
        assert set(lengths) == {nt}
    else:
        assert q in lengths


def test_moe_replay_exactly_matches_oracle():
    """Uneven phases (DeepSeek-V3's dispatch and combine, per-pair
    counts redrawn from the seed) on a 2 x 8 HyperX with 2 terminals
    and 4 tokens a rank: the compiled engine equals the numpy oracle
    packet for packet, phase for phase and latency for latency."""
    from repro.sim.workloads import MoESpec, moe_dispatch_workload, replay
    fab = make_fabric(HyperXConfig(dims=(2, 8), terminals=2,
                                   instance="circle"))
    topo = fab.sim_topology()
    moe = MoESpec(batch_tokens=4, ranks_per_node=2, sigma=0.5)
    for seed in (3, 2**31 + 9):
        w = moe_dispatch_workload(fab, moe, seed)
        assert len({ph.peak_messages for ph in w.phases}) > 1
        s_np = replay(topo, "minimal", w, backend="numpy")
        s_jx = replay(topo, "minimal", w, backend="jax")
        assert (s_np.packets_delivered == s_jx.packets_delivered
                == s_np.packets_generated == w.num_packets)
        assert s_np.phase_cycles == s_jx.phase_cycles
        assert s_np.completion_cycles == s_jx.completion_cycles
        assert s_np.ideal_cycles == s_jx.ideal_cycles == w.ideal_cycles
        assert s_jx.completion_cycles >= w.ideal_cycles
        for f in ("latency_mean", "latency_p50", "latency_p99",
                  "latency_max", "accepted"):
            assert getattr(s_np, f) == getattr(s_jx, f), f
        assert np.array_equal(s_np.latency_histogram,
                              s_jx.latency_histogram)
        assert np.array_equal(s_np.link_loads, s_jx.link_loads)
