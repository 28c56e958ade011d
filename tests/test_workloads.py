"""Collective replay (repro.sim.workloads): the schedule -> simulator seam.

The paper's contention-freedom claim, *measured*: replaying a fabric's
own LACIN schedule through the packet engines must complete in exactly
the schedule algebra's lower bound (``num_steps x message_size``) when
every phase is a matching on its links — and never beat it anywhere.
Plus: numpy/xengine agreement on replays, Workload round-trips through
ExperimentSpec JSON, studies/CLI integration, and the one-shot traffic
``terminals`` recording fix.
"""
import json
import math

import numpy as np
import pytest

import repro.fabric.mirror  # noqa: F401  (registers the mirror instance)
from repro import sim, studies
from repro.core.dragonfly import DragonflyConfig
from repro.core.hyperx import HyperXConfig
from repro.core.schedule import make_schedule
from repro.fabric import instance_names, make_fabric
from repro.fabric.registry import get_instance
from repro.sim import workloads
from repro.sim.workloads import Phase, Workload, collective_workload, replay


def _supported_n(name: str) -> int:
    spec = get_instance(name)
    for n in (8, 9, 12, 16):
        if spec.supports(n):
            return n
    raise AssertionError(f"no test size for instance {name}")


# ---------------------------------------------------------------------------
# Workload construction.
# ---------------------------------------------------------------------------

def test_workload_from_schedule_structure():
    sched = make_schedule("xor", 8)
    w = Workload.from_schedule(sched, message_size=3)
    assert w.num_phases == sched.num_steps == 7
    assert w.ideal_cycles == 7 * 3
    assert w.num_packets == 7 * 8 * 3
    for k, ph in enumerate(w.phases):
        assert ph.messages == 3
        # each phase is exactly the schedule step's matching
        partners = dict(zip(ph.src, ph.dst))
        row = sched.partners(k)
        assert partners == {s: int(row[s]) for s in range(8) if row[s] != s}


def test_workload_odd_circle_drops_idles():
    w = Workload.from_schedule(make_schedule("circle", 9))
    assert w.num_phases == 9
    # odd-N Circle idles one device per step
    assert all(len(ph.src) == 8 for ph in w.phases)


def test_workload_validation():
    with pytest.raises(ValueError, match="distinct"):
        Phase((0, 1), (0, 2))
    with pytest.raises(ValueError, match="messages"):
        Phase((0,), (1,), messages=0)
    with pytest.raises(ValueError, match="outside"):
        Workload("bad", 4, (Phase((0,), (7,)),))
    with pytest.raises(ValueError, match="spans"):
        replay(sim.cin_topology("xor", 8), "minimal",
               Workload("w", 4, (Phase((0,), (1,)),)))


def test_workload_traffic_encodes_phases():
    w = collective_workload(make_fabric("xor", 8), "all_to_all",
                            message_size=2)
    tr = w.traffic()
    assert tr.workload is w
    assert tr.offered == 0.0
    assert tr.num_packets == w.num_packets
    # gen is the phase ordinal, counting each phase's packets
    assert np.array_equal(np.bincount(tr.gen),
                          [ph.num_packets for ph in w.phases])


def test_all_reduce_two_level_shape():
    fab = make_fabric(DragonflyConfig(group_size=4, terminals_per_switch=2,
                                      global_ports_per_switch=2,
                                      num_groups=6))
    w = collective_workload(fab, "all_reduce", message_size=4)
    sched = fab.schedule()
    nl, ng = sched["local"].num_steps, sched["global"].num_steps
    assert w.num_phases == 2 * nl + 2 * ng
    # global phases carry the 1/a-scaled shard payload (ceil(4/4) = 1)
    assert [ph.messages for ph in w.phases] == \
        [4] * nl + [1] * (2 * ng) + [4] * nl


# ---------------------------------------------------------------------------
# Contention-free equality: the paper's claim under queueing.
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("instance", instance_names())
def test_cin_replay_meets_bound_exactly(instance):
    """Unit-message a2a replay on a bare CIN under minimal routing
    completes in exactly num_steps cycles — every phase in exactly 1."""
    n = _supported_n(instance)
    fab = make_fabric(instance, n)
    stats = fab.replay("all_to_all")
    assert stats.packets_delivered == stats.packets_generated
    assert stats.completion_cycles == stats.ideal_cycles
    assert set(stats.phase_cycles) == {1}


@pytest.mark.parametrize("message_size", [1, 3])
def test_cin_replay_scales_with_message_size(message_size):
    stats = make_fabric("xor", 16).replay("all_to_all",
                                          message_size=message_size)
    assert stats.completion_cycles == stats.ideal_cycles \
        == 15 * message_size
    assert set(stats.phase_cycles) == {message_size}


def test_hyperx_grid_replay_meets_bound_exactly():
    """Dimension-order grid schedule: each phase rides one dimension's
    1-factors, so the composed a2a is contention-free end to end."""
    fab = make_fabric(HyperXConfig(dims=(4, 8), terminals=2))
    stats = fab.replay("all_to_all", message_size=2)
    assert stats.completion_cycles == stats.ideal_cycles == (3 + 7) * 2
    assert set(stats.phase_cycles) == {2}


def test_dragonfly_replay_exceeds_bound_on_global_steps():
    """Global grid steps funnel group_size flows over one global link:
    measured completion must exceed the naive bound by the
    serialization, while local phases stay contention-free."""
    fab = make_fabric(DragonflyConfig(group_size=4, terminals_per_switch=2,
                                      global_ports_per_switch=2,
                                      num_groups=6))
    stats = fab.replay("all_to_all")
    sched = fab.schedule()
    nl = sched["local"].num_steps
    assert stats.completion_cycles > stats.ideal_cycles
    # local phases (first nl) are matchings on local links: 1 cycle each
    assert set(stats.phase_cycles[:nl]) == {1}
    # global phases serialize a flows per link (plus l-g-l pipelining)
    assert all(c >= fab.config.group_size for c in stats.phase_cycles[nl:])


def test_nonminimal_replay_cannot_beat_bound():
    for policy in ("valiant", "adaptive"):
        stats = make_fabric("xor", 16).replay("all_to_all", policy=policy)
        assert stats.packets_delivered == stats.packets_generated
        assert stats.completion_cycles >= stats.ideal_cycles


# ---------------------------------------------------------------------------
# numpy vs compiled engine on replays.
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("fab,msg", [
    (make_fabric("xor", 16), 2),
    (make_fabric("circle", 9), 1),
    (make_fabric(HyperXConfig(dims=(4, 4), terminals=2)), 2),
    (make_fabric(DragonflyConfig(group_size=4, terminals_per_switch=2,
                                 global_ports_per_switch=2, num_groups=6)),
     1),
])
def test_engines_agree_on_replay(fab, msg):
    """Minimal-routing replays are work-conserving with unique routes:
    both engines must report identical per-phase completion and
    link-for-link loads."""
    topo = fab.sim_topology()
    w = collective_workload(fab, "all_to_all", message_size=msg)
    s_np = replay(topo, "minimal", w, backend="numpy")
    s_jx = replay(topo, "minimal", w, backend="jax")
    assert s_np.packets_delivered == s_jx.packets_delivered == w.num_packets
    assert s_np.completion_cycles == s_jx.completion_cycles
    assert s_np.phase_cycles == s_jx.phase_cycles
    assert np.array_equal(s_np.link_loads, s_jx.link_loads)


@pytest.mark.parametrize("backend", ["numpy", "jax"])
def test_replay_stats_measure_the_replay_timeline(backend):
    """Summary stats are framed by the replay itself, not by the phase
    count: cycles = completion, accepted normalizes by it, and latency
    measures from each phase's release cycle (so a drained 1-hop phase
    shows pipeline latency, not the phase ordinal)."""
    stats = make_fabric("xor", 16).replay("all_to_all", message_size=2,
                                          backend=backend)
    assert stats.cycles == stats.completion_cycles == 30
    # 1 terminal/switch injecting every cycle of the run
    assert stats.accepted == pytest.approx(1.0)
    # per phase: first packet lat 2 (inject+eject pipeline), second 3
    assert stats.latency_max <= 3
    assert stats.latency_mean == pytest.approx(2.5)
    # per-link utilization is per-completion, not per-phase-count
    assert stats.link_util_max == pytest.approx(2 / 30)


def test_compiled_replay_drains_nonminimal():
    w = collective_workload(make_fabric("xor", 16), "all_to_all")
    s = replay(sim.cin_topology("xor", 16), "valiant", w, backend="jax")
    assert s.packets_delivered == s.packets_generated
    assert s.completion_cycles >= s.ideal_cycles


def test_batched_sweep_rejects_mixed_replay_and_open_loop():
    topo = sim.cin_topology("xor", 8)
    w = collective_workload(make_fabric("xor", 8), "all_to_all")
    trs = [w.traffic(), sim.uniform(8, offered=0.2, cycles=50)]
    with pytest.raises(ValueError, match="mix"):
        sim.xengine.sweep(topo, "minimal", lambda i: trs[int(i)], [0, 1])


# ---------------------------------------------------------------------------
# Studies integration: serialization, resume, CLI.
# ---------------------------------------------------------------------------

def _replay_spec(traffic_params, name=""):
    return studies.ExperimentSpec(
        fabric=studies.FabricSpec("cin", {"instance": "xor", "n": 8}),
        traffic=studies.TrafficSpec("workload", traffic_params),
        routing=studies.RoutingSpec("minimal"),
        sweep=studies.SweepSpec(loads=(0.0,), seeds=(0,)),
        name=name)


def test_workload_round_trips_through_experiment_spec():
    """An explicit Workload embedded in a spec survives JSON exactly and
    resolves back to an equal Workload."""
    w = collective_workload(make_fabric("xor", 8), "all_to_all",
                            message_size=2)
    spec = _replay_spec({"workload": w.to_dict()})
    rt = studies.ExperimentSpec.from_json(spec.to_json())
    assert rt == spec
    topo = rt.fabric.resolve_topology()
    resolved = rt.traffic._resolve_workload(topo)
    assert resolved == w
    # and the JSON payload itself is the canonical to_dict form
    raw = json.loads(spec.to_json())
    assert Workload.from_dict(raw["traffic"]["params"]["workload"]) == w


def test_explicit_workload_spec_rejects_fabric_size_mismatch():
    w = collective_workload(make_fabric("xor", 32), "all_to_all")
    spec = _replay_spec({"workload": w.to_dict()})   # fabric is n=8
    with pytest.raises(ValueError, match="spans 32 switches"):
        studies.Study(spec, backend="numpy").run()


def test_named_collective_spec_round_trips_and_runs_both_backends(tmp_path):
    spec = _replay_spec({"collective": "all_to_all", "message_size": 2})
    assert studies.ExperimentSpec.from_json(spec.to_json()) == spec
    assert spec.name == "cin-xor-8/replay-all_to_all/minimal"
    for backend in ("numpy", "jax"):
        out = studies.Study(spec, backend=backend).run()
        [r] = out.results
        assert r.completion_cycles == r.ideal_cycles == 7 * 2
        assert r.phase_cycles == [2] * 7
        assert out.replay_points()[spec.name] == {
            "measured": 14, "ideal": 14, "ratio": 1.0}


def test_replay_study_persists_and_resumes(tmp_path):
    store = tmp_path / "replay.jsonl"
    spec = _replay_spec({"collective": "all_to_all"})
    out1 = studies.Study(spec, store=str(store), backend="numpy").run()
    assert (out1.executed, out1.restored) == (1, 0)
    out2 = studies.Study(spec, store=str(store), backend="numpy").run()
    assert (out2.executed, out2.restored) == (0, 1)
    # restored records keep the replay summary fields
    [r] = out2.results
    assert r.completion_cycles == r.ideal_cycles == 7
    assert r.phase_cycles == [1] * 7


def test_bundled_collective_replay_spec_loads_and_round_trips():
    path = studies.bundled_spec_path("collective_replay")
    specs = studies.load_specs(path)
    assert {e.fabric.kind for e in specs} == {"cin", "hyperx", "dragonfly"}
    assert {e.routing.policy for e in specs} == {"minimal", "adaptive"}
    for e in specs:
        assert studies.ExperimentSpec.from_json(e.to_json()) == e
        assert not e.is_inline


def test_replay_cli_end_to_end(tmp_path, capsys):
    from repro.studies.__main__ import main as cli
    spec = _replay_spec({"collective": "all_to_all"})
    spec_path = tmp_path / "replay_spec.json"
    studies.dump_specs([spec], str(spec_path))
    store = tmp_path / "cli.jsonl"
    assert cli(["run", str(spec_path), "--backend", "numpy",
                "--store", str(store)]) == 0
    out = capsys.readouterr().out
    assert "collective replay" in out
    assert "measured=7 ideal=7 ratio=1.0" in out
    stored = studies.JsonlStore(str(store)).load()
    [rec] = stored.values()
    assert rec.completion_cycles == 7 and rec.phase_cycles == [1] * 7


# ---------------------------------------------------------------------------
# Satellite: one-shot generators record terminals like open-loop ones.
# ---------------------------------------------------------------------------

def test_one_shot_records_terminals():
    tr = sim.one_shot_all_to_all(8, terminals=4)
    assert tr.terminals == 4
    eng = sim.Engine(sim.cin_topology("xor", 8), sim.MinimalPolicy(), tr)
    assert eng.terminals == 4                     # engine defaults to it
    with pytest.raises(ValueError, match="terminals=2 disagrees"):
        sim.Engine(sim.cin_topology("xor", 8), sim.MinimalPolicy(), tr,
                   terminals=2)
    # default stays None: legacy explicit-terminals callers still work
    legacy = sim.one_shot_all_to_all(8)
    assert legacy.terminals is None
    eng = sim.Engine(sim.cin_topology("xor", 8), sim.MinimalPolicy(),
                     legacy, terminals=3)
    assert eng.terminals == 3


def test_one_shot_permutation_records_terminals():
    tr = sim.one_shot_permutation(np.array([1, 0, 3, 2]), terminals=2)
    assert tr.terminals == 2
    with pytest.raises(ValueError, match="disagrees"):
        sim.xengine.simulate_jax(sim.cin_topology("xor", 4),
                                 sim.MinimalPolicy(), tr, terminals=4)


# ---------------------------------------------------------------------------
# Per-pair phases and the MoE expert-parallel exchange.
# ---------------------------------------------------------------------------

def test_uniform_phases_keep_the_scalar_bound_and_packets():
    """An int ``messages`` is the old path: same packets, same bound; the
    same counts written per pair give the same packets too."""
    w = collective_workload(make_fabric(HyperXConfig(dims=(4, 4),
                                                     terminals=2)),
                            "all_to_all", message_size=3)
    assert all(ph.uniform for ph in w.phases)
    assert w.ideal_cycles == sum(ph.messages for ph in w.phases) == 6 * 3
    per_pair = Workload(w.name, w.num_switches, tuple(
        Phase(ph.src, ph.dst, tuple([ph.messages] * len(ph.src)))
        for ph in w.phases))
    a, b = w.traffic(), per_pair.traffic()
    for field in ("src", "dst", "gen"):
        assert np.array_equal(getattr(a, field), getattr(b, field))
    assert per_pair.ideal_cycles == w.ideal_cycles
    assert np.array_equal(per_pair.phase_cum(), w.phase_cum())


def test_per_pair_phase_counts_and_round_trip():
    w = Workload("uneven", 4, (Phase((0, 1, 2), (1, 2, 3), (3, 1, 5)),
                               Phase((3,), (0,), 2),
                               Phase((), (), ())))
    assert [ph.num_packets for ph in w.phases] == [9, 2, 0]
    assert w.ideal_cycles == 5 + 2 + 0
    assert w.phase_cum().tolist() == [9, 11, 11]
    tr = w.traffic()
    assert tr.src.tolist() == [0] * 3 + [1] + [2] * 5 + [3] * 2
    assert tr.gen.tolist() == [0] * 9 + [1] * 2
    d = w.to_dict()
    assert d["phases"][0]["messages"] == [3, 1, 5]
    assert d["phases"][1]["messages"] == 2
    assert Workload.from_dict(json.loads(json.dumps(d))) == w
    spec = _replay_spec({"workload": d})
    assert studies.ExperimentSpec.from_json(spec.to_json()) == spec


def test_per_pair_phase_validation():
    with pytest.raises(ValueError, match="one count per pair"):
        Phase((0, 1), (1, 0), (1,))
    with pytest.raises(ValueError, match=">= 1"):
        Phase((0, 1), (1, 0), (1, 0))


def _noaux_tc_per_token(logits, n_group, topk_group, top_k):
    """DeepSeek-V3's noaux_tc for one token, as the model card describes
    it: sigmoid scores; each of ``n_group`` groups of consecutive experts
    scores the sum of its top-2 scores; keep the ``topk_group`` best
    groups; take the ``top_k`` best experts among the kept groups."""
    scores = [1.0 / (1.0 + math.exp(-float(x))) for x in logits]
    size = len(scores) // n_group
    group_score = []
    for g in range(n_group):
        best = sorted(scores[g * size:(g + 1) * size], reverse=True)
        group_score.append(best[0] + best[1])
    kept = sorted(range(n_group), key=lambda g: -group_score[g])[:topk_group]
    candidates = [e for g in kept for e in range(g * size, (g + 1) * size)]
    return set(sorted(candidates, key=lambda e: -scores[e])[:top_k])


@pytest.mark.parametrize("seed", [0, 17, 2**31 + 3])
def test_gate_matches_per_token_noaux_tc_at_published_widths(seed):
    """The vectorised draw against per-token loops: the same selected
    experts and the same copies per (node, node) pair, at DeepSeek-V3's
    widths (256 experts, 8 groups, top-4 groups, top-8 experts) on two
    EP groups of 8 nodes x 24 tokens (384 tokens)."""
    moe = workloads.MoESpec(batch_tokens=8, ranks_per_node=3, sigma=0.5)
    groups, nodes = 2, 8
    tokens = moe.batch_tokens * moe.ranks_per_node
    rng = np.random.default_rng(seed)
    want = np.zeros((groups, nodes, nodes), dtype=np.int64)
    for r in range(groups):
        bias = rng.normal(0.0, moe.sigma, moe.n_routed_experts)
        for s in range(nodes):
            logits = rng.standard_normal((tokens,
                                          moe.n_routed_experts)) + bias
            got = workloads.noaux_tc(logits, moe)
            for t in range(tokens):
                chosen = _noaux_tc_per_token(logits[t], 8, 4, 8)
                assert set(got[t].tolist()) == chosen
                # node-limited: at most topk_group nodes a token
                assert len({e // 32 for e in chosen}) <= 4
                for d in {e // 32 for e in chosen} - {s}:
                    want[r, s, d] += 1
    assert np.array_equal(
        workloads._gate_copies(moe, groups, nodes, seed), want)


def test_moe_workload_follows_the_row_schedule():
    """Dispatch phase k is the last dimension's Circle step k inside every
    row, 2 packets a copy; combine phase k the same step carrying 4
    packets a copy the other way; per-pair counts follow the draw."""
    fab = make_fabric(HyperXConfig(dims=(3, 8), terminals=2,
                                   instance="circle"))
    moe = workloads.MoESpec(batch_tokens=16, ranks_per_node=2)
    w = workloads.moe_dispatch_workload(fab, moe, seed=5)
    copies = workloads._gate_copies(moe, 3, 8, 5)
    sched = fab.schedule()[-1]
    assert w.num_phases == 2 * sched.num_steps == 14
    for k in range(7):
        row = sched.partners(k)
        disp, comb = w.phases[k], w.phases[7 + k]
        for ph, per_copy, transpose in ((disp, 2, False), (comb, 4, True)):
            for s, d, m in zip(ph.src, ph.dst, ph.messages):
                assert s // 8 == d // 8 and d % 8 == row[s % 8]
                a, b = (d % 8, s % 8) if transpose else (s % 8, d % 8)
                assert m == per_copy * copies[s // 8, a, b]
    assert w.num_packets == 6 * copies.sum()
    assert w.ideal_cycles == sum(ph.peak_messages for ph in w.phases)
    again = workloads.moe_dispatch_workload(fab, moe, seed=5)
    assert again == w
    assert workloads.moe_dispatch_workload(fab, moe, seed=6) != w


def test_moe_workload_refuses_what_it_cannot_place():
    moe = workloads.MoESpec(batch_tokens=2, ranks_per_node=1)
    with pytest.raises(ValueError, match="HyperX"):
        workloads.moe_dispatch_workload(make_fabric("xor", 8), moe, 0)
    with pytest.raises(ValueError, match="last dimension is 8"):
        workloads.moe_dispatch_workload(
            make_fabric(HyperXConfig(dims=(8, 4), terminals=1)), moe, 0)
    with pytest.raises(ValueError, match="unknown moe params"):
        workloads.MoESpec.coerce({"experts": 256})
    with pytest.raises(ValueError, match="topk_group"):
        workloads.MoESpec(topk_group=9)


@pytest.mark.parametrize("chunk", range(4))
def test_moe_full_size_packet_counts_share_one_bucket(chunk):
    """The benchmark's deployment (12 x 8 HyperX, 128 tokens a rank, 7
    ranks a node, sigma 0.5): the packet counts of 64 seeds, 16 per case,
    fall in one ``_bucket_count`` rung, so every grid reuses one
    compiled program.  Host only: no simulation."""
    from repro.sim.xengine import _bucket_count
    fab = make_fabric(HyperXConfig(dims=(12, 8), terminals=7,
                                   instance="circle"))
    moe = workloads.MoESpec(batch_tokens=128, ranks_per_node=7, sigma=0.5)
    rungs = {_bucket_count(6 * workloads._gate_copies(moe, 12, 8, seed).sum())
             for seed in range(16 * chunk, 16 * chunk + 16)}
    assert rungs == {_bucket_count(1_789_000)} == {2_097_152}
