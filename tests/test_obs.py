"""Observability (repro.obs): traces, spans, telemetry, provenance.

The contract under test: tracing is *free of semantic side effects*
(trace-on and trace-off runs produce identical dynamics on both
engines), the two engines' time series agree **exactly** on drained
deterministic workloads (collective replays whose phases are matchings,
one-shot permutations), a stride-k trace is precisely the stride-1
trace downsampled, and every run carries compile-vs-execute telemetry
that survives the studies store round-trip.  Plus the Perfetto export
schema, the Dragonfly serialization plateau made visible, and the CLI.
"""
import json

import numpy as np
import pytest

from repro.core.dragonfly import DragonflyConfig
from repro.fabric import make_fabric
from repro.obs import (Trace, TraceConfig, counter_events, derive_backlog,
                       export_perfetto, link_classes, packet_events,
                       phase_events, replay_trace_events, timed_compiled,
                       validate_trace_events)
from repro.sim import simulate
from repro.sim.policies import make_policy
from repro.sim.traffic import one_shot_permutation


def _cin16():
    return make_fabric("xor", 16)


def _replay(backend, **kw):
    return _cin16().replay("all_to_all", message_size=2, backend=backend,
                           **kw)


# ---------------------------------------------------------------------------
# TraceConfig / Trace containers.
# ---------------------------------------------------------------------------

def test_trace_config_coerce_forms():
    assert TraceConfig.coerce(None) is None
    assert TraceConfig.coerce(False) is None
    assert TraceConfig.coerce(True) == TraceConfig()
    cfg = TraceConfig(stride=4, packets=2)
    assert TraceConfig.coerce(cfg) is cfg
    assert TraceConfig.coerce({"stride": 4, "packets": 2}) == cfg
    with pytest.raises(TypeError):
        TraceConfig.coerce("yes")
    with pytest.raises(ValueError):
        TraceConfig(stride=0)
    with pytest.raises(ValueError):
        TraceConfig(max_samples=0)


def test_trace_round_trips_through_dict():
    tr = _replay("numpy", trace=TraceConfig(packets=4)).trace
    back = Trace.from_dict(json.loads(json.dumps(tr.to_dict())))
    assert back.equals(tr)
    assert back.events == tr.events
    assert back.meta["backend"] == "numpy"
    assert tr.diff_summary(back) == "traces are equal"


def test_trace_diff_summary_localizes_mismatch():
    tr = _replay("numpy", trace=True).trace
    other = Trace.from_dict(tr.to_dict())
    other.delivered[3] += 7
    assert not tr.equals(other)
    assert "delivered" in tr.diff_summary(other)


# ---------------------------------------------------------------------------
# Numpy engine tracing semantics.
# ---------------------------------------------------------------------------

def test_numpy_trace_channels_are_consistent():
    stats = _replay("numpy", trace=TraceConfig(packets=8))
    tr = stats.trace
    # end-of-cycle sampling over exactly the executed cycles [0, completion]
    assert tr.cycles[0] == 0
    assert tr.cycles[-1] == stats.completion_cycles
    assert tr.num_samples == stats.completion_cycles + 1
    # cumulative channels are monotone; the drained run ends settled
    for ch in (tr.link_load, tr.injected):
        assert (np.diff(ch, axis=0) >= 0).all()
    assert (np.diff(tr.delivered) >= 0).all()
    assert tr.delivered[-1] == stats.packets_generated
    assert tr.in_flight[-1] == 0
    assert tr.backlog.min() >= 0 and tr.backlog[-1].sum() == 0
    # injected counts every packet exactly once by the end
    assert tr.injected[-1].sum() == stats.packets_generated
    # utilization is a fraction of link-cycles
    util = tr.link_util()
    assert util.shape == (tr.num_samples,)
    assert 0 <= util.min() and util.max() <= 1


def test_numpy_packet_spans_follow_sampled_packets():
    k = 6
    tr = _replay("numpy", trace=TraceConfig(packets=k)).trace
    pids = {ev[0] for ev in tr.events}
    assert len(pids) == k
    by_pid = {}
    for pid, cycle, frm, to in tr.events:
        by_pid.setdefault(pid, []).append((cycle, frm, to))
    for pid, hops in by_pid.items():
        hops.sort()
        # every traced packet's record ends with its ejection...
        assert hops[-1][2] == -1
        # ...and consecutive hops chain: each move arrives where the
        # next one departs.
        for (c0, f0, t0), (c1, f1, _t1) in zip(hops, hops[1:]):
            assert c0 < c1
            assert t0 == -1 or t0 == f1


def test_trace_off_is_bitwise_identical_numpy():
    base = _replay("numpy")
    traced = _replay("numpy", trace=TraceConfig(packets=4))
    assert base.completion_cycles == traced.completion_cycles
    assert base.phase_cycles == traced.phase_cycles
    assert np.array_equal(base.link_loads, traced.link_loads)
    assert np.array_equal(base.latency_histogram, traced.latency_histogram)
    assert base.latency_mean == traced.latency_mean


def test_trace_off_is_bitwise_identical_jax():
    base = _replay("jax")
    traced = _replay("jax", trace=True)
    assert base.completion_cycles == traced.completion_cycles
    assert base.phase_cycles == traced.phase_cycles
    assert np.array_equal(base.link_loads, traced.link_loads)
    assert np.array_equal(base.latency_histogram, traced.latency_histogram)
    assert base.latency_mean == traced.latency_mean


# ---------------------------------------------------------------------------
# Cross-engine exact agreement (deterministic drained workloads).
# ---------------------------------------------------------------------------

def test_engines_trace_equal_on_cin_replay():
    a = _replay("numpy", trace=True).trace
    b = _replay("jax", trace=True).trace
    assert a.equals(b), a.diff_summary(b)
    assert b.meta["backend"] == "jax" and b.events == []


def test_engines_trace_equal_on_drained_permutation():
    topo = _cin16().sim_topology()
    pol = make_policy("minimal")
    traces = {}
    partners = (np.arange(16) + 5) % 16
    for be in ("numpy", "jax"):
        traces[be] = simulate(topo, pol, one_shot_permutation(partners),
                              backend=be, trace=True).trace
    assert traces["numpy"].equals(traces["jax"]), \
        traces["numpy"].diff_summary(traces["jax"])


@pytest.mark.parametrize("backend", ["numpy", "jax"])
def test_stride_k_is_downsampled_stride_1(backend):
    fine = _replay(backend, trace=TraceConfig(stride=1)).trace
    coarse = _replay(backend, trace=TraceConfig(stride=3)).trace
    assert coarse.stride == 3
    assert coarse.equals(fine.downsample(3)), \
        coarse.diff_summary(fine.downsample(3))


def test_max_samples_caps_rows_identically():
    cfg = TraceConfig(max_samples=7)
    a = _replay("numpy", trace=cfg).trace
    b = _replay("jax", trace=cfg).trace
    assert a.num_samples == b.num_samples == 7
    assert a.equals(b), a.diff_summary(b)


def test_batched_sweep_traces_slice_per_point():
    """Two copies of the same deterministic replay batched into one
    compiled program must each reproduce the oracle's trace — pinning
    the per-copy column slicing of the flat ring buffers."""
    from repro.sim import xengine
    from repro.sim.workloads import collective_workload
    fab = _cin16()
    oracle = _replay("numpy", trace=True).trace
    w = collective_workload(fab, "all_to_all", message_size=2)
    grid = xengine.sweep(fab.sim_topology(), make_policy("minimal"),
                         lambda _l, _s: w.traffic(), [0.0], seeds=(0, 1),
                         warmup=0, trace=True)
    for stats in grid[0]:
        assert stats.trace.equals(oracle), stats.trace.diff_summary(oracle)
        assert stats.timing["grid_points"] == 2


# ---------------------------------------------------------------------------
# Backlog derivation.
# ---------------------------------------------------------------------------

def test_derive_backlog_open_loop_math():
    # 2 switches; switch 0 owns gens [0, 2, 2], switch 1 owns [1]
    gen = np.array([0, 2, 2, 1])
    blk_start, blk_end = np.array([0, 3]), np.array([3, 4])
    cycles = np.array([0, 1, 2, 3])
    injected = np.zeros((4, 2), np.int64)
    out = derive_backlog(cycles, injected, gen, blk_start, blk_end)
    assert out.tolist() == [[1, 0], [1, 1], [3, 1], [3, 1]]
    # injections subtract
    injected[:, 0] = [1, 1, 2, 3]
    out = derive_backlog(cycles, injected, gen, blk_start, blk_end)
    assert out[:, 0].tolist() == [0, 0, 1, 0]


def test_derive_backlog_replay_gates_on_phases():
    gen = np.array([0, 1, 2])          # phase ordinals, one switch
    blk_start, blk_end = np.array([0]), np.array([3])
    phase_done = np.array([4, 9, -1])  # phase 2 incomplete
    cycles = np.array([0, 4, 5, 9, 10])
    injected = np.zeros((5, 1), np.int64)
    out = derive_backlog(cycles, injected, gen, blk_start, blk_end,
                         phase_done=phase_done)
    # eligible = packets whose phase < completed-phase count at the cycle
    assert out[:, 0].tolist() == [1, 2, 2, 3, 3]


# ---------------------------------------------------------------------------
# Spans + Perfetto export.
# ---------------------------------------------------------------------------

def test_phase_events_cover_the_replay():
    stats = _replay("numpy")
    evs = [e for e in phase_events(stats) if e["ph"] == "X"]
    assert len(evs) == len(stats.phase_cycles)
    assert sum(e["dur"] for e in evs) == stats.completion_cycles
    assert evs[-1]["ts"] + evs[-1]["dur"] == stats.completion_cycles


def test_export_perfetto_payload_loads(tmp_path):
    stats = _replay("numpy", trace=TraceConfig(packets=8))
    out = tmp_path / "replay.json"
    payload = export_perfetto(str(out),
                              replay_trace_events(stats,
                                                  topo=_cin16().sim_topology()))
    on_disk = json.loads(out.read_text())
    assert on_disk == payload
    events = on_disk["traceEvents"]
    validate_trace_events(events)
    phs = {e["ph"] for e in events}
    assert phs <= {"X", "C", "M"}
    assert any(e["ph"] == "X" and e.get("cat") == "packet" for e in events)
    counters = {e["name"] for e in events if e["ph"] == "C"}
    assert {"in_flight", "inj_backlog"} <= counters
    assert any(n.startswith("link_util") for n in counters)


def test_validate_trace_events_rejects_bad_events():
    ok = [{"name": "a", "ph": "X", "ts": 0, "dur": 1, "pid": 1, "tid": 0}]
    assert validate_trace_events(ok) is ok
    for bad, msg in [
            ([{"name": "a", "ph": "Z", "ts": 0}], "unknown phase"),
            ([{"ph": "X", "ts": 0, "dur": 1}], "missing name"),
            ([{"name": "a", "ph": "X", "ts": 0.5, "dur": 1}], "ts"),
            ([{"name": "a", "ph": "X", "ts": 0, "dur": -1}], "dur"),
            ([{"name": "a", "ph": "X", "ts": 0}], "dur"),
            ([{"name": "a", "ph": "C", "ts": 0}], "args"),
            ("nope", "list"),
    ]:
        with pytest.raises(ValueError, match=msg):
            validate_trace_events(bad)


def test_counter_events_round_values():
    evs = counter_events("u", [0, 2], [0.123456789, 1.0])
    samples = [e for e in evs if e["ph"] == "C"]
    assert [e["args"]["u"] for e in samples] == [0.123457, 1.0]
    assert [e["ts"] for e in samples] == [0, 2]


def test_packet_events_lane_per_switch():
    tr = _replay("numpy", trace=TraceConfig(packets=8)).trace
    evs = packet_events(tr)
    spans = [e for e in evs if e["ph"] == "X"]
    lanes = {e["tid"] for e in spans}
    assert spans and all(e["dur"] >= 1 for e in spans)
    # each span sits on the lane of the switch the hop arrived at
    assert all(e["tid"] == e["args"]["to"] for e in spans)
    named = {e["tid"] for e in evs
             if e["ph"] == "M" and e["name"] == "thread_name"}
    assert lanes <= named


# ---------------------------------------------------------------------------
# The Dragonfly serialization plateau, measured from the trace.
# ---------------------------------------------------------------------------

def test_dragonfly72_trace_shows_serialization_plateau():
    fab = make_fabric(DragonflyConfig(group_size=6, terminals_per_switch=2,
                                      global_ports_per_switch=2,
                                      num_groups=12))
    stats = fab.replay("all_to_all", message_size=2,
                       trace=TraceConfig(packets=8))
    ratio = stats.completion_cycles / stats.ideal_cycles
    assert ratio > 3, ratio          # the ~4.4x headline serialization
    topo = fab.sim_topology()
    classes = link_classes(topo)
    assert classes["global"].any() and classes["local"].any()
    tr = stats.trace
    # per-cycle traversals over the scarce global wires
    g_load = tr.link_load[:, classes["global"]].sum(axis=1)
    g_rate = np.diff(np.concatenate([[0], g_load]))
    busy = g_rate > 0
    # global phases dominate the run (that's where the 4.4x comes from)...
    assert busy.mean() > 0.5
    # ...and while one is active, every group's chosen global link is
    # saturated: the plateau sits at exactly num_groups traversals/cycle.
    assert g_rate.max() == fab.config.num_groups
    assert np.median(g_rate[busy]) == fab.config.num_groups
    # the exported trace carries the split as separate counter tracks
    names = {e["name"] for e in replay_trace_events(stats, topo=topo)
             if e["ph"] == "C"}
    assert {"link_util/global", "link_util/local"} <= names


def test_link_classes_flat_fabric_is_all_local():
    topo = _cin16().sim_topology()
    classes = link_classes(topo)
    assert set(classes) == {"local"}
    assert classes["local"].sum() == np.count_nonzero(
        topo.neighbor.reshape(-1) >= 0)


# ---------------------------------------------------------------------------
# Telemetry: compile-vs-execute, provenance, store round-trip.
# ---------------------------------------------------------------------------

def test_numpy_runs_carry_wall_clock_timing():
    stats = _replay("numpy")
    t = stats.timing
    assert t["backend"] == "numpy" and t["compile_s"] == 0.0
    assert t["execute_s"] > 0 and t["total_s"] == t["execute_s"]


def test_jax_runs_split_compile_from_execute():
    from repro.obs.telemetry import clear_caches
    clear_caches(memory=True, disk=True)
    # message_size=4 gives this test a program no other test in the
    # session compiles, so the cold run is genuinely cold (jax keeps its
    # own in-process HLO-level compile cache that clear_caches cannot
    # reach — a shape-identical program compiled elsewhere would make
    # "cold" compile in milliseconds and invert the timing assertions).
    _cache_replay = lambda: _cin16().replay(  # noqa: E731
        "all_to_all", message_size=4, backend="jax")
    cold = _cache_replay()
    warm = _cache_replay()
    assert cold.timing["backend"] == "jax"
    assert not cold.timing["compile_cached"]
    assert cold.timing["compile_s"] > 0 and cold.timing["execute_s"] > 0
    assert warm.timing["compile_cached"] == "memory"
    assert warm.timing["compile_s"] == 0.0
    # dropping the memory layer falls back to the persistent disk layer:
    # same program, deserialized in milliseconds instead of recompiled
    clear_caches(memory=True, disk=False)
    disk = _cache_replay()
    assert disk.timing["compile_cached"] == "disk"
    assert disk.timing["compile_s"] < cold.timing["compile_s"]


def test_timed_compiled_caches_per_signature():
    import jax
    import jax.numpy as jnp
    from functools import partial

    calls = []

    @partial(jax.jit, static_argnums=0)
    def f(k, x):
        calls.append(k)
        return x * k

    x = jnp.arange(4.0)
    out1, t1 = timed_compiled(f, 3, x)
    out2, t2 = timed_compiled(f, 3, x)
    _, t3 = timed_compiled(f, 4, x)
    assert np.array_equal(np.asarray(out1), np.asarray(out2))
    assert not t1["compile_cached"] and t2["compile_cached"]
    assert not t3["compile_cached"]       # new static arg -> new program
    _, t4 = timed_compiled(f, 3, jnp.arange(8.0))
    assert not t4["compile_cached"]       # new shape -> new program


def test_result_provenance_round_trips_through_store(tmp_path):
    from repro.studies import JsonlStore, Result
    stats = _replay("numpy")
    res = Result.from_stats(stats, key="k", experiment="e", load=0.0,
                            seed=0, backend="numpy", spec_digest="d1")
    assert res.in_flight_at_end == 0
    prov = res.provenance
    assert prov["backend"] == "numpy" and prov["spec_digest"] == "d1"
    assert prov["timings"] == stats.timing
    assert prov["numpy"] == np.__version__
    import jax
    dev = jax.devices()[0]
    assert prov["device"] == {"platform": dev.platform,
                              "kind": dev.device_kind,
                              "count": len(jax.devices())}
    store = JsonlStore(tmp_path / "r.jsonl")
    store.append(res)
    back = store.load()["k"]
    assert back.provenance == prov
    assert back.in_flight_at_end == 0
    # records from stores written before the telemetry fields existed
    # still load (defaulted fields)
    old = dict(json.loads(res.to_line()))
    old.pop("provenance")
    old.pop("in_flight_at_end")
    legacy = Result.from_record(old)
    assert legacy.provenance is None and legacy.in_flight_at_end == 0


def test_to_record_carries_replay_and_residue_fields():
    from repro.sim.report import to_record
    stats = _replay("numpy")
    rec = to_record(stats)
    assert rec["completion_cycles"] == stats.completion_cycles
    assert rec["ideal_cycles"] == stats.ideal_cycles
    assert rec["phase_cycles"] == list(stats.phase_cycles)
    assert rec["in_flight_at_end"] == 0
    assert rec["timing"] == stats.timing
    json.dumps(rec)                       # everything JSON-scalar
    # open-loop runs omit the replay keys but keep the residue count
    open_stats = simulate(_cin16().sim_topology(), make_policy("minimal"),
                          one_shot_permutation((np.arange(16) + 1) % 16),
                          backend="numpy")
    open_rec = to_record(open_stats)
    assert "completion_cycles" not in open_rec
    assert "in_flight_at_end" in open_rec


def test_study_telemetry_counts_batched_programs_once(tmp_path):
    from repro import studies
    exp = studies.ExperimentSpec(
        fabric=studies.FabricSpec("cin", {"instance": "xor", "n": 8}),
        traffic=studies.TrafficSpec("uniform"),
        routing=studies.RoutingSpec("minimal"),
        sweep=studies.SweepSpec(loads=(0.2, 0.4), seeds=(0, 1),
                                cycles=120, warmup=30))
    out = studies.Study(exp, backend="jax").run()
    tel = out.telemetry()[exp.name]
    assert tel["points"] == 4
    assert tel["programs"] == 1           # one batched program, counted once
    assert tel["backend"] == "jax"


# ---------------------------------------------------------------------------
# CLI.
# ---------------------------------------------------------------------------

def test_cli_trace_export_both_backends(tmp_path, capsys):
    from repro.studies.__main__ import main as cli
    out = tmp_path / "trace.json"
    rc = cli(["trace", "export", "collective_replay",
              "--experiment", "cin-xor-16/replay-all_to_all/minimal",
              "--backend", "both", "--packets", "4",
              "--out", str(out)])
    assert rc == 0
    text = capsys.readouterr().out
    assert "cross-engine traces agree exactly" in text
    assert "ratio=1.000" in text
    payload = json.loads(out.read_text())
    validate_trace_events(payload["traceEvents"])


def test_cli_trace_export_rejects_unknown_experiment(tmp_path):
    from repro.studies.__main__ import main as cli
    with pytest.raises(SystemExit, match="no experiment named"):
        cli(["trace", "export", "collective_replay",
             "--experiment", "nope", "--out", str(tmp_path / "t.json")])


def test_cli_show_trace_reads_store(tmp_path, capsys, monkeypatch):
    from repro.studies.__main__ import main as cli
    monkeypatch.chdir(tmp_path)
    store = tmp_path / "s.jsonl"
    rc = cli(["run", "studies_smoke", "--backend", "numpy",
              "--store", str(store)])
    assert rc == 0
    capsys.readouterr()
    rc = cli(["show", "studies_smoke", "--trace", "--store", str(store)])
    assert rc == 0
    text = capsys.readouterr().out
    assert "backend=numpy" in text
    assert "compile tax per experiment" in text
    # without a store: a pointer, not a crash
    rc = cli(["show", "studies_smoke", "--trace"])
    assert rc == 0
    assert "no result store" in capsys.readouterr().out
