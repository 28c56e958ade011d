"""repro.workload: arrival processes, serving traffic, HLO extraction.

Property tests run under hypothesis.  The extraction tests compile a
real multi-device training step in a subprocess (XLA_FLAGS must be set before jax imports), lower
its collective sequence, and replay the result on both cycle engines.
"""
import json
import os
import subprocess
import sys
import textwrap

import numpy as np
import pytest
from hypothesis import given, settings
from hypothesis import strategies as st

from repro.fabric import make_fabric
from repro.workload import ArrivalSpec, serving_demands, serving_traffic

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


# ---------------------------------------------------------------------------
# ArrivalSpec properties.
# ---------------------------------------------------------------------------

@settings(max_examples=15, deadline=None)
@given(kind=st.sampled_from(["poisson", "mmpp"]),
       rate=st.floats(min_value=0.005, max_value=0.08),
       seed=st.integers(min_value=0, max_value=2**16))
def test_arrivals_deterministic_and_sorted(kind, rate, seed):
    spec = ArrivalSpec(kind=kind, rate=rate)
    src1, gen1 = spec.arrivals(n=8, horizon=64, seed=seed)
    src2, gen2 = spec.arrivals(n=8, horizon=64, seed=seed)
    np.testing.assert_array_equal(src1, src2)
    np.testing.assert_array_equal(gen1, gen2)
    # (src, gen)-sorted and in range: the order both engines rely on.
    order = np.lexsort((gen1, src1))
    np.testing.assert_array_equal(order, np.arange(order.size))
    if src1.size:
        assert 0 <= src1.min() and src1.max() < 8
        assert 0 <= gen1.min() and gen1.max() < 64


@settings(max_examples=10, deadline=None)
@given(kind=st.sampled_from(["poisson", "mmpp"]),
       rate=st.floats(min_value=0.01, max_value=0.06),
       seed=st.integers(min_value=0, max_value=2**16))
def test_arrivals_rate_conservation(kind, rate, seed):
    n, horizon = 16, 400
    spec = ArrivalSpec(kind=kind, rate=rate)
    src, _ = spec.arrivals(n=n, horizon=horizon, seed=seed)
    expected = spec.mean_rate * n * horizon
    # Poisson counts concentrate at sqrt(mean); the MMPP window mean has
    # extra variance from state correlation (~1/(p_on+p_off) cycles), so
    # the bound is loose — it still catches any systematic rate error.
    assert abs(src.size - expected) < 0.4 * expected + 40


@settings(max_examples=10, deadline=None)
@given(rate=st.floats(min_value=0.02, max_value=0.06),
       seed=st.integers(min_value=0, max_value=2**16))
def test_arrivals_scale_increases_volume(rate, seed):
    spec = ArrivalSpec(kind="poisson", rate=rate)
    base, _ = spec.arrivals(n=16, horizon=300, seed=seed)
    scaled, _ = spec.arrivals(n=16, horizon=300, seed=seed, scale=3.0)
    assert scaled.size > base.size


def test_arrivals_empty_window_and_zero_rate():
    src, gen = ArrivalSpec(rate=0.05).arrivals(n=4, horizon=0, seed=1)
    assert src.size == 0 and gen.size == 0
    for kind in ("poisson", "mmpp"):
        src, gen = ArrivalSpec(kind=kind, rate=0.0).arrivals(
            n=4, horizon=200, seed=1)
        assert src.size == 0 and gen.size == 0


def test_arrivals_pinned_seed_ignores_caller_seed():
    spec = ArrivalSpec(rate=0.05, seed=11)
    a = spec.arrivals(n=8, horizon=100, seed=1)
    b = spec.arrivals(n=8, horizon=100, seed=2)
    np.testing.assert_array_equal(a[0], b[0])
    np.testing.assert_array_equal(a[1], b[1])


@settings(max_examples=15, deadline=None)
@given(kind=st.sampled_from(["poisson", "mmpp", "trace"]),
       rate=st.floats(min_value=0.001, max_value=0.2),
       seed=st.integers(min_value=0, max_value=99))
def test_arrival_spec_json_roundtrip(kind, rate, seed):
    kw = {"kind": kind, "rate": rate, "seed": seed}
    if kind == "trace":
        kw["times"] = (5, 1, 9)
        kw["sources"] = (2, 0, 1)
    spec = ArrivalSpec(**kw)
    assert ArrivalSpec.from_json(spec.to_json()) == spec


def test_trace_canonicalization_and_replay():
    spec = ArrivalSpec(kind="trace", times=(9, 1, 5), sources=(1, 2, 0))
    assert spec.times == (1, 5, 9)          # sorted by (time, source)
    assert spec.sources == (2, 0, 1)
    src, gen = spec.arrivals(n=4, horizon=6, seed=0)
    # 9 >= horizon dropped; output re-sorted by (src, gen) like every
    # arrival stream, so (t=5, s=0) precedes (t=1, s=2).
    np.testing.assert_array_equal(src, [0, 2])
    np.testing.assert_array_equal(gen, [5, 1])
    with pytest.raises(ValueError, match="rate-scaled"):
        spec.arrivals(n=4, horizon=6, seed=0, scale=2.0)


def test_trace_validation():
    with pytest.raises(ValueError, match="at least one"):
        ArrivalSpec(kind="trace")
    with pytest.raises(ValueError, match="match"):
        ArrivalSpec(kind="trace", times=(1, 2), sources=(0,))
    spec = ArrivalSpec(kind="trace", times=(0,), sources=(9,))
    with pytest.raises(ValueError, match="outside"):
        spec.arrivals(n=4, horizon=10)


def test_mmpp_mean_rate_matches_mixture():
    spec = ArrivalSpec(kind="mmpp", rate=0.02, burst=5.0, p_on=0.1,
                       p_off=0.3)
    pi = 0.1 / 0.4
    assert spec.mean_rate == pytest.approx(0.02 * (1 - pi) + 0.1 * pi)


# ---------------------------------------------------------------------------
# Serving traffic and per-request metrics.
# ---------------------------------------------------------------------------

def test_serving_traffic_shape_and_demands():
    tr = serving_traffic(ArrivalSpec(rate=0.04), 8, cycles=200,
                         packets_per_request=3, slo=25.0, seed=3)
    assert tr.request is not None and tr.slo == 25.0
    assert tr.num_packets % 3 == 0
    counts = np.bincount(tr.request)
    assert (counts == 3).all()              # every request fans 3 packets
    assert (tr.src != tr.dst).all()         # peers exclude the source
    s, d, rate = serving_demands(tr, 8)
    assert rate.sum() * tr.horizon == pytest.approx(tr.num_packets)
    assert (s != d).all()


def test_serving_cross_engine_exact_agreement():
    """The same Traffic through numpy and the compiled engine: request
    counts and drained deliveries are exact.  Percentiles agree within
    the seed-matched latency tolerance of ``tests/test_xengine.py``: the
    engines break arbitration ties from different RNG streams, so the
    order in which contending packets leave a queue may differ."""
    from repro.sim import xengine
    from repro.sim.engine import simulate
    from repro.sim.policies import make_policy
    topo = make_fabric("xor", 8).sim_topology()
    tr = serving_traffic(ArrivalSpec(rate=0.04), 8, cycles=150,
                         packets_per_request=4, slo=30.0, seed=5)
    a = simulate(topo, make_policy("minimal"), tr, cycles=150, warmup=0,
                 drain=True)
    b = xengine.simulate_jax(topo, make_policy("minimal"), tr, cycles=150,
                             warmup=0, drain=True)
    assert a.request_count == b.request_count > 0
    assert a.packets_delivered == b.packets_delivered \
        == a.packets_generated == b.packets_generated
    for pct in ("p50", "p95", "p99"):
        name = f"request_latency_{pct}"
        assert getattr(b, name) == pytest.approx(getattr(a, name),
                                                 rel=0.25, abs=2.0)
    assert b.slo_attainment == pytest.approx(a.slo_attainment, rel=0.12)
    assert a.request_latency_p50 <= a.request_latency_p95 \
        <= a.request_latency_p99


def test_serving_engine_arrival_trace():
    """Submitted requests record their decode-step arrival and export a
    replayable trace-kind ArrivalSpec."""
    from repro.models import ModelConfig
    from repro.serving.engine import Request, ServingEngine
    cfg = ModelConfig(name="t", family="dense", num_layers=1, d_model=16,
                      num_heads=2, num_kv_heads=1, d_ff=32, vocab_size=32)
    eng = ServingEngine(cfg, None, slots=2, max_seq=16)
    eng.submit(Request(0, np.array([1, 2], np.int32)), at=3)
    eng.submit(Request(1, np.array([1], np.int32)))        # clock is 0
    trace = eng.arrival_trace()
    assert trace.kind == "trace" and trace.times == (0, 3)
    src, gen = trace.arrivals(n=4, horizon=8, seed=0)
    assert gen.size == 2 and src.size == 2


def test_request_latency_summary_incomplete_request():
    from repro.sim.metrics import request_latency_summary
    rs = request_latency_summary(request=[0, 0, 1, 1], gen=[2, 2, 5, 5],
                                 deliver=[4, 6, -1, 8])
    assert rs["count"] == 2 and rs["completed"] == 1
    np.testing.assert_array_equal(rs["arrival"], [2, 5])
    np.testing.assert_array_equal(rs["latency"], [5, -1])   # open req = -1


def test_request_events_spans():
    from repro.obs import request_events, validate_trace_events
    ev = request_events(request=[0, 0, 1], gen=[2, 2, 5],
                        deliver=[4, 6, -1], slo=4.0)
    validate_trace_events(ev)
    spans = [e for e in ev if e["ph"] == "X"]
    opens = [e for e in ev if e["ph"] == "I"]
    assert len(spans) == 1 and len(opens) == 1
    assert spans[0]["ts"] == 2 and spans[0]["dur"] == 5
    assert spans[0]["args"]["slo_met"] is False


# ---------------------------------------------------------------------------
# HLO parsing and lowering.
# ---------------------------------------------------------------------------

_SYNTH_HLO = textwrap.dedent("""\
    HloModule synth

    %cond.1 (arg.0: (s32[], f32[64])) -> pred[] {
      %p0 = (s32[], f32[64]) parameter(0)
      %i = s32[] get-tuple-element(%p0), index=0
      ROOT %lt = pred[] compare(%i, %i), direction=LT
    }

    %body.2 (arg.1: (s32[], f32[64])) -> (s32[], f32[64]) {
      %p1 = (s32[], f32[64]) parameter(0)
      %x = f32[64] get-tuple-element(%p1), index=1
      %cp = f32[64] collective-permute(%x), source_target_pairs={{0,1},{1,2},{2,3}}
      %j = s32[] get-tuple-element(%p1), index=0
      ROOT %tup = (s32[], f32[64]) tuple(%j, %cp)
    }

    ENTRY %main.3 (a: f32[64]) -> f32[64] {
      %z = s32[] constant(0)
      %t0 = (s32[], f32[64]) tuple(%z, %a)
      %w = (s32[], f32[64]) while(%t0), condition=%cond.1, body=%body.2, backend_config={"known_trip_count":{"n":"5"}}
      ROOT %out = f32[64] get-tuple-element(%w), index=1
    }
""")


def test_collective_sequence_sees_tuple_param_while_body():
    """Computation headers with tuple-typed params (every while body)
    must parse; their collectives carry the loop's trip multiplier."""
    from repro.launch.hlo_analysis import collective_sequence, parse_module
    comps, entry = parse_module(_SYNTH_HLO)
    assert entry == "main.3"
    assert "body.2" in comps and "cond.1" in comps
    ops = collective_sequence(_SYNTH_HLO, 4)
    assert len(ops) == 1
    op = ops[0]
    assert op.kind == "collective-permute"
    assert op.count == 5
    assert op.pairs == ((0, 1), (1, 2), (2, 3))
    assert op.raw_bytes == 64 * 4


def test_workload_from_hlo_permute_lowering():
    from repro.workload import workload_from_hlo
    from repro.sim.workloads import replay
    w = workload_from_hlo(_SYNTH_HLO, ("xor", 4), bytes_per_packet=128)
    # ceil(256 / 128) = 2 packets per pair, 5 loop trips.
    assert all(p.messages == 2 for p in w.phases)
    assert sum(len(p.src) for p in w.phases) == 3 * 5
    topo = make_fabric("xor", 4).sim_topology()
    stats = replay(topo, "minimal", w, backend="numpy")
    assert stats.completion_cycles >= stats.ideal_cycles
    assert stats.in_flight_at_end == 0


# ---------------------------------------------------------------------------
# Real extraction: compile an 8-device MoE step, lower, replay on both
# engines.  The compile needs XLA_FLAGS before jax imports -> subprocess.
# ---------------------------------------------------------------------------

_EXTRACT_CHILD = """
import json
from repro.workload import moe_step_hlo, workload_from_hlo
hlo = moe_step_hlo(8, d_model=32, d_ff=16, batch=4, seq=8)
w = workload_from_hlo(hlo, ("xor", 8), bytes_per_packet=256)
print("RESULT " + json.dumps(w.to_dict()))
"""


@pytest.fixture(scope="module")
def extracted_moe_workload():
    env = dict(os.environ,
               XLA_FLAGS="--xla_force_host_platform_device_count=8",
               PYTHONPATH="src")
    res = subprocess.run([sys.executable, "-c", _EXTRACT_CHILD], env=env,
                         capture_output=True, text=True, timeout=600,
                         cwd=REPO)
    assert res.returncode == 0, res.stderr[-2000:]
    line = [l for l in res.stdout.splitlines() if l.startswith("RESULT ")][-1]
    return json.loads(line[len("RESULT "):])


def test_extracted_moe_workload_structure(extracted_moe_workload):
    from repro.sim.workloads import Workload
    w = Workload.from_dict(extracted_moe_workload)
    assert w.num_switches == 8
    assert len(w.phases) > 0
    assert all(p.messages >= 1 for p in w.phases)
    # JSON round-trip is exact (the store/CLI contract).
    assert Workload.from_dict(w.to_dict()).to_dict() == w.to_dict()


def test_extracted_moe_replay_cross_engine(extracted_moe_workload):
    from repro.sim.workloads import Workload, replay
    w = Workload.from_dict(extracted_moe_workload)
    topo = make_fabric("xor", 8).sim_topology()
    a = replay(topo, "minimal", w, backend="numpy")
    b = replay(topo, "minimal", w, backend="jax")
    assert a.completion_cycles >= a.ideal_cycles
    assert a.in_flight_at_end == 0
    assert a.completion_cycles == b.completion_cycles
    assert tuple(a.phase_cycles) == tuple(b.phase_cycles)


# ---------------------------------------------------------------------------
# Studies integration: serving specs, SLO capacity, flow cross-check,
# forward-compatible Result records.
# ---------------------------------------------------------------------------

def _serving_spec(slo=40.0, rate=0.05, cycles=150):
    from repro.studies import (ExperimentSpec, FabricSpec, RoutingSpec,
                               SweepSpec, TrafficSpec)
    return ExperimentSpec(
        fabric=FabricSpec(kind="cin", params={"instance": "xor", "n": 8}),
        traffic=TrafficSpec(pattern="serving",
                            params={"arrival": {"kind": "poisson",
                                                "rate": rate},
                                    "packets_per_request": 2, "slo": slo}),
        routing=RoutingSpec(policy="minimal"),
        sweep=SweepSpec(loads=(1.0,), seeds=(3,), cycles=cycles, warmup=0),
        terminals=1, engine={"drain": True})


def test_serving_spec_roundtrip_and_label():
    from repro.studies import ExperimentSpec
    spec = _serving_spec()
    assert ExperimentSpec.from_json(spec.to_json()) == spec
    assert "serving-poisson" in spec.name


def test_serving_study_numpy_vs_flow():
    from repro.studies import Study
    spec = _serving_spec()
    cyc = Study(spec, backend="numpy").run()
    flow = Study(spec, backend="flow").run()
    rc = [r for r in cyc.results if r.request_count is not None]
    rf = [r for r in flow.results if r.request_count is not None]
    assert len(rc) == len(rf) == 1
    # Same seeded arrival stream on both tiers...
    assert rc[0].request_count == rf[0].request_count > 0
    # ...and the flow proxy is a lower bound on per-request latency.
    assert rf[0].request_latency_p99 <= rc[0].request_latency_p99
    assert rc[0].slo_attainment is not None
    assert rc[0].fidelity == "cycle" and rf[0].fidelity == "flow"


def test_slo_capacity_search():
    from repro.studies import Study
    cap = Study(_serving_spec(), backend="numpy").slo_capacity(
        percentile=99.0, lo=0.1, hi=1.0, tol=0.2)
    assert set(cap) >= {"experiment", "percentile", "slo", "probes",
                        "capacity"}
    assert cap["probes"]
    assert 0.0 <= cap["capacity"] <= 1.0


def test_result_record_preserves_unknown_fields():
    """A store written by a newer repo version round-trips through
    load -> append untouched (satellite: show must not drop fields)."""
    from repro.studies import Result
    from repro.studies import Study
    out = Study(_serving_spec(), backend="numpy").run()
    rec = out.results[0].record()
    assert "request_count" in rec and "slo_attainment" in rec
    rec2 = dict(rec, future_metric=1.5)
    r2 = Result.from_record(rec2)
    assert r2.extra == {"future_metric": 1.5}
    assert r2.record() == rec2
