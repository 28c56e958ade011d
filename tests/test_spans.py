"""In-program tracing (repro.obs.telemetry): the cycle step's named
scopes, the scope map of every acquired program, and the per-grid host
spans, in the timing dict and on the profiler's host plane."""
import glob
import io
import json
import os
import time
from contextlib import redirect_stdout

import pytest

from repro import sim
from repro.obs import telemetry
from repro.obs.telemetry import (clear_caches, scope_map, scope_maps,
                                 span, span_record)
from repro.sim import xengine

STAGES = ("rng", "eject", "route", "arbitrate", "move")
SWEEP_SPANS = ("sweep.traffic", "sweep.pack", "sweep.tables",
               "sweep.transfer", "sweep.acquire", "sweep.execute",
               "sweep.fetch", "sweep.stats")
STUDY_SPANS = ("study.resolve", "study.records")
T, CYCLES = 4, 24


def _sweep(policy="adaptive", seeds=(0,)):
    topo = sim.cin_topology("xor", 16)

    def tf(load, seed):
        return sim.uniform(16, offered=load, cycles=CYCLES, terminals=T,
                           seed=seed)

    return xengine.sweep(topo, policy, tf, [0.3, 0.6], seeds=seeds,
                         terminals=T, cycles=CYCLES)


@pytest.fixture()
def cache(tmp_path, monkeypatch):
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(tmp_path))
    clear_caches(memory=True)
    yield tmp_path
    clear_caches(memory=True)


def _stages_named(m: dict) -> set:
    return {part for path in m.values() for part in path.split("/")
            if part in STAGES + ("sample",)}


@pytest.mark.parametrize("policy", ["minimal", "adaptive"])
def test_compiled_step_names_each_stage(cache, policy):
    _sweep(policy)
    maps = scope_maps()
    assert len(maps) == 1
    (key, m), = maps.items()
    assert key.startswith("jit__run_loop(")
    # Every stage of the step is named; the trace rows are not compiled.
    assert _stages_named(m) == set(STAGES)
    assert all(name.startswith("%") for name in m)


def test_lowered_step_names_each_stage(monkeypatch):
    captured = {}

    def capture(fn, static_arg, *args, **kw):
        captured.update(fn=fn, spec=static_arg, args=args)
        raise RuntimeError("captured")

    monkeypatch.setattr(xengine, "timed_compiled", capture)
    with pytest.raises(RuntimeError, match="captured"):
        _sweep()
    text = captured["fn"].lower(captured["spec"], *captured["args"]
                                ).as_text(debug_info=True)
    for stage in STAGES:
        assert f"/{stage}/" in text, stage


def test_sample_scope_exists_only_when_tracing(cache):
    topo = sim.cin_topology("xor", 16)
    xengine.simulate_jax(
        topo, "minimal", sim.uniform(16, offered=0.4, cycles=CYCLES,
                                     terminals=T, seed=0),
        terminals=T, cycles=CYCLES, trace={"stride": 4})
    (m,) = scope_maps().values()
    assert _stages_named(m) == set(STAGES) | {"sample"}


def test_scope_map_of_fresh_and_disk_restored_programs(cache):
    _sweep()
    fresh = scope_maps()
    assert telemetry.cache_stats()["misses"] >= 1
    clear_caches(memory=True)
    assert scope_maps() == {}
    grid = _sweep(seeds=(5,))
    assert grid[0][0].timing["compile_cached"] == "disk"
    restored = scope_maps()
    assert restored == fresh
    (m,) = restored.values()
    assert _stages_named(m) == set(STAGES)


def test_a_fusion_takes_its_roots_scope():
    import jax
    import jax.numpy as jnp

    def f(x):
        with jax.named_scope("outer"):
            y = jnp.sin(x) * 2.0
        with jax.named_scope("inner"):
            return jnp.cos(y) + 1.0

    compiled = jax.jit(f).lower(jnp.ones(64)).compile()
    key, m = scope_map(compiled)
    assert key.startswith("jit_f")
    fusions = [n for n in m if "fusion" in n]
    assert fusions and all("inner" in m[n].split("/") for n in fusions)


def test_a_sweep_fills_every_span_key_within_its_wall_time(cache):
    _sweep()                                    # compiles
    t0 = time.perf_counter()
    grid = _sweep(seeds=(3,))
    wall = time.perf_counter() - t0
    timing = grid[0][0].timing
    for name in SWEEP_SPANS:
        assert timing[f"{name}_s"] >= 0, name
    assert sum(timing[f"{n}_s"] for n in SWEEP_SPANS) <= wall
    assert timing["sweep.execute_s"] >= timing["execute_s"]
    assert timing["compile_cached"] == "memory"
    assert all(p.timing is timing for row in grid for p in row)
    json.dumps(timing)


def test_spans_nest_into_the_open_record():
    with span_record() as outer:
        with span("a"):
            with span_record() as inner:
                assert inner is outer
                with span("b"):
                    pass
        with span("a"):
            pass
    assert set(outer) == {"a_s", "b_s"}
    assert outer["a_s"] >= outer["b_s"] >= 0
    with span("unrecorded"):
        pass
    assert telemetry.recorded_spans() == {}


def test_counters_add_or_keep_the_largest_in_the_open_record():
    with span_record() as rec:
        telemetry.count("copies", 3)
        telemetry.count("copies", 3)
        telemetry.count("ratio", 1.5, largest=True)
        telemetry.count("ratio", 1.25, largest=True)
    assert rec == {"copies": 6, "ratio": 1.5}
    telemetry.count("copies", 1)              # no open record: dropped
    assert telemetry.recorded_spans() == {}


def _study_spec(seed):
    from repro.studies import ExperimentSpec
    return ExperimentSpec(
        fabric={"kind": "cin", "params": {"instance": "xor", "n": 16}},
        traffic={"pattern": "uniform"}, routing={"policy": "minimal"},
        sweep={"loads": [0.3, 0.6], "seeds": [seed], "cycles": CYCLES,
               "warmup": 4},
        terminals=T, name="cin16")


def test_a_study_records_its_spans_in_the_stored_provenance(cache):
    from repro.studies import Study
    t0 = time.perf_counter()
    res = Study(_study_spec(1), backend="jax").run()
    wall = time.perf_counter() - t0
    timing = res.results[0].stats.timing
    for name in SWEEP_SPANS + STUDY_SPANS:
        assert timing[f"{name}_s"] >= 0, name
    assert sum(timing[f"{n}_s"] for n in SWEEP_SPANS + STUDY_SPANS) <= wall
    for r in res.results:
        assert r.provenance["timings"] == timing
    t = res.telemetry()["cin16"]
    for name in SWEEP_SPANS + STUDY_SPANS:
        assert t[f"{name}_s"] == timing[f"{name}_s"]


def test_show_trace_prints_the_host_spans(cache, tmp_path):
    from repro.studies import Study
    from repro.studies.__main__ import _show_trace
    from repro.studies.store import JsonlStore
    store = tmp_path / "store.jsonl"
    Study(_study_spec(1), backend="jax", store=JsonlStore(str(store))).run()
    out = io.StringIO()
    with redirect_stdout(out):
        _show_trace("unused.json", [_study_spec(1)], str(store))
    text = out.getvalue()
    assert "sweep.traffic=" in text and "study.records=" in text
    assert "compile tax per experiment" in text


def test_a_profiled_sweep_shows_each_span_on_the_host_plane(cache, tmp_path):
    import jax
    from jax.profiler import ProfileData
    _sweep()                                    # compiles outside
    jax.profiler.start_trace(str(tmp_path))
    with span("outer.harness"):
        from repro.studies import Study
        Study(_study_spec(2), backend="jax").run()
    jax.profiler.stop_trace()
    path, = glob.glob(os.path.join(str(tmp_path), "**", "*.xplane.pb"),
                      recursive=True)
    names = {ev.name for plane in ProfileData.from_file(path).planes
             if plane.name.startswith("/host:")
             for line in plane.lines for ev in line.events}
    for name in SWEEP_SPANS + STUDY_SPANS + ("outer.harness",):
        assert name in names, name
