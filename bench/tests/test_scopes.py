"""The scope reduction (``scopereduce.py``): leaf ops, the host-device
clock offset, device time per named scope and the program's host spans,
on synthetic intervals and on two traces recorded on a TPU v5e:
``data/small.xplane.pb`` (``record_trace.py``) and
``data/cin16.xplane.pb.gz`` with its scope map ``data/cin16.scopes.json``
(``record_sim_trace.py``: one CIN-16 study grid, 32 cycles, adaptive
routing, inside the harness's ``window`` and ``grid`` spans)."""
import gzip
import json
import os
import shutil

import pytest

import harness
import scopereduce
from scopereduce import (STAGES, clock_offset, leaf_ops, module_map,
                         reduce_scopes)
from tracereduce import reduce_trace

DATA = os.path.join(os.path.dirname(__file__), "data")
SMALL = os.path.join(DATA, "small.xplane.pb")
CIN16_GZ = os.path.join(DATA, "cin16.xplane.pb.gz")
CIN16_SCOPES = os.path.join(DATA, "cin16.scopes.json")
SIM_METRICS = ["rng_ms.sim", "eject_ms.sim", "route_ms.sim",
               "arbitrate_ms.sim", "move_ms.sim", "unscoped_share.sim",
               "traffic_s_per_grid.sim", "prepare_s_per_grid.sim",
               "collect_s_per_grid.sim"]


def test_leaf_ops_drop_every_op_that_holds_another():
    ops = [("while", 0, 100), ("cond", 10, 50), ("a", 10, 20),
           ("b", 20, 50), ("cond2", 60, 90), ("c", 60, 90),
           ("d", 95, 99), ("e", 120, 130), ("z", 40, 40)]
    assert [n for n, _, _ in leaf_ops(ops)] == ["a", "b", "c", "d", "e"]


def test_leaf_ops_keep_a_line_of_disjoint_ops():
    ops = [("a", 0, 1), ("b", 1, 2), ("c", 5, 9)]
    assert leaf_ops(ops) == ops


def test_clock_offset_bounds_and_no_match():
    modules = {1: (100, 200), 2: (300, 400)}
    assert clock_offset({1: 150, 2: 340}, {1: 270, 2: 460},
                        modules) == (50, 60)
    assert clock_offset({7: 0}, {7: 0}, modules) is None


def test_a_traced_program_finds_its_map_by_name_and_instructions():
    maps = {"jit_f(ab)": {"%a": "x"}, "jit_g(cd)": {"%b": "y", "%c": "y"},
            "jit_g(ef)": {"%b": "z", "%d": "z"}}
    assert module_map(maps, "jit_f(ab)", set()) == {"%a": "x"}
    assert module_map(maps, "jit_g(99)", {"%b", "%c"}) == maps["jit_g(cd)"]
    assert module_map(maps, "jit_g(99)", {"%d"}) == maps["jit_g(ef)"]
    assert module_map(maps, "jit_h(1)", {"%a"}) == {}


def test_program_spans_label_gaps_beside_the_harness_spans():
    names = ["window", "grid", "setup", "sweep.stats", "study.records",
             "PjitFunction(_run_loop)", "check"]
    assert [scopereduce.is_span(n) for n in names] == [
        True, True, True, True, True, False, True]


def test_clock_offset_of_the_recorded_launches():
    s = reduce_scopes(SMALL, {})
    lo, hi = s.clock_offset_ms
    assert lo == pytest.approx(1.389, abs=0.01)
    assert hi == pytest.approx(1.508, abs=0.01)


def test_recorded_small_trace_leaf_time_is_its_program_time():
    s = reduce_scopes(SMALL, {})
    (module, leaf), = s.leaf_s.items()
    assert leaf == pytest.approx(reduce_trace(SMALL).module_s[module],
                                 rel=1e-3)
    # No program names a scope, so all of it is unscoped.
    assert s.scope_s[module] == {None: pytest.approx(leaf)}


@pytest.fixture(scope="module")
def cin16_path(tmp_path_factory):
    path = tmp_path_factory.mktemp("cin16") / "run.xplane.pb"
    with gzip.open(CIN16_GZ) as src, open(path, "wb") as dst:
        shutil.copyfileobj(src, dst)
    return str(path)


@pytest.fixture(scope="module")
def cin16(cin16_path):
    with open(CIN16_SCOPES) as f:
        maps = json.load(f)
    return (reduce_scopes(cin16_path, maps, STAGES),
            reduce_trace(cin16_path), maps)


def test_recorded_sim_stages_and_unscoped_are_the_leaf_time(cin16):
    s, t, _ = cin16
    program = max(t.module_s, key=t.module_s.get)
    per = s.scope_s[program]
    assert sum(per.values()) == pytest.approx(s.leaf_s[program], rel=1e-9)
    assert set(STAGES[:5]) <= set(per)
    assert all(per[st] > 0 for st in STAGES[:5])
    assert per.get(None, 0.0) < 0.1 * s.leaf_s[program]


def test_recorded_sim_leaves_hold_no_loop_or_branch(cin16):
    s, t, _ = cin16
    instrs = [k.split(":", 1)[1] for k in s.ops_s]
    assert instrs
    assert not any(i.startswith(("%while", "%conditional")) for i in instrs)
    # Containers and their bodies together exceed the busy time; the
    # leaves alone do not.
    assert sum(t.op_s.values()) > t.busy_s
    assert sum(s.ops_s.values()) <= t.busy_s * (1 + 1e-9)
    assert sum(s.leaf_s.values()) == pytest.approx(sum(s.ops_s.values()))


def test_recorded_sim_gaps_carry_program_span_labels(cin16):
    s, t, _ = cin16
    lo, hi = s.clock_offset_ms
    assert 0 < lo <= hi
    idle = sum(d for _, d in s.gaps)
    assert idle == pytest.approx(t.window_s - t.busy_s, rel=0.05)
    long_gaps = [(label, d) for label, d in s.gaps if d > 2e-4]
    assert long_gaps
    assert all(label.startswith(("sweep.", "study."))
               for label, _ in long_gaps), long_gaps
    assert {"sweep.traffic", "sweep.execute", "sweep.stats",
            "study.resolve", "study.records"} <= set(s.span_s)


def test_recorded_sim_breakdown_names_stage_and_instruction(cin16):
    s, _, _ = cin16
    ops = s.breakdown(20)["device_ops"]
    assert len(ops) == 20
    assert all(name.split(":", 1)[0] in STAGES + ("unscoped",)
               for name, _ in ops)
    assert [v for _, v in ops] == sorted((v for _, v in ops), reverse=True)


def _fake_driver_run(tmp_path, ctx, cell):
    """What a driver's ``run`` holds when it reads its metrics: the
    profiler that wrote the trace, in a local variable."""
    prof = harness.Profiler(True, str(tmp_path))
    assert prof.path() is not None
    return harness.read_metrics(cell, ctx)


def _sim_ctx(trace, grids=1, cycles=32):
    return {"summary": reduce_trace(trace),
            "grids": [{"wall_s": 1.0, "execute_s": 0.5, "compile_s": 0.0}]
            * grids,
            "warm": {"compile_s": 1.0}, "cycles": cycles,
            "peaks": {"hbm_bytes_per_s": 819e9}, "step_bytes": 1e6}


def test_readers_read_the_recorded_sim_trace(tmp_path, monkeypatch, cin16,
                                             cin16_path):
    from repro.obs import telemetry
    s, t, maps = cin16
    shutil.copy(cin16_path, tmp_path / "run.xplane.pb")
    monkeypatch.setattr(telemetry, "scope_maps", lambda: maps)
    cell = harness.load_cell("hx12x8.uniform.adaptive")
    ctx = _sim_ctx(str(tmp_path / "run.xplane.pb"))
    got = _fake_driver_run(tmp_path, ctx, cell)
    for name in SIM_METRICS:
        assert got[name]["value"] >= 0, name
    # The stage metrics share step_ms.sim's program and denominator.
    program = max(t.module_s, key=t.module_s.get)
    stages = sum(got[f"{st}_ms.sim"]["value"] for st in STAGES[:5])
    unscoped = got["unscoped_share.sim"]["value"] / 100
    assert stages == pytest.approx(
        s.leaf_s[program] * (1 - unscoped) / 32 * 1e3, rel=1e-9)
    assert s.leaf_s[program] <= t.module_s[program] * (1 + 1e-9)
    assert got["traffic_s_per_grid.sim"]["value"] == pytest.approx(
        s.span_s["sweep.traffic"])
    # The accepted metrics read what they read without the new ones.
    cell.per_layer = [m for m in cell.per_layer
                      if m["name"] not in SIM_METRICS]
    before = harness.read_metrics(cell, _sim_ctx(str(tmp_path /
                                                     "run.xplane.pb")))
    assert {k: v for k, v in got.items() if k in before} == before


def test_readers_find_nothing_in_a_trace_without_scopes(tmp_path,
                                                       monkeypatch):
    from repro.obs import telemetry
    monkeypatch.setattr(telemetry, "scope_maps", dict)
    shutil.copy(SMALL, tmp_path / "run.xplane.pb")
    cell = harness.load_cell("hx12x8.a2a.replay")
    got = _fake_driver_run(tmp_path, _sim_ctx(str(tmp_path /
                                                  "run.xplane.pb")), cell)
    assert not set(SIM_METRICS) & set(got)
    assert "step_ms.sim" in got


def test_readers_find_nothing_without_a_traced_run():
    cell = harness.load_cell("df2064.uniform.minimal")
    got = harness.read_metrics(cell, {"summary": None, "grids": [],
                                      "cycles": 0})
    assert got == {}
    coll = harness.load_cell("lacin.allreduce.4chip")
    assert harness.read_metrics(coll, {"summary": None, "calls": 0}) == {}


def test_the_collective_program_names_both_phases():
    import jax
    import numpy as np
    from jax.sharding import Mesh
    from repro.fabric import LacinCollectives
    from repro.obs.telemetry import scope_map
    import cells
    cell = cells.tiny("lacin.allreduce.4chip")
    driver = harness.load_module("drivers", "collective")
    mesh = Mesh(np.array(jax.devices()[:4]), ("x",))
    coll = LacinCollectives(mesh=mesh, instance=cell.config["instance"])
    f = driver.all_reduce_fn(mesh, coll)
    bufs = driver.buckets(mesh, 4, 1024, 1, 7)
    key, m = scope_map(f.lower(bufs[0]).compile())
    named = {scopereduce.scope_of(p, scopereduce.PHASES) for p in m.values()}
    assert {"reduce_scatter", "all_gather"} <= named
    assert key.split("(", 1)[0].startswith("jit")
