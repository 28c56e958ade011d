"""The DeepSeek-V3 expert-parallel cell (``hx12x8.dsv3.ep64``): its
reference draw is the program's, its files agree with each other, and a
test-size run is correct while a broken one is not.

Test size: a 2 x 8 HyperX (two EP groups of 8 nodes) with 2 terminals,
4 tokens a rank and 2 ranks a node; every width of the gate (256
experts, 8 groups, top-4 groups, top-8 experts) and both message sizes
stay as published.
"""
import copy
import tempfile

import numpy as np
import pytest

import calibrate
import harness
from cells import run

CELL = "hx12x8.dsv3.ep64"
SEEDS = [1, 42, 9_999, 2**31 + 11, 3 * 2**32 + 7]


def tiny() -> harness.Cell:
    cell = copy.deepcopy(harness.load_cell(CELL))
    cell.config["fabric"]["params"].update(dims=[2, 8], terminals=2)
    cell.config["terminals"] = 2
    cell.traffic["traffic"]["params"]["moe"].update(batch_tokens=4,
                                                    ranks_per_node=2)
    return cell


def _program_traffic(cell, seed):
    from repro.core.hyperx import HyperXConfig
    from repro.sim.workloads import moe_dispatch_workload
    prm = cell.config["fabric"]["params"]
    cfg = HyperXConfig(dims=tuple(prm["dims"]), terminals=prm["terminals"],
                       instance=prm["instance"])
    return moe_dispatch_workload(cfg, cell.traffic["traffic"]["params"]["moe"],
                                 seed)


@pytest.mark.parametrize("seed", SEEDS)
def test_draw_is_the_programs(seed):
    """The cell's own sizes: the same phases, the same packets."""
    cell = harness.load_cell(CELL)
    mix = harness.load_module("reference/mixes", cell.traffic["reference"])
    got = mix.packets(cell.config["fabric"], cell.traffic,
                      cell.config["terminals"], 0.0, seed)
    w = _program_traffic(cell, seed)
    want = w.traffic()
    assert np.array_equal(got.phase_sizes,
                          np.diff(w.phase_cum(), prepend=0))
    a = np.lexsort((want.dst, want.src, want.gen))
    b = np.lexsort((got.dst, got.src, got.gen))
    for field in ("src", "dst", "gen"):
        assert np.array_equal(getattr(want, field)[a],
                              getattr(got, field)[b]), field


def test_published_widths_are_the_traffic_moe_params():
    cell = harness.load_cell(CELL)
    cfg = cell.config
    moe = cell.traffic["traffic"]["params"]["moe"]
    pub = cfg["published"]
    for key in ("n_routed_experts", "n_group", "topk_group",
                "num_experts_per_tok", "dispatch_bytes", "combine_bytes"):
        assert moe[key] == pub[key] == cfg.get(key, pub[key]), key
    for key in ("topk_method", "scoring_func", "hidden_size"):
        assert pub[key] == cfg[key], key
    hidden = pub["hidden_size"]
    assert pub["dispatch_bytes"] == hidden + hidden // 128 * 4   # FP8 + scales
    assert pub["combine_bytes"] == 2 * hidden                    # BF16
    for key in cfg["reduced"]:
        assert moe[key] == cfg[key] != pub[key], key
    assert cfg["ranks_per_node"] == cfg["terminals"]
    assert cfg["fabric"]["params"]["dims"][-1] == moe["n_group"]


def correct(cell) -> bool:
    try:
        return run(cell)["correct"]
    except RuntimeError:
        return False


def test_sound_run_is_correct():
    out = run(tiny())
    assert out["correct"], out["check"]
    assert all(c["value"] == 0 for c in out["check"].values())


def test_step_returning_its_state(monkeypatch):
    from repro.sim import xengine
    monkeypatch.setattr(xengine, "_step", lambda spec, tables, pkt, key,
                        warmup, st: st._replace(cycle=st.cycle + 1))
    assert not correct(tiny())


def test_answer_altered_where_produced(monkeypatch):
    from repro.sim import xengine
    build = xengine.build_stats

    def late(**kw):
        kw["deliver"] = np.where(kw["deliver"] >= 0, kw["deliver"] + 1, -1)
        return build(**kw)

    monkeypatch.setattr(xengine, "build_stats", late)
    assert not correct(tiny())


def test_gate_without_its_group_limit(monkeypatch):
    """Top-8 over all 256 experts, no node limit: other copies, other
    phases."""
    from repro.sim import workloads

    def ungrouped(logits, moe):
        k = moe.num_experts_per_tok
        return np.argpartition(logits, -k, axis=-1)[:, -k:]

    monkeypatch.setattr(workloads, "noaux_tc", ungrouped)
    assert not correct(tiny())


def test_control_fails_at_test_size():
    ok, numbers = harness.checked(calibrate.simulator_control(tiny(), 4242),
                                  tiny().limits["limits"])
    assert not ok, numbers


def test_gate_reader_reads_the_span():
    """The program opens ``sweep.gate`` inside ``sweep.traffic`` on the
    profiler's host plane, and ``gate_s_per_grid.sim`` reads it, per
    window grid, from the summary's program spans; a program without the
    span reads nothing."""
    from jax.profiler import ProfileData

    import scopereduce
    from drivers.study import experiment
    from repro.studies import Study
    prof = harness.Profiler(True, tempfile.mkdtemp())
    with prof:
        with harness.span("window"):
            res = Study(experiment(tiny(), [5]), backend="jax").run()
    timing = res.results[0].stats.timing
    assert timing["moe_copies"] > 0 and timing["sweep.gate_s"] > 0
    assert timing["moe_pair_max_over_mean"] >= 1.0
    spans = [(ev.name, ev.start_ns, ev.end_ns)
             for plane in ProfileData.from_file(prof.path()).planes
             if plane.name.startswith("/host:")
             for line in plane.lines for ev in line.events
             if scopereduce.is_span(ev.name)]
    gates = [(s, e) for n, s, e in spans if n == "sweep.gate"]
    traffic = [(s, e) for n, s, e in spans if n == "sweep.traffic"]
    assert gates and all(any(ts <= s and e <= te for ts, te in traffic)
                         for s, e in gates)
    reader = harness.load_module("metrics", "gate_s_per_grid.sim")
    summary = scopereduce.ScopeSummary(
        offset_ns=None, devices=1,
        span_s={"sweep.gate": 3.0, "sweep.traffic": 3.5})
    assert reader.read({"_scope_summary": summary,
                        "grids": [{}, {}]}) == pytest.approx(1.5)
    del summary.span_s["sweep.gate"]
    assert reader.read({"_scope_summary": summary,
                        "grids": [{}, {}]}) is None
