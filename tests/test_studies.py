"""repro.studies: spec serialization, the Study runner, store resume,
backend selection, the legacy shims, and the CLI.

Spec round-trips must be *exact* (``from_json(to_json(s)) == s``) for
every registered CIN instance and for HyperX/Dragonfly parameter sets —
a spec file is the durable name of an experiment, so any drift silently
reruns (or worse, mislabels) grid points.
"""
import json
import os
import warnings

import numpy as np
import pytest

import repro.fabric.mirror  # noqa: F401  (registers the mirror instance)
from repro import sim, studies
from repro.fabric import LacinDeprecationWarning, instance_names, make_fabric
from repro.fabric.registry import get_instance
from repro.studies import (ExperimentSpec, FabricSpec, JsonlStore, Result,
                           RoutingSpec, Study, SweepSpec, TrafficSpec)

CYCLES = 160
WARMUP = 40


def _cin_spec(n=8, instance="xor", *, loads=(0.2, 0.6), seeds=(0,),
              policy="minimal", pattern="uniform", terminals=2,
              cycles=CYCLES, warmup=WARMUP, **traffic_params):
    return ExperimentSpec(
        fabric=FabricSpec("cin", {"instance": instance, "n": n}),
        traffic=TrafficSpec(pattern, traffic_params),
        routing=RoutingSpec(policy),
        sweep=SweepSpec(loads=loads, seeds=seeds, cycles=cycles,
                        warmup=warmup),
        terminals=terminals)


# ---------------------------------------------------------------------------
# Serialization: exact JSON round-trip.
# ---------------------------------------------------------------------------

def _supported_n(name: str) -> int:
    spec = get_instance(name)
    for n in (8, 9, 12, 16):
        if spec.supports(n):
            return n
    raise AssertionError(f"no test size for instance {name}")


@pytest.mark.parametrize("instance", instance_names())
def test_round_trip_exact_every_registry_instance(instance):
    n = _supported_n(instance)
    spec = _cin_spec(n=n, instance=instance, loads=(0.1, 0.35, 0.9),
                     seeds=(0, 3), policy="adaptive")
    rt = ExperimentSpec.from_json(spec.to_json())
    assert rt == spec
    assert rt.to_json() == spec.to_json()
    assert rt.fabric.resolve().num_switches == n


@pytest.mark.parametrize("fabric", [
    FabricSpec("hyperx", {"dims": (4, 4), "terminals": 4,
                          "instance": "xor"}),
    FabricSpec("hyperx", {"dims": [8, 4, 4], "terminals": 2,
                          "instance": "circle"}),
    FabricSpec("dragonfly", {"group_size": 4, "terminals_per_switch": 2,
                             "global_ports_per_switch": 2, "num_groups": 8}),
    FabricSpec("dragonfly", {"group_size": 6, "terminals_per_switch": 3,
                             "global_ports_per_switch": 2, "num_groups": 12,
                             "local_instance": "circle",
                             "global_instance": "mirror"}),
])
def test_round_trip_exact_hyperx_dragonfly(fabric):
    spec = ExperimentSpec(
        fabric=fabric, traffic=TrafficSpec("uniform", {"seed": 5}),
        routing=RoutingSpec("valiant"),
        sweep=SweepSpec(loads=(0.25,), seeds=(1, 2), cycles=80, warmup=20),
        terminals=2)
    rt = ExperimentSpec.from_json(spec.to_json())
    assert rt == spec
    # list/tuple params normalize to one canonical form
    assert rt.fabric.params == spec.fabric.params
    assert rt.fabric.resolve().num_switches == spec.fabric.resolve(
        ).num_switches


def test_round_trip_traffic_params_and_engine_kwargs():
    spec = _cin_spec(pattern="hotspot", hot_fraction=0.75, seed=7,
                     policy="adaptive")
    spec = ExperimentSpec(
        fabric=spec.fabric, traffic=spec.traffic,
        routing=RoutingSpec("adaptive", {"threshold": 2.0, "weight": 1.5}),
        sweep=spec.sweep, terminals=spec.terminals,
        engine={"queue_capacity": 8, "num_vcs": 2})
    rt = ExperimentSpec.from_json(spec.to_json())
    assert rt == spec
    assert rt.engine == {"queue_capacity": 8, "num_vcs": 2}
    pol = rt.routing.make()
    assert (pol.threshold, pol.weight) == (2.0, 1.5)


def test_spec_file_round_trip(tmp_path):
    specs = [_cin_spec(policy="minimal"), _cin_spec(policy="valiant")]
    path = tmp_path / "study.json"
    studies.dump_specs(specs, str(path), study="t", description="d")
    loaded = studies.load_specs(str(path))
    assert loaded == specs


def test_bundled_specs_load_and_round_trip():
    bundles = studies.bundled_specs()
    assert {"cin16_saturation", "hyperx256_uniform", "dragonfly72_uniform",
            "dragonfly_adversarial", "studies_smoke"} <= set(bundles)
    for name, path in bundles.items():
        for exp in studies.load_specs(path):
            assert ExperimentSpec.from_json(exp.to_json()) == exp, name


def test_resolved_declarative_specs_still_serialize(tmp_path):
    """Resolving (or running) a declarative spec must not flip it inline:
    run-then-save and share-then-save both work."""
    spec = _cin_spec()
    spec.fabric.resolve()
    spec.fabric.resolve_topology()
    assert not spec.is_inline
    assert ExperimentSpec.from_json(spec.to_json()) == spec

    from_fab = FabricSpec.from_fabric(make_fabric("xor", 8))
    assert not from_fab.is_inline
    assert FabricSpec.from_json(from_fab.to_json()) == from_fab

    specs = [_cin_spec(policy="minimal"), _cin_spec(policy="valiant")]
    Study(specs, backend="numpy").run()       # shares + resolves fabrics
    studies.dump_specs(specs, str(tmp_path / "after_run.json"))
    assert studies.load_specs(str(tmp_path / "after_run.json")) == specs


def test_inline_specs_refuse_to_serialize():
    spec = ExperimentSpec(
        fabric=FabricSpec("cin", {"instance": "xor", "n": 8}),
        traffic=TrafficSpec.custom(lambda load: sim.uniform(
            8, offered=load, cycles=50, terminals=1)),
        routing=RoutingSpec("minimal"),
        sweep=SweepSpec(loads=(0.2,), cycles=50))
    assert spec.is_inline
    with pytest.raises(ValueError, match="inline"):
        spec.to_dict()


# ---------------------------------------------------------------------------
# The Study runner.
# ---------------------------------------------------------------------------

def test_study_runs_grid_both_backends_agree():
    spec = _cin_spec(loads=(0.2, 0.6), seeds=(0, 1))
    out_np = Study(spec, backend="numpy").run()
    out_jx = Study(spec, backend="jax").run()
    assert out_np.executed == out_jx.executed == 4
    for a, b in zip(out_np.results, out_jx.results):
        assert a.key == b.key
        assert a.accepted == pytest.approx(b.accepted, rel=0.15, abs=0.02)
    # grid order: loads major, seeds minor
    assert [(r.load, r.seed) for r in out_np.results] == [
        (0.2, 0), (0.2, 1), (0.6, 0), (0.6, 1)]


def test_study_auto_backend_prefers_jax():
    out = Study(_cin_spec(loads=(0.3,))).run()
    assert out.backend == "jax"       # jax is a hard dependency in-repo
    assert out.results[0].backend == "jax"


def test_study_shares_fabric_resolution_across_experiments(monkeypatch):
    specs = [_cin_spec(policy="minimal"), _cin_spec(policy="valiant")]
    built = []
    orig = studies.FabricSpec.resolve_topology

    def counting(self):
        built.append(self)
        return orig(self)

    monkeypatch.setattr(studies.FabricSpec, "resolve_topology", counting)
    Study(specs, backend="numpy").run()
    assert len(built) == 1    # the second experiment reused the study cache


def test_study_rejects_duplicate_experiment_names():
    with pytest.raises(ValueError, match="unique"):
        Study([_cin_spec(), _cin_spec()])


def test_declarative_traffic_uses_grid_seed_unless_fixed():
    spec = _cin_spec(loads=(0.4,), seeds=(3, 4))
    topo = spec.fabric.resolve_topology()
    tf = spec.traffic.factory(topo, cycles=CYCLES, terminals=2)
    a, b = tf(0.4, 3), tf(0.4, 4)
    assert not np.array_equal(a.gen, b.gen) or not np.array_equal(a.dst,
                                                                  b.dst)
    fixed = _cin_spec(loads=(0.4,), seeds=(3, 4), seed=17)
    tf = fixed.traffic.factory(topo, cycles=CYCLES, terminals=2)
    a, b = tf(0.4, 3), tf(0.4, 4)
    assert np.array_equal(a.src, b.src) and np.array_equal(a.dst, b.dst)


# ---------------------------------------------------------------------------
# Store + resume.
# ---------------------------------------------------------------------------

def test_empty_sweep_grid_rejected():
    with pytest.raises(ValueError, match="at least one load"):
        SweepSpec(loads=(), cycles=50)
    with pytest.raises(ValueError, match="at least one load"):
        SweepSpec(loads=(0.5,), seeds=(), cycles=50)


def test_store_persists_and_resume_skips_everything(tmp_path):
    store = str(tmp_path / "r.jsonl")
    spec = _cin_spec(loads=(0.2, 0.6), seeds=(0, 1))
    first = Study(spec, store=store, backend="numpy").run()
    assert first.executed == 4 and first.restored == 0
    again = Study(spec, store=store, backend="numpy").run()
    assert again.executed == 0 and again.restored == 4
    # resume=False starts the store clean: no duplicate keys, no growth
    fresh = Study(spec, store=store, backend="numpy").run(resume=False)
    assert fresh.executed == 4 and fresh.restored == 0
    with open(store) as f:
        assert len(f.read().splitlines()) == 4
    # restored results carry the stored summary, not in-memory stats
    assert all(r.stats is None for r in again.results)
    for a, b in zip(first.results, again.results):
        assert a.key == b.key and a.accepted == b.accepted


def test_resume_half_written_store_runs_only_missing(tmp_path):
    """The satellite acceptance: a re-run over a half-written JSONL store
    executes only the missing grid points (for both backends)."""
    store = str(tmp_path / "r.jsonl")
    spec = _cin_spec(loads=(0.2, 0.4, 0.6), seeds=(0, 1))
    full = Study(spec, store=store, backend="numpy").run()
    assert full.executed == 6

    # keep the first 2 complete lines + one torn line (a killed writer)
    with open(store) as f:
        lines = f.read().splitlines()
    with open(store, "w") as f:
        f.write("\n".join(lines[:2]) + "\n" + lines[2][: len(lines[2]) // 2])

    for backend in ("numpy", "jax"):
        out = Study(spec, store=store, backend=backend).run()
        assert out.restored == 2
        assert out.executed == 4
        assert [r.key for r in out.results] == [r.key for r in full.results]
        accepted = {r.key: r.accepted for r in out.results}
        # numpy resume is bit-identical to the full run (same engine seeds)
        if backend == "numpy":
            assert accepted == {r.key: r.accepted for r in full.results}
        # next resume over the repaired store skips everything
        assert Study(spec, store=store, backend=backend).run().executed == 0
        os.unlink(store)
        JsonlStore(store).append(full.results[:2])


def test_resume_rejects_stale_results_from_an_edited_spec(tmp_path):
    """The store key names the grid point, not the spec's parameters —
    so resuming after editing cycles/warmup/params must refuse to restore
    the stale records instead of silently mislabeling them."""
    store = str(tmp_path / "r.jsonl")
    spec = _cin_spec(loads=(0.2, 0.6), cycles=CYCLES)
    Study(spec, store=store, backend="numpy").run()
    edited = spec.with_sweep(cycles=2 * CYCLES)
    with pytest.raises(ValueError, match="different version"):
        Study(edited, store=store, backend="numpy").run()
    # --no-resume is the documented way out
    out = Study(edited, store=store, backend="numpy").run(resume=False)
    assert out.executed == 2
    assert all(r.cycles == 2 * CYCLES for r in out.results)
    # and the unedited spec still resumes cleanly from its own records
    Study(edited, store=store, backend="numpy").run()


def test_growing_the_grid_resumes_cleanly(tmp_path):
    """loads/seeds are key-encoded, not digest-encoded: extending the
    sweep grid resumes the stored points and runs only the new ones."""
    store = str(tmp_path / "r.jsonl")
    base = _cin_spec(loads=(0.2,), seeds=(0,))
    Study(base, store=store, backend="numpy").run()
    grown = base.with_sweep(loads=(0.2, 0.6), seeds=(0, 1))
    out = Study(grown, store=store, backend="numpy").run()
    assert out.restored == 1 and out.executed == 3


def test_store_corrupt_middle_line_raises(tmp_path):
    store = str(tmp_path / "r.jsonl")
    spec = _cin_spec(loads=(0.2, 0.6))
    Study(spec, store=store, backend="numpy").run()
    with open(store) as f:
        lines = f.read().splitlines()
    with open(store, "w") as f:
        f.write(lines[0] + "\n{broken\n" + lines[1] + "\n")
    with pytest.raises(ValueError, match="corrupt"):
        JsonlStore(store).load()
    # a newline-terminated corrupt FINAL record is an error too (only a
    # true torn tail — no trailing newline — is tolerated)
    with open(store, "w") as f:
        f.write(lines[0] + "\n{broken\n")
    with pytest.raises(ValueError, match="corrupt"):
        JsonlStore(store).load()
    with open(store, "w") as f:
        f.write(lines[0] + "\n{broken")
    assert len(JsonlStore(store).load()) == 1


def test_append_preserves_parseable_unterminated_tail(tmp_path):
    """A record whose JSON was flushed but whose newline was not (killed
    at exactly the wrong moment) is restored by load() — so append() must
    terminate it, never truncate it away."""
    store = str(tmp_path / "r.jsonl")
    spec = _cin_spec(loads=(0.2, 0.4, 0.6))
    full = Study(spec, store=store, backend="numpy").run()
    with open(store) as f:
        text = f.read()
    with open(store, "w") as f:
        f.write(text.rstrip("\n"))           # strip the final newline only
    out = Study(spec, store=store, backend="numpy").run()
    assert out.restored == 3 and out.executed == 0
    # the append-free resume left all three records intact; a later append
    # keeps them too
    JsonlStore(store).append(
        Result.from_record({**full.results[0].record(), "key": "extra"}))
    kept = JsonlStore(store).load()
    assert set(kept) == {r.key for r in full.results} | {"extra"}


def test_result_record_round_trip():
    out = Study(_cin_spec(loads=(0.3,)), backend="numpy").run()
    r = out.results[0]
    rt = Result.from_record(json.loads(r.to_line()))
    assert rt.key == r.key and rt.accepted == r.accepted
    assert rt.stats is None


# ---------------------------------------------------------------------------
# Legacy shims: equal results, deprecation-warned.
# ---------------------------------------------------------------------------

def test_saturation_sweep_shim_equals_direct_study():
    """Acceptance: the shim routes through Study and returns results equal
    to a directly-constructed Study run — on both backends."""
    topo = sim.cin_topology("xor", 8)

    def tf(load):
        return sim.uniform(8, offered=load, cycles=CYCLES, terminals=4,
                           seed=9)

    direct = ExperimentSpec(
        fabric=FabricSpec.from_topology(topo),
        traffic=TrafficSpec.custom(tf),
        routing=RoutingSpec("minimal"),
        sweep=SweepSpec(loads=(0.2, 0.6), seeds=(0,), cycles=CYCLES,
                        warmup=WARMUP))
    for backend in ("numpy", "jax"):
        want = Study(direct, backend=backend).run()
        with pytest.warns(LacinDeprecationWarning):
            got = sim.saturation_sweep(topo, sim.MinimalPolicy, tf,
                                       [0.2, 0.6], cycles=CYCLES,
                                       warmup=WARMUP, backend=backend)
        for w, g in zip(want.results, got):
            assert g.accepted == w.stats.accepted
            assert g.latency_p99 == w.stats.latency_p99
            assert np.array_equal(g.link_loads, w.stats.link_loads)


def test_fabric_sim_sweep_shim_equals_direct_study():
    fab = make_fabric("xor", 8)

    def tf(load, seed):
        return sim.uniform(8, offered=load, cycles=CYCLES, terminals=4,
                           seed=seed)

    direct = ExperimentSpec(
        fabric=FabricSpec.from_fabric(fab),
        traffic=TrafficSpec.custom(tf),
        routing=RoutingSpec("minimal"),
        sweep=SweepSpec(loads=(0.3, 0.7), seeds=(1, 2), cycles=CYCLES,
                        warmup=WARMUP))
    want = Study(direct, backend="jax").run().grid()
    with pytest.warns(LacinDeprecationWarning):
        got = fab.sim_sweep("minimal", tf, [0.3, 0.7], seeds=(1, 2),
                            cycles=CYCLES, warmup=WARMUP, backend="jax")
    assert len(got) == 2 and len(got[0]) == 2
    for wrow, grow in zip(want, got):
        for w, g in zip(wrow, grow):
            assert g.accepted == w.stats.accepted
            assert np.array_equal(g.link_loads, w.stats.link_loads)


def test_compare_policies_shim_one_study():
    topo = sim.cin_topology("xor", 8)

    def tf(load):
        return sim.uniform(8, offered=load, cycles=CYCLES, terminals=4,
                           seed=2)

    with pytest.warns(LacinDeprecationWarning):
        got = sim.compare_policies(topo, ["minimal", "valiant"], tf,
                                   [0.2, 0.6], cycles=CYCLES, warmup=WARMUP,
                                   backend="jax")
    assert set(got) == {"minimal", "valiant"}
    assert all(len(v) == 2 for v in got.values())
    assert got["minimal"][0].policy == "minimal"
    assert got["valiant"][1].policy == "valiant"


# ---------------------------------------------------------------------------
# The CLI.
# ---------------------------------------------------------------------------

def test_cli_run_show_specs(tmp_path, capsys, monkeypatch):
    from repro.studies.__main__ import main
    monkeypatch.chdir(tmp_path)
    assert main(["specs"]) == 0
    assert "studies_smoke" in capsys.readouterr().out

    assert main(["show", "studies_smoke"]) == 0
    out = capsys.readouterr().out
    assert "4 grid points" in out

    store = str(tmp_path / "smoke.jsonl")
    assert main(["run", "studies_smoke", "--backend", "numpy",
                 "--store", store, "--table"]) == 0
    out = capsys.readouterr().out
    assert "ran 4 grid points" in out
    assert "saturation points:" in out
    # the store parses back into Result records
    stored = JsonlStore(store).load()
    assert len(stored) == 4
    assert all(isinstance(r, Result) for r in stored.values())
    # second run resumes
    assert main(["run", "studies_smoke", "--backend", "numpy",
                 "--store", store]) == 0
    assert "ran 0 grid points (4 restored" in capsys.readouterr().out


def test_cli_rejects_unknown_spec():
    from repro.studies.__main__ import main
    with pytest.raises(SystemExit):
        main(["run", "no_such_spec"])


# ---------------------------------------------------------------------------
# Satellite regressions living at the studies surface.
# ---------------------------------------------------------------------------

def test_terminals_footgun_mismatch_raises():
    topo = sim.cin_topology("xor", 8)
    tr = sim.uniform(8, offered=0.3, cycles=80, terminals=4, seed=0)
    with pytest.raises(ValueError, match="terminals=2 disagrees"):
        sim.simulate(topo, sim.MinimalPolicy(), tr, terminals=2)
    with pytest.raises(ValueError, match="disagrees"):
        sim.simulate(topo, sim.MinimalPolicy(), tr, terminals=2,
                     backend="jax")


def test_terminals_derived_from_traffic():
    topo = sim.cin_topology("xor", 8)
    tr = sim.uniform(8, offered=0.3, cycles=80, terminals=4, seed=0)
    s = sim.simulate(topo, sim.MinimalPolicy(), tr, cycles=80)
    assert s.terminals == 4
    s = sim.simulate(topo, sim.MinimalPolicy(), tr, cycles=80,
                     backend="jax")
    assert s.terminals == 4
    # one-shot traffic records nothing; explicit values pass through
    one = sim.one_shot_all_to_all(8)
    assert sim.simulate(topo, sim.MinimalPolicy(), one).terminals == 1
    assert sim.simulate(topo, sim.MinimalPolicy(), one,
                        terminals=3).terminals == 3


# ---------------------------------------------------------------------------
# flush_interval: amortized fsync + mid-write crash repair.
# ---------------------------------------------------------------------------

def test_flush_interval_validates_and_defaults():
    with pytest.raises(ValueError, match="flush_interval"):
        JsonlStore("x.jsonl", flush_interval=0)
    assert JsonlStore("x.jsonl").flush_interval == 1


def test_flush_interval_batches_fsyncs_but_loses_nothing(tmp_path,
                                                         monkeypatch):
    """With flush_interval=k the store fsyncs ~1/k as often, but every
    record is still written+flushed per append — a clean process exit
    (or Study.run's trailing sync()) loses nothing."""
    syncs = []
    real_fsync = os.fsync
    monkeypatch.setattr(os, "fsync", lambda fd: (syncs.append(fd),
                                                 real_fsync(fd)))
    store_path = str(tmp_path / "r.jsonl")
    spec = _cin_spec(loads=(0.2, 0.4, 0.6), seeds=(0, 1))
    batched = JsonlStore(store_path, flush_interval=4)
    out = Study(spec, store=batched, backend="numpy").run()
    assert out.executed == 6
    # 6 appended records at interval 4: one fsync mid-run, one from the
    # study-end sync() that settles the remaining 2
    assert len(syncs) == 2
    assert len(JsonlStore(store_path).load()) == 6
    assert batched._unsynced == 0


def test_flush_interval_mid_write_crash_repairs_and_resumes(tmp_path):
    """The satellite crash test: a writer killed mid-record between
    fsyncs leaves complete lines plus a torn tail; load() skips the
    fragment, append() repairs it in place, and a resumed study re-runs
    exactly the lost grid points."""
    store_path = str(tmp_path / "r.jsonl")
    spec = _cin_spec(loads=(0.2, 0.4, 0.6), seeds=(0, 1))
    full = Study(spec, store=JsonlStore(store_path, flush_interval=3),
                 backend="numpy").run()
    assert full.executed == 6
    with open(store_path) as f:
        lines = f.read().splitlines()

    # crash variant A: torn JSON fragment (killed mid-buffer-write)
    with open(store_path, "w") as f:
        f.write("\n".join(lines[:3]) + "\n" + lines[3][: 20])
    out = Study(spec, store=JsonlStore(store_path, flush_interval=3),
                backend="numpy").run()
    assert out.restored == 3 and out.executed == 3
    # numpy re-execution is bit-identical to the uninterrupted run
    assert {r.key: r.accepted for r in out.results} == \
        {r.key: r.accepted for r in full.results}
    repaired = JsonlStore(store_path).load()
    assert set(repaired) == {r.key for r in full.results}

    # crash variant B: complete final record, missing only its newline
    with open(store_path, "w") as f:
        f.write("\n".join(lines[:4]))         # no trailing newline
    out = Study(spec, store=JsonlStore(store_path, flush_interval=3),
                backend="numpy").run()
    assert out.restored == 4 and out.executed == 2
    assert len(JsonlStore(store_path).load()) == 6


@pytest.mark.parametrize("params", [
    {"colective": "all_to_all"},
    {"collective": "all_to_all", "messages": 2},
    {"moe": {"batch_tokens": 2}, "message_size": 2},
    {"collective": "all_to_all", "moe": {"batch_tokens": 2}},
    {"moe": {"batch_tokens": 2, "experts": 64}},
])
def test_unknown_workload_params_raise(params):
    spec = ExperimentSpec(
        fabric=FabricSpec("hyperx", {"dims": [2, 8], "terminals": 1}),
        traffic=TrafficSpec("workload", params),
        routing=RoutingSpec("minimal"), sweep=SweepSpec(loads=(0.0,)))
    with pytest.raises(ValueError, match="unknown|moe params"):
        Study(spec, backend="numpy").run()


def _moe_spec(seeds):
    [exp] = studies.load_specs(studies.bundled_spec_path("moe_dispatch"))
    return exp.with_sweep(seeds=tuple(seeds))


def test_moe_study_draws_from_each_point_seed():
    """The bundled ``moe_dispatch`` spec through the compiled sweep: each
    grid seed redraws the gate, the same seed the same packets, and
    every point meets its own contention-free bound."""
    spec = _moe_spec([0, 1])
    assert ExperimentSpec.from_json(spec.to_json()) == spec
    assert spec.traffic.label == "replay-moe"
    out = Study(spec, backend="jax").run()
    a, b = out.results
    assert a.packets_generated != b.packets_generated
    [c] = Study(_moe_spec([0]), backend="jax").run().results
    assert (a.packets_generated, a.ideal_cycles, a.phase_cycles) == \
        (c.packets_generated, c.ideal_cycles, c.phase_cycles)
    for r in out.results:
        assert r.completion_cycles == r.ideal_cycles
    timing = out.results[0].stats.timing
    assert timing["moe_copies"] > 0 and "sweep.gate_s" in timing
    assert timing["moe_pair_max_over_mean"] >= 1.0
    numpy = Study(_moe_spec([1]), backend="numpy").run().results[0]
    assert (numpy.packets_generated, numpy.phase_cycles) == \
        (b.packets_generated, b.phase_cycles)


def test_replay_points_hold_each_point_to_its_bound():
    """The worst ratio over the grid, with that point's own bound (not
    the first point's)."""
    from types import SimpleNamespace
    from repro.studies.runner import StudyResult
    spec = _moe_spec([0, 1])

    def rec(completion, ideal):
        return SimpleNamespace(experiment=spec.name,
                               completion_cycles=completion,
                               ideal_cycles=ideal)

    res = StudyResult([spec], [rec(100, 100), rec(90, 60)], 2, 0, "jax")
    assert res.replay_points()[spec.name] == {
        "measured": 90, "ideal": 60, "ratio": 1.5}


def test_moe_cli_example(tmp_path, capsys):
    from repro.studies.__main__ import main as cli
    store = tmp_path / "moe.jsonl"
    assert cli(["run", "moe_dispatch", "--backend", "numpy",
                "--store", str(store)]) == 0
    out = capsys.readouterr().out
    assert "collective replay" in out and "ratio=1.0" in out
