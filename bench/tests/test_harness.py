"""Lookup by name, the step's byte count and the device gate."""
import json
import os
import shutil
import subprocess
import sys

import numpy as np
import pytest

import check
import harness
from cells import tiny
from reference import traffic as ref_traffic
from stepbytes import step_bytes

from conftest import BENCH, ROOT


def test_step_bytes_of_the_simulator_cells():
    # df2064.uniform.minimal: 4 copies, 2064 switches, 23 ports, 3 VCs.
    assert step_bytes(copies=4, switches=2064, ports=23, vcs=3, capacity=4,
                      terminals=8) == 44_582_400
    # hx12x8.uniform.adaptive: 16 copies, 96 switches, 18 ports, 4 VCs.
    assert step_bytes(copies=16, switches=96, ports=18, vcs=4, capacity=4,
                      terminals=7) == 8_491_008


@pytest.mark.parametrize("cell", [w["name"] for w in
                                  harness.benchmark()["workloads"]])
def test_every_cell_finds_its_files(cell):
    c = harness.load_cell(cell)
    driver = harness.load_module("drivers", c.traffic["driver"])
    assert callable(driver.run)
    assert c.limits["limits"] and c.limits["control"]
    names = {m["name"] for m in c.per_layer}
    assert names, "every cell reports a per-layer metric"
    for m in c.per_layer:
        assert callable(harness.load_module("metrics", m["name"]).read)
    e2e = {m["name"] for m in c.end_to_end}
    assert "setup_s" in e2e and len(e2e) >= 2
    if c.traffic["driver"] == "study":
        mix = harness.load_module("reference/mixes", c.traffic["reference"])
        assert callable(mix.packets)


def _checkout_with_cell(tmp_path, cell: str, traffic: str):
    """A copy of the benchmark with one more configuration (a 6x4 HyperX)
    and one more cell on ``traffic``, added as new files and entries."""
    root = tmp_path / "checkout"
    shutil.copytree(BENCH, root / "bench",
                    ignore=shutil.ignore_patterns("__pycache__"))
    bm = harness.benchmark()
    cfg = json.loads((root / "bench/configs/hyperx-12x8.json").read_text())
    cfg["fabric"]["params"]["dims"] = [6, 4]
    (root / "bench/configs/hyperx-6x4.json").write_text(json.dumps(cfg))
    bm["configs"].append({"name": "hyperx-6x4", "source": "test",
                          "file": "bench/configs/hyperx-6x4.json",
                          "reduced": ["dims"], "why": "test"})
    bm["workloads"].append({"name": cell, "config": "hyperx-6x4",
                            "traffic": traffic, "chips": 1, "why": "test"})
    shutil.copy(root / "bench/limits/df2064.uniform.minimal.json",
                root / f"bench/limits/{cell}.json")
    (root / "BENCHMARK.json").write_text(json.dumps(bm))
    return root


def test_a_cell_added_as_files_only(tmp_path):
    """A new configuration, mix and cell need new files and new entries,
    and no edit of an existing file."""
    root = _checkout_with_cell(tmp_path, "hx6x4.uniform.minimal",
                               "uniform.minimal")
    cell = harness.load_cell("hx6x4.uniform.minimal", str(root))
    assert cell.config["fabric"]["params"]["dims"] == [6, 4]
    assert cell.traffic["routing"]["policy"] == "minimal"
    assert {m["name"] for m in cell.per_layer} == set()   # not listed yet


#: A mix that only the temporary checkout has: each switch sends to the
#: next, on the first draw every open-loop mix shares.
_NEXT_SWITCH = """
import numpy as np
from reference import fabric as ref_fabric, traffic as ref_traffic


def packets(fabric, traffic, terminals, load, seed):
    n = ref_fabric.num_switches(fabric)
    src, gen = ref_traffic.poisson_arrivals(
        np.random.default_rng(seed), n, load * terminals, traffic["cycles"])
    return ref_traffic.Packets(src, (src + 1) % n, gen, None)
"""

#: Run from the temporary checkout, so that ``harness`` finds its files.
_BUILD_REFERENCE = """
import json, sys
sys.path[:0] = ["bench", sys.argv[1]]
import check, harness
cell = harness.load_cell("hx6x4.next.minimal", ".")
ref = check.Reference(cell.config, cell.traffic)
pk = ref.packets(0.3, 17)
out = ref.simulate(0.3, 17, rng_seed=5)
print(json.dumps({"src": pk.src.tolist(), "dst": pk.dst.tolist(),
                  "generated": out["packets_generated"],
                  "delivered": out["packets_delivered"]}))
"""


def test_a_mix_added_as_files_only(tmp_path):
    """A mix whose reference builder exists only in the new checkout is
    found by the name its traffic file gives, and the reference runs on
    its packets: no existing file changes."""
    root = _checkout_with_cell(tmp_path, "hx6x4.next.minimal",
                               "next.minimal")
    before = {p: p.read_bytes() for p in (root / "bench").rglob("*")
              if p.is_file()}
    tr = json.loads((root / "bench/traffic/uniform.minimal.json")
                    .read_text())
    tr.update(reference="next_switch", cycles=40, warmup=10)
    (root / "bench/traffic/next.minimal.json").write_text(json.dumps(tr))
    (root / "bench/reference/mixes/next_switch.py").write_text(_NEXT_SWITCH)
    assert not os.path.exists(
        os.path.join(BENCH, "reference/mixes/next_switch.py"))

    proc = subprocess.run(
        [sys.executable, "-c", _BUILD_REFERENCE, os.path.join(ROOT, "src")],
        cwd=root, capture_output=True, text=True, timeout=120)
    assert proc.returncode == 0, proc.stderr
    got = json.loads(proc.stdout.splitlines()[-1])
    src, _ = ref_traffic.poisson_arrivals(np.random.default_rng(17), 24,
                                          0.3 * 7, 40)
    assert got["src"] == src.tolist()
    assert got["dst"] == ((src + 1) % 24).tolist()
    assert got["generated"] == src.size and got["delivered"] > 0
    assert all(p.read_bytes() == b for p, b in before.items())


def _plain_draw(cell, load, seed):
    """What the reference drew for each mix before builders were named."""
    cfg, tr = cell.config, cell.traffic
    if tr["traffic"]["pattern"] == "uniform":
        n = check.Reference(cfg, tr).fabric.num_switches
        return ref_traffic.uniform(n, offered=load, cycles=tr["cycles"],
                                   terminals=cfg["terminals"], seed=seed)
    return ref_traffic.a2a_replay(
        cfg["fabric"]["params"]["dims"],
        int(tr["traffic"]["params"]["message_size"]))


@pytest.mark.parametrize("seed", [5, 77, 2**31 + 3])
@pytest.mark.parametrize("name", ["df2064.uniform.minimal",
                                  "hx12x8.uniform.adaptive",
                                  "hx12x8.a2a.replay"])
def test_named_builders_return_the_plain_draws(name, seed):
    cell = tiny(name)
    ref = check.Reference(cell.config, cell.traffic)
    for load in cell.traffic["loads"]:
        got, want = ref.packets(load, seed), _plain_draw(cell, load, seed)
        for field in ("src", "dst", "gen", "phase_sizes"):
            a, b = getattr(got, field), getattr(want, field)
            assert (a is None) == (b is None), field
            if a is not None:
                assert a.dtype == b.dtype and np.array_equal(a, b), field


def test_a_traffic_file_without_a_builder_is_refused():
    cell = tiny("df2064.uniform.minimal")
    del cell.traffic["reference"]
    with pytest.raises(harness.Refused, match="reference"):
        check.Reference(cell.config, cell.traffic)


def _run(cwd, *extra):
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    return subprocess.run(
        [sys.executable, "bench/run.py", "--workload",
         "df2064.uniform.minimal", "--seed", "1", "--seconds", "1",
         "--trace", "0", *extra],
        cwd=cwd, env=env, capture_output=True, text=True, timeout=300)


def test_no_tpu_exits_nonzero_without_a_result():
    proc = _run(ROOT)
    assert proc.returncode != 0
    assert proc.stdout.strip() == ""
    assert "no TPU" in proc.stderr


def test_benchmark_files_alone_exit_nonzero(tmp_path):
    shutil.copytree(BENCH, tmp_path / "bench",
                    ignore=shutil.ignore_patterns("__pycache__"))
    shutil.copy(os.path.join(ROOT, "BENCHMARK.json"), tmp_path)
    proc = _run(tmp_path)
    assert proc.returncode != 0
    assert proc.stdout.strip() == ""
