"""Lookup by name, the step's byte count and the device gate."""
import json
import os
import shutil
import subprocess
import sys

import pytest

import harness
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


def test_a_cell_added_as_files_only(tmp_path):
    """A new configuration, mix and cell need new files and new entries,
    and no edit of an existing file."""
    root = tmp_path / "checkout"
    shutil.copytree(BENCH, root / "bench")
    bm = harness.benchmark()
    cfg = json.loads((root / "bench/configs/hyperx-12x8.json").read_text())
    cfg["fabric"]["params"]["dims"] = [6, 4]
    (root / "bench/configs/hyperx-6x4.json").write_text(json.dumps(cfg))
    bm["configs"].append({"name": "hyperx-6x4", "source": "test",
                          "file": "bench/configs/hyperx-6x4.json",
                          "reduced": ["dims"], "why": "test"})
    bm["workloads"].append({"name": "hx6x4.uniform.minimal",
                            "config": "hyperx-6x4",
                            "traffic": "uniform.minimal", "chips": 1,
                            "why": "test"})
    shutil.copy(root / "bench/limits/df2064.uniform.minimal.json",
                root / "bench/limits/hx6x4.uniform.minimal.json")
    (root / "BENCHMARK.json").write_text(json.dumps(bm))
    cell = harness.load_cell("hx6x4.uniform.minimal", str(root))
    assert cell.config["fabric"]["params"]["dims"] == [6, 4]
    assert cell.traffic["routing"]["policy"] == "minimal"
    assert {m["name"] for m in cell.per_layer} == set()   # not listed yet


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
