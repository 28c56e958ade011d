"""Compiles for one described TPU v5e chip, with no chip attached.

The TPU compiler refuses here what the chip would refuse: kernels whose
blocks do not match the TPU's tiling, and programs that do not fit its
memory.  Nothing runs, so these tests say nothing about results or times
(``chip_smoke.py`` runs the program on the chip).

The topology is described inside a module fixture, never at import: only
one process at a time may load the TPU library, and every test worker
imports this file.  JAX's persistent cache is off around these compiles,
since an executable for a described chip cannot be read back without one.
"""
import os

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

#: HBM of one TPU v5e chip (Google Cloud documentation, "TPU v5e").
V5E_HBM_BYTES = 16 * 2**30


@pytest.fixture(scope="module")
def one_chip():
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache
    log_dir = os.environ.setdefault("TPU_LOG_DIR", "disabled")
    try:
        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    was_on = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield SingleDeviceSharding(topo.devices[0])
    jax.config.update("jax_enable_compilation_cache", was_on)
    compilation_cache.reset_cache()
    if log_dir == "disabled":
        os.environ.pop("TPU_LOG_DIR", None)


def _shapes(tree, sharding):
    return jax.tree_util.tree_map(
        lambda a: jax.ShapeDtypeStruct(jnp.shape(a), jnp.asarray(a).dtype,
                                       sharding=sharding), tree)


class _Captured(Exception):
    pass


def test_xengine_dragonfly_1040_grid_compiles_and_fits(one_chip,
                                                       monkeypatch):
    """The cycle engine's program for the 1040-switch Dragonfly
    (a16 p8 h8 g65, 8320 terminals), four grid points at 256 cycles."""
    from repro import sim
    from repro.core import DragonflyConfig
    from repro.sim import xengine
    cfg = DragonflyConfig(group_size=16, terminals_per_switch=8,
                          global_ports_per_switch=8, num_groups=65)
    topo = sim.dragonfly_topology(cfg)
    captured = {}

    def capture(fn, static_arg, *args, **kw):
        captured.update(fn=fn, spec=static_arg, args=args)
        raise _Captured

    monkeypatch.setattr(xengine, "timed_compiled", capture)
    with pytest.raises(_Captured):
        xengine.sweep(
            topo, "minimal",
            lambda load, seed: sim.uniform(topo.num_switches, offered=load,
                                           cycles=256, terminals=8,
                                           seed=seed),
            [0.1, 0.3, 0.5, 0.7], seeds=(0,), terminals=8, cycles=256)
    assert captured["fn"] is xengine._run_flat
    compiled = xengine._run_flat.lower(
        captured["spec"], *_shapes(captured["args"], one_chip)).compile()
    mem = compiled.memory_analysis()
    used = mem.argument_size_in_bytes + mem.temp_size_in_bytes
    assert 0 < used < V5E_HBM_BYTES, mem


def test_xengine_moe_replay_compiles_and_fits(one_chip, monkeypatch):
    """The drained replay of DeepSeek-V3's EP dispatch and combine on the
    12 x 8 HyperX (128 tokens a rank, 7 ranks a node): ~1.8 M packets
    in 14 uneven phases, one grid point, the benchmark cell's program."""
    from repro.core.hyperx import HyperXConfig
    from repro.fabric import make_fabric
    from repro.sim import xengine
    from repro.sim.workloads import MoESpec, moe_dispatch_workload
    fab = make_fabric(HyperXConfig(dims=(12, 8), terminals=7,
                                   instance="circle"))
    moe = MoESpec(batch_tokens=128, ranks_per_node=7, sigma=0.5)
    captured = {}

    def capture(fn, static_arg, *args, **kw):
        captured.update(fn=fn, spec=static_arg, args=args)
        raise _Captured

    monkeypatch.setattr(xengine, "timed_compiled", capture)
    with pytest.raises(_Captured):
        xengine.sweep(fab.sim_topology(), "minimal",
                      lambda load, seed: moe_dispatch_workload(
                          fab, moe, seed).traffic(),
                      [0.0], seeds=(5,), terminals=7)
    spec = captured["spec"]
    assert (spec.drain, spec.num_phases) == (True, 14)
    assert captured["args"][1]["src"].shape == (2_097_152,)
    compiled = xengine._run_flat.lower(
        spec, *_shapes(captured["args"], one_chip)).compile()
    mem = compiled.memory_analysis()
    used = mem.argument_size_in_bytes + mem.temp_size_in_bytes
    assert 0 < used < V5E_HBM_BYTES, mem


def test_flash_attention_compiles_for_tpu(one_chip):
    from repro.kernels.ops import flash_attention
    qkv = jax.ShapeDtypeStruct((1, 2048, 8, 128), jnp.bfloat16,
                               sharding=one_chip)
    compiled = flash_attention.lower(qkv, qkv, qkv).compile()
    assert "tpu_custom_call" in compiled.as_text()


def test_mlstm_scan_compiles_for_tpu(one_chip):
    from repro.kernels.ops import mlstm_scan
    qkv = jax.ShapeDtypeStruct((1, 2048, 4, 256), jnp.float32,
                               sharding=one_chip)
    gate = jax.ShapeDtypeStruct((1, 2048, 4), jnp.float32, sharding=one_chip)
    compiled = mlstm_scan.lower(qkv, qkv, qkv, gate, gate).compile()
    assert "tpu_custom_call" in compiled.as_text()
