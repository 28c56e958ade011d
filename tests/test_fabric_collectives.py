"""Hierarchical LACIN collectives vs ``lax`` references on an 8-host-device
mesh (subprocess keeps the main test process single-device).

* multi-axis dimension-order all-to-all over HyperX-shaped meshes
  ((2,4) and (2,2,2)) — bit-identical to ``lax.all_to_all`` with a tuple
  of axis names (pure permutation, so exact equality is required);
* two-level Dragonfly all-reduce (local RS -> global AR -> local AG) —
  bit-identical to ``lax.psum`` over both axes on integer-valued floats
  (exact summation) and allclose on gaussians;
* mesh-aware size inference: no ``axis_size=`` anywhere in the child —
  sizes come from the bound mesh or the axis environment, including an
  odd local axis (3) that exercises the idle-step Circle schedule;
* ``DragonflyFabric.collectives(mesh, ...)`` binding local/global
  instances per axis.
"""
import json
import os
import subprocess
import sys

import pytest

_CHILD = r"""
import json
import numpy as np, jax, jax.numpy as jnp
from jax import lax
from jax.sharding import Mesh, PartitionSpec as P
from jax import shard_map
from repro.core import DragonflyConfig
from repro.fabric import LacinCollectives, make_fabric

devs = jax.devices()
assert len(devs) == 8, len(devs)
results = {}


def run(mesh, axes, fn, x):
    return shard_map(lambda xl: fn(xl[0])[None], mesh=mesh,
                     in_specs=P(axes), out_specs=P(axes))(x)


# ---- multi-axis dimension-order all-to-all (HyperX-shaped meshes) ----------
for shape, names in (((2, 4), ("a", "b")), ((2, 2, 2), ("a", "b", "c")),
                     ((4, 2), ("a", "b"))):
    mesh = Mesh(np.array(devs).reshape(shape), names)
    coll = LacinCollectives(mesh=mesh)
    n = 8
    x = jax.random.normal(jax.random.PRNGKey(0), (n, n, 3, 2))
    got = run(mesh, names, lambda xl: coll.all_to_all_grid(xl, names), x)
    ref = run(mesh, names,
              lambda xl: lax.all_to_all(xl[:, None], names, split_axis=0,
                                        concat_axis=0).reshape(n, 3, 2), x)
    tag = "x".join(map(str, shape))
    results[f"grid_a2a_{tag}"] = bool(jnp.array_equal(got, ref))

# meshless variant: sizes inferred from the axis environment inside the
# shard_map body (no mesh bound, no axis_size threading).
mesh = Mesh(np.array(devs).reshape(2, 4), ("a", "b"))
free = LacinCollectives()
x = jax.random.normal(jax.random.PRNGKey(4), (8, 8, 5))
got = run(mesh, ("a", "b"), lambda xl: free.all_to_all_grid(xl, ("a", "b")), x)
ref = run(mesh, ("a", "b"),
          lambda xl: lax.all_to_all(xl[:, None], ("a", "b"), split_axis=0,
                                    concat_axis=0).reshape(8, 5), x)
results["grid_a2a_meshless"] = bool(jnp.array_equal(got, ref))

# ---- two-level Dragonfly all-reduce ----------------------------------------
# mesh (g, l) = (2, 4): groups of 4 under a global CIN of 2.
meshd = Mesh(np.array(devs).reshape(2, 4), ("g", "l"))
colld = LacinCollectives(mesh=meshd,
                         axis_instances=(("l", "circle"), ("g", "circle")))

xi = jnp.asarray(np.random.default_rng(0).integers(-8, 8, (8, 7, 3)),
                 jnp.float32)
got = run(meshd, ("g", "l"),
          lambda xl: colld.all_reduce_two_level(xl, "l", "g"), xi)
ref = run(meshd, ("g", "l"), lambda xl: lax.psum(xl, ("g", "l")), xi)
results["two_level_ar_exact"] = bool(jnp.array_equal(got, ref))

xg = jax.random.normal(jax.random.PRNGKey(1), (8, 6, 5))
got = run(meshd, ("g", "l"),
          lambda xl: colld.all_reduce_two_level(xl, "l", "g"), xg)
ref = run(meshd, ("g", "l"), lambda xl: lax.psum(xl, ("g", "l")), xg)
results["two_level_ar_close"] = bool(jnp.allclose(got, ref, rtol=1e-5,
                                                  atol=1e-6))

# odd local axis (3 of the 8 devices unused): mesh (2, 3), Circle with an
# idle device per local step.
mesh6 = Mesh(np.array(devs[:6]).reshape(2, 3), ("g", "l"))
coll6 = LacinCollectives(mesh=mesh6)
xo = jnp.asarray(np.random.default_rng(2).integers(-4, 4, (6, 5)),
                 jnp.float32)
got = run(mesh6, ("g", "l"),
          lambda xl: coll6.all_reduce_two_level(xl, "l", "g"), xo)
ref = run(mesh6, ("g", "l"), lambda xl: lax.psum(xl, ("g", "l")), xo)
results["two_level_ar_odd_exact"] = bool(jnp.array_equal(got, ref))

# ---- fabric-bound collectives ----------------------------------------------
# A dragonfly whose group_size matches the mesh's local axis; instances
# bound per axis by the fabric (mirror globally exercises the registered
# instance end to end).
fab = make_fabric(DragonflyConfig(4, 2, 1, 5, local_instance="circle",
                                  global_instance="mirror"))
try:
    fab.collectives(meshd, local_axis="l", global_axis="g")
    results["fabric_mesh_check"] = False      # g axis is 2 != 5 groups
except ValueError:
    results["fabric_mesh_check"] = True
collf = fab.collectives(meshd, local_axis="l")
assert collf.axis_instance("l") == "circle"
got = run(meshd, ("g", "l"),
          lambda xl: collf.all_reduce_two_level(xl, "l", "g"), xi)
refi = run(meshd, ("g", "l"), lambda xl: lax.psum(xl, ("g", "l")), xi)
results["fabric_two_level_ar"] = bool(jnp.array_equal(got, refi))

print("RESULT " + json.dumps(results))
"""


@pytest.fixture(scope="module")
def ref_results():
    env = dict(os.environ,
               XLA_FLAGS="--xla_force_host_platform_device_count=8",
               PYTHONPATH="src")
    res = subprocess.run([sys.executable, "-c", _CHILD], env=env,
                         capture_output=True, text=True, timeout=600,
                         cwd=os.path.dirname(os.path.dirname(__file__)))
    assert res.returncode == 0, res.stderr[-2000:]
    line = [l for l in res.stdout.splitlines() if l.startswith("RESULT ")][-1]
    return json.loads(line[len("RESULT "):])


@pytest.mark.parametrize("key", ["grid_a2a_2x4", "grid_a2a_2x2x2",
                                 "grid_a2a_4x2", "grid_a2a_meshless"])
def test_grid_all_to_all_bit_identical_to_lax(ref_results, key):
    assert ref_results[key], key


@pytest.mark.parametrize("key", ["two_level_ar_exact", "two_level_ar_close",
                                 "two_level_ar_odd_exact"])
def test_two_level_dragonfly_all_reduce_matches_psum(ref_results, key):
    assert ref_results[key], key


def test_fabric_bound_collectives(ref_results):
    assert ref_results["fabric_mesh_check"]
    assert ref_results["fabric_two_level_ar"]
