"""LACIN collectives on 8 host devices (subprocess — the main test process
keeps the default single-device environment)."""
import json
import os
import subprocess
import sys

import pytest

_CHILD = r"""
import json
import numpy as np, jax, jax.numpy as jnp
from jax.sharding import Mesh, PartitionSpec as P
from jax import shard_map
from repro.core import (all_to_all_lacin, all_gather_lacin,
                        reduce_scatter_lacin, all_reduce_lacin)

devs = jax.devices(); n = len(devs)
assert n == 8, n
mesh = Mesh(np.array(devs), ("x",))
results = {}

for inst in ("xor", "circle", "cyclic"):
    x = jnp.arange(n * n * 12, dtype=jnp.float32).reshape(n, n, 4, 3)
    out = shard_map(lambda xl: all_to_all_lacin(xl[0], "x", axis_size=n,
                                                instance=inst)[None],
                    mesh=mesh, in_specs=P("x"), out_specs=P("x"))(x)
    results[f"a2a_{inst}"] = bool(jnp.array_equal(out, jnp.swapaxes(x, 0, 1)))

    xs = jnp.arange(n * 5, dtype=jnp.float32).reshape(n, 5)
    out = shard_map(lambda xl: all_gather_lacin(xl[0], "x", axis_size=n,
                                                instance=inst)[None],
                    mesh=mesh, in_specs=P("x"), out_specs=P("x"))(xs)
    results[f"ag_{inst}"] = bool(jnp.array_equal(out, jnp.broadcast_to(xs, (n, n, 5))))

    xr = jax.random.normal(jax.random.PRNGKey(0), (n, n, 6))
    out = shard_map(lambda xl: reduce_scatter_lacin(xl[0], "x", axis_size=n,
                                                    instance=inst)[None],
                    mesh=mesh, in_specs=P("x"), out_specs=P("x"))(xr)
    results[f"rs_{inst}"] = bool(jnp.allclose(out, jnp.sum(xr, 0), rtol=1e-4,
                                              atol=1e-5))

    xa = jax.random.normal(jax.random.PRNGKey(1), (n, 7, 3))
    out = shard_map(lambda xl: all_reduce_lacin(xl[0], "x", axis_size=n,
                                                instance=inst)[None],
                    mesh=mesh, in_specs=P("x"), out_specs=P("x"))(xa)
    want = jnp.broadcast_to(jnp.sum(xa, 0), (n, 7, 3))
    results[f"ar_{inst}"] = bool(jnp.allclose(out, want, rtol=1e-4, atol=1e-5))

# odd axis size with circle (5 devices of the 8)
mesh5 = Mesh(np.array(devs[:5]), ("x",))
x5 = jax.random.normal(jax.random.PRNGKey(2), (5, 5, 4))
out = shard_map(lambda xl: all_to_all_lacin(xl[0], "x", axis_size=5,
                                            instance="circle")[None],
                mesh=mesh5, in_specs=P("x"), out_specs=P("x"))(x5)
results["a2a_circle_odd"] = bool(jnp.allclose(out, jnp.swapaxes(x5, 0, 1)))

# gradient flows through the schedule (ppermute transpose)
def loss(x):
    def body(xl):
        return all_reduce_lacin(xl[0], "x", axis_size=n)[None]
    y = shard_map(body, mesh=mesh, in_specs=P("x"), out_specs=P("x"))(x)
    return (y ** 2).sum()
g = jax.grad(loss)(jnp.ones((n, 4)))
results["grad_finite"] = bool(jnp.isfinite(g).all())

# HLO step count: all-reduce = RS + AG = 2(N-1) collective-permutes
import re
txt = jax.jit(shard_map(lambda xl: all_reduce_lacin(xl[0], "x", axis_size=n,
                                                    instance="xor")[None],
              mesh=mesh, in_specs=P("x"), out_specs=P("x"))).lower(
    jax.ShapeDtypeStruct((n, 16, 16), jnp.float32)).compile().as_text()
# match op instances only ("collective-permute(") — the bare name also
# appears in metadata/op_name annotations on some XLA versions.
results["ar_permutes"] = len(re.findall(r"collective-permute\(", txt))
print("RESULT " + json.dumps(results))
"""


@pytest.fixture(scope="module")
def child_results():
    env = dict(os.environ,
               XLA_FLAGS="--xla_force_host_platform_device_count=8",
               PYTHONPATH="src")
    res = subprocess.run([sys.executable, "-c", _CHILD], env=env,
                         capture_output=True, text=True, timeout=600,
                         cwd=os.path.dirname(os.path.dirname(__file__)))
    assert res.returncode == 0, res.stderr[-2000:]
    line = [l for l in res.stdout.splitlines() if l.startswith("RESULT ")][-1]
    return json.loads(line[len("RESULT "):])


@pytest.mark.parametrize("op", ["a2a", "ag", "rs", "ar"])
@pytest.mark.parametrize("inst", ["xor", "circle", "cyclic"])
def test_collective_correct(child_results, op, inst):
    assert child_results[f"{op}_{inst}"], (op, inst)


def test_odd_axis_circle(child_results):
    assert child_results["a2a_circle_odd"]


def test_gradients_flow_through_schedule(child_results):
    assert child_results["grad_finite"]


def test_all_reduce_is_2_n_minus_1_matchings(child_results):
    assert child_results["ar_permutes"] == 2 * 7
