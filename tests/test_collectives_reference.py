"""LACIN collectives vs the XLA reference collectives, on an 8-host-device
mesh (subprocess keeps the main test process single-device).

Complements ``test_collectives_multidev.py`` (which checks algebraic
post-conditions): here every LACIN collective is compared against the
corresponding ``lax`` collective — ``all_to_all``, ``all_gather``, and
``psum``-derived references — for both even (8) and odd (5) axis sizes.
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
from repro.core import (all_to_all_lacin, all_gather_lacin,
                        reduce_scatter_lacin, all_reduce_lacin)

devs = jax.devices()
assert len(devs) == 8, len(devs)
results = {}


def compare(n, inst, tag):
    mesh = Mesh(np.array(devs[:n]), ("x",))
    sm = lambda f: shard_map(f, mesh=mesh, in_specs=P("x"), out_specs=P("x"))

    # all-to-all: x[j] is this device's chunk for device j.
    x = jax.random.normal(jax.random.PRNGKey(0), (n, n, 3, 2))
    got = sm(lambda xl: all_to_all_lacin(xl[0], "x", axis_size=n,
                                         instance=inst)[None])(x)
    ref = sm(lambda xl: lax.all_to_all(xl[0][:, None], "x", split_axis=0,
                                       concat_axis=0).reshape(n, 3, 2)[None])(x)
    results[f"{tag}_a2a"] = bool(jnp.allclose(got, ref, rtol=1e-5, atol=1e-6))

    # all-gather of each device's shard.
    xs = jax.random.normal(jax.random.PRNGKey(1), (n, 4, 3))
    got = sm(lambda xl: all_gather_lacin(xl[0], "x", axis_size=n,
                                         instance=inst)[None])(xs)
    ref = sm(lambda xl: lax.all_gather(xl[0], "x")[None])(xs)
    results[f"{tag}_ag"] = bool(jnp.allclose(got, ref, rtol=1e-5, atol=1e-6))

    # reduce-scatter: reference = full psum, then take own shard.
    xr = jax.random.normal(jax.random.PRNGKey(2), (n, n, 5))
    got = sm(lambda xl: reduce_scatter_lacin(xl[0], "x", axis_size=n,
                                             instance=inst)[None])(xr)
    ref = sm(lambda xl: lax.psum(xl[0], "x")[lax.axis_index("x")][None])(xr)
    results[f"{tag}_rs"] = bool(jnp.allclose(got, ref, rtol=1e-4, atol=1e-5))

    # all-reduce vs lax.psum.
    xa = jax.random.normal(jax.random.PRNGKey(3), (n, 6, 3))
    got = sm(lambda xl: all_reduce_lacin(xl[0], "x", axis_size=n,
                                         instance=inst)[None])(xa)
    ref = sm(lambda xl: lax.psum(xl[0], "x")[None])(xa)
    results[f"{tag}_ar"] = bool(jnp.allclose(got, ref, rtol=1e-4, atol=1e-5))


compare(8, "xor", "even_xor")
compare(8, "circle", "even_circle")
compare(5, "circle", "odd_circle")    # odd axis: one idle device per step
compare(5, "auto", "odd_auto")
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


@pytest.mark.parametrize("tag", ["even_xor", "even_circle", "odd_circle",
                                 "odd_auto"])
@pytest.mark.parametrize("op", ["a2a", "ag", "rs", "ar"])
def test_lacin_matches_lax_reference(ref_results, tag, op):
    assert ref_results[f"{tag}_{op}"], (tag, op)
