"""Context-parallel mLSTM == sequential oracle (8-device subprocess)."""
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
from repro.models.xlstm import mlstm_sequential
from repro.models.xlstm_sp import mlstm_context_parallel

devs = jax.devices(); S = len(devs)
mesh = Mesh(np.array(devs), ("seq",))
b, t, h, d = 2, 8 * 64, 2, 32        # 64 tokens per device
ks = jax.random.split(jax.random.PRNGKey(0), 5)
q = jax.random.normal(ks[0], (b, t, h, d))
k = jax.random.normal(ks[1], (b, t, h, d))
v = jax.random.normal(ks[2], (b, t, h, d))
li = jax.random.normal(ks[3], (b, t, h)) * 2
lf = jax.nn.log_sigmoid(jax.random.normal(ks[4], (b, t, h)) * 2 + 1)

ref, _ = mlstm_sequential(q, k, v, li, lf)

def body(qs, ks_, vs, lis, lfs):
    return mlstm_context_parallel(qs, ks_, vs, lis, lfs,
                                  axis_name="seq", axis_size=S, chunk=32)

sp = shard_map(body, mesh=mesh,
               in_specs=(P(None, "seq"), P(None, "seq"), P(None, "seq"),
                         P(None, "seq"), P(None, "seq")),
               out_specs=P(None, "seq"), check_vma=False)
out = sp(q, k, v, li, lf)
err = float(jnp.max(jnp.abs(out.astype(jnp.float32) - ref)))
rel = err / float(jnp.max(jnp.abs(ref)))
# gradient flows through the distributed scan
g = jax.grad(lambda q_: (sp(q_, k, v, li, lf) ** 2).sum())(q)
print("RESULT " + json.dumps({
    "rel": rel, "grad_finite": bool(jnp.isfinite(g).all())}))
"""


@pytest.fixture(scope="module")
def sp_results():
    env = dict(os.environ,
               XLA_FLAGS="--xla_force_host_platform_device_count=8",
               PYTHONPATH="src")
    r = subprocess.run([sys.executable, "-c", _CHILD], env=env,
                       capture_output=True, text=True, timeout=900,
                       cwd=os.path.dirname(os.path.dirname(__file__)))
    assert r.returncode == 0, r.stderr[-3000:]
    line = [l for l in r.stdout.splitlines() if l.startswith("RESULT ")][-1]
    return json.loads(line[len("RESULT "):])


def test_context_parallel_mlstm_matches_sequential(sp_results):
    assert sp_results["rel"] < 1e-4, sp_results


def test_context_parallel_gradients_finite(sp_results):
    assert sp_results["grad_finite"]
