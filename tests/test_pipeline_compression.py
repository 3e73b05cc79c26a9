"""Pipeline parallelism + gradient compression tests (multi-host-device
subprocesses: XLA device count must be set before jax import)."""
import os
import subprocess
import sys

import pytest

ENV = dict(os.environ,
           PYTHONPATH=os.path.join(os.path.dirname(__file__), "..", "src"))


def _run(script: str, timeout=600):
    r = subprocess.run([sys.executable, "-c", script], capture_output=True,
                       text=True, env=ENV, timeout=timeout)
    assert r.returncode == 0, (r.stdout[-1500:], r.stderr[-2500:])
    return r.stdout


PIPELINE = r"""
import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=4"
import jax, jax.numpy as jnp, numpy as np
from repro.distributed.pipeline import pipeline_apply
from repro.distributed.mesh import make_debug_mesh

mesh = make_debug_mesh((4,), ("stage",))
L, B, Dm = 8, 8, 16
key = jax.random.PRNGKey(0)
ws = jax.random.normal(key, (L, Dm, Dm)) * 0.3
x = jax.random.normal(jax.random.PRNGKey(1), (B, Dm))

def block(w, h):
    return jnp.tanh(h @ w)

# sequential reference
ref = x
for i in range(L):
    ref = block(ws[i], ref)

for n_micro in (2, 4):
    got = pipeline_apply(mesh, "stage", block, ws, x, n_micro=n_micro)
    np.testing.assert_allclose(np.asarray(got), np.asarray(ref),
                               rtol=1e-5, atol=1e-5)
print("PIPELINE-OK")
"""


COMPRESSION = r"""
import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
import jax, jax.numpy as jnp, numpy as np
from jax.sharding import PartitionSpec as P
from repro.distributed.compression import compressed_allreduce_int8
from repro.distributed.mesh import make_debug_mesh

mesh = make_debug_mesh((8,), ("data",))
G = 8
x = jax.random.normal(jax.random.PRNGKey(0), (G, 64, 32))

def f(xs, err):
    m, e = compressed_allreduce_int8(xs[0], "data", err[0])
    return m[None], e[None]

fn = jax.jit(jax.shard_map(f, mesh=mesh, in_specs=(P("data"), P("data")),
                           out_specs=(P("data"), P("data")),
                           check_vma=False))
err0 = jnp.zeros_like(x)
mean, err = fn(x, err0)
true_mean = x.mean(0)
# every shard holds the same (approximate) mean
got = np.asarray(mean)
for g in range(G):
    rel = np.abs(got[g] - np.asarray(true_mean)).max() / (np.abs(np.asarray(true_mean)).max() + 1e-9)
    assert rel < 0.05, rel

# error feedback: accumulated mean over many steps converges to true mean
acc_c = np.zeros((64, 32)); acc_t = np.zeros((64, 32))
err = err0
for step in range(30):
    mean, err = fn(x, err)
    acc_c += np.asarray(mean[0]); acc_t += np.asarray(true_mean)
rel = np.abs(acc_c - acc_t).max() / np.abs(acc_t).max()
assert rel < 0.01, f"error feedback failed to cancel bias: {rel}"
print("COMPRESSION-OK")
"""


def test_pipeline_matches_sequential():
    assert "PIPELINE-OK" in _run(PIPELINE)


def test_compressed_allreduce_with_error_feedback():
    assert "COMPRESSION-OK" in _run(COMPRESSION)
