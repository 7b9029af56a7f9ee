"""int8 gradient compression: exactness properties + convergence with error
feedback on a shard_map DP group."""
import os
import pathlib
import subprocess
import sys

import numpy as np
import pytest

SCRIPT = r"""
import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=4"
import jax, jax.numpy as jnp
from repro.optim.compression import (compressed_grad_sync,
                                     compressed_psum_mean,
                                     init_error_feedback)

mesh = jax.make_mesh((4,), ("dp",), axis_types=(jax.sharding.AxisType.Auto,))
use_mesh = jax.set_mesh
from jax.sharding import PartitionSpec as P

def _shard_map(fn, mesh, in_specs, out_specs):
    return jax.shard_map(fn, mesh=mesh, in_specs=in_specs,
                         out_specs=out_specs, check_vma=False)

# --- property: compressed mean ≈ exact mean within quantization bound
def sync(g, e):
    return compressed_psum_mean(g, e, "dp")

g = jax.random.normal(jax.random.PRNGKey(0), (4, 64))
e0 = jnp.zeros((4, 64))
f = _shard_map(sync, mesh, in_specs=(P("dp"), P("dp")),
               out_specs=(P("dp"), P("dp")))
with use_mesh(mesh):
    mean, err = jax.jit(f)(g, e0)
exact = jnp.broadcast_to(g.mean(0, keepdims=True), g.shape)
bound = jnp.max(jnp.abs(g)) / 127.0 + 1e-6
assert float(jnp.max(jnp.abs(mean - exact))) <= float(bound), "mean bound"
# error feedback holds the residual
assert float(jnp.max(jnp.abs(err))) <= float(bound)

# --- convergence: distributed quadratic with EF keeps descending
w = jnp.ones((4, 8)) * 3.0          # per-shard copy of the parameter
targets = jax.random.normal(jax.random.PRNGKey(1), (4, 8))  # shard-local data

def step(w, t, e):
    grad = w - t                     # local gradient (per-shard data)
    mean_g, e = compressed_psum_mean(grad, e, "dp")
    return w - 0.3 * mean_g, e

fstep = _shard_map(step, mesh,
                   in_specs=(P("dp"), P("dp"), P("dp")),
                   out_specs=(P("dp"), P("dp")))
e = jnp.zeros((4, 8))
with use_mesh(mesh):
    jstep = jax.jit(fstep)
    for _ in range(120):
        w, e = jstep(w, targets, e)
opt = jnp.broadcast_to(targets.mean(0, keepdims=True), targets.shape)
final = float(jnp.max(jnp.abs(w - opt)))
assert final < 0.05, f"EF compression failed to converge: {final}"
print("COMPRESSION_OK")
"""


@pytest.mark.slow
def test_compressed_sync_on_dp_group():
    env = dict(os.environ)
    env["PYTHONPATH"] = str(pathlib.Path(__file__).resolve().parents[1] / "src")
    env.pop("XLA_FLAGS", None)
    out = subprocess.run([sys.executable, "-c", SCRIPT], env=env,
                         capture_output=True, text=True, timeout=240)
    assert "COMPRESSION_OK" in out.stdout, out.stdout + out.stderr
