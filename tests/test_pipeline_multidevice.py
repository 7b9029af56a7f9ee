"""Pipeline runtime on a real multi-device (host) mesh — subprocess because
the device count must be set before jax initializes.

The FIFO-stream path takes its lowering from the planner's `ChannelPlan`
records through the shared registry (`plans=`); the reorder-buffer baseline
is forced by registry name.  Both must match the sequential reference."""
import os
import pathlib
import subprocess
import sys

import pytest

SCRIPT = r"""
import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=4"
import numpy as np
import jax, jax.numpy as jnp
from jax.sharding import Mesh
from repro.comm import PipelineSpec, analyze_pipeline
from repro.comm.pipeline import pipeline_loss_fn, ring_lowering

mesh = Mesh(np.array(jax.devices()).reshape(4), ("pipe",))
S, D, M, mb = 4, 16, 8, 4

def stage_fn(p, h):
    return jnp.tanh(h @ p["w"] + p["b"])

def loss_head(h, tgt):
    return jnp.mean((h - tgt) ** 2)

rng = jax.random.PRNGKey(0)
params = {"w": 0.5 * jax.random.normal(rng, (S, D, D)), "b": jnp.zeros((S, D))}
xs = jax.random.normal(rng, (M, mb, D))
tg = jax.random.normal(jax.random.PRNGKey(1), (M, mb, D))

def ref_loss(params, xs, tg):
    def one(mb_x, mb_t):
        h = mb_x
        for s in range(S):
            h = stage_fn({"w": params["w"][s], "b": params["b"][s]}, h)
        return loss_head(h, mb_t)
    return jnp.mean(jax.vmap(one)(xs, tg))

want = float(ref_loss(params, xs, tg))

# the planner's records drive the lowering selection (registry path)
_, plans = analyze_pipeline(PipelineSpec(stages=S, microbatches=M))
assert ring_lowering(plans) == "ppermute", plans
for kwargs in ({"plans": plans}, {"lowering": "reorder-buffer"}):
    f = pipeline_loss_fn(stage_fn, loss_head, mesh, "pipe", **kwargs)
    got = float(jax.jit(f)(params, xs, tg))
    g = jax.jit(jax.grad(f))(params, xs, tg)
    assert abs(got - want) < 1e-5, (kwargs, got, want)
    gn = float(jnp.sqrt(sum(jnp.sum(x * x) for x in jax.tree.leaves(g))))
    assert gn > 0
print("PIPELINE_OK")
"""


@pytest.mark.slow
def test_pipeline_matches_reference_on_4_devices():
    env = dict(os.environ)
    env["PYTHONPATH"] = str(pathlib.Path(__file__).resolve().parents[1] / "src")
    env.pop("XLA_FLAGS", None)
    out = subprocess.run([sys.executable, "-c", SCRIPT], env=env,
                         capture_output=True, text=True, timeout=240)
    assert "PIPELINE_OK" in out.stdout, out.stdout + out.stderr
