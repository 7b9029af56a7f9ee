"""SPMD pipeline parallelism with registry-selected channel lowerings.

GPipe-style schedule over a `pipe` mesh axis inside `shard_map`: stage
parameters are sharded over the axis; microbatches stream through a rotating
communication step whose implementation comes from the ``"jax"`` backend of
the lowering registry (`repro.runtime.lowering`).  The step is selected from
`ChannelPlan` records — pass the planner's output (`analyze_pipeline(spec)`)
via ``plans=`` and the ring runs the cheapest lowering that serves every
planned channel; `tests/test_pipeline_multidevice.py` measures the
reorder-buffer alternative by forcing ``lowering=`` explicitly.  Gradients
flow through the transposed collectives automatically under `jax.grad`.

The old ``fifo: bool`` toggle is deprecated (warn-once): it was a private
re-encoding of the verdict→lowering table that now lives in the registry.
"""
from __future__ import annotations

import functools
from typing import Callable, Dict, Iterable, Optional, Tuple

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, PartitionSpec as P

from ..core.deprecation import warn_deprecated
from ..runtime.lowering import (FIFO_STREAM, REORDER_BUFFER, backend,
                                is_cheap)


def ring_lowering(plans: Iterable) -> str:
    """The single lowering a rotating ring needs to serve every planned
    channel: the cheap ppermute stream iff every `ChannelPlan` record is a
    stream (recovered splits included), else the reorder-buffer fallback.
    Accepts plan objects or their `as_dict()` form."""
    names = [p["lowering"] if isinstance(p, dict) else p.lowering
             for p in plans]
    return (FIFO_STREAM if all(is_cheap(n) for n in names)
            else REORDER_BUFFER)


def _split_backend(lowering: str) -> Tuple[str, str]:
    """Parse an optionally backend-qualified lowering name.  The existing
    ``lowering=`` path accepts ``"ppermute"`` (served by the default
    ``"jax"`` backend) or ``"pallas:ppermute"`` — same flag, richer values,
    no parallel knob.  Plan records stay unqualified; the registry decides."""
    if ":" in lowering:
        bname, lname = lowering.split(":", 1)
        return bname, lname
    return "jax", lowering


def _resolve_lowering(lowering: Optional[str], plans, fifo) -> str:
    if isinstance(lowering, bool):
        # a pre-registry caller passing the old fifo flag positionally in
        # the slot the lowering name now occupies — route to the shim
        lowering, fifo = None, lowering
    if fifo is not None:
        warn_deprecated(
            "comm.pipeline.fifo",
            "the fifo: bool toggle is deprecated; pass plans=<ChannelPlan "
            "records> (or lowering=<registry name>) so the implementation "
            "comes from the shared lowering registry",
            stacklevel=4)      # user -> pipeline_loss_fn -> here -> warn
    # precedence matches the docstring: plan records, then an explicit
    # registry name, then the deprecated flag
    if plans is not None:
        return ring_lowering(plans)
    if lowering is not None:
        return lowering
    if fifo is not None:
        return FIFO_STREAM if fifo else REORDER_BUFFER
    return FIFO_STREAM


def pipeline_loss_fn(stage_fn: Callable, loss_head: Callable, mesh: Mesh,
                     axis: str = "pipe", lowering: Optional[str] = None,
                     *, plans=None, fifo: Optional[bool] = None):
    """Build loss(params_stacked, xs, targets) running the stage pipeline.

    stage_fn(stage_params, h) -> h           (one stage's computation)
    loss_head(h, target_mb) -> scalar        (applied at the last stage)
    params_stacked: pytree with leading dim = n_stages
    xs: (M, mb, …) microbatched inputs; targets: (M, …) per microbatch.

    The inter-stage channel implementation is selected through the lowering
    registry: from ``plans`` (`ChannelPlan` records, preferred), an explicit
    ``lowering`` name — optionally backend-qualified, e.g.
    ``"pallas:ppermute"`` — or the deprecated ``fifo`` flag.
    """
    n = mesh.shape[axis]
    bname, lname = _split_backend(_resolve_lowering(lowering, plans, fifo))
    step = backend(bname).implementation(lname)

    def inner(params, xs, targets):
        stage = jax.lax.axis_index(axis)
        params_local = jax.tree.map(lambda a: a[0], params)
        M = xs.shape[0]
        T = M + n - 1                        # pipeline ticks
        h = jnp.zeros_like(xs[0])
        loss_acc = jnp.zeros((), jnp.float32)

        def tick(carry, t):
            h, loss_acc = carry
            # first stage injects microbatch t (if any left)
            mb = jax.lax.dynamic_index_in_dim(
                xs, jnp.minimum(t, M - 1), 0, keepdims=False)
            h_in = jnp.where(stage == 0, mb, h)
            h_out = stage_fn(params_local, h_in)
            # last stage consumes microbatch t-(n-1)
            out_id = t - (n - 1)
            tgt = jax.lax.dynamic_index_in_dim(
                targets, jnp.clip(out_id, 0, M - 1), 0, keepdims=False)
            mb_loss = loss_head(h_out, tgt)
            take = jnp.logical_and(stage == n - 1,
                                   jnp.logical_and(out_id >= 0, out_id < M))
            loss_acc = loss_acc + jnp.where(take, mb_loss, 0.0)
            # stage s → s+1 channel: one registry-selected lowering step
            h_next = step.step(h_out, axis, stage, n)
            return (h_next, loss_acc), None

        (h, loss_acc), _ = jax.lax.scan(tick, (h, loss_acc), jnp.arange(T))
        # every stage returns the (replicated) total loss
        loss = jax.lax.psum(loss_acc, axis) / M
        return loss

    return jax.shard_map(inner, mesh=mesh, in_specs=(P(axis), P(), P()),
                         out_specs=P(), check_vma=False)


def pipeline_train_step(stage_fn, loss_head, mesh: Mesh, axis: str = "pipe",
                        lowering: Optional[str] = None, lr: float = 1e-2,
                        *, plans=None, fifo: Optional[bool] = None):
    """SGD step on the pipelined loss (used by examples/tests)."""
    loss_fn = pipeline_loss_fn(stage_fn, loss_head, mesh, axis, lowering,
                               plans=plans, fifo=fifo)

    @jax.jit
    def step(params, xs, targets):
        loss, grads = jax.value_and_grad(loss_fn)(params, xs, targets)
        params = jax.tree.map(lambda p, g: p - lr * g, params, grads)
        return params, loss

    return step
