"""JAX collective primitives behind the ``"jax"`` lowering backend.

These are the raw transfers `repro.runtime.jax_backend` registers against
the lowering vocabulary (which lowering uses which primitive is the
registry's business, not encoded here):

* `fifo_shift` — one `lax.ppermute` hop to the next stage.  Cheap: a single
  neighbor link transfer, double-buffered by XLA.
* `reorder_buffer_read` — every stage's value is all-gathered and the
  consumer dynamically indexes what it needs.  This is the expensive
  transfer the paper's algorithm exists to avoid; it is implemented (and
  benchmarked) as the baseline.
"""
from __future__ import annotations

from typing import Sequence

import jax
import jax.numpy as jnp


def fifo_shift(x, axis: str, shift: int = 1, wrap: bool = False):
    """Send x to the next device along `axis` (FIFO neighbor stream)."""
    n = jax.lax.axis_size(axis)
    if wrap:
        perm = [(i, (i + shift) % n) for i in range(n)]
    else:
        perm = [(i, i + shift) for i in range(n - shift)]
    return jax.lax.ppermute(x, axis, perm)


def reorder_buffer_read(x, axis: str, index):
    """Out-of-order channel: publish everyone's value (all_gather), read an
    arbitrary producer's slot by dynamic index."""
    buf = jax.lax.all_gather(x, axis)            # (n, …) addressable buffer
    return jax.lax.dynamic_index_in_dim(buf, index, axis=0, keepdims=False)
