"""Plain float32 reference for jacobi-2d, and the work one update counts.

One step updates every cell of the N x N array at once, with zero values
outside it:

    a[i, j] <- (a[i, j] + a[i, j-1] + a[i, j+1] + a[i-1, j] + a[i+1, j]) / 5

Operations per update, counted by hand from that line: four additions and
one division, so ``FLOPS_PER_UPDATE = 5``.  PolyBench writes the same
update as ``0.2 * (...)``: four additions and one multiplication, also 5.
"""
import jax.numpy as jnp

FLOPS_PER_UPDATE = 5


def step(a):
    p = jnp.pad(a, 1)
    return (p[1:-1, 1:-1] + p[1:-1, :-2] + p[1:-1, 2:]
            + p[:-2, 1:-1] + p[2:, 1:-1]) / 5.0
