"""Plain float32 reference for heat-3d, and the work one update counts.

One step updates every cell of the N x N x N array at once, with zero
values outside it:

    a <- a + 0.125 (a[i-1] - 2a + a[i+1])
           + 0.125 (a[j-1] - 2a + a[j+1])
           + 0.125 (a[k-1] - 2a + a[k+1])

Operations per update, counted by hand from that line: each of the three
axes takes one multiplication (2a), one subtraction, one addition and one
multiplication by 0.125, which is 4; the three terms are then added to
``a`` by three additions.  So ``FLOPS_PER_UPDATE = 3 * 4 + 3 = 15``.
"""
import jax.numpy as jnp

FLOPS_PER_UPDATE = 15


def step(a):
    p = jnp.pad(a, 1)
    c = p[1:-1, 1:-1, 1:-1]
    return (c
            + 0.125 * (p[:-2, 1:-1, 1:-1] - 2.0 * c + p[2:, 1:-1, 1:-1])
            + 0.125 * (p[1:-1, :-2, 1:-1] - 2.0 * c + p[1:-1, 2:, 1:-1])
            + 0.125 * (p[1:-1, 1:-1, :-2] - 2.0 * c + p[1:-1, 1:-1, 2:]))
