"""Plain float32 reference for seidel-2d, and the work one update counts.

One step sweeps the N x N array in place, rows in order and, within a row,
j in order, with zero values outside it:

    a[i, j] <- (a[i-1, j-1] + a[i-1, j] + a[i-1, j+1]
                + a[i, j-1] + a[i, j] + a[i, j+1]
                + a[i+1, j-1] + a[i+1, j] + a[i+1, j+1]) / 9

Row i-1 and a[i, j-1] already hold this sweep's values.  So each row is a
first-order recurrence along j, x_j = (c_j + x_{j-1}) / 9 with c the other
eight terms, which this reference computes, row after row, as an
associative scan of the affine maps x -> x/9 + c_j/9.

Operations per update, counted by hand from PolyBench's statement: eight
additions and one division, so ``FLOPS_PER_UPDATE = 9``.  The scan's own
operations are this implementation's, not the problem's.
"""
import jax
import jax.numpy as jnp

FLOPS_PER_UPDATE = 9


def _compose(f, g):
    """The affine map g after f, each a pair (slope, offset)."""
    return f[0] * g[0], g[0] * f[1] + g[1]


def _row(prev, old):
    """Row i from the new row i-1 (``prev``) and the old rows i and i+1."""
    up, mid, down = jnp.pad(prev, 1), jnp.pad(old[0], 1), jnp.pad(old[1], 1)
    c = (up[:-2] + up[1:-1] + up[2:] + mid[1:-1] + mid[2:]
         + down[:-2] + down[1:-1] + down[2:])
    _, x = jax.lax.associative_scan(_compose,
                                    (jnp.full_like(c, 1.0 / 9.0), c / 9.0))
    return x, x


def step(a):
    with jax.default_matmul_precision("highest"):
        below = jnp.concatenate([a[1:], jnp.zeros_like(a[:1])])
        _, rows = jax.lax.scan(_row, jnp.zeros_like(a[0]),
                               jnp.stack([a, below], axis=1))
        return rows
