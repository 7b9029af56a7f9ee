"""Pure-jnp oracles for the band stencils the pallas backend generates.

Every cell updates every step, with zero (Dirichlet) values outside the
array.  The update formulas mirror `runtime.pallas_codegen.STENCIL_PROGRAMS`
exactly — the parity tests compare the generated fused VMEM-ring kernels
against these, so the two must stay in lockstep.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp


def _iterate(step, a0: jnp.ndarray, steps: int) -> jnp.ndarray:
    a = jax.lax.fori_loop(0, steps, lambda _, a: step(a),
                          a0.astype(jnp.float32))
    return a.astype(a0.dtype)


def jacobi_1d(a0: jnp.ndarray, steps: int) -> jnp.ndarray:
    """T steps of the 3-point average a[i] ← (a[i−1] + a[i] + a[i+1]) / 3 —
    the paper's motivating kernel (Fig. 1)."""
    def step(a):
        p = jnp.pad(a, 1)
        return (p[:-2] + p[1:-1] + p[2:]) / 3.0
    return _iterate(step, a0, steps)


def jacobi_2d(a0: jnp.ndarray, steps: int) -> jnp.ndarray:
    """T steps of the 5-point average
    a[i,j] ← (a[i,j] + a[i,j−1] + a[i,j+1] + a[i−1,j] + a[i+1,j]) / 5."""
    def step(a):
        p = jnp.pad(a, 1)
        return (p[1:-1, 1:-1] + p[1:-1, :-2] + p[1:-1, 2:]
                + p[:-2, 1:-1] + p[2:, 1:-1]) / 5.0
    return _iterate(step, a0, steps)


def heat_3d(a0: jnp.ndarray, steps: int) -> jnp.ndarray:
    """T steps of the 7-point heat update
    a ← a + 0.125·(∂²ᵢ + ∂²ⱼ + ∂²ₖ), each ∂² the central second difference."""
    def step(a):
        p = jnp.pad(a, 1)
        c = p[1:-1, 1:-1, 1:-1]
        return (c
                + 0.125 * (p[:-2, 1:-1, 1:-1] - 2.0 * c + p[2:, 1:-1, 1:-1])
                + 0.125 * (p[1:-1, :-2, 1:-1] - 2.0 * c + p[1:-1, 2:, 1:-1])
                + 0.125 * (p[1:-1, 1:-1, :-2] - 2.0 * c + p[1:-1, 1:-1, 2:]))
    return _iterate(step, a0, steps)


def seidel_2d(a0: jnp.ndarray, steps: int) -> jnp.ndarray:
    """T in-place sweeps of the 9-point Gauss-Seidel average, rows in order
    (i then j, as PolyBench's loop nest):
    a[i,j] ← (a[i−1,j−1] + a[i−1,j] + a[i−1,j+1] + a[i,j−1] + a[i,j]
              + a[i,j+1] + a[i+1,j−1] + a[i+1,j] + a[i+1,j+1]) / 9,
    where row i−1 and a[i,j−1] already hold this sweep's values.  Each row
    is a first-order recurrence along j, x_j = (c_j + x_{j−1}) / 9 with c
    the other eight terms, computed as an associative scan of the affine
    maps x ↦ x/9 + c_j/9."""
    def compose(f, g):                    # g applied after f
        return f[0] * g[0], g[0] * f[1] + g[1]

    def row(prev, old):                   # prev: new row i−1; old: rows i, i+1
        up, mid, down = jnp.pad(prev, 1), jnp.pad(old[0], 1), \
            jnp.pad(old[1], 1)
        c = (up[:-2] + up[1:-1] + up[2:] + mid[1:-1] + mid[2:]
             + down[:-2] + down[1:-1] + down[2:])
        _, x = jax.lax.associative_scan(
            compose, (jnp.full_like(c, 1.0 / 9.0), c / 9.0))
        return x, x

    def step(a):
        below = jnp.concatenate([a[1:], jnp.zeros_like(a[:1])])
        _, rows = jax.lax.scan(row, jnp.zeros_like(a[0]),
                               jnp.stack([a, below], axis=1))
        return rows
    with jax.default_matmul_precision("highest"):
        return _iterate(step, a0, steps)
