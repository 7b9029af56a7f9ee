"""Pure-jnp oracles for the stencils the pallas backend generates.

In the band stencils every cell updates every step, with zero (Dirichlet)
values outside the array; adi keeps PolyBench's own boundaries (`adi`).
The update formulas mirror `runtime.pallas_codegen.STENCIL_PROGRAMS`
exactly — the parity tests compare the generated kernels against these,
so the two must stay in lockstep.
"""
from __future__ import annotations

from typing import Tuple

import jax
import jax.numpy as jnp
import numpy as np


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


def adi_coefficients(n: int) -> Tuple[float, ...]:
    """PolyBench 4.2.1 adi's ``(a, b, c, d, e, f)`` for an ``n × n`` grid,
    computed in float32 as its ``kernel_adi`` does: ``DX = DY = 1/N``,
    ``DT = 1/TSTEPS``, ``B1 = 2``, ``B2 = 1``, ``mul1 = B1·DT/DX²``,
    ``mul2 = B2·DT/DY²``, ``a = c = −mul1/2``, ``b = 1 + mul1``, ``d = f =
    −mul2/2``, ``e = 1 + mul2``.  A step sees only the array, so TSTEPS is
    taken as ``N // 2``, PolyBench's own at MEDIUM, LARGE and EXTRALARGE
    (N = 2000: a = −4000, b = 8001, d = −2000, e = 4001)."""
    f32 = np.float32
    dx = dy = f32(1.0) / f32(n)
    dt = f32(1.0) / f32(n // 2)
    mul1 = f32(2.0) * dt / (dx * dx)
    mul2 = f32(1.0) * dt / (dy * dy)
    a = -mul1 / f32(2.0)
    d = -mul2 / f32(2.0)
    return tuple(float(x) for x in (a, f32(1.0) + mul1, a,
                                    d, f32(1.0) + mul2, d))


def _line_solve(rhs: jnp.ndarray, lo: float, diag: float,
                up: float) -> jnp.ndarray:
    """The tridiagonal solve ``lo·x[j−1] + diag·x[j] + up·x[j+1] =
    rhs[j−1]`` for ``0 < j < m − 1`` along axis 0, with ``x[0] = x[m−1] =
    1``, every column at once, as PolyBench writes it: a forward
    elimination ``p[j] = −up / (lo·p[j−1] + diag)``, ``q[j] = (rhs − lo·
    q[j−1]) / (lo·p[j−1] + diag)`` from ``p[0] = 0``, ``q[0] = 1``, then a
    back substitution ``x[j] = p[j]·x[j+1] + q[j]``; both sequential."""
    ones = jnp.ones_like(rhs[0])

    def forward(carry, r):
        p, q = carry
        den = lo * p + diag
        p, q = -up / den, (r - lo * q) / den
        return (p, q), (p, q)

    def back(x, pq):
        x = pq[0] * x + pq[1]
        return x, x

    _, pq = jax.lax.scan(forward, (jnp.zeros_like(ones), ones), rhs)
    _, x = jax.lax.scan(back, ones, pq, reverse=True)
    return jnp.concatenate([ones[None], x, ones[None]])


def adi(u0: jnp.ndarray, steps: int) -> jnp.ndarray:
    """T steps of PolyBench 4.2.1 adi on the ``n × n`` array ``u``: a
    column sweep (for each column ``i`` in ``1 … n−2``, the solve down the
    rows with coefficients ``a, b, c`` and right-hand side ``−d·u[j][i−1]
    + (1+2d)·u[j][i] − f·u[j][i+1]``, into ``v``) and a row sweep (for each
    row, the solve along it with ``d, e, f`` and ``−a·v[i−1][j] +
    (1+2a)·v[i][j] − c·v[i+1][j]``, into ``u``).  Rows 0 and ``n−1`` of
    ``u`` are never written; columns 0 and ``n−1`` of the rows between
    become 1 (`adi_coefficients` gives the constants)."""
    a, b, c, d, e, f = adi_coefficients(u0.shape[0])
    kd = float(np.float32(1.0) + np.float32(2.0) * np.float32(d))
    ka = float(np.float32(1.0) + np.float32(2.0) * np.float32(a))

    def step(u):
        v = _line_solve(-d * u[1:-1, :-2] + kd * u[1:-1, 1:-1]
                        - f * u[1:-1, 2:], a, b, c)
        rows = _line_solve((-a * v[:-2] + ka * v[1:-1] - c * v[2:]).T,
                           d, e, f).T
        return u.at[1:-1].set(rows)
    with jax.default_matmul_precision("highest"):
        return _iterate(step, u0, steps)
