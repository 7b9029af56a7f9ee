"""Plain float32 reference for adi, and the work one update counts.

PolyBench 4.2.1 ``kernel_adi``: one step is a column sweep and a row sweep
of tridiagonal line solves.  The column sweep solves, for each column ``i``
in ``1 .. N-2``, down the rows:

    p[j] = -c / (a*p[j-1] + b)
    q[j] = (-d*u[j][i-1] + (1+2d)*u[j][i] - f*u[j][i+1] - a*q[j-1])
           / (a*p[j-1] + b)
    v[j][i] = p[j]*v[j+1][i] + q[j]         (j from N-2 down to 1)

from ``p[0] = 0``, ``q[0] = v[0][i] = 1`` and ``v[N-1][i] = 1``; the row
sweep solves along each row of ``v`` the same way, with ``d, e, f`` and
``-a*v[i-1][j] + (1+2a)*v[i][j] - c*v[i+1][j]``, into ``u`` (``u[i][0] =
u[i][N-1] = 1``).  Both recurrences run as sequential scans in PolyBench's
order.  Rows 0 and N-1 of ``u`` are never written.

The constants are PolyBench's, computed in float32 as ``kernel_adi`` does,
with ``TSTEPS = N/2`` (PolyBench's own from MEDIUM up): at N = 2000,
``a = c = -4000``, ``b = 8001``, ``d = f = -2000``, ``e = 4001``.

Operations per update, counted by hand from the statements (`adi.json`),
leaving out those on constants alone (``-c``, ``-d``, ``1 + 2d``), which
``kernel_adi`` could hoist: per sweep ``p`` 3 (a multiplication, an
addition, a division), ``q`` 10, ``v`` 2; so ``FLOPS_PER_UPDATE = 30``.
"""
import jax
import jax.numpy as jnp
import numpy as np

FLOPS_PER_UPDATE = 30


def coefficients(n):
    f32 = np.float32
    dx = dy = f32(1.0) / f32(n)
    dt = f32(1.0) / f32(n // 2)
    mul1 = f32(2.0) * dt / (dx * dx)
    mul2 = f32(1.0) * dt / (dy * dy)
    a, d = -mul1 / f32(2.0), -mul2 / f32(2.0)
    return a, f32(1.0) + mul1, a, d, f32(1.0) + mul2, d


def _solve(rhs, lo, diag, up):
    """Along axis 0: x[0] = x[m-1] = 1 and, between them, the forward
    elimination then the back substitution over ``rhs`` (m - 2 rows)."""
    ones = jnp.ones_like(rhs[0])

    def forward(pq, r):
        den = lo * pq[0] + diag
        pq = (-up / den, (r - lo * pq[1]) / den)
        return pq, pq

    def back(x, pq):
        x = pq[0] * x + pq[1]
        return x, x

    _, pq = jax.lax.scan(forward, (jnp.zeros_like(ones), ones), rhs)
    _, x = jax.lax.scan(back, ones, pq, reverse=True)
    return jnp.concatenate([ones[None], x, ones[None]])


def step(u):
    a, b, c, d, e, f = (float(k) for k in coefficients(u.shape[0]))
    kd = float(np.float32(1.0) + np.float32(2.0) * np.float32(d))
    ka = float(np.float32(1.0) + np.float32(2.0) * np.float32(a))
    with jax.default_matmul_precision("highest"):
        v = _solve(-d * u[1:-1, :-2] + kd * u[1:-1, 1:-1] - f * u[1:-1, 2:],
                   a, b, c)
        rows = _solve((-a * v[:-2] + ka * v[1:-1] - c * v[2:]).T, d, e, f).T
        return u.at[1:-1].set(rows)
