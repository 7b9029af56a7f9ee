"""Fig. 3 counterpart: the FIFO-streamed jacobi-1d kernel generated from the
planned PPN — correctness vs the oracle, wall time, and the HBM-traffic
model that is the kernel's roofline claim (T·2N → 2N bytes).

Off-TPU the kernel runs in Pallas interpret mode (never skipped silently);
every row is tagged with the mode that actually ran.  The rows come from
`Analysis.compile(backend="pallas")` — the codegen path
`BENCH_pallas.json` benchmarks in full.
"""
from __future__ import annotations

import time

import jax.numpy as jnp
import numpy as np

from repro.kernels.stencil_bands import jacobi_1d


def hbm_traffic_model(n: int, steps: int, dtype_bytes: int = 4) -> dict:
    """Bytes moved to/from HBM (the roofline 'memory' term numerator):
    the addressable pattern reads and writes the array every timestep; the
    FIFO ring reads and writes it once — cross-tile dependences live in the
    VMEM ring (the paper's channel split, (T+1)·2 slots per depth)."""
    naive = steps * 2 * n * dtype_bytes
    fifo = 2 * n * dtype_bytes
    return {"naive_bytes": float(naive), "fifo_bytes": float(fifo),
            "reduction": naive / fifo}


def _timed(fn):
    t0 = time.perf_counter()
    out = fn()
    out.block_until_ready()
    return out, time.perf_counter() - t0


def main(emit) -> None:
    from repro.runtime.pallas_codegen import default_interpret

    mode = "interpret" if default_interpret() else "tpu"

    import repro.core.polybench  # noqa: F401  (populate the registry)
    from repro.core.analysis import analyze
    from repro.core.registry import get

    gen = (analyze(get("jacobi-1d")).classify().fifoize().size().plan()
           .compile(backend="pallas"))

    rng = np.random.default_rng(0)
    for n, bn in ((1024, 128), (4096, 256)):
        x = jnp.asarray(rng.normal(size=(n,)), jnp.float32)
        want = jacobi_1d(x, bn)
        m = hbm_traffic_model(n, bn)
        got, dt = _timed(lambda: gen(x, bn, bn))
        err = float(jnp.max(jnp.abs(got - want)))
        emit(f"fig3/generated_n{n}_T{bn}", dt * 1e6,
             f"mode={mode} err={err:.1e} traffic {m['naive_bytes']:.2e}B -> "
             f"{m['fifo_bytes']:.2e}B ({m['reduction']:.0f}x)")
