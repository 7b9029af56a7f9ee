"""Pallas codegen benchmark: generated VMEM-ring kernels vs the fallback.

    PYTHONPATH=src python -m benchmarks.bench_pallas [--repeats N] [--force-interpret]

Per stencil kernel (jacobi-1d, jacobi-2d, heat-3d), three measurements
over identical inputs:

* **fifo_ring** — `Analysis.compile(backend="pallas")` on the planned PPN:
  one fused kernel, every cross-block channel a VMEM scratch ring carried
  across the sequential grid (the paper's recovered-FIFO saving);
* **addressable** — the same compiler forced to ``mode="addressable"``:
  one kernel launch per time step, the whole level round-tripping through
  HBM each time (the reorder-buffer cost model a non-FIFO plan forces);
* **oracle** — the pure-jnp reference the outputs are checked against.

Every recorded row requires (a) fifo_ring/addressable outputs
allclose to the oracle, and (b) `Analysis.validate(backend="pallas")` green —
the same planned traces replayed through real VMEM rings, positive AND
negative directions.  The script REFUSES to write results otherwise.

Timings run on whatever backend jax reports; off-TPU the kernels execute in
Pallas interpret mode and the JSON labels them so (`execution_mode`) —
structural, not silicon, numbers, but the launch-per-step vs fused-ring gap
they measure is exactly the HBM-round-trip cost the mode restates.

Writes BENCH_pallas.json.
"""
from __future__ import annotations

import argparse
import json
import os
import platform
import time
from pathlib import Path
from typing import Dict, List, Optional

import jax
import jax.numpy as jnp
import numpy as np

import repro.core.polybench  # noqa: F401  (populate the kernel registry)
from repro.core.analysis import analyze
from repro.core.registry import get
from repro.runtime.pallas_codegen import default_interpret

BENCH_PATH = Path(__file__).resolve().parent.parent / "BENCH_pallas.json"

DESCRIPTION = (
    "Generated fused VMEM-ring kernels (Analysis.compile(backend='pallas') "
    "over the planned PPN) vs the addressable per-timestep HBM-round-trip "
    "fallback, outputs checked "
    "against the pure-jnp oracles and every plan replayed through "
    "Analysis.validate(backend='pallas') positively and negatively. "
    "execution_mode says whether timings are TPU silicon or Pallas "
    "interpret mode (off-TPU CI). "
    "Regenerate with: PYTHONPATH=src python -m benchmarks.bench_pallas")

#: kernel → (input shape, time steps, streamed-axis block).  Blocks fill
#: whole lanes (jacobi-1d) or sublane rows (jacobi-2d), as a TPU needs.
GEOMETRIES = {
    "jacobi-1d": ((4096,), 128, 128),
    "jacobi-2d": ((256, 64), 32, 32),
    "heat-3d": ((64, 16, 16), 16, 16),
}


def _time(fn, repeats: int) -> float:
    fn().block_until_ready()                     # compile + warm
    best = float("inf")
    for _ in range(repeats):
        t0 = time.perf_counter()
        fn().block_until_ready()
        best = min(best, time.perf_counter() - t0)
    return best


def run_kernel(name: str, repeats: int, interpret: Optional[bool]) -> dict:
    shape, steps, block = GEOMETRIES[name]
    a = analyze(get(name)).classify().fifoize().size().plan()
    ring = a.compile(backend="pallas", interpret=interpret)
    buf = a.compile(backend="pallas", mode="addressable", interpret=interpret)
    assert ring.mode == "fifo-ring", ring.describe()

    rng = np.random.default_rng(0)
    x = jnp.asarray(rng.standard_normal(shape), jnp.float32)
    want = ring.program.ref(x, steps)

    runs: Dict[str, Dict[str, float]] = {}
    errors: List[str] = []

    def record(label: str, fn) -> None:
        got = fn()
        err = float(jnp.max(jnp.abs(got - want)))
        ok = bool(jnp.allclose(got, want, rtol=1e-5, atol=1e-5))
        if not ok:
            errors.append(f"{label}: max|err|={err:.3e}")
        runs[label] = {"seconds": round(_time(fn, repeats), 6),
                       "max_abs_err": err, "allclose": ok}

    record("fifo_ring", lambda: ring(x, steps, block))
    record("addressable", lambda: buf(x, steps, block))

    # the acceptance gate: the same planned traces through real VMEM rings,
    # positive and negative directions
    v = a.validate(backend="pallas").validation
    if errors:
        raise SystemExit(f"{name}: output mismatch vs oracle — refusing to "
                         f"record ({errors})")

    speedup = runs["addressable"]["seconds"] / runs["fifo_ring"]["seconds"]
    row = {
        "kernel": name,
        "shape": list(shape), "steps": steps, "block": block,
        "mode": ring.mode,
        "plans": ring.diagnostics,
        "ring_slots": ring.ring_slots(steps),
        "runs": runs,
        "ring_vs_addressable_speedup": round(speedup, 2),
        "validate": {"backend": "pallas", "replays": v.replays,
                     "negative_rejections": v.rejections},
    }
    return row


def run(repeats: int, interpret: Optional[bool]) -> dict:
    mode = ("interpret" if (default_interpret() if interpret is None
                            else interpret) else "compiled")
    print(f"jax backend: {jax.default_backend()}  execution_mode: {mode}")
    rows = []
    for name in GEOMETRIES:
        row = run_kernel(name, repeats, interpret)
        rows.append(row)
        r = row["runs"]
        print(f"{name:10s} ring {r['fifo_ring']['seconds']*1e3:8.1f}ms "
              f"addressable {r['addressable']['seconds']*1e3:8.1f}ms "
              f"({row['ring_vs_addressable_speedup']:5.1f}x)  "
              f"validate {row['validate']['replays']} replays /"
              f" {row['validate']['negative_rejections']} rejections")
    return {
        "description": DESCRIPTION,
        "execution_mode": mode,
        "jax_backend": jax.default_backend(),
        "kernels": rows,
        "host": {"python": platform.python_version(),
                 "machine": platform.machine(),
                 "cpus": os.cpu_count(),
                 "jax": jax.__version__},
    }


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--repeats", type=int, default=5)
    ap.add_argument("--force-interpret", action="store_true",
                    help="run the Pallas interpreter even on a TPU host")
    args = ap.parse_args()
    if args.repeats < 1:
        ap.error("--repeats must be >= 1")
    doc = run(args.repeats, True if args.force_interpret else None)
    BENCH_PATH.write_text(json.dumps(doc, indent=1) + "\n")
    worst = min(r["ring_vs_addressable_speedup"] for r in doc["kernels"])
    print(f"wrote {BENCH_PATH.name} ({doc['execution_mode']} mode); "
          f"ring >= {worst}x vs addressable on every kernel")


if __name__ == "__main__":
    main()
