# One function per paper table. Print ``name,us_per_call,derived`` CSV.
#
#     python -m benchmarks.run            # full sweep (all tables)
#     python -m benchmarks.run --smoke    # CI subset: 3-kernel table2 rows
#                                         # via the Analysis driver + the
#                                         # pipeline planner (fast, no jax)
#     ... --smoke --validate              # + operational validation: replay
#                                         # every verdict on the runtime
#                                         # simulator, per-channel occupancy
from __future__ import annotations

import argparse
import sys


def _emit(name: str, us: float, derived: str = "") -> None:
    print(f"{name},{us:.1f},{derived}")
    sys.stdout.flush()


def validate_kernels(kernels) -> None:
    """`Analysis.validate()` per kernel (post-FIFOIZE): print the verdict /
    occupancy confirmation for every channel."""
    import time

    from repro.core import analyze
    from repro.core.polybench import get

    for kernel in kernels:
        sized = analyze(get(kernel)).classify().fifoize().size(pow2=True)
        t0 = time.perf_counter()          # time the replay alone, so the
        a = sized.validate()              # row is comparable to ci_smoke's
        dt = time.perf_counter() - t0     # validate/analysis ratio
        v = a.validation
        _emit(f"validate/{kernel}", dt * 1e6,
              f"{v.replays} replays {v.rejections} rejections ok")
        for row in v.channels:
            print(f"#   {row.name:36s} {row.verdict:22s} -> {row.lowering:22s}"
                  f" peak {row.peak:4d} <= {row.slots:4d} slots")


def backend_smoke() -> None:
    """One row per registry backend: a lazily-registered backend whose
    import is broken shows up here by NAME (`available_backends()`), not as
    a bare ModuleNotFoundError on first use three imports deep."""
    import time

    from repro.runtime.lowering import available_backends

    t0 = time.perf_counter()
    status = available_backends()
    dt = (time.perf_counter() - t0) / max(len(status), 1)
    for name, state in sorted(status.items()):
        _emit(f"backend/{name}", dt * 1e6, state)


def smoke(validate: bool = False) -> None:
    from . import pipeline_comm, table2_fifo

    print("name,us_per_call,derived")
    backend_smoke()
    for kernel in ("gemm", "jacobi-1d", "seidel-2d"):
        r = table2_fifo.run_kernel(kernel)
        _emit(f"table2/{r['kernel']}", r["seconds"] * 1e6,
              f"fifo {r['fifo_before']}/{r['channels_before']} -> "
              f"{r['fifo_after']}/{r['channels_after']}")
    if validate:
        validate_kernels(("gemm", "jacobi-1d", "seidel-2d"))
    pipeline_comm.main(_emit)


def main() -> None:
    from . import (moe_capacity, pipeline_comm, roofline_report,
                   table1_storage, table2_fifo)

    print("name,us_per_call,derived")
    table2_fifo.main(_emit)      # paper Table 2: FIFO recovery
    table1_storage.main(_emit)   # paper Table 1: storage impact
    pipeline_comm.main(_emit)    # the planner on pipeline/SP schedules
    moe_capacity.main(_emit)     # capacity-factor → drop-rate ablation
    roofline_report.main(_emit)  # §Roofline summary from the dry-run cache


if __name__ == '__main__':
    ap = argparse.ArgumentParser()
    ap.add_argument("--smoke", action="store_true",
                    help="fast CI subset exercising the public Analysis API")
    ap.add_argument("--validate", action="store_true",
                    help="replay every verdict on the runtime simulator and "
                         "print per-channel occupancy confirmation")
    args = ap.parse_args()
    if args.smoke:
        smoke(validate=args.validate)
    else:
        main()
        if args.validate:
            from repro.core.polybench import kernel_names
            validate_kernels(kernel_names())
