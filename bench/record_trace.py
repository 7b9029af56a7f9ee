#!/usr/bin/env python3
"""Record the small chip trace that `test_bench.py` checks the reduction on.

    python bench/record_trace.py <out.xplane.pb>

On a TPU: jacobi-2d at N=256, two runs of two chained calls of 8 steps,
inside the benchmark's own spans (``window``, ``run``, ``call``, ``sync``),
traced by the JAX profiler.  Writes the trace, then prints each plane's
lines with their event counts and `trace.summarize` of it.
"""
import pathlib
import shutil
import sys
import tempfile

BENCH = pathlib.Path(__file__).resolve().parent
sys.path[:0] = [str(BENCH), str(BENCH.parent / "src")]


def main(out: str) -> int:
    import jax
    from jax.profiler import ProfileData, TraceAnnotation

    import harness
    trace = harness.trace

    harness.device_or_exit(1)
    cell = harness.load_cell("jacobi2d-xl-ring")
    cell.config["datasets"]["EXTRALARGE"] = {"N": 256, "TSTEPS": 16}
    cell.traffic["t_call"] = 8
    x = harness.seeded_input(0, cell.shape)
    call = harness.call_of(cell, harness.compile_stencil(
        cell, harness.analysis_chain(cell)))
    harness.whole_run(call, x, cell.n_calls)
    tmp = tempfile.mkdtemp(prefix="bench-trace-")
    try:
        jax.profiler.start_trace(tmp)
        with TraceAnnotation("window"):
            for _ in range(2):
                harness.whole_run(call, x, cell.n_calls)
        jax.profiler.stop_trace()
        shutil.copy(next(pathlib.Path(tmp).rglob("*.xplane.pb")), out)
    finally:
        shutil.rmtree(tmp, ignore_errors=True)
    profile = ProfileData.from_file(out)
    for plane in profile.planes:
        print(plane.name, [(line.name, len(list(line.events)))
                           for line in plane.lines])
        if trace._is_chip(plane.name):
            for line in plane.lines:
                for e in list(line.events)[:12]:
                    print(f"    {line.name!r} {e.name!r} {e.start_ns:.0f} "
                          f"{e.duration_ns:.0f}")
    for s in trace.host_spans(profile):
        print(f"  span {s.name} {s.start:.0f} {s.end:.0f}")
    print(trace.summarize(profile))
    return 0


if __name__ == "__main__":
    sys.exit(main(sys.argv[1]))
