#!/usr/bin/env python3
"""Run one cell of BENCHMARK.json once, on the chips of this machine.

    python bench/run.py --workload <name> --seed <n> --seconds <s> --trace <0|1>

Set-up (imports, inputs made on the device from the seed, the analysis, the
compile and one warm-up run) is timed as ``setup_s``; the loop of the cell's
traffic then runs for ``--seconds``.  The output of the window is compared
with the benchmark's own plain reference.  The last line of standard output
is one JSON object: ``correct``, ``attempted``, ``failed``, ``metrics``
(the end-to-end metrics, or with ``--trace 1`` the per-layer ones read from
a profiler trace of the window), ``device``, and last ``checks``: each
number compared, with its limit, which the last lines of standard error
repeat.  Without a TPU, or with fewer chips than the cell needs, it exits
non-zero and prints no result.

JAX's persistent compile cache is kept in the checkout's ``.cache/jax``,
the TPU runtime's logs in ``.cache/tpu_logs``.
"""
import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import pathlib  # noqa: E402
import sys  # noqa: E402

BENCH = pathlib.Path(__file__).resolve().parent
ROOT = BENCH.parent


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)
    if args.seed < 0:
        ap.error("--seed must be a whole number >= 0")

    cache = ROOT / ".cache"
    os.environ["JAX_COMPILATION_CACHE_DIR"] = str(cache / "jax")
    os.environ["JAX_PERSISTENT_CACHE_MIN_COMPILE_TIME_SECS"] = "0"
    os.environ["TPU_LOG_DIR"] = str(cache / "tpu_logs")
    (cache / "tpu_logs").mkdir(parents=True, exist_ok=True)
    sys.path[:0] = [str(BENCH), str(ROOT / "src")]

    import harness
    cell = harness.load_cell(args.workload)
    device = harness.device_or_exit(cell.chips)
    result = harness.run(cell, args.seed, args.seconds, bool(args.trace),
                         T_START, device)
    for name, c in result["checks"].items():
        print(f"check {name}: {c['value']} (limit {c['limit']})",
              file=sys.stderr)
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
