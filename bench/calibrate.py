#!/usr/bin/env python3
"""Readings that the limit of ``max_rel_err`` is set from, on the chip.

    python bench/calibrate.py <cell> --program 12 --control 3 [--first-seed S]

In one process, at the cell's own size: the program's compiled stencil
runs the whole problem on each of ``--program`` seeds, and every chained
call is compared with the plain float32 reference (as a run does); then
the control, the reference computed in bfloat16 (the precision below the
configuration's float32), is compared with the float32 reference on
``--control`` seeds.  One JSON line per reading; the limit lies between
the program's largest reading and the control's smallest.
"""
import argparse
import json
import pathlib
import sys

BENCH = pathlib.Path(__file__).resolve().parent
sys.path[:0] = [str(BENCH), str(BENCH.parent / "src")]


def control_reading(harness, cell, x) -> float:
    import jax.numpy as jnp
    return max(harness.rel_gap(low, ref) for low, ref in zip(
        harness.reference_outputs(cell, x, jnp.bfloat16),
        harness.reference_outputs(cell, x), strict=True))


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("cell")
    ap.add_argument("--program", type=int, default=12)
    ap.add_argument("--control", type=int, default=3)
    ap.add_argument("--first-seed", type=int, default=1000)
    args = ap.parse_args()

    import harness
    cell = harness.load_cell(args.cell)
    harness.device_or_exit(cell.chips)
    stencil = harness.compile_stencil(cell, harness.analysis_chain(cell))
    call = harness.call_of(cell, stencil)
    seeds = range(args.first_seed, args.first_seed + args.program)
    for seed in seeds:
        x = harness.seeded_input(seed, cell.shape)
        outs = harness.whole_run(call, x, cell.n_calls)
        print(json.dumps({"cell": cell.name, "kind": "program", "seed": seed,
                          "mode": stencil.mode,
                          "max_rel_err": harness.max_rel_err(cell, x, outs)}),
              flush=True)
    for seed in seeds[:args.control]:
        x = harness.seeded_input(seed, cell.shape)
        print(json.dumps({"cell": cell.name, "kind": "control:bfloat16",
                          "seed": seed,
                          "max_rel_err": control_reading(harness, cell, x)}),
              flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
