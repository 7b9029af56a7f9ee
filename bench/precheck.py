#!/usr/bin/env python3
"""Compile each cell's kernel for a described TPU v5e, with no chip.

    JAX_PLATFORMS=cpu python bench/precheck.py [cell ...]

For every cell of BENCHMARK.json (or those named), the program's kernel at
the cell's geometry (one chained call of ``t_call`` steps on the dataset's
N) is lowered and compiled by the TPU compiler for one chip of a described
``v5e:2x2``, and its ``memory_analysis()`` printed.  What the compiler
refuses here (tiling, VMEM) costs no chip time.  Run it before a chip run
and after changing a geometry.
"""
import json
import os
import pathlib
import sys
import time

BENCH = pathlib.Path(__file__).resolve().parent
sys.path[:0] = [str(BENCH), str(BENCH.parent / "src")]
os.environ.setdefault("TPU_LOG_DIR", "disabled")


def main(names) -> int:
    import jax
    import jax.numpy as jnp
    from jax.experimental import topologies
    from jax.sharding import SingleDeviceSharding

    import harness

    jax.config.update("jax_enable_compilation_cache", False)
    topo = topologies.get_topology_desc(platform="tpu",
                                        topology_name="v5e:2x2")
    one_chip = SingleDeviceSharding(topo.devices[0])
    spec = json.loads((BENCH.parent / "BENCHMARK.json").read_text())
    failed = 0
    for w in spec["workloads"]:
        if names and w["name"] not in names:
            continue
        cell = harness.load_cell(w["name"], spec)
        t0 = time.perf_counter()
        try:
            stencil = harness.compile_stencil(cell,
                                              harness.analysis_chain(cell))
            t_call, block = cell.traffic["t_call"], cell.traffic["block"]
            x = jax.ShapeDtypeStruct(cell.shape, jnp.float32,
                                     sharding=one_chip)
            compiled = jax.jit(lambda a: stencil(
                a, t_call, block, interpret=False)).lower(x).compile()
        except Exception as e:  # report every cell, then fail
            failed += 1
            print(f"{w['name']}: REFUSED {type(e).__name__}: {e}")
            continue
        mem = compiled.memory_analysis()
        print(f"{w['name']}: mode={stencil.mode} shape={cell.shape} "
              f"t_call={t_call} block={block} "
              f"compile_s={time.perf_counter() - t0:.2f} "
              f"custom_calls={compiled.as_text().count('tpu_custom_call')}")
        print(f"    {mem}")
    return 1 if failed else 0


if __name__ == "__main__":
    sys.exit(main(sys.argv[1:]))
