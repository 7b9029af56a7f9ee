"""Tests of the seidel-2d configuration, on the CPU.

    JAX_PLATFORMS=cpu python -m pytest -q bench/test_seidel.py

* ``FLOPS_PER_UPDATE`` against PolyBench's statement, counted by hand;
* the plain reference against PolyBench's loop nest;
* the control (the reference in bfloat16) fails the cell's limit;
* ``dependent_step_ns`` reads the program's own counter, and gives nothing
  where the program has none.
"""
import json
import os
import pathlib
import re
import sys

os.environ.setdefault("JAX_PLATFORMS", "cpu")

import jax.numpy as jnp  # noqa: E402
import numpy as np  # noqa: E402
import pytest  # noqa: E402

BENCH = pathlib.Path(__file__).resolve().parent
sys.path[:0] = [str(BENCH), str(BENCH.parent / "src")]

import harness  # noqa: E402

CONFIG = json.loads((BENCH / "configs" / "seidel-2d.json").read_text())
REF = harness._load_module(BENCH / "configs" / "seidel-2d.py")
CELL = "seidel2d-xl-ring"


def test_flops_per_update_counts_the_statement():
    """Eight additions and one division: the statement's arithmetic
    operators once its array subscripts (whose ``i-1`` are index
    arithmetic) are taken out."""
    body = re.sub(r"\[[^\]]*\]", "", CONFIG["statement"].split("=", 1)[1])
    ops = {op: body.count(op) for op in "+-*/"}
    assert ops == {"+": 8, "-": 0, "*": 0, "/": 1}
    assert REF.FLOPS_PER_UPDATE == sum(ops.values())


def _loop(a, steps):
    """PolyBench's loop nest in float64: i then j, in place, every cell of
    the array, zero outside it."""
    p = np.pad(a.astype(np.float64), 1)
    for _ in range(steps):
        for i in range(1, a.shape[0] + 1):
            for j in range(1, a.shape[1] + 1):
                p[i, j] = p[i - 1:i + 2, j - 1:j + 2].sum() / 9
    return p[1:-1, 1:-1]


@pytest.mark.parametrize("shape", [(7, 7), (10, 17)])
def test_reference_matches_the_loop(shape):
    """Within float32 rounding of terms of magnitude ≤ ~3: the scan
    regroups each row's recurrence, so the last bit is not expected."""
    a = np.random.default_rng(3).standard_normal(shape)
    got = jnp.asarray(a, jnp.float32)
    for _ in range(4):
        got = REF.step(got)
    np.testing.assert_allclose(np.asarray(got), _loop(a, 4), rtol=0,
                               atol=2e-6)


def test_control_fails_the_limit():
    """The reference in bfloat16 reads above the limit that the float32
    program stays under (`test_bench.py` runs the program against it)."""
    import calibrate
    cell = harness.load_cell(CELL)
    cell.config["datasets"][cell.traffic["dataset"]] = {"N": 16,
                                                        "TSTEPS": 64}
    cell.traffic.update(t_call=16, block=4)
    x = harness.seeded_input(5, cell.shape)
    assert calibrate.control_reading(harness, cell, x) \
        > cell.traffic["max_rel_err"]


def _record(kernel, runs=2, busy=0.5):
    cell = harness.load_cell(CELL)
    cell.config = dict(cell.config, kernel=kernel)
    summary = harness.trace.Summary(window_s=1.0, busy_s=busy, runs=runs,
                                    ops_in_runs=5.0 * runs,
                                    busy_in_runs_s=busy, top_ops=[],
                                    idle_gaps=[])
    return harness.Record(cell, {}, {}, summary)


def test_dependent_step_ns_reads_the_program_counter():
    from repro.runtime.pallas_codegen import STENCIL_PROGRAMS
    steps = STENCIL_PROGRAMS["seidel-2d"].dependent_steps((4000, 4000),
                                                          200, 8)
    assert steps == 525 * 200 * 8 * 13
    got = harness.read_metric("dependent_step_ns", _record("seidel-2d"))
    assert got == pytest.approx(0.5e9 / (2 * 5 * steps))


def test_dependent_step_ns_gives_nothing_without_a_counter():
    assert harness.read_metric("dependent_step_ns",
                               _record("no-such-kernel")) is None
    assert harness.read_metric("dependent_step_ns",
                               _record("seidel-2d", runs=0)) is None
