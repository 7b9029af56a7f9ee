"""Tests of the adi configuration, on the CPU.

    JAX_PLATFORMS=cpu python -m pytest -q bench/test_adi.py

* ``FLOPS_PER_UPDATE`` against PolyBench's statements (`adi.json`);
* the plain reference against PolyBench's loop nest;
* the control (the reference in bfloat16) fails the cell's limit, and so
  does the column sweep with its back substitution skipped (``v = q``);
  `test_bench.py` runs the program's comparison and its other planted
  faults (state unchanged, one answer altered, half the array left out)
  on every cell, this one included;
* the three sweep metrics read their kernels' ops from a trace summary,
  and give nothing without them.
"""
import ast
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

CONFIG = json.loads((BENCH / "configs" / "adi.json").read_text())
REF = harness._load_module(BENCH / "configs" / "adi.py")
CELL = "adi-xl-sweeps"
ARRAYS = {"u", "v", "p", "q"}
CPU = {"platform": "cpu", "kind": "cpu", "count": 1,
       "peaks": {"flops_per_s": 1e12, "hbm_bytes_per_s": 1e11}}


def _data_ops(statement: str) -> int:
    """Binary operations of a statement's right-hand side that touch an
    array; those on constants alone (``1.0 + 2.0*d``) are loop invariants
    that ``kernel_adi`` could hoist, and a unary minus is not counted."""
    rhs = statement.split(":")[-1].split("=", 1)[1]
    rhs = re.sub(r"SCALAR_VAL\(([^)]*)\)", r"\1", rhs)
    rhs = re.sub(r"\[[^\]]*\]", "", rhs)

    def arrays(node):
        return any(isinstance(n, ast.Name) and n.id in ARRAYS
                   for n in ast.walk(node))

    return sum(isinstance(n, ast.BinOp) and arrays(n)
               for n in ast.walk(ast.parse(rhs.strip(), mode="eval")))


def test_flops_per_update_counts_the_statements():
    """Per sweep ``p`` 3, ``q`` 10, ``v`` (or ``u``) 2, and the boundary
    statements none: 15 a sweep, 30 a step."""
    counts = {sweep: [_data_ops(s) for s in body]
              for sweep, body in CONFIG["statements"].items()}
    assert list(counts.values()) == [[0, 0, 0, 3, 10, 0, 2]] * 2
    assert REF.FLOPS_PER_UPDATE == sum(map(sum, counts.values())) == 30


def _loop(u0, steps):
    """``kernel_adi`` in float64, statement by statement."""
    n = u0.shape[0]
    a, b, c, d, e, f = (float(k) for k in REF.coefficients(n))
    u = u0.astype(np.float64)
    v, p, q = (np.zeros((n, n)) for _ in range(3))
    for _ in range(steps):
        for i in range(1, n - 1):
            v[0][i], p[i][0] = 1.0, 0.0
            q[i][0] = v[0][i]
            for j in range(1, n - 1):
                p[i][j] = -c / (a * p[i][j - 1] + b)
                q[i][j] = (-d * u[j][i - 1] + (1.0 + 2.0 * d) * u[j][i]
                           - f * u[j][i + 1] - a * q[i][j - 1]) \
                    / (a * p[i][j - 1] + b)
            v[n - 1][i] = 1.0
            for j in range(n - 2, 0, -1):
                v[j][i] = p[i][j] * v[j + 1][i] + q[i][j]
        for i in range(1, n - 1):
            u[i][0], p[i][0] = 1.0, 0.0
            q[i][0] = u[i][0]
            for j in range(1, n - 1):
                p[i][j] = -f / (d * p[i][j - 1] + e)
                q[i][j] = (-a * v[i - 1][j] + (1.0 + 2.0 * a) * v[i][j]
                           - c * v[i + 1][j] - d * q[i][j - 1]) \
                    / (d * p[i][j - 1] + e)
            u[i][n - 1] = 1.0
            for j in range(n - 2, 0, -1):
                u[i][j] = p[i][j] * u[i][j + 1] + q[i][j]
    return u


@pytest.mark.parametrize("shape,steps", [((6, 6), 3), ((10, 10), 4)])
def test_reference_matches_the_loop(shape, steps):
    """Within float32 rounding: the right-hand sides are differences of
    terms 2N to 4N times the cells, so the gap is a few 1e-7 of the
    array's magnitude, not its last bit."""
    u0 = np.random.default_rng(3).standard_normal(shape)
    got = jnp.asarray(u0, jnp.float32)
    for _ in range(steps):
        got = REF.step(got)
    want = _loop(u0, steps)
    assert np.abs(np.asarray(got) - want).max() < 2e-6 * np.abs(want).max()


def test_constants_at_extralarge():
    a, b, c, d, e, f = REF.coefficients(2000)
    assert (a, b, c, d, e, f) == pytest.approx(
        (-4000, 8001, -4000, -2000, 4001, -2000), rel=1e-6)


def tiny(n=16, steps=8, t_call=4):
    cell = harness.load_cell(CELL)
    cell.config["datasets"][cell.traffic["dataset"]] = {"N": n,
                                                        "TSTEPS": steps}
    cell.traffic.update(t_call=t_call, block=4)
    return cell


def test_control_fails_the_limit():
    """The reference in bfloat16 reads far above the limit that the float32
    program stays under (0.065 here; it grows with N)."""
    import calibrate
    cell = tiny(steps=64, t_call=16)
    x = harness.seeded_input(5, cell.shape)
    assert calibrate.control_reading(harness, cell, x) \
        > cell.traffic["max_rel_err"]


def test_skipped_back_substitution_is_not_correct(monkeypatch):
    """The column sweep hands on its forward elimination's ``q`` as ``v``,
    the back substitution left out."""
    from repro.runtime import pallas_codegen
    orig = pallas_codegen._adi_column_kernel

    def without_back_substitution(den_ref, p_ref, u_ref, v_ref, q_ref, **kw):
        orig(den_ref, p_ref, u_ref, v_ref, q_ref, **kw)
        v_ref[...] = q_ref[...]

    monkeypatch.setattr(pallas_codegen, "_adi_column_kernel",
                        without_back_substitution)
    r = harness.run(tiny(), 3, 0.2, False, 0.0, CPU)
    assert not r["correct"] and r["failed"] >= 1


# ---------------------------------------------------------------- metrics --

def _record(top_ops, runs=2, busy=0.5):
    summary = harness.trace.Summary(window_s=1.0, busy_s=busy, runs=runs,
                                    ops_in_runs=4.0 * runs,
                                    busy_in_runs_s=busy, top_ops=top_ops,
                                    idle_gaps=[])
    return harness.Record(harness.load_cell(CELL), CPU, {}, summary)


SWEEPS = [("%adi_column_sweep.3 custom-call tpu_custom_call", 0.2),
          ("%adi_row_sweep.4 custom-call tpu_custom_call", 0.25),
          ("%copy.1 copy", 0.05)]


def test_column_sweep_share_reads_the_kernel():
    got = harness.read_metric("column_sweep_share", _record(SWEEPS))
    assert got == pytest.approx(100 * 0.2 / 0.5)


@pytest.mark.parametrize("name,busy", [("column_sweep_roofline", 0.2),
                                       ("row_sweep_roofline", 0.25)])
def test_sweep_roofline_reads_its_kernel(name, busy):
    """Per sweep 15·N² operations and 2·4·N² bytes: at the CPU table's
    peaks (1e12 FLOP/s, 1e11 B/s) the bytes bind, 3.2e-4 s at N = 2000,
    times 2 runs of 1000 sweeps, over the kernel's busy time."""
    got = harness.read_metric(name, _record(SWEEPS))
    assert got == pytest.approx(100 * 8 * 2000 ** 2 / 1e11 * 2000 / busy)


@pytest.mark.parametrize("name", ["column_sweep_share",
                                  "column_sweep_roofline",
                                  "row_sweep_roofline"])
def test_sweep_metrics_give_nothing_without_their_ops(name):
    other = [("%seidel_2d_ring.1 custom-call tpu_custom_call", 0.5)]
    assert harness.read_metric(name, _record(other)) is None
    assert harness.read_metric(name, _record(SWEEPS, runs=0)) is None
    record = _record(SWEEPS)
    record.trace = None
    assert harness.read_metric(name, record) is None
