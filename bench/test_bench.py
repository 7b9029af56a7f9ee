"""Tests of the benchmark itself, on the CPU (Pallas kernels interpreted).

    JAX_PLATFORMS=cpu python -m pytest -q bench/test_bench.py

* the operation and byte counts against the references' own arithmetic;
* the trace reduction, by hand on intervals and on a trace recorded on a
  TPU v5e (``fixtures/trace_small.xplane.pb``, made by `record_trace.py`);
* the comparison that decides ``correct``: the program passes it, the
  control (the reference in bfloat16) fails it, and so does every fault
  planted under the timed path;
* the command refuses to run without a TPU, or outside a checkout.
"""
import json
import math
import os
import pathlib
import shutil
import subprocess
import sys

os.environ.setdefault("JAX_PLATFORMS", "cpu")

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402
import pytest  # noqa: E402

BENCH = pathlib.Path(__file__).resolve().parent
ROOT = BENCH.parent
sys.path[:0] = [str(BENCH), str(ROOT / "src")]

import harness  # noqa: E402

trace = harness.trace

SPEC = json.loads((ROOT / "BENCHMARK.json").read_text())
CELLS = [w["name"] for w in SPEC["workloads"]]
CPU = {"platform": "cpu", "kind": "cpu", "count": 1,
       "peaks": {"flops_per_s": 1e12, "hbm_bytes_per_s": 1e11}}


def tiny(name: str, n: int = 16, steps: int = 8, t_call: int = 4):
    """The cell ``name`` at a size the interpreter runs in seconds."""
    cell = harness.load_cell(name, SPEC)
    cell.config["datasets"][cell.traffic["dataset"]] = {"N": n,
                                                        "TSTEPS": steps}
    cell.traffic["t_call"] = t_call
    if cell.traffic["block"]:
        cell.traffic["block"] = 4
    return cell


# ---------------------------------------------------------------- counts --

ARITH = {"add", "sub", "mul", "div"}


@pytest.mark.parametrize("config", ["jacobi-2d", "heat-3d"])
def test_flops_per_update_counts_the_reference(config):
    """Every elementwise add, sub, mul and div over the whole array in the
    reference's jaxpr is one operation per update."""
    cfg = json.loads((BENCH / "configs" / f"{config}.json").read_text())
    ref = harness._load_module(BENCH / "configs" / f"{config}.py")
    shape = (6,) * cfg["rank"]
    jaxpr = jax.make_jaxpr(ref.step)(jnp.zeros(shape, jnp.float32))
    ops = sum(math.prod(e.outvars[0].aval.shape) for e in jaxpr.eqns
              if e.primitive.name in ARITH)
    assert ops == ref.FLOPS_PER_UPDATE * math.prod(shape)


def test_work_per_run():
    cell = harness.load_cell("jacobi2d-xl-ring", SPEC)
    assert cell.shape == (2800, 2800) and cell.n_calls == 5
    assert cell.updates_per_run == 2800 ** 2 * 1000
    assert cell.flops_per_run == 5 * 2800 ** 2 * 1000
    assert cell.bytes_per_run == 2 * 4 * 2800 ** 2


@pytest.mark.parametrize("config", ["jacobi-2d", "heat-3d"])
def test_reference_matches_a_loop(config):
    """The jnp reference step against a plain Python loop over cells."""
    import numpy as np
    cfg = json.loads((BENCH / "configs" / f"{config}.json").read_text())
    ref = harness._load_module(BENCH / "configs" / f"{config}.py")
    a = np.random.default_rng(0).standard_normal((5,) * cfg["rank"])
    p = np.pad(a, 1)
    want = np.zeros_like(a)
    for idx in np.ndindex(a.shape):
        c = tuple(i + 1 for i in idx)
        nbrs = [(p[c[:d] + (c[d] - 1,) + c[d + 1:]],
                 p[c[:d] + (c[d] + 1,) + c[d + 1:]]) for d in range(a.ndim)]
        if config == "jacobi-2d":
            want[idx] = (p[c] + sum(lo + hi for lo, hi in nbrs)) / 5
        else:
            want[idx] = p[c] + sum(0.125 * (lo - 2 * p[c] + hi)
                                   for lo, hi in nbrs)
    got = np.asarray(ref.step(jnp.asarray(a, jnp.float32)))
    np.testing.assert_allclose(got, want, rtol=1e-5, atol=1e-6)


# ----------------------------------------------------------------- trace --

def test_interval_arithmetic_by_hand():
    merged = trace.union([(5, 7), (0, 2), (1, 3), (6, 9)])
    assert merged == [(0, 3), (5, 9)]
    assert trace.covered(merged, 2, 6) == 2
    assert trace.gaps(merged, -1, 10) == [(-1, 0), (3, 5), (9, 10)]
    assert trace.overlap(merged, [(2, 5.5), (8, 20)]) == 1 + 0.5 + 1


FIXTURE = BENCH / "fixtures" / "trace_small.xplane.pb"


def test_reduction_of_a_chip_trace():
    """The v5e trace of two runs of two chained calls of one kernel each
    (`record_trace.py`): four ops, two in each run, busy for exactly their
    summed durations, the rest of the window idle."""
    from jax.profiler import ProfileData
    profile = ProfileData.from_file(str(FIXTURE))
    spans = trace.host_spans(profile)
    (window,) = [x for x in spans if x.name == "window"]
    (ops,) = trace.device_ops(profile).values()
    assert len(ops) == 4 and [x.name for x in spans].count("call") == 4
    assert ops[0].name == "%_lambda_.1 custom-call tpu_custom_call"
    s = trace.summarize(profile)
    assert s.runs == 2 and s.ops_in_runs == 4
    assert s.window_s == pytest.approx((window.end - window.start) * 1e-9)
    busy = sum(o.end - o.start for o in ops) * 1e-9
    assert s.busy_s == pytest.approx(busy) and s.busy_in_runs_s == s.busy_s
    assert s.top_ops == [(ops[0].name, pytest.approx(busy))]
    assert sum(g for _, g in s.idle_gaps) == pytest.approx(s.window_s - busy)
    assert {name for name, _ in s.idle_gaps} <= set(trace.SPANS)
    aligned = trace.align(ops, spans)
    runs = [x for x in spans if x.name == "run"]
    for op, run in zip(aligned, [runs[0], runs[0], runs[1], runs[1]]):
        assert run.start <= op.start < op.end <= run.end


# --------------------------------------------------------------- correct --

@pytest.mark.parametrize("traced", [False, True])
@pytest.mark.parametrize("name", CELLS)
def test_program_passes_its_comparison(name, traced):
    """On the CPU a traced run finds no chip in its trace, so only the
    host-clock metrics are read."""
    r = harness.run(tiny(name), 3, 0.2, traced, 0.0, CPU)
    assert r["correct"], r["checks"]
    assert list(r)[-1] == "checks"
    entries = SPEC["per_layer"] if traced else SPEC["end_to_end"]
    want = {m["name"] for m in entries if name in m.get("workloads", [name])
            and not (traced and m["source"] == "device_trace")}
    assert set(r["metrics"]) == want


@pytest.mark.parametrize("name", ["jacobi2d-xl-ring", "heat3d-xl-ring"])
def test_control_fails_the_limit(name):
    """The reference in bfloat16, in the program's place, reads above the
    limit that the float32 program stays under."""
    import calibrate
    cell = tiny(name, steps=64, t_call=16)
    x = harness.seeded_input(5, cell.shape)
    assert calibrate.control_reading(harness, cell, x) \
        > cell.traffic["max_rel_err"]


def _unchanged(orig):
    return lambda self, x, steps, block, **kw: x.astype(jnp.float32)


def _altered(orig):
    def call(self, x, steps, block, **kw):
        out = orig(self, x, steps, block, **kw)
        return out.at[(1,) * out.ndim].add(1.0)
    return call


def _half_left_out(orig):
    def call(self, x, steps, block, **kw):
        out = orig(self, x, steps, block, **kw)
        half = x.shape[0] // 2
        return out.at[half:].set(x[half:].astype(jnp.float32))
    return call


@pytest.mark.parametrize("fault", [_unchanged, _altered, _half_left_out])
@pytest.mark.parametrize("name", CELLS)
def test_planted_fault_is_not_correct(monkeypatch, name, fault):
    """Past the device check, a run whose compiled stencil returns its
    state unchanged, alters one answer, or leaves half of the array out
    reads ``correct`` false."""
    from repro.runtime.pallas_codegen import CompiledStencil
    monkeypatch.setattr(CompiledStencil, "__call__",
                        fault(CompiledStencil.__call__))
    r = harness.run(tiny(name), 3, 0.2, False, 0.0, CPU)
    assert not r["correct"] and r["failed"] >= 1


@pytest.mark.parametrize("name", ["jacobi2d-xl-ring", "heat3d-xl-compile"])
def test_ring_cell_fails_when_the_plan_stops_selecting_the_ring(monkeypatch,
                                                                name):
    from repro.runtime import pallas_backend
    orig = pallas_backend.PALLAS.compile
    monkeypatch.setattr(
        pallas_backend.PALLAS, "compile",
        lambda analysis, mode=None, **kw: orig(analysis, "addressable", **kw))
    r = harness.run(tiny(name), 3, 0.2, False, 0.0, CPU)
    assert r["checks"]["plan_mode"]["value"] == "addressable"
    assert not r["correct"]


# --------------------------------------------------------------- command --

def _run_cli(cwd, env_extra=None):
    env = dict(os.environ, JAX_PLATFORMS="cpu", **(env_extra or {}))
    return subprocess.run(
        [sys.executable, "bench/run.py", "--workload", CELLS[0], "--seed",
         "2147483659", "--seconds", "1", "--trace", "0"],
        cwd=cwd, env=env, capture_output=True, text=True, timeout=300)


def test_command_refuses_without_a_tpu():
    p = _run_cli(ROOT)
    assert p.returncode != 0 and p.stdout == ""


def test_command_refuses_outside_a_checkout(tmp_path):
    shutil.copy(ROOT / "BENCHMARK.json", tmp_path)
    shutil.copytree(BENCH, tmp_path / "bench",
                    ignore=shutil.ignore_patterns("__pycache__"))
    p = _run_cli(tmp_path)
    assert p.returncode != 0 and p.stdout == ""


def test_every_entry_is_found_by_name():
    for c in SPEC["configs"]:
        assert (ROOT / c["file"]).exists()
        assert (ROOT / c["file"]).with_suffix(".py").exists()
    for w in SPEC["workloads"]:
        assert (BENCH / "traffic" / f"{w['traffic']}.json").exists()
    for m in SPEC["per_layer"]:
        assert callable(harness._load_module(
            BENCH / "metrics" / f"{m['name']}.py").read)
