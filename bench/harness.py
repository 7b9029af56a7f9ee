"""The benchmark's harness: one cell of BENCHMARK.json, one run.

A cell names a configuration and a traffic mix, and the harness finds each
by that name:

* ``configs/<config>.json`` holds the PolyBench kernel and its dataset
  sizes, and ``configs/<config>.py`` its plain float32 reference (``step``)
  with the operations one cell update counts (``FLOPS_PER_UPDATE``);
* ``traffic/<traffic>.json`` holds the parameters of one closed loop:
  ``loop`` (``full_run`` or ``compile_loop``), ``dataset``, ``t_call`` and
  ``block`` (the tiling of one compiled call), ``mode`` (``null`` for the
  mode the plan selects, or a mode to force), ``expect_mode`` and the
  limit ``max_rel_err`` of the comparison that decides ``correct``;
* ``metrics/<metric>.py`` reads one per-layer metric from a `Record`.

A run of the whole problem is ``TSTEPS / t_call`` chained calls of the
compiled stencil: the state after step t is the whole array, so chaining is
exact.  Work is counted as the problem's, not the implementation's: N^d
updates per step, ``FLOPS_PER_UPDATE`` operations per update, and one read
and one write of the float32 array per run as the compulsory bytes
(``2 * 4 * N^d``).
"""
from __future__ import annotations

import contextlib
import importlib.util
import json
import math
import pathlib
import shutil
import sys
import tempfile
import time
from dataclasses import dataclass, field
from types import ModuleType
from typing import Callable, Dict, List, Optional

import jax
import jax.numpy as jnp
from jax.profiler import TraceAnnotation

BENCH = pathlib.Path(__file__).resolve().parent
ROOT = BENCH.parent
BYTES_PER_CELL = 4  # float32


# ----------------------------------------------------------------- cells --

def _load_module(path: pathlib.Path) -> ModuleType:
    """The module in ``path``, under a name no installed module has."""
    name = "bench_" + path.stem.replace("-", "_").replace(".", "_")
    spec = importlib.util.spec_from_file_location(name, path)
    module = importlib.util.module_from_spec(spec)
    sys.modules[name] = module
    spec.loader.exec_module(module)
    return module


#: the trace reduction (``import trace`` would find the standard library's)
trace = _load_module(BENCH / "trace.py")


@dataclass
class Cell:
    """One entry of ``workloads`` with its files read."""
    name: str
    chips: int
    config: dict
    ref: ModuleType
    traffic: dict
    end_to_end: List[dict] = field(default_factory=list)
    per_layer: List[dict] = field(default_factory=list)

    @property
    def sizes(self) -> dict:
        return self.config["datasets"][self.traffic["dataset"]]

    @property
    def shape(self) -> tuple:
        return (self.sizes["N"],) * self.config["rank"]

    @property
    def n_calls(self) -> int:
        steps, t_call = self.sizes["TSTEPS"], self.traffic["t_call"]
        if steps % t_call:
            raise ValueError(f"{self.name}: TSTEPS {steps} is not a multiple "
                             f"of t_call {t_call}")
        return steps // t_call

    @property
    def updates_per_run(self) -> int:
        return math.prod(self.shape) * self.sizes["TSTEPS"]

    @property
    def flops_per_run(self) -> int:
        return self.updates_per_run * self.ref.FLOPS_PER_UPDATE

    @property
    def bytes_per_run(self) -> int:
        return 2 * BYTES_PER_CELL * math.prod(self.shape)


def _applies(entry: dict, cell: str) -> bool:
    return cell in entry.get("workloads", [cell])


def load_cell(name: str, spec: Optional[dict] = None) -> Cell:
    """The cell ``name`` of BENCHMARK.json (or of ``spec``)."""
    spec = spec or json.loads((ROOT / "BENCHMARK.json").read_text())
    entry = next((w for w in spec["workloads"] if w["name"] == name), None)
    if entry is None:
        raise SystemExit(f"no workload {name!r} in BENCHMARK.json")
    cfg = next(c for c in spec["configs"] if c["name"] == entry["config"])
    cfg_path = ROOT / cfg["file"]
    return Cell(
        name=name, chips=entry["chips"],
        config=json.loads(cfg_path.read_text()),
        ref=_load_module(cfg_path.with_suffix(".py")),
        traffic=json.loads(
            (BENCH / "traffic" / f"{entry['traffic']}.json").read_text()),
        end_to_end=[m for m in spec["end_to_end"] if _applies(m, name)],
        per_layer=[m for m in spec["per_layer"] if _applies(m, name)])


def device_or_exit(chips: int) -> dict:
    """The chips JAX sees, with their peaks; exits non-zero, printing
    nothing on stdout, where there is no TPU, too few chips or a chip that
    the peak table does not know."""
    devices = jax.devices()
    kind = devices[0].device_kind
    if devices[0].platform != "tpu" or len(devices) < chips:
        sys.exit(f"bench: this cell needs {chips} TPU chip(s); JAX found "
                 f"{len(devices)} {devices[0].platform} device(s) ({kind})")
    peaks = json.loads((BENCH / "peaks.json").read_text())
    if kind not in peaks:
        sys.exit(f"bench: no peaks for device kind {kind!r} in peaks.json")
    return {"platform": devices[0].platform, "kind": kind,
            "count": len(devices), "peaks": peaks[kind]}


# ---------------------------------------------------- inputs and program --

def seeded_input(seed: int, shape: tuple) -> jax.Array:
    """Standard-normal float32 cells from ``seed`` (any size of integer:
    its low and high 32 bits both enter the key), made on the device."""
    key = jax.random.fold_in(jax.random.key(seed & 0xFFFFFFFF),
                             (seed >> 32) & 0xFFFFFFFF)
    return jax.jit(lambda k: jax.random.normal(k, shape, jnp.float32))(key)


def analysis_chain(cell: Cell):
    """Spec to plan, from a cleared polyhedron memo: what a compiler user
    pays for a kernel not analysed before."""
    import repro.core.polybench  # noqa: F401  (fills the kernel registry)
    from repro.core.analysis import analyze
    from repro.core.polyhedron import clear_polyhedron_cache
    from repro.core.registry import get
    clear_polyhedron_cache()
    return (analyze(get(cell.config["kernel"]))
            .classify().fifoize().size().plan())


def compile_stencil(cell: Cell, plan):
    """The program's `CompiledStencil` for this cell's mode."""
    return plan.compile(backend="pallas", mode=cell.traffic.get("mode"))


def call_of(cell: Cell, stencil) -> Callable:
    """One chained call: ``t_call`` steps of the compiled stencil."""
    t_call, block = cell.traffic["t_call"], cell.traffic["block"]
    return jax.jit(lambda a: stencil(a, t_call, block))


def whole_run(call: Callable, x: jax.Array, n_calls: int) -> List[jax.Array]:
    """One run of the whole problem; returns every chained call's output."""
    outs, a = [], x
    with TraceAnnotation("run"):
        for _ in range(n_calls):
            with TraceAnnotation("call"):
                a = call(a)
            outs.append(a)
        with TraceAnnotation("sync"):
            a.block_until_ready()
    return outs


# ------------------------------------------------------------ reference --

def reference_outputs(cell: Cell, x: jax.Array, dtype=jnp.float32):
    """The plain reference's state after each chained call, one at a time
    (float32 at ``highest`` precision; ``dtype`` lower for the control)."""
    t_call = cell.traffic["t_call"]
    step = cell.ref.step
    run = jax.jit(lambda a: jax.lax.fori_loop(
        0, t_call, lambda _, v: step(v), a))
    a = x.astype(dtype)
    with jax.default_matmul_precision("highest"):
        for _ in range(cell.n_calls):
            a = run(a)
            yield a


def rel_gap(got: jax.Array, ref: jax.Array) -> float:
    """Largest gap between ``got`` and ``ref``, as a share of the largest
    magnitude in ``ref``."""
    ref = ref.astype(jnp.float32)
    gap = jnp.max(jnp.abs(got.astype(jnp.float32) - ref))
    return float(gap / jnp.max(jnp.abs(ref)))


def max_rel_err(cell: Cell, x: jax.Array, outs: List[jax.Array],
                finals: List[jax.Array] = ()) -> float:
    """`rel_gap` of every chained call of a run (``outs``) against the
    reference's state after the same steps, and of each of ``finals``
    against its final state; the largest of them."""
    worst = 0.0
    for got, ref in zip(outs, reference_outputs(cell, x), strict=True):
        worst = max(worst, rel_gap(got, ref))
    return max([worst] + [rel_gap(f, ref) for f in finals])


# ----------------------------------------------------------------- loops --

@dataclass
class Outcome:
    """What a loop hands back to `run`."""
    end_to_end: Dict[str, float]
    attempted: int
    checks: Dict[str, dict]           # name -> {"value": .., "limit": ..}
    failed: int = 0
    host: Dict[str, List[float]] = field(default_factory=dict)


def _check(value, limit, ok) -> dict:
    return {"value": value, "limit": limit, "ok": bool(ok)}


def _mode_check(cell: Cell, stencil) -> dict:
    want = cell.traffic["expect_mode"]
    return _check(stencil.mode, want, stencil.mode == want)


def _err_check(cell: Cell, value: float) -> dict:
    limit = cell.traffic["max_rel_err"]
    return _check(value, limit, value <= limit)


def full_run(cell: Cell, seed: int, seconds: float, profile) -> Outcome:
    """Closed loop: one caller runs the whole problem on the seeded input,
    waits for it, and runs it again until ``seconds`` have passed (the run
    under way at that moment is finished and counted)."""
    x = seeded_input(seed, cell.shape)
    stencil = compile_stencil(cell, analysis_chain(cell))
    call = call_of(cell, stencil)
    whole_run(call, x, cell.n_calls)             # compiles: set-up
    profile.setup_done()

    runs, first = 0, None
    with profile.window(warm=lambda: whole_run(call, x, cell.n_calls)):
        t0 = time.perf_counter()
        while True:
            outs = whole_run(call, x, cell.n_calls)
            runs += 1
            first = outs[-1] if first is None else first
            if time.perf_counter() - t0 >= seconds:
                break
        window = time.perf_counter() - t0
    profile.window_closed()

    # every call of the last run, and the first run's final state
    err = max_rel_err(cell, x, outs, [first])
    checks = {"max_rel_err": _err_check(cell, err),
              "plan_mode": _mode_check(cell, stencil)}
    return Outcome(
        end_to_end={"stencil_rate": runs * cell.updates_per_run / window
                    * 1e-9},
        attempted=runs, failed=0 if checks["max_rel_err"]["ok"] else 1,
        checks=checks)


def produce_kernel(cell: Cell, x: jax.Array, host: Dict[str, List[float]]):
    """Spec to runnable kernel: the analysis chain, then the Pallas codegen
    and JAX's lowering and compilation from a new closure."""
    t0 = time.perf_counter()
    with TraceAnnotation("analysis"):
        plan = analysis_chain(cell)
    t1 = time.perf_counter()
    with TraceAnnotation("codegen"):
        stencil = compile_stencil(cell, plan)
        kernel = call_of(cell, stencil).lower(x).compile()
    t2 = time.perf_counter()
    host.setdefault("analysis_s", []).append(t1 - t0)
    host.setdefault("lower_compile_s", []).append(t2 - t1)
    return stencil, kernel


@contextlib.contextmanager
def persistent_cache_off():
    from jax.experimental.compilation_cache import compilation_cache as cc
    before = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    cc.reset_cache()
    try:
        yield
    finally:
        jax.config.update("jax_enable_compilation_cache", before)
        cc.reset_cache()


def compile_loop(cell: Cell, seed: int, seconds: float, profile) -> Outcome:
    """Closed loop: one caller goes from the spec to a runnable kernel for
    the cell's geometry, again and again, with JAX's persistent compile
    cache off, until ``seconds`` have passed.  The last kernel the window
    produced then runs the whole problem for the comparison."""
    x = seeded_input(seed, cell.shape)
    with persistent_cache_off():
        first: Dict[str, List[float]] = {}
        produce_kernel(cell, x, first)           # imports, first traces
        profile.setup_done()
        print(f"first kernel, in set-up: analysis {first['analysis_s'][0]} s,"
              f" codegen {first['lower_compile_s'][0]} s", file=sys.stderr)
        host: Dict[str, List[float]] = {}
        with profile.window():
            t0 = time.perf_counter()
            while True:
                stencil, kernel = produce_kernel(cell, x, host)
                if time.perf_counter() - t0 >= seconds:
                    break
            window = time.perf_counter() - t0
            with TraceAnnotation("check"):
                outs = whole_run(kernel, x, cell.n_calls)
    profile.window_closed()

    err = max_rel_err(cell, x, outs)
    checks = {"max_rel_err": _err_check(cell, err),
              "plan_mode": _mode_check(cell, stencil)}
    kernels = len(host["analysis_s"])
    return Outcome(
        end_to_end={"time_to_kernel_s": window / kernels},
        attempted=kernels, failed=0 if checks["max_rel_err"]["ok"] else 1,
        checks=checks, host=host)


LOOPS = {"full_run": full_run, "compile_loop": compile_loop}


# --------------------------------------------------------------- profile --

class Profile:
    """Set-up clock, the measured window's trace, and the memory peak.

    ``setup_done`` ends set-up; ``window`` wraps the measured window (and,
    with tracing on, records the profiler's trace of it); ``window_closed``
    reads the device's memory peak before the reference runs."""

    def __init__(self, t_start: float, trace: bool):
        self.t_start, self.trace = t_start, trace
        self.setup_s: Optional[float] = None
        self.memory_peak_bytes: Optional[int] = None
        self.profile = None

    def setup_done(self) -> None:
        self.setup_s = time.perf_counter() - self.t_start

    @contextlib.contextmanager
    def window(self, warm: Optional[Callable] = None):
        """With tracing on, ``warm`` runs under the tracer before the
        window opens: the first program it sees waits about 0.1 s for the
        device tracer, which would read as idle time in the window."""
        if not self.trace:
            with TraceAnnotation("window"):
                yield
            return
        from jax.profiler import ProfileData
        tmp = tempfile.mkdtemp(prefix="bench-trace-")
        try:
            jax.profiler.start_trace(tmp)
            try:
                if warm is not None:
                    warm()
                with TraceAnnotation("window"):
                    yield
            finally:
                jax.profiler.stop_trace()
            pb = next(pathlib.Path(tmp).rglob("*.xplane.pb"))
            self.profile = ProfileData.from_serialized_xspace(pb.read_bytes())
        finally:
            shutil.rmtree(tmp, ignore_errors=True)

    def window_closed(self) -> None:
        self.memory_peak_bytes = max(
            (d.memory_stats() or {}).get("peak_bytes_in_use", 0)
            for d in jax.devices())


# ------------------------------------------------------------------- run --

@dataclass
class Record:
    """What a per-layer metric's reader reads."""
    cell: Cell
    device: dict
    host: Dict[str, List[float]]
    trace: Optional[object]           # trace.Summary, or None


def read_metric(name: str, record: Record) -> Optional[float]:
    return _load_module(BENCH / "metrics" / f"{name}.py").read(record)


#: the longest window a traced run records: enough for hundreds of runs of
#: every cell, and a trace of the addressable cell (a million ops in 10 s)
#: that the reduction reads in seconds
TRACED_SECONDS = 10.0


def run(cell: Cell, seed: int, seconds: float, traced: bool, t_start: float,
        device: dict) -> dict:
    """One run of ``cell``; returns the result line's object."""
    profile = Profile(t_start, traced)
    if traced:
        seconds = min(seconds, TRACED_SECONDS)
    out = LOOPS[cell.traffic["loop"]](cell, seed, seconds, profile)
    correct = all(c["ok"] for c in out.checks.values())
    dev = {k: device[k] for k in ("platform", "kind", "count")}
    dev["memory_peak_bytes"] = profile.memory_peak_bytes
    result = {"correct": correct, "attempted": out.attempted,
              "failed": out.failed}
    if not traced:
        values = dict(out.end_to_end, setup_s=profile.setup_s)
        result["metrics"] = {m["name"]: {"value": values[m["name"]],
                                         "unit": m["unit"]}
                             for m in cell.end_to_end}
    else:
        summary = (trace.summarize(profile.profile)
                   if profile.profile is not None else None)
        record = Record(cell, device, out.host, summary)
        metrics = {}
        for m in cell.per_layer:
            value = read_metric(m["name"], record)
            if value is not None:
                metrics[m["name"]] = {"value": value, "unit": m["unit"]}
        result["metrics"] = metrics
        if summary is not None:
            dev["busy_s"], dev["window_s"] = summary.busy_s, summary.window_s
            result["breakdown"] = {
                "device_ops": [list(o) for o in summary.top_ops],
                "idle_gaps": [list(g) for g in summary.idle_gaps]}
    result["device"] = dev
    result["checks"] = {k: {"value": c["value"], "limit": c["limit"]}
                        for k, c in out.checks.items()}
    return result
