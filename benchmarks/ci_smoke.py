"""CI smoke benchmark: registry specs + table2 subset + tile-sweep engine +
operational validation, with guards.

    PYTHONPATH=src python -m benchmarks.ci_smoke

Nine sections, in order:

1. **Registry check** (`repro.lang.check_registry`, same gate as
   ``python -m repro.lang --check-registry``): every registered kernel spec
   must build and validate.  Runs FIRST and aborts the run on failure, so a
   malformed spec fails with authoring-level diagnostics before any
   analysis timing section touches it.
2. **Sweep smoke** (cold caches): for gemm / jacobi-1d / seidel-2d × 3 tile
   sizes, the sweep engine must produce reports identical to a fresh
   `analyze()` per tiling and finish within ``SWEEP_BUDGET`` (0.6×) of the
   naive per-tiling loop — the amortization regression guard.  Runs before
   any disk-warmed cache can distort the ratio.
3. **Validate smoke**: `Analysis.validate()` on the same 3 kernels, pre- AND
   post-FIFOIZE — every verdict replayed on the runtime simulator (positive
   and negative directions) and peak occupancy checked against `size()`
   slots, within ``VALIDATE_BUDGET`` of the analysis it checks.
4. **Pallas smoke**: `Analysis.compile(backend="pallas")` on jacobi-1d in
   interpret mode — the generated VMEM-ring kernel must match the oracle,
   an undersized ring must diverge, and the planned traces must replay
   green through the pallas backend (`validate(backend="pallas")`), all
   within ``PALLAS_BUDGET`` seconds.
5. **Self-timed smoke**: every registered kernel executes to completion on
   the self-timed engine under its planned capacities (sequential policy),
   and an injected deadlock — the decode loop's KV feedback channel shrunk
   below the batch width — must be *detected* as a structural deadlock
   naming that channel in bounded time, all within ``SELFTIMED_BUDGET``.
6. **Faults smoke**: the fault matrix (`Analysis.validate(mode="faults")`)
   on the same 3 kernels — every injected fault detected and recovered or
   loudly named, a guarded fault-free run stays clean — within
   ``FAULTS_BUDGET`` seconds.
7. **Parametric smoke**: one symbolic-size template per smoke kernel
   (``analyze(case, sizes=symbolic)``) must close without falling back and
   instantiate byte-identically to a from-scratch concrete analysis at 2
   sizes each, within ``PARAMETRIC_BUDGET`` seconds.
8. **Artifact guard**: every ``benchmarks/bench_*.py`` must have a
   committed, parseable, non-empty ``BENCH_*.json`` at the repo root (and
   vice versa) — a benchmark whose recorded artifact is missing or corrupt
   fails CI, not the next reader.
9. **DSE smoke**: a 2-kernel × 3-tiling × 2-size design-space run through
   `repro.dse` against the persistent store (``REPRO_DSE_STORE``; CI wires
   it under `actions/cache`): budgeted run (the interrupt), resume to
   completion, a verification pass that must compute **zero** points, and
   per-kernel Pareto frontiers — all within ``DSE_BUDGET``.  Assertions
   are count-based so a warm store (cache hit) passes identically.
10. **Persistent store**: if ``REPRO_POLY_CACHE`` is set (CI wires it to an
    `actions/cache` path), the verdict store is loaded here — warming the
    domain-enumeration boxes for the next section — and saved again at exit.
11. **Table2 subset**: classifications must match the recorded
    BENCH_table2.json rows exactly and stay within GUARD_FACTOR of the
    recorded wall-clock.
"""
from __future__ import annotations

import json
import os
import sys
import time
from pathlib import Path

from repro.core import (analyze, clear_polyhedron_cache,
                        load_polyhedron_cache, report_payload,
                        save_polyhedron_cache, sweep)
from repro.core.polybench import get
from repro.core.tiling import rescale_tilings

from . import table2_fifo

KERNELS = ("gemm", "jacobi-1d", "seidel-2d")
GUARD_FACTOR = 4.0

SWEEP_SIZES = (2, 4, 6)
SWEEP_BUDGET = 0.6        # sweep must cost ≤ 0.6× the naive per-tiling loop

VALIDATE_BUDGET = 1.5     # validate() must cost ≤ 1.5× the analysis itself
                          # (measured ~0.4× — vectorized trace replays)

PALLAS_BUDGET = 120.0     # seconds for the whole interpret-mode pallas
                          # section (measured ~15s on CI-class CPUs: the
                          # interpreter pays per grid step, so the smoke
                          # geometry is deliberately tiny)

SELFTIMED_BUDGET = 60.0   # seconds for the self-timed section: ~25k fires
                          # across every registered kernel (measured ~10s)
                          # plus one injected deadlock that must be
                          # DETECTED, not waited out

FAULTS_BUDGET = 60.0      # seconds for the fault matrix on the 3 smoke
                          # kernels: ~16 guarded engine runs + the trace
                          # replays each (measured ~5s) — recovery must be
                          # bounded, so a blown budget means a guard loop

PARAMETRIC_BUDGET = 60.0  # seconds for the parametric section: one symbolic
                          # template per smoke kernel (probe grids at the
                          # small end of the lattice, measured ~3s total)
                          # plus 2 concrete baselines each for the parity
                          # check; the fallback path counts as a failure
                          # here — these 3 kernels are known to close

DSE_BUDGET = 90.0         # seconds for the DSE section: 24 design points
                          # (2 kernels x 3 tilings x 2 topologies x 2
                          # sizes) through run/interrupt/resume/frontier,
                          # inline manager (measured ~8s cold, ~0.1s when
                          # the actions/cache store is warm)

REPO = Path(__file__).resolve().parent.parent
BENCH_PATH = REPO / "BENCH_table2.json"
CACHE_ENV = "REPRO_POLY_CACHE"


def registry_smoke(failures: list) -> None:
    from repro.core.registry import kernel_names
    from repro.lang import check_registry

    t0 = time.perf_counter()
    fails = check_registry()
    dt = time.perf_counter() - t0
    status = "ok" if not fails else "INVALID"
    print(f"registry check  {len(kernel_names())} kernel specs "
          f"{dt*1e3:7.1f}ms {status}")
    failures.extend(f"registry/{f}" for f in fails)


def sweep_smoke(failures: list) -> None:
    total_naive = total_sweep = 0.0
    for name in KERNELS:
        case = get(name)
        cfgs = [rescale_tilings(case.tilings, b) for b in SWEEP_SIZES]
        t0 = time.perf_counter()
        naive = []
        for cfg in cfgs:
            clear_polyhedron_cache()
            naive.append(analyze(case.kernel, tilings=cfg).classify()
                         .fifoize().size(pow2=True).report())
        t_naive = time.perf_counter() - t0
        clear_polyhedron_cache()
        t0 = time.perf_counter()
        swept = sweep(case.kernel, cfgs)
        t_sweep = time.perf_counter() - t0
        if ([report_payload(r) for r in naive]
                != [report_payload(r) for r in swept]):
            failures.append(f"sweep/{name}: reports differ from fresh "
                            f"analyze() — amortization changed results")
        total_naive += t_naive
        total_sweep += t_sweep
    ratio = total_sweep / total_naive
    status = "ok" if ratio <= SWEEP_BUDGET else "SLOW"
    print(f"sweep smoke  naive {total_naive*1e3:7.1f}ms "
          f"sweep {total_sweep*1e3:7.1f}ms ratio {ratio:.2f} "
          f"(budget {SWEEP_BUDGET}) {status}")
    if ratio > SWEEP_BUDGET:
        failures.append(f"sweep: {total_sweep:.3f}s exceeds "
                        f"{SWEEP_BUDGET}x naive loop ({total_naive:.3f}s)")


def validate_smoke(failures: list) -> None:
    from repro.runtime import ValidationError

    t_an = t_val = 0.0
    replays = rejections = 0
    for name in KERNELS:
        case = get(name)
        t0 = time.perf_counter()
        base = analyze(case).classify()
        pre = base.size(pow2=True)
        post = base.fifoize().size(pow2=True)
        t_an += time.perf_counter() - t0
        t0 = time.perf_counter()
        for a in (pre, post):
            try:
                v = a.validate().validation
                replays += v.replays
                rejections += v.rejections
            except ValidationError as e:
                failures.append(f"validate/{name}: {e}")
        t_val += time.perf_counter() - t0
    ratio = t_val / t_an
    status = "ok" if ratio <= VALIDATE_BUDGET else "SLOW"
    print(f"validate smoke  {replays} replays {rejections} rejections  "
          f"analysis {t_an*1e3:7.1f}ms validate {t_val*1e3:7.1f}ms "
          f"ratio {ratio:.2f} (budget {VALIDATE_BUDGET}) {status}")
    if ratio > VALIDATE_BUDGET:
        failures.append(f"validate: {t_val:.3f}s exceeds {VALIDATE_BUDGET}x "
                        f"the analysis time ({t_an:.3f}s)")


def pallas_smoke(failures: list) -> None:
    import jax.numpy as jnp
    import numpy as np

    from repro.runtime import ValidationError

    t0 = time.perf_counter()
    try:
        a = (analyze(get("jacobi-1d")).classify().fifoize().size().plan())
        c = a.compile(backend="pallas")
        if c.mode != "fifo-ring":
            failures.append(f"pallas: expected fifo-ring mode, got {c.mode}")
        steps = block = 16
        x = jnp.asarray(np.random.default_rng(0).standard_normal(256),
                        jnp.float32)
        want = c.program.ref(x, steps)
        got = c(x, steps, block)
        if not jnp.allclose(got, want, rtol=1e-5, atol=1e-5):
            failures.append("pallas: generated kernel diverged from oracle")
        bad = c(x, steps, block, ring_depth=(steps + 1) // 2)
        if jnp.allclose(bad, want, rtol=1e-5, atol=1e-5):
            failures.append("pallas: undersized ring did NOT corrupt the "
                            "output — negative direction broken")
        v = a.validate(backend="pallas").validation
        replays, rejections = v.replays, v.rejections
    except ValidationError as e:
        failures.append(f"pallas: validate(backend='pallas') failed: {e}")
        replays = rejections = 0
    except Exception as e:
        failures.append(f"pallas: {type(e).__name__}: {e}")
        replays = rejections = 0
    dt = time.perf_counter() - t0
    status = "ok" if dt <= PALLAS_BUDGET else "SLOW"
    print(f"pallas smoke  jacobi-1d fifo-ring + undersized + "
          f"{replays} replays {rejections} rejections  "
          f"{dt*1e3:7.1f}ms (budget {PALLAS_BUDGET*1e3:.0f}ms) {status}")
    if dt > PALLAS_BUDGET:
        failures.append(f"pallas: {dt:.1f}s exceeds the {PALLAS_BUDGET}s "
                        f"interpret-mode budget")


def selftimed_smoke(failures: list) -> None:
    from repro.core.registry import kernel_names
    from repro.runtime.selftimed import execute_ppn
    from repro.runtime.selftimed.validate import executable_capacities
    from repro.serve.batching import decode_loop_ppn

    t0 = time.perf_counter()
    fires = done = 0
    for name in kernel_names():
        a = analyze(get(name)).classify().fifoize().size(pow2=True)
        caps = executable_capacities(a)
        rep = execute_ppn(a.ppn, caps, policy="sequential",
                          on_deadlock="report")
        fires += rep.fires
        if rep.completed:
            done += 1
        else:
            failures.append(f"selftimed/{name}: planned capacities did not "
                            f"complete: {rep.deadlock.summary()}")
    # injected deadlock: the decode loop's KV feedback shrunk below the
    # batch width must be DETECTED (bounded time), naming the channel
    ppn = decode_loop_ppn(slots=4, steps=8)
    fb = "decode->decode.state[0]"
    rep = execute_ppn(ppn, {fb: 3, "prefill->decode.state[0]": 4},
                      policy="concurrent", on_deadlock="report")
    if rep.completed:
        failures.append("selftimed: undersized decode feedback did NOT "
                        "deadlock — detection broken")
    elif fb not in (rep.deadlock.cycle_channels() or [rep.deadlock.culprit]):
        failures.append(f"selftimed: deadlock report blames "
                        f"{rep.deadlock.culprit!r}, not the shrunk {fb!r}")
    dt = time.perf_counter() - t0
    status = "ok" if dt <= SELFTIMED_BUDGET else "SLOW"
    print(f"selftimed smoke  {done} kernels completed ({fires} fires) + "
          f"injected deadlock detected  {dt*1e3:7.1f}ms "
          f"(budget {SELFTIMED_BUDGET*1e3:.0f}ms) {status}")
    if dt > SELFTIMED_BUDGET:
        failures.append(f"selftimed: {dt:.1f}s exceeds the "
                        f"{SELFTIMED_BUDGET}s budget")


def faults_smoke(failures: list) -> None:
    from repro.runtime import ValidationError

    t0 = time.perf_counter()
    engine = wire = recovered = 0
    for name in KERNELS:
        a = analyze(get(name)).classify().fifoize().size(pow2=True)
        try:
            v = a.validate(mode="faults").resilience
        except ValidationError as e:
            failures.append(f"faults/{name}: {e}")
            continue
        engine += len(v.matrix)
        wire += len(v.trace_matrix)
        recovered += v.recovered
    dt = time.perf_counter() - t0
    status = "ok" if dt <= FAULTS_BUDGET else "SLOW"
    print(f"faults smoke  {engine} engine faults "
          f"({recovered} recovered/degraded) + {wire} wire faults rejected  "
          f"{dt*1e3:7.1f}ms (budget {FAULTS_BUDGET*1e3:.0f}ms) {status}")
    if dt > FAULTS_BUDGET:
        failures.append(f"faults: {dt:.1f}s exceeds the {FAULTS_BUDGET}s "
                        f"budget — recovery is supposed to be bounded")


def parametric_smoke(failures: list) -> None:
    import warnings

    from repro.core import symbolic
    from repro.core.parametric import ParametricFallbackWarning

    t0 = time.perf_counter()
    evals = proved = flags = 0
    for name in KERNELS:
        case = get(name)
        pa = (analyze(case, sizes=symbolic)
              .classify().fifoize().size(pow2=True).plan())
        with warnings.catch_warnings():
            warnings.simplefilter("error", ParametricFallbackWarning)
            try:
                pa.prepare()
            except ParametricFallbackWarning as w:
                failures.append(f"parametric/{name}: fell back ({w})")
                continue
        t = pa._template
        doc = pa.report().parametric
        for ch in doc["channels"].values():
            for flag in ("in_order", "unicity"):
                flags += 1
                proved += ch[flag]["status"] in ("proved", "proved_ray")
        for k in (0, 1):      # 2 sizes per kernel: θ and θ + stride
            env = {p: t["theta"][p] + k * t["strides"][p]
                   for p in pa.symbolic_params}
            ev = report_payload(pa.evaluate(**env))
            conc = report_payload(
                analyze(case.kernel, params=dict(env), tilings=case.tilings)
                .classify().fifoize().size(pow2=True).plan().report())
            evals += 1
            if json.dumps(ev, sort_keys=True) != json.dumps(conc,
                                                            sort_keys=True):
                failures.append(f"parametric/{name}: evaluate({env}) is not "
                                f"byte-identical to concrete analysis")
        pa.release()
    dt = time.perf_counter() - t0
    status = "ok" if dt <= PARAMETRIC_BUDGET else "SLOW"
    print(f"parametric smoke  {len(KERNELS)} templates, {evals} sizes "
          f"byte-checked, {proved}/{flags} flags proved  {dt*1e3:7.1f}ms "
          f"(budget {PARAMETRIC_BUDGET*1e3:.0f}ms) {status}")
    if dt > PARAMETRIC_BUDGET:
        failures.append(f"parametric: {dt:.1f}s exceeds the "
                        f"{PARAMETRIC_BUDGET}s budget")


def artifact_guard(failures: list) -> None:
    """Every bench_*.py ↔ a committed parseable BENCH_*.json, both ways."""
    benches = {p.stem[len("bench_"):]
               for p in (REPO / "benchmarks").glob("bench_*.py")}
    artifacts = {p.stem[len("BENCH_"):] for p in REPO.glob("BENCH_*.json")}
    for name in sorted(benches - artifacts):
        failures.append(f"artifacts: benchmarks/bench_{name}.py has no "
                        f"committed BENCH_{name}.json — run it and commit "
                        f"the result")
    for name in sorted(artifacts - benches):
        failures.append(f"artifacts: BENCH_{name}.json has no "
                        f"benchmarks/bench_{name}.py to regenerate it")
    parsed = 0
    for name in sorted(benches & artifacts):
        path = REPO / f"BENCH_{name}.json"
        try:
            doc = json.loads(path.read_text())
            if not doc:
                raise ValueError("empty document")
            parsed += 1
        except Exception as e:
            failures.append(f"artifacts: {path.name} is not parseable "
                            f"({type(e).__name__}: {e})")
    status = "ok" if not any(f.startswith("artifacts:")
                             for f in failures) else "BROKEN"
    print(f"artifact guard  {len(benches)} benchmarks, {parsed} recorded "
          f"artifacts parseable {status}")


def dse_smoke(failures: list) -> None:
    import tempfile

    from repro.dse import ArtifactStore, DSEService, default_experiment
    from repro.dse.store import ENV_STORE

    t0 = time.perf_counter()
    root = os.environ.get(ENV_STORE) or tempfile.mkdtemp(prefix="ci-dse-")
    # default name, so CI's `repro.dse status` CLI step (same axes) resolves
    # to the same experiment id and sees this section's completed store
    exp = default_experiment(kernels=["gemm", "jacobi-1d"],
                             tile_sizes=[2, 3, 4], size_count=2)
    total = len(exp.points())
    svc = DSEService(exp, ArtifactStore(root), manager="inline")
    budgeted = svc.run(max_points=6)       # the interrupted first slice
    resumed = svc.run()                    # store-first: finishes the rest
    verify = svc.run()                     # must compute NOTHING
    if resumed["pending"] != 0 or resumed["errors"]:
        failures.append(f"dse: resume did not complete cleanly ({resumed})")
    if budgeted["computed"] + budgeted["from_store"] \
            + resumed["computed"] != total:
        failures.append(
            f"dse: interrupt+resume accounting does not cover the grid "
            f"(budgeted {budgeted['computed']}+{budgeted['from_store']}, "
            f"resumed {resumed['computed']}, total {total})")
    if verify["computed"] != 0 or verify["from_store"] != total:
        failures.append(f"dse: verification pass recomputed "
                        f"{verify['computed']} points (zero-recompute "
                        f"resume broken)")
    frontier = svc.frontier()
    for kernel in exp.kernels:
        kdoc = frontier["kernels"].get(kernel)
        if not kdoc or not kdoc["predicted"]["frontier"]:
            failures.append(f"dse: no Pareto frontier for {kernel}")
            continue
        best = kdoc["predicted"]["frontier"][0]["vector"]
        if not (0.0 <= best[0] <= 1.0 and best[1] > 0 and best[2] > 0):
            failures.append(f"dse: degenerate frontier vector {best} "
                            f"for {kernel}")
    dt = time.perf_counter() - t0
    status = "ok" if dt <= DSE_BUDGET else "SLOW"
    print(f"dse smoke  {total} points (computed "
          f"{budgeted['computed']}+{resumed['computed']}, store "
          f"{budgeted['from_store']}), verify recompute "
          f"{verify['computed']}, frontiers "
          f"{sum(len(k['predicted']['frontier']) for k in frontier['kernels'].values())}  "
          f"{dt*1e3:7.1f}ms (budget {DSE_BUDGET*1e3:.0f}ms) {status}")
    if dt > DSE_BUDGET:
        failures.append(f"dse: {dt:.1f}s exceeds the {DSE_BUDGET}s budget")


def table2_smoke(failures: list) -> None:
    doc = json.loads(BENCH_PATH.read_text())
    recorded = {r["kernel"]: r for r in doc["optimized"]}
    drop = table2_fifo.strip_timing
    for name in KERNELS:
        got = min((table2_fifo.run_kernel(name) for _ in range(2)),
                  key=lambda r: r["seconds"])
        want = recorded[name]
        if drop(got) != drop(want):
            failures.append(f"{name}: classification drift {drop(got)} "
                            f"!= {drop(want)}")
        budget = want["seconds"] * GUARD_FACTOR
        status = "ok" if got["seconds"] <= budget else "SLOW"
        print(f"{name:12s} {got['seconds']*1e3:7.1f}ms "
              f"(budget {budget*1e3:7.1f}ms) {status}")
        if got["seconds"] > budget:
            failures.append(f"{name}: {got['seconds']:.3f}s exceeds "
                            f"{budget:.3f}s timing budget")


def main() -> int:
    failures: list = []
    # 1. spec validation — malformed kernel specs abort before any timing
    #    section spends time (or crashes) on them
    registry_smoke(failures)
    if not failures:
        # 2. sweep guard next — it clears caches, so it must not see (or
        #    wipe) the persistent store
        sweep_smoke(failures)
        # 3. operational validation of the same kernels, pre/post-FIFOIZE
        validate_smoke(failures)
        # 4. generated-kernel path: compile + parity + undersized-ring +
        #    trace replay through the pallas backend, interpret mode
        pallas_smoke(failures)
        # 5. dataflow-driven execution: every kernel completes self-timed,
        #    an injected deadlock is detected and attributed
        selftimed_smoke(failures)
        # 6. fault matrix: injected faults detected, recovered or named
        faults_smoke(failures)
        # 7. symbolic templates instantiate byte-identically to concrete
        #    analysis on 3 kernels x 2 sizes
        parametric_smoke(failures)
        # 8. every benchmark's recorded artifact exists and parses
        artifact_guard(failures)
        # 9. design-space service: budgeted run -> resume -> zero-recompute
        #    verify -> frontiers, against the persistent DSE store
        dse_smoke(failures)
        # 10. warm start for the remaining sections, refreshed on the way out
        cache_path = os.environ.get(CACHE_ENV)
        if cache_path:
            clear_polyhedron_cache()
            print(f"persistent store: loaded "
                  f"{load_polyhedron_cache(cache_path)} entries "
                  f"from {cache_path}")
        # 11. table2 classification + timing guard
        table2_smoke(failures)
        if cache_path and not failures:
            print(f"persistent store: saved "
                  f"{save_polyhedron_cache(cache_path)} entries")
    for f in failures:
        print("FAIL:", f, file=sys.stderr)
    return 1 if failures else 0


if __name__ == "__main__":
    raise SystemExit(main())
