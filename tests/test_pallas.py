"""The pallas codegen backend (runtime/pallas_backend + pallas_codegen).

1. Registry: the ``"pallas"`` backend implements the full lowering
   vocabulary, carries the whole-PPN compile hook, and shows up in
   `available_backends()`.
2. Trace replay through real VMEM rings: the 2-process verdict matrix of
   `test_runtime` holds identically on this backend (positive and
   negative), and an undersized ring raises `RingOverflow` — the failure
   the reference backend cannot produce.
3. Generated fused kernels: numerical parity vs the `kernels/stencil_bands`
   oracles across tile sizes including the degenerate block=1 tiling,
   mode selection from the plan records, and the undersized-ring /
   narrowed-halo injections whose outputs must DIVERGE from the oracle.
4. `Analysis.validate(backend="pallas")`: green on planned PolyBench
   stencils, loud on injected wrong plans (mirroring the reference-backend
   wrong-plan cases).

Everything runs in Pallas interpret mode (no TPU needed); geometries are
deliberately tiny because the interpreter pays per grid step.
"""
import dataclasses

import numpy as np
import pytest

jax = pytest.importorskip("jax")
jnp = pytest.importorskip("jax.numpy")

import repro.core.polybench  # noqa: F401,E402  (populate the registry)
from repro.core import Pattern, analyze  # noqa: E402
from repro.core.registry import get  # noqa: E402
from repro.runtime import (LOWERINGS, FIFO_STREAM,  # noqa: E402
                           BROADCAST_REGISTER, REORDER_BUFFER,
                           OrderViolation, ValidationError,
                           available_backends, backend, trace_channel)
from repro.runtime.pallas_backend import RingOverflow  # noqa: E402
from repro.runtime.pallas_codegen import STENCIL_PROGRAMS  # noqa: E402

from test_polybench import ADI_OUT_OF_ORDER  # noqa: E402
from test_runtime import CASES, two_proc_ppn  # noqa: E402

ATOL = dict(rtol=1e-5, atol=1e-5)


def planned(name):
    return analyze(get(name)).classify().fifoize().size().plan()


# ------------------------------------------------------------ registry -----


def test_pallas_backend_covers_vocabulary_and_compiles():
    pb = backend("pallas")
    for name in LOWERINGS:
        impl = pb.implementation(name)
        assert impl.lowering == name
        assert hasattr(impl, "run") and hasattr(impl, "step")
    assert pb.compile is not None


def test_available_backends_lists_all_three():
    status = available_backends()
    assert set(status) >= {"reference", "jax", "pallas"}
    for name, state in status.items():
        assert state.startswith("ok"), f"{name}: {state}"
    assert "+compile" in status["pallas"]


def test_unknown_backend_stays_loud():
    with pytest.raises(KeyError, match="no backend"):
        backend("fpga")


# ---------------------------------------------- trace replay on VMEM rings --


@pytest.mark.parametrize("src,verdict", CASES)
def test_planned_lowering_executes_on_vmem_ring(src, verdict):
    """Same acceptance matrix as the reference backend: the verdict's own
    lowering serves the trace and reports the reference peak."""
    from repro.runtime.lowering import lowering_for_pattern
    from repro.runtime.simulator import simulate_channel

    ppn, ch = two_proc_ppn(src)
    trace = trace_channel(ppn, ch)
    lowering = lowering_for_pattern(verdict)
    peak = backend("pallas").implementation(lowering).run(trace)
    assert peak == simulate_channel(ppn, ch, lowering)


@pytest.mark.parametrize("src,verdict", CASES)
def test_cheaper_lowerings_reject_on_vmem_ring(src, verdict):
    """Negative direction, in-kernel: the FIFO ring rejects every non-FIFO
    trace, the carried register also rejects out-of-order ones."""
    ppn, ch = two_proc_ppn(src)
    trace = trace_channel(ppn, ch)
    pb = backend("pallas")
    if verdict is Pattern.FIFO:
        return
    with pytest.raises(OrderViolation):
        pb.implementation(FIFO_STREAM).run(trace)
    if verdict in (Pattern.OOO, Pattern.OOO_UNICITY):
        with pytest.raises(OrderViolation):
            pb.implementation(BROADCAST_REGISTER).run(trace)
    else:
        assert pb.implementation(BROADCAST_REGISTER).run(trace) >= 1


def test_undersized_ring_overflows():
    """Fewer slots than peak occupancy must clobber a live value — the ring
    is a real ring, not an elastic buffer."""
    ppn, ch = two_proc_ppn([0, 1, 2, 3])
    trace = trace_channel(ppn, ch)
    impl = backend("pallas").implementation(FIFO_STREAM)
    peak = impl.run(trace)
    assert peak >= 1
    assert impl.run(trace, slots=peak) == peak
    if peak > 1:
        with pytest.raises(RingOverflow, match="too small"):
            impl.run(trace, slots=peak - 1)


def test_reorder_buffer_is_addressable_but_capacity_checked():
    ppn, ch = two_proc_ppn([1, 1, 0, 0])          # OOO trace
    trace = trace_channel(ppn, ch)
    impl = backend("pallas").implementation("reorder-buffer")
    peak = impl.run(trace)                         # any pop order is fine
    assert peak >= 2
    with pytest.raises(RingOverflow):
        impl.run(trace, slots=1)


# --------------------------------------------------- generated kernels -----

#: kernel → (shape, steps, blocks to try — 1 is the degenerate tiling)
GEOMETRIES = {
    "jacobi-1d": ((32,), 4, (1, 2, 4)),
    "jacobi-2d": ((16, 8), 4, (1, 4)),
    "heat-3d": ((8, 4, 4), 2, (1, 2)),
    "seidel-2d": ((16, 20), 8, (1, 4, 8)),     # width not 128·k
}


@pytest.mark.parametrize("name", sorted(GEOMETRIES))
def test_generated_kernel_matches_reference(name):
    shape, steps, blocks = GEOMETRIES[name]
    c = planned(name).compile(backend="pallas")
    assert c.mode == "fifo-ring", c.describe()
    x = jnp.asarray(np.random.default_rng(0).standard_normal(shape),
                    jnp.float32)
    want = c.program.ref(x, steps)
    for block in blocks:
        got = c(x, steps, block)
        assert jnp.allclose(got, want, **ATOL), (name, block)


@pytest.mark.parametrize("steps,block", [(16, 16), (32, 16)])
def test_generated_jacobi1d_matches_reference(steps, block):
    """jacobi-1d streams its cells on the lane axis: more than one flush
    block (steps = 2·block) drains the skewed tail as well."""
    c = planned("jacobi-1d").compile(backend="pallas")
    x = jnp.asarray(np.random.default_rng(1).standard_normal(64), jnp.float32)
    got = c(x, steps, block)
    assert got.shape == x.shape
    assert jnp.allclose(got, c.program.ref(x, steps), **ATOL)


#: (kernel, shape, steps, block)
EXACT_CASES = [
    ("jacobi-2d", (64, 200), 16, 8),
    ("jacobi-2d", (48, 130), 8, 8),     # width not 128·k
    ("jacobi-2d", (64, 256), 32, 16),
    ("jacobi-2d", (16, 8), 4, 4),
    ("jacobi-2d", (16, 8), 4, 2),       # window 0 is the halo alone
    ("jacobi-2d", (16, 8), 4, 1),       # block < halo
    ("heat-3d", (16, 12, 12), 8, 8),
    ("jacobi-1d", (64,), 8, 8),
]


@pytest.mark.parametrize(
    "name,shape,steps,block", EXACT_CASES,
    ids=lambda v: "x".join(map(str, v)) if isinstance(v, tuple) else str(v))
def test_ring_kernel_is_bitwise_the_reference(name, shape, steps, block):
    """The ring kernel and the oracle sum the same terms in the same order,
    so however the shifted windows are built the output is equal to the
    last bit, not merely close."""
    c = planned(name).compile(backend="pallas")
    x = jnp.asarray(np.random.default_rng(4).standard_normal(shape),
                    jnp.float32)
    assert np.array_equal(c(x, steps, block), c.program.ref(x, steps))


def _assert_undersized_ring_diverges(name, shape):
    c = planned(name).compile(backend="pallas")
    steps = block = 8
    x = jnp.asarray(np.random.default_rng(2).standard_normal(shape),
                    jnp.float32)
    want = c.program.ref(x, steps)
    assert jnp.allclose(c(x, steps, block), want, **ATOL)
    bad_depth = c(x, steps, block, ring_depth=(steps + 1) // 2)
    assert not jnp.allclose(bad_depth, want, **ATOL)
    bad_halo = c(x, steps, block, halo=c.program.halo - 1)
    assert not jnp.allclose(bad_halo, want, **ATOL)


def test_undersized_generated_ring_diverges():
    """Compiling the ring with fewer levels than steps+1 (or a narrower halo
    than 2·radius) must corrupt the output — the negative direction of the
    generated-kernel path."""
    _assert_undersized_ring_diverges("jacobi-1d", (64,))


def test_undersized_generated_ring_diverges_on_rows():
    """The same on jacobi-2d, whose rows stream on the sublane axis."""
    _assert_undersized_ring_diverges("jacobi-2d", (32, 16))


def test_undersized_in_place_ring_diverges():
    """The same on seidel-2d, whose halo is one row (``radius``, not
    ``2·radius``): a ring of half the levels, or no halo at all, loses the
    row that the next block reads at both levels."""
    assert STENCIL_PROGRAMS["seidel-2d"].halo == 1
    _assert_undersized_ring_diverges("seidel-2d", (32, 20))


# ------------------------------------------------- in-place (seidel-2d) ----


def _seidel_loop(a, steps, dtype):
    """PolyBench's loop nest in ``dtype``: i then j, in place, every cell of
    the array updated, zero outside it (the repo's spec)."""
    p = np.zeros((a.shape[0] + 2, a.shape[1] + 2), dtype)
    p[1:-1, 1:-1] = a
    nine = dtype(9.0)
    for _ in range(steps):
        for i in range(1, a.shape[0] + 1):
            for j in range(1, a.shape[1] + 1):
                p[i, j] = (p[i - 1, j - 1] + p[i - 1, j] + p[i - 1, j + 1]
                           + p[i, j - 1] + p[i, j] + p[i, j + 1]
                           + p[i + 1, j - 1] + p[i + 1, j]
                           + p[i + 1, j + 1]) / nine
    return p[1:-1, 1:-1]


def _bench_seidel_step():
    import importlib.util
    import pathlib
    path = (pathlib.Path(__file__).resolve().parents[1] / "bench"
            / "configs" / "seidel-2d.py")
    spec = importlib.util.spec_from_file_location("bench_seidel_2d", path)
    module = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(module)
    return module.step


def _bench_seidel(a0, steps):
    step = _bench_seidel_step()
    a = a0
    for _ in range(steps):
        a = step(a)
    return a


#: a float32 reference against the loop: the scan regroups each row's
#: recurrence as products of powers of 1/9, in another order than the
#: loop's nine-term sums, so equality to the last bit is not expected; a
#: few float32 ulps of the terms (magnitude ≤ ~3, ulp ≈ 2.4e-7) part them,
#: and against the float64 loop the references' own rounding adds as much
SEIDEL_ATOL = 2e-6


@pytest.mark.parametrize("dtype", [np.float64, np.float32],
                         ids=["float64", "float32"])
@pytest.mark.parametrize("shape", [(6, 6), (9, 13)],
                         ids=lambda s: "x".join(map(str, s)))
@pytest.mark.parametrize("which", ["oracle", "bench"])
def test_seidel_references_match_polybench_loop(which, shape, dtype):
    steps = 3
    a = np.random.default_rng(7).standard_normal(shape)
    want = _seidel_loop(a.astype(dtype), steps, dtype)
    ref = (STENCIL_PROGRAMS["seidel-2d"].ref if which == "oracle"
           else _bench_seidel)
    got = np.asarray(ref(jnp.asarray(a, jnp.float32), steps))
    np.testing.assert_allclose(got, want, rtol=0, atol=SEIDEL_ATOL)


def test_lane_recurrence_spans_the_whole_width():
    """With decay 1 the recurrence is a running sum, whose first term
    weighs as much on the last lane as on the second: a scan cut to a
    window of lanes would lose it.  Widths on both sides of a power of
    two."""
    from repro.runtime.pallas_codegen import (_lane_recurrence,
                                              lane_scan_levels)
    assert [lane_scan_levels(w) for w in (1, 2, 3, 128, 129, 4000)] == \
        [0, 1, 2, 7, 8, 12]
    for width in (5, 128, 129, 4000):
        c = np.random.default_rng(width).integers(-4, 5, (2, width))
        got = _lane_recurrence(jnp.asarray(c, jnp.float32), 1.0)
        assert np.array_equal(np.asarray(got), np.cumsum(c, axis=1)), width


def _full_depth_scan(c, decay):
    """The Hillis–Steele scan with all ``⌈log2 W⌉`` levels, zero factors
    included: level ``d`` adds ``decay**(2**d)`` times ``x`` shifted
    ``2**d`` lanes right."""
    x = c
    for d in range((c.shape[-1] - 1).bit_length()):
        shifted = jnp.pad(x, ((0, 0), (2 ** d, 0)))[:, :c.shape[-1]]
        x = x + decay ** (2 ** d) * shifted
    return x


@pytest.mark.parametrize("scale", [1e-3, 1.0, 1e3])
@pytest.mark.parametrize("decay", [1 / 9, 0.5, 0.9, 1.0],
                         ids=["1/9", "0.5", "0.9", "1.0"])
@pytest.mark.parametrize("width", [20, 64, 65, 130, 256, 4000])
def test_lane_recurrence_equals_the_full_depth_scan(width, decay, scale):
    """Leaving out the levels whose float32 factor is 0.0 changes no bit:
    the truncated scan equals the full one under ``==``.  Both run op by
    op, each product and sum rounded on its own: jitted, XLA's CPU fusions
    of the two graphs may round a product-and-sum differently, which says
    nothing of the levels left out."""
    from repro.runtime.pallas_codegen import _lane_recurrence
    rng = np.random.default_rng(width)
    c = jnp.asarray(scale * rng.standard_normal((8, width)), jnp.float32)
    got = _lane_recurrence(c, decay)
    assert np.array_equal(np.asarray(got), np.asarray(_full_depth_scan(c, decay)))


@pytest.mark.parametrize("decay,levels", [(1 / 9, 6), (0.5, 8), (0.9, 10),
                                          (1.0, 12)],
                         ids=["1/9", "0.5", "0.9", "1.0"])
def test_lane_scan_levels_stop_at_the_first_zero_factor(decay, levels):
    """At W = 4000 the scan has 12 levels; ``decay**(2**d)`` is 0.0 in
    float32 from d = 6 for 1/9, d = 8 for 0.5 (2**-128 is subnormal, and
    kept) and d = 10 for 0.9; for 1.0 it never is."""
    from repro.runtime.pallas_codegen import lane_scan_levels
    assert lane_scan_levels(4000, decay) == levels
    assert np.float32(decay ** (2 ** (levels - 1))) != 0
    if levels < 12:
        assert np.float32(decay ** (2 ** levels)) == 0


def _count_mul(jaxpr) -> int:
    """``mul`` equations in ``jaxpr`` and the jaxprs nested in it."""
    count = 0
    for eqn in jaxpr.eqns:
        count += eqn.primitive.name == "mul"
        for param in eqn.params.values():
            inner = getattr(param, "jaxpr", param)
            if hasattr(inner, "eqns"):
                count += _count_mul(inner)
    return count


def test_seidel_update_emits_the_levels_dependent_steps_counts():
    """The seidel-2d update multiplies once per scan level and nowhere else
    (its 9-point sum divides), so its ``mul`` count at W = 4000 is the
    scan depth that ``dependent_steps`` charges each wavefront."""
    seidel = STENCIL_PROGRAMS["seidel-2d"]
    row = jnp.zeros((8, 4000), jnp.float32)
    muls = _count_mul(jax.make_jaxpr(seidel.update)(row, row, row).jaxpr)
    per_wavefront = seidel.dependent_steps((4000, 4000), 200, 8) // (525 * 207)
    assert muls == per_wavefront - 1 == 6


def test_dependent_steps_counts_the_chain():
    """Grid steps × the serial depth of one: ``steps`` for a Jacobi
    program, ``(steps + block − 1)`` wavefronts of one stencil sum and the
    emitted lane scan levels for an in-place one: at W = 4000, 6 of
    ``⌈log2 W⌉ = 12``, as seidel-2d's ``(1/9)**64`` is 0.0 in float32."""
    jacobi, seidel = STENCIL_PROGRAMS["jacobi-2d"], STENCIL_PROGRAMS["seidel-2d"]
    assert jacobi.dependent_steps((2800, 2800), 200, 8) == (350 + 25) * 200
    assert seidel.dependent_steps((4000, 4000), 200, 8) == \
        (500 + 25) * 207 * 7
    assert seidel.dependent_steps((16, 20), 8, 1) == (16 + 8) * 8 * 1 * 6


def _row_order_replay(update, a, steps):
    """An in-place program's levels with each level's rows in order: row
    ``i`` from the new row above and the old rows ``i`` and ``i + 1``, zero
    outside the array."""
    update = jax.jit(update)
    zero = jnp.zeros((1, a.shape[1]), jnp.float32)
    rows = [a[i:i + 1] for i in range(a.shape[0])] + [zero]
    for _ in range(steps):
        above = zero
        for i in range(a.shape[0]):
            rows[i] = above = update(above, rows[i], rows[i + 1])
    return jnp.concatenate(rows[:-1])


#: (block, steps, width): every block that divides ``steps`` (the skewed
#: writes stay block-aligned), so one step runs at block 1 alone
WAVEFRONT_CASES = [(block, steps, width)
                   for block in (1, 4, 8, 16) for steps in (1, 8, 16)
                   for width in (20, 130, 256) if steps % block == 0]


@pytest.mark.parametrize("block,steps,width", WAVEFRONT_CASES)
def test_in_place_wavefronts_are_bitwise_the_row_order(block, steps, width):
    """The ring kernel takes a block's rows along the diagonal wavefront,
    each row at its own level; every row gets the same update on the same
    inputs as in row order, so the output is equal to the last bit.

    One step is the exception, and not the schedule's: XLA's CPU compiler
    removes the kernel's one-trip time loop and then folds the lane scan's
    constant factors into products of powers of 1/9, which round otherwise
    (one step runs at block 1 alone, where a wavefront is one row: the row
    order itself).  That case is held to 2 ulps of the array's largest
    magnitude: its values near zero are differences of larger terms, so an
    ulp of each value says nothing."""
    c = planned("seidel-2d").compile(backend="pallas")
    x = jnp.asarray(np.random.default_rng(block * width + steps)
                    .standard_normal((32, width)), jnp.float32)
    want = np.asarray(_row_order_replay(c.program.update, x, steps))
    got = np.asarray(c(x, steps, block))
    if steps == 1:
        np.testing.assert_allclose(
            got, want, rtol=0, atol=2 * np.spacing(np.abs(want).max()))
    else:
        assert np.array_equal(got, want)


@pytest.mark.parametrize("change,shape", [({"radius": 2}, (16, 20)),
                                          ({"inner_rank": 0}, (32,))],
                         ids=["radius-2", "lane-axis"])
def test_in_place_ring_refuses_other_geometries(change, shape):
    """The wavefront schedule is written for rows on the sublane axis at
    radius 1; any other in-place geometry is refused by name."""
    from repro.runtime.pallas_codegen import CompiledStencil
    program = dataclasses.replace(STENCIL_PROGRAMS["seidel-2d"], **change)
    stencil = CompiledStencil(program, "fifo-ring")
    with pytest.raises(ValueError, match="seidel-2d: the in-place ring"):
        stencil(jnp.zeros(shape, jnp.float32), 8, 8)


def test_in_place_plan_selects_the_ring_and_refuses_addressable():
    """seidel-2d's compute channels are all planned as cheap FIFOs, so the
    plan alone selects the ring; the addressable step has no in-place form
    and refuses by name rather than sweep it as Jacobi does."""
    a = planned("seidel-2d")
    c = a.compile(backend="pallas")
    assert c.mode == "fifo-ring" and c.diagnostics["reorder_plans"] == []
    assert c.diagnostics["compute_plans"] == 16
    with pytest.raises(ValueError, match="seidel-2d.*addressable.*in-place"):
        a.compile(backend="pallas", mode="addressable")


def test_compile_mode_follows_the_plans():
    """The ChannelPlan records ARE the compiler's input: inject a
    reorder-buffer plan on a compute channel and the compiler must refuse
    the ring and fall back to addressable.  Memory (load/store) channels
    are exempt — they map to BlockSpec DMA, so jacobi-1d's pre-FIFOIZE
    out-of-order load channel does NOT force the fallback."""
    from repro.runtime.pallas_codegen import _memory_channels

    pre = analyze(get("jacobi-1d")).classify().size().plan()
    assert any(not p.is_cheap for p in pre.plans)       # load_A reorder plan
    assert pre.compile(backend="pallas").mode == "fifo-ring"

    a = planned("jacobi-1d")
    victim = next(p for p in a.plans if p.name not in _memory_channels(a))
    bad = dataclasses.replace(victim, lowering=REORDER_BUFFER)
    forced = dataclasses.replace(
        a, plans=tuple(bad if p.name == victim.name else p for p in a.plans))
    c = forced.compile(backend="pallas")
    assert c.mode == "addressable"
    assert c.diagnostics["reorder_plans"] == [victim.name]
    with pytest.raises(ValueError, match="reorder"):
        forced.compile(backend="pallas", mode="fifo-ring")
    # the fallback still computes the right answer (it just pays HBM)
    x = jnp.asarray(np.random.default_rng(3).standard_normal(32), jnp.float32)
    assert jnp.allclose(c(x, 4, 4), c.program.ref(x, 4), **ATOL)


def test_compile_requires_plan_stage_and_known_program():
    with pytest.raises(ValueError, match="plan"):
        analyze(get("jacobi-1d")).classify().compile(backend="pallas")
    with pytest.raises(KeyError, match="STENCIL_PROGRAMS"):
        planned("gemm").compile(backend="pallas")


def test_stencil_programs_mirror_registered_kernels():
    from repro.core.registry import kernel_names

    assert set(STENCIL_PROGRAMS) <= set(kernel_names())


# ------------------------------------------------------ line solves (adi) --


def _adi_loop(u0, steps):
    """PolyBench 4.2.1's ``kernel_adi`` loop nest in float64, statement by
    statement, with the constants of `adi_coefficients`."""
    from repro.kernels.stencil_bands.ref import adi_coefficients
    n = u0.shape[0]
    a, b, c, d, e, f = adi_coefficients(n)
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


def _rel_gap(got, want):
    got, want = np.asarray(got, np.float64), np.asarray(want, np.float64)
    return np.abs(got - want).max() / np.abs(want).max()


@pytest.mark.parametrize("shape,steps", [((6, 6), 3), ((11, 11), 4)],
                         ids=["6x6", "11x11"])
def test_adi_reference_matches_polybench_loop(shape, steps):
    """The float32 oracle, two sequential solves a sweep, against the C
    loop nest in float64: the right-hand sides take differences of terms
    ``2N`` to ``4N`` times the cells', so float32 rounding shows at a few
    1e-7 of the array's magnitude, not at its last bit."""
    u0 = np.random.default_rng(9).standard_normal(shape)
    got = STENCIL_PROGRAMS["adi"].ref(jnp.asarray(u0, jnp.float32), steps)
    assert _rel_gap(got, _adi_loop(u0, steps)) < 2e-6


#: the compiled sweeps against the float32 oracle, as a share of the
#: oracle's largest magnitude: the row sweep's log-step scans regroup each
#: recurrence's terms and multiply by reciprocals where the oracle
#: divides, so the two round apart; the largest gap over these cases,
#: seeds 0-2 and blocks 1-8, read 5.2e-6
ADI_REL_TOL = 5e-5


@pytest.mark.parametrize("n,steps,block", [(12, 4, 4), (12, 8, 1),
                                           (40, 4, 8), (40, 8, 4)])
def test_adi_matches_reference(n, steps, block):
    """adi through the normal path, in the mode its plan selects, equals
    the oracle: every sweep over the whole array, boundary rows and
    columns included."""
    c = planned("adi").compile(backend="pallas")
    assert c.mode == "addressable"
    x = jnp.asarray(np.random.default_rng(n + steps).standard_normal((n, n)),
                    jnp.float32)
    want = c.program.ref(x, steps)
    got = c(x, steps, block)
    assert got.shape == x.shape
    assert _rel_gap(got, want) < ADI_REL_TOL
    assert np.array_equal(got[0], x[0]) and np.array_equal(got[-1], x[-1])
    assert np.all(np.asarray(got[1:-1, [0, -1]]) == 1.0)


def _sequential(c, decay, reverse):
    """``x_j = c_j + decay_j·x_{j∓1}`` in float64, one lane after another."""
    x = np.zeros(c.shape)
    lanes = range(c.shape[-1])
    for j in (reversed(lanes) if reverse else lanes):
        k = j + 1 if reverse else j - 1
        before = x[..., k] if 0 <= k < c.shape[-1] else 0.0
        x[..., j] = c[..., j] + decay[j] * before
    return x


@pytest.mark.parametrize("reverse", [False, True], ids=["forward", "backward"])
@pytest.mark.parametrize("width", [5, 64, 130, 2000])
def test_affine_lane_scan_matches_the_sequential_recurrence(width, reverse):
    """The log-step scan with per-lane factors (`lane_scan_factors`)
    equals the recurrence run lane by lane, within float32 rounding of its
    terms, forward and backward; decays near 0.98 as adi's, so that every
    one of the ``⌈log2 W⌉`` levels counts."""
    from repro.runtime.pallas_codegen import (_lane_recurrence,
                                              lane_scan_factors)
    rng = np.random.default_rng(width)
    decay = rng.uniform(0.9, 0.99, width).astype(np.float32)
    c = rng.standard_normal((8, width)).astype(np.float32)
    factors = lane_scan_factors(decay, reverse)
    assert len(factors) == (width - 1).bit_length()
    got = _lane_recurrence(jnp.asarray(c), list(jnp.asarray(factors)),
                           reverse)
    want = _sequential(c.astype(np.float64), decay.astype(np.float64),
                       reverse)
    assert _rel_gap(got, want) < 1e-5


def test_lane_scan_factors_stop_at_the_first_zero_level():
    """A decay of 0 on a lane cuts every product that crosses it: with a
    zero on every other lane, level 1's factors are all 0.0 and the scan
    stops after level 0; one decay on every lane gives its powers, as
    `lane_scan_levels` counts them."""
    from repro.runtime.pallas_codegen import (lane_scan_factors,
                                              lane_scan_levels)
    decay = np.tile([0.5, 0.0], 8).astype(np.float32)
    assert len(lane_scan_factors(decay)) == 1
    assert len(lane_scan_factors(np.zeros(16, np.float32))) == 0
    flat = lane_scan_factors(np.full(4000, 1 / 9, np.float32))
    assert len(flat) == lane_scan_levels(4000, 1 / 9) == 6
    assert flat[3][-1] == pytest.approx((1 / 9) ** 8, rel=1e-6)


def test_adi_plan_selects_addressable_and_refuses_the_ring():
    """adi's reversed (``p``, ``q``) and transposed (``u``, ``v``) compute
    channels are planned as reorder buffers, so the plan alone selects the
    addressable mode, and the ring is refused by name."""
    a = planned("adi")
    c = a.compile(backend="pallas")
    assert c.mode == "addressable"
    assert set(c.diagnostics["reorder_plans"]) == ADI_OUT_OF_ORDER
    with pytest.raises(ValueError, match="reorder"):
        a.compile(backend="pallas", mode="fifo-ring")
    cheap = dataclasses.replace(a, plans=tuple(
        dataclasses.replace(p, lowering=FIFO_STREAM) for p in a.plans))
    with pytest.raises(ValueError, match="adi: line solves have no ring"):
        cheap.compile(backend="pallas")


def test_adi_dependent_steps_counts_the_chain():
    """Per step the column sweep's ``n − 2`` eliminated and ``n − 2``
    substituted rows, one after another, then one block of the row sweep:
    its right-hand side and both scans, all 11 levels each at n = 2000."""
    adi = STENCIL_PROGRAMS["adi"]
    assert adi.dependent_steps((2000, 2000), 100, 8) == \
        100 * (2 * 1998 + 1 + 11 + 11)
    assert adi.dependent_steps((12, 12), 4, 4) == 4 * (2 * 10 + 1 + 4 + 4)


@pytest.mark.parametrize("shape,block,match", [
    ((12, 16), 4, "square"), ((12, 12), 5, "divide")],
    ids=["not-square", "block"])
def test_adi_refuses_other_geometries(shape, block, match):
    c = planned("adi").compile(backend="pallas")
    with pytest.raises(ValueError, match=f"adi.*{match}"):
        c(jnp.zeros(shape, jnp.float32), 4, block)


# ------------------------------------------- Analysis.validate on pallas ---


@pytest.mark.parametrize("name", sorted(GEOMETRIES))
def test_validate_on_pallas_backend(name):
    v = planned(name).validate(backend="pallas").validation
    assert v.backend == "pallas"
    assert v.replays >= 1
    # non-FIFO verdicts were rejected by the VMEM FIFO ring in kernel
    assert any(FIFO_STREAM in row.rejected for row in v.channels
               if row.verdict != Pattern.FIFO.value and row.parts == 1) or \
        all(row.verdict == Pattern.FIFO.value or row.parts > 1
            for row in v.channels)


def test_validate_pallas_catches_wrong_plan():
    """Mirror of the reference-backend wrong-plan case: a FIFO ring planned
    for a non-FIFO channel must fail on the pallas backend too."""
    a = analyze(get("jacobi-1d")).classify().size(pow2=True).plan()
    broken = [p for p in a.plans if p.pattern_before != Pattern.FIFO.value
              and not p.split]
    assert broken
    bad = dataclasses.replace(broken[0], lowering=FIFO_STREAM)
    plans = tuple(bad if p.name == bad.name else p for p in a.plans)
    with pytest.raises(ValidationError, match="does not execute"):
        dataclasses.replace(a, plans=plans).validate(backend="pallas")


def test_validate_pallas_catches_undersized_buffers():
    a = analyze(get("jacobi-1d")).classify().fifoize().size(pow2=True)
    shrunk = {k: max(0, v - 1) for k, v in a.sizes.items()}
    with pytest.raises(ValidationError, match="exceeds"):
        dataclasses.replace(a, sizes=shrunk).validate(backend="pallas")
