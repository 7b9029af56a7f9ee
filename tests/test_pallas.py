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
}


@pytest.mark.parametrize("name", sorted(GEOMETRIES))
def test_generated_kernel_matches_reference(name):
    shape, steps, blocks = GEOMETRIES[name]
    c = planned(name).compile(backend="pallas", interpret=True)
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
    c = planned("jacobi-1d").compile(backend="pallas", interpret=True)
    x = jnp.asarray(np.random.default_rng(1).standard_normal(64), jnp.float32)
    got = c(x, steps, block)
    assert got.shape == x.shape
    assert jnp.allclose(got, c.program.ref(x, steps), **ATOL)


def test_undersized_generated_ring_diverges():
    """Compiling the ring with fewer levels than steps+1 (or a narrower halo
    than 2·radius) must corrupt the output — the negative direction of the
    generated-kernel path."""
    c = planned("jacobi-1d").compile(backend="pallas", interpret=True)
    steps = block = 8
    x = jnp.asarray(np.random.default_rng(2).standard_normal(64), jnp.float32)
    want = c.program.ref(x, steps)
    assert jnp.allclose(c(x, steps, block), want, **ATOL)
    bad_depth = c(x, steps, block, ring_depth=(steps + 1) // 2)
    assert not jnp.allclose(bad_depth, want, **ATOL)
    bad_halo = c(x, steps, block, halo=2 * c.program.radius - 1)
    assert not jnp.allclose(bad_halo, want, **ATOL)


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
    c = forced.compile(backend="pallas", interpret=True)
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
