"""Pallas codegen support: planned PPNs → fused VMEM-ring stencil kernels.

This module holds the *program* side of the ``"pallas"`` backend
(`runtime/pallas_backend.py` holds the per-channel trace-replay
implementations and registers both into the lowering registry).  It
generates the paper's Fig. 3 idiom for any band stencil: a time-tiled
stencil whose iteration space is blocked along one *streamed* spatial axis, with the dependences crossing the block boundary —
the channels the paper's SPLIT isolates at each depth — carried in a VMEM
scratch ring across the *sequential* Pallas grid.  In-block dependences
never leave VMEM/VREGs; the addressable-buffer fallback round-trips the
whole array per timestep instead (the FPGA FIFO-vs-buffer saving, restated
for the TPU memory hierarchy).

The generated geometry, for a stencil of radius ``r`` along the streamed
axis (items are cells for jacobi-1d, carried on the lane axis; rows for
jacobi-2d, on the sublane axis; planes for heat-3d; the skew is ``r``
cells per time step so tile writes stay block-aligned):

* ring level ``t`` holds the trailing ``2r`` items of the global item
  stream at time level ``t`` — block ``j`` deposits them, block ``j+1``
  consumes them;
* the ring has ``steps + 1`` levels; levels are addressed modulo
  ``ring_depth`` (default ``steps + 1``), so an *undersized* ring is a real
  ring-capacity failure (level ``t`` is clobbered before the next block
  reads it), not an index error — `tests/test_pallas.py` injects exactly
  that;
* blocks need ``r·steps ≡ 0 (mod block)`` so the skewed final row is
  block-aligned; ``r·steps / block`` extra flush blocks drain the tail.
  ``block = 1`` (the degenerate 1×…×1 tiling) is supported: the trailing
  halo then accumulates across several predecessor blocks.  Compiled for
  a TPU, the block must fill whole lanes (jacobi-1d: a multiple of 128) or
  whole sublane rows (jacobi-2d: a multiple of 8), and the ring must fit
  `VMEM_LIMIT_BYTES`; `CompiledStencil` refuses other geometries by name
  before lowering.

`compile_analysis` is the `Analysis.compile(backend="pallas")` entry point:
it reads the `.plan()` records, picks the VMEM-ring mode iff every planned
lowering is a stream/register (`is_cheap`), and binds the kernel's
*semantics* from the `STENCIL_PROGRAMS` table (the polyhedral spec carries
dataflow, not arithmetic — the update function is the one ingredient the
analysis cannot derive).
"""
from __future__ import annotations

import functools
import math
from dataclasses import dataclass, field
from typing import Callable, Dict, Optional, Tuple

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from .lowering import is_cheap


def default_interpret() -> bool:
    """True off-TPU: generated kernels run (and are CI-tested) through the
    Pallas interpreter; on a TPU host they compile for real."""
    return jax.default_backend() != "tpu"


# ---------------------------------------------------------------- programs --

@dataclass(frozen=True)
class StencilProgram:
    """The semantic half of a band-stencil kernel: what one time step
    computes.  ``update`` receives ``2·radius + 1`` arrays — the previous
    time level shifted by ``-radius … +radius`` along the streamed axis,
    each of shape ``(block,) + inner`` — and returns the new level.  Inner
    (non-streamed) axes are full-width; their boundary handling lives
    inside ``update`` (Dirichlet-zero, matching the `ref` oracle)."""

    name: str                                  # registry kernel it mirrors
    radius: int                                # dependence radius, streamed axis
    inner_rank: int                            # rank of one streamed item
    update: Callable[..., jnp.ndarray]
    ref: Callable[[jnp.ndarray, int], jnp.ndarray]   # pure-jnp oracle
    notes: str = ""


def _shift_inner(a: jnp.ndarray, axis: int, off: int) -> jnp.ndarray:
    """``a`` shifted by ``off`` along ``axis`` with Dirichlet-zero fill
    (jnp.pad-free: concatenation lowers cleanly in Pallas)."""
    if off == 0:
        return a
    pad_shape = list(a.shape)
    pad_shape[axis] = abs(off)
    zeros = jnp.zeros(pad_shape, a.dtype)
    if off > 0:      # neighbor at index - off
        body = jax.lax.slice_in_dim(a, 0, a.shape[axis] - off, axis=axis)
        return jnp.concatenate([zeros, body], axis=axis)
    body = jax.lax.slice_in_dim(a, -off, a.shape[axis], axis=axis)
    return jnp.concatenate([body, zeros], axis=axis)


def _jacobi1d_update(left, center, right):
    return (left + center + right) / 3.0


def _jacobi2d_update(up, center, down):
    jl = _shift_inner(center, -1, +1)
    jr = _shift_inner(center, -1, -1)
    return (center + jl + jr + up + down) / 5.0


def _heat3d_update(up, center, down):
    jl = _shift_inner(center, -2, +1)
    jr = _shift_inner(center, -2, -1)
    kl = _shift_inner(center, -1, +1)
    kr = _shift_inner(center, -1, -1)
    return (center
            + 0.125 * (up - 2.0 * center + down)
            + 0.125 * (jl - 2.0 * center + jr)
            + 0.125 * (kl - 2.0 * center + kr))


def _lazy_ref(module: str, fn: str):
    def call(a0, steps):
        import importlib
        return getattr(importlib.import_module(module), fn)(a0, steps)
    return call


#: kernel-registry name → band-stencil semantics.  The analysis plans the
#: channels; this table supplies the arithmetic the PPN does not carry.
STENCIL_PROGRAMS: Dict[str, StencilProgram] = {
    "jacobi-1d": StencilProgram(
        "jacobi-1d", radius=1, inner_rank=0, update=_jacobi1d_update,
        ref=_lazy_ref("repro.kernels.stencil_bands.ref", "jacobi_1d"),
        notes="3-point average; items are cells (paper Fig. 1/3)"),
    "jacobi-2d": StencilProgram(
        "jacobi-2d", radius=1, inner_rank=1, update=_jacobi2d_update,
        ref=_lazy_ref("repro.kernels.stencil_bands.ref", "jacobi_2d"),
        notes="5-point average; items are rows, j streams inside"),
    "heat-3d": StencilProgram(
        "heat-3d", radius=1, inner_rank=2, update=_heat3d_update,
        ref=_lazy_ref("repro.kernels.stencil_bands.ref", "heat_3d"),
        notes="7-point star; items are planes, (j,k) stream inside"),
}


# -------------------------------------------------------- fused ring kernel --

#: VMEM the generated kernels claim (`vmem_limit_bytes`): half of a TPU v5e
#: core's 128 MiB, leaving the rest to the compiler's own scratch.
VMEM_LIMIT_BYTES = 64 * 1024 * 1024
_SUBLANES, _LANES = 8, 128


def _vmem_bytes(shape: Tuple[int, ...]) -> int:
    """Bytes an f32 array of ``shape`` takes in VMEM, whose two minor
    dimensions are padded to the (8, 128) tile."""
    shape = (1,) * max(0, 2 - len(shape)) + tuple(shape)
    sub = -(-shape[-2] // _SUBLANES) * _SUBLANES
    lane = -(-shape[-1] // _LANES) * _LANES
    return 4 * math.prod(shape[:-2]) * sub * lane


def _check_vmem(what: str, need: int) -> None:
    if need > VMEM_LIMIT_BYTES:
        raise ValueError(
            f"{what} needs ~{need / 2**20:.1f} MiB of VMEM, over the "
            f"{VMEM_LIMIT_BYTES / 2**20:.0f} MiB the kernel may claim")


def _ring_kernel(x_ref, o_ref, ring_old, ring_new, *, block: int, steps: int,
                 nblocks: int, radius: int, halo: int, ring_depth: int,
                 n_items: int, axis: int, update: Callable):
    """One grid step = one block of the streamed ``axis``; the FIFO ring
    carries each time level's trailing ``halo`` items to the next block."""
    j = pl.program_id(0)

    # left of the domain is Dirichlet-zero: initialize the ring at block 0
    @pl.when(j == 0)
    def _init():
        ring_old[...] = jnp.zeros_like(ring_old)

    # this block's t=0 items; flush blocks (j >= nblocks) are all-zero
    row = jnp.where(j < nblocks, x_ref[...], jnp.zeros_like(x_ref[...]))

    # item index of row position s at time level t is  j·block − r·t + s
    ids = jax.lax.broadcasted_iota(
        jnp.int32, tuple(block if d == axis else 1 for d in range(row.ndim)),
        axis)

    def trailing(level, new):
        """The trailing ``halo`` items of the stream ending with ``new`` (for
        block < halo the window spans predecessor blocks — accumulate)."""
        if block < halo:
            new = jnp.concatenate([level, new], axis=axis)
        n = new.shape[axis]
        return jax.lax.slice_in_dim(new, n - halo, n, axis=axis)

    ring_new[0] = trailing(ring_old[0], row)

    def time_step(t, row):
        left = ring_old[(t - 1) % ring_depth]          # halo items
        prev_full = jnp.concatenate([left, row], axis=axis)
        if halo < 2 * radius:     # injected narrow halo: the missing items
            gone = list(left.shape)                    # are GONE
            gone[axis] = 2 * radius - halo
            prev_full = jnp.concatenate(
                [jnp.zeros(gone, row.dtype), prev_full], axis=axis)
        new_row = update(*[jax.lax.slice_in_dim(prev_full, k, k + block,
                                                axis=axis)
                           for k in range(2 * radius + 1)])
        idx = j * block - radius * t + ids
        new_row = jnp.where((idx >= 0) & (idx < n_items), new_row, 0.0)
        ring_new[t % ring_depth] = trailing(ring_old[t % ring_depth], new_row)
        return new_row

    row = jax.lax.fori_loop(1, steps + 1, time_step, row, unroll=False)

    # block j's final row covers items [(j − flush)·block, …); early blocks
    # write a dummy block 0 that block `flush` overwrites
    o_ref[...] = row

    # publish this block's ring levels for the next grid step
    ring_old[...] = ring_new[...]


def _addressable_step(x: jnp.ndarray, *, radius: int, axis: int,
                      update: Callable, interpret: bool) -> jnp.ndarray:
    """One time step as its own pallas_call over the WHOLE array — the
    addressable-buffer fallback: every step writes the full level back to
    HBM and reads it again (the paper's reorder-buffer cost model)."""

    def kernel(x_ref, o_ref):
        a = x_ref[...]
        shifts = [_shift_inner(a, axis, radius - k)
                  for k in range(2 * radius + 1)]
        o_ref[...] = update(*shifts)

    return pl.pallas_call(
        kernel,
        out_shape=jax.ShapeDtypeStruct(x.shape, jnp.float32),
        compiler_params=pltpu.CompilerParams(
            vmem_limit_bytes=VMEM_LIMIT_BYTES),
        interpret=interpret,
    )(x)


@dataclass
class CompiledStencil:
    """The executable `Analysis.compile(backend="pallas")` returns.

    ``mode`` is ``"fifo-ring"`` (fused kernel, channels in VMEM scratch) or
    ``"addressable"`` (per-timestep HBM round-trip — the fallback a
    reorder-buffer plan forces).  ``ring_depth`` / ``halo`` exist for the
    negative direction: compiling with fewer ring levels than ``steps + 1``
    (or a narrower halo than ``2·radius``) produces a kernel whose output
    provably diverges from the oracle — an undersized ring *fails*, it does
    not degrade gracefully.

    The streamed axis is axis 0 of the input.  A 1-D stream (jacobi-1d)
    rides the lane axis, as ``(1, n)``; a rank-2 input streams rows along
    the sublane axis.  Compiled for a TPU, a geometry the tiling or VMEM
    cannot hold raises ValueError before lowering; the interpreter has
    neither limit.
    """

    program: StencilProgram
    mode: str
    plans: Tuple = ()
    kernel_name: str = ""
    interpret: Optional[bool] = None
    diagnostics: Dict[str, object] = field(default_factory=dict)

    def ring_slots(self, steps: int) -> int:
        """Items held in one ring buffer: (steps+1) levels × 2r per level
        (each item is one channel value of inner shape)."""
        return (steps + 1) * 2 * self.program.radius

    def __call__(self, x: jnp.ndarray, steps: int, block: int,
                 interpret: Optional[bool] = None,
                 ring_depth: Optional[int] = None,
                 halo: Optional[int] = None) -> jnp.ndarray:
        interpret = (default_interpret() if interpret is None
                     else interpret) if self.interpret is None else (
                         self.interpret if interpret is None else interpret)
        p = self.program
        x = x.astype(jnp.float32)
        if len(x.shape) != p.inner_rank + 1:
            raise ValueError(f"{p.name}: expected rank {p.inner_rank + 1} "
                             f"input, got shape {x.shape}")
        lanes = p.inner_rank == 0
        a = x.reshape(1, -1) if lanes else x
        axis = 1 if lanes else 0
        if self.mode == "addressable":
            if not interpret:
                # input and output whole, plus the shifted copies in flight
                # (v5e compiler: 2560² f32 fits the limit, 2816² does not)
                _check_vmem(f"{p.name} addressable step over {x.shape}",
                            5 * _vmem_bytes(a.shape) // 2)
            step = functools.partial(_addressable_step, radius=p.radius,
                                     axis=axis, update=p.update,
                                     interpret=interpret)
            for _ in range(steps):      # deliberately NOT fused: one kernel
                a = step(a)             # launch + full-array round trip per t
            return a.reshape(x.shape)
        n_items = x.shape[0]
        if n_items % block:
            raise ValueError(f"n_items {n_items} % block {block} != 0")
        if (p.radius * steps) % block:
            raise ValueError(f"radius·steps ({p.radius * steps}) must be a "
                             f"multiple of block ({block}) so skewed writes "
                             f"stay block-aligned")
        nblocks = n_items // block
        flush = (p.radius * steps) // block
        depth = steps + 1 if ring_depth is None else ring_depth
        h = 2 * p.radius if halo is None else halo
        blk = (1, block) if lanes else (block,) + x.shape[1:]
        level = tuple(h if d == axis else s for d, s in enumerate(blk))
        if not interpret:
            self._check_tiling(blk, axis)
            ext = tuple(s + 2 * p.radius if d == axis else s
                        for d, s in enumerate(blk))
            _check_vmem(
                f"{p.name} ring of {depth} levels over blocks {blk}",
                2 * _vmem_bytes((depth,) + level) + 4 * _vmem_bytes(blk)
                + (2 * p.radius + 3) * _vmem_bytes(ext))

        def at(i):
            return tuple(i if d == axis else 0 for d in range(len(blk)))

        out = pl.pallas_call(
            functools.partial(
                _ring_kernel, block=block, steps=steps, nblocks=nblocks,
                radius=p.radius, halo=h, ring_depth=depth, n_items=n_items,
                axis=axis, update=p.update),
            grid=(nblocks + flush,),
            in_specs=[pl.BlockSpec(
                blk, lambda j: at(jnp.minimum(j, nblocks - 1)))],
            out_specs=pl.BlockSpec(blk, lambda j: at(jnp.maximum(j - flush,
                                                                 0))),
            out_shape=jax.ShapeDtypeStruct(a.shape, jnp.float32),
            scratch_shapes=[
                pltpu.VMEM((depth,) + level, jnp.float32),  # ring (read)
                pltpu.VMEM((depth,) + level, jnp.float32),  # ring (write)
            ],
            compiler_params=pltpu.CompilerParams(
                vmem_limit_bytes=VMEM_LIMIT_BYTES),
            interpret=interpret,
        )(a)
        return out.reshape(x.shape)

    def _check_tiling(self, blk: Tuple[int, ...], axis: int) -> None:
        """The TPU tiling rules a compiled block must meet: the streamed
        block fills whole lanes (1-D) or whole sublane rows (rank 2)."""
        name, block = self.program.name, blk[axis]
        if axis == len(blk) - 1 and block % _LANES:
            raise ValueError(
                f"{name} streams along the lane axis: block {block} must be "
                f"a multiple of {_LANES} when compiled for a TPU")
        if axis == len(blk) - 2 and block % _SUBLANES:
            raise ValueError(
                f"{name} streams along the sublane axis: block {block} must "
                f"be a multiple of {_SUBLANES} when compiled for a TPU")

    def describe(self) -> str:
        return (f"CompiledStencil[{self.program.name}] mode={self.mode} "
                f"radius={self.program.radius} "
                f"plans={len(self.plans)} ({self.diagnostics})")


def _memory_channels(analysis) -> frozenset:
    """Names of channels touching a load/store (memory) process.  In the
    generated kernel these are served by `BlockSpec` index maps — HBM DMA,
    addressable by nature — so their verdicts never force the addressable
    *compute* mode; only compute↔compute channels decide ring vs. buffer."""
    mem = lambda p: p.startswith(("load", "store"))
    return frozenset(ch.name for ch in analysis.ppn.channels
                     if mem(ch.producer) or mem(ch.consumer))


def compile_analysis(analysis, mode: Optional[str] = None,
                     interpret: Optional[bool] = None) -> CompiledStencil:
    """The pallas backend's `Backend.compile` hook.

    Requires a `.plan()` stage: the ChannelPlan records decide the mode —
    the fused VMEM-ring kernel iff every compute↔compute lowering is served
    by a stream/register (`is_cheap`; load/store-process channels map to
    `BlockSpec` DMA and are exempt), else the addressable per-timestep
    fallback.  ``mode`` forces one (the benchmark measures both)."""
    if analysis.plans is None:
        raise ValueError("compile() needs the .plan() stage: run "
                         "analyze(...).classify().fifoize().size().plan() "
                         "first — the ChannelPlan records ARE the input")
    name = analysis.ppn.kernel_name
    program = STENCIL_PROGRAMS.get(name)
    if program is None:
        raise KeyError(
            f"no pallas stencil program for kernel {name!r} "
            f"(have: {sorted(STENCIL_PROGRAMS)}) — the PPN carries dataflow, "
            f"not arithmetic; register the update in STENCIL_PROGRAMS")
    memory = _memory_channels(analysis)
    compute_plans = [p for p in analysis.plans if p.name not in memory]
    cheap = all(p.is_cheap for p in compute_plans)
    expensive = [p.name for p in compute_plans if not p.is_cheap]
    if mode is None:
        mode = "fifo-ring" if cheap else "addressable"
    if mode == "fifo-ring" and not cheap:
        raise ValueError(
            f"{name}: cannot compile the VMEM-ring kernel — plan(s) "
            f"{expensive} need the addressable reorder buffer (run "
            f".fifoize() first, or compile mode='addressable')")
    if mode not in ("fifo-ring", "addressable"):
        raise ValueError(f"unknown mode {mode!r}")
    return CompiledStencil(
        program=program, mode=mode, plans=tuple(analysis.plans),
        kernel_name=name, interpret=interpret,
        diagnostics={"cheap_plans": sum(p.is_cheap for p in compute_plans),
                     "compute_plans": len(compute_plans),
                     "memory_plans": len(memory),
                     "reorder_plans": expensive})
