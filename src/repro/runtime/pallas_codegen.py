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
jacobi-2d and seidel-2d, on the sublane axis; planes for heat-3d; the skew
is ``r`` items per time step so tile writes stay block-aligned):

* ring level ``t`` holds the trailing ``halo`` items of the global item
  stream at time level ``t`` — block ``j`` deposits them, block ``j+1``
  consumes them.  A Jacobi program computes level ``t`` from level
  ``t − 1`` alone, items up to ``2r`` positions back: ``halo = 2r``, and a
  block takes ``steps`` time steps, the whole block at one level each.  An
  in-place (Gauss-Seidel) program reads the ``r`` items before its own at
  level ``t`` and items ``0 … +r`` of level ``t − 1``, which lie up to
  ``r`` positions back: ``halo = r``.  For ``r = 1`` those are, in the
  block's skewed positions, item ``s − 1`` at ``t`` and items ``s − 1``
  and ``s`` at ``t − 1``, all on earlier anti-diagonals ``s + t``: so a
  block takes ``steps + block − 1`` wavefronts, item ``s`` going to level
  ``w − s`` in wavefront ``w``, and its rows share the sublanes.  The
  kernel is written for that geometry alone, rows on the sublane axis at
  radius 1;
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
  whole sublane rows (rank 2: a multiple of 8), and the ring must fit
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
import numpy as np
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
    computes.  ``update`` receives ``2·radius + 1`` arrays — the item
    stream shifted by ``-radius … +radius`` along the streamed axis — and
    returns the new items.  For a Jacobi program all of them are the
    previous time level, each of shape ``(block,) + inner``.  An
    ``in_place`` program sweeps in stream order, as Gauss-Seidel does: the
    ``radius`` inputs before the item are already at the level being
    computed, the rest still at the previous one.  Its ``update`` gets a
    block's diagonal, each input of shape ``(block,) + inner`` with every
    item at its own level, so it must not mix items.  Inner
    (non-streamed) axes are full-width; their boundary handling, and any
    same-level dependence along them, lives inside ``update``
    (Dirichlet-zero, matching the `ref` oracle)."""

    name: str                                  # registry kernel it mirrors
    radius: int                                # dependence radius, streamed axis
    inner_rank: int                            # rank of one streamed item
    update: Callable[..., jnp.ndarray]
    ref: Callable[[jnp.ndarray, int], jnp.ndarray]   # pure-jnp oracle
    in_place: bool = False
    #: the decay of an in-place program's same-level recurrence along the
    #: lanes, ``x_j = c_j + lane_decay·x_{j−1}``, which its ``update`` runs
    #: as `_lane_recurrence`; it sets how many scan levels the kernel emits
    lane_decay: float = 1.0
    #: the ``pallas_call``'s name, which the device trace shows; None keeps
    #: JAX's default, the name of the jitted caller
    trace_name: Optional[str] = None

    @property
    def halo(self) -> int:
        """Items of each level that a block hands the next: how far back of
        its own position an item reads (the skew is ``radius`` a level)."""
        return self.radius if self.in_place else 2 * self.radius

    def dependent_steps(self, shape: Tuple[int, ...], steps: int,
                        block: int) -> int:
        """The longest chain of dependent vector steps in one call of the
        generated ring kernel over ``shape``, over its grid steps
        (``shape[0] / block`` blocks and ``radius·steps / block`` flush
        blocks).  A Jacobi grid step is ``steps`` time steps of one vector
        step each: the block's items at once.  An in-place one is ``steps +
        block − 1`` wavefronts, each one stencil sum and then the levels
        the lane recurrence emits over the width ``W = shape[-1]``,
        `lane_scan_levels` of ``W`` and ``lane_decay``: ``(steps + block −
        1) · (1 + L)``, with ``L`` at most ``⌈log2 W⌉`` and fewer where
        ``lane_decay``'s powers reach 0.0 in float32 (seidel-2d at W = 4000:
        6 of 12).  Of a wavefront's items, ``steps / (steps + block − 1)``
        do work on average; the rest wait for the diagonal to reach or
        leave them."""
        grid = (shape[0] + self.radius * steps) // block
        if not self.in_place:
            return grid * steps
        levels = lane_scan_levels(shape[-1], self.lane_decay)
        return grid * (steps + block - 1) * (1 + levels)


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


def lane_scan_levels(width: int, decay: float = 1.0) -> int:
    """Levels of a log-step scan across ``width`` lanes whose factor
    ``decay**(2**d)`` is nonzero in float32: shifts of 1, 2, 4, … lanes
    reach ``2**levels − 1 ≥ width − 1`` lanes back, unless the factor of a
    level rounds to exactly 0.0 first, as every higher power then does.  A
    subnormal factor is not zero, and its level stays."""
    levels = 0
    while (levels < (width - 1).bit_length()
           and np.float32(decay ** (2 ** levels)) != 0):
        levels += 1
    return levels


def _lane_recurrence(c: jnp.ndarray, decay: float) -> jnp.ndarray:
    """``x_j = c_j + decay·x_{j−1}`` along the last axis, ``x_{−1} = 0``,
    as a log-step (Hillis–Steele) scan: after level ``d`` each ``x_j``
    holds ``Σ_{m < 2^(d+1)} decay^m c_{j−m}``.  Over the whole width that
    takes ``⌈log2 W⌉`` levels, and no window of lanes may be left out: with
    decay 1 the first term weighs on the last lane as on the second.  The
    loop stops only at the first level whose float32 factor
    ``decay^(2^d)`` is exactly 0.0 (`lane_scan_levels`): that level and
    every later one would add ``0.0·shift(x)``, which for finite ``x``
    changes no bit of ``x`` but a −0 turned to +0.  So the result is the
    full scan's, less multiplications by an exact zero (seidel-2d's 1/9
    reaches 0.0 at ``2^6``: 6 levels of 12 at W = 4000)."""
    x = c
    for d in range(lane_scan_levels(c.shape[-1], decay)):
        x = x + decay ** (2 ** d) * _shift_inner(x, -1, 2 ** d)
    return x


#: seidel-2d's weight of ``A[i][j−1]`` in its 9-point average: the decay of
#: its lane recurrence, read by `_seidel2d_update` and by the program's
#: ``lane_decay`` (and so by ``dependent_steps``)
_SEIDEL_DECAY = 1.0 / 9.0


def _seidel2d_update(up, center, down):
    """Row i from row i−1 at the new level (``up``) and rows i, i+1 at the
    previous one: the 9-point average, whose ``A[i][j−1]`` is new too, so
    ``x_j = (c_j + x_{j−1}) / 9`` with ``c`` the other eight terms."""
    c = (_shift_inner(up, -1, +1) + up + _shift_inner(up, -1, -1)
         + center + _shift_inner(center, -1, -1)
         + _shift_inner(down, -1, +1) + down + _shift_inner(down, -1, -1))
    return _lane_recurrence(c / 9.0, _SEIDEL_DECAY)


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
        ref=_lazy_ref("repro.kernels.stencil_bands.ref", "jacobi_1d")),
    "jacobi-2d": StencilProgram(
        "jacobi-2d", radius=1, inner_rank=1, update=_jacobi2d_update,
        ref=_lazy_ref("repro.kernels.stencil_bands.ref", "jacobi_2d")),
    "heat-3d": StencilProgram(
        "heat-3d", radius=1, inner_rank=2, update=_heat3d_update,
        ref=_lazy_ref("repro.kernels.stencil_bands.ref", "heat_3d")),
    "seidel-2d": StencilProgram(
        "seidel-2d", radius=1, inner_rank=1, update=_seidel2d_update,
        ref=_lazy_ref("repro.kernels.stencil_bands.ref", "seidel_2d"),
        in_place=True, lane_decay=_SEIDEL_DECAY,
        trace_name="seidel_2d_ring"),
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


def _gone(left, reach: int, axis: int):
    """``left`` with zero items in front up to ``reach`` items: what an
    injected narrow halo leaves out is GONE."""
    halo = left.shape[axis]
    if halo >= reach:
        return left
    gone = list(left.shape)
    gone[axis] = reach - halo
    return jnp.concatenate([jnp.zeros(gone, left.dtype), left], axis=axis)


def _shifted_windows(left, row, *, reach: int, axis: int):
    """The previous level shifted back by ``reach … 0`` items along
    ``axis`` (a Jacobi program's offsets ``-r … +r``, ``reach = 2r``; an
    in-place one's ``0 … +r``, ``reach = r``): window ``k`` is items ``k …
    k + block − 1`` of ``left ++ row``, the ring's halo items then the
    block.  Each is built from ``left``'s tail and ``row``'s head, never
    sliced out of the joined value: on the sublane axis that value would
    start ``reach`` sublanes into a tile, and every window cut from it
    would span two vregs per tile of ``row``."""
    left = _gone(left, reach, axis)
    block, halo = row.shape[axis], left.shape[axis]
    wins = []
    for k in range(reach + 1):
        if k == halo:                 # the block itself
            wins.append(row)
            continue
        tail = jax.lax.slice_in_dim(left, k, min(halo, k + block), axis=axis)
        m = k + block - halo          # the window's items that are row's
        wins.append(tail if m <= 0 else jnp.concatenate(
            [tail, jax.lax.slice_in_dim(row, 0, m, axis=axis)], axis=axis))
    return wins


def _ring_kernel(x_ref, o_ref, ring_old, ring_new, *, program: StencilProgram,
                 block: int, steps: int, nblocks: int, halo: int,
                 ring_depth: int, n_items: int, axis: int):
    """One grid step = one block of the streamed ``axis``; the FIFO ring
    carries each time level's trailing ``halo`` items to the next block
    (fewer than ``program.halo`` is an injected narrow halo)."""
    radius, update = program.radius, program.update
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

    def in_domain(t):
        idx = j * block - radius * t + ids
        return (idx >= 0) & (idx < n_items)

    def time_step(t, row):
        left = ring_old[(t - 1) % ring_depth]          # halo items
        wins = _shifted_windows(left, row, reach=program.halo, axis=axis)
        new_row = update(*wins)
        new_row = jnp.where(in_domain(t), new_row, 0.0)
        ring_new[t % ring_depth] = trailing(ring_old[t % ring_depth], new_row)
        return new_row

    def wavefront(w, state):
        """Wavefront ``w`` of an in-place program (radius 1): item ``s``
        goes to level ``t = w − s`` where ``1 ≤ t ≤ steps``.  It reads the
        item before it at ``t`` (``cur``, one item on, the ring's level
        ``w`` in front), its own at ``t − 1`` (``prev``, after wavefront
        ``w − 2``, likewise with level ``w − 1``) and the one after it at
        ``t − 1`` (``cur`` as it is): all on earlier wavefronts, so the
        block's items go at once."""
        cur, prev = state
        up = _shifted_windows(ring_old[w % ring_depth], cur, reach=radius,
                              axis=axis)[0]
        center = _shifted_windows(ring_old[(w - 1) % ring_depth], prev,
                                  reach=radius, axis=axis)[0]
        t = w - ids
        new = jnp.where(in_domain(t), update(up, center, cur), 0.0)
        new = jnp.where((t >= 1) & (t <= steps), new, cur)
        # the block's last item reaches level w − block + 1; before level 1
        # it still holds level 0, which level 0's slot already has
        ring_new[jnp.maximum(w - block + 1, 0) % ring_depth] = \
            jax.lax.slice_in_dim(new, block - halo, block, axis=axis)
        return new, cur

    if program.in_place:
        row, _ = jax.lax.fori_loop(1, steps + block, wavefront, (row, row),
                                   unroll=False)
    else:
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
    diagnostics: Dict[str, object] = field(default_factory=dict)

    def __call__(self, x: jnp.ndarray, steps: int, block: int,
                 interpret: Optional[bool] = None,
                 ring_depth: Optional[int] = None,
                 halo: Optional[int] = None) -> jnp.ndarray:
        interpret = default_interpret() if interpret is None else interpret
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
        if p.in_place and (p.radius != 1 or lanes):
            raise ValueError(
                f"{p.name}: the in-place ring kernel runs its wavefronts on "
                f"rows along the sublane axis at radius 1; got radius "
                f"{p.radius}{', items on the lane axis' if lanes else ''}")
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
        h = p.halo if halo is None else halo
        blk = (1, block) if lanes else (block,) + x.shape[1:]
        level = tuple(h if d == axis else s for d, s in enumerate(blk))
        if not interpret:
            self._check_tiling(blk, axis)
            ext = tuple(s + p.halo if d == axis else s
                        for d, s in enumerate(blk))
            _check_vmem(
                f"{p.name} ring of {depth} levels over blocks {blk}",
                2 * _vmem_bytes((depth,) + level) + 4 * _vmem_bytes(blk)
                + (p.halo + 3) * _vmem_bytes(ext))

        def at(i):
            return tuple(i if d == axis else 0 for d in range(len(blk)))

        out = pl.pallas_call(
            functools.partial(
                _ring_kernel, program=p, block=block, steps=steps,
                nblocks=nblocks, halo=h, ring_depth=depth, n_items=n_items,
                axis=axis),
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
            name=p.trace_name,
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


def compile_analysis(analysis, mode: Optional[str] = None) -> CompiledStencil:
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
    if mode == "addressable" and program.in_place:
        raise ValueError(
            f"{name}: the addressable mode has no in-place step — its "
            f"whole-array kernel would sweep {name} as Jacobi does; only "
            f"the fifo-ring mode runs an in-place program")
    return CompiledStencil(
        program=program, mode=mode, plans=tuple(analysis.plans),
        diagnostics={"cheap_plans": sum(p.is_cheap for p in compute_plans),
                     "compute_plans": len(compute_plans),
                     "memory_plans": len(memory),
                     "reorder_plans": expensive})
