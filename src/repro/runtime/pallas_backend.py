"""The ``"pallas"`` backend: lowering IR executed as Pallas kernels.

Where the ``"reference"`` backend replays a channel's trace with numpy array
ops, this backend replays the SAME trace through a ring of slots addressed
by a Pallas kernel — compiled on a TPU, interpret-mode elsewhere, so CI
exercises it everywhere.  Each event touches one slot, so events and ring
are SMEM words; a trace too long for SMEM raises `TraceTooLong`:

* the ppermute family (`FIFO_STREAM` and both split variants) and the
  broadcast register run the trace's push/pop/retire events against a ring
  of ``slots`` words, checking *in kernel* that every pop finds the
  value it expects (an undersized ring gets clobbered and fails as
  `RingOverflow` — the negative direction `Analysis.validate` demands) and
  that the pop order is one the structure can serve (violations surface as
  the same `OrderViolation` the reference backend raises, so the validator's
  negative checks work unchanged on this backend);
* the reorder buffer runs the same kernel with order checking disabled —
  addressable slots, any pop order, still capacity-checked.

Event lists are built host-side from the dense-rank trace
(`simulator.trace_channel`): pushes at key ``2·w_rank + 1``, retires at
``2·last_read``, pops at ``2·r_rank``, sorted by ``(key, kind)`` with
push < pop < retire at equal key — the exact sweep semantics of
`ChannelTrace.peak_occupancy`.  Edges the sequential linearization cannot
serialize (``late_edges``: a pop ranked at/before its push — self-timed in
reality) get their push forced early to ``min(2·w_rank+1, 2·first_read)``
so the kernel can still serve them; the reported peak then comes from the
host sweep, matching the reference backend's accounting.

Ring slots are assigned host-side by greedy interval allocation (optimal:
max-live slots suffice), then folded modulo the ring size — so compiling
with fewer slots than peak occupancy provably collides instead of silently
widening the buffer.

The whole-PPN compiler (`Backend.compile` hook → `Analysis.compile`) lives
in `runtime.pallas_codegen`; this module wires it to the registry.
"""
from __future__ import annotations

import functools
import heapq
from dataclasses import dataclass
from typing import Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from .lowering import (BROADCAST_REGISTER, CHUNK_SPLIT, DEPTH_SPLIT,
                       FIFO_STREAM, REORDER_BUFFER, ChannelLowering,
                       register_backend)
from .pallas_codegen import compile_analysis, default_interpret
from .simulator import ChannelTrace, OrderViolation, SimulationError

# event kinds (host-built, executed in kernel order)
_PUSH, _POP, _RETIRE, _NOOP = 0, 1, 2, 3
# order disciplines (static kernel parameter)
_FIFO, _REGISTER, _REORDER = 0, 1, 2


class RingOverflow(SimulationError):
    """The replay ring was too small for the trace: a push clobbered a live
    slot, or a pop read back a value the ring no longer held."""


@dataclass(frozen=True)
class _EventList:
    """A channel trace lowered to ring operations, in replay order."""

    kind: np.ndarray       # _PUSH/_POP/_RETIRE per event
    value: np.ndarray      # push position the event concerns
    slot: np.ndarray       # greedy-allocated ring slot (pre-modulo)
    needed: int            # slots a collision-free replay requires


def _build_events(trace: ChannelTrace) -> _EventList:
    """Lower the dense-rank trace to a push/pop/retire event list with
    host-assigned ring slots.  Values are identified by PUSH POSITION
    (write-rank order), the identity `trace.pops` already uses."""
    nv, ne = trace.num_values, trace.num_edges
    # push position <-> value id (per-process ranks are strictly ordered,
    # so value_wrank has no ties and this is a bijection)
    order = np.argsort(trace.value_wrank, kind="stable")
    pos_of_value = np.empty(nv, dtype=np.int64)
    pos_of_value[order] = np.arange(nv)
    wrank_by_pos = trace.value_wrank[order]
    last_read_by_pos = trace.value_last_read[order]
    # pops arrive in consumer-rank order; their keys are the sorted r_ranks
    pop_keys = 2 * np.sort(trace.r_rank, kind="stable")
    first_read_by_pos = np.full(nv, np.iinfo(np.int64).max)
    np.minimum.at(first_read_by_pos, trace.pops, pop_keys)
    # late edges: force the push early enough to serve its first pop
    push_keys = np.minimum(2 * wrank_by_pos + 1, first_read_by_pos)
    retire_keys = 2 * last_read_by_pos
    kind = np.concatenate([np.full(nv, _PUSH), np.full(ne, _POP),
                           np.full(nv, _RETIRE)]).astype(np.int64)
    value = np.concatenate([np.arange(nv), trace.pops,
                            np.arange(nv)]).astype(np.int64)
    key = np.concatenate([push_keys, pop_keys, retire_keys])
    perm = np.lexsort((kind, key))           # push < pop < retire at a tie
    kind, value = kind[perm], value[perm]
    # greedy interval allocation: lowest free slot at push, freed at retire
    slot = np.zeros(len(kind), dtype=np.int64)
    free: list = []
    top = 0
    held = np.empty(nv, dtype=np.int64)
    needed = 0
    for i, (k, v) in enumerate(zip(kind, value)):
        if k == _PUSH:
            s = heapq.heappop(free) if free else top
            if s == top:
                top += 1
            held[v] = s
            needed = max(needed, s + 1)
        elif k == _RETIRE:
            heapq.heappush(free, held[v])
        slot[i] = held[v]
    return _EventList(kind, value, slot, max(1, needed))


def _pow2(n: int) -> int:
    return 1 << max(0, int(n - 1).bit_length())


#: SMEM the replay kernel may fill: a TPU v5e core has 1 MiB of scalar
#: memory, less a few KiB the compiler keeps; the three event arrays, the
#: ring and the counters live there.
SMEM_LIMIT_BYTES = 1024 * 1024 - 16 * 1024


class TraceTooLong(ValueError):
    """The trace's events and ring do not fit the replay kernel's SMEM."""


def _replay_kernel(kind_ref, value_ref, slot_ref, o_ref, ring, *,
                   n_events: int, order_op: int):
    """Execute the event list against a ring of scalar slots, counting
    every way the structure can fail.  ``order_op`` is the pop discipline:
    _FIFO rejects any pop that is not the next push position, _REGISTER any
    regression, _REORDER nothing (addressable).  Events and ring are SMEM
    words: each event touches one slot, which vector memory cannot address
    without a whole tile."""

    def clear(i, carry):
        ring[i] = jnp.int32(-1)
        return carry

    jax.lax.fori_loop(0, ring.shape[0], clear, 0)

    def body(e, state):
        live, peak, last_p, order_v, mism, ovf, unf = state
        k, v, s = kind_ref[e], value_ref[e], slot_ref[e]
        cur = ring[s]
        is_push = (k == _PUSH).astype(jnp.int32)
        is_pop = (k == _POP).astype(jnp.int32)
        is_retire = (k == _RETIRE).astype(jnp.int32)
        # push: the slot must be free, else the ring is undersized
        ovf = ovf + is_push * (cur != -1).astype(jnp.int32)
        # pop: the slot must still hold the value this edge consumes
        unf = unf + is_pop * (cur == -1).astype(jnp.int32)
        mism = mism + is_pop * ((cur != v) & (cur != -1)).astype(jnp.int32)
        if order_op == _FIFO:          # head-only, consumed exactly once
            bad = (v <= last_p).astype(jnp.int32)
        elif order_op == _REGISTER:    # front re-readable, no regression
            bad = (v < last_p).astype(jnp.int32)
        else:
            bad = jnp.int32(0)
        order_v = order_v + is_pop * bad
        last_p = jnp.where(is_pop == 1, jnp.maximum(last_p, v), last_p)
        ring[s] = jnp.where(is_push == 1, v,
                            jnp.where(is_retire == 1, -1, cur))
        live = live + is_push - is_retire
        peak = jnp.maximum(peak, live)
        return live, peak, last_p, order_v, mism, ovf, unf

    zero = jnp.int32(0)
    init = (zero, zero, jnp.int32(-1), zero, zero, zero, zero)
    out = jax.lax.fori_loop(0, n_events, body, init, unroll=False)
    live, peak, _, order_v, mism, ovf, unf = out
    for i, x in enumerate((peak, order_v, mism, ovf, unf, live)):
        o_ref[i] = x


@functools.lru_cache(maxsize=None)
def _replay_call(n_events: int, ring_size: int, order_op: int,
                 interpret: bool):
    """Compiled replay kernel, cached on the pow2-padded shape bucket so
    channels of similar size share one compilation."""
    need = 4 * (3 * n_events + ring_size + 6)
    if need > SMEM_LIMIT_BYTES:
        raise TraceTooLong(
            f"{n_events} events and a {ring_size}-slot ring need {need} "
            f"bytes of SMEM, over the {SMEM_LIMIT_BYTES} the replay kernel "
            f"may fill")
    smem = pl.BlockSpec(memory_space=pltpu.SMEM)
    return jax.jit(pl.pallas_call(
        functools.partial(_replay_kernel, n_events=n_events,
                          order_op=order_op),
        out_shape=jax.ShapeDtypeStruct((6,), jnp.int32),
        in_specs=[smem, smem, smem],
        out_specs=smem,
        scratch_shapes=[pltpu.SMEM((ring_size,), jnp.int32)],
        interpret=interpret,
    ))


class _VmemReplay(ChannelLowering):
    """Shared machinery: lower the trace to events, run the kernel, raise."""

    order_op: int = _REORDER

    def run(self, trace: ChannelTrace, slots: Optional[int] = None,
            interpret: Optional[bool] = None) -> int:
        if trace.num_edges == 0:
            return 0
        if interpret is None:
            interpret = default_interpret()
        ev = _build_events(trace)
        nslots = ev.needed if slots is None else max(1, int(slots))
        n = len(ev.kind)
        n_pad = _pow2(n)
        pad = n_pad - n
        kind = np.concatenate([ev.kind, np.full(pad, _NOOP)])
        value = np.concatenate([ev.value, np.zeros(pad, dtype=np.int64)])
        slot = np.concatenate([ev.slot % nslots, np.zeros(pad,
                                                          dtype=np.int64)])
        call = _replay_call(n_pad, _pow2(nslots), self.order_op,
                            bool(interpret))
        peak, order_v, mism, ovf, unf = (int(x) for x in np.asarray(
            call(jnp.asarray(kind, jnp.int32), jnp.asarray(value, jnp.int32),
                 jnp.asarray(slot, jnp.int32)))[:5])
        if order_v:
            raise OrderViolation(
                trace.channel,
                f"{order_v} pop(s) the {self.lowering!r} ring cannot "
                f"serve (pop order violates the structure's discipline)")
        if ovf or mism or unf:
            raise RingOverflow(
                trace.channel,
                f"ring of {nslots} slot(s) too small for the trace: "
                f"{ovf} clobbering push(es), {mism} corrupted pop(s), "
                f"{unf} pop(s) from an empty slot "
                f"(needs {ev.needed} slots)")
        # forced-early pushes (self-timed edges) inflate the kernel's live
        # counter; report the sequential-schedule peak the validator checks
        return peak if trace.late_edges == 0 else trace.peak_occupancy()


PALLAS = register_backend("pallas")


@PALLAS.register(FIFO_STREAM, DEPTH_SPLIT, CHUNK_SPLIT)
class VmemRingFifo(_VmemReplay):
    """FIFO verdicts: a VMEM scratch ring carried across the sequential
    grid, popped strictly in push order (the generated-kernel idiom of
    `pallas_codegen`; split variants are the same ring per part)."""

    order_op = _FIFO

    def step(self, h, axis: str, stage, n: int):
        from ..comm.channels import fifo_shift
        return fifo_shift(h, axis, 1, wrap=True)


@PALLAS.register(BROADCAST_REGISTER)
class CarriedRegister(_VmemReplay):
    """In-order+multiplicity: the front value stays readable (a carried
    VREG broadcast); only regression past the stream head fails."""

    order_op = _REGISTER

    def step(self, h, axis: str, stage, n: int):
        from ..comm.channels import fifo_shift
        return fifo_shift(h, axis, 1, wrap=True)


@PALLAS.register(REORDER_BUFFER)
class AddressableVmem(_VmemReplay):
    """Out-of-order: addressable VMEM scratch sized by `Analysis.size()`
    slots — any pop order, capacity still enforced."""

    order_op = _REORDER

    def step(self, h, axis: str, stage, n: int):
        from ..comm.channels import reorder_buffer_read
        return reorder_buffer_read(h, axis, (stage - 1) % n)


# whole-PPN compiler: Analysis.compile(backend="pallas") resolves here
PALLAS.compile = compile_analysis


# ------------------------------------------------------------ timing hook ---

def measure_compiled(compiled, n_items: int, steps: int, block: int,
                     repeats: int = 1, interpret: Optional[bool] = None,
                     seed: int = 0) -> dict:
    """Wall-clock one compiled stencil (`Analysis.compile(backend="pallas")`)
    on a concrete geometry: best-of-``repeats`` after a warm-up call, the
    `bench_pallas` discipline.  This is the DSE's *measured* cost channel —
    where the pallas backend applies, the Pareto frontier ranks design
    points by this alongside the roofline prediction.

    Raises ValueError on a geometry the kernel cannot run (`n_items` not a
    multiple of ``block``, skew misalignment) — callers decide whether to
    snap the geometry or skip the measurement, but never get a silently
    different one."""
    import time

    p = compiled.program
    if n_items % block:
        raise ValueError(f"n_items {n_items} % block {block} != 0")
    if (p.radius * steps) % block:
        raise ValueError(f"radius*steps ({p.radius * steps}) % block "
                         f"{block} != 0")
    shape = (n_items,) + tuple(max(4, block) for _ in range(p.inner_rank))
    rng = np.random.default_rng(seed)
    x = jnp.asarray(rng.standard_normal(shape), jnp.float32)

    def run():
        return compiled(x, steps, block, interpret=interpret)

    run().block_until_ready()                     # compile + warm
    best = float("inf")
    for _ in range(max(1, repeats)):
        t0 = time.perf_counter()
        run().block_until_ready()
        best = min(best, time.perf_counter() - t0)
    return {"seconds": best, "mode": compiled.mode,
            "n_items": n_items, "steps": steps, "block": block,
            "interpret": bool(default_interpret() if interpret is None
                              else interpret),
            "repeats": max(1, repeats)}
