"""adi's column sweep kernel's share of its roofline, in per cent.

The least time one column sweep could take on the chip is the larger of its
operations over the peak FLOP/s and its compulsory bytes over the peak HBM
bandwidth.  Operations: half of the configuration's ``FLOPS_PER_UPDATE``
a cell (the two sweeps' statements are alike: ``p`` 3, ``q`` 10,
``v`` 2), over the N x N cells the harness counts; bytes: one read and
one write of the float32 N x N array.  The share is that least time, times
the sweeps traced (one a step: the runs traced times TSTEPS), over the
summed durations of the ops named ``adi_column_sweep`` in the trace's top
ops.  A trace with no such op gives nothing.
"""
import math
import sys

KERNEL = "adi_column_sweep"


def read(record):
    t, cell = record.trace, record.cell
    if t is None or not t.runs:
        return None
    busy = sum(s for name, s in t.top_ops
               if name.split()[0].lstrip("%").split(".")[0] == KERNEL)
    if not busy:
        return None
    peaks, cells = record.device["peaks"], math.prod(cell.shape)
    flop_s = cell.ref.FLOPS_PER_UPDATE / 2 * cells / peaks["flops_per_s"]
    byte_s = 2 * 4 * cells / peaks["hbm_bytes_per_s"]
    bound = "compute" if flop_s >= byte_s else "memory"
    print(f"{KERNEL}_roofline: {bound}-bound ({flop_s:.3e} s of operations, "
          f"{byte_s:.3e} s of bytes per sweep)", file=sys.stderr)
    sweeps = t.runs * cell.sizes["TSTEPS"]
    return 100.0 * max(flop_s, byte_s) * sweeps / busy
