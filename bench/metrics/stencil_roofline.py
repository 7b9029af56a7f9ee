"""The runs' share of their roofline, in per cent.

The least time the chip could take for one run of the problem is the larger
of its operations over the peak FLOP/s and its compulsory bytes (one read
and one write of the array) over the peak HBM bandwidth.  The share is that
least time, times the runs traced, over the device-busy time inside the run
spans: all device work of the runs, whatever its kernels are named.
"""
import sys


def read(record):
    t = record.trace
    if t is None or not t.runs or t.busy_in_runs_s <= 0:
        return None
    peaks, cell = record.device["peaks"], record.cell
    flop_s = cell.flops_per_run / peaks["flops_per_s"]
    byte_s = cell.bytes_per_run / peaks["hbm_bytes_per_s"]
    bound = "compute" if flop_s >= byte_s else "memory"
    print(f"stencil_roofline: {bound}-bound ({flop_s:.3e} s of operations, "
          f"{byte_s:.3e} s of bytes per run)", file=sys.stderr)
    return 100.0 * max(flop_s, byte_s) * t.runs / t.busy_in_runs_s
