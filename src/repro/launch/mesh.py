"""Mesh builders.

These are FUNCTIONS (never module-level constants) so that importing this
module never touches jax device state.  `make_production_mesh` is the dry
run's described fleet — single-pod: 16×16 (256 chips, TPU v5e pod);
multi-pod: 2×16×16 = 512 chips, the "pod" axis crossing the data-center
network.  `make_local_mesh` is what the launchers run on.
"""
from __future__ import annotations

import jax
import numpy as np
from jax.sharding import Mesh


def make_production_mesh(*, multi_pod: bool = False):
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return jax.make_mesh(shape, axes)


def make_local_mesh() -> Mesh:
    """(data, model) mesh over the devices this process sees, all on the
    data axis: one chip gives 1×1, a four-chip host 4×1."""
    return Mesh(np.array(jax.devices()).reshape(-1, 1), ("data", "model"))


# TPU v5e hardware constants for the roofline (per chip)
PEAK_FLOPS_BF16 = 197e12      # FLOP/s
HBM_BW = 819e9                # B/s
ICI_BW = 50e9                 # B/s per link
VMEM_BW = 8e12                # B/s on-chip scratch (order of magnitude: the
                              # VMEM-vs-HBM gap the FIFO recovery monetizes)
