"""Band-stencil reference oracles for the pallas codegen backend.

There is no hand-written kernel here: the fused VMEM-ring kernels for these
shapes are *generated* by `repro.runtime.pallas_codegen` from the planned
PPN; this package holds only the pure-jnp oracles the generated kernels are
parity-tested against (`tests/test_pallas.py`).
"""
from .ref import heat_3d, jacobi_1d, jacobi_2d

__all__ = ["heat_3d", "jacobi_1d", "jacobi_2d"]
