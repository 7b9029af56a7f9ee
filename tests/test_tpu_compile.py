"""The main path's kernels compiled for a described TPU v5e, with no chip.

Interpret mode hides what the TPU compiler refuses (tiling, VMEM, SMEM), so
the kernels `chip_smoke.py` runs are compiled here at its shapes: the
generated ring kernels, the addressable fallback and the SMEM trace-replay
kernel.  Each geometry the compiler would refuse must instead raise a named
error before lowering.  The topology is described inside a fixture, never
on import: only one process may load the TPU compiler at a time.
"""
import os
import pathlib
import sys

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

sys.path.insert(0, str(pathlib.Path(__file__).resolve().parents[1]))

import repro.core.polybench  # noqa: E402,F401  (populate the registry)
from chip_smoke import COMPILER_CASES  # noqa: E402
from repro.core import analyze  # noqa: E402
from repro.core.registry import get  # noqa: E402
from repro.runtime import pallas_backend as pb  # noqa: E402


@pytest.fixture(scope="module")
def topo():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies
    try:
        return topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def one_chip(topo):
    """One described chip, with the persistent cache off: what is compiled
    for it cannot be read back here."""
    from jax.experimental.compilation_cache import compilation_cache as cc
    before = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    cc.reset_cache()
    yield SingleDeviceSharding(topo.devices[0])
    jax.config.update("jax_enable_compilation_cache", before)
    cc.reset_cache()


def _compile_stencil(one_chip, name, shape, steps, block, mode=None):
    c = (analyze(get(name)).classify().fifoize().size().plan()
         .compile(backend="pallas", mode=mode))
    x = jax.ShapeDtypeStruct(shape, jnp.float32, sharding=one_chip)
    fn = jax.jit(lambda a: c(a, steps, block, interpret=False))
    return fn.lower(x).compile().as_text()


#: the smoke's cases, and heat-3d at the deepest ring its VMEM estimate
#: admits (the compiler's limit lies between T=200 and T=240)
@pytest.mark.parametrize(
    "name,shape,steps,block,mode",
    COMPILER_CASES + (("heat-3d", (128, 128, 128), 200, 8, None),),
    ids=lambda v: "x".join(map(str, v)) if isinstance(v, tuple) else str(v))
def test_stencil_kernel_compiles_for_v5e(one_chip, name, shape, steps, block,
                                         mode):
    assert "tpu_custom_call" in _compile_stencil(one_chip, name, shape, steps,
                                                 block, mode)


@pytest.mark.parametrize("n_events,ring,order", [
    (1024, 16, pb._FIFO), (1024, 16, pb._REGISTER),
    (65536, 4096, pb._REORDER)])
def test_replay_kernel_compiles_for_v5e(one_chip, n_events, ring, order):
    x = jax.ShapeDtypeStruct((n_events,), jnp.int32, sharding=one_chip)
    txt = pb._replay_call(n_events, ring, order, False).lower(
        x, x, x).compile().as_text()
    assert "tpu_custom_call" in txt


@pytest.mark.parametrize("name,shape,steps,block,mode,match", [
    ("jacobi-2d", (1024, 1024), 4, 4, None, "multiple of 8"),
    ("jacobi-1d", (65536,), 64, 64, None, "multiple of 128"),
    ("heat-3d", (128, 128, 128), 256, 8, None, "VMEM"),
    ("jacobi-2d", (4096, 4096), 1, 8, "addressable", "VMEM"),
], ids=["sublane-block", "lane-block", "ring-vmem", "addressable-vmem"])
def test_uncompilable_geometry_is_refused_by_name(one_chip, name, shape,
                                                  steps, block, mode, match):
    with pytest.raises(ValueError, match=match):
        _compile_stencil(one_chip, name, shape, steps, block, mode)


def test_trace_too_long_for_smem_is_refused(one_chip):
    with pytest.raises(pb.TraceTooLong, match="SMEM"):
        pb._replay_call(1 << 17, 16, pb._FIFO, False)
