"""The main path's kernels compiled for a described TPU v5e, with no chip.

Interpret mode hides what the TPU compiler refuses (tiling, VMEM, SMEM), so
the kernels `chip_smoke.py` runs are compiled here at its shapes: the
generated ring kernels, the addressable fallback and the SMEM trace-replay
kernel.  Each geometry the compiler would refuse must instead raise a named
error before lowering.  The topology is described inside a fixture, never
on import: only one process may load the TPU compiler at a time.  One test
reads Mosaic's own dump of the ring kernel, made by a process of its own
before this one loads the compiler, and bounds the rotates its time-step
loop spends.
"""
import os
import pathlib
import re
import subprocess
import sys

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

ROOT = pathlib.Path(__file__).resolve().parents[1]
sys.path.insert(0, str(ROOT))

import repro.core.polybench  # noqa: E402,F401  (populate the registry)
from chip_smoke import COMPILER_CASES  # noqa: E402
from repro.core import analyze  # noqa: E402
from repro.core.registry import get  # noqa: E402
from repro.runtime import pallas_backend as pb  # noqa: E402


#: compiles the jacobi-2d ring kernel at (64, 256), 8 steps, block 8, for a
#: described v5e, in a process of its own so that libtpu reads the Mosaic
#: dump flag at its start
_DUMP_SCRIPT = """
import jax, jax.numpy as jnp
from jax.experimental import topologies
from jax.sharding import SingleDeviceSharding
import repro.core.polybench
from repro.core import analyze
from repro.core.registry import get
jax.config.update("jax_enable_compilation_cache", False)
topo = topologies.get_topology_desc(platform="tpu", topology_name="v5e:2x2")
c = (analyze(get("jacobi-2d")).classify().fifoize().size().plan()
     .compile(backend="pallas"))
x = jax.ShapeDtypeStruct((64, 256), jnp.float32,
                         sharding=SingleDeviceSharding(topo.devices[0]))
jax.jit(lambda a: c(a, 8, 8, interpret=False)).lower(x).compile()
"""


@pytest.fixture(scope="module")
def ring_dump(tmp_path_factory):
    """Mosaic's passes over the jacobi-2d ring kernel, compiled for a
    described v5e by a process of its own: libtpu reads its dump flag only
    when a process loads it, and one process at a time may hold it, so this
    runs before `topo` loads it here."""
    dump = tmp_path_factory.mktemp("mosaic")
    env = dict(os.environ, JAX_PLATFORMS="cpu", TPU_LOG_DIR="disabled",
               PYTHONPATH=str(ROOT / "src"),
               LIBTPU_INIT_ARGS=" ".join(filter(None, [
                   os.environ.get("LIBTPU_INIT_ARGS"),
                   f"--xla_mosaic_dump_to={dump}"])))
    try:
        out = subprocess.run([sys.executable, "-c", _DUMP_SCRIPT], env=env,
                             capture_output=True, text=True, timeout=240)
    except subprocess.TimeoutExpired as slow:   # fails the one test that
        out = slow                              # reads the dump, no other
    return dump, out


@pytest.fixture(scope="module")
def topo(ring_dump):
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


#: the smoke's cases, heat-3d at the deepest ring its VMEM estimate
#: admits (the compiler's limit lies between T=200 and T=240), the
#: in-place seidel-2d ring and adi's two sweeps at their benchmark cells'
#: geometries
@pytest.mark.parametrize(
    "name,shape,steps,block,mode",
    COMPILER_CASES + (("heat-3d", (128, 128, 128), 200, 8, None),
                      ("seidel-2d", (4000, 4000), 200, 8, None),
                      ("adi", (2000, 2000), 100, 8, None)),
    ids=lambda v: "x".join(map(str, v)) if isinstance(v, tuple) else str(v))
def test_stencil_kernel_compiles_for_v5e(one_chip, name, shape, steps, block,
                                         mode):
    assert "tpu_custom_call" in _compile_stencil(one_chip, name, shape, steps,
                                                 block, mode)


def test_in_place_kernel_carries_its_name(one_chip):
    """The device trace names a kernel by its HLO instruction: seidel-2d's
    is its program's ``trace_name``, and jacobi-2d keeps JAX's default."""
    assert "%seidel_2d_ring" in _compile_stencil(one_chip, "seidel-2d",
                                                 (64, 256), 8, 8)
    assert "%_lambda_" in _compile_stencil(one_chip, "jacobi-2d",
                                           (64, 256), 8, 8)


def test_adi_kernels_carry_their_names(one_chip):
    """adi's sweeps are two kernels, each named by its program's
    ``trace_name``, run once a step inside one loop: one instruction each."""
    text = _compile_stencil(one_chip, "adi", (64, 64), 4, 8)
    assert text.count("tpu_custom_call") == 2
    assert "%adi_column_sweep" in text and "%adi_row_sweep" in text


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
    ("adi", (2000, 2000), 4, 4, None, "adi.*multiple of 8"),
    ("adi", (2400, 2400), 4, 8, None, "adi column sweep.*VMEM"),
], ids=["sublane-block", "lane-block", "ring-vmem", "addressable-vmem",
        "adi-block", "adi-vmem"])
def test_uncompilable_geometry_is_refused_by_name(one_chip, name, shape,
                                                  steps, block, mode, match):
    with pytest.raises(ValueError, match=match):
        _compile_stencil(one_chip, name, shape, steps, block, mode)


def test_trace_too_long_for_smem_is_refused(one_chip):
    with pytest.raises(pb.TraceTooLong, match="SMEM"):
        pb._replay_call(1 << 17, 16, pb._FIFO, False)


def _ring_loop_body(dump_dir: pathlib.Path):
    """The time-step loop of the ring kernel after Mosaic's vector layout
    pass: its out vregs (the loop's carried values) and its body's lines."""
    path, = dump_dir.glob("*_ring_kernel-post-apply-vector-layout-simplify*")
    lines = path.read_text().splitlines()
    start = next(i for i, line in enumerate(lines) if "scf.for" in line)
    carried = int(re.search(r"%\w+:(\d+) = scf.for", lines[start]).group(1))
    depth, body = 0, []
    for line in lines[start:]:
        depth += line.count("{") - line.count("}")
        body.append(line)
        if depth <= 0:
            break
    return carried, body


def test_ring_windows_cost_few_rotates_per_vreg(topo, ring_dump):
    """jacobi-2d streams rows on the sublane axis: built from the halo's
    tail and the block's head, each shifted window costs one sublane rotate
    per vreg, and ``update`` runs on the block's own vregs.  Built as slices
    of one (2 + 8)-row concatenation it cost 10 non-zero rotates per out
    vreg (7 on sublanes, 3 on lanes); now 4 (2 and 2)."""
    dump, out = ring_dump
    assert not isinstance(out, subprocess.TimeoutExpired), out
    assert out.returncode == 0, out.stderr[-4000:]
    out_vregs, body = _ring_loop_body(dump)
    assert out_vregs == 2                     # 8 rows x 256 lanes
    rotates = [m.groups() for line in body for m in re.finditer(
        r"tpu\.rotate \S+ by (\d+) dim (\d+)", line) if m.group(1) != "0"]
    sublane = sum(dim == "0" for _, dim in rotates)
    assert sublane <= 2 * out_vregs, rotates
    assert len(rotates) <= 5 * out_vregs, rotates
