"""Per-kernel shape/dtype sweeps against the pure-jnp oracles."""
import jax.numpy as jnp
import numpy as np
import pytest

from repro.kernels.flash_attention import attention_ref, flash_attention
from repro.kernels.stencil_bands import jacobi_1d


@pytest.mark.parametrize("n,bn", [(256, 32), (512, 64), (1024, 128),
                                  (384, 128)])
def test_stencil_fifo_matches_oracle(n, bn):
    """The paper's Fig. 3 kernel — jacobi-1d with FIFO channels in a VMEM
    ring — generated from the planned PPN, with T = block as in the figure."""
    import repro.core.polybench  # noqa: F401  (populate the registry)
    from repro.core import analyze
    from repro.core.registry import get

    c = (analyze(get("jacobi-1d")).classify().fifoize().size().plan()
         .compile(backend="pallas"))
    rng = np.random.default_rng(0)
    x = jnp.asarray(rng.normal(size=(n,)), jnp.float32)
    got = c(x, bn, bn)
    want = jacobi_1d(x, bn)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               atol=1e-5, rtol=1e-5)


@pytest.mark.parametrize("B,S,H,KV,hd,causal,dt,tol", [
    (2, 128, 4, 2, 64, True, jnp.float32, 1e-5),
    (1, 256, 8, 8, 128, True, jnp.bfloat16, 2e-2),
    (2, 128, 4, 1, 64, False, jnp.float32, 1e-5),
    (1, 128, 6, 3, 32, True, jnp.float32, 1e-5),
    (1, 64, 2, 2, 128, True, jnp.float16, 1e-2),
])
def test_flash_attention_sweep(B, S, H, KV, hd, causal, dt, tol):
    rng = np.random.default_rng(7)
    q = jnp.asarray(rng.normal(size=(B, S, H, hd)), dt)
    k = jnp.asarray(rng.normal(size=(B, S, KV, hd)), dt)
    v = jnp.asarray(rng.normal(size=(B, S, KV, hd)), dt)
    got = flash_attention(q, k, v, causal=causal, bq=64, bk=64)
    want = attention_ref(q, k, v, causal=causal)
    np.testing.assert_allclose(np.asarray(got, np.float32),
                               np.asarray(want, np.float32), atol=tol, rtol=tol)


def test_flash_attention_block_shapes():
    """Block-shape sweep: result must be block-size independent."""
    rng = np.random.default_rng(3)
    q = jnp.asarray(rng.normal(size=(1, 256, 4, 64)), jnp.float32)
    k = jnp.asarray(rng.normal(size=(1, 256, 2, 64)), jnp.float32)
    v = jnp.asarray(rng.normal(size=(1, 256, 2, 64)), jnp.float32)
    outs = [flash_attention(q, k, v, causal=True, bq=bq, bk=bk)
            for bq, bk in ((32, 32), (64, 128), (128, 64), (256, 256))]
    for o in outs[1:]:
        np.testing.assert_allclose(np.asarray(outs[0]), np.asarray(o),
                                   atol=1e-5, rtol=1e-5)


@pytest.mark.parametrize("B,S,H,hd,chunk,decay_scale", [
    (2, 128, 3, 32, 32, 0.5),
    (1, 256, 2, 64, 64, 1.0),     # fast decays: overflow regression case
    (2, 128, 4, 16, 128, 1.5),
])
def test_gla_timemix_matches_sequential(B, S, H, hd, chunk, decay_scale):
    from repro.kernels.gla_timemix import gla_timemix, timemix_ref
    rng = np.random.default_rng(11)
    r = jnp.asarray(rng.normal(size=(B, S, H, hd)), jnp.float32)
    k = jnp.asarray(rng.normal(size=(B, S, H, hd)), jnp.float32)
    v = jnp.asarray(rng.normal(size=(B, S, H, hd)), jnp.float32)
    logw = jnp.asarray(-np.exp(rng.normal(size=(B, S, H, hd)) * decay_scale),
                       jnp.float32)
    u = jnp.asarray(rng.normal(size=(H, hd)), jnp.float32)
    got = gla_timemix(r, k, v, logw, u, chunk=chunk)
    want = timemix_ref(r, k, v, logw, u)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               atol=2e-3, rtol=2e-3)
