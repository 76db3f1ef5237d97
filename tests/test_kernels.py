"""Pallas kernel validation: shape/dtype sweeps, allclose vs ref.py oracles
(interpret=True on CPU)."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core.tiling import VMEM_LIMIT, choose_matmul_tiling
from repro.kernels import ops, ref


def _rand(i, shape, dtype):
    x = jax.random.normal(jax.random.PRNGKey(i), shape, jnp.float32)
    return x.astype(dtype)


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
@pytest.mark.parametrize("m,n,k,bm,bn,bk", [
    (128, 128, 128, 128, 128, 128),
    (256, 128, 384, 128, 128, 128),
    (512, 256, 256, 256, 128, 256),
    (128, 512, 640, 128, 256, 128),
])
def test_matmul_sweep(m, n, k, bm, bn, bk, dtype):
    a = _rand(0, (m, k), dtype)
    b = _rand(1, (k, n), dtype)
    out = ops.matmul(a, b, bm=bm, bn=bn, bk=bk, interpret=True)
    expect = ref.matmul_ref(a, b)
    tol = 2e-2 if dtype == jnp.bfloat16 else 2e-4
    np.testing.assert_allclose(np.asarray(out, np.float32),
                               np.asarray(expect, np.float32),
                               rtol=tol, atol=tol * k ** 0.5)


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
@pytest.mark.parametrize("B,H,Hkv,S,D,bq,bk,causal,window", [
    (1, 2, 2, 128, 32, 64, 64, True, 0),
    (2, 4, 2, 128, 64, 64, 32, True, 0),      # GQA
    (1, 2, 1, 256, 32, 128, 64, True, 48),    # MQA + sliding window
    (1, 2, 2, 128, 32, 64, 64, False, 0),     # non-causal (encoder)
])
def test_flash_attention_sweep(B, H, Hkv, S, D, bq, bk, causal, window,
                               dtype):
    q = _rand(2, (B, H, S, D), dtype)
    k = _rand(3, (B, Hkv, S, D), dtype)
    v = _rand(4, (B, Hkv, S, D), dtype)
    out = ops.flash_attention(q, k, v, causal=causal, window=window,
                              bq=bq, bk=bk, interpret=True)
    kf = jnp.repeat(k, H // Hkv, 1)
    vf = jnp.repeat(v, H // Hkv, 1)
    expect = ref.flash_attention_ref(q, kf, vf, causal=causal, window=window)
    tol = 3e-2 if dtype == jnp.bfloat16 else 1e-4
    np.testing.assert_allclose(np.asarray(out, np.float32),
                               np.asarray(expect, np.float32),
                               rtol=tol, atol=tol)


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
@pytest.mark.parametrize("b,S,d,N,bd,chunk", [
    (1, 32, 16, 8, 16, 16),
    (2, 64, 32, 16, 16, 32),
    (1, 128, 64, 8, 32, 64),
    # 128-lane channel blocks, two 16-row slabs per chunk, two chunks:
    # the state carries across slab, chunk and block boundaries
    (1, 64, 256, 16, 128, 32),
])
def test_mamba_scan_sweep(b, S, d, N, bd, chunk, dtype):
    x = _rand(5, (b, S, d), dtype)
    dt = jax.nn.softplus(_rand(6, (b, S, d), jnp.float32)).astype(dtype)
    B = _rand(7, (b, S, N), dtype)
    C = _rand(8, (b, S, N), dtype)
    A = -jnp.exp(_rand(9, (d, N), jnp.float32) * 0.3)
    D = jnp.ones((d,), jnp.float32)
    out = ops.mamba_scan(x, dt, B, C, A, D, bd=bd, chunk=chunk,
                         interpret=True)
    expect = ref.mamba_scan_ref(x, dt, B, C, A, D)
    tol = 8e-2 if dtype == jnp.bfloat16 else 2e-4
    np.testing.assert_allclose(np.asarray(out, np.float32),
                               np.asarray(expect, np.float32),
                               rtol=tol, atol=tol * 4)


@pytest.mark.parametrize("m,n,k", [(256, 256, 256), (512, 512, 2048)])
def test_matmul_uses_tiling_optimizer_defaults(m, n, k):
    # at (512, 512, 2048) in f32 the largest blocks would take 19 MiB of
    # VMEM with their double buffers, over v5e's 16 MiB per kernel
    t = choose_matmul_tiling(m, n, k, dtype_bytes=4)
    assert t.vmem_bytes <= VMEM_LIMIT
    a = _rand(0, (m, k), jnp.float32)
    b = _rand(1, (k, n), jnp.float32)
    # block shapes from choose_matmul_tiling
    out = ops.matmul(a, b, interpret=True)
    np.testing.assert_allclose(np.asarray(out), np.asarray(
        ref.matmul_ref(a, b)), rtol=1e-4, atol=1e-3 * (k / 256) ** 0.5)


def test_chunked_attention_matches_flash_kernel():
    """The jnp chunked implementation and the Pallas kernel implement the
    same dataflow — cross-validate them."""
    from repro.models.attention import chunked_attention
    q = _rand(0, (1, 2, 128, 32), jnp.float32)
    k = _rand(1, (1, 2, 128, 32), jnp.float32)
    v = _rand(2, (1, 2, 128, 32), jnp.float32)
    a = chunked_attention(q, k, v, causal=True, chunk=32)
    b = ops.flash_attention(q, k, v, causal=True, bq=64, bk=32,
                            interpret=True)
    np.testing.assert_allclose(np.asarray(a), np.asarray(b), rtol=1e-4,
                               atol=1e-4)
