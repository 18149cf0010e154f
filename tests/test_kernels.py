"""Per-kernel shape/dtype sweeps: every Pallas kernel (interpret mode)
against its pure-jnp oracle in ref.py."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.kernels import ref
from repro.kernels.adamw import adamw_update
from repro.kernels.bicgk import bicgk
from repro.kernels.decode_attention import decode_attention
from repro.kernels.gemver import gemver
from repro.kernels.rmsnorm import rmsnorm
from repro.kernels.softmax_xent import softmax_xent

RNG = np.random.default_rng(42)


def randn(*shape, dtype=np.float32, scale=1.0):
    return (RNG.standard_normal(shape) * scale).astype(dtype)


@pytest.mark.parametrize("T,D", [(8, 128), (64, 256), (128, 512), (32, 384)])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_rmsnorm(T, D, dtype):
    x = jnp.asarray(randn(T, D), dtype)
    g = jnp.asarray(randn(D))
    got = rmsnorm(x, g, interpret=True)
    want = ref.rmsnorm(x, g)
    np.testing.assert_allclose(np.asarray(got, np.float32),
                               np.asarray(want, np.float32),
                               rtol=2e-2 if dtype == jnp.bfloat16 else 1e-5,
                               atol=2e-2 if dtype == jnp.bfloat16 else 1e-5)


@pytest.mark.parametrize("n", [128, 1024, 4096, 128 * 17])
@pytest.mark.parametrize("step", [1, 10])
def test_adamw(n, step):
    p, g = jnp.asarray(randn(n)), jnp.asarray(randn(n))
    m, v = jnp.asarray(randn(n) * 0.1), jnp.abs(jnp.asarray(randn(n))) * 0.01
    kw = dict(lr=1e-3, beta1=0.9, beta2=0.95, eps=1e-8, weight_decay=0.01,
              step=step)
    got = adamw_update(p, g, m, v, **kw, interpret=True)
    want = ref.adamw(p, g, m, v, **kw)
    for a, b in zip(got, want):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   rtol=1e-5, atol=1e-6)


@pytest.mark.parametrize("m,n,bc", [(128, 256, 128), (256, 128, 64),
                                    (512, 512, 512), (128, 384, 128)])
def test_bicgk(m, n, bc):
    A, p, r = jnp.asarray(randn(m, n)), jnp.asarray(randn(n)), jnp.asarray(randn(m))
    q1, s1 = bicgk(A, p, r, block_cols=bc, interpret=True)
    q2, s2 = ref.bicgk(A, p, r)
    np.testing.assert_allclose(np.asarray(q1), np.asarray(q2), rtol=1e-4,
                               atol=1e-4)
    np.testing.assert_allclose(np.asarray(s1), np.asarray(s2), rtol=1e-4,
                               atol=1e-4)


@pytest.mark.parametrize("m,n", [(128, 128), (256, 128), (128, 256)])
def test_gemver(m, n):
    A = jnp.asarray(randn(m, n))
    u1, u2, y = (jnp.asarray(randn(m)) for _ in range(3))
    v1, v2, z = (jnp.asarray(randn(n)) for _ in range(3))
    got = gemver(A, u1, v1, u2, v2, y, z, 1.3, 0.7, interpret=True)
    want = ref.gemver(A, u1, v1, u2, v2, y, z, 1.3, 0.7)
    for a, b in zip(got, want):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b), rtol=2e-4,
                                   atol=2e-4)


@pytest.mark.parametrize("T,V", [(8, 512), (32, 1000), (16, 4096)])
def test_softmax_xent(T, V):
    lg = jnp.asarray(randn(T, V, scale=3.0))
    lb = jnp.asarray(RNG.integers(0, V, T).astype(np.int32))
    got = softmax_xent(lg, lb, interpret=True)
    want = ref.softmax_xent(lg, lb)
    np.testing.assert_allclose(float(got), float(want), rtol=1e-5)


@pytest.mark.parametrize("B,Hq,Hkv,S,d", [(1, 4, 4, 256, 128),
                                          (2, 8, 2, 512, 128),
                                          (2, 16, 1, 256, 128)])
def test_decode_attention(B, Hq, Hkv, S, d):
    q = jnp.asarray(randn(B, Hq, d, scale=0.5))
    k = jnp.asarray(randn(B, S, Hkv, d, scale=0.2))
    v = jnp.asarray(randn(B, S, Hkv, d))
    got = decode_attention(q, k, v, interpret=True)
    want = ref.decode_attention(q, k, v)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=3e-4, atol=3e-4)


def test_fused_adamw_matches_pallas_and_ref():
    """Three implementations of the same update: fusion-compiler (jnp),
    hand Pallas kernel, jnp reference."""
    from repro.optim import fused_adamw_update
    n = 1024
    p, g = jnp.asarray(randn(n)), jnp.asarray(randn(n))
    m, v = jnp.zeros(n), jnp.zeros(n) + 0.05
    kw = dict(lr=2e-3, beta1=0.9, beta2=0.95, eps=1e-8, weight_decay=0.1,
              step=7)
    a = fused_adamw_update(p, g, m, v, **kw)
    b = adamw_update(p, g, m, v, **kw, interpret=True)
    c = ref.adamw(p, g, m, v, **kw)
    for x1, x2 in zip(a, c):
        np.testing.assert_allclose(np.asarray(x1), np.asarray(x2),
                                   rtol=1e-5, atol=1e-6)
    for x1, x2 in zip(b, c):
        np.testing.assert_allclose(np.asarray(x1), np.asarray(x2),
                                   rtol=1e-5, atol=1e-6)
