"""Fused AdamW update Pallas kernel.

The AdamW step is a pure BLAS-1 map chain (scal/axpy/square/rsqrt) over
four same-length vectors — precisely the paper's fusion territory.
Unfused it streams p,g,m,v several times (one kernel per op); fused it is
one read of (p,g,m,v) + one write of (p,m,v): 7 array streams instead of
~17, a ~2.4x HBM-traffic cut on a memory-bound step.

Hyperparameters arrive as one (1, 8) f32 SMEM-style block
[lr, b1, b2, eps, wd, c1, c2, pad] so the kernel is shape-stable across
steps (c1/c2 are the step-dependent bias corrections, computed outside).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

LANES = 128


def _adamw_kernel(h_ref, p_ref, g_ref, m_ref, v_ref,
                  po_ref, mo_ref, vo_ref):
    lr, b1, b2 = h_ref[0, 0], h_ref[0, 1], h_ref[0, 2]
    eps, wd, c1, c2 = h_ref[0, 3], h_ref[0, 4], h_ref[0, 5], h_ref[0, 6]
    g = g_ref[...].astype(jnp.float32)
    p = p_ref[...].astype(jnp.float32)
    m = b1 * m_ref[...] + (1.0 - b1) * g
    v = b2 * v_ref[...] + (1.0 - b2) * (g * g)
    upd = (m * c1) / (jnp.sqrt(v * c2) + eps) + wd * p
    po_ref[...] = (p - lr * upd).astype(po_ref.dtype)
    mo_ref[...] = m
    vo_ref[...] = v


@functools.partial(jax.jit,
                   static_argnames=("block_rows", "interpret"))
def adamw_update(p, g, m, v, *, lr, beta1=0.9, beta2=0.95, eps=1e-8,
                 weight_decay=0.0, step=1, block_rows: int = 512,
                 interpret: bool = False):
    """Flat 1-D p/g/m/v of equal length N (N % 128 == 0 after caller pads).

    Returns (p', m', v').  m, v are f32; p may be bf16/f32.
    """
    (n,) = p.shape
    assert n % LANES == 0, "caller must pad to a multiple of 128"
    rows = n // LANES
    br = min(block_rows, rows)
    while rows % br:
        br //= 2
    grid = (rows // br,)
    step = jnp.asarray(step, jnp.float32)
    c1 = 1.0 / (1.0 - beta1 ** step)
    c2 = 1.0 / (1.0 - beta2 ** step)
    h = jnp.stack([jnp.asarray(lr, jnp.float32), jnp.float32(beta1),
                   jnp.float32(beta2), jnp.float32(eps),
                   jnp.float32(weight_decay), c1, c2,
                   jnp.float32(0.0)]).reshape(1, 8)

    def two_d(x):
        return x.reshape(rows, LANES)

    blk = lambda dt: pl.BlockSpec((br, LANES), lambda i: (i, 0))
    po, mo, vo = pl.pallas_call(
        _adamw_kernel,
        grid=grid,
        in_specs=[pl.BlockSpec((1, 8), lambda i: (0, 0)),
                  blk(p.dtype), blk(g.dtype), blk(jnp.float32),
                  blk(jnp.float32)],
        out_specs=[blk(p.dtype), blk(jnp.float32), blk(jnp.float32)],
        out_shape=[jax.ShapeDtypeStruct((rows, LANES), p.dtype),
                   jax.ShapeDtypeStruct((rows, LANES), jnp.float32),
                   jax.ShapeDtypeStruct((rows, LANES), jnp.float32)],
        interpret=interpret,
    )(h, two_d(p), two_d(g), two_d(m), two_d(v))
    return po.reshape(n), mo.reshape(n), vo.reshape(n)
