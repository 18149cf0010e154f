"""Hand-tuned fused GEMVER Pallas kernels (paper's 2.61× headline case).

    B = A + u1 v1ᵀ + u2 v2ᵀ ;  x = β Bᵀ y + z ;  w = α B x

Fusion structure chosen by the compiler (and pinned here):

* kernel 1: rank-2 update **and** the Bᵀy matvec in one pass — A is read
  once, B is written once (it escapes) and its VMEM tile feeds the
  transposed matvec partials immediately.
* barrier (x depends on the finished reduction t = Bᵀy — paper §3.2.2),
  then the cheap x = βt + z map runs fused into kernel 2's prologue.
* kernel 2: w = α B x, streaming B back once.

HBM traffic: read A + write B + read B + vectors ≈ 3 matrix streams vs
CUBLAS's 5 (copy A→B, GER, GER, GEMV, GEMV ⇒ read/write B repeatedly).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl


def _k1(A_ref, u1_ref, v1_ref, u2_ref, v2_ref, y_ref, B_ref, tp_ref):
    A = A_ref[...].astype(jnp.float32)            # (bi, n) row stripe
    u1 = u1_ref[...].astype(jnp.float32)          # (bi,)
    u2 = u2_ref[...].astype(jnp.float32)
    v1 = v1_ref[...].astype(jnp.float32)          # (n,)
    v2 = v2_ref[...].astype(jnp.float32)
    y = y_ref[...].astype(jnp.float32)            # (bi,)
    B = A + u1[:, None] * v1[None, :] + u2[:, None] * v2[None, :]
    B_ref[...] = B
    tp_ref[0, :] = jnp.dot(B.T, y, precision="highest")   # partial Bᵀy


def _k2(B_ref, x_ref, a_ref, w_ref):
    B = B_ref[...].astype(jnp.float32)            # (bi, n)
    x = x_ref[...].astype(jnp.float32)            # (n,)
    w_ref[...] = a_ref[0, 0] * jnp.dot(B, x, precision="highest")


@functools.partial(jax.jit, static_argnames=("block_rows", "interpret"))
def gemver(A, u1, v1, u2, v2, y, z, alpha, beta, *,
           block_rows: int = 256, interpret: bool = False):
    m, n = A.shape
    bi = min(block_rows, m)
    while m % bi:
        bi //= 2
    gi = m // bi
    B, t_parts = pl.pallas_call(
        _k1,
        grid=(gi,),
        in_specs=[
            pl.BlockSpec((bi, n), lambda i: (i, 0)),
            pl.BlockSpec((bi,), lambda i: (i,)),
            pl.BlockSpec((n,), lambda i: (0,)),
            pl.BlockSpec((bi,), lambda i: (i,)),
            pl.BlockSpec((n,), lambda i: (0,)),
            pl.BlockSpec((bi,), lambda i: (i,)),
        ],
        out_specs=[
            pl.BlockSpec((bi, n), lambda i: (i, 0)),
            pl.BlockSpec((1, n), lambda i: (i, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((m, n), jnp.float32),
            jax.ShapeDtypeStruct((gi, n), jnp.float32),
        ],
        interpret=interpret,
    )(A, u1, v1, u2, v2, y)
    x = beta * jnp.sum(t_parts, axis=0) + z        # cheap depth-1 map
    alpha2 = jnp.asarray(alpha, jnp.float32).reshape(1, 1)
    w = pl.pallas_call(
        _k2,
        grid=(gi,),
        in_specs=[
            pl.BlockSpec((bi, n), lambda i: (i, 0)),
            pl.BlockSpec((n,), lambda i: (0,)),
            pl.BlockSpec((1, 1), lambda i: (0, 0)),
        ],
        out_specs=pl.BlockSpec((bi,), lambda i: (i,)),
        out_shape=jax.ShapeDtypeStruct((m,), jnp.float32),
        interpret=interpret,
    )(B, x, alpha2)
    return B, x, w
