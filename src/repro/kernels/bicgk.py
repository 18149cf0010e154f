"""Hand-tuned fused BiCGK Pallas kernel:  q = A p ; s = Aᵀ r in ONE pass.

The paper's headline BLAS-2 fusion (§4.4): both matvecs share the matrix
``A``, so a fused kernel reads A from HBM exactly once (unfused: twice).
TPU adaptation: the grid walks column stripes; each grid cell holds an
(m × bj) stripe of A in VMEM, computes the full partial q contribution
(emitted as per-stripe partials — the paper's "extra kernel" reduction
finalization, since TPUs have no atomicAdd) and the final s block
(accumulated wholly in VMEM within the cell).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl


def _bicgk_kernel(A_ref, p_ref, r_ref, qp_ref, s_ref):
    A = A_ref[...].astype(jnp.float32)          # (m, bj) stripe
    p = p_ref[...].astype(jnp.float32)          # (bj,)
    r = r_ref[...].astype(jnp.float32)          # (m,)
    qp_ref[0, :] = jnp.dot(A, p, precision="highest")       # partial q
    s_ref[...] = jnp.dot(A.T, r, precision="highest")       # final s block


@functools.partial(jax.jit, static_argnames=("block_cols", "interpret"))
def bicgk(A: jax.Array, p: jax.Array, r: jax.Array, *,
          block_cols: int = 512, interpret: bool = False):
    """A: (m, n); p: (n,); r: (m,).  Returns (q, s)."""
    m, n = A.shape
    bj = min(block_cols, n)
    while n % bj:
        bj //= 2
    gj = n // bj
    q_parts, s = pl.pallas_call(
        _bicgk_kernel,
        grid=(gj,),
        in_specs=[
            pl.BlockSpec((m, bj), lambda j: (0, j)),
            pl.BlockSpec((bj,), lambda j: (j,)),
            pl.BlockSpec((m,), lambda j: (0,)),
        ],
        out_specs=[
            pl.BlockSpec((1, m), lambda j: (j, 0)),
            pl.BlockSpec((bj,), lambda j: (j,)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((gj, m), jnp.float32),
            jax.ShapeDtypeStruct((n,), jnp.float32),
        ],
        interpret=interpret,
    )(A, p, r)
    return jnp.sum(q_parts, axis=0), s
