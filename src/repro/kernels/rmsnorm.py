"""Fused RMSNorm Pallas kernel.

This is the LM-side instantiation of the paper's nested map∘reduce
pattern: per row (map over tokens) reduce(x², +) then map(x·rsqrt·γ) —
one HBM read + one write instead of three kernel round-trips.  Generated
structurally by the fusion compiler; this hand version pins the layout:
row-block × full-feature tiles resident in VMEM.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl


def _rmsnorm_kernel(x_ref, g_ref, o_ref, *, eps: float):
    x = x_ref[...].astype(jnp.float32)
    ms = jnp.mean(x * x, axis=-1, keepdims=True)
    y = x * jax.lax.rsqrt(ms + eps) * g_ref[...].astype(jnp.float32)
    o_ref[...] = y.astype(o_ref.dtype)


@functools.partial(jax.jit, static_argnames=("eps", "block_rows", "interpret"))
def rmsnorm(x: jax.Array, gamma: jax.Array, *, eps: float = 1e-6,
            block_rows: int = 256, interpret: bool = False) -> jax.Array:
    """x: (T, D), gamma: (D,) -> (T, D).  T must divide by block_rows."""
    T, D = x.shape
    br = min(block_rows, T)
    while T % br:
        br //= 2
    grid = (T // br,)
    return pl.pallas_call(
        functools.partial(_rmsnorm_kernel, eps=eps),
        grid=grid,
        in_specs=[
            pl.BlockSpec((br, D), lambda i: (i, 0)),
            pl.BlockSpec((1, D), lambda i: (0, 0)),
        ],
        out_specs=pl.BlockSpec((br, D), lambda i: (i, 0)),
        out_shape=jax.ShapeDtypeStruct((T, D), x.dtype),
        interpret=interpret,
    )(x, gamma.reshape(1, D))
