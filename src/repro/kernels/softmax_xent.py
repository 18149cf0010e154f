"""Fused softmax cross-entropy Pallas kernel.

The LM loss is the paper's nested map∘reduce shape again: per token row,
reduce(max), map(exp), reduce(sum), gather — fused so the (T, V) logits
block is read from HBM exactly once (unfused: 3-4 passes over 150k-wide
vocab rows dominate the step at small batch).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl


def _xent_kernel(logits_ref, labels_ref, loss_ref):
    x = logits_ref[...].astype(jnp.float32)          # (br, V)
    labels = labels_ref[...]                          # (br, 1) int32
    m = jnp.max(x, axis=-1, keepdims=True)
    lse = jnp.log(jnp.sum(jnp.exp(x - m), axis=-1)) + m[:, 0]
    cols = jax.lax.broadcasted_iota(jnp.int32, x.shape, 1)
    ll = jnp.sum(jnp.where(cols == labels, x, 0.0), axis=-1)
    loss_ref[...] = lse - ll


@functools.partial(jax.jit, static_argnames=("block_rows", "interpret"))
def softmax_xent(logits: jax.Array, labels: jax.Array, *,
                 block_rows: int = 8, interpret: bool = False) -> jax.Array:
    """logits (T, V), labels (T,) int32 -> mean cross-entropy (scalar)."""
    T, V = logits.shape
    br = min(block_rows, T)
    while T % br:
        br //= 2
    per_row = pl.pallas_call(
        _xent_kernel,
        grid=(T // br,),
        in_specs=[
            pl.BlockSpec((br, V), lambda i: (i, 0)),
            pl.BlockSpec((br, 1), lambda i: (i, 0)),
        ],
        out_specs=pl.BlockSpec((br,), lambda i: (i,)),
        out_shape=jax.ShapeDtypeStruct((T,), jnp.float32),
        interpret=interpret,
    )(logits, labels.reshape(T, 1).astype(jnp.int32))
    return jnp.mean(per_row)
