"""GQA decode attention Pallas kernel (flash-style online softmax).

One new query token attends to a long KV cache.  Decode is purely
memory-bound (every KV byte is read once per step), so the kernel's job
is to stream K/V through VMEM exactly once while carrying the online
softmax state (m, l, acc) in VMEM scratch across KV blocks — the TPU
analogue of flash-decoding.  Grouped queries (Hq = G·Hkv) share each KV
head's stream, which divides KV traffic by G vs per-head attention.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


def _decode_attn_kernel(q_ref, k_ref, v_ref, o_ref,
                        acc_ref, m_ref, l_ref, *, scale: float):
    s_idx = pl.program_id(1)
    n_s = pl.num_programs(1)

    @pl.when(s_idx == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)
        m_ref[...] = jnp.full_like(m_ref, -jnp.inf)
        l_ref[...] = jnp.zeros_like(l_ref)

    q = q_ref[0].astype(jnp.float32)                 # (G, d)
    k = k_ref[0].astype(jnp.float32)                 # (bs, d)
    v = v_ref[0].astype(jnp.float32)                 # (bs, d)
    logits = jnp.dot(q, k.T, precision="highest") * scale   # (G, bs)
    m_new = jnp.maximum(m_ref[...], jnp.max(logits, axis=-1, keepdims=True))
    p = jnp.exp(logits - m_new)                      # (G, bs)
    alpha = jnp.exp(m_ref[...] - m_new)              # (G, 1)
    l_new = alpha * l_ref[...] + jnp.sum(p, axis=-1, keepdims=True)
    acc_ref[...] = acc_ref[...] * alpha + jnp.dot(p, v, precision="highest")
    m_ref[...] = m_new
    l_ref[...] = l_new

    @pl.when(s_idx == n_s - 1)
    def _flush():
        o_ref[0] = (acc_ref[...] / l_ref[...]).astype(o_ref.dtype)


@functools.partial(jax.jit, static_argnames=("block_kv", "interpret"))
def decode_attention(q: jax.Array, k: jax.Array, v: jax.Array, *,
                     block_kv: int = 512,
                     interpret: bool = False) -> jax.Array:
    """q: (B, Hq, d); k, v: (B, S, Hkv, d) -> (B, Hq, d)."""
    B, Hq, d = q.shape
    _, S, Hkv, _ = k.shape
    G = Hq // Hkv
    bs = min(block_kv, S)
    while S % bs:
        bs //= 2
    scale = 1.0 / (d ** 0.5)

    qh = q.reshape(B, Hkv, G, d).reshape(B * Hkv, G, d)
    kh = k.transpose(0, 2, 1, 3).reshape(B * Hkv, S, d)
    vh = v.transpose(0, 2, 1, 3).reshape(B * Hkv, S, d)

    o = pl.pallas_call(
        functools.partial(_decode_attn_kernel, scale=scale),
        grid=(B * Hkv, S // bs),
        in_specs=[
            pl.BlockSpec((1, G, d), lambda h, s: (h, 0, 0)),
            pl.BlockSpec((1, bs, d), lambda h, s: (h, s, 0)),
            pl.BlockSpec((1, bs, d), lambda h, s: (h, s, 0)),
        ],
        out_specs=pl.BlockSpec((1, G, d), lambda h, s: (h, 0, 0)),
        out_shape=jax.ShapeDtypeStruct((B * Hkv, G, d), q.dtype),
        scratch_shapes=[
            pltpu.VMEM((G, d), jnp.float32),
            pltpu.VMEM((G, 1), jnp.float32),
            pltpu.VMEM((G, 1), jnp.float32),
        ],
        interpret=interpret,
    )(qh, kh, vh)
    return o.reshape(B, Hkv, G, d).reshape(B, Hq, d)
