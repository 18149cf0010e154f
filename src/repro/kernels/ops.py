"""Public jit'd API over the Pallas kernels.

With ``use_pallas=True`` the kernels compile with Mosaic, which needs a
TPU; pass ``interpret=True`` to run them in the Pallas interpreter on any
other platform.  Shapes that do not meet a kernel's tiling constraints
transparently fall back to the jnp reference implementation — production
behaviour, not test scaffolding.
"""
from __future__ import annotations

import jax.numpy as jnp

from . import ref
from .adamw import adamw_update as _adamw_pallas
from .bicgk import bicgk as _bicgk_pallas
from .decode_attention import decode_attention as _decode_attn_pallas
from .gemver import gemver as _gemver_pallas
from .rmsnorm import rmsnorm as _rmsnorm_pallas
from .softmax_xent import softmax_xent as _xent_pallas

LANES = 128


def rmsnorm(x, gamma, eps=1e-6, *, use_pallas=False, interpret=False):
    if use_pallas and x.ndim == 2 and x.shape[-1] % LANES == 0:
        return _rmsnorm_pallas(x, gamma, eps=eps, interpret=interpret)
    return ref.rmsnorm(x, gamma, eps)


def adamw_update(p, g, m, v, *, lr, beta1=0.9, beta2=0.95, eps=1e-8,
                 weight_decay=0.0, step=1, use_pallas=False, interpret=False):
    if use_pallas and p.ndim == 1 and p.shape[0] % LANES == 0:
        return _adamw_pallas(p, g, m, v, lr=lr, beta1=beta1, beta2=beta2,
                             eps=eps, weight_decay=weight_decay, step=step,
                             interpret=interpret)
    return ref.adamw(p, g, m, v, lr=lr, beta1=beta1, beta2=beta2, eps=eps,
                     weight_decay=weight_decay, step=step)


def bicgk(A, p, r, *, use_pallas=False, interpret=False):
    if use_pallas:
        return _bicgk_pallas(A, p, r, interpret=interpret)
    return ref.bicgk(A, p, r)


def gemver(A, u1, v1, u2, v2, y, z, alpha, beta, *, use_pallas=False,
           interpret=False):
    if use_pallas:
        return _gemver_pallas(A, u1, v1, u2, v2, y, z, alpha, beta,
                              interpret=interpret)
    return ref.gemver(A, u1, v1, u2, v2, y, z, alpha, beta)


def softmax_xent(logits, labels, *, use_pallas=False, interpret=False):
    if use_pallas and logits.ndim == 2:
        return _xent_pallas(logits, labels, interpret=interpret)
    return ref.softmax_xent(logits, labels)


def decode_attention(q, k, v, *, use_pallas=False, interpret=False):
    B, Hq, d = q.shape
    Hkv = k.shape[2]
    if use_pallas and Hq % Hkv == 0 and d % LANES == 0:
        return _decode_attn_pallas(q, k, v, interpret=interpret)
    return ref.decode_attention(q, k, v)
