"""Kimi Delta Attention (KDA) of Kimi-Linear-48B-A3B at decode
(arXiv:2510.26692), one token for n rows, each row one (session, head)
with its own (key, value) state S:

    qn = q rsqrt(sum_i q_i^2 + 1e-6) 128**-0.5     kn = k rsqrt(sum_i k_i^2 + 1e-6)
    alpha_i = exp(-exp(a_log) softplus(f_i))        beta = sigmoid(b)
    u_j = sum_i kn_i alpha_i S_ij
    S_new_ij = alpha_i S_ij + kn_i beta (v_j - u_j)
    o_j = sum_i qn_i S_new_ij

at the published width: head_dim 128 (linear_attn_config).
"""
from concurrent.futures import ThreadPoolExecutor

import jax
import jax.numpy as jnp
import numpy as np

from bench.refs import ROWS, THREADS

DIM, EPS = 128, 1e-6

INPUTS = {"S": ("n", DIM, DIM), "q": ("n", DIM), "k": ("n", DIM),
          "v": ("n", DIM), "f": ("n", DIM), "a_log": ("n",), "b": ("n",)}
OUTPUTS = {"S_new": ("n", DIM, DIM), "o": ("n", DIM)}
#: the configuration states float32, so the control computes in bfloat16
CONTROL = "bfloat16"


def flops(n: int) -> float:
    """Per row, on the state: the gated read u 2, the update S_new 3 and
    the read-out o 2 per entry (7 * 128**2); per key or value channel:
    the two squared norms 4, the two normed vectors 5, the gate 7, the
    gated key 1, the write w 2, a sigmoid's 4 spread over the row
    (23 * 128)."""
    return n * (7.0 * DIM * DIM + 23.0 * DIM)


def _rows(S, q, k, v, f, a_log, b):
    """The step for one block of rows, in float64."""
    S, q, k, v, f, a_log, b = (np.asarray(x, np.float64)
                               for x in (S, q, k, v, f, a_log, b))
    qn = q / np.sqrt(np.sum(q * q, axis=1, keepdims=True) + EPS) / np.sqrt(DIM)
    kn = k / np.sqrt(np.sum(k * k, axis=1, keepdims=True) + EPS)
    alpha = np.exp(-np.exp(a_log)[:, None] * np.logaddexp(f, 0.0))
    beta = 1.0 / (1.0 + np.exp(-b))
    S_new = alpha[:, :, None] * S
    u = np.einsum("ri,rij->rj", kn, S_new)
    S_new += kn[:, :, None] * (beta[:, None] * (v - u))[:, None, :]
    return S_new, np.einsum("ri,rij->rj", qn, S_new)


def reference(S, q, k, v, f, a_log, b):
    """Float64 on the host, in blocks of ``ROWS`` rows."""
    n = S.shape[0]
    S_new, o = np.empty(S.shape), np.empty(q.shape)

    def block(i):
        r = slice(i, i + ROWS)
        S_new[r], o[r] = _rows(S[r], q[r], k[r], v[r], f[r], a_log[r], b[r])

    with ThreadPoolExecutor(THREADS) as ex:
        list(ex.map(block, range(0, n, ROWS)))
    return S_new, o


def control(S, q, k, v, f, a_log, b):
    """The step on the device in bfloat16."""
    S, q, k, v, f, a_log, b = (jnp.asarray(x, jnp.bfloat16)
                               for x in (S, q, k, v, f, a_log, b))

    def l2norm(x):
        return x * jax.lax.rsqrt(jnp.sum(x * x, axis=1, keepdims=True) + EPS)

    qn, kn = l2norm(q) * DIM ** -0.5, l2norm(k)
    alpha = jnp.exp(-jnp.exp(a_log)[:, None] * jax.nn.softplus(f))
    gated = alpha[:, :, None] * S
    u = jnp.sum(kn[:, :, None] * gated, axis=1)
    w = jax.nn.sigmoid(b)[:, None] * (v - u)
    S_new = gated + kn[:, :, None] * w[:, None, :]
    return S_new, jnp.sum(qn[:, :, None] * S_new, axis=1)
