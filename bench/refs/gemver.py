"""GEMVER (BLAS Technical Forum, arXiv:1305.1183 Table 1):

    B = A + u1 v1^T + u2 v2^T;  x = beta B^T y + z;  w = alpha B x
"""
from concurrent.futures import ThreadPoolExecutor

import jax.numpy as jnp
import numpy as np

from bench.refs import ROWS, THREADS

INPUTS = {"A": ("n", "n"), "u1": ("n",), "v1": ("n",), "u2": ("n",),
          "v2": ("n",), "y": ("n",), "z": ("n",), "alpha": (), "beta": ()}
OUTPUTS = {"B": ("n", "n"), "x": ("n",), "w": ("n",)}
#: the configuration states plain float32 (its matvecs are products
#: summed in float32, no matmul), so the control computes in bfloat16
CONTROL = "bfloat16"


def flops(n: int) -> float:
    """Rank-2 update 4n^2, B^T y 2n^2, beta t + z 2n, B x 2n^2, alpha t n."""
    return 8.0 * n * n + 3.0 * n


def reference(A, u1, v1, u2, v2, y, z, alpha, beta):
    """Float64 on the host, B built and reduced in row blocks."""
    u1, v1, u2, v2, y, z = (np.asarray(a, np.float64)
                            for a in (u1, v1, u2, v2, y, z))
    alpha, beta = float(alpha), float(beta)
    n = A.shape[0]
    B = np.empty((n, n))

    def rank2(i):
        s = slice(i, i + ROWS)
        blk = B[s]
        blk[...] = A[s]
        blk += np.outer(u1[s], v1)
        blk += np.outer(u2[s], v2)
        return blk.T @ y[s]

    with ThreadPoolExecutor(THREADS) as ex:
        parts = list(ex.map(rank2, range(0, n, ROWS)))
    x = beta * np.sum(parts, axis=0) + z
    w = alpha * (B @ x)
    return B, x, w


def control(A, u1, v1, u2, v2, y, z, alpha, beta):
    """The reference on the device in bfloat16, its sums in float32."""
    A, u1, v1, u2, v2, y, z, alpha, beta = (
        jnp.asarray(a, jnp.bfloat16) for a in (A, u1, v1, u2, v2, y, z, alpha, beta))
    B = A + u1[:, None] * v1[None, :] + u2[:, None] * v2[None, :]
    t = jnp.dot(B.T, y, preferred_element_type=jnp.float32).astype(jnp.bfloat16)
    x = beta * t + z
    w = alpha * jnp.dot(B, x, preferred_element_type=jnp.float32).astype(jnp.bfloat16)
    return B, x, w
