"""DeepSeek-V2-Lite's routed experts at decode (arXiv:2405.04434 section
2.2), the experts one chip holds under expert parallelism, each on the n
tokens dispatched to it:

    g[e, c] = w_gate[e] x[e, c]        u[e, c] = w_up[e] x[e, c]
    y[e, c] = r[e, c] w_down[e] (silu(g[e, c]) * u[e, c])

at the published widths: hidden_size 2048, moe_intermediate_size 1408,
8 of the 64 routed experts (one chip's share over 8 chips).
"""
from concurrent.futures import ThreadPoolExecutor

import jax
import jax.numpy as jnp
import numpy as np

from bench.refs import THREADS

EXPERTS, HIDDEN, WIDTH = 8, 2048, 1408

INPUTS = {"x": (EXPERTS, "n", HIDDEN), "r": (EXPERTS, "n"),
          "w_gate": (EXPERTS, WIDTH, HIDDEN), "w_up": (EXPERTS, WIDTH, HIDDEN),
          "w_down": (EXPERTS, HIDDEN, WIDTH)}
OUTPUTS = {"y": (EXPERTS, "n", HIDDEN)}
#: the configuration states float32 at full matmul precision, so the
#: control computes in bfloat16
CONTROL = "bfloat16"
#: experts per block of the float64 reference (92 MB of float64 weights)
BLOCK = 1


def flops(n: int) -> float:
    """Per expert, token and expert-width channel: the gate and up
    projections 2 each and the down projection 2, per hidden channel
    (6 * 2048); the sigmoid 4, its product with g, u and r 3 (7)."""
    return EXPERTS * n * WIDTH * (6.0 * HIDDEN + 7.0)


def _experts(x, r, w_gate, w_up, w_down):
    """The experts of one block, in float64, by batched matmuls."""
    x, r, w_gate, w_up, w_down = (np.asarray(a, np.float64)
                                  for a in (x, r, w_gate, w_up, w_down))
    g = np.matmul(x, np.swapaxes(w_gate, 1, 2))
    a = r[:, :, None] * (g / (1.0 + np.exp(-g))) * np.matmul(
        x, np.swapaxes(w_up, 1, 2))
    return np.matmul(a, np.swapaxes(w_down, 1, 2))


def reference(x, r, w_gate, w_up, w_down):
    """Float64 on the host, in blocks of ``BLOCK`` experts."""
    y = np.empty(x.shape)

    def block(i):
        e = slice(i, i + BLOCK)
        y[e] = _experts(x[e], r[e], w_gate[e], w_up[e], w_down[e])

    with ThreadPoolExecutor(THREADS) as ex:
        list(ex.map(block, range(0, x.shape[0], BLOCK)))
    return (y,)


def control(x, r, w_gate, w_up, w_down):
    """The experts on the device in bfloat16, their products summed in
    float32."""
    x, r, w_gate, w_up, w_down = (jnp.asarray(a, jnp.bfloat16)
                                  for a in (x, r, w_gate, w_up, w_down))
    g = jnp.einsum("ech,eih->eci", x, w_gate,
                   preferred_element_type=jnp.float32)
    u = jnp.einsum("ech,eih->eci", x, w_up,
                   preferred_element_type=jnp.float32)
    a = (r[:, :, None] * jax.nn.silu(g) * u).astype(jnp.bfloat16)
    return (jnp.einsum("eci,edi->ecd", a, w_down,
                       preferred_element_type=jnp.float32)
            .astype(jnp.bfloat16),)
