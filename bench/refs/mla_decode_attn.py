"""DeepSeek-V2-Lite latent attention (MLA) at decode, absorbed form
(arXiv:2405.04434 section 2.1), for one session of n cached positions:

    s[h, t] = scale (sum_c q_lat[h, c] ckv[t, c] + sum_r q_rope[h, r] kr[t, r])
    p[h, t] = softmax over t of s[h, t]
    o_lat[h, c] = sum_t p[h, t] ckv[t, c]

at the published widths: 16 heads, kv_lora_rank 512, qk_rope_head_dim 64.
"""
import math
from concurrent.futures import ThreadPoolExecutor

import jax
import jax.numpy as jnp
import numpy as np

from bench.refs import ROWS, THREADS

HEADS, RANK, ROPE, NOPE = 16, 512, 64, 128
#: DeepSeek's softmax scale: (qk_nope_head_dim + qk_rope_head_dim)**-0.5
#: times YaRN's mscale**2, mscale = 0.1 * mscale_all_dim * ln(factor) + 1
#: (rope_scaling: factor 40, mscale_all_dim 0.707)
SCALE = (NOPE + ROPE) ** -0.5 * (0.1 * 0.707 * math.log(40) + 1.0) ** 2

INPUTS = {"q_lat": (HEADS, RANK), "q_rope": (HEADS, ROPE),
          "ckv": ("n", RANK), "kr": ("n", ROPE)}
OUTPUTS = {"o_lat": (HEADS, RANK)}
#: the configuration states float32 at full matmul precision, so the
#: control computes in bfloat16
CONTROL = "bfloat16"


def flops(n: int) -> float:
    """Per head and position: scores 2 (rank + rope), scale and add 2,
    max 1, subtract and exp 2, sum 1, divide 1, weighted rows 2 rank."""
    return HEADS * n * (4.0 * RANK + 2.0 * ROPE + 7.0)


def reference(q_lat, q_rope, ckv, kr):
    """Float64 on the host, the cache read in row blocks: every score,
    then the softmax, then the weighted latent rows summed block by
    block."""
    q_lat, q_rope = (np.asarray(a, np.float64) for a in (q_lat, q_rope))
    n = ckv.shape[0]
    s = np.empty((q_lat.shape[0], n))

    def score(i):
        blk = slice(i, i + ROWS)
        s[:, blk] = SCALE * (q_lat @ np.asarray(ckv[blk], np.float64).T
                             + q_rope @ np.asarray(kr[blk], np.float64).T)

    with ThreadPoolExecutor(THREADS) as ex:
        list(ex.map(score, range(0, n, ROWS)))
    p = np.exp(s - np.max(s, axis=1, keepdims=True))
    p /= np.sum(p, axis=1, keepdims=True)

    def rows(i):
        blk = slice(i, i + ROWS)
        return p[:, blk] @ np.asarray(ckv[blk], np.float64)

    with ThreadPoolExecutor(THREADS) as ex:
        parts = list(ex.map(rows, range(0, n, ROWS)))
    return (np.sum(parts, axis=0),)


def control(q_lat, q_rope, ckv, kr):
    """The reference on the device in bfloat16, its products summed in
    float32."""
    q_lat, q_rope, ckv, kr = (jnp.asarray(a, jnp.bfloat16)
                              for a in (q_lat, q_rope, ckv, kr))
    s = (jnp.dot(q_lat, ckv.T, preferred_element_type=jnp.float32)
         + jnp.dot(q_rope, kr.T, preferred_element_type=jnp.float32))
    p = jax.nn.softmax((SCALE * s).astype(jnp.bfloat16), axis=-1)
    return (jnp.dot(p, ckv, preferred_element_type=jnp.float32)
            .astype(jnp.bfloat16),)
