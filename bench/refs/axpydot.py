"""AXPYDOT (arXiv:1305.1183 Table 1):  z = w - alpha v;  r = z^T u."""
import jax.numpy as jnp
import numpy as np

INPUTS = {"w": ("n",), "v": ("n",), "u": ("n",), "alpha": ()}
OUTPUTS = {"z": ("n",), "r": ()}
#: the configuration states plain float32 (no matmul), so the control
#: computes in bfloat16
CONTROL = "bfloat16"
#: the dot is held to a limit of its own, set from its own readings:
#: under z's, a reduce that left out a block of terms would pass
CHECKS = {"r": "r_err"}


def flops(n: int) -> float:
    """w - alpha v 2n, z * u n, the sum n."""
    return 4.0 * n


def reference(w, v, u, alpha):
    """Float64 on the host (vectors: a block is the whole operand)."""
    z = np.asarray(w, np.float64) - float(alpha) * np.asarray(v, np.float64)
    return z, np.dot(z, np.asarray(u, np.float64))


def scale(name, inputs, want):
    """r is compared in units of the 2-norm of its terms z_i u_i.  The
    rounding of a float32 sum of terms of random sign grows with that
    norm, as does the sum of any block of terms left out, whatever n is;
    |r| itself can be small beside its terms (they cancel), and a gap
    over |r| would swing from seed to seed without any change in how well
    the sum was made."""
    if name != "r":
        return None
    return float(np.linalg.norm(want[0] * np.asarray(inputs["u"], np.float64)))


def control(w, v, u, alpha):
    """The reference on the device in bfloat16, summed in float32."""
    w, v, u, alpha = (jnp.asarray(a, jnp.bfloat16) for a in (w, v, u, alpha))
    z = w - alpha * v
    return z, jnp.sum(z * u, dtype=jnp.float32)
