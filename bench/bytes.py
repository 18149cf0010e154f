"""The bytes and operations one call of a program requires.

Required bytes are every interface input read once and every output
written once, taken from the arrays of the call itself.  They never come
from a plan's own traffic count, so a roofline share reads the same work
whichever plan or backend implements the call: a fusion that moves fewer
bytes shows as less time, never as a smaller yardstick.
"""
import math

import numpy as np


def nbytes(arrays) -> int:
    """Bytes of ``arrays`` (any mix of arrays and scalars) at their size."""
    total = 0
    for a in arrays:
        shape = np.shape(a)
        total += math.prod(shape) * np.dtype(getattr(a, "dtype", np.float32)).itemsize
    return total


def required_bytes(inputs: dict, outputs) -> int:
    """Each input read once plus each output written once."""
    outs = outputs if isinstance(outputs, (tuple, list)) else (outputs,)
    return nbytes(inputs.values()) + nbytes(outs)


def roofline_s(req_bytes: float, flops: float, peak: dict) -> tuple[float, str]:
    """The least time the chip could take for the work, and its bound."""
    t_bytes = req_bytes / peak["hbm_bytes_per_s"]
    t_flops = flops / peak["flops_per_s"]
    return (t_bytes, "hbm") if t_bytes >= t_flops else (t_flops, "flops")
