"""Plain references of the benchmarked programs, one module per program.

Each module holds the program's interface (``INPUTS``, ``OUTPUTS``: the
shape of every argument and result, ``"n"`` standing for the size), the
operations a call needs (``flops``), the float64 reference in row blocks
(``reference``) and the lower-precision control (``control``).  It may
give an output a scale of its own to compare against (``scale``), and a
number of its own to be held to (``CHECKS``: output name -> the number's
name in the cell's limits; ``max_err`` by default).
Nothing here imports the program: a later change to it cannot move the
oracle.
"""
import importlib
from concurrent.futures import ThreadPoolExecutor
import os

import numpy as np

#: rows per block of a float64 reference (1024 x 16384 float64 = 128 MiB)
ROWS = 1024
THREADS = min(8, os.cpu_count() or 1)


def load(program: str):
    """The reference module of ``program`` (``"GEMVER"`` -> ``gemver``)."""
    return importlib.import_module(f"bench.refs.{program.lower()}")


def shapes(spec: dict, n: int) -> dict:
    """``{name: ("n", "n")}`` -> ``{name: (n, n)}``."""
    return {k: tuple(n if d == "n" else d for d in v) for k, v in spec.items()}


def check_of(ref, output: str) -> str:
    """The number compared that holds ``output`` of reference ``ref``."""
    return getattr(ref, "CHECKS", {}).get(output, "max_err")


def errors(ref, got, inputs: dict, want) -> dict[str, float]:
    """``rel_err`` of each output of ``got`` against ``want``, the
    reference module ``ref``'s outputs for ``inputs``, by output name."""
    scale = getattr(ref, "scale", lambda name, inputs, want: None)
    return {name: rel_err(g, w, scale(name, inputs, want))
            for name, g, w in zip(ref.OUTPUTS, got, want)}


def rel_err(got, ref, scale: float | None = None) -> float:
    """max |got - ref| / scale: the widest gap against the reference, in
    units of ``scale``, by default the reference's largest entry.  2-D
    operands are compared in row blocks, so a 16384 x 16384 comparison
    needs no full-size temporary.  A non-finite or misshapen ``got``
    reads ``inf``."""
    got = np.asarray(got)
    ref = np.asarray(ref)
    if got.shape != ref.shape:
        return float("inf")
    if ref.ndim < 2:
        g = got.astype(np.float64)
        if not np.all(np.isfinite(g)):
            return float("inf")
        err = float(np.max(np.abs(g - ref), initial=0.0))
        return _ratio(err, float(np.max(np.abs(ref), initial=0.0))
                      if scale is None else scale)

    def block(i):
        g = got[i:i + ROWS].astype(np.float64)
        if not np.all(np.isfinite(g)):
            return float("inf"), 0.0
        r = ref[i:i + ROWS]
        return float(np.max(np.abs(g - r))), float(np.max(np.abs(r)))

    with ThreadPoolExecutor(THREADS) as ex:
        parts = list(ex.map(block, range(0, ref.shape[0], ROWS)))
    return _ratio(max(p[0] for p in parts),
                  max(p[1] for p in parts) if scale is None else scale)


def _ratio(err: float, scale: float) -> float:
    if err == 0.0:
        return 0.0
    return err / scale if scale > 0 else float("inf")
