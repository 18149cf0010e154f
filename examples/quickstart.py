"""Quickstart: the kernel-fusion compiler on a BLAS sequence.

Reproduces the paper's core flow on the BiCGK sequence (q = Ap, s = Aᵀr):
trace the script, search the fusion space, compare the compiler's fused
code against the unfused (CUBLAS-dispatch-style) baseline, and validate
against numpy.

    PYTHONPATH=src python examples/quickstart.py
"""
import time

import numpy as np

from repro.core import FusionCompiler
from repro.programs import BLAS, make_inputs

def main():
    n = 2048
    seq = BLAS["BiCGK"]
    cc = FusionCompiler()

    prog, report = cc.compile(seq.script, seq.shapes(n), report=True)
    print(f"fusions considered: {report.n_fusions}, implementations: "
          f"{report.n_impls}, combinations: {report.n_combinations}")
    print(f"predicted speedup vs unfused: {report.predicted_speedup:.2f}x")
    for impl in report.best.impls:
        print("  kernel:", impl.describe())

    inputs = make_inputs(seq, n)
    q, s = prog(**inputs)
    qr, sr = seq.reference(**inputs)
    np.testing.assert_allclose(np.asarray(q), qr, rtol=1e-4, atol=1e-3)
    np.testing.assert_allclose(np.asarray(s), sr, rtol=1e-4, atol=1e-3)
    print("matches numpy oracle ✓")

    unfused = cc.compile(seq.script, seq.shapes(n), mode="unfused")
    import jax
    for name, p in [("fused", prog), ("unfused", p_u := unfused)]:
        jax.block_until_ready(p(**inputs))
        t0 = time.perf_counter()
        for _ in range(10):
            jax.block_until_ready(p(**inputs))
        print(f"{name}: {(time.perf_counter()-t0)/10*1e6:.0f} us/call")

    # the same compiler, Pallas backend (TPU-targeted; interpret on CPU)
    ccp = FusionCompiler(backend="pallas", interpret=True)
    progp = ccp.compile(seq.script, seq.shapes(512), mode="best")
    inp = make_inputs(seq, 512)
    qp, sp = progp(**inp)
    qr2, sr2 = seq.reference(**inp)
    np.testing.assert_allclose(np.asarray(qp), qr2, rtol=1e-3, atol=1e-3)
    print("Pallas backend (interpret) matches ✓")

if __name__ == "__main__":
    main()
