"""Synthetic elementary-call chains of arbitrary length.

The paper's sequences are at most 5 calls; these chains scale the
search and the backends past the hand-sized scripts.
"""
from __future__ import annotations

from . import elementary_lib as lib

__all__ = ["make_synthetic_chain"]


def make_synthetic_chain(n_calls: int, *, reduce_consume: bool = False,
                         gemv: bool = False, scalar_input: bool = False):
    """A depth-1 map/accumulate chain of ``n_calls`` elementary calls.

    Mimics the dataflow of long vector pipelines (paper sequences are
    ≤ 5 calls; serving-scale graphs are not).  Returns ``(script,
    shapes_fn, reference)`` in the ``Program`` calling convention so
    tests and benchmarks can drive the full compiler pipeline on graphs
    of arbitrary length.

    Optional structure for backend stress tests (all default off, so
    the historical ``make_synthetic_chain(n)`` graphs are unchanged):

    * ``scalar_input`` — a scalar graph input ``alpha`` scales ``a``
      first (exercises the ``(1, 1)``-carrier BlockSpec path);
    * ``reduce_consume`` — the chain tail is sum-reduced and the
      finished scalar consumed by a later map (``xpay``), the fusion
      rule-2 reduce→consume link the pallas backend phases through a
      VMEM scratch accumulator;
    * ``gemv`` — an ATAX-shaped depth-2 pair ``A^T (A v)`` hangs off
      the chain tail: the second matvec consumes the first's finished
      reduction (needs a fresh ``(n, n)`` input ``A``).
    """

    def script(g, a, b, **extra):
        if scalar_input:
            a = g.apply(lib.scal, extra["alpha"], a)
        v = g.apply(lib.ew_add, a, b)
        vals = [a, b, v]
        for i in range(n_calls - 1):
            if i % 3 == 2:
                v = g.apply(lib.ew_add, vals[-1], vals[-2])
            else:
                v = g.apply(lib.ew_mul, vals[-1], vals[-3])
            vals.append(v)
        outs = [vals[-1]]
        if reduce_consume:
            s = g.apply(lib.sum_reduce, vals[-1])
            outs.append(g.apply(lib.xpay, s, a, b))
        if gemv:
            t = g.apply(lib.gemv_t, extra["A"], vals[-1])
            outs.append(g.apply(lib.gemtv_t, extra["A"], t))
        return tuple(outs)

    def shapes(n):
        d = {"a": (n,), "b": (n,)}
        if scalar_input:
            d["alpha"] = ()
        if gemv:
            d["A"] = (n, n)
        return d

    def reference(a, b, alpha=None, A=None):
        if scalar_input:
            a = alpha * a
        v = a + b
        vals = [a, b, v]
        for i in range(n_calls - 1):
            if i % 3 == 2:
                v = vals[-1] + vals[-2]
            else:
                v = vals[-1] * vals[-3]
            vals.append(v)
        outs = [vals[-1]]
        if reduce_consume:
            s = vals[-1].sum(dtype=vals[-1].dtype)
            outs.append(s * a + b)
        if gemv:
            t = A @ vals[-1]
            outs.append(A.T @ t)
        return tuple(outs)

    return script, shapes, reference
