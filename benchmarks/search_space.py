"""Paper Table 4: optimization-space size and prediction quality.

For every sequence: number of generated combinations, the *rank* the
empirically-fastest combination gets from the performance predictor,
and first/worst relative performance — the paper's measure of whether
predicted ordering makes empirical search cheap.
"""
from __future__ import annotations

import time

import numpy as np

from repro.blas import REGISTRY, make_inputs
from repro.core import FusionCompiler, codegen, scheduler

PAPER_T4 = {  # impl count, best rank (paper Table 4)
    "AXPYDOT": (25, 4), "ATAX": (1, 1), "BiCGK": (5, 1), "SGEMV": (83, 14),
    "SGEMVT": (41, 5), "SSCAL": (1, 1), "GEMVER": (1271, 54),
    "GESUMMV": (415, 51), "MADD": (1, 1), "VADD": (41, 14), "WAXPBY": (83, 1),
}


def _time(prog, inputs, iters=3):
    import jax
    jax.block_until_ready(prog(**inputs))
    ts = []
    for _ in range(iters):
        t0 = time.perf_counter()
        jax.block_until_ready(prog(**inputs))
        ts.append(time.perf_counter() - t0)
    return float(np.median(ts))


def run_sequence(name: str, n: int = 1024, limit: int = 64, iters: int = 3):
    seq = REGISTRY[name]
    cc = FusionCompiler()
    g = cc.trace(seq.script, seq.shapes(n))
    space = cc.space(g)
    combos = scheduler.enumerate_combinations(space, limit=limit)
    times = []
    for c in combos:
        prog = codegen.compile_combination(g, c, backend="jnp")
        inputs = make_inputs(seq, n)
        times.append(_time(prog, inputs, iters))
    times = np.asarray(times)
    best_idx = int(np.argmin(times))
    # rank counts predictions whose measured time ties within 0.1%
    t_best = times[best_idx]
    first_rel = t_best / times[0]
    worst_rel = t_best / times.max()
    return {
        "name": name,
        "n_fusions": len(space.fusions),
        "n_impls": space.n_impls,
        "n_combinations_total": len(
            scheduler.enumerate_combinations(space, limit=5000)),
        "n_benchmarked": len(combos),
        "best_rank": best_idx + 1,
        "first_impl_rel_perf": float(first_rel),
        "worst_impl_rel_perf": float(worst_rel),
        "paper_impls": PAPER_T4[name][0],
        "paper_best_rank": PAPER_T4[name][1],
    }


def main(limit: int = 32):
    print(f"{'seq':9s} {'combos':>7s} {'bench':>6s} {'best@':>6s} "
          f"{'first%':>7s} {'worst%':>7s}   paper(count,rank)")
    rows = []
    for name in REGISTRY:
        r = run_sequence(name, limit=limit)
        rows.append(r)
        print(f"{r['name']:9s} {r['n_combinations_total']:7d} "
              f"{r['n_benchmarked']:6d} {r['best_rank']:6d} "
              f"{100*r['first_impl_rel_perf']:6.1f}% "
              f"{100*r['worst_impl_rel_perf']:6.1f}%   "
              f"({r['paper_impls']},{r['paper_best_rank']})")
    return rows


if __name__ == "__main__":
    from repro.launch import enable_compile_cache
    enable_compile_cache()
    main()
