"""Paper Table 5: compiler timing — first implementation, all
implementations, and (bounded) empirical search."""
from __future__ import annotations

import time

from repro.blas import REGISTRY, make_inputs
from repro.core import FusionCompiler, codegen, scheduler


def run_sequence(name: str, n: int = 1024, search_limit: int = 16):
    seq = REGISTRY[name]
    cc = FusionCompiler()

    t0 = time.perf_counter()
    g = cc.trace(seq.script, seq.shapes(n))
    space = cc.space(g)
    best = scheduler.best_combination(space)
    codegen.compile_combination(g, best, backend="jnp")
    t_first = time.perf_counter() - t0

    t0 = time.perf_counter()
    combos = scheduler.enumerate_combinations(space, limit=5000)
    t_all = time.perf_counter() - t0 + t_first

    t0 = time.perf_counter()
    inputs = make_inputs(seq, n)
    import jax
    for c in combos[:search_limit]:
        prog = codegen.compile_combination(g, c, backend="jnp")
        jax.block_until_ready(prog(**inputs))
    t_search = time.perf_counter() - t0

    return {"name": name, "t_first_s": t_first, "t_all_s": t_all,
            "n_combinations": len(combos),
            "t_search_s": t_search, "searched": min(search_limit, len(combos))}


def main():
    print(f"{'seq':9s} {'first':>8s} {'enumerate':>10s} {'combos':>7s} "
          f"{'search(16)':>11s}")
    rows = []
    for name in REGISTRY:
        r = run_sequence(name)
        rows.append(r)
        print(f"{r['name']:9s} {r['t_first_s']:7.3f}s {r['t_all_s']:9.3f}s "
              f"{r['n_combinations']:7d} {r['t_search_s']:10.2f}s")
    return rows


if __name__ == "__main__":
    from repro.launch import enable_compile_cache
    enable_compile_cache()
    main()
