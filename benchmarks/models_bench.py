"""Model-workload benchmark (DESIGN.md §10): the registered LM
decode-step programs — rmsnorm, rmsnorm→matvec residual block, decode
attention, fused AdamW — compiled fused (``mode='best'``) vs unfused
(``mode='unfused'``) per size, plus mixed model traffic served through
the batched ``ServingEngine`` (masked attention included).  Writes
``BENCH_models.json``.

    PYTHONPATH=src python -m benchmarks.models_bench [--quick] [--emit-json [PATH]]

Timing reuses the interleaved min-of-batches discipline of
``benchmarks.blas_sequences._time_pair`` (machine-speed drift hits both
programs equally) and the serving series reuses ``benchmarks.serving``'s
GC hygiene (collector flushed before and disabled during each timed
window).
"""
from __future__ import annotations

import argparse
import json
import time

import numpy as np

from benchmarks.blas_sequences import _time_pair
from benchmarks.serving import REPS, WARMUP_PASSES, _best_serve

SIZES = (256, 1024, 2048)
QUICK_SIZES = (128, 256)
SERVE_SIZES = (64, 100, 128, 256)
MODEL_NAMES = ("LM_RMSNORM", "LM_BLOCK", "LM_DECODE_ATTN", "FUSED_ADAMW")


def run_program(name: str, n: int, iters: int = 5) -> dict:
    """Fused vs unfused wall time for one program at one size, with the
    f64 reference check on the fused outputs."""
    from repro.core import FusionCompiler
    from repro.programs import REGISTRY, make_inputs

    prog = REGISTRY[name]
    cc = FusionCompiler(cache=None)
    shapes = prog.shapes(n)
    fused = cc.compile(prog.script, shapes, mode="best")
    unfused = cc.compile(prog.script, shapes, mode="unfused")
    inputs = make_inputs(prog, n, seed=0)

    out = fused(**inputs)
    if not isinstance(out, tuple):
        out = (out,)
    ref = prog.reference(**{k: np.asarray(v, np.float64)
                            for k, v in inputs.items()})
    verified = all(
        np.allclose(np.asarray(o, np.float64), r,
                    rtol=1e-4, atol=1e-4 * max(1.0, np.abs(r).max()))
        for o, r in zip(out, ref))

    t_fused, t_unfused = _time_pair(fused, unfused, inputs, iters=iters)
    g = cc.trace(prog.script, shapes)
    return {
        "name": name, "n": n, "n_calls": len(g.calls),
        "t_fused_s": t_fused, "t_unfused_s": t_unfused,
        "speedup": t_unfused / t_fused,
        "gflops_fused": prog.flops(n) / t_fused / 1e9,
        "verified": bool(verified),
    }


def run_serving(n_requests: int = 64, max_batch: int = 8,
                sizes=SERVE_SIZES, seed: int = 0) -> dict:
    """Mixed model traffic (all four programs, mixed sizes) through the
    batched engine — the masked decode-attention path included."""
    from repro.core import FusionCompiler, PlanCache
    from repro.programs import REGISTRY, make_inputs
    from repro.serving import ServingEngine

    workload = []
    for i in range(n_requests):
        name = MODEL_NAMES[i % len(MODEL_NAMES)]
        n = sizes[(i // len(MODEL_NAMES)) % len(sizes)]
        workload.append((name, n, make_inputs(REGISTRY[name], n,
                                              seed=seed + i)))

    engine = ServingEngine(compiler=FusionCompiler(cache=PlanCache()),
                           max_batch=max_batch, min_bucket=min(sizes),
                           registry=REGISTRY)
    t0 = time.perf_counter()
    for name in MODEL_NAMES:
        engine.warm(name, sizes, trace_packs=False)
    engine.warm_packs()
    t_warm = time.perf_counter() - t0
    for _ in range(WARMUP_PASSES):
        engine.serve(workload)

    t_serve, results = _best_serve(lambda: engine.serve(workload))

    verified = True
    for (name, n, inputs), res in zip(workload,
                                      sorted(results, key=lambda r: r.rid)):
        ref = REGISTRY[name].reference(
            **{k: np.asarray(v, np.float64) for k, v in inputs.items()})
        for o, r in zip(res.outputs, ref):
            if not np.allclose(np.asarray(o, np.float64), r, rtol=1e-4,
                               atol=1e-4 * max(1.0, np.abs(r).max())):
                verified = False

    stats = engine.stats()
    masked = sorted(k[0] for k, spec in engine._specs.items() if spec[3])
    passes = WARMUP_PASSES + REPS    # untimed warmups + timed reps
    return {
        "n_requests": n_requests, "sizes": list(sizes),
        "sequences": list(MODEL_NAMES), "max_batch": max_batch,
        "throughput_rps": len(results) / t_serve,
        "t_serve_s": t_serve, "t_warm_s": t_warm,
        "n_dispatches": stats["n_dispatches"] // passes,
        "batch_occupancy": stats["batch_occupancy"],
        "masked_programs": sorted(set(masked)),
        "verified": bool(verified),
    }


def run_all(sizes=SIZES, iters: int = 5, n_requests: int = 64) -> dict:
    programs = [run_program(name, n, iters=iters)
                for name in MODEL_NAMES for n in sizes]
    return {
        "sizes": list(sizes),
        "programs": programs,
        "serving": run_serving(n_requests=n_requests),
    }


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--quick", action="store_true")
    ap.add_argument("--iters", type=int, default=5)
    ap.add_argument("--requests", type=int, default=None)
    ap.add_argument("--emit-json", nargs="?", const="BENCH_models.json",
                    default=None, metavar="PATH")
    args = ap.parse_args()
    sizes = QUICK_SIZES if args.quick else SIZES
    n_requests = args.requests or (16 if args.quick else 64)

    r = run_all(sizes=sizes, iters=args.iters, n_requests=n_requests)
    for p in r["programs"]:
        print(f"  {p['name']:>16} n={p['n']:<5} fused {p['t_fused_s']*1e6:8.1f} us  "
              f"unfused {p['t_unfused_s']*1e6:8.1f} us  "
              f"speedup {p['speedup']:.2f}x  verified={p['verified']}")
    s = r["serving"]
    print(f"  serving {s['n_requests']} mixed model requests: "
          f"{s['throughput_rps']:.1f} req/s, {s['n_dispatches']} dispatches, "
          f"occupancy {s['batch_occupancy']:.2f}, "
          f"masked={s['masked_programs']}, verified={s['verified']}")
    if args.emit_json:
        with open(args.emit_json, "w") as f:
            json.dump(r, f, indent=1)
        print(f"written: {args.emit_json}")
    return r


if __name__ == "__main__":
    from repro.launch import enable_compile_cache
    enable_compile_cache()
    main()
