"""Serving-engine benchmark: batched ServingEngine (shape buckets + vmap
horizontal fusion, DESIGN.md §6) vs the PR 1 one-request-per-dispatch
loop on the same mixed-size workload, plus — with a multi-device mesh —
the shard_map-sharded engine (DESIGN.md §7).  Writes
``BENCH_serving.json``.

The ``engine`` series packs cross-sequence batches into multi-graph
dispatches (DESIGN.md §9, ``max_pack=8``).  ``packed_vs_unpacked``
compares packed vs ``max_pack=1`` engines on the regime packing
targets — mixed traffic over ALL registry sequences at small/medium
sizes, where per-dispatch overhead is a real fraction of serve time
(the main series' large buckets are bandwidth-bound and packing is
neutral there) — reporting the dispatch-count reduction, the
requests/sec speedup, and whether the two paths' outputs are bitwise
equal (they must be).

    PYTHONPATH=src python -m benchmarks.serving [--quick] [--emit-json [PATH]]
    PYTHONPATH=src python -m benchmarks.serving --devices 8 --emit-json

``--devices N`` forces N host CPU devices (set before jax initializes)
and adds the ``sharded`` series: the same workload spread over the
``data`` axis of an N-replica mesh.  On a forced-CPU mesh the replicas
share physical cores, so the sharded series measures dispatch/routing
overhead rather than real scaling; on a real multi-chip mesh the same
code path scales throughput with the replica count.

All paths are fully warmed (plans compiled, jits traced) before timing,
and all dispatch asynchronously with one final block — what's measured
is the steady-state serving difference: one dispatch per *batch* vs one
dispatch per *request*, padding overhead included on the engine side.

Timing hardening: after warming, the process holds ~100k live objects
(jax traces), so one cyclic-GC full pass costs tens of ms — longer than
a whole serve pass.  Whether that pass lands inside the timed window is
an allocation-count accident (measured: a 6x swing from inert code
changes), and because ``gc.collect()`` resets the allocation counters,
a pass that allocates past the gen-2 threshold re-triggers it on EVERY
rep identically — min-of-reps alone can't escape.  Each serve is
therefore timed as the best of ``REPS`` runs with ``gc.collect()``
flushed before and the collector disabled during each window
(re-enabled after), the same min-of-batches discipline BENCH_fusion
uses plus standard benchmark GC hygiene.
"""
from __future__ import annotations

import argparse
import gc
import json
import time

import numpy as np

REPS = 3
WARMUP_PASSES = 5     # untimed serve passes before timing (see _run_with)
PASSES = REPS + WARMUP_PASSES   # total per-engine passes, for counters


def _best_serve(run_once):
    """Best-of-REPS timed runs of ``run_once``; GC flushed before and
    DISABLED during each window; returns (t_best, results_of_best).

    Disabling matters, not just flushing: collect() resets the
    allocation counters, so a pass that allocates past the gen-2
    threshold (~70k objects — the 11-sequence packed workload does)
    would trigger a full collection INSIDE the window on every rep
    identically, and min-of-reps can't average away a deterministic
    10x hit."""
    best_t, best_r = None, None
    for _ in range(REPS):
        gc.collect()
        gc.disable()
        try:
            t0 = time.perf_counter()
            results = run_once()
            t = time.perf_counter() - t0
        finally:
            gc.enable()
        if best_t is None or t < best_t:
            best_t, best_r = t, results
    return best_t, best_r

SIZES = (256, 1000, 1024, 2048)
SEQUENCES = ("AXPYDOT", "VADD", "WAXPBY", "SSCAL")


def build_workload(sequences, sizes, n_requests, seed=0):
    from repro.blas import REGISTRY, make_inputs
    workload = []
    for i in range(n_requests):
        name = sequences[i % len(sequences)]
        n = sizes[(i // len(sequences)) % len(sizes)]
        workload.append((name, n, make_inputs(REGISTRY[name], n, seed=seed + i)))
    return workload


def _run_with(engine, workload, sequences, sizes):
    """Warm, best-of-REPS serve, and the engine-independent stats.

    ``warm()``/``warm_packs()`` pre-trace the predictable shapes, but a
    drain can still form pack compositions warm can't predict (uneven
    per-key unit counts — DESIGN.md §9 open edge), and a freshly built
    XLA:CPU executable takes a few executions to reach steady state
    (measured: 1260 → 28 → 9 → 6 ms over the first passes of a packed
    program).  ``WARMUP_PASSES`` untimed serve passes absorb both
    before the timed reps; ``PASSES`` normalizes the cumulative
    dispatch counters back to per-pass."""
    t0 = time.perf_counter()
    for name in sequences:
        engine.warm(name, sizes, trace_packs=False)
    engine.warm_packs()     # once, over the full warmed key set
    t_warm = time.perf_counter() - t0
    for _ in range(WARMUP_PASSES):   # untimed (see docstring)
        engine.serve(workload)

    t_serve, results = _best_serve(lambda: engine.serve(workload))
    lat = np.sort([r.latency_s for r in results])
    stats = engine.stats()
    return {
        "throughput_rps": len(results) / t_serve,
        "t_serve_s": t_serve, "t_warm_s": t_warm,
        "p50_ms": float(lat[len(lat) // 2]) * 1e3,
        "p99_ms": float(lat[min(len(lat) - 1, int(len(lat) * 0.99))]) * 1e3,
        "n_dispatches": stats["n_dispatches"] // PASSES,   # per serve pass
        "batch_occupancy": stats["batch_occupancy"],
    }, results, stats


def run_engine(workload, sequences, sizes, max_batch=8, max_pack=8) -> dict:
    from repro.serving import ServingEngine
    engine = ServingEngine(max_batch=max_batch, min_bucket=min(sizes),
                           max_pack=max_pack)
    out, results, stats = _run_with(engine, workload, sequences, sizes)
    out |= {"n_programs": len(stats["programs"]),
            "max_pack": max_pack,
            "n_packed_dispatches": stats["n_packed_dispatches"] // PASSES,
            "n_packed_members": stats["n_packed_members"] // PASSES,
            "queue_wait": stats["queue_wait"],
            "bucket_stats": stats["cache"]["buckets"]}
    return out, results


def run_sharded(workload, sequences, sizes, max_batch=8) -> dict:
    """The §7 engine: same workload, dispatches shard_mapped over the
    ``data`` axis of a replica mesh over all local devices."""
    from repro.serving import ShardedServingEngine
    engine = ShardedServingEngine(max_batch=max_batch, min_bucket=min(sizes))
    out, results, stats = _run_with(engine, workload, sequences, sizes)
    out |= {"n_replicas": stats["n_replicas"],
            "replica_rows": [r // PASSES for r in stats["replica_rows"]],
            "max_batch": engine.max_batch}
    return out, results


def run_baseline(workload) -> dict:
    """PR 1 serving: one exact-shape compile per (sequence, n), one
    dispatch per request (async), one final block."""
    import jax
    from repro.blas import REGISTRY
    from repro.core import FusionCompiler
    cc = FusionCompiler()
    t0 = time.perf_counter()
    progs = {}
    for name, n, inputs in workload:
        key = (name, n)
        if key not in progs:
            seq = REGISTRY[name]
            progs[key] = cc.compile(seq.script, seq.shapes(n))
            progs[key].block_until_ready(progs[key](**inputs))  # trace warm
    t_warm = time.perf_counter() - t0

    def once():
        outs = [progs[(name, n)](**inputs) for name, n, inputs in workload]
        jax.block_until_ready(outs)
        return outs

    t_serve, _ = _best_serve(once)
    return {"throughput_rps": len(workload) / t_serve, "t_serve_s": t_serve,
            "t_warm_s": t_warm, "n_dispatches": len(workload),
            "n_programs": len(progs)}


def verify(workload, results) -> bool:
    """Every engine result matches its per-request numpy reference on
    the unpadded slice (float64 oracle, f32-roundoff tolerance).

    Results are matched to the workload by submission order (ascending
    rid) — repeat serve passes renumber rids but preserve order."""
    from repro.blas import REGISTRY
    ordered = sorted(results, key=lambda r: r.rid)
    for (name, n, inputs), res in zip(workload, ordered):
        ref = REGISTRY[name].reference(
            **{k: np.asarray(v, np.float64) for k, v in inputs.items()})
        got = res.outputs
        for o, r in zip(got, ref):
            if not np.allclose(np.asarray(o, np.float64), r,
                               rtol=1e-4, atol=1e-4 * max(1.0, np.abs(r).max())):
                return False
    return True


def bitwise_equal(results_a, results_b) -> bool:
    """Every output of every request identical (by rid order) between
    two serve passes — the packed path's correctness bar."""
    a = sorted(results_a, key=lambda r: r.rid)
    b = sorted(results_b, key=lambda r: r.rid)
    return (len(a) == len(b) and all(
        len(x.outputs) == len(y.outputs)
        and all(np.array_equal(p, q) for p, q in zip(x.outputs, y.outputs))
        for x, y in zip(a, b)))


PACK_SIZES = (64, 100, 128)      # dispatch-overhead-bound buckets


def run_packed_comparison(n_requests=128, max_batch=8, seed=0) -> dict:
    """Packed (max_pack=8) vs unpacked (max_pack=1) engines on mixed
    traffic over every registry sequence at the ``PACK_SIZES`` buckets
    — the dispatch-bound regime §9 packing targets."""
    from repro.blas import REGISTRY
    sequences, sizes = tuple(REGISTRY), PACK_SIZES
    workload = build_workload(sequences, sizes, n_requests, seed)
    packed, presults = run_engine(workload, sequences, sizes, max_batch)
    unpacked, uresults = run_engine(workload, sequences, sizes, max_batch,
                                    max_pack=1)
    return {
        "n_requests": n_requests, "sizes": list(sizes),
        "sequences": list(sequences),
        "packed_dispatches": packed["n_dispatches"],
        "n_packed_dispatches": packed["n_packed_dispatches"],
        "unpacked_dispatches": unpacked["n_dispatches"],
        "dispatch_reduction": (unpacked["n_dispatches"]
                               / max(packed["n_dispatches"], 1)),
        "throughput_packed_rps": packed["throughput_rps"],
        "throughput_unpacked_rps": unpacked["throughput_rps"],
        "speedup_rps": packed["throughput_rps"] / unpacked["throughput_rps"],
        "queue_wait": packed["queue_wait"],
        "verified": verify(workload, presults),
        "bitwise_equal": bitwise_equal(presults, uresults),
    }


def run_all(n_requests=128, sizes=SIZES, sequences=SEQUENCES, max_batch=8,
            seed=0, sharded=False) -> dict:
    workload = build_workload(sequences, sizes, n_requests, seed)
    engine, results = run_engine(workload, sequences, sizes, max_batch)
    baseline = run_baseline(workload)
    out = {
        "n_requests": n_requests, "sizes": list(sizes),
        "sequences": list(sequences), "max_batch": max_batch,
        "verified": verify(workload, results),
        "engine": engine, "baseline": baseline,
        "speedup_rps": engine["throughput_rps"] / baseline["throughput_rps"],
        "packed_vs_unpacked": run_packed_comparison(
            n_requests=n_requests, max_batch=max_batch, seed=seed),
    }
    if sharded:
        shd, sresults = run_sharded(workload, sequences, sizes, max_batch)
        out["sharded"] = shd
        out["sharded_verified"] = verify(workload, sresults)
        out["sharded_speedup_rps"] = (shd["throughput_rps"]
                                      / baseline["throughput_rps"])
    return out


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--quick", action="store_true")
    ap.add_argument("--requests", type=int, default=None)
    ap.add_argument("--max-batch", type=int, default=8)
    ap.add_argument("--devices", type=int, default=0,
                    help="force N host CPU devices and add the sharded-"
                    "engine series (sets XLA_FLAGS before jax init)")
    ap.add_argument("--emit-json", nargs="?", const="BENCH_serving.json",
                    default=None, metavar="PATH")
    args = ap.parse_args()
    from repro.launch import force_host_devices
    force_host_devices(args.devices)
    sizes = (64, 100, 128, 256) if args.quick else SIZES
    # 128 = 4 sequences x 4 sizes x one full max_batch=8 batch each
    n_requests = args.requests or (32 if args.quick else 128)

    r = run_all(n_requests=n_requests, sizes=sizes, max_batch=args.max_batch,
                sharded=args.devices > 1)
    print(f"serving {r['n_requests']} requests, sizes {r['sizes']}, "
          f"sequences {r['sequences']}, max_batch {r['max_batch']}, "
          f"verified={r['verified']}")
    print(f"  engine:   {r['engine']['throughput_rps']:10.1f} req/s  "
          f"p50 {r['engine']['p50_ms']:.2f} ms  p99 {r['engine']['p99_ms']:.2f} ms  "
          f"{r['engine']['n_dispatches']} dispatches  "
          f"occupancy {r['engine']['batch_occupancy']:.2f}")
    print(f"  baseline: {r['baseline']['throughput_rps']:10.1f} req/s  "
          f"{r['baseline']['n_dispatches']} dispatches")
    print(f"  speedup:  {r['speedup_rps']:.2f}x requests/sec")
    p = r["packed_vs_unpacked"]
    print(f"  packed vs unpacked ({len(p['sequences'])} sequences, "
          f"{p['n_requests']} requests, sizes {p['sizes']}): "
          f"{p['unpacked_dispatches']} -> {p['packed_dispatches']} "
          f"dispatches ({p['dispatch_reduction']:.2f}x fewer), "
          f"{p['speedup_rps']:.2f}x requests/sec, "
          f"bitwise_equal={p['bitwise_equal']}")
    if "sharded" in r:
        s = r["sharded"]
        print(f"  sharded:  {s['throughput_rps']:10.1f} req/s  "
              f"p50 {s['p50_ms']:.2f} ms  p99 {s['p99_ms']:.2f} ms  "
              f"{s['n_dispatches']} dispatches over {s['n_replicas']} "
              f"replicas  verified={r['sharded_verified']}  "
              f"({r['sharded_speedup_rps']:.2f}x vs baseline)")
    if args.emit_json:
        with open(args.emit_json, "w") as f:
            json.dump(r, f, indent=1)
        print(f"written: {args.emit_json}")
    return r


if __name__ == "__main__":
    from repro.launch import enable_compile_cache
    enable_compile_cache()
    main()
