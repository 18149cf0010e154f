"""Serving launcher: batched prefill + decode loop with continuous
token generation (greedy), KV cache managed on-mesh.

    PYTHONPATH=src python -m repro.launch.serve --arch llama3_8b --smoke \
        --batch 4 --prompt-len 32 --gen 32

BLAS-sequence serving (the fusion compiler's steady-state path): compile
a paper sequence once through the plan cache, then serve a request loop
where every request is ONE dispatch of the jitted whole-program
function.

    PYTHONPATH=src python -m repro.launch.serve --blas GEMVER \
        --requests 200 --n 1024

``--backend pallas`` serves the same program through the pallas backend
instead — every fused group one ``pl.pallas_call``, including
kernels that consume finished reductions in-kernel, in the grid step
that finishes them or a phase later (DESIGN.md §2).  The kernels compile with Mosaic, which needs a TPU;
``--interpret`` runs them in the Pallas interpreter on any platform:

    PYTHONPATH=src python -m repro.launch.serve --blas GEMVER \
        --backend pallas --requests 8 --n 16384          # on a TPU
    PYTHONPATH=src python -m repro.launch.serve --blas ATAX \
        --backend pallas --interpret --requests 4 --n 256

Empirical autotuning (DESIGN.md §8): ``--autotune`` compiles with
``mode="autotune"`` — the top ``--budget`` predicted combinations are
measured on a calibrated hardware model and the measured winner is
served; measurements persist in the plan cache's measured-cost table,
so a warm cache (or fleet-shared ``REPRO_PLAN_CACHE_DIR``) re-measures
nothing.

    PYTHONPATH=src python -m repro.launch.serve --blas GEMVER \
        --autotune --budget 4 --requests 8 --n 256

Batched serving (DESIGN.md §6): ``--engine`` drives a mixed-size
synthetic open-loop workload through the ``ServingEngine`` — power-of-two
shape buckets, reduction-safe padding, one vmap dispatch per batch, and
cross-sequence packed dispatch of a mixed drain (DESIGN.md §9,
``--max-pack``) — reporting throughput, p50/p99 latency, and p50/p99
queue wait.

    PYTHONPATH=src python -m repro.launch.serve --blas GEMVER --engine \
        --requests 64 --sizes 256,1000,1024,2048 --rate 200

Sharded serving (DESIGN.md §7): ``--engine --sharded`` spreads every
dispatch over the ``data`` axis of a replica mesh; ``--devices N``
forces N host CPU devices (must be set before jax initializes, which is
why this module imports jax lazily).

    PYTHONPATH=src python -m repro.launch.serve --blas GEMVER --engine \
        --sharded --devices 8 --requests 64 --quick
"""
from __future__ import annotations

import argparse
import time

import numpy as np


def serve_blas(args) -> dict:
    """Request loop over one compiled BLAS sequence.

    Demonstrates the serving contract of the plan pipeline: compile #1
    populates the plan cache, compile #2 (a restarted worker in the same
    process) is served from it, and each request dispatches exactly one
    jitted call."""
    from repro.programs import BLAS, make_inputs
    from repro.core import V5E, FusionCompiler, PlanCache

    if args.blas not in BLAS:
        raise SystemExit(f"unknown sequence {args.blas!r}; "
                         f"choose from {', '.join(BLAS)}")
    seq = BLAS[args.blas]
    cache = PlanCache()
    mode = "autotune" if args.autotune else "best"
    # calibrated constants make the predicted candidate ordering (which
    # the autotune budget is spent on) meaningful off-TPU
    hw = "calibrate" if args.autotune else V5E
    cc = FusionCompiler(cache=cache, hw=hw, autotune_budget=args.budget,
                        backend=args.backend, interpret=args.interpret)

    t0 = time.perf_counter()
    prog = cc.compile(seq.script, seq.shapes(args.n), mode=mode)
    t_compile = time.perf_counter() - t0
    if args.autotune and cc.last_autotune is not None:
        print(cc.last_autotune.describe())
    if args.refit:
        # two-phase flow (DESIGN.md §8): the autotune pass populated the
        # per-group measured-cost table; regress the predictor over it
        # and recompile mode="best" under the refit model — the hw repr
        # is a cache-key component, so this searches a fresh plan
        hw_before = cc.hw
        cc.refit_hardware()
        print(f"refit: {hw_before.name} -> {cc.hw.name} "
              f"(bw {hw_before.hbm_bw:.3g} -> {cc.hw.hbm_bw:.3g} B/s, "
              f"launch {hw_before.launch_overhead_s:.3g} -> "
              f"{cc.hw.launch_overhead_s:.3g} s, "
              f"{len(cache.group_records())} group records)")
        prog = cc.compile(seq.script, seq.shapes(args.n), mode="best")
    t0 = time.perf_counter()
    cc.compile(seq.script, seq.shapes(args.n),
               mode="best" if args.refit else mode)  # warm: cache hit
    t_recompile = time.perf_counter() - t0

    inputs = make_inputs(seq, args.n, seed=args.seed)
    out = prog(**inputs)
    prog.block_until_ready(out)                  # warmup jit

    t0 = time.perf_counter()
    for i in range(args.requests):
        out = prog(**inputs)
    prog.block_until_ready(out)
    t_serve = time.perf_counter() - t0

    us_per_req = t_serve / max(args.requests, 1) * 1e6
    stats = cache.stats.as_dict()
    print(f"serve {args.blas} n={args.n}: compile {t_compile*1e3:.1f} ms, "
          f"recompile {t_recompile*1e6:.0f} us (cache hit), "
          f"{args.requests} requests at {us_per_req:.1f} us/req "
          f"({prog.n_groups} kernels, 1 dispatch/req)")
    print(f"cache stats: {stats}")
    return {"t_compile_s": t_compile, "t_recompile_s": t_recompile,
            "us_per_request": us_per_req, "n_groups": prog.n_groups,
            "cache": stats}


def serve_engine(args) -> dict:
    """Mixed-size synthetic workload through the batched ServingEngine
    (``--sharded``: the mesh-sharded variant)."""
    from repro.programs import BLAS, make_inputs
    from repro.core import FusionCompiler
    from repro.serving import ServingEngine, ShardedServingEngine

    names = [s.strip() for s in args.blas.split(",")]
    for nm in names:
        if nm not in BLAS:
            raise SystemExit(f"unknown sequence {nm!r}; "
                             f"choose from {', '.join(BLAS)}")
    if args.sizes:
        sizes = [int(s) for s in args.sizes.split(",")]
    else:
        sizes = [64, 100, 128] if args.quick else [256, 1000, 1024, 2048]

    mode = "autotune" if args.autotune else "best"
    cc = (FusionCompiler(hw="calibrate", autotune_budget=args.budget,
                         interpret=args.interpret)
          if args.autotune else FusionCompiler(interpret=args.interpret))
    if args.sharded:
        # sharded engine pins max_pack=1 (DESIGN.md §9 open edge)
        engine = ShardedServingEngine(compiler=cc, max_batch=args.max_batch,
                                      min_bucket=min(64, min(sizes)),
                                      mode=mode, backend=args.backend)
        print(f"sharded engine: {engine.n_replicas} replicas, "
              f"max_batch {engine.max_batch}")
    else:
        engine = ServingEngine(compiler=cc, max_batch=args.max_batch,
                               min_bucket=min(64, min(sizes)), mode=mode,
                               max_pack=args.max_pack,
                               backend=args.backend)
    t0 = time.perf_counter()
    # warm packs once over the full key set, not per sequence
    buckets = {nm: engine.warm(nm, sizes, trace_packs=False) for nm in names}
    if not args.sharded:
        engine.warm_packs()
    t_warm = time.perf_counter() - t0

    workload = []
    for i in range(args.requests):
        nm, n = names[i % len(names)], sizes[i % len(sizes)]
        workload.append((nm, n, make_inputs(BLAS[nm], n,
                                            seed=args.seed + i)))

    t0 = time.perf_counter()
    results = engine.serve(workload, rate_hz=args.rate or None)
    t_serve = time.perf_counter() - t0

    lat = np.sort([r.latency_s for r in results])
    p50 = float(lat[len(lat) // 2]) if len(lat) else 0.0
    p99 = float(lat[min(len(lat) - 1, int(len(lat) * 0.99))]) if len(lat) else 0.0
    rps = len(results) / max(t_serve, 1e-9)
    st = engine.stats()
    print(f"engine {','.join(names)} sizes={sizes} buckets={buckets}: "
          f"warm {t_warm*1e3:.1f} ms ({sum(map(len, buckets.values()))} "
          f"programs), {len(results)} requests in {t_serve*1e3:.1f} ms")
    print(f"  throughput {rps:.1f} req/s | latency p50 {p50*1e3:.2f} ms "
          f"p99 {p99*1e3:.2f} ms | {st['n_dispatches']} dispatches, "
          f"batch occupancy {st['batch_occupancy']:.2f}")
    qw = st["queue_wait"]
    if qw and qw["count"]:
        print(f"  queue wait p50 {qw['p50_ms']:.2f} ms "
              f"p99 {qw['p99_ms']:.2f} ms ({qw['count']} waits)")
    if st["n_packed_dispatches"]:
        print(f"  packed dispatches: {st['n_packed_dispatches']} carrying "
              f"{st['n_packed_members']} member batches "
              f"(max_pack {st['max_pack']})")
    print(f"  bucket stats: {st['cache']['buckets']}")
    if args.sharded:
        print(f"  replica rows: {st['replica_rows']}")
    return {"throughput_rps": rps, "p50_s": p50, "p99_s": p99,
            "t_warm_s": t_warm, "t_serve_s": t_serve,
            "n_results": len(results), "stats": st}


def main(argv=None):
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch")
    ap.add_argument("--blas", help="serve BLAS sequence(s) (e.g. GEMVER or "
                    "AXPYDOT,VADD) through the fusion compiler instead of "
                    "an LM")
    ap.add_argument("--engine", action="store_true",
                    help="batched ServingEngine (shape buckets + vmap) "
                    "over a mixed-size workload")
    ap.add_argument("--backend", default="jnp",
                    help="codegen backend for --blas serving: 'jnp' "
                    "(XLA sub-functions) or 'pallas' (one pallas_call "
                    "per fused group, compiled for a TPU)")
    ap.add_argument("--interpret", action="store_true",
                    help="with --backend pallas: run the kernels in the "
                    "Pallas interpreter (any platform) instead of "
                    "compiling them for a TPU")
    ap.add_argument("--sharded", action="store_true",
                    help="with --engine: shard dispatches over the "
                    "'data' axis of a replica mesh (DESIGN.md §7)")
    ap.add_argument("--autotune", action="store_true",
                    help="compile with mode='autotune': measure the top "
                    "--budget predicted combinations on a calibrated "
                    "hardware model and serve the measured winner "
                    "(DESIGN.md §8)")
    ap.add_argument("--budget", type=int, default=8,
                    help="autotune candidate budget (measurements per "
                    "program on a cold cache)")
    ap.add_argument("--refit", action="store_true",
                    help="after the autotune pass, refit the hardware "
                    "model from the per-group measured-cost table "
                    "(HardwareModel.refit) and serve the mode='best' "
                    "plan searched under the refit predictor")
    ap.add_argument("--devices", type=int, default=0,
                    help="force N host CPU devices (sets XLA_FLAGS; "
                    "must run before jax initializes)")
    ap.add_argument("--requests", type=int, default=100)
    ap.add_argument("--n", type=int, default=1024)
    ap.add_argument("--sizes", help="comma-separated request sizes for "
                    "--engine (default 256,1000,1024,2048; --quick "
                    "shrinks them)")
    ap.add_argument("--max-batch", type=int, default=8)
    ap.add_argument("--max-pack", type=int, default=8,
                    help="with --engine: most (sequence, bucket) batches "
                    "merged into one packed dispatch per drain round "
                    "(DESIGN.md §9; 1 disables packing)")
    ap.add_argument("--rate", type=float, default=0.0,
                    help="open-loop arrival rate in req/s for --engine "
                    "(0 = closed loop)")
    ap.add_argument("--quick", action="store_true",
                    help="small sizes for CI smoke")
    ap.add_argument("--smoke", action="store_true")
    ap.add_argument("--batch", type=int, default=4)
    ap.add_argument("--prompt-len", type=int, default=32)
    ap.add_argument("--gen", type=int, default=32)
    ap.add_argument("--model-parallel", type=int, default=1)
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args(argv)

    # validate against the one authoritative backend set (RPL401) —
    # argparse `choices` would drift from KNOWN_BACKENDS and exit with
    # a codeless usage error instead of a diagnostic
    from repro.core.diagnostics import KNOWN_BACKENDS, VerificationError
    if args.backend not in KNOWN_BACKENDS:
        raise VerificationError.single(
            "RPL401", "cli.--backend",
            f"unknown backend {args.backend!r}",
            f"valid backends: {', '.join(KNOWN_BACKENDS)}")

    from repro.launch import enable_compile_cache, force_host_devices
    force_host_devices(args.devices)
    enable_compile_cache()

    if args.blas:
        return serve_engine(args) if args.engine else serve_blas(args)
    if not args.arch:
        ap.error("one of --arch or --blas is required")

    import jax
    import jax.numpy as jnp

    from repro import models
    from repro.configs import get_config, smoke_config
    from repro.launch.mesh import make_host_mesh
    from repro.train import steps as steps_lib

    cfg = smoke_config(args.arch) if args.smoke else get_config(args.arch)
    mesh = make_host_mesh(args.model_parallel)
    B, P, G = args.batch, args.prompt_len, args.gen
    total = P + G
    rng = np.random.default_rng(args.seed)
    prompts = rng.integers(0, cfg.vocab, (B, P)).astype(np.int32)

    with jax.sharding.set_mesh(mesh):
        params = models.init_params(cfg, jax.random.PRNGKey(args.seed))
        extra = {}
        if cfg.family == "vlm":
            extra["patches"] = jnp.asarray(rng.standard_normal(
                (B, cfg.n_patches, cfg.d_model)), jnp.float32)
        if cfg.family == "encdec":
            extra["frames"] = jnp.asarray(rng.standard_normal(
                (B, cfg.encoder_frames, cfg.d_model)), jnp.float32)

        t0 = time.perf_counter()
        logits, cache = models.prefill(cfg, params, jnp.asarray(prompts),
                                       **extra)
        # grow the cache to the full generation horizon
        def grow(a):
            if a.ndim >= 3 and a.shape[2] == P and cfg.family != "hybrid":
                pad = [(0, 0)] * a.ndim
                pad[2] = (0, total - P)
                return jnp.pad(a, pad)
            return a
        cache = jax.tree_util.tree_map(grow, cache)
        t_prefill = time.perf_counter() - t0

        decode = jax.jit(steps_lib.make_decode_step(cfg),
                         donate_argnums=(1,))
        tok = jnp.argmax(logits, axis=-1).astype(jnp.int32)
        out_tokens = [tok]
        t0 = time.perf_counter()
        for i in range(G - 1):
            tok, logits, cache = decode(params, cache, tok,
                                        jnp.int32(P + i))
            out_tokens.append(tok)
        jax.block_until_ready(tok)
        t_decode = time.perf_counter() - t0

    gen = np.stack([np.asarray(t) for t in out_tokens], axis=1)
    tput = B * (G - 1) / max(t_decode, 1e-9)
    print(f"prefill {P} toks x{B}: {t_prefill*1e3:.1f} ms")
    print(f"decode  {G-1} steps x{B}: {t_decode*1e3:.1f} ms "
          f"({tput:.1f} tok/s)")
    print("sample generation (first sequence):", gen[0][:16].tolist())
    return gen


if __name__ == "__main__":
    main()
