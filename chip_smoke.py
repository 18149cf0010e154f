"""Smoke run of the fusion compiler and the serving engine on a TPU.

Drives the main path once, through the entry points a user calls, at
full width, and checks every output against the program's numpy
reference (run in float64 on the host):

* ``FusionCompiler.compile`` on both backends (``jnp`` and ``pallas``,
  the Pallas kernels compiled by Mosaic, never interpreted): GEMVER and
  ATAX at n=16384 (a 1 GiB f32 matrix), AXPYDOT at n=2**24;
* ``ServingEngine`` over the program registry on both backends, at
  Llama-3-8B width: LM_BLOCK and LM_RMSNORM at d=4096, LM_DECODE_ATTN
  over ragged KV lengths 4096..32768, FUSED_ADAMW over 2**24 parameters.

    python chip_smoke.py               # one chip
    python chip_smoke.py --four-chips  # needs 4 chips; runs only the
                                       # sharded-serving comparison

``--four-chips`` serves the same GEMVER requests through a
``ShardedServingEngine`` over a ``('data', 4)`` mesh and through the
one-chip ``ServingEngine``, checks that they agree, and that a batch's
row blocks land on all four devices.

Every phase runs in this one process (a chip belongs to one process).
Lines before the last, prefixed ``[smoke]``, are smoke output — sizes,
compile seconds, per-request milliseconds, errors against the
reference — that show the system runs; they are not measurements.  The
last line is one JSON object::

    {"ok": true, "device": {"platform": "tpu", "kind": "...", "count": 1}}

The script exits non-zero, without that line, when JAX finds no TPU or
any phase fails.
"""
from __future__ import annotations

import argparse
import json
import os
import sys
import time
import traceback

import numpy as np

ROOT = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, os.path.join(ROOT, "src"))

#: a check passes when max |got - ref| <= TOL * max |ref| (float64 ref)
TOL = 1e-4
BACKENDS = ("jnp", "pallas")
#: (program, n) compiled with FusionCompiler.compile
COMPILER_CASES = (("GEMVER", 16384), ("ATAX", 16384), ("AXPYDOT", 1 << 24))
#: (program, request sizes) served through ServingEngine
SERVING_CASES = (
    ("LM_BLOCK", (4096, 4096, 4096)),
    ("LM_RMSNORM", (4096, 4096, 4096)),
    ("LM_DECODE_ATTN", (4096, 6000, 21000, 32768)),
    ("FUSED_ADAMW", (1 << 24, 1 << 24, 1 << 24)),
)
#: GEMVER requests of this size for --four-chips
FOUR_CHIP_CASE = ("GEMVER", 4096, 8)
REQUESTS = 3


class SmokeFailure(RuntimeError):
    pass


def say(msg: str) -> None:
    print(f"[smoke] {msg}", flush=True)


def reference(prog, inputs) -> tuple:
    ref = prog.reference(**{k: np.asarray(v, np.float64)
                            for k, v in inputs.items()})
    return ref if isinstance(ref, tuple) else (ref,)


def check(label: str, got, want) -> None:
    """Normwise comparison of one output with its float64 reference."""
    got = np.asarray(got, np.float64)
    want = np.asarray(want, np.float64)
    if got.shape != want.shape:
        raise SmokeFailure(f"{label}: shape {got.shape} != {want.shape}")
    if not np.all(np.isfinite(got)):
        raise SmokeFailure(f"{label}: non-finite output")
    err = float(np.max(np.abs(got - want))) if got.size else 0.0
    scale = float(np.max(np.abs(want))) if want.size else 0.0
    say(f"  {label}: max abs err {err:.3g} (max |ref| {scale:.3g})")
    if err > TOL * max(scale, 1e-30):
        raise SmokeFailure(f"{label}: max abs err {err:.3g} exceeds "
                           f"{TOL:g} x max |ref| {scale:.3g}")


def vary(inputs: dict, seed: int) -> dict:
    """A further request sharing ``inputs``' matrices and scalars, with
    fresh vectors (keeps host input generation off the critical path)."""
    rng = np.random.default_rng(seed)
    return {k: (rng.standard_normal(v.shape).astype(v.dtype)
                if np.ndim(v) == 1 else v) for k, v in inputs.items()}


def phase_compiler() -> None:
    """Compile each program with FusionCompiler on every backend and run
    a few requests through the compiled whole-program function."""
    import jax

    from repro.core import FusionCompiler
    from repro.programs import REGISTRY, make_inputs

    for name, n in COMPILER_CASES:
        prog = REGISTRY[name]
        t0 = time.perf_counter()
        base = make_inputs(prog, n, seed=0)
        reqs = [base] + [vary(base, seed=i) for i in range(1, REQUESTS)]
        refs = [reference(prog, r) for r in reqs]
        say(f"{name} n={n}: inputs + float64 reference for {REQUESTS} "
            f"requests in {time.perf_counter() - t0:.1f} s (host)")
        on_device: dict[int, object] = {}   # host array id -> device copy
        for backend in BACKENDS:
            cc = FusionCompiler(backend=backend)
            t0 = time.perf_counter()
            compiled = cc.compile(prog.script, prog.shapes(n))
            t_plan = time.perf_counter() - t0
            for i, (inp, ref) in enumerate(zip(reqs, refs)):
                args = {}
                for k, v in inp.items():
                    if id(v) not in on_device:
                        on_device[id(v)] = jax.device_put(v)
                    args[k] = on_device[id(v)]
                jax.block_until_ready(args)     # time the program only
                t0 = time.perf_counter()
                out = jax.block_until_ready(compiled(**args))
                dt = time.perf_counter() - t0
                out = out if isinstance(out, tuple) else (out,)
                if i == 0:
                    say(f"{name}/{backend}: plan {t_plan:.2f} s, "
                        f"{compiled.n_groups} kernels; first request "
                        f"(compile included) {dt:.2f} s")
                else:
                    say(f"{name}/{backend}: request {i} {dt * 1e3:.2f} ms")
                for j, (o, r) in enumerate(zip(out, ref)):
                    check(f"{name}/{backend} req {i} out {j}", o, r)
                del out


def _serve(engine, name: str, reqs: list) -> dict:
    """Submit ``reqs`` (``(n, inputs)``) with explicit ids and drain."""
    for rid, (n, inputs) in enumerate(reqs):
        engine.submit(name, n, inputs, rid=rid)
    return {r.rid: r for r in engine.drain()}


def phase_serving() -> None:
    """Serve a few requests of each program through one ServingEngine
    per backend; the first drain of a program compiles it, the second
    is warm."""
    from repro.programs import REGISTRY, make_inputs
    from repro.serving import ServingEngine

    engines = {b: ServingEngine(registry=REGISTRY, backend=b, max_batch=4)
               for b in BACKENDS}
    for name, sizes in SERVING_CASES:
        prog = REGISTRY[name]
        t0 = time.perf_counter()
        reqs = [(n, make_inputs(prog, n, seed=i)) for i, n in enumerate(sizes)]
        refs = [reference(prog, inp) for _, inp in reqs]
        say(f"{name} sizes={list(sizes)}: inputs + float64 reference in "
            f"{time.perf_counter() - t0:.1f} s (host)")
        for backend, engine in engines.items():
            t0 = time.perf_counter()
            _serve(engine, name, reqs)
            t_cold = time.perf_counter() - t0
            t0 = time.perf_counter()
            res = _serve(engine, name, reqs)
            t_warm = time.perf_counter() - t0
            buckets = sorted({r.bucket for r in res.values()})
            say(f"{name}/{backend}: buckets {buckets}; first drain "
                f"(compile included) {t_cold:.2f} s, warm drain "
                f"{t_warm * 1e3 / len(reqs):.2f} ms/request")
            for rid, ref in enumerate(refs):
                outs = res[rid].outputs
                if len(outs) != len(ref):
                    raise SmokeFailure(f"{name}/{backend} req {rid}: "
                                       f"{len(outs)} outputs, want {len(ref)}")
                for j, (o, r) in enumerate(zip(outs, ref)):
                    check(f"{name}/{backend} req {rid} n={reqs[rid][0]} "
                          f"out {j}", o, r)


def phase_four_chips() -> None:
    """The same GEMVER requests through a ShardedServingEngine over a
    ('data', 4) mesh and the one-chip ServingEngine: outputs agree, and
    a sharded batch's row blocks land on all four devices."""
    import jax

    from repro.launch.mesh import make_data_mesh
    from repro.programs import REGISTRY, make_inputs
    from repro.serving import ServingEngine, ShardedServingEngine

    name, n, count = FOUR_CHIP_CASE
    prog = REGISTRY[name]
    mesh = make_data_mesh(4)
    reqs = [(n, make_inputs(prog, n, seed=i)) for i in range(count)]
    refs = [reference(prog, inp) for _, inp in reqs]
    for backend in BACKENDS:
        single = ServingEngine(registry=REGISTRY, backend=backend,
                               max_batch=count, max_pack=1)
        sharded = ShardedServingEngine(mesh, registry=REGISTRY,
                                       backend=backend, max_batch=count)
        got = {}
        for label, engine in (("one-chip", single), ("sharded", sharded)):
            t0 = time.perf_counter()
            got[label] = _serve(engine, name, reqs)
            say(f"{name}/{backend} {label}: {count} requests n={n} in "
                f"{time.perf_counter() - t0:.2f} s (compile included)")
        for rid, ref in enumerate(refs):
            for j, r in enumerate(ref):
                one = got["one-chip"][rid].outputs[j]
                shd = got["sharded"][rid].outputs[j]
                check(f"{name}/{backend} req {rid} out {j} one-chip", one, r)
                check(f"{name}/{backend} req {rid} out {j} sharded", shd, r)
                check(f"{name}/{backend} req {rid} out {j} sharded vs "
                      f"one-chip", shd, one)
        rows = sharded.stats()["replica_rows"]
        say(f"{name}/{backend} sharded replica rows {rows}")
        if min(rows) == 0:
            raise SmokeFailure(f"sharded engine left a replica idle: {rows}")

        # where the rows of one sharded batch actually live
        bucket = sharded.bucket_of(n)
        program = sharded.compiler.compile_sharded(
            prog.script, prog.shapes(bucket), mesh=mesh,
            max_batch=sharded.max_batch, backend=backend)
        batch = {k: np.stack([inp[k] for _, inp in reqs])
                 for k in program.plan.input_names}
        outs = program(**batch)
        outs = outs if isinstance(outs, tuple) else (outs,)
        for j, o in enumerate(outs):
            shards = o.addressable_shards
            devices = {s.device for s in shards}
            per_device = sorted(s.data.shape[0] for s in shards)
            say(f"{name}/{backend} sharded batch out {j}: rows per device "
                f"{per_device} on {len(devices)} devices")
            if len(devices) != 4 or per_device != [count // 4] * 4:
                raise SmokeFailure(f"out {j}: row blocks {per_device} on "
                                   f"{sorted(map(str, devices))}, want "
                                   f"{count // 4} rows on each of 4 devices")
            check(f"{name}/{backend} sharded batch out {j}", np.asarray(o),
                  np.stack([r[j] for r in refs]))


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--four-chips", action="store_true",
                    help="run only the sharded-serving comparison over a "
                    "('data', 4) mesh (needs 4 chips)")
    args = ap.parse_args(argv)

    import jax

    devices = jax.devices()
    platform = devices[0].platform
    if platform != "tpu":
        print(f"chip_smoke: no TPU — JAX found {len(devices)} "
              f"{platform} device(s); this smoke run needs a TPU",
              file=sys.stderr)
        return 1
    device = {"platform": platform, "kind": devices[0].device_kind,
              "count": len(devices)}
    say(f"device {device['kind']} x{device['count']}")
    if args.four_chips and len(devices) < 4:
        print(f"chip_smoke: --four-chips needs 4 chips, found "
              f"{len(devices)}", file=sys.stderr)
        return 1

    try:
        from repro.launch import enable_compile_cache
        say(f"compile cache {enable_compile_cache()}")
        t0 = time.perf_counter()
        if args.four_chips:
            phase_four_chips()
        else:
            phase_compiler()
            phase_serving()
        say(f"all phases passed in {time.perf_counter() - t0:.1f} s")
    except Exception:
        traceback.print_exc()
        print("chip_smoke: FAILED", file=sys.stderr)
        return 1
    print(json.dumps({"ok": True, "device": device}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
