"""Framework-side fused-kernel benchmarks: the paper's technique applied
beyond BLAS — fused AdamW (via the fusion compiler), fused RMSNorm and
softmax-xent.  Reports measured CPU time (jnp/XLA backend) and the exact
HBM-traffic accounting that determines the TPU win."""
from __future__ import annotations

import time

import jax
import jax.numpy as jnp
import numpy as np


def _t(fn, *a, iters=5, **kw):
    jax.block_until_ready(fn(*a, **kw))
    ts = []
    for _ in range(iters):
        t0 = time.perf_counter()
        jax.block_until_ready(fn(*a, **kw))
        ts.append(time.perf_counter() - t0)
    return float(np.median(ts)) * 1e6


def bench_adamw(n: int, iters: int = 5) -> list[str]:
    from repro.optim import fused_adamw_update, make_fused_adamw
    rng = np.random.default_rng(0)
    p, g = (jnp.asarray(rng.standard_normal(n), jnp.float32) for _ in "pg")
    m = jnp.zeros(n, jnp.float32)
    v = jnp.zeros(n, jnp.float32) + 0.1

    kw = dict(lr=1e-3, weight_decay=0.1, step=5)
    t_fused = _t(lambda: fused_adamw_update(p, g, m, v, **kw), iters=iters)
    # unfused: each elementary map its own kernel
    from repro.optim.fused import make_fused_adamw as mk
    prog_u = mk(n, "jnp", mode="unfused")
    sf = jnp.float32(5.0)
    ins = dict(p=p, grad=g, m=m, v=v, lr=jnp.float32(1e-3),
               b1=jnp.float32(0.9), b2=jnp.float32(0.95),
               eps=jnp.float32(1e-8), wd=jnp.float32(0.1),
               c1=1/(1-0.9**sf), c2=1/(1-0.95**sf))
    t_unf = _t(lambda: prog_u(**ins), iters=iters)
    # traffic: fused reads p,g,m,v + writes p,m,v = 7n·4B;
    # unfused adds u round-trip + extra reads = 13n·4B
    return [
        f"ADAMW_fused_n{n},{t_fused:.1f},traffic=28B/param",
        f"ADAMW_unfused_n{n},{t_unf:.1f},"
        f"speedup={t_unf/max(t_fused,1e-9):.2f}x traffic=52B/param",
    ]


def bench_rmsnorm(T: int, D: int, iters: int = 5) -> list[str]:
    from repro.kernels import ref
    rng = np.random.default_rng(0)
    x = jnp.asarray(rng.standard_normal((T, D)), jnp.float32)
    g = jnp.asarray(rng.standard_normal(D), jnp.float32)
    fused = jax.jit(ref.rmsnorm)

    @jax.jit
    def unfused_stage1(x):
        return jnp.mean(x * x, axis=-1, keepdims=True)

    @jax.jit
    def unfused_stage2(x, ms, g):
        return x * jax.lax.rsqrt(ms + 1e-6) * g

    t_f = _t(fused, x, g, iters=iters)
    t_u = _t(lambda: unfused_stage2(x, unfused_stage1(x), g), iters=iters)
    return [f"RMSNORM_fused_{T}x{D},{t_f:.1f},2_streams",
            f"RMSNORM_unfused_{T}x{D},{t_u:.1f},"
            f"speedup={t_u/max(t_f,1e-9):.2f}x 4_streams"]


def bench_xent(T: int, V: int, iters: int = 5) -> list[str]:
    from repro.kernels import ref
    rng = np.random.default_rng(0)
    lg = jnp.asarray(rng.standard_normal((T, V)), jnp.float32)
    lb = jnp.asarray(rng.integers(0, V, T), jnp.int32)
    fused = jax.jit(ref.softmax_xent)

    @jax.jit
    def unfused(lg, lb):
        p = jax.nn.softmax(lg, axis=-1)           # materializes probs
        ll = jnp.take_along_axis(jnp.log(p + 1e-30), lb[:, None], axis=-1)
        return -jnp.mean(ll)

    t_f = _t(fused, lg, lb, iters=iters)
    t_u = _t(unfused, lg, lb, iters=iters)
    return [f"XENT_fused_{T}x{V},{t_f:.1f},1_logit_stream",
            f"XENT_unfused_{T}x{V},{t_u:.1f},"
            f"speedup={t_u/max(t_f,1e-9):.2f}x 3_logit_streams"]


def bench_backend_series(name: str, n: int, iters: int = 3) -> dict:
    """Three-way series for one program: compiler-emitted pallas kernels
    (interpret mode) vs the hand-written ``repro.kernels`` pallas
    kernels (interpret mode) vs the compiler's jnp/XLA backend.

    On this CPU container the pallas numbers go through the
    interpreter, so absolute times measure structural parity (same
    groups, same dispatch count), NOT TPU performance — the jnp series
    is the wall-clock anchor.  Numerics of all three are cross-checked
    (allclose) before timing."""
    from repro.core import FusionCompiler
    from repro.kernels import ops
    from repro.programs import REGISTRY, make_inputs

    prog = REGISTRY[name]
    inputs = {k: jnp.asarray(v)
              for k, v in make_inputs(prog, n, seed=0).items()}

    def compiled(backend):
        cc = FusionCompiler(backend=backend, interpret=True)
        return cc.compile(prog.script, prog.shapes(n))

    hand = {
        "GEMVER": lambda i: ops.gemver(
            i["A"], i["u1"], i["v1"], i["u2"], i["v2"], i["y"], i["z"],
            i["alpha"], i["beta"], use_pallas=True, interpret=True),
        "BiCGK": lambda i: ops.bicgk(i["A"], i["p"], i["r"],
                                     use_pallas=True, interpret=True),
        "LM_RMSNORM": lambda i: ops.rmsnorm(i["x"][None], i["gamma"],
                                            use_pallas=True,
                                            interpret=True)[0],
    }[name]

    series = {}
    p_jnp = compiled("jnp")
    p_pl = compiled("pallas")
    o_jnp = p_jnp(**inputs)
    o_pl = p_pl(**inputs)
    o_hand = hand(inputs)
    flat = lambda o: o if isinstance(o, tuple) else (o,)
    for a, b in zip(flat(o_pl), flat(o_jnp)):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   rtol=1e-4, atol=1e-3)
    for a, b in zip(flat(o_hand), flat(o_jnp)):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   rtol=1e-4, atol=1e-3)
    series["compiler_pallas_us"] = _t(lambda: p_pl(**inputs), iters=iters)
    series["hand_pallas_us"] = _t(lambda: hand(inputs), iters=iters)
    series["jnp_us"] = _t(lambda: p_jnp(**inputs), iters=iters)
    series.update(name=name, n=n, n_groups=p_pl.n_groups)
    return series


def run_backend_series(quick: bool = False) -> tuple[list[str], list[dict]]:
    """CSV rows + JSON records for the 3-way backend comparison."""
    n = 256 if quick else 512
    iters = 3 if quick else 5
    rows, records = [], []
    for name in ("GEMVER", "BiCGK", "LM_RMSNORM"):
        r = bench_backend_series(name, n, iters)
        records.append(r)
        rows.append(
            f"FUSED3_{name}_n{n},{r['jnp_us']:.1f},"
            f"compiler_pallas={r['compiler_pallas_us']:.1f}us "
            f"hand_pallas={r['hand_pallas_us']:.1f}us "
            f"groups={r['n_groups']} (pallas=interpret-mode)")
    return rows, records


def run_all(quick: bool = False) -> list[str]:
    n = 1 << 20 if quick else 1 << 22
    iters = 3 if quick else 5
    rows = []
    rows += bench_adamw(n, iters)
    rows += bench_rmsnorm(2048 if quick else 8192, 1024, iters)
    rows += bench_xent(512 if quick else 2048, 32000, iters)
    rows += run_backend_series(quick)[0]
    return rows


if __name__ == "__main__":
    from repro.launch import enable_compile_cache
    enable_compile_cache()
    for r in run_all():
        print(r)
