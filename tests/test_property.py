"""Property-based tests (hypothesis): the compiler preserves program
semantics for arbitrary random map/reduce scripts and combination
choices; numeric invariants of the quantizer and predictor."""
import numpy as np
import pytest

pytest.importorskip(
    "hypothesis",
    reason="optional dev dependency (pip install repro[dev])")
from hypothesis import example, given, settings, strategies as st

import jax.numpy as jnp

from repro.core import (FusionCompiler, build_space, codegen,
                        enumerate_combinations, trace)
from repro.core.elementary import make_map, make_reduce, Monoid
from repro.blas import elementary_lib as lib

# a pool of depth-1 elementary maps to compose random scripts from
UNARY = [
    make_map("neg", lambda x: -x, arity=1),
    make_map("sq", lambda x: x * x, arity=1),
    make_map("half", lambda x: 0.5 * x, arity=1),
]
BINARY = [
    make_map("add", lambda x, y: x + y, arity=2),
    make_map("sub", lambda x, y: x - y, arity=2),
    make_map("mul", lambda x, y: x * y, arity=2),
]
SUM = make_reduce("rsum", Monoid.SUM)


@st.composite
def random_script(draw):
    n_inputs = draw(st.integers(2, 3))
    n_ops = draw(st.integers(2, 6))
    ops = []
    for i in range(n_ops):
        if draw(st.booleans()):
            ops.append(("u", draw(st.integers(0, len(UNARY) - 1)),
                        draw(st.integers(0, n_inputs + i - 1))))
        else:
            ops.append(("b", draw(st.integers(0, len(BINARY) - 1)),
                        draw(st.integers(0, n_inputs + i - 1)),
                        draw(st.integers(0, n_inputs + i - 1))))
    with_reduce = draw(st.booleans())
    n_outputs = draw(st.integers(1, 2))
    return n_inputs, ops, with_reduce, n_outputs


def build(spec):
    n_inputs, ops, with_reduce, n_outputs = spec

    def script(g, **kw):
        vals = [kw[f"x{i}"] for i in range(n_inputs)]
        for op in ops:
            if op[0] == "u":
                vals.append(g.apply(UNARY[op[1]], vals[op[2]]))
            else:
                vals.append(g.apply(BINARY[op[1]], vals[op[2]], vals[op[3]]))
        outs = list(vals[-n_outputs:])
        if with_reduce:
            outs.append(g.apply(SUM, vals[-1]))
        return tuple(outs)

    shapes = {f"x{i}": (256,) for i in range(n_inputs)}
    return script, shapes


@settings(max_examples=30, deadline=None)
@given(random_script())
def test_random_scripts_best_matches_oracle(spec):
    script, shapes = build(spec)
    cc = FusionCompiler()
    g = trace(script, shapes)
    rng = np.random.default_rng(0)
    inputs = {k: rng.standard_normal(v).astype(np.float32)
              for k, v in shapes.items()}
    want = codegen.execute_dense(g, inputs)
    prog = cc.compile(script, shapes, mode="best")
    got = prog(**inputs)
    for w, o in zip(jnp.asarray(want).reshape(-1) if not isinstance(want, tuple) else want,
                    jnp.asarray(got).reshape(-1) if not isinstance(got, tuple) else got):
        np.testing.assert_allclose(np.asarray(o), np.asarray(w),
                                   rtol=1e-4, atol=1e-4)


@settings(max_examples=10, deadline=None)
@given(random_script(), st.integers(0, 5))
def test_random_scripts_any_combination_matches(spec, rank):
    """EVERY legal combination computes the same function."""
    script, shapes = build(spec)
    g = trace(script, shapes)
    space = build_space(g)
    combos = enumerate_combinations(space, limit=rank + 1)
    combo = combos[min(rank, len(combos) - 1)]
    rng = np.random.default_rng(1)
    inputs = {k: rng.standard_normal(v).astype(np.float32)
              for k, v in shapes.items()}
    want = codegen.execute_dense(g, inputs)
    prog = codegen.compile_combination(g, combo, backend="jnp")
    got = prog(**inputs)
    want_t = want if isinstance(want, tuple) else (want,)
    got_t = got if isinstance(got, tuple) else (got,)
    for w, o in zip(want_t, got_t):
        np.testing.assert_allclose(np.asarray(o), np.asarray(w),
                                   rtol=1e-4, atol=1e-4)


@settings(max_examples=15, deadline=None)
@given(st.integers(2, 8), st.booleans(), st.booleans(), st.booleans(),
       st.integers(0, 5), st.sampled_from([32, 64]))
def test_synthetic_chain_backends_agree(n_calls, reduce_consume, gemv,
                                        scalar_input, rank, n):
    """Arbitrary synthetic chains — optionally with reduce→consume
    links (the multi-phase pallas path), an ATAX-shaped gemv pair, and
    scalar/(1,1)-carrier inputs — agree across backends for arbitrary
    legal combinations and shapes."""
    from repro.blas import make_synthetic_chain
    script, shapes_fn, reference = make_synthetic_chain(
        n_calls, reduce_consume=reduce_consume, gemv=gemv,
        scalar_input=scalar_input)
    shapes = shapes_fn(n)
    g = trace(script, shapes)
    space = build_space(g)
    combos = enumerate_combinations(space, limit=rank + 1)
    combo = combos[min(rank, len(combos) - 1)]
    rng = np.random.default_rng(n_calls * 1000 + rank)
    inputs = {k: (np.float32(rng.uniform(0.5, 1.5)) if s == ()
                  else rng.standard_normal(s).astype(np.float32))
              for k, s in shapes.items()}
    want = reference(**inputs)
    jnp_prog = codegen.compile_combination(g, combo, backend="jnp")
    pl_prog = codegen.compile_combination(g, combo, backend="pallas",
                                          interpret=True)
    jnp_out = jnp_prog(**inputs)
    pl_out = pl_prog(**inputs)
    if not isinstance(jnp_out, tuple):
        jnp_out, pl_out = (jnp_out,), (pl_out,)
    for o_p, o_j, w in zip(pl_out, jnp_out, want):
        np.testing.assert_allclose(np.asarray(o_p), np.asarray(o_j),
                                   rtol=1e-4, atol=1e-3)
        np.testing.assert_allclose(np.asarray(o_j), np.asarray(w),
                                   rtol=1e-3, atol=1e-3)


@settings(max_examples=25, deadline=None)
@given(st.integers(1, 4096), st.floats(1e-6, 1e4))
@example(2247, 3.029010477954064)   # misses bmax/254 by 0.63 bmax u
def test_quantize_roundtrip_bound(n, scale):
    """int8 blockwise quantization: |x - dq(q(x))| <= bmax (1/254 + 3u).

    In exact arithmetic the scale is s = bmax/127 and the error at most
    s/2 = bmax/254.  float32 adds, with u = 2**-24: s itself (relative
    u, so s/2 grows by bmax u/254); ``blocks / scale`` (relative u on a
    quotient up to 127, so an element near a rounding tie may round
    the other way: 127 u more in q, bmax u in x); and ``q * scale``
    (half an ulp of a product up to bmax: bmax u).  That is
    bmax (1/254 + 2.004 u); 3u covers it.  The error is taken in
    float64, so the comparison adds no rounding of its own.
    """
    from repro.optim import dequantize, quantize
    rng = np.random.default_rng(n)
    x = jnp.asarray(rng.standard_normal(n) * scale, jnp.float32)
    q, s = quantize(x)
    y = dequantize(q, s, n)
    blocks = int(np.ceil(n / 128))
    xpad = np.zeros(blocks * 128, np.float64)
    xpad[:n] = np.asarray(x, np.float64)
    bmax = np.abs(xpad.reshape(blocks, 128)).max(axis=1)
    tol = np.repeat(bmax, 128)[:n] * (1 / 254 + 3 * 2.0**-24)
    err = np.abs(np.asarray(y, np.float64) - np.asarray(x, np.float64))
    assert np.all(err <= tol)


def test_predictor_monotonic_in_traffic():
    """More HBM traffic never predicts faster (same flops/overhead).

    The overhead a block choice costs (grid steps, pipeline fill) is
    fixed by the blocks, so each kept impl is compared with every grid
    order of its own blocks, where only the traffic moves."""
    import itertools
    from repro.core.predictor import V5E, cost_impl
    from repro.programs import BLAS
    seq = BLAS["BiCGK"]
    g = trace(seq.script, seq.shapes(512))
    space = build_space(g)
    n_unequal = 0
    for f in space.fusions:
        for im in space.impls_by_fusion[f.key]:
            blk = dict(zip(im.order, im.blocks))
            impls = [cost_impl(f, g, o, tuple(blk[r] for r in o), V5E)
                     for o in itertools.permutations(f.axis_roots)]
            for a in impls:
                for b in impls:
                    n_unequal += a.traffic_bytes < b.traffic_bytes
                    if (a.traffic_bytes <= b.traffic_bytes
                            and a.flops == b.flops):
                        assert a.t_pred <= b.t_pred + 1e-12
    assert n_unequal > 0


# ---------------------------------------------------------------------------
# HardwareModel.refit — learning from the per-group measured-cost table
# (DESIGN.md §8).  Stores are arbitrary well-formed group records; the
# invariants are the strict fallback semantics the autotune loop relies
# on: constants stay finite/positive whatever the store holds, and a
# too-small store is a no-op returning the analytic model itself.
# ---------------------------------------------------------------------------

import math

from repro.core import V5E

group_record = st.fixed_dictionaries({
    "kind": st.just("group"),
    "t_meas": st.floats(1e-9, 1e-1, allow_nan=False, allow_infinity=False),
    "traffic_bytes": st.integers(1, 10**10),
    "flops": st.integers(0, 10**10),
})


@settings(max_examples=50, deadline=None)
@given(st.lists(group_record, min_size=0, max_size=24))
def test_refit_constants_finite_positive(records):
    hw = V5E.refit(records)
    for v in (hw.peak_flops, hw.hbm_bw, hw.launch_overhead_s, hw.f32_scale):
        assert math.isfinite(v) and v > 0
    # policy constants are never refit
    assert hw.min_tile == V5E.min_tile
    assert hw.vmem_bytes == V5E.vmem_bytes


@settings(max_examples=20, deadline=None)
@given(group_record)
def test_refit_empty_and_singleton_are_noops(rec):
    """Below the record minimum the refit is the identity — the SAME
    analytic model object, so downstream cache keys (repr(hw)) are
    bit-identical to never having refit at all."""
    assert V5E.refit([]) is V5E
    assert V5E.refit([rec]) is V5E


@settings(max_examples=20, deadline=None)
@given(st.lists(group_record, min_size=3, max_size=24))
def test_refit_ignores_foreign_schemas(records):
    """Records from other generations sharing the measurement namespace
    (legacy whole-program, calibration, junk) never shift the fit."""
    noise = [{"t_meas": 1e-6, "reps": 1},               # legacy program
             {"kind": "calibration", "hbm_bw": 1.0},    # calibration
             {"kind": "group"},                         # missing t_meas
             {"kind": "group", "t_meas": float("nan"),
              "traffic_bytes": 1, "flops": 1},          # non-finite
             "not-a-dict", None, 42]
    assert V5E.refit(records + noise) == V5E.refit(records)


@settings(max_examples=40, deadline=None)
@given(st.lists(group_record, min_size=0, max_size=24),
       st.integers(1, 10**10), st.integers(1, 10**10),
       st.integers(0, 10**10))
def test_group_cost_monotone_in_traffic(records, tr1, tr2, fl):
    """At fixed flops, more traffic never predicts faster — for the
    analytic model AND any model refit from a well-formed store."""
    lo, hi = sorted((tr1, tr2))
    for hw in (V5E, V5E.refit(records)):
        assert hw.group_cost(lo, fl) <= hw.group_cost(hi, fl) + 1e-15
