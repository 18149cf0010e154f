"""Batched serving engine (DESIGN.md §6) + the plan-pipeline bugfix
sweep: shape buckets, reduction-safe padding against numpy oracles,
vmap horizontal fusion bitwise-equal to single dispatch, one plan per
(sequence, bucket), per-bucket cache stats, and the hardened error
paths (unfused singletons, empty enumeration, unknown kwargs)."""
import numpy as np
import pytest

from repro.blas import elementary_lib as lib
from repro.core import (FusionCompiler, Monoid, OptimizationSpace, PlanCache,
                        scheduler)
from repro.programs import BLAS, Program, make_inputs
from repro.serving import (ServingEngine, bucket_of, input_pad_values,
                           pad_to_shape)

# sizes kept small so the full BLAS sweep (matrices included) is fast
SIZES = (96, 100, 128)
BUCKET = 128


def _engine(max_batch=4, **kw):
    return ServingEngine(compiler=FusionCompiler(cache=PlanCache()),
                         max_batch=max_batch, min_bucket=64, **kw)


def _reference64(seq, inputs):
    return seq.reference(**{k: np.asarray(v, np.float64)
                            for k, v in inputs.items()})


# ---------------------------------------------------------------------------
# buckets
# ---------------------------------------------------------------------------

def test_bucket_rounding():
    assert bucket_of(1000) == 1024
    assert bucket_of(1024) == 1024
    assert bucket_of(1025) == 2048
    assert bucket_of(3, min_bucket=128) == 128
    assert bucket_of(200, min_bucket=64) == 256
    with pytest.raises(ValueError):
        bucket_of(0)


def test_pad_to_shape():
    x = np.arange(6, dtype=np.float32).reshape(2, 3)
    p = pad_to_shape(x, (4, 4), -1.0)
    assert p.shape == (4, 4)
    np.testing.assert_array_equal(p[:2, :3], x)
    assert (p[2:, :] == -1.0).all() and (p[:, 3:] == -1.0).all()
    assert pad_to_shape(x, (2, 3), 0.0) is x
    with pytest.raises(ValueError):
        pad_to_shape(x, (1, 3), 0.0)


# ---------------------------------------------------------------------------
# padding safety: every BLAS sequence, padded to a larger bucket,
# matches its numpy reference on the unpadded slice
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("name", list(BLAS))
def test_padding_safety_registry(name):
    seq = BLAS[name]
    engine = _engine()
    n = 100                                   # pads 100 -> bucket 128
    results = engine.serve([(name, n, make_inputs(seq, n, seed=7))])
    (res,) = results
    assert res.bucket == BUCKET and res.n == n
    ref = _reference64(seq, make_inputs(seq, n, seed=7))
    assert len(res.outputs) == len(ref)
    for o, r in zip(res.outputs, ref):
        assert o.shape == r.shape             # sliced back to request size
        np.testing.assert_allclose(np.asarray(o, np.float64), r,
                                   rtol=1e-4, atol=1e-5 * max(1.0, np.abs(r).max()))


@pytest.mark.parametrize("name", ["AXPYDOT", "ATAX", "BiCGK"])
def test_padding_safety_sum_reductions_batched(name):
    """The SUM-reduction sequences, mixed sizes in one engine run: the
    zero-padded lanes must be invisible to the dot products."""
    seq = BLAS[name]
    engine = _engine()
    workload = [(name, n, make_inputs(seq, n, seed=i))
                for i, n in enumerate(SIZES * 2)]
    results = engine.serve(workload)
    assert len(results) == len(workload)
    by_rid = {r.rid: r for r in results}
    for rid, (_, n, inputs) in enumerate(workload):
        ref = _reference64(seq, inputs)
        for o, r in zip(by_rid[rid].outputs, ref):
            np.testing.assert_allclose(
                np.asarray(o, np.float64), r,
                rtol=1e-4, atol=1e-5 * max(1.0, np.abs(r).max()))


@pytest.mark.parametrize("name", ["GEMVER", "ATAX", "AXPYDOT"])
def test_batched_bitwise_equals_single_padded_dispatch(name):
    """Horizontal fusion adds zero numerical difference: every row of
    the engine's batched result is bit-for-bit the one-request-per-
    dispatch result on the same padded inputs."""
    seq = BLAS[name]
    cc = FusionCompiler(cache=PlanCache())
    prog_b = cc.compile_batched(seq.script, seq.shapes(BUCKET), max_batch=4)
    prog_s = cc.compile(seq.script, seq.shapes(BUCKET))
    shapes = seq.shapes(BUCKET)
    n = 100
    reqs = [make_inputs(seq, n, seed=i) for i in range(4)]
    padded = [{k: (v if np.ndim(v) == 0 else pad_to_shape(v, shapes[k], 0.0))
               for k, v in inp.items()} for inp in reqs]
    batch = {k: np.stack([np.asarray(p[k]) for p in padded]) for k in shapes}
    b_out = prog_b(**batch)
    if not isinstance(b_out, tuple):
        b_out = (b_out,)
    for i in range(4):
        s_out = prog_s(**padded[i])
        if not isinstance(s_out, tuple):
            s_out = (s_out,)
        for bo, so in zip(b_out, s_out):
            np.testing.assert_array_equal(np.asarray(bo[i]), np.asarray(so))


# ---------------------------------------------------------------------------
# pad-value analysis
# ---------------------------------------------------------------------------

def test_monoid_identities():
    assert Monoid.SUM.identity == 0.0
    assert Monoid.MAX.identity == -np.inf
    assert Monoid.MIN.identity == np.inf
    for m in Monoid:
        assert m.combine(m.identity, 3.0) == 3.0


def test_max_reduce_padded_with_identity():
    """A MAX-reduction graph pads with -inf, so padded lanes never win."""

    def script(g, x):
        return (g.apply(lib.max_reduce, x, name="m"),)

    maxseq = Program("MAXR", "", script, lambda n: {"x": (n,)},
                      lambda x: (np.max(x),), lambda n: float(n))
    engine = _engine(registry={"MAXR": maxseq})
    g = engine.compiler.trace(script, {"x": (BUCKET,)})
    assert input_pad_values(g) == {"x": -np.inf}
    n = 100
    x = -np.abs(np.random.default_rng(0).standard_normal(n)).astype(np.float32)
    (res,) = engine.serve([("MAXR", n, {"x": x})])
    assert float(res.outputs[0]) == pytest.approx(float(np.max(x)))


def test_map_into_max_reduce_refuses_to_pad():
    """-inf padding is not preserved through maps (a*x with a<0 flips
    it), so identity padding only covers direct-input MAX/MIN reduces."""

    def script(g, x, alpha):
        s = g.apply(lib.scal, alpha, x)
        return (g.apply(lib.max_reduce, s, name="m"),)

    cc = FusionCompiler(cache=None)
    g = cc.trace(script, {"x": (BUCKET,), "alpha": ()})
    with pytest.raises(ValueError, match="mask"):
        input_pad_values(g)


def test_drain_preserves_queue_on_compile_failure():
    """A poison request (unpaddable AND unmaskable graph) must not drop
    the other queued requests: drain() restores the queue and re-raises.

    map-into-MAX alone no longer poisons — the engine re-traces it
    through the per-lane masking rewrite (DESIGN.md §10) — so the
    poison here also pads two INDEPENDENT extents (n and n // 2), which
    one ``_mask`` row cannot cover."""

    def bad_script(g, x, y, alpha):
        s = g.apply(lib.scal, alpha, x)
        t = g.apply(lib.max_reduce, s)
        return (g.apply(lib.axpy, t, y, y),)

    bad = Program("BAD", "", bad_script,
                   lambda n: {"x": (n,), "y": (n // 2,), "alpha": ()},
                   lambda x, y, alpha: (np.max(alpha * x) * y + y,),
                   lambda n: float(n))
    registry = dict(BLAS)
    registry["BAD"] = bad
    engine = _engine(registry=registry)
    engine.submit("VADD", 100, make_inputs(BLAS["VADD"], 100, seed=0))
    engine.submit("BAD", 100, {"x": np.ones(100, np.float32),
                               "y": np.ones(50, np.float32),
                               "alpha": np.float32(2.0)})
    with pytest.raises(ValueError, match="mask"):
        engine.drain()
    assert [r.sequence for r in engine._queue] == ["VADD", "BAD"]
    engine._queue = [r for r in engine._queue if r.sequence == "VADD"]
    (res,) = engine.drain()
    assert res.sequence == "VADD" and res.n == 100


def test_mixed_monoids_refuse_to_pad():
    def script(g, x):
        a = g.apply(lib.sum_reduce, x)
        b = g.apply(lib.max_reduce, x)
        c = g.apply(lib.axpby, a, x, b, x)
        return (c,)

    cc = FusionCompiler(cache=None)
    g = cc.trace(script, {"x": (BUCKET,)})
    with pytest.raises(ValueError, match="monoid"):
        input_pad_values(g)


# ---------------------------------------------------------------------------
# engine behaviour: batching, plan reuse, telemetry
# ---------------------------------------------------------------------------

def test_one_plan_per_sequence_bucket():
    """A mixed-size workload compiles at most one plan per (sequence,
    bucket) and serves every later request from cache."""
    engine = _engine()
    names = ["AXPYDOT", "VADD"]
    workload = [(nm, n, make_inputs(BLAS[nm], n, seed=n))
                for nm in names for n in [96, 100, 128, 200]] * 2
    results = engine.serve(workload)
    assert len(results) == 16
    buckets = engine.stats()["cache"]["buckets"]
    # sizes {96,100,128} -> bucket 128; 200 -> 256: two buckets per sequence
    assert sorted(buckets) == ["AXPYDOT/128", "AXPYDOT/256", "VADD/128",
                               "VADD/256"]
    for b in buckets.values():
        assert b["misses"] == 1
    # a second engine round over the same workload is all hits
    engine.serve(workload)
    buckets = engine.stats()["cache"]["buckets"]
    for b in buckets.values():
        assert b["misses"] == 1
    # plan layer searched once per (sequence, bucket) too
    st = engine.compiler.cache.stats
    assert st.plan_misses == 4


def test_fewer_dispatches_than_requests():
    engine = _engine(max_batch=8)
    seq = BLAS["WAXPBY"]
    workload = [("WAXPBY", 100, make_inputs(seq, 100, seed=i))
                for i in range(16)]
    engine.serve(workload)
    st = engine.stats()
    assert st["n_requests"] == 16
    assert st["n_dispatches"] == 2            # 16 requests / max_batch 8
    assert st["batch_occupancy"] == 1.0


def test_warm_then_serve_never_compiles():
    engine = _engine()
    engine.warm("SSCAL", [96, 100, 200])
    st0 = engine.stats()["cache"]["buckets"]
    # warm also pre-builds the pack composition over the warmed keys (§9)
    assert sorted(st0) == ["SSCAL/128", "SSCAL/256",
                           "pack/SSCAL/128+SSCAL/256"]
    workload = [("SSCAL", n, make_inputs(BLAS["SSCAL"], n, seed=n))
                for n in (96, 100, 128, 200)]
    results = engine.serve(workload)
    assert len(results) == 4
    st1 = engine.stats()["cache"]["buckets"]
    assert sum(b["misses"] for b in st1.values()) == \
        sum(b["misses"] for b in st0.values())


def test_open_loop_serve_reports_latency():
    engine = _engine()
    engine.warm("VADD", [100])
    seq = BLAS["VADD"]
    workload = [("VADD", 100, make_inputs(seq, 100, seed=i))
                for i in range(8)]
    results = engine.serve(workload, rate_hz=2000.0)
    assert len(results) == 8
    assert all(r.latency_s >= 0.0 for r in results)
    ref = _reference64(seq, workload[3][2])
    got = {r.rid: r for r in results}[3].outputs
    np.testing.assert_allclose(np.asarray(got[0], np.float64), ref[0],
                               rtol=1e-5, atol=1e-5)


def test_unknown_sequence_rejected():
    engine = _engine()
    with pytest.raises(KeyError, match="NOPE"):
        engine.submit("NOPE", 100, {})


# ---------------------------------------------------------------------------
# bugfix sweep: hardened error paths
# ---------------------------------------------------------------------------

def test_unknown_kwargs_raise_typeerror():
    seq = BLAS["AXPYDOT"]
    cc = FusionCompiler(cache=None)
    prog = cc.compile(seq.script, seq.shapes(128))
    inputs = make_inputs(seq, 128)
    with pytest.raises(TypeError, match="bogus"):
        prog(bogus=1.0, **inputs)
    bat = cc.compile_batched(seq.script, seq.shapes(128), max_batch=2)
    with pytest.raises(TypeError, match="typo"):
        bat(typo=1.0, **{k: np.asarray(v)[None] for k, v in inputs.items()})
    with pytest.raises(KeyError, match="missing input"):
        prog(w=inputs["w"])


def test_unfused_combination_names_dropped_call():
    seq = BLAS["VADD"]
    cc = FusionCompiler(cache=None)
    g = cc.trace(seq.script, seq.shapes(128))
    space = cc.space(g)
    # simulate build_space dropping call #1's singleton (VMEM-pruned)
    key = frozenset({1})
    space.fusions = [f for f in space.fusions if f.key != key]
    space.impls_by_fusion.pop(key)
    with pytest.raises(ValueError, match=r"call #1 \(ew_add"):
        scheduler.unfused_combination(space)


def test_integer_mode_empty_enumeration_is_clear_error():
    seq = BLAS["SSCAL"]
    cc = FusionCompiler(cache=None)
    g = cc.trace(seq.script, seq.shapes(128))
    empty = OptimizationSpace(graph=g, fusions=[], impls_by_fusion={})
    with pytest.raises(ValueError, match="no legal combination"):
        cc.search(empty, 2)

