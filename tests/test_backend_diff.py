"""Backend-differential harness (DESIGN.md §2/§10).

The enforcement teeth behind "the pallas backend emits every fusion the
scheduler can legally form": every REGISTRY program (11 BLAS + 4 LM
decode-step workloads), every scheduler-enumerated combination at a
small size budget, compiled under ``backend="pallas"`` (interpret mode)
and compared against the ``jnp`` backend within the §10 tolerance
envelope — bitwise for map/reduce-only graphs, allclose for
matvec-bearing ones.  Includes the acceptance pins for multi-phase
in-kernel reduce consumption (ATAX's second matvec, rmsnorm's
rsqrt-of-sum, softmax's exp-sub-of-max) and the clear-error contract
for group shapes the backend cannot emit.
"""
import numpy as np
import pytest

from repro.core import FusionCompiler, PlanCache, V5E, trace
from repro.core import codegen
from repro.core.fusion import (call_phases, consumed_reductions,
                               local_reductions)
from repro.core.plan import build_plan
from repro.core.predictor import cost_impl
from repro.core.scheduler import (Combination, build_space,
                                  enumerate_combinations)
from repro.programs import REGISTRY, make_inputs
from repro.serving import ServingEngine

#: small size budget: every axis one grid cell at depth 1, a handful of
#: cells at depth 2 — fast enough to sweep every combination
N = 32
#: combinations per program (the spaces at N=32 are mostly smaller)
COMBO_LIMIT = 16

#: programs whose optimization space must contain a fusion consuming a
#: finished reduction in-kernel (the multi-phase pallas path)
CONSUMING = ("ATAX", "LM_RMSNORM", "LM_BLOCK", "LM_DECODE_ATTN")


def _graph(name, n=N):
    prog = REGISTRY[name]
    return prog, trace(prog.script, prog.shapes(n))


def _combos(g, limit=COMBO_LIMIT):
    return enumerate_combinations(build_space(g), limit=limit)


def _outputs(cp, env):
    out = cp(**env)
    return out if isinstance(out, tuple) else (out,)


def _bitwise(g) -> bool:
    """§10 envelope: map/reduce-only graphs (every call depth <= 1) are
    bitwise across backends at N=32 — depth-1 blocks are full-size (the
    128-lane tile floor exceeds N), so even reductions see one grid
    cell and the identical summation order.  Matvec-bearing graphs
    block their depth-2 axes and are allclose."""
    return all(len(c.axis_sizes) <= 1 for c in g.calls)


# ---------------------------------------------------------------------------
# the differential sweep: every program x every combination
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("name", sorted(REGISTRY))
def test_all_combinations_match_across_backends(name):
    prog, g = _graph(name)
    combos = _combos(g)
    assert combos, f"{name}: scheduler enumerated no combinations"
    env = make_inputs(prog, N, seed=7)
    ref = prog.reference(**env)
    if not isinstance(ref, tuple):
        ref = (ref,)
    bitwise = _bitwise(g)
    for k, combo in enumerate(combos):
        jnp_out = _outputs(codegen.compile_combination(
            g, combo, backend="jnp"), env)
        pl_out = _outputs(codegen.compile_combination(
            g, combo, backend="pallas", interpret=True), env)
        for o_p, o_j, r in zip(pl_out, jnp_out, ref):
            o_p, o_j = np.asarray(o_p), np.asarray(o_j)
            if bitwise:
                np.testing.assert_array_equal(
                    o_p, o_j, err_msg=f"{name} combo {k}: pallas != jnp")
            else:
                np.testing.assert_allclose(
                    o_p, o_j, rtol=1e-4, atol=1e-3,
                    err_msg=f"{name} combo {k}: pallas != jnp")
            if k == 0:  # anchor both backends to the numpy oracle once
                np.testing.assert_allclose(
                    o_j, np.asarray(r), rtol=1e-4, atol=1e-3,
                    err_msg=f"{name}: jnp != reference")


# ---------------------------------------------------------------------------
# acceptance pins: in-kernel reduce consumption actually happens
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("name", CONSUMING)
def test_consuming_fusion_exists_and_validates(name):
    """Each of these programs must offer >= 1 fused group whose
    reduction output is consumed in-kernel (rmsnorm's rsqrt-of-sum,
    softmax's exp-sub-of-max, ATAX's second matvec), and that
    combination must compile and validate on pallas."""
    prog, g = _graph(name)
    combos = _combos(g, limit=64)
    consuming = [c for c in combos
                 if any(consumed_reductions(im.fusion, g)
                        for im in c.impls)]
    assert consuming, f"{name}: no combination consumes a reduction"
    env = make_inputs(prog, N, seed=3)
    jnp_out = _outputs(codegen.compile_combination(
        g, consuming[0], backend="jnp"), env)
    pl_out = _outputs(codegen.compile_combination(
        g, consuming[0], backend="pallas", interpret=True), env)
    for o_p, o_j in zip(pl_out, jnp_out):
        np.testing.assert_allclose(np.asarray(o_p), np.asarray(o_j),
                                   rtol=1e-4, atol=1e-3)
    # and the consuming fusion carries the finished value with no kernel
    # boundary: at N=32 its blocks hold every reduce axis whole, so the
    # value is block-local, consumed in the step that finishes it (the
    # phase barrier, where a reduce axis is split: ``_atax_phase_impl``)
    im = next(im for im in consuming[0].impls
              if consumed_reductions(im.fusion, g))
    assert local_reductions(im.fusion, g, im.whole_roots)
    assert im.n_phases == call_phases(im.fusion, g, im.whole_roots)[1] == 1


def test_no_program_forced_to_singletons():
    """Zero programs fall back to per-call singleton groups because of
    the backend: wherever the scheduler's space contains a multi-call
    fusion, the best combination keeps one, and it compiles on
    pallas."""
    for name in sorted(REGISTRY):
        prog, g = _graph(name)
        space = build_space(g)
        has_multi = any(len(f.calls) > 1 for f in space.fusions)
        best = enumerate_combinations(space, limit=1)[0]
        if has_multi:
            assert any(len(im.fusion.calls) > 1 for im in best.impls), (
                f"{name}: space has multi-call fusions but the best "
                f"combination is all singletons")
        codegen.compile_combination(g, best, backend="pallas",
                                    interpret=True, jit=False)


def test_attn_softmax_is_three_phases():
    """LM_DECODE_ATTN's softmax chain (scale, max-reduce, exp-sub,
    sum-reduce, div) fuses into one kernel over one axis set with two
    consumed reductions — a 3-phase body wherever t is split into
    blocks, and a single sweep where t is one whole block (both
    reductions block-local).  (The space also holds the whole chain as
    one online-softmax group, which has no phases.)"""
    _, g = _graph("LM_DECODE_ATTN")
    space = build_space(g)
    widest = max((f for f in space.fusions
                  if f.stream_root is None and not f.whole_roots),
                 key=lambda f: len(f.calls))
    consumed = consumed_reductions(widest, g)
    assert len(consumed) >= 2
    _, n_phases = call_phases(widest, g)
    assert n_phases >= 3
    assert call_phases(widest, g, widest.axis_roots)[1] == 1


# ---------------------------------------------------------------------------
# masked programs served through the engine on pallas
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("name", ["LM_DECODE_ATTN", "LM_RMSNORM"])
def test_masked_engine_pallas_matches_jnp(name):
    """Padded buckets (96, 120 -> bucket 128) through the per-lane
    masking rewrite, served by a pallas-backend engine, equal to the
    jnp-backend engine on the same drain."""
    sizes = (96, 120)
    engines = {}
    results = {}
    for backend in ("jnp", "pallas"):
        eng = ServingEngine(compiler=FusionCompiler(cache=PlanCache(),
                                                    interpret=True),
                            max_batch=4, min_bucket=128,
                            registry=REGISTRY, backend=backend)
        reqs = [(name, n, make_inputs(REGISTRY[name], n, seed=i))
                for i, n in enumerate(sizes)]
        results[backend] = {r.rid: r for r in eng.serve(reqs)}
        engines[backend] = eng
    if name == "LM_DECODE_ATTN":  # mixed monoids: masked fallback
        assert engines["pallas"]._compile_specs(name, 128)[3] is True
    _, g = _graph(name)
    bitwise = _bitwise(g)
    for rid in results["jnp"]:
        for o_p, o_j in zip(results["pallas"][rid].outputs,
                            results["jnp"][rid].outputs):
            if bitwise:
                np.testing.assert_array_equal(o_p, o_j)
            else:
                np.testing.assert_allclose(o_p, o_j,
                                           rtol=1e-4, atol=1e-3)


# ---------------------------------------------------------------------------
# clear-error contract for shapes the backend cannot emit
# ---------------------------------------------------------------------------

def _atax_bad_impl():
    """ATAX's consuming fusion under the one order multi-phase codegen
    cannot serve: gemv's reduce axis (j) outermost instead of an
    innermost suffix."""
    prog, g = _graph("ATAX", n=256)
    space = build_space(g)
    f = next(f for f in space.fusions if len(f.calls) == 2)
    t = f.calls[0].out                      # gemv out, keeps axis i
    i_root = g.axis_root(t.axis_ids[0])
    j_root = next(r for r in f.axis_roots if r != i_root)
    im = cost_impl(f, g, (j_root, i_root), (128, 128), V5E)
    assert im is not None
    return g, f, im


def test_bad_order_raises_clear_error():
    g, f, im = _atax_bad_impl()
    with pytest.raises(NotImplementedError, match=r"gemv\+gemtv"):
        codegen._group_pallas_fn(g, im, interpret=True)
    with pytest.raises(NotImplementedError, match="innermost suffix"):
        codegen._group_pallas_fn(g, im, interpret=True)


def test_pallas_without_interpret_raises_off_tpu():
    """Compiled Mosaic kernels need a TPU: off-TPU, a Pallas compile
    that does not ask for interpret mode raises — it never falls back
    to the interpreter in silence."""
    prog = REGISTRY["AXPYDOT"]
    cc = FusionCompiler(backend="pallas", cache=None)
    with pytest.raises(RuntimeError, match="interpret=True"):
        cc.compile(prog.script, prog.shapes(256))
    # the interpreter, asked for, serves the same compile
    FusionCompiler(backend="pallas", cache=None, interpret=True).compile(
        prog.script, prog.shapes(256))


def test_compile_surfaces_group_names():
    """The whole-program compile path reports the offending group's
    elementary names, not a KeyError from the kernel env."""
    g, f, im = _atax_bad_impl()
    combo = Combination(impls=(im,), t_pred=im.t_pred)
    plan = build_plan(g, combo, backend="pallas")
    with pytest.raises(NotImplementedError, match=r"gemv\+gemtv"):
        codegen.compile_plan(g, plan, interpret=True, jit=False)


def _atax_phase_impl(n=256, block=128):
    """ATAX's consuming fusion with gemv's reduce axis j split into
    blocks, innermost: the phase barrier, two phases."""
    prog, g = _graph("ATAX", n=n)
    f = next(f for f in build_space(g).fusions if len(f.calls) == 2)
    t = f.calls[0].out                      # gemv out, keeps axis i
    i_root = g.axis_root(t.axis_ids[0])
    j_root = next(r for r in f.axis_roots if r != i_root)
    im = cost_impl(f, g, (i_root, j_root), (block, block), V5E)
    assert im.n_phases == 2 and not local_reductions(f, g, im.whole_roots)
    return prog, g, im


def test_measure_group_times_multiphase_pallas_kernel():
    """The autotune seam (DESIGN.md §8): ``measure_group`` with
    ``backend="pallas"`` times the SAME multi-phase consuming kernel
    ``_group_pallas_fn`` emits — no measurement-loop changes needed for
    the new group shapes."""
    from repro.core.autotune import measure_group
    _, g, im = _atax_phase_impl()
    assert consumed_reductions(im.fusion, g)
    t = measure_group(g, im, backend="pallas", interpret=True,
                      reps=2, warmup=1, inner=2)
    assert np.isfinite(t) and t > 0


def test_enumerated_impls_never_raise():
    """enumerate_impls only emits accumulable orders for consuming
    fusions — every scheduler-produced impl must build."""
    for name in CONSUMING:
        _, g = _graph(name)
        space = build_space(g)
        for f in space.fusions:
            if not consumed_reductions(f, g):
                continue
            for im in space.impls_by_fusion[f.key]:
                codegen._group_pallas_fn(g, im, interpret=True)  # no raise
