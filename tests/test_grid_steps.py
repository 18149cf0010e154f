"""What a group's grid costs (DESIGN.md §8): the predictor charges every
grid step and the pipeline's first fetch and last write-back, so a 1-D
group picks its block for time instead of for the least VMEM.

Planning checks run no kernel; the accumulation checks run AXPYDOT in
the Pallas interpreter on blocks the predictor no longer picks at test
sizes, against a float64 reference.
"""
import dataclasses

import numpy as np
import pytest

from repro.core import FusionCompiler, PlanCache, V5E, codegen, trace
from repro.core.plan import build_plan
from repro.core.predictor import (_pareto_time_vmem, cost_impl,
                                  operand_carrier)
from repro.core.scheduler import Combination, build_space
from repro.programs import REGISTRY, make_inputs


def _axpydot_fusion(n):
    """AXPYDOT's graph at size ``n`` and its one three-call fusion."""
    prog = REGISTRY["AXPYDOT"]
    g = trace(prog.script, prog.shapes(n))
    f = next(f for f in build_space(g).fusions if len(f.calls) == 3)
    return prog, g, f


# ---------------------------------------------------------------------------
# plans at the benchmark's sizes
# ---------------------------------------------------------------------------

def _check_axpydot(cp, hw):
    (im,) = cp.group_impls
    assert im.grid_steps <= 512
    assert im.vmem_bytes <= hw.vmem_bytes
    (n,), (b,) = im.fusion.axis_sizes, im.blocks
    assert operand_carrier((n,), (b,), np.float32, hw) == (
        (n // 128, 128), (b // 128, 128), False)


def _check_gemver(cp, hw):
    labels = cp.group_labels
    assert [lb.split("_", 1)[1] for lb in labels] == [
        "rank2_update_gemtv_xpay_gemv", "scal"]
    g0 = cp.group_impls[0]
    assert (g0.order, g0.blocks) == ((0, 1), (16384, 128))
    steps = dict(zip(labels, (im.grid_steps for im in cp.group_impls)))
    assert steps["g0_rank2_update_gemtv_xpay_gemv"] == 128
    assert steps["g1_scal"] == 1


@pytest.mark.parametrize("name,n,check", [
    ("AXPYDOT", 1 << 26, _check_axpydot),
    ("GEMVER", 16384, _check_gemver),
])
def test_plan_grid_steps_at_benchmark_size(name, n, check):
    prog = REGISTRY[name]
    cc = FusionCompiler(backend="pallas", cache=None, interpret=True)
    cp = cc.compile(prog.script, prog.shapes(n))
    check(cp, cc.hw)
    assert cp.grid_steps == sum(im.grid_steps for im in cp.group_impls)
    assert all(f"steps={im.grid_steps}" in im.describe()
               for im in cp.group_impls)


# ---------------------------------------------------------------------------
# the cost model's grid terms
# ---------------------------------------------------------------------------

def test_one_dim_t_pred_falls_with_block_until_fill_turns():
    """Equal traffic at every block: the step term falls as the block
    grows, the fill term rises, and the fastest block lies between."""
    _, g, f = _axpydot_fusion(1 << 26)
    impls = [cost_impl(f, g, f.axis_roots, (1 << k,), V5E)
             for k in range(7, 23)]
    assert len({im.traffic_bytes for im in impls}) == 1
    t = [im.t_pred for im in impls]
    best = t.index(min(t))
    assert 0 < best < len(t) - 1
    assert all(a > b for a, b in zip(t[:best], t[1:best + 1]))
    assert all(a < b for a, b in zip(t[best:], t[best + 1:]))


def test_prune_on_time_and_vmem():
    """A faster impl that needs more VMEM survives; a slower one that
    needs more is dropped; survivors come fastest first."""
    _, g, f = _axpydot_fusion(1 << 14)
    base = cost_impl(f, g, f.axis_roots, (1024,), V5E)

    def impl(t, vmem):
        return dataclasses.replace(base, t_pred=t, vmem_bytes=vmem)

    small, fast_big, slow_small, slow_big = (
        impl(2.0, 10.0), impl(1.0, 30.0), impl(3.0, 5.0), impl(4.0, 20.0))
    kept = _pareto_time_vmem([small, fast_big, slow_small, slow_big], 64)
    assert kept == [fast_big, small, slow_small]
    assert _pareto_time_vmem([small, fast_big, slow_small], 2) == [
        fast_big, small]


def test_group_cost_without_grid_terms_is_the_roofline():
    hw = V5E
    for tr, fl in ((1e6, 1e3), (0.0, 5e12), (3e9, 0.0)):
        roofline = max(tr / hw.hbm_bw,
                       fl / (hw.peak_flops * hw.f32_scale)) \
            + hw.launch_overhead_s
        assert hw.group_cost(tr, fl) == roofline
        assert hw.group_cost(tr, fl, steps=0, fill_bytes=0.0) == roofline
    assert hw.group_cost(1e6, 1e3, steps=10, fill_bytes=819e3) == \
        pytest.approx(hw.group_cost(1e6, 1e3) + 10 * hw.grid_step_s + 1e-6)


def _records(with_steps):
    """Group records timed by an exact machine: 200 GB/s, 5 TFLOP/s,
    3 us a dispatch, and ``grid_step_s`` a step where they carry
    ``grid_steps``."""
    recs = []
    for tr, fl, steps in ((1e6, 2e6, 8), (4e6, 1e6, 300), (2e7, 9e7, 1),
                          (5e5, 4e8, 64), (8e7, 3e6, 4096)):
        t = tr / 2e11 + fl / 5e12 + 3e-6
        rec = {"kind": "group", "traffic_bytes": tr, "flops": fl}
        if with_steps:
            t += steps * V5E.grid_step_s
            rec["grid_steps"] = steps
        recs.append(dict(rec, t_meas=t))
    return recs


def test_refit_without_grid_steps_regresses_as_measured():
    hw = V5E.refit(_records(with_steps=False))
    assert (hw.hbm_bw, hw.peak_flops, hw.f32_scale) == (2e11, 5e12, 1.0)
    assert hw.launch_overhead_s == pytest.approx(3e-6)
    assert hw.grid_step_s == V5E.grid_step_s


def test_refit_subtracts_the_step_term():
    recs = _records(with_steps=True)
    assert V5E.refit(recs) == V5E.refit(_records(with_steps=False))
    # a record whose steps account for all its time has none left to fit
    spent = dict(recs[0], t_meas=recs[0]["grid_steps"] * V5E.grid_step_s)
    assert V5E.refit(recs + [spent]) == V5E.refit(recs)


def test_autotune_records_carry_grid_steps():
    prog = REGISTRY["AXPYDOT"]
    cache = PlanCache()
    cc = FusionCompiler(cache=cache, autotune_budget=2, autotune_reps=1,
                        autotune_warmup=1)
    cp = cc.compile(prog.script, prog.shapes(256), mode="autotune")
    recs = cache.group_records()
    assert recs and all(r["grid_steps"] >= 1 for r in recs)
    assert cp.group_impls[0].grid_steps in {r["grid_steps"] for r in recs}


# ---------------------------------------------------------------------------
# accumulation across grid steps, on both vector carriers
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("block,steps,carrier_block", [
    (128, 128, (1, 128)),       # a (1, n) row, blocks along its lanes
    (1024, 16, (8, 128)),       # a lane-dense (n / 128, 128) view
])
def test_axpydot_accumulates_across_grid_steps(block, steps, carrier_block):
    n = 1 << 14
    prog, g, f = _axpydot_fusion(n)
    im = cost_impl(f, g, f.axis_roots, (block,), V5E)
    assert im.grid_steps == steps
    assert operand_carrier((n,), (block,), np.float32, V5E)[1] == \
        carrier_block
    plan = build_plan(g, Combination(impls=(im,), t_pred=im.t_pred),
                      backend="pallas")
    cp = codegen.compile_plan(g, plan, interpret=True)
    env = make_inputs(prog, n, seed=7)
    z, r = (np.asarray(x, np.float64) for x in cp(**env))

    w, v, u = (np.asarray(env[k], np.float64) for k in "wvu")
    z_ref = w - float(env["alpha"]) * v
    np.testing.assert_allclose(z, z_ref, rtol=1e-6, atol=1e-6)
    terms = z_ref * u
    # in units of the terms' 2-norm: one dropped block reads about 0.09
    assert abs(float(r) - terms.sum()) <= 2e-5 * np.linalg.norm(terms)
