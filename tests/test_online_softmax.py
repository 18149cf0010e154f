"""The online-softmax group: a MAX over t consumed in the same sweep over
t, as a running max that rescales every sum it feeds (DESIGN.md §2).

``MLA_DECODE_ATTN`` and ``LM_DECODE_ATTN`` hold the chain score → max →
exp → sum → divide → weighted sum, and take the path by what their
graphs show.  Plans at the benchmark's size are checked without running
a kernel; the kernels run in the Pallas interpreter at small sizes,
re-blocked to many steps over t, on inputs whose scores rise along t, so
the block maxima rise late and every step rescales.
"""
import dataclasses

import numpy as np
import pytest

from repro.core import V5E, FusionCompiler, PlanCache, codegen, trace
from repro.core.diagnostics import UnsupportedGroupError
from repro.core.fusion import ACC, DIV, EXP, MAX, PRE
from repro.core.masking import masked_wrapper, padded_dims
from repro.core.plan import ExecutionPlan, build_plan
from repro.core.predictor import cost_impl
from repro.core.scheduler import Combination, best_combination, build_space
from repro.programs import REGISTRY, make_inputs
from repro.programs.models import mla_program

from repro.analysis.checks import verify_plan

#: as ``tests/test_mla.py``: the error of an output over the reference's
#: largest |entry|, the ``mla.call`` cell's limit
TOL = 1e-4

SMALL = mla_program(heads=4, rank=32, rope=8, name="MLA_SMALL")
MLA = REGISTRY["MLA_DECODE_ATTN"]
ATTN = REGISTRY["LM_DECODE_ATTN"]


def _err(got, want) -> float:
    got = np.asarray(got, np.float64)
    return float(np.max(np.abs(got - want)) / np.max(np.abs(want)))


def _rising(prog, n, seed):
    """Seeded inputs whose cache rows grow along t (0.2x to 3x), so
    scores, and each block's max, rise late; and the float64
    reference's output."""
    inp = make_inputs(prog, n, seed=seed)
    key = "ckv" if "ckv" in inp else "K"
    ramp = np.linspace(0.2, 3.0, n, dtype=np.float32)[:, None]
    inp[key] = (inp[key] * ramp).astype(np.float32)
    f64 = {k: np.asarray(v, np.float64) for k, v in inp.items()}
    return inp, prog.reference(**f64)[0]


def _online(combo):
    return [im for im in combo.impls if im.fusion.stream_root is not None]


def _reblocked(prog, n, block):
    """The predictor's plan with the streamed axis cut into ``block``
    positions, compiled for the Pallas interpreter."""
    g = trace(prog.script, prog.shapes(n))
    impls = []
    for im in best_combination(build_space(g)).impls:
        t = im.fusion.stream_root
        blocks = tuple(block if r == t else b
                       for r, b in zip(im.order, im.blocks))
        impls.append(cost_impl(im.fusion, g, im.order, blocks, V5E))
    combo = Combination(tuple(impls), sum(i.t_pred for i in impls))
    assert len(_online(combo)) == 1
    return codegen.compile_combination(g, combo, backend="pallas",
                                       interpret=True)


# ---------------------------------------------------------------------------
# the plans
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("prog,n", [(SMALL, 1000), (MLA, 1024),
                                    (MLA, 131072)],
                         ids=["small", "published", "benchmark"])
def test_mla_plan_reads_the_cache_once(prog, n):
    cp = FusionCompiler(backend="pallas", cache=None,
                        interpret=True).compile(prog.script, prog.shapes(n))
    (im,) = [im for im in cp.group_impls
             if {"mla_max", "mla_value"} <= {c.elem.name
                                             for c in im.fusion.calls}]
    assert im.fusion.stream_root is not None and im.n_phases == 1
    assert cp.input_passes["ckv"] == 1
    required = (sum(v.nbytes for v in cp.graph.inputs)
                + sum(v.nbytes for v in cp.graph.outputs))
    traffic = sum(i.traffic_bytes for i in cp.group_impls)
    assert traffic <= 1.06 * required
    label = cp.group_labels[cp.group_impls.index(im)]
    assert "mla_max" in label and "mla_value" in label


def test_roles_of_the_mla_chain():
    g = trace(MLA.script, MLA.shapes(1024))
    (im,) = _online(best_combination(build_space(g)))
    roles = dict(zip((c.out.name for c in im.fusion.calls), im.fusion.roles))
    assert roles == {"s_lat": PRE, "s_rope": PRE, "s": PRE, "mx": MAX,
                     "e": EXP, "z": ACC, "p": DIV, "o_lat": ACC}
    ckv = next(v for v in g.inputs if v.name == "ckv")
    assert im.fusion.stream_root == g.axis_root(ckv.axis_ids[0])
    assert [v.name for v in im.fusion.outputs] == ["o_lat"]


def test_lm_decode_attn_takes_the_same_path():
    n = 4096
    cp = FusionCompiler(backend="pallas", cache=None,
                        interpret=True).compile(ATTN.script, ATTN.shapes(n))
    (im,) = cp.group_impls
    assert im.fusion.stream_root is not None
    assert cp.input_passes == {"q": 1, "K": 1, "V": 1, "scale": 1}


@pytest.mark.parametrize("name", sorted(REGISTRY))
def test_graphs_without_the_chain_form_no_online_group(name):
    """A program with no MAX reduction, or one whose max feeds no sum
    through an exp, has no online-softmax group in its space: its plans
    are the ones the compiler chose before."""
    prog = REGISTRY[name]
    g = trace(prog.script, prog.shapes(1024))
    has_chain = any(c.elem.exp_sub_args for c in g.calls)
    online = [f for f in build_space(g).fusions if f.stream_root is not None]
    assert bool(online) == has_chain


def test_masked_decode_attn_keeps_the_split_plan():
    """Served with per-lane masks, the masks sit between the exp and the
    sums, which the online group does not admit: the split plan stays."""
    a, b = ATTN.shapes(1024), ATTN.shapes(2048)
    script, shapes = masked_wrapper(ATTN.script, a, padded_dims(a, b))
    g = trace(script, shapes)
    assert all(f.stream_root is None for f in build_space(g).fusions)


# ---------------------------------------------------------------------------
# the kernel, in the interpreter
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("seed", [0, 1, 2])
@pytest.mark.parametrize("prog,block", [(SMALL, 128), (MLA, 256)],
                         ids=["small", "published"])
def test_online_kernel_matches_the_float64_reference(prog, block, seed):
    cp = _reblocked(prog, 1024, block)
    assert cp.group_impls[0].grid_steps == 1024 // block
    inp, want = _rising(prog, 1024, seed)
    assert _err(cp(**inp), want) < TOL


@pytest.mark.parametrize("seed", [0, 1])
def test_lm_decode_attn_online_kernel_matches_its_reference(seed):
    cp = _reblocked(ATTN, 1024, 128)
    inp, want = _rising(ATTN, 1024, seed)
    assert _err(cp(**inp), want) < TOL


@pytest.mark.parametrize("block", [128, 512])
def test_lm_decode_attn_takes_k_and_v_swapped(block):
    """K and V (n, 48) are narrow: the kernel takes both swapped, (48,
    n), blocked along their lanes, and still meets the reference over
    several steps of t."""
    cp = _reblocked(ATTN, 1024, block)
    assert cp.transposed_operands == ("K", "V")
    assert cp.group_impls[0].grid_steps == 1024 // block
    inp, want = _rising(ATTN, 1024, seed=4)
    assert _err(cp(**inp), want) < TOL


def test_a_kernel_that_skips_the_rescale_fails_the_limit(monkeypatch):
    """Planted fault: the running output ``acc`` (h, c) is not carried to
    the new running max (the running sum still is).  The same inputs
    that pass above then miss the reference by far more than ``TOL``."""
    rescaled = codegen._rescaled
    monkeypatch.setattr(
        codegen, "_rescaled",
        lambda acc, alpha: acc if acc.ndim == 2 else rescaled(acc, alpha))
    cp = _reblocked(MLA, 1024, 256)
    inp, want = _rising(MLA, 1024, 0)
    assert _err(cp(**inp), want) > 10 * TOL


# ---------------------------------------------------------------------------
# plan round trip and verification
# ---------------------------------------------------------------------------

def test_plan_round_trips_and_rebinds_to_the_online_group():
    n = 1024
    g = trace(MLA.script, MLA.shapes(n))
    plan = build_plan(g, best_combination(build_space(g)), "pallas")
    again = ExecutionPlan.from_json(plan.to_json())
    assert again == plan
    (im,) = again.bind(g, V5E)
    assert im.fusion.stream_root is not None
    assert verify_plan(again, g) == []
    cp = codegen.compile_plan(g, again, interpret=True)
    inp, want = _rising(MLA, n, 5)
    assert _err(cp(**inp), want) < TOL


def test_a_cold_plan_cache_serves_the_online_plan(tmp_path):
    """A second compiler over the same disk plan cache loads the plan
    and binds it back to the online group."""
    n = 1024
    first = PlanCache(disk_dir=str(tmp_path))
    FusionCompiler(backend="pallas", interpret=True,
                   cache=first).compile(MLA.script, MLA.shapes(n))
    assert first.stats.disk_writes == 1
    cold = PlanCache(disk_dir=str(tmp_path))
    cp = FusionCompiler(backend="pallas", interpret=True,
                        cache=cold).compile(MLA.script, MLA.shapes(n))
    assert cold.stats.disk_hits == 1
    (im,) = cp.group_impls
    assert im.fusion.stream_root is not None
    inp, want = _rising(MLA, n, 6)
    assert _err(cp(**inp), want) < TOL


def test_an_online_group_split_off_its_streamed_axis_is_refused():
    """The latent width cut into blocks of 128: the score sum over it
    would not finish inside a step.  The verifier names it (RPL214) and
    codegen will not emit it."""
    g = trace(MLA.script, MLA.shapes(1024))
    plan = build_plan(g, best_combination(build_space(g)), "pallas")
    (gp,) = plan.groups
    blocks = tuple(128 if b == 512 else b for b in gp.blocks)
    bad = dataclasses.replace(
        plan, groups=(dataclasses.replace(gp, blocks=blocks),))
    assert [d.code for d in verify_plan(bad, g)] == ["RPL214"]
    with pytest.raises(UnsupportedGroupError):
        codegen.compile_plan(g, bad, interpret=True)
