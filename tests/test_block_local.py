"""The block-local barrier (DESIGN.md §2, fusion rules 1-2): a reduction
whose reduce axes are one whole block finishes inside every grid step
and is consumed in that step, so a group may hold calls over fewer axes
and reductions no phase could carry.

Plans are checked on the CPU planner at the benchmark's sizes (no
kernel runs); kernels run in the Pallas interpreter at small sizes,
against the float64 references, with several grid steps each.
"""
import numpy as np
import pytest

from repro.analysis.checks import verify_plan
from repro.core import V5E, FusionCompiler, analyse_group, codegen, trace
from repro.core import fusion as fusion_mod
from repro.core.diagnostics import UnsupportedGroupError
from repro.core.plan import build_plan
from repro.core.predictor import cost_impl, enumerate_impls
from repro.core.scheduler import Combination, best_combination, build_space
from repro.programs import REGISTRY, make_inputs

#: error of an output, as a share of the reference's largest |entry|:
#: float32 sums of a few hundred terms round to about 1e-7 of it
TOL = 1e-5


def _err(got, want) -> float:
    got = np.asarray(got, np.float64)
    return float(np.max(np.abs(got - want)) / np.max(np.abs(want)))


def _errs(cp, name, n, seed=5):
    prog = REGISTRY[name]
    inp = make_inputs(prog, n, seed=seed)
    got = cp(**inp)
    got = got if isinstance(got, tuple) else (got,)
    want = prog.reference(**{k: np.asarray(v, np.float64)
                             for k, v in inp.items()})
    return [_err(g, w) for g, w in zip(got, want)]


def _plan(name, n):
    prog = REGISTRY[name]
    return FusionCompiler(backend="pallas", cache=None,
                          interpret=True).compile(prog.script,
                                                  prog.shapes(n))


def _required(g) -> int:
    return (sum(v.nbytes for v in g.inputs)
            + sum(v.nbytes for v in g.outputs))


def _traffic(cp) -> float:
    return sum(im.traffic_bytes for im in cp.group_impls)


def _rows_cols(g):
    A = g.inputs[0]
    return g.axis_root(A.axis_ids[0]), g.axis_root(A.axis_ids[1])


# ---------------------------------------------------------------------------
# plans at the benchmark's sizes
# ---------------------------------------------------------------------------

def test_gemver_reads_a_once_and_writes_b_once_in_column_blocks():
    """GEMVER at 16384: a column block (16384, 128) of B holds whole
    columns, so x = beta B^T y + z is finished in the step that reads
    the block and w's sum accumulates across the column grid: the
    rank-2 update, both matrix-vector products and xpay are one group,
    and scal, which consumes a sum over 128 column blocks, stays apart."""
    cp = _plan("GEMVER", 16384)
    g = cp.graph
    i, j = _rows_cols(g)
    names = [[c.elem.name for c in im.fusion.calls] for im in cp.group_impls]
    assert names == [["rank2_update", "gemtv", "xpay", "gemv"], ["scal"]]
    one = cp.group_impls[0]
    assert one.fusion.whole_roots == (i,)
    assert (one.block_of(i), one.block_of(j)) == (16384, 128)
    assert one.n_phases == 1 and one.grid_steps == 128
    assert cp.local_reductions == ("t",)
    assert set(cp.input_passes.values()) == {1}
    assert _traffic(cp) < 1.001 * _required(g)


def test_atax_is_one_pass_in_row_blocks_that_hold_whole_rows():
    """ATAX at 16384 (the paper's two-kernel example): t = A x is
    finished in each block of whole rows, so A^T t accumulates in the
    same sweep, one phase where the phase barrier takes two."""
    cp = _plan("ATAX", 16384)
    i, j = _rows_cols(cp.graph)
    (im,) = cp.group_impls
    assert im.n_phases == 1 and im.fusion.whole_roots == ()
    assert im.block_of(j) == 16384 and im.block_of(i) < 16384
    assert cp.local_reductions == ("t",)
    assert cp.input_passes == {"A": 1, "x": 1}
    assert _traffic(cp) < 1.001 * _required(cp.graph)


@pytest.mark.parametrize("name,n,groups", [
    # whole columns of A and B: (65536, 128) blocks, 32 MiB a buffer,
    # four buffers against the 64 MiB budget
    ("GEMVER", 65536, [["rank2_update", "gemtv"], ["xpay"], ["gemv"],
                       ["scal"]]),
    # eight whole rows of A: 64 MiB a buffer
    ("ATAX", 1 << 21, [["gemv"], ["gemtv"]]),
])
def test_a_whole_axis_past_the_vmem_budget_keeps_the_split_plan(name, n,
                                                                groups):
    """Where no block holding the reduce axis whole fits VMEM, no
    block-local impl survives and the plan splits as it did before."""
    cp = _plan(name, n)
    assert [[c.elem.name for c in im.fusion.calls]
            for im in cp.group_impls] == groups
    assert cp.local_reductions == ()
    assert all(not im.fusion.whole_roots for im in cp.group_impls)


def test_local_reductions_name_the_values_in_plan_order():
    """The counter on both backends: KDA's two norms and its state read,
    one per group in plan order; none where no reduction is consumed
    (AXPYDOT) or the softmax is online (MLA)."""
    for backend in ("jnp", "pallas"):
        prog = REGISTRY["KDA_DECODE_STEP"]
        cp = FusionCompiler(backend=backend, cache=None,
                            interpret=True).compile(prog.script,
                                                    prog.shapes(4096))
        assert cp.local_reductions == ("qss", "kss", "u")
    assert _plan("AXPYDOT", 1 << 20).local_reductions == ()
    assert _plan("MLA_DECODE_ATTN", 4096).local_reductions == ()


# ---------------------------------------------------------------------------
# kernels in the interpreter, against the float64 references
# ---------------------------------------------------------------------------

def _column_blocked_gemver(n=512, block=128):
    """GEMVER's block-local group at column blocks of ``block`` (n /
    block steps, w's sum accumulated across them) and scal."""
    g = trace(REGISTRY["GEMVER"].script, REGISTRY["GEMVER"].shapes(n))
    i, j = _rows_cols(g)
    f = analyse_group(g, g.calls[:4])
    one = cost_impl(f, g, (i, j), (n, block), V5E)
    scal = enumerate_impls(analyse_group(g, g.calls[4:]), g, V5E)[0]
    return g, Combination((one, scal), one.t_pred + scal.t_pred)


def test_gemver_column_blocks_match_the_float64_reference():
    g, combo = _column_blocked_gemver()
    assert combo.impls[0].grid_steps == 4 and combo.impls[0].n_phases == 1
    cp = codegen.compile_combination(g, combo, backend="pallas",
                                     interpret=True)
    assert cp.local_reductions == ("t",)
    assert max(_errs(cp, "GEMVER", 512)) < TOL


def _free_axis(im, g):
    """An axis of ``im``'s group that no member needs whole and no
    consumed reduction reduces over, or None."""
    f = im.fusion
    reduced = set().union(*(fusion_mod.reduce_roots(c, g)
                            for c in fusion_mod.consumed_reductions(f, g)))
    return next((r for r in im.order
                 if r not in f.whole_roots and r not in reduced), None)


@pytest.mark.parametrize("name,n", [("ATAX", 512), ("KDA_DECODE_STEP", 256),
                                    ("GESUMMV", 512), ("SGEMV", 512)])
def test_one_pass_plans_match_the_float64_reference(name, n):
    """Each plan's groups re-blocked to 128 along an axis no reduction
    they consume reduces over, so the block-local ones run several grid
    steps, and still meet the reference."""
    prog = REGISTRY[name]
    g = trace(prog.script, prog.shapes(n))
    impls = []
    for im in best_combination(build_space(g)).impls:
        free = _free_axis(im, g)
        blocks = tuple(128 if r == free else b
                       for r, b in zip(im.order, im.blocks))
        impls.append(cost_impl(im.fusion, g, im.order, blocks, V5E))
    assert any(im.grid_steps >= 2 and im.n_phases == 1
               and fusion_mod.local_reductions(im.fusion, g, im.whole_roots)
               for im in impls)
    cp = codegen.compile_combination(
        g, Combination(tuple(impls), sum(i.t_pred for i in impls)),
        backend="pallas", interpret=True)
    assert cp.local_reductions
    assert max(_errs(cp, name, n)) < TOL


def _atax_two_column_blocks(n=256):
    """ATAX's group with gemv's reduce axis j in two blocks, innermost:
    the phase barrier's case, two phases."""
    g = trace(REGISTRY["ATAX"].script, REGISTRY["ATAX"].shapes(n))
    i, j = _rows_cols(g)
    f = analyse_group(g, g.calls)
    im = cost_impl(f, g, (i, j), (n // 2, n // 2), V5E)
    assert im.n_phases == 2
    return g, Combination((im,), im.t_pred)


def test_a_reduce_axis_in_two_blocks_taken_as_local_fails(monkeypatch):
    """The planted fault: a kernel that takes gemv's sum over half of
    j (a reduce axis of two blocks) as finished misses the reference by
    far more than ``TOL``; the phase barrier the plan asks for meets
    it."""
    g, combo = _atax_two_column_blocks()
    good = codegen.compile_combination(g, combo, backend="pallas",
                                       interpret=True)
    assert good.local_reductions == ()
    assert max(_errs(good, "ATAX", 256)) < TOL
    monkeypatch.setattr(fusion_mod, "reduce_roots",
                        lambda c, g: frozenset())
    bad = codegen.compile_combination(g, combo, backend="pallas",
                                      interpret=True)
    assert bad.local_reductions == ("t",)
    assert max(_errs(bad, "ATAX", 256)) > 100 * TOL


# ---------------------------------------------------------------------------
# the whole-axis contract: codegen and the verifier
# ---------------------------------------------------------------------------

def test_a_plan_that_splits_a_whole_axis_is_refused():
    """A hand-built plan blocking GEMVER's row axis, which xpay lacks
    and gemtv reduces over, in two: codegen raises RPL214 naming the
    group, and the verifier reports it."""
    n = 512
    g, combo = _column_blocked_gemver(n)
    i, j = _rows_cols(g)
    im = cost_impl(combo.impls[0].fusion, g, (j, i), (128, n // 2), V5E)
    with pytest.raises(UnsupportedGroupError, match="whole block") as e:
        codegen._group_pallas_fn(g, im, interpret=True)
    assert "rank2_update+gemtv+xpay+gemv" in str(e.value)
    plan = build_plan(g, Combination((im, combo.impls[1]), 0.0), "pallas")
    codes = [d.code for d in verify_plan(plan, g) if d.is_error]
    assert codes == ["RPL214"]
    ok = build_plan(g, combo, "pallas")
    assert not [d for d in verify_plan(ok, g) if d.is_error]
