"""DeepSeek-V2-Lite's latent attention (MLA) at decode through the fusion
compiler: ``MLA_DECODE_ATTN``, 16 heads over one shared latent cache.

The compiled program (``jnp``, and Pallas in the interpreter) is held to
two oracles on seeded inputs: the float64 ``Program.reference`` of the
absorbed equations, and ``mla_unabsorbed_reference``, the architecture's
own per-head keys and values in float32 ``jax.numpy``.  Sizes: a reduced
instance (4 heads, rank 32, rope 8, n=1000, a multiple of no block) and
the published widths at n=1024.  Planning checks run no kernel.
"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import FusionCompiler, V5E, codegen, trace
from repro.core.plan import build_plan
from repro.core.predictor import cost_impl
from repro.core.scheduler import Combination, best_combination, build_space
from repro.programs import REGISTRY, make_inputs
from repro.programs.models import (MLA_SCALE, mla_program,
                                   mla_unabsorbed_reference)

#: error of an output, as a share of the reference's largest |entry|.
#: float32 products summed over at most 1024 positions and 512 latent
#: columns round to about 1e-6 of that scale (5e-7 to 1.4e-6 measured on
#: the CPU); 1e-4 leaves room for the chip's order of summation, while
#: one bfloat16 rounding of the inputs alone (2**-9) moves the answer by
#: about 4e-3 (``test_bfloat16_computation_fails_the_tolerance``)
TOL = 1e-4

SMALL = mla_program(heads=4, rank=32, rope=8, name="MLA_SMALL")
PUBLISHED = REGISTRY["MLA_DECODE_ATTN"]
CASES = [(SMALL, 1000), (PUBLISHED, 1024)]
BACKENDS = [("jnp", False), ("pallas", True)]


def _err(got, want) -> float:
    got = np.asarray(got, np.float64)
    return float(np.max(np.abs(got - want)) / np.max(np.abs(want)))


def _reference(prog, inp):
    f64 = {k: np.asarray(v, np.float64) for k, v in inp.items()}
    return prog.reference(**f64)[0]


def test_scale_is_deepseeks_yarn_softmax_scale():
    assert MLA_SCALE == pytest.approx(0.1147214, abs=5e-8)


@pytest.mark.parametrize("backend,interpret", BACKENDS)
@pytest.mark.parametrize("prog,n", CASES, ids=["small", "published"])
def test_compiled_program_matches_the_float64_reference(prog, n, backend,
                                                        interpret):
    inp = make_inputs(prog, n, seed=11)
    cp = FusionCompiler(backend=backend, interpret=interpret,
                        cache=None).compile(prog.script, prog.shapes(n))
    assert all("mla_" in label for label in cp.group_labels)
    assert _err(cp(**inp), _reference(prog, inp)) < TOL


def _unabsorbed_inputs(heads, rank, rope, n, seed):
    """The architecture's per-head queries, caches and up-projections
    ``(q_nope, q_rope, ckv, kr, W_UK, W_UV)``; the projections are scaled
    by rank**-0.5, as trained ones keep activations near unit size."""
    rng = np.random.default_rng(seed)

    def f32(*shape, scale=1.0):
        return jnp.asarray(scale * rng.standard_normal(shape), jnp.float32)

    return (f32(heads, 128), f32(heads, rope), f32(n, rank), f32(n, rope),
            f32(heads, rank, 128, scale=rank ** -0.5),
            f32(heads, rank, 128, scale=rank ** -0.5))


def mla_head_outputs(o_lat, w_uv):
    """Each head's output ``W_UV[h]^T o_lat[h]`` ``(h, v)``."""
    return jnp.einsum("hc,hcd->hd", o_lat, w_uv, precision="highest")


def _unabsorbed_case(heads, rank, rope, n, seed):
    """The absorbed inputs the program takes, ``W_UV``, and every head's
    output by the unabsorbed reference."""
    q_nope, q_rope, ckv, kr, w_uk, w_uv = _unabsorbed_inputs(
        heads, rank, rope, n, seed)
    # the absorbed query q_lat[h] = W_UK[h] q_nope[h]
    q_lat = jnp.einsum("hcd,hd->hc", w_uk, q_nope, precision="highest")
    absorbed = {"q_lat": q_lat, "q_rope": q_rope, "ckv": ckv, "kr": kr}
    want = mla_unabsorbed_reference(q_nope, q_rope, ckv, kr, w_uk, w_uv)
    return absorbed, w_uv, np.asarray(want, np.float64)


@pytest.mark.parametrize("backend,interpret", BACKENDS)
@pytest.mark.parametrize("prog,n", CASES, ids=["small", "published"])
def test_head_outputs_match_the_unabsorbed_architecture(prog, n, backend,
                                                        interpret):
    """``W_UV[h]^T o_lat[h]`` is head h's attention output with keys
    ``[ckv W_UK[h] ; kr]`` and values ``ckv W_UV[h]``.  Both sides are
    float32: the gap is two orders of summation (about 1e-6), under
    ``TOL``."""
    shapes = prog.shapes(n)
    heads, rank = shapes["q_lat"]
    absorbed, w_uv, want = _unabsorbed_case(heads, rank, shapes["kr"][1],
                                            n, seed=5)
    cp = FusionCompiler(backend=backend, interpret=interpret,
                        cache=None).compile(prog.script, shapes)
    assert _err(mla_head_outputs(cp(**absorbed), w_uv), want) < TOL


@pytest.mark.parametrize("prog,n", CASES, ids=["small", "published"])
def test_bfloat16_computation_fails_the_tolerance(prog, n):
    """The same compiled chain in bfloat16 misses both oracles by more
    than ``TOL``: the tolerance tells the precision the program states
    from the one below it."""
    shapes = prog.shapes(n)
    cp = FusionCompiler(backend="jnp", dtype="bfloat16",
                        cache=None).compile(prog.script, shapes)
    bf16 = lambda d: {k: jnp.asarray(v, jnp.bfloat16)  # noqa: E731
                      for k, v in d.items()}
    inp = make_inputs(prog, n, seed=11)
    assert _err(cp(**bf16(inp)), _reference(prog, inp)) > 10 * TOL
    heads, rank = shapes["q_lat"]
    absorbed, w_uv, want = _unabsorbed_case(heads, rank, shapes["kr"][1],
                                            n, seed=5)
    got = mla_head_outputs(jnp.asarray(cp(**bf16(absorbed)), jnp.float32),
                           w_uv)
    assert _err(got, want) > 10 * TOL


def _roots(g):
    """Axis roots of the cache positions t, latent width c, rope width r."""
    ckv = next(v for v in g.inputs if v.name == "ckv")
    kr = next(v for v in g.inputs if v.name == "kr")
    return (g.axis_root(ckv.axis_ids[0]), g.axis_root(ckv.axis_ids[1]),
            g.axis_root(kr.axis_ids[1]))


def _split_space(g):
    """The optimization space without its online-softmax groups, nor
    the groups that need an axis whole (``Fusion.whole_roots``): the
    plans the compiler chose before it could stream the cache once."""
    space = build_space(g)
    space.fusions = [f for f in space.fusions
                     if f.stream_root is None and not f.whole_roots]
    return space


def test_multi_step_grids_on_the_interpreter():
    """The split plan at the published widths, re-blocked so every group
    runs many grid steps: t in blocks of 256 (the scores and the 3-phase
    softmax over four steps, the value sum accumulated across them) and
    the latent width in blocks of 128 (the score sum accumulated across
    four)."""
    n = 1024
    g = trace(PUBLISHED.script, PUBLISHED.shapes(n))
    t, c, _ = _roots(g)
    impls = []
    for im in best_combination(_split_space(g)).impls:
        blocks = tuple(256 if r == t else 128 if r == c else b
                       for r, b in zip(im.order, im.blocks))
        impls.append(cost_impl(im.fusion, g, im.order, blocks, V5E))
    combo = Combination(tuple(impls), sum(i.t_pred for i in impls))
    assert max(im.n_phases for im in impls) >= 2
    assert all(im.grid_steps >= 4 for im in impls)
    cp = codegen.compile_combination(g, combo, backend="pallas",
                                     interpret=True)
    inp = make_inputs(PUBLISHED, n, seed=3)
    assert _err(cp(**inp), _reference(PUBLISHED, inp)) < TOL


@pytest.mark.parametrize("prog,block,swapped", [
    (SMALL, 128, ("ckv", "kr")), (PUBLISHED, 256, ("kr",))],
    ids=["small", "published"])
def test_swapped_kr_matches_both_oracles_over_several_steps(prog, block,
                                                           swapped):
    """The rotary key cache kr (n, rope) is narrow (so is the small
    instance's latent cache, rank 32), so the kernel takes it swapped,
    (rope, n), in blocks along its lanes, and turns each block back:
    over several steps of t the program still meets the float64
    reference and the unabsorbed architecture."""
    n = 1024
    shapes = prog.shapes(n)
    g = trace(prog.script, shapes)
    (im,) = best_combination(build_space(g)).impls
    t = im.fusion.stream_root
    blocks = tuple(block if r == t else b for r, b in zip(im.order, im.blocks))
    im = cost_impl(im.fusion, g, im.order, blocks, V5E)
    cp = codegen.compile_combination(g, Combination((im,), im.t_pred),
                                     backend="pallas", interpret=True)
    assert cp.transposed_operands == swapped
    assert im.grid_steps == n // block
    inp = make_inputs(prog, n, seed=8)
    assert _err(cp(**inp), _reference(prog, inp)) < TOL
    heads, rank = shapes["q_lat"]
    absorbed, w_uv, want = _unabsorbed_case(heads, rank, shapes["kr"][1],
                                            n, seed=9)
    assert _err(mla_head_outputs(cp(**absorbed), w_uv), want) < TOL


# ---------------------------------------------------------------------------
# the plan at the benchmark's size, and the counters
# ---------------------------------------------------------------------------

def _plan(name, n):
    prog = REGISTRY[name]
    cc = FusionCompiler(backend="pallas", cache=None)
    g = cc.trace(prog.script, prog.shapes(n))
    return g, cc.search(cc.space(g), "best")


def test_the_cache_is_charged_once_per_pass_with_every_head_in_a_block():
    """The split plan's score group over (h, t, c) reads ckv once when
    the 16 heads are one block, and once per head block when they are
    split: the predictor keeps the whole-head block."""
    prog = REGISTRY["MLA_DECODE_ATTN"]
    g = trace(prog.script, prog.shapes(131072))
    combo = best_combination(_split_space(g))
    t, c, _ = _roots(g)
    ckv = next(v for v in g.inputs if v.name == "ckv")
    score = next(im for im in combo.impls
                 if ckv in im.fusion.external_inputs
                 and im.fusion.calls[0].elem.name == "mla_score")
    h = next(r for r in score.fusion.axis_roots if r not in (t, c))
    assert score.block_of(h) == 16
    split = cost_impl(score.fusion, g, score.order,
                      tuple(8 if r == h else b
                            for r, b in zip(score.order, score.blocks)), V5E)
    assert split.traffic_bytes - score.traffic_bytes == ckv.nbytes
    assert split.t_pred > score.t_pred


def test_input_passes_count_each_phase_of_each_group_that_reads_it():
    cc = FusionCompiler(backend="pallas", cache=None, interpret=True)
    mla = cc.compile(PUBLISHED.script, PUBLISHED.shapes(131072))
    # one pass scores and weights the latent rows (the online softmax)
    assert mla.input_passes == {"q_lat": 1, "q_rope": 1, "ckv": 1, "kr": 1}
    gemver = REGISTRY["GEMVER"]
    cp = cc.compile(gemver.script, gemver.shapes(16384))
    assert cp.input_passes == dict.fromkeys(gemver.shapes(16384), 1)
    for prog in (mla, cp):
        want = dict.fromkeys(prog.input_passes, 0)
        for im in prog.group_impls:
            for v in im.fusion.external_inputs:
                if v.is_input:
                    want[v.name] += im.n_phases
        assert prog.input_passes == want


def test_required_cache_bytes_dominate_and_traffic_counts_two_passes():
    """The caches are nearly all of the required bytes.  The plan's
    traffic counts one pass over ckv where the split plan counts two."""
    g, combo = _plan("MLA_DECODE_ATTN", 131072)
    ckv, kr = (next(v for v in g.inputs if v.name == nm)
               for nm in ("ckv", "kr"))
    traffic = sum(im.traffic_bytes for im in combo.impls)
    required = sum(v.nbytes for v in g.inputs) + 16 * 512 * 4
    assert ckv.nbytes + kr.nbytes > 0.99 * required
    assert required <= traffic <= 1.06 * required
    split = sum(im.traffic_bytes
                for im in best_combination(_split_space(g)).impls)
    assert 2 * ckv.nbytes + kr.nbytes < split < 3 * required


#: the plans of the benchmarked programs (and ATAX) at the benchmark's
#: sizes: per group its calls, grid order (positions into its sorted axis
#: roots) and blocks.  GEMVER's first group, ATAX's one and KDA's second
#: hold a reduce axis whole, so the reduction they consume is block-local
#: (one pass over A, B's one write, one pass over S)
PLANS = {
    ("GEMVER", 16384): [((0, 1, 2, 3), (0, 1), (16384, 128)),
                        ((4,), (0,), (16384,))],
    ("AXPYDOT", 1 << 26): [((0, 1, 2), (0,), (1048576,))],
    ("ATAX", 16384): [((0, 1), (0, 1), (256, 16384))],
    ("KDA_DECODE_STEP", 4096): [((0, 2), (0, 1), (1024, 128)),
                                ((1, 3, 4, 5, 6, 7, 8, 9), (0, 1, 2),
                                 (128, 128, 128))],
    ("MLA_DECODE_ATTN", 131072): [((0, 1, 2, 3, 4, 5, 6, 7), (0, 1, 2, 3),
                                   (16, 512, 64, 4096))],
}


@pytest.mark.parametrize("name,n", sorted(PLANS))
def test_benchmarked_blas_plans_are_unchanged(name, n):
    g, combo = _plan(name, n)
    plan = build_plan(g, combo, "pallas")
    assert [(gp.call_indices, gp.order_pos, gp.blocks)
            for gp in plan.groups] == PLANS[name, n]


def test_lint_cli_verifies_mla_at_the_benchmark_size(capsys):
    from repro.analysis.cli import main
    assert main(["--programs", "MLA_DECODE_ATTN", "--n", "131072"]) == 0
    out = capsys.readouterr().out
    assert "0 errors" in out


def test_jnp_reference_runs_at_full_precision():
    """The unabsorbed reference sets its own matmul precision: under a
    caller's lower default it reads the same."""
    args = _unabsorbed_inputs(4, 32, 8, 300, seed=2)
    want = np.asarray(mla_unabsorbed_reference(*args))
    with jax.default_matmul_precision("bfloat16"):
        again = np.asarray(mla_unabsorbed_reference(*args))
    np.testing.assert_array_equal(again, want)
