"""Kimi Linear's Kimi Delta Attention (KDA) at decode through the fusion
compiler: ``KDA_DECODE_STEP``, one token for rows of (session, head),
each with a 128 x 128 state read, gated, updated by a rank-1 delta-rule
term and written back.

The compiled step (``jnp``, and Pallas in the interpreter) is held to
two oracles on seeded inputs: the float64 ``Program.reference`` of one
step, and ``kda_recurrent_reference``, the paper's recurrence in float32
``jax.numpy`` over several tokens.  Sizes: the published width 128 at
n = 64 rows (n = 256 for the multi-step grid).  Planning
checks at the benchmark's n = 4096 run no kernel.
"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import V5E, FusionCompiler, codegen, trace
from repro.core.elementary import make_nested_map, make_tensor_map_reduce
from repro.core.predictor import block_granules, cost_impl
from repro.core.scheduler import Combination, best_combination, build_space
from repro.programs import REGISTRY, make_inputs
from repro.programs import model_lib as mlib
from repro.programs.models import kda_recurrent_reference

#: error of an output, as a share of the reference's largest |entry|.
#: float32 state entries and 128-term sums round to about 1e-7 of that
#: scale (0.9e-7 on S_new, 1.6e-7 on o at n = 64 on the CPU); 1e-4
#: leaves room for the chip's order of summation and for eight chained
#: steps, while one bfloat16 rounding (2**-9) moves the answer by about
#: 4e-3 (``test_bfloat16_computation_fails_the_tolerance``)
TOL = 1e-4

PROG = REGISTRY["KDA_DECODE_STEP"]
N = 64
BACKENDS = [("jnp", False), ("pallas", True)]


def _err(got, want) -> float:
    got = np.asarray(got, np.float64)
    return float(np.max(np.abs(got - want)) / np.max(np.abs(want)))


def _reference(prog, inp):
    return prog.reference(**{k: np.asarray(v, np.float64)
                             for k, v in inp.items()})


def _compile(backend="jnp", interpret=False, prog=PROG, n=N, **kw):
    return FusionCompiler(backend=backend, interpret=interpret, cache=None,
                          **kw).compile(prog.script, prog.shapes(n))


def _errs(cp, prog=PROG, n=N, seed=11):
    inp = make_inputs(prog, n, seed=seed)
    return [_err(g, w) for g, w in zip(cp(**inp), _reference(prog, inp))]


@pytest.mark.parametrize("backend,interpret", BACKENDS)
def test_compiled_step_matches_the_float64_reference(backend, interpret):
    cp = _compile(backend, interpret)
    assert all("kda_" in label for label in cp.group_labels)
    assert max(_errs(cp)) < TOL


def _tokens(n, T, d, seed):
    """T tokens of q, k, v, f ``(T, n, d)`` and b ``(T, n)``, a per-row
    ``a_log`` ``(n,)`` and a start state ``(n, d, d)``, all N(0, 1)."""
    rng = np.random.default_rng(seed)

    def f32(*shape):
        return rng.standard_normal(shape).astype(np.float32)

    return (f32(T, n, d), f32(T, n, d), f32(T, n, d), f32(T, n, d),
            f32(n), f32(T, n), f32(n, d, d))


@pytest.mark.parametrize("backend,interpret", BACKENDS)
def test_eight_chained_steps_match_the_recurrent_reference(backend,
                                                           interpret):
    """Eight decode steps, each one call fed the ``S_new`` of the one
    before, against the paper's recurrence ``S_t = (I - beta k k^T)
    Diag(alpha) S_{t-1} + beta k v^T``, ``o_t = S_t^T q_t``: every
    token's read-out and the final state."""
    q, k, v, f, a_log, b, S = _tokens(N, 8, 128, seed=4)
    want_o, want_S = kda_recurrent_reference(q, k, v, f, a_log, b, S)
    cp = _compile(backend, interpret)
    outs = []
    for t in range(8):
        S, o = cp(S=S, q=q[t], k=k[t], v=v[t], f=f[t], a_log=a_log, b=b[t])
        outs.append(o)
    assert _err(np.stack(outs), np.asarray(want_o, np.float64)) < TOL
    assert _err(S, np.asarray(want_S, np.float64)) < TOL


def test_bfloat16_computation_fails_the_tolerance():
    """The same compiled step in bfloat16 misses the float64 reference
    by more than ``TOL`` on both outputs: the tolerance tells the
    precision the program states from the one below it."""
    cp = _compile(dtype="bfloat16")
    inp = make_inputs(PROG, N, seed=11)
    got = cp(**{k: jnp.asarray(v, jnp.bfloat16) for k, v in inp.items()})
    for g, w in zip(got, _reference(PROG, inp)):
        assert _err(jnp.asarray(g, jnp.float32), w) > 10 * TOL


def _no_delta():
    """``kda_u`` reading nothing: the delta rule's correction dropped."""
    return make_tensor_map_reduce(
        "kda_u", lambda ka, S: 0.0 * mlib._kda_contract(ka, S),
        in_axes=[(0, 1), (0, 1, 2)], reduce_axis=1)


def _no_gate():
    """``kda_gate`` at 1: the state never decays."""
    return make_nested_map("kda_gate", lambda f, a: 0.0 * f + 1.0,
                                in_axes=[(0, 1), (0,)])


@pytest.mark.parametrize("name,variant", [("kda_u", _no_delta),
                                          ("kda_gate", _no_gate)],
                         ids=["no_delta", "no_gate"])
def test_a_step_without_the_delta_term_or_the_gate_fails(monkeypatch, name,
                                                         variant):
    """The tolerance also tells the mechanism: a step whose state read
    ``u`` is 0 (plain linear attention's write), or whose decay is 1,
    misses the new state by far more than ``TOL``."""
    monkeypatch.setattr(mlib, name, variant())
    assert _errs(_compile())[0] > 100 * TOL


def test_multi_step_grids_on_the_interpreter():
    """At n = 256 the plan re-blocked to the fewest rows each group's
    operands allow (128 where a vector over the rows is an operand, as
    in both groups of this plan, else 8): every group runs several grid
    steps over the rows, the one group that reads the state finishing
    ``u`` inside each step and writing the rank-3 ``S_new`` block by
    block, and still meets the float64 reference."""
    n = 256
    g = trace(PROG.script, PROG.shapes(n))
    S = next(v for v in g.inputs if v.name == "S")
    rows = g.axis_root(S.axis_ids[0])
    impls = []
    for im in best_combination(build_space(g)).impls:
        least = max(8, block_granules(im.fusion, g, V5E)[rows])
        blocks = tuple(least if r == rows else b
                       for r, b in zip(im.order, im.blocks))
        impls.append(cost_impl(im.fusion, g, im.order, blocks, V5E))
    assert all(im.grid_steps >= 2 for im in impls)
    state = [im for im in impls if S in im.fusion.external_inputs]
    assert len(state) == 1 and state[0].grid_steps == 2
    assert state[0].n_phases == 1
    cp = codegen.compile_combination(
        g, Combination(tuple(impls), sum(i.t_pred for i in impls)),
        backend="pallas", interpret=True)
    assert "u" in cp.local_reductions
    assert max(_errs(cp, n=n)) < TOL


def test_softplus_absorbs_an_inexact_log1p(monkeypatch):
    """The gate's softplus refines its log1p by one Newton step on
    exp(y) = 1 + t, so a log1p that errs by 2**-11 of its value (a v5e
    kernel's errs by up to 2.6e-4, its exp by 5e-6) still yields
    softplus to float32 rounding."""
    f = np.linspace(-12.0, 12.0, 4001, dtype=np.float32)
    want = np.logaddexp(f.astype(np.float64), 0.0)
    log1p = jnp.log1p
    monkeypatch.setattr(jnp, "log1p", lambda t: log1p(t) * (1 + 2.0 ** -11))
    got = np.asarray(mlib._softplus(jnp.asarray(f)), np.float64)
    assert np.max(np.abs(got - want)) < 1e-6
    coarse = np.maximum(f, 0) + np.asarray(jnp.log1p(jnp.exp(-jnp.abs(f))))
    assert np.max(np.abs(coarse - want)) > 1e-4


def _pallas_calls(jaxpr):
    """Every ``pallas_call`` equation in ``jaxpr`` and its sub-jaxprs."""
    for e in jaxpr.eqns:
        if e.primitive.name == "pallas_call":
            yield e
        for p in e.params.values():
            sub = getattr(p, "jaxpr", p)
            if hasattr(sub, "eqns"):
                yield from _pallas_calls(sub)


def test_contractions_run_on_the_vector_unit():
    """No kernel holds a ``dot_general``: per row the contractions over
    the key width are matrix-vector products, which run as broadcasts
    and sublane sums, not as MXU passes at M = 1."""
    cp = _compile("pallas", True)
    args = [jax.ShapeDtypeStruct(s, jnp.float32)
            for s in PROG.shapes(N).values()]
    kernels = [e.params["jaxpr"]
               for e in _pallas_calls(jax.make_jaxpr(cp.fn)(*args).jaxpr)]
    assert len(kernels) == cp.n_groups
    assert not any("dot_general" in str(k) for k in kernels)


# ---------------------------------------------------------------------------
# the plan at the benchmark's size, and the counters
# ---------------------------------------------------------------------------

def _plan_4096():
    return FusionCompiler(backend="pallas", cache=None,
                          interpret=True).compile(PROG.script,
                                                  PROG.shapes(4096))


def _split_plan_4096():
    """The plan without the block-local barrier: the best combination
    of the groups that need no axis whole (every call over one axis
    set), which the compiler chose before it could consume a reduction
    in the step that finishes it."""
    g = trace(PROG.script, PROG.shapes(4096))
    space = build_space(g)
    space.fusions = [f for f in space.fusions if not f.whole_roots]
    return g, best_combination(space).impls


def _required(g):
    return (sum(v.nbytes for v in g.inputs)
            + sum(v.nbytes for v in g.outputs))


def test_the_state_is_read_twice_and_written_once():
    """Without the block-local barrier, fusion rule 1 keeps the write
    ``w`` over (rows, value width) out of the (rows, key, value) groups,
    so the state read ``u`` and the update ``S_new`` are two kernels
    that each stream ``S``.  With it, ``u`` finishes inside each grid
    step (its key axis is one whole block), so one kernel reads ``S``
    once, consumes ``u`` in the same step and writes ``S_new`` once, to
    a buffer of its own."""
    g, split = _split_plan_4096()
    S = next(v for v in g.inputs if v.name == "S")
    assert sum(S in im.fusion.external_inputs for im in split) == 2
    groups = [[c.elem.name for c in im.fusion.calls] for im in split]
    assert ["kda_u"] in groups and ["kda_snew", "kda_o"] in groups
    cp = _plan_4096()
    assert cp.input_passes["S"] == 1
    assert cp.transposed_operands == ()
    assert {"qss", "kss", "u"} <= set(cp.local_reductions)
    (one,) = [im for im in cp.group_impls
              if any(v.name == "S" for v in im.fusion.external_inputs)]
    assert [v.name for v in one.fusion.outputs] == ["S_new", "o"]
    assert one.n_phases == 1 and cp.n_groups <= 2


def test_planned_traffic_counts_two_passes_over_the_state():
    """The state's read and write are over 97 % of the required bytes.
    The split plan moves one more pass over it and the small vectors
    between its groups (``compiler.traffic_ratio`` about 1.54); the
    block-local plan moves the required bytes and the normed query
    between its two groups, under 1.02 times them."""
    g, split = _split_plan_4096()
    S = next(v for v in g.inputs if v.name == "S")
    required = _required(g)
    assert 2 * S.nbytes > 0.97 * required
    traffic = sum(im.traffic_bytes for im in split)
    assert required + S.nbytes < traffic < 1.6 * required
    cp = _plan_4096()
    traffic = sum(im.traffic_bytes for im in cp.group_impls)
    assert required <= traffic < 1.02 * required


def test_lint_cli_verifies_kda_at_the_benchmark_size(capsys):
    from repro.analysis.cli import main
    assert main(["--programs", "KDA_DECODE_STEP", "--n", "4096"]) == 0
    assert "0 errors" in capsys.readouterr().out


def test_recurrent_reference_runs_at_full_precision():
    """The recurrent reference sets its own matmul precision: under a
    caller's lower default it reads the same."""
    args = _tokens(8, 3, 16, seed=2)
    want = [np.asarray(x) for x in kda_recurrent_reference(*args)]
    with jax.default_matmul_precision("bfloat16"):
        again = [np.asarray(x) for x in kda_recurrent_reference(*args)]
    for a, w in zip(again, want):
        np.testing.assert_array_equal(a, w)
