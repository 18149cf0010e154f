"""DeepSeek-V2-Lite's routed experts at decode through the fusion
compiler: ``MOE_EXPERTS``, the experts one chip holds under expert
parallelism, each on the tokens dispatched to it.

The compiled program (``jnp``, and Pallas in the interpreter) is held to
the float64 ``Program.reference`` of its equations, and, through
``moe_dispatch`` and ``moe_combine``, to ``moe_layer_reference``, the
whole MoE layer in float32 ``jax.numpy``: the shares of every chip and
the shared experts, counted once, add up to the uncut layer.  Sizes: a
reduced instance (8 experts, hidden 256, width 384, n = 16 tokens per
expert); planning checks at the published widths run no kernel.
"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import V5E, FusionCompiler, trace
from repro.core.elementary import col, make_tensor_map
from repro.core.fusion import analyse_group
from repro.core.predictor import cost_impl, enumerate_impls
from repro.programs import REGISTRY, make_inputs
from repro.programs import model_lib as mlib
from repro.programs.models import (MOE_EXPERTS_ALL, moe_combine,
                                   moe_dispatch, moe_layer_reference,
                                   moe_program)

#: error of an output, as a share of the reference's largest |entry|.
#: float32 products summed over 256 hidden and 384 width channels round
#: to about 1e-6 of that scale (5.3e-7 to 1.1e-6 measured on the CPU,
#: three seeds, both backends); 1e-4 leaves room for the chip's order
#: of summation, while computing in bfloat16 moves the answer by about
#: 6e-3 (``test_bfloat16_computation_fails_the_tolerance``)
TOL = 1e-4

EXPERTS, HIDDEN, WIDTH, N = 8, 256, 384, 16
SMALL = moe_program(EXPERTS, HIDDEN, WIDTH, name="MOE_SMALL")
PUBLISHED = REGISTRY["MOE_EXPERTS"]
BACKENDS = [("jnp", False), ("pallas", True)]


def _err(got, want) -> float:
    got = np.asarray(got, np.float64)
    return float(np.max(np.abs(got - want)) / np.max(np.abs(want)))


def _reference(prog, inp):
    f64 = {k: np.asarray(v, np.float64) for k, v in inp.items()}
    return prog.reference(**f64)[0]


def _compile(backend="jnp", interpret=False, prog=SMALL, n=N, **kw):
    return FusionCompiler(backend=backend, interpret=interpret, cache=None,
                          **kw).compile(prog.script, prog.shapes(n))


def _err_of(call, prog=SMALL, n=N, seed=11):
    inp = make_inputs(prog, n, seed=seed)
    return _err(call(**inp), _reference(prog, inp))


@pytest.mark.parametrize("backend,interpret", BACKENDS)
def test_compiled_experts_match_the_float64_reference(backend, interpret):
    """Over several grid steps: the reduced instance's plan blocks the
    expert width in 128-wide steps."""
    cp = _compile(backend, interpret)
    assert all("moe_" in label for label in cp.group_labels)
    assert cp.grid_steps >= 3
    assert _err_of(cp) < TOL


def _layer_weights(seed, hidden=HIDDEN, width=WIDTH):
    """Router, 64 routed experts and the shared experts as one MLP of
    twice the expert width, scaled so activations stay of order 1."""
    rng = np.random.default_rng(seed)

    def w(*shape):
        return (rng.standard_normal(shape) / np.sqrt(shape[-1])).astype(
            np.float32)

    e = MOE_EXPERTS_ALL
    experts = (w(e, width, hidden), w(e, width, hidden), w(e, hidden, width))
    shared = (w(2 * width, hidden), w(2 * width, hidden),
              w(hidden, 2 * width))
    return w(e, hidden), experts, shared


@pytest.mark.parametrize("backend,interpret", BACKENDS)
def test_shares_of_every_chip_add_up_to_the_whole_layer(backend, interpret):
    """Additivity (model-configs §4): route seeded tokens over all 64
    experts, run the program on each chip's 8 held experts, combine each
    share back to the tokens, and add the shared experts once, run
    through the same program as one expert twice as wide with r = 1:
    the sum is the uncut ``moe_layer_reference``."""
    tokens = 24
    router_w, experts, shared = _layer_weights(seed=5)
    x = np.random.default_rng(6).standard_normal(
        (tokens, HIDDEN)).astype(np.float32)
    want = np.asarray(moe_layer_reference(x, router_w, experts, shared),
                      np.float64)

    cp = _compile(backend, interpret, n=tokens)
    got = np.zeros((tokens, HIDDEN))
    for held in np.split(np.arange(MOE_EXPERTS_ALL),
                         MOE_EXPERTS_ALL // EXPERTS):
        buf, r, slots = moe_dispatch(x, router_w, held)
        y = cp(x=buf, r=r, w_gate=experts[0][held], w_up=experts[1][held],
               w_down=experts[2][held])
        got += np.asarray(moe_combine(y, slots, tokens))
    one = moe_program(1, HIDDEN, 2 * WIDTH, name="MOE_SHARED")
    y = _compile(backend, interpret, prog=one, n=tokens)(
        x=x[None], r=np.ones((1, tokens), np.float32),
        w_gate=shared[0][None], w_up=shared[1][None], w_down=shared[2][None])
    got += np.asarray(y[0])
    assert _err(got, want) < 1e-5


def test_dispatch_groups_each_tokens_six_experts():
    """Over the 8 shares, every token lands in exactly 6 rows, in token
    order within each expert; rows past an expert's count are zero and
    point past the last token; the weights are the router's top 6, not
    renormalised (each token's sum below 1)."""
    tokens = 40
    router_w, _, _ = _layer_weights(seed=1)
    x = np.random.default_rng(2).standard_normal(
        (tokens, HIDDEN)).astype(np.float32)
    count = np.zeros(tokens, int)
    weight = np.zeros(tokens)
    for held in np.split(np.arange(MOE_EXPERTS_ALL), 8):
        buf, r, slots = (np.asarray(a) for a in moe_dispatch(x, router_w,
                                                             held))
        assert buf.shape == (8, tokens, HIDDEN) and r.shape == (8, tokens)
        for e in range(8):
            kept = slots[e][slots[e] < tokens]
            k = len(kept)
            assert np.all(np.diff(kept) > 0)
            assert np.all(slots[e][k:] == tokens)
            np.testing.assert_array_equal(buf[e, :k], x[kept])
            assert not buf[e, k:].any() and not r[e, k:].any()
            assert np.all(r[e, :k] > 0)
            count[kept] += 1
            weight[kept] += r[e, :k]
    assert np.all(count == 6)
    assert np.all(weight < 1.0)


def test_combine_adds_rows_back_to_their_tokens():
    slots = np.array([[0, 2, 3], [2, 3, 3]])
    y = np.arange(6, dtype=np.float32).reshape(2, 3, 1) + 1.0
    got = np.asarray(moe_combine(y, slots, 3))
    np.testing.assert_array_equal(got[:, 0], [1.0, 0.0, 2.0 + 4.0])


# ---------------------------------------------------------------------------
# the tolerance tells the precision and the mechanism
# ---------------------------------------------------------------------------

def test_bfloat16_computation_fails_the_tolerance():
    cp = _compile(dtype="bfloat16")
    inp = make_inputs(SMALL, N, seed=11)
    y = cp(**{k: jnp.asarray(v, jnp.bfloat16) for k, v in inp.items()})
    assert _err(jnp.asarray(y, jnp.float32),
                _reference(SMALL, inp)) > 10 * TOL


def test_a_down_projection_that_drops_one_width_block_fails():
    """The program as compiled, but the last 128-wide block of the
    expert width never reaches the down projection: one grid step of
    its reduction skipped."""
    cp = _compile("pallas", True)

    def dropped(w_down, **inputs):
        return cp(w_down=jnp.asarray(w_down).at[..., -128:].set(0.0),
                  **inputs)
    assert _err_of(dropped) > 10 * TOL


def test_an_activation_without_the_silu_gate_fails(monkeypatch):
    monkeypatch.setattr(mlib, "moe_act", make_tensor_map(
        "moe_act", lambda r, g, u: col(r) * g * u,
        in_axes=[(0, 1), (0, 1, 2), (0, 1, 2)], depth=3))
    assert _err_of(_compile()) > 10 * TOL


# ---------------------------------------------------------------------------
# the plan at the benchmark's size, and the counters
# ---------------------------------------------------------------------------

def _plan_64():
    return _compile("pallas", True, prog=PUBLISHED, n=64)


def _required(g):
    return (sum(v.nbytes for v in g.inputs)
            + sum(v.nbytes for v in g.outputs))


def test_plan_at_the_benchmark_size():
    """Two kernels: gate, up and the activation at (hidden whole, 8
    experts, 64 tokens, 128 of the width), gate and up finished and
    consumed in the step; the down projection at (1 expert, 64 tokens,
    the width whole, 1024 of hidden).  Every input is streamed once and
    the plan moves 1.02 times the required bytes."""
    cp = _plan_64()
    assert cp.group_labels == ["g0_moe_proj_moe_proj_moe_act", "g1_moe_down"]
    assert cp.local_reductions == ("gg", "uu")
    assert cp.transposed_operands == ()
    assert set(cp.input_passes.values()) == {1}
    g0, g1 = cp.group_impls
    sizes = {r: s for im in cp.group_impls
             for r, s in zip(im.fusion.axis_roots, im.fusion.axis_sizes)}
    assert sorted(zip((sizes[r] for r in g0.order), g0.blocks)) == [
        (8, 8), (64, 64), (1408, 128), (2048, 2048)]
    assert g0.grid_steps == 11 and g1.grid_steps == 16
    assert g0.n_phases == g1.n_phases == 1
    required = _required(cp.graph)
    traffic = sum(im.traffic_bytes for im in cp.group_impls)
    assert 285e6 < required < 285.3e6
    assert required < traffic < 1.021 * required


def test_one_kernel_ffn_is_legal_but_over_the_vmem_budget():
    """All four calls in one group are legal (hidden and the down
    projection's output whole), but ``r``'s (experts, tokens) carrier
    puts the experts on sublanes, so they cannot be blocked below 8: the
    one candidate needs more VMEM than the budget, and the predictor
    offers none."""
    g = trace(PUBLISHED.script, PUBLISHED.shapes(64))
    f = analyse_group(g, g.calls)
    assert f is not None and len(f.whole_roots) == 2
    assert enumerate_impls(f, g, V5E) == []
    least = cost_impl(f, g, f.axis_roots, (2048, 8, 64, 128, 2048), V5E)
    assert V5E.vmem_bytes < least.vmem_bytes < 68e6


def test_lint_cli_verifies_moe_at_the_benchmark_size(capsys):
    from repro.analysis.cli import main
    assert main(["--programs", "MOE_EXPERTS", "--n", "64"]) == 0
    assert "0 errors" in capsys.readouterr().out


def test_layer_reference_runs_at_full_precision():
    """The layer reference sets its own matmul precision: under a
    caller's lower default it reads the same."""
    router_w, experts, shared = _layer_weights(seed=3, hidden=32, width=16)
    x = np.random.default_rng(4).standard_normal((5, 32)).astype(np.float32)
    want = np.asarray(moe_layer_reference(x, router_w, experts, shared))
    with jax.default_matmul_precision("bfloat16"):
        again = np.asarray(moe_layer_reference(x, router_w, experts, shared))
    np.testing.assert_array_equal(again, want)
