"""End-to-end correctness of all 11 paper sequences through the full
compiler pipeline, on both backends, against numpy oracles."""
import numpy as np
import pytest

from repro.core import FusionCompiler
from repro.programs import BLAS, make_inputs

SIZES = {"jnp": 1024, "pallas": 256}


def _run(name, backend, n, mode="best"):
    seq = BLAS[name]
    cc = FusionCompiler(backend=backend, interpret=True)
    prog = cc.compile(seq.script, seq.shapes(n), mode=mode)
    inputs = make_inputs(seq, n, seed=3)
    out = prog(**inputs)
    ref = seq.reference(**inputs)
    if not isinstance(out, tuple):
        out = (out,)
    for o, r in zip(out, ref):
        np.testing.assert_allclose(np.asarray(o), r, rtol=1e-4, atol=1e-3)


@pytest.mark.parametrize("name", list(BLAS))
def test_jnp_backend(name):
    _run(name, "jnp", SIZES["jnp"])


@pytest.mark.parametrize("name", list(BLAS))
def test_pallas_backend(name):
    _run(name, "pallas", SIZES["pallas"])


@pytest.mark.parametrize("name", ["BiCGK", "GEMVER", "AXPYDOT", "VADD"])
def test_unfused_mode_matches(name):
    _run(name, "jnp", 512, mode="unfused")


@pytest.mark.parametrize("rank", [0, 1, 2, 3])
def test_ranked_combinations_all_correct(rank):
    """Every combination in the optimization space computes the same
    function (the empirical-search guarantee)."""
    _run("GEMVER", "jnp", 256, mode=rank)


@pytest.mark.parametrize("n", [256, 512, 768, 1024])
def test_shape_sweep_jnp(n):
    _run("BiCGK", "jnp", n)


@pytest.mark.parametrize("n", [128, 256, 384])
def test_shape_sweep_pallas(n):
    _run("GEMVER", "pallas", n)


def test_nonsquare_padding_contract():
    """Sizes are padded to the 32-element granularity by the caller
    (paper §4.4) — compiler accepts any multiple-of-128 size."""
    _run("SGEMV", "jnp", 640)
