"""The generalized program registry (repro.programs, DESIGN.md §10):
group structure, registration invariants, and per-program input
factories."""
import numpy as np
import pytest

from repro import programs
from repro.programs import (ADAMW_HYPERS, BLAS, MODELS, REGISTRY,
                            make_inputs, register)
from repro.programs.models import moe_program

PAPER_SEQUENCES = ["AXPYDOT", "ATAX", "BiCGK", "SGEMV", "SGEMVT", "SSCAL",
                   "GEMVER", "GESUMMV", "MADD", "VADD", "WAXPBY"]
MODEL_SEQUENCES = ["LM_RMSNORM", "LM_BLOCK", "LM_DECODE_ATTN", "FUSED_ADAMW",
                   "MLA_DECODE_ATTN", "KDA_DECODE_STEP", "MOE_EXPERTS"]
#: reduced-width instances of programs whose published widths hold
#: weights of 92 MB each whatever ``n``, and add no coverage here
REDUCED = {"MOE_EXPERTS": moe_program(8, 128, 256, name="MOE_EXPERTS")}


def test_groups_partition_the_registry():
    assert sorted(BLAS) == sorted(PAPER_SEQUENCES)
    assert sorted(MODELS) == sorted(MODEL_SEQUENCES)
    assert set(REGISTRY) == set(BLAS) | set(MODELS)
    assert not set(BLAS) & set(MODELS)
    for name, prog in REGISTRY.items():
        assert prog.name == name


def test_blas_module_reexports_the_blas_group():
    """The package-level BLAS group holds exactly the 11 paper
    sequences, none of the model workloads registered beside them."""
    assert sorted(programs.BLAS) == sorted(PAPER_SEQUENCES)


def test_register_rejects_duplicate_names():
    with pytest.raises(ValueError, match="VADD"):
        register(REGISTRY["VADD"], None)


def test_make_inputs_honors_program_factory():
    """Model programs carry input factories encoding their numerical
    contracts — e.g. LM_RMSNORM's inv_d is the exact f32 1/n that the
    reference's mean constant-folds to."""
    prog = REGISTRY["LM_RMSNORM"]
    inp = make_inputs(prog, 96, seed=1)
    assert inp["inv_d"] == np.float32(1.0) / np.float32(96)
    assert inp["x"].shape == (96,) and inp["x"].dtype == np.float32
    # deterministic per seed
    again = make_inputs(prog, 96, seed=1)
    np.testing.assert_array_equal(inp["x"], again["x"])


def test_make_inputs_default_path_for_blas():
    inp = make_inputs(REGISTRY["AXPYDOT"], 64, seed=0)
    assert inp["w"].shape == (64,)
    assert np.ndim(inp["alpha"]) == 0


def test_explicit_pad_values_on_fused_adamw():
    prog = REGISTRY["FUSED_ADAMW"]
    assert prog.pad_values is not None
    assert set(prog.pad_values) == set(prog.shapes(8))
    assert all(v == 0.0 for v in prog.pad_values.values())
    # BLAS programs rely on analysis instead
    assert REGISTRY["ATAX"].pad_values is None


def test_references_match_scripts_via_compiler():
    """Spot-check that each MODEL program's registry reference agrees
    with its compiled script (allclose in f64 — bitwise contracts are
    pinned in test_model_serving.py)."""
    from repro.core import FusionCompiler

    cc = FusionCompiler(cache=None)
    for name in MODEL_SEQUENCES:
        prog = REDUCED.get(name, REGISTRY[name])
        n = 64
        compiled = cc.compile(prog.script, prog.shapes(n))
        inp = make_inputs(prog, n, seed=5)
        out = compiled(**inp)
        if not isinstance(out, tuple):
            out = (out,)
        ref = prog.reference(**{k: np.asarray(v, np.float64)
                                for k, v in inp.items()})
        assert len(out) == len(ref)
        for o, r in zip(out, ref):
            np.testing.assert_allclose(np.asarray(o, np.float64), r,
                                       rtol=1e-4, atol=1e-5)


def test_programs_namespace_exports():
    assert programs.ADAMW_HYPERS is ADAMW_HYPERS
    assert programs.HEAD_DIM == 48
    assert ADAMW_HYPERS["step"] >= 1
