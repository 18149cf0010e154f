"""Plan/pack corruption fuzzing (DESIGN.md §11, satellite of the static
analysis layer).

Deterministic mutants — one per corruption class the cache healer must
survive — always run; each must be rejected by the verifier with its
stable RPL code.  A hypothesis-driven fuzzer (optional dev dependency;
skipped when not installed) additionally random-walks the same mutation
space.  Finally, every unmutated REGISTRY plan must verify clean: the
fuzzer is only trustworthy if the verifier's false-positive rate on
real plans is zero.
"""
import copy
import json

import pytest

from repro.analysis import VerificationError, verify_plan, verify_plan_quick
from repro.core import graph as graph_mod
from repro.core.plan import ExecutionPlan, PackedPlan, build_packed_plan, \
    build_plan
from repro.core.predictor import V5E
from repro.core.scheduler import (best_combination, build_space,
                                  unfused_combination)
from repro.programs import REGISTRY

_CACHE = {}


def _fixture(name, mode="best", backend="jnp", n=128):
    """(plan-dict, graph) for one registry program, memoized per module."""
    key = (name, mode, backend, n)
    if key not in _CACHE:
        prog = REGISTRY[name]
        g = graph_mod.trace(prog.script, prog.shapes(n))
        space = build_space(g, V5E)
        combo = (unfused_combination(space) if mode == "unfused"
                 else best_combination(space))
        plan = build_plan(g, combo, backend=backend)
        _CACHE[key] = (json.loads(plan.to_json()), g)
    d, g = _CACHE[key]
    return copy.deepcopy(d), g


def _reject(d, g, expected):
    """The verifier must reject plan-dict ``d`` with a code in
    ``expected`` — either at deserialization or in the full pass."""
    try:
        plan = ExecutionPlan.from_json(json.dumps(d))
    except VerificationError as e:
        assert set(e.codes) & expected, (e.codes, expected)
        return set(e.codes)
    codes = {x.code for x in verify_plan(plan, g) if x.is_error}
    assert codes & expected, (codes, expected)
    return codes


# ---------------------------------------------------------------------------
# deterministic mutants: one per corruption class, stable code pinned
# ---------------------------------------------------------------------------

def test_mutant_bad_version():
    d, g = _fixture("AXPYDOT")
    d["version"] = 99
    _reject(d, g, {"RPL201"})


def test_mutant_skewed_signature():
    d, g = _fixture("AXPYDOT")
    d["signature"] = "0" * 64
    _reject(d, g, {"RPL210"})


def test_mutant_unknown_backend():
    d, g = _fixture("AXPYDOT")
    d["backend"] = "cuda"
    _reject(d, g, {"RPL401"})


def test_mutant_skewed_dtype():
    d, g = _fixture("AXPYDOT")
    d["dtype"] = "float64"
    _reject(d, g, {"RPL219"})


def test_mutant_dropped_group():
    # GEMVER unfused: multiple groups, later ones read earlier outputs —
    # dropping one breaks both coverage and ref resolution
    d, g = _fixture("GEMVER", mode="unfused")
    del d["groups"][-1]
    _reject(d, g, {"RPL202", "RPL218"})


def test_mutant_duplicated_coverage():
    d, g = _fixture("GEMVER", mode="unfused")
    d["groups"][1]["calls"] = d["groups"][0]["calls"]
    _reject(d, g, {"RPL205"})


def test_mutant_broken_topo():
    d, g = _fixture("GEMVER", mode="unfused")
    gi, ri = next((gi, ri)
                  for gi, gp in enumerate(d["groups"])
                  for ri, r in enumerate(gp["inputs"]) if r[0] == "group")
    d["groups"][gi]["inputs"][ri][1] = gi      # self-reference
    _reject(d, g, {"RPL203"})


def test_mutant_unresolvable_ref():
    d, g = _fixture("GEMVER", mode="unfused")
    d["groups"][0]["inputs"][0] = ["input", "no_such_input"]
    _reject(d, g, {"RPL202"})


def test_mutant_unknown_ref_tag():
    d, g = _fixture("AXPYDOT")
    d["groups"][0]["inputs"][0] = ["teleport", 0]
    _reject(d, g, {"RPL202"})


def test_mutant_swapped_routing_ref():
    # the quick subset accepts this one — only the full routing
    # reconstruction catches a resolvable-but-wrong ref
    d, g = _fixture("AXPYDOT")
    refs = d["groups"][0]["inputs"]
    a, b = (i for i, r in enumerate(refs)
            if r[0] == "input" and r[1] in ("w", "v"))
    refs[a], refs[b] = refs[b], refs[a]
    assert not [x for x in
                verify_plan_quick(ExecutionPlan.from_json(json.dumps(d)), g)
                if x.is_error]
    _reject(d, g, {"RPL216"})


def test_mutant_corrupt_order_pos():
    d, g = _fixture("AXPYDOT")
    gp = d["groups"][0]
    gp["order_pos"] = [99] * len(gp["order_pos"])
    _reject(d, g, {"RPL204"})


def test_mutant_zero_block():
    d, g = _fixture("AXPYDOT")
    d["groups"][0]["blocks"][0] = 0
    _reject(d, g, {"RPL204"})


def test_mutant_oversized_block():
    d, g = _fixture("AXPYDOT")
    d["groups"][0]["blocks"][0] = 1 << 30
    _reject(d, g, {"RPL213"})


@pytest.mark.parametrize("backend", ["jnp", "pallas"])
def test_mutant_block_off_the_mosaic_tiling(backend):
    """A vector axis blocked at 8 (a divisor, but not a multiple of the
    128-lane tile) is a kernel Mosaic refuses.  The block rule has one
    owner, the predictor, so the verifier rejects it on either backend."""
    d, g = _fixture("AXPYDOT", backend=backend, n=256)
    d["groups"][0]["blocks"][0] = 8
    _reject(d, g, {"RPL213"})


def test_mutant_zero_n_outputs():
    d, g = _fixture("AXPYDOT")
    d["groups"][0]["n_outputs"] = 0
    _reject(d, g, {"RPL204"})


def test_mutant_swapped_output_refs():
    d, g = _fixture("AXPYDOT")           # two outputs (z, r)
    d["outputs"][0], d["outputs"][1] = d["outputs"][1], d["outputs"][0]
    _reject(d, g, {"RPL217"})


def test_mutant_illegal_group_merge():
    # fuse calls the scheduler never would: claim one group covers the
    # whole unfused GEMVER call set with a single-axis grid
    d, g = _fixture("GEMVER", mode="unfused")
    calls = sorted(i for gp in d["groups"] for i in gp["calls"])
    d["groups"] = [{"calls": calls, "order_pos": [0], "blocks": [1],
                    "inputs": [["input", nm] for nm in d["input_names"]],
                    "n_outputs": len(d["outputs"])}]
    d["outputs"] = [["group", 0, i] for i in range(len(d["outputs"]))]
    _reject(d, g, {"RPL211", "RPL212", "RPL216"})


def test_mutant_pack_noncanonical_order():
    da, _ = _fixture("AXPYDOT")
    dv, _ = _fixture("VADD")
    pa = ExecutionPlan.from_json(json.dumps(da))
    pv = ExecutionPlan.from_json(json.dumps(dv))
    packed = build_packed_plan([pa, pv])
    d = json.loads(packed.to_json())
    d["members"].reverse()
    with pytest.raises(VerificationError) as ei:
        PackedPlan.from_json(json.dumps(d))
    assert "RPL301" in ei.value.codes


# ---------------------------------------------------------------------------
# zero false positives: every unmutated REGISTRY plan verifies clean
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("name", sorted(REGISTRY))
def test_unmutated_registry_plans_verify_clean(name):
    for backend in ("jnp", "pallas"):
        for mode in ("best", "unfused"):
            d, g = _fixture(name, mode=mode, backend=backend)
            plan = ExecutionPlan.from_json(json.dumps(d))
            diags = verify_plan(plan, g)
            assert not [x for x in diags if x.is_error], (
                name, backend, mode, [x.format() for x in diags])


# ---------------------------------------------------------------------------
# hypothesis fuzzer (optional dev dependency)
# ---------------------------------------------------------------------------

try:
    from hypothesis import given, settings, strategies as st
    HAVE_HYPOTHESIS = True
except ImportError:        # optional dev dependency — the deterministic
    HAVE_HYPOTHESIS = False  # mutants above cover every corruption class

_KINDS = ("version", "signature", "backend", "dtype", "drop_group",
          "order_pos", "block", "ref")


def _mutate(d, kind, rng):
    """Apply one random corruption of class ``kind``; returns the
    expected rejection codes (or None when this draw can't apply)."""
    if kind == "version":
        d["version"] = rng.randrange(2, 1000)
        return {"RPL201"}
    if kind == "signature":
        d["signature"] = f"{rng.getrandbits(256):064x}"
        return {"RPL210"}
    if kind == "backend":
        d["backend"] = rng.choice(["cuda", "opencl", "", "JNP"])
        return {"RPL401"}
    if kind == "dtype":
        d["dtype"] = rng.choice(["float64", "int32", "bogus"])
        return {"RPL219", "RPL201"}
    if kind == "drop_group":
        if len(d["groups"]) < 2:
            return None
        del d["groups"][rng.randrange(len(d["groups"]))]
        return {"RPL202", "RPL218", "RPL216", "RPL217"}
    if kind == "order_pos":
        gp = rng.choice(d["groups"])
        gp["order_pos"] = [p + 100 for p in gp["order_pos"]]
        return {"RPL204"}
    if kind == "block":
        gp = rng.choice(d["groups"])
        gp["blocks"][rng.randrange(len(gp["blocks"]))] = rng.choice(
            [0, -1, 1 << 30])
        return {"RPL204", "RPL213"}
    if kind == "ref":
        gp = rng.choice(d["groups"])
        gp["inputs"][rng.randrange(len(gp["inputs"]))] = rng.choice(
            [["input", "no_such"], ["group", 999, 0], ["wat"], []])
        return {"RPL202"}
    raise AssertionError(kind)


if HAVE_HYPOTHESIS:
    @given(st.sampled_from(_KINDS), st.integers(0, 2**32 - 1))
    @settings(max_examples=40, deadline=None)
    def test_fuzz_random_mutants_rejected(kind, seed):
        import random
        d, g = _fixture("GEMVER", mode="unfused")
        expected = _mutate(d, kind, random.Random(seed))
        if expected is None:
            return
        _reject(d, g, expected)
else:
    @pytest.mark.skip(reason="hypothesis not installed (optional dev "
                      "dependency); deterministic mutants still run")
    def test_fuzz_random_mutants_rejected():
        pass
