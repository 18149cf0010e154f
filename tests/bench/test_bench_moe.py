"""The ``moe.call`` cell (DeepSeek-V2-Lite's routed experts) run whole on
the CPU at the published widths and a small n, with Pallas kernels in
the interpreter: a sound run comes out correct; the control, and experts
whose down projection drops one block of the expert width, do not.
Also its blocked reference and its roofline reader."""
import copy
import functools
import json
import pathlib
import sys
import time

import numpy as np
import pytest

ROOT = pathlib.Path(__file__).resolve().parents[2]
sys.path.insert(0, str(ROOT))

from bench import control, metrics, refs, run  # noqa: E402
from bench.refs import moe_experts  # noqa: E402

SPEC = run.read_json("BENCHMARK.json")
#: tokens per expert of the small runs
N = 16
#: input sets of the small runs: each set's last call in the window is
#: compared
SETS = 2
#: seconds of the small runs' window: an interpreted call takes about
#: 0.6 s, and the window has to reach the second set
SECONDS = 3.0
#: expert-width channels a faulty down projection never reads: one grid
#: step of the 128-wide blocks ``g0`` writes
BLOCK = 128


@pytest.fixture
def interpret(monkeypatch):
    """Pallas kernels in the interpreter: there is no chip here."""
    from repro.core import compiler
    init = compiler.FusionCompiler.__init__

    @functools.wraps(init)
    def interpreted(self, *a, **k):
        k.setdefault("interpret", True)
        init(self, *a, **k)
    monkeypatch.setattr(compiler.FusionCompiler, "__init__", interpreted)


def run_small(swap=None):
    cell, config, mix, limits = copy.deepcopy(run.load_cell(SPEC, "moe.call"))
    mix["n"], mix["input_sets"] = N, SETS
    return run.run_cell(SPEC, "moe.call", config, mix, limits, 2**31 + 7,
                        SECONDS, False, time.perf_counter(), swap)


def dropped_width_block(program, ref):
    """The experts as the program computes them, but the down
    projection never reads the last ``BLOCK`` channels of the expert
    width: one step of its reduction lost."""
    def call(w_down, **inputs):
        return program(w_down=w_down.at[..., -BLOCK:].set(0.0), **inputs)
    return call


def test_moe_cell_runs_correct_and_reports_its_metrics(interpret):
    res = run_small()
    assert res["correct"], res["checks"]
    assert res["failed"] == 0 and res["attempted"] >= SETS
    assert set(res["metrics"]) == {"setup_s", "call_ms"}
    assert set(res["checks"]) == {"max_err"}
    json.dumps(res, allow_nan=False)


def test_the_control_in_the_programs_place_is_not_correct(interpret):
    res = run_small(swap=control.control_swap)
    assert not res["correct"]
    assert res["checks"]["max_err"]["value"] > res["checks"]["max_err"]["limit"]


def test_a_dropped_width_block_is_not_correct(interpret):
    res = run_small(swap=dropped_width_block)
    assert not res["correct"]
    assert res["checks"]["max_err"]["value"] > res["checks"]["max_err"]["limit"]


def test_blocked_reference_agrees_with_the_plain_formula(monkeypatch):
    """The copy in ``bench/refs`` (experts in blocks of ``BLOCK``, 3
    here, the last block partial) computes the program's float64
    reference, at the published widths."""
    from repro.programs import REGISTRY, make_inputs
    monkeypatch.setattr(moe_experts, "BLOCK", 3)
    prog = REGISTRY["MOE_EXPERTS"]
    ref = refs.load("MOE_EXPERTS")
    n = 4
    assert prog.shapes(n) == refs.shapes(ref.INPUTS, n)
    inputs = make_inputs(prog, n, seed=7)
    f64 = {k: np.asarray(v, np.float64) for k, v in inputs.items()}
    g = np.einsum("ech,eih->eci", f64["x"], f64["w_gate"])
    u = np.einsum("ech,eih->eci", f64["x"], f64["w_up"])
    a = f64["r"][:, :, None] * g / (1.0 + np.exp(-g)) * u
    want = np.einsum("eci,edi->ecd", a, f64["w_down"])
    for got in (ref.reference(**inputs)[0], prog.reference(**f64)[0]):
        np.testing.assert_allclose(got, want, rtol=1e-12, atol=1e-9)
    assert ref.flops(n) == pytest.approx(prog.flops(n))


PEAK = {"hbm_bytes_per_s": 819e9, "flops_per_s": 197e12}


def facts(ops, calls=10):
    return {"call": {"calls": calls, "required_bytes": 819e6, "flops": 0.0},
            "trace": {"busy_s": 1.0, "window_s": 1.0,
                      "breakdown": {"device_ops": ops, "idle_gaps": []}},
            "peak": PEAK}


def test_moe_roofline_reads_the_moe_kernels_alone():
    """819 MB at 819 GB/s is 1 ms a call; the two expert kernels take 20
    ms over 10 calls, 2 ms a call: 50 %.  The copy and the KDA kernel
    are not theirs."""
    ops = [["%g0_moe_proj_moe_proj_moe_act.1 custom-call", 0.013],
           ["%copy.2 copy", 0.5],
           ["%g1_kda_sumsq_kda_knorm.1 custom-call", 0.3],
           ["%g1_moe_down.1 custom-call", 0.007]]
    assert metrics.read("kernels.moe_roofline", facts(ops)) == \
        pytest.approx(50.0)


def test_moe_roofline_is_silent_without_moe_kernels():
    assert metrics.read("kernels.moe_roofline",
                        facts([["%g0_gemv.1 custom-call", 0.2]])) is None
    assert metrics.read("kernels.moe_roofline",
                        {"call": facts([])["call"], "trace": None,
                         "peak": PEAK}) is None
