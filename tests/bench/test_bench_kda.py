"""The ``kda.call`` cell (Kimi Linear's KDA decode step) run whole on the
CPU at a size a test can hold, with Pallas kernels in the interpreter: a
sound run comes out correct; the control, and a step that loses one
block of rows' state write-back, do not.  Also its blocked reference and
its roofline reader."""
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
from bench.refs import kda_decode_step  # noqa: E402

SPEC = run.read_json("BENCHMARK.json")
#: rows (sessions x heads) of the small runs
N = 64
#: input sets of the small runs: each set's last call in the window is
#: compared, and a 0.5 s window on a loaded CPU may end before a 20th set
SETS = 2
#: rows whose new state a faulty step never writes back: one grid step
#: of 8 rows
BLOCK = 8


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
    cell, config, mix, limits = copy.deepcopy(run.load_cell(SPEC, "kda.call"))
    mix["n"], mix["input_sets"] = N, SETS
    return run.run_cell(SPEC, "kda.call", config, mix, limits, 2**31 + 7,
                        0.5, False, time.perf_counter(), swap)


def lost_write_back(program, ref):
    """The step as the program computes it, but the last ``BLOCK`` rows
    keep their old state: one block's write-back of ``S_new`` lost."""
    def call(**inputs):
        S_new, o = program(**inputs)
        return S_new.at[-BLOCK:].set(inputs["S"][-BLOCK:]), o
    return call


def test_kda_cell_runs_correct_and_reports_its_metrics(interpret):
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


def test_a_lost_state_write_back_is_not_correct(interpret):
    res = run_small(swap=lost_write_back)
    assert not res["correct"]
    assert res["checks"]["max_err"]["value"] > res["checks"]["max_err"]["limit"]


def test_blocked_reference_agrees_with_the_plain_formula(monkeypatch):
    """The copy in ``bench/refs`` (rows in blocks of ``refs.ROWS``, cut
    to 32 here) computes the program's float64 reference, at a size
    spanning blocks, the last one partial."""
    from repro.programs import REGISTRY, make_inputs
    monkeypatch.setattr(kda_decode_step, "ROWS", 32)
    prog = REGISTRY["KDA_DECODE_STEP"]
    ref = refs.load("KDA_DECODE_STEP")
    n = 2 * 32 + 16
    assert prog.shapes(n) == refs.shapes(ref.INPUTS, n)
    inputs = make_inputs(prog, n, seed=7)
    f64 = {k: np.asarray(v, np.float64) for k, v in inputs.items()}
    for got, want in zip(ref.reference(**inputs), prog.reference(**f64)):
        np.testing.assert_allclose(got, want, rtol=1e-12, atol=1e-12)
    assert ref.flops(n) == pytest.approx(prog.flops(n))


PEAK = {"hbm_bytes_per_s": 819e9, "flops_per_s": 197e12}


def facts(ops, calls=10):
    return {"call": {"calls": calls, "required_bytes": 819e6, "flops": 0.0},
            "trace": {"busy_s": 1.0, "window_s": 1.0,
                      "breakdown": {"device_ops": ops, "idle_gaps": []}},
            "peak": PEAK}


def test_kda_roofline_reads_the_kda_kernels_alone():
    """819 MB at 819 GB/s is 1 ms a call; the two KDA kernels take 20 ms
    over 10 calls, 2 ms a call: 50 %.  The copy and the MLA kernel are
    not theirs."""
    ops = [["%g5_kda_snew_kda_o.1 custom-call", 0.015],
           ["%copy.2 copy", 0.5],
           ["%g0_mla_score.1 custom-call", 0.3],
           ["%g3_kda_u.1 custom-call", 0.005]]
    assert metrics.read("kernels.kda_roofline", facts(ops)) == \
        pytest.approx(50.0)


def test_kda_roofline_is_silent_without_kda_kernels():
    assert metrics.read("kernels.kda_roofline",
                        facts([["%g0_gemv.1 custom-call", 0.2]])) is None
    assert metrics.read("kernels.kda_roofline",
                        {"call": facts([])["call"], "trace": None,
                         "peak": PEAK}) is None
