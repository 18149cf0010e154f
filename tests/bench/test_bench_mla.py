"""The ``mla.call`` cell (DeepSeek-V2-Lite latent attention at decode) run
whole on the CPU at a size a test can hold, with Pallas kernels in the
interpreter: a sound run comes out correct; the control, and a value sum
that drops one block of the cache, do not.  Also its blocked reference
and its roofline reader."""
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

SPEC = run.read_json("BENCHMARK.json")
#: cache positions of the small runs: 16 blocks of 128
N = 2048
#: input sets of the small runs: each set's last call in the window is
#: compared, and a 0.5 s window on a loaded CPU may end before the 27th
#: set is reached (interpreted calls take 10-30 ms)
SETS = 3
#: rows of the cache a faulty value sum leaves out: one grid step of 128
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
    cell, config, mix, limits = copy.deepcopy(run.load_cell(SPEC, "mla.call"))
    mix["n"], mix["input_sets"] = N, SETS
    return run.run_cell(SPEC, "mla.call", config, mix, limits, 2**31 + 7,
                        0.5, False, time.perf_counter(), swap)


def dropped_cache_block(program, ref):
    """MLA as the program computes it, but its weighted sum of latent
    rows without the last ``BLOCK`` cache positions: a value reduce that
    skips one grid step."""
    import jax
    import jax.numpy as jnp

    def call(q_lat, q_rope, ckv, kr):
        o_lat = program(q_lat=q_lat, q_rope=q_rope, ckv=ckv, kr=kr)
        dot = functools.partial(jnp.dot, precision="highest")
        p = jax.nn.softmax(ref.SCALE * (dot(q_lat, ckv.T) + dot(q_rope, kr.T)),
                           axis=-1)
        return o_lat - dot(p[:, -BLOCK:], ckv[-BLOCK:])
    return call


def test_mla_cell_runs_correct_and_reports_its_metrics(interpret):
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


def test_a_value_sum_that_drops_one_cache_block_is_not_correct(interpret):
    res = run_small(swap=dropped_cache_block)
    assert not res["correct"]
    assert res["checks"]["max_err"]["value"] > res["checks"]["max_err"]["limit"]


def test_blocked_reference_agrees_with_the_plain_formula():
    """The copy in ``bench/refs`` (cache rows in blocks of ``refs.ROWS``)
    computes the program's float64 reference, at a size spanning
    blocks."""
    from repro.programs import REGISTRY, make_inputs
    prog = REGISTRY["MLA_DECODE_ATTN"]
    ref = refs.load("MLA_DECODE_ATTN")
    n = 2 * refs.ROWS + 256
    assert prog.shapes(n) == refs.shapes(ref.INPUTS, n)
    assert ref.SCALE == pytest.approx(0.1147214, abs=5e-8)
    inputs = make_inputs(prog, n, seed=7)
    f64 = {k: np.asarray(v, np.float64) for k, v in inputs.items()}
    (got,), (want,) = ref.reference(**inputs), prog.reference(**f64)
    np.testing.assert_allclose(got, want, rtol=1e-12, atol=1e-12)
    assert ref.flops(n) == pytest.approx(prog.flops(n))


PEAK = {"hbm_bytes_per_s": 819e9, "flops_per_s": 197e12}


def facts(ops, calls=10):
    return {"call": {"calls": calls, "required_bytes": 819e6, "flops": 0.0},
            "trace": {"busy_s": 1.0, "window_s": 1.0,
                      "breakdown": {"device_ops": ops, "idle_gaps": []}},
            "peak": PEAK}


def test_mla_roofline_reads_the_mla_kernels_alone():
    """819 MB at 819 GB/s is 1 ms a call; the two MLA kernels take 40 ms
    over 10 calls, 4 ms a call: 25 %.  The copy is XLA's, not theirs."""
    ops = [["%g0_mla_score.1 custom-call", 0.03],
           ["%copy.2 copy", 0.5],
           ["%g3_mla_value.1 custom-call", 0.01]]
    assert metrics.read("kernels.mla_roofline", facts(ops)) == \
        pytest.approx(25.0)


def test_mla_roofline_is_silent_without_mla_kernels():
    assert metrics.read("kernels.mla_roofline",
                        facts([["%g0_gemv.1 custom-call", 0.2]])) is None
    assert metrics.read("kernels.mla_roofline",
                        {"call": facts([])["call"], "trace": None,
                         "peak": PEAK}) is None
