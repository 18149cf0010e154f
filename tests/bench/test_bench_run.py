"""Whole runs of the benchmark's cells on the CPU, at sizes a test can
hold, with the harness's look for a chip skipped (``run_cell`` is called
directly and Pallas kernels run in the interpreter): sound runs come out
correct; the control, and each fault a cell can have, come out not
correct."""
import copy
import functools
import json
import os
import pathlib
import shutil
import subprocess
import sys
import time

import numpy as np
import pytest

ROOT = pathlib.Path(__file__).resolve().parents[2]
sys.path.insert(0, str(ROOT))

from bench import control, run  # noqa: E402

SPEC = run.read_json("BENCHMARK.json")
SMALL_CALL = {"gemver.call": 256, "axpydot.call": 1 << 16}


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


def small_cell(workload: str):
    cell, config, mix, limits = copy.deepcopy(run.load_cell(SPEC, workload))
    mix["n"] = SMALL_CALL[workload]
    return config, mix, limits


def run_small(workload, swap=None, seconds=0.5, tracing=False):
    config, mix, limits = small_cell(workload)
    return run.run_cell(SPEC, workload, config, mix, limits, 2**31 + 7,
                        seconds, tracing, time.perf_counter(), swap)


@pytest.mark.parametrize("workload", ["gemver.call", "axpydot.call"])
def test_call_cell_runs_correct_and_reports_its_metrics(interpret, workload):
    res = run_small(workload)
    assert res["correct"], res["checks"]
    assert res["failed"] == 0 and res["attempted"] >= 2
    assert set(res["metrics"]) == {"setup_s", "call_ms"}
    assert list(res)[-1] == "checks"
    json.dumps(res, allow_nan=False)


@pytest.mark.parametrize("workload", ["gemver.call", "axpydot.call"])
def test_the_control_in_the_programs_place_is_not_correct(interpret, workload):
    """The control, the reference in bfloat16, fails the cell's limit."""
    res = run_small(workload, swap=control.control_swap)
    assert not res["correct"]
    assert res["checks"]["max_err"]["value"] > res["checks"]["max_err"]["limit"]


def altered_answer(program, ref):
    """A call whose first output (GEMVER's B, AXPYDOT's z) has one entry
    off by 1 % where it is made."""
    def call(**inputs):
        outs = list(program(**inputs))
        flat = outs[0].reshape(-1)
        outs[0] = flat.at[0].add(1e-2 * (1 + abs(flat[0]))).reshape(outs[0].shape)
        return tuple(outs)
    return call


@pytest.mark.parametrize("workload", ["gemver.call", "axpydot.call"])
def test_an_answer_altered_where_it_is_made_is_not_correct(interpret, workload):
    res = run_small(workload, swap=altered_answer)
    assert not res["correct"]


def test_a_reduce_that_drops_one_block_is_not_correct(interpret):
    """AXPYDOT's dot without one grid step's terms fails ``r_err`` while
    z, untouched, stays within ``max_err``."""
    sound = run_small("axpydot.call")["checks"]
    res = run_small("axpydot.call", swap=control.dropped_block)
    assert not res["correct"]
    assert res["checks"]["max_err"] == sound["max_err"]
    assert res["checks"]["r_err"]["value"] > res["checks"]["r_err"]["limit"]
    assert sound["r_err"]["value"] <= sound["r_err"]["limit"]


def test_each_number_compared_is_the_widest_gap_of_its_outputs():
    from bench.refs import axpydot, gemver
    limits = {"max_err": 1e-4, "r_err": 2e-4}
    errs = {"set0.z": 1e-6, "set1.z": 3e-6, "set0.r": 5e-4,
            "set1.r": float("inf")}
    checks = run.checks_of(axpydot, errs, limits)
    assert checks == {"max_err": {"value": 3e-6, "limit": 1e-4},
                      "r_err": {"value": 1e308, "limit": 2e-4}}
    checks = run.checks_of(gemver, {"set0.B": 2e-7, "set0.w": 4e-7}, limits)
    assert checks == {"max_err": {"value": 4e-7, "limit": 1e-4}}


def test_run_without_a_tpu_exits_nonzero_with_no_result():
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    r = subprocess.run([sys.executable, str(ROOT / "bench" / "run.py"),
                        "--workload", "gemver.call", "--seed", "1",
                        "--seconds", "1", "--trace", "0"],
                       capture_output=True, text=True, env=env, timeout=300)
    assert r.returncode != 0
    assert r.stdout.strip() == ""
    assert "TPU" in r.stderr


def test_run_with_only_the_benchmark_exits_nonzero(tmp_path):
    spec = json.loads((ROOT / "BENCHMARK.json").read_text())
    shutil.copy(ROOT / "BENCHMARK.json", tmp_path)
    for p in spec["paths"]:
        shutil.copytree(ROOT / p, tmp_path / p,
                        ignore=shutil.ignore_patterns("__pycache__"))
    env = {k: v for k, v in os.environ.items() if k != "PYTHONPATH"}
    env["JAX_PLATFORMS"] = "cpu"
    r = subprocess.run([sys.executable, *spec["command"][1:],
                        "--workload", "gemver.call", "--seed", "1",
                        "--seconds", "1", "--trace", "0"],
                       capture_output=True, text=True, env=env, timeout=300,
                       cwd=tmp_path)
    assert r.returncode != 0
    assert r.stdout.strip() == ""
    assert np.all([not ln.startswith("{") for ln in r.stdout.splitlines()])
