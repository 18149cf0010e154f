"""Trace reduction and per-layer metric arithmetic of the benchmark
(``bench/trace.py``, ``bench/metrics``), on a synthetic trace."""
import pathlib
import sys

import pytest

sys.path.insert(0, str(pathlib.Path(__file__).resolve().parents[2]))

from bench import metrics  # noqa: E402
from bench import trace as tr  # noqa: E402

MS = 1e6  # ns


def synthetic() -> tr.Trace:
    """Window 0-100 ms; device 0 busy 10-30 (two overlapping ops and one
    nested in them), 50-60 and 95-110 (clipped at 100); device 1 busy
    0-50; device 2 idle in the window.  Host spans: call 0-40, drain
    40-70, wait 70-100."""
    ops = {
        "/device:TPU:0": [("fusion", 10 * MS, 25 * MS),
                          ("kernel", 20 * MS, 30 * MS),
                          ("copy", 12 * MS, 14 * MS),
                          ("kernel", 50 * MS, 60 * MS),
                          ("fusion", 95 * MS, 110 * MS),
                          ("before", -20 * MS, -10 * MS)],
        "/device:TPU:1": [("kernel", 0, 50 * MS)],
        # a chip of the host the run did not use
        "/device:TPU:2": [("before", -20 * MS, -10 * MS)],
    }
    spans = [("window", 0, 100 * MS), ("call", 0, 40 * MS),
             ("drain", 40 * MS, 70 * MS), ("wait", 70 * MS, 100 * MS)]
    return tr.Trace(ops, spans)


def test_union_merges_overlaps_and_clips():
    got = tr.union([(5, 8), (0, 2), (1, 3), (7, 12), (20, 30)], 0, 25)
    assert got == [(0, 3), (5, 12), (20, 25)]
    assert tr.gaps(got, 0, 30) == [(3, 5), (12, 20), (25, 30)]


def test_busy_is_the_union_of_every_op_in_the_window():
    t = synthetic()
    busy = tr.busy_s(t)
    assert busy["/device:TPU:0"] == pytest.approx(0.020 + 0.010 + 0.005)
    assert busy["/device:TPU:1"] == pytest.approx(0.050)
    assert tr.op_count(t) == {"/device:TPU:0": 5, "/device:TPU:1": 1,
                              "/device:TPU:2": 0}


def test_reduce_averages_devices_and_names_idle_gaps_by_host_span():
    out = tr.reduce(synthetic())
    assert out["window_s"] == pytest.approx(0.1)
    assert out["busy_s"] == pytest.approx((0.035 + 0.050) / 2)
    assert out["idle_share"] == pytest.approx(1 - 0.0425 / 0.1)
    assert out["ops"] == 6
    # device 0 gaps: 0-10 call, 30-50 (mid 40) drain, 60-95 (mid 77.5)
    # wait; device 1: 50-100 (mid 75) wait
    idle = dict(out["breakdown"]["idle_gaps"])
    assert idle["call"] == pytest.approx(0.010 / 2)
    assert idle["drain"] == pytest.approx(0.020 / 2)
    assert idle["wait"] == pytest.approx((0.035 + 0.050) / 2)
    ops = out["breakdown"]["device_ops"]
    assert ops[0] == ["kernel", pytest.approx(0.070)]
    assert dict(ops)["fusion"] == pytest.approx(0.015 + 0.005)
    assert "before" not in dict(ops)


def test_op_names_keep_the_result_and_the_operation():
    hlo = ("%program_b3.4 = (f32[16384,16384]{1,0:T(8,128)}, f32[128,128]"
           "{1,0:T(8,128)S(1)}) custom-call(f32[16384,16384]{1,0:T(8,128)} "
           "%input_vals_0_.1), custom_call_target=\"tpu_custom_call\"")
    assert tr.op_name(hlo) == "%program_b3.4 custom-call"
    assert tr.op_name("%copy-start = (f32[1,16]{1,0:T(1,128)}) copy-start("
                      "f32[1,16]{1,0} %p.5)") == "%copy-start copy-start"
    assert tr.op_name("fusion") == "fusion"


def test_reduce_refuses_a_trace_without_a_device():
    t = synthetic()
    t.ops = {}
    with pytest.raises(ValueError, match="no TPU"):
        tr.reduce(t)
    t.spans = t.spans[1:]
    with pytest.raises(ValueError, match="window"):
        t.window()


def call_facts(trace):
    return {"call": {"calls": 10, "required_bytes": 2.0e9, "flops": 1.0e9,
                     "impls": [{"traffic_bytes": 2.0e9, "t_pred": 2e-3},
                               {"traffic_bytes": 1.0e9, "t_pred": 1e-3}]},
            "plan_s": 0.25, "trace": trace,
            "peak": {"hbm_bytes_per_s": 1e12, "flops_per_s": 1e14}}


def test_call_metrics_from_facts():
    trace = {"busy_s": 0.05, "window_s": 0.1, "idle_share": 0.5, "ops": 40}
    f = call_facts(trace)
    # 10 calls, 5 ms busy each; the call needs 2 ms of HBM time
    assert metrics.read("call_roofline", f) == pytest.approx(40.0)
    assert metrics.read("call_mfu", f) == pytest.approx(100 * 1e10 / 0.1 / 1e14)
    assert metrics.read("compiler.traffic_ratio", f) == pytest.approx(1.5)
    assert metrics.read("predictor.pred_over_busy", f) == pytest.approx(0.6)
    assert metrics.read("codegen.kernels_per_call", f) == pytest.approx(4.0)
    assert metrics.read("compiler.plan_s", f) == 0.25
    assert metrics.read("device.idle_share.call", f) == 0.5
    # with no trace, the device metrics find nothing to read
    assert metrics.read("call_roofline", call_facts(None)) is None
    assert metrics.read("device.idle_share.call", call_facts(None)) is None
