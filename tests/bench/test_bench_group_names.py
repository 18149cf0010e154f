"""The benchmark's trace reduction over the names the program gives its
work (``repro.core.tracing``): each fused group's kernel keeps its label
in ``breakdown.device_ops``, and no per-layer metric depends on what
the operations or the host spans are called."""
import pathlib
import sys

import pytest

sys.path.insert(0, str(pathlib.Path(__file__).resolve().parents[2]))

from bench import metrics  # noqa: E402
from bench import trace as tr  # noqa: E402

MS = 1e6  # ns

#: XLA Ops event names of one call of GEMVER at n=16384 in a TPU v5e
#: trace (operands abridged), with the names the breakdown gives them
V5E_EVENTS = [
    ("%g0_rank2_update_gemtv.1 = (f32[16384,16384]{1,0:T(8,128)}, "
     "f32[128,128]{1,0:T(8,128)S(1)}) custom-call(f32[16384,16384]"
     "{1,0:T(8,128)} %input_vals_0_.1, f32[1,16384]{1,0:T(1,128)} "
     "%bitcast.16), custom_call_target=\"tpu_custom_call\"",
     "%g0_rank2_update_gemtv.1 custom-call"),
    ("%g1_xpay.1 = f32[1,16384]{1,0:T(1,128)S(1)} custom-call(f32[1,1]"
     "{1,0:T(1,128)} %bitcast.13, f32[1,16384]{1,0:T(1,128)S(1)} "
     "%bitcast.22), custom_call_target=\"tpu_custom_call\"",
     "%g1_xpay.1 custom-call"),
    ("%copy-start = (f32[1,16384]{1,0:T(1,128)}, f32[1,16384]"
     "{1,0:T(1,128)S(1)}, u32[]{:S(2)}) copy-start(f32[1,16384]"
     "{1,0:T(1,128)S(1)} %g1_xpay.1)",
     "%copy-start copy-start"),
    ("%g2_gemv.1 = f32[128,128]{1,0:T(8,128)S(1)} custom-call(f32[16384,"
     "16384]{1,0:T(8,128)} %pallas_call.7, f32[1,16384]{1,0:T(1,128)S(1)} "
     "%g1_xpay.1), custom_call_target=\"tpu_custom_call\"",
     "%g2_gemv.1 custom-call"),
    ("%g3_scal.1 = f32[1,16384]{1,0:T(1,128)} custom-call(f32[1,1]"
     "{1,0:T(1,128)} %bitcast.12, f32[1,16384]{1,0:T(1,128)S(1)} "
     "%bitcast.23), custom_call_target=\"tpu_custom_call\"",
     "%g3_scal.1 custom-call"),
    ("%copy-done = f32[1,16384]{1,0:T(1,128)} copy-done((f32[1,16384]"
     "{1,0:T(1,128)}, f32[1,16384]{1,0:T(1,128)S(1)}, u32[]{:S(2)}) "
     "%copy-start)",
     "%copy-done copy-done"),
]
#: the same call as the program named it before it labelled its groups
UNLABELLED = ["%program_b3161db4.4 = custom-call(%a)",
              "%program_b3161db4.5 = custom-call(%a)",
              "%copy-start = copy-start(%a)",
              "%program_b3161db4.6 = custom-call(%a)",
              "%program_b3161db4.7 = custom-call(%a)",
              "%copy-done = copy-done(%a)"]
#: device intervals (ms) of each event of one call, repeated every 6 ms
SLOTS = [(0.0, 3.3), (3.3, 3.32), (3.32, 3.321), (3.321, 4.8),
         (4.8, 4.82), (4.82, 4.821)]
METRICS = ["compiler.plan_s", "compiler.traffic_ratio",
           "predictor.pred_over_busy", "codegen.kernels_per_call",
           "call_roofline", "call_mfu", "device.idle_share.call"]


def calls_trace(names, dispatch: bool) -> tr.Trace:
    """Ten calls of 6 ms in a 60 ms window; with ``dispatch``, a
    ``repro.dispatch`` span opens each ``call`` span and nests in it."""
    ops, spans = [], [("window", 0, 60 * MS)]
    for k in range(10):
        t = 6 * k
        ops += [(n, (t + s + 0.5) * MS, (t + e + 0.5) * MS)
                for n, (s, e) in zip(names, SLOTS)]
        spans.append(("call", t * MS, (t + 6) * MS))
        if dispatch:
            spans.append(("repro.dispatch", (t + 0.01) * MS,
                          (t + 0.35) * MS))
    return tr.Trace({"/device:TPU:0": ops}, spans)


def facts(trace: tr.Trace) -> dict:
    return {"call": {"calls": 10, "required_bytes": 2.148e9, "flops": 1.6e9,
                     "impls": [{"traffic_bytes": 2.148e9, "t_pred": 2.6e-3},
                               {"traffic_bytes": 1.074e9, "t_pred": 1.3e-3}]},
            "plan_s": 0.05, "trace": tr.reduce(trace),
            "peak": {"hbm_bytes_per_s": 819e9, "flops_per_s": 197e12}}


@pytest.mark.parametrize("event,name", V5E_EVENTS,
                         ids=[n.split()[0] for _, n in V5E_EVENTS])
def test_breakdown_names_each_group_kernel_by_its_label(event, name):
    assert tr.op_name(event) == name


def test_breakdown_lists_every_group_by_label():
    out = tr.reduce(calls_trace([e for e, _ in V5E_EVENTS], True))
    names = [n for n, _ in out["breakdown"]["device_ops"]]
    assert names[:2] == ["%g0_rank2_update_gemtv.1 custom-call",
                         "%g2_gemv.1 custom-call"]
    assert sorted(names) == sorted(n for _, n in V5E_EVENTS)
    assert not any("program_" in n for n in names)


@pytest.mark.parametrize("name", METRICS)
def test_metrics_read_the_same_whatever_the_names(name):
    before = metrics.read(name, facts(calls_trace(UNLABELLED, False)))
    after = metrics.read(name, facts(calls_trace(
        [e for e, _ in V5E_EVENTS], True)))
    assert before is not None
    assert after == before
