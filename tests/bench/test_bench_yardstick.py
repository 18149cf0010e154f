"""The benchmark's yardstick: required bytes, peaks, references, seeded
traffic and the layout of ``BENCHMARK.json`` (``bench/``)."""
import json
import pathlib
import sys

import numpy as np
import pytest

ROOT = pathlib.Path(__file__).resolve().parents[2]
sys.path.insert(0, str(ROOT))

from bench import bytes as req  # noqa: E402
from bench import peaks, refs, traffic  # noqa: E402


def test_required_bytes_are_the_same_for_two_plans_of_gemver():
    """Two plans that move different bytes by the predictor's count
    require the same bytes: the yardstick reads the call, not the plan."""
    from repro.core import FusionCompiler
    from repro.programs import REGISTRY, make_inputs
    prog, n = REGISTRY["GEMVER"], 256
    inputs = make_inputs(prog, n, seed=3)
    cc = FusionCompiler(backend="jnp", cache=None)
    counts, traffic = [], []
    for mode in ("best", "unfused"):
        compiled = cc.compile(prog.script, prog.shapes(n), mode=mode)
        traffic.append(sum(i.traffic_bytes for i in compiled.group_impls))
        counts.append(req.required_bytes(inputs, compiled(**inputs)))
    assert traffic[0] != traffic[1]
    assert counts[0] == counts[1] == 4 * (2 * n * n + 8 * n + 2)


def test_roofline_takes_the_larger_bound():
    peak = {"hbm_bytes_per_s": 1e12, "flops_per_s": 1e14}
    assert req.roofline_s(2e9, 1e9, peak) == (pytest.approx(2e-3), "hbm")
    assert req.roofline_s(1e6, 1e12, peak) == (pytest.approx(1e-2), "flops")


def test_peaks_are_keyed_by_device_kind_and_unknown_kinds_fail():
    v5e = peaks.peak("TPU v5 lite")
    assert v5e["hbm_bytes_per_s"] == 819e9 and v5e["flops_per_s"] == 197e12
    with pytest.raises(KeyError, match="no peaks"):
        peaks.peak("cpu")


@pytest.mark.parametrize("program", ["GEMVER", "AXPYDOT"])
def test_blocked_reference_agrees_with_the_plain_formula(program):
    """The copy in ``bench/refs`` (row blocks of ``refs.ROWS``) computes
    the program's plain numpy reference, at a size spanning blocks."""
    from repro.programs import REGISTRY, make_inputs
    prog = REGISTRY[program]
    ref = refs.load(program)
    n = 2 * refs.ROWS + 256
    assert prog.shapes(n) == refs.shapes(ref.INPUTS, n)
    inputs = make_inputs(prog, n, seed=7)
    f64 = {k: np.asarray(v, np.float64) for k, v in inputs.items()}
    for got, want in zip(ref.reference(**inputs), prog.reference(**f64)):
        np.testing.assert_allclose(got, want, rtol=1e-12, atol=1e-9)


def test_rel_err_reads_the_widest_gap_and_infinity_for_bad_answers():
    ref = np.arange(1.0, 3 * refs.ROWS + 1).reshape(3, refs.ROWS)
    got = ref.astype(np.float32)
    got[2, 5] += 3.0
    assert refs.rel_err(got, ref) == pytest.approx(3.0 / ref.max())
    assert refs.rel_err(ref[0], ref[0]) == 0.0
    got[1, 1] = np.nan
    assert refs.rel_err(got, ref) == float("inf")
    assert refs.rel_err(got[:2], ref) == float("inf")


def test_axpydot_compares_its_dot_in_units_of_its_terms():
    """r's gap is read against the 2-norm of its terms z_i u_i, not |r|,
    which cancellation can make as small as it likes, and is a number of
    its own; z is read against max |z|."""
    from bench.refs import axpydot
    n = 1000
    inputs = {"w": np.ones(n, np.float32), "v": np.zeros(n, np.float32),
              "u": np.tile(np.float32([1, -1]), n // 2),
              "alpha": np.float32(1)}
    want = axpydot.reference(**inputs)
    assert want[1] == 0.0
    errs = refs.errors(axpydot, (want[0], np.float32(0.5)), inputs, want)
    assert errs == {"z": 0.0, "r": pytest.approx(0.5 / np.sqrt(n))}
    assert refs.check_of(axpydot, "r") == "r_err"
    assert refs.check_of(axpydot, "z") == "max_err"


def test_a_dropped_block_reads_the_same_at_any_n():
    """The scale of r makes a reduce that leaves out one block of 128
    terms read |N(0, 1)| sqrt(128 / n) at every n (the lost terms have
    random signs), far over a float32 dot's rounding."""
    from bench.refs import axpydot
    for n in (1 << 12, 1 << 16, 1 << 20):
        dropped, rounded = [], []
        for seed in range(16):
            r = np.random.default_rng([n, seed])
            inputs = {k: r.standard_normal(n).astype(np.float32)
                      for k in ("w", "v", "u")}
            inputs["alpha"] = np.float32(0.75)
            want = axpydot.reference(**inputs)
            u = inputs["u"].astype(np.float64)
            lost = np.dot(want[0][-128:], u[-128:])
            got32 = np.dot(want[0].astype(np.float32), inputs["u"])
            dropped.append(refs.errors(axpydot, (want[0], want[1] - lost),
                                       inputs, want)["r"])
            rounded.append(refs.errors(axpydot, (want[0], got32),
                                       inputs, want)["r"])
        # the median of |N(0, 1)| is 0.674
        assert 0.4 < np.median(dropped) / np.sqrt(128 / n) < 1.0
        assert max(rounded) < np.median(dropped) / 30


def test_device_inputs_repeat_for_a_seed_and_keep_large_seeds_apart():
    from bench.refs import axpydot
    shapes = refs.shapes(axpydot.INPUTS, 256)
    big = 2**33 + 7
    call = traffic.kind("call")
    a, d = call.device_inputs(shapes, big, 2)
    (b,) = call.device_inputs(shapes, big, 1)
    (c,) = call.device_inputs(shapes, 7, 1)
    for name in shapes:
        np.testing.assert_array_equal(np.asarray(a[name]), np.asarray(b[name]))
        assert not np.array_equal(np.asarray(a[name]), np.asarray(c[name]))
        assert not np.array_equal(np.asarray(a[name]), np.asarray(d[name]))
    assert 0.5 <= float(a["alpha"]) < 1.5 and a["w"].dtype == np.float32


def test_benchmark_json_names_a_file_for_every_part():
    """Each cell's configuration, traffic mix, the mix's kind and the
    cell's limits, and each per-layer metric's reader, is a file found by
    its name; the limits are those of the numbers its reference compares."""
    spec = json.loads((ROOT / "BENCHMARK.json").read_text())
    configs = {c["name"]: c for c in spec["configs"]}
    for cell in spec["workloads"]:
        cfg = json.loads((ROOT / configs[cell["config"]]["file"]).read_text())
        mix = json.loads((ROOT / "bench" / "traffic"
                          / f"{cell['traffic']}.json").read_text())
        assert hasattr(traffic.kind(mix["kind"]), "run")
        limits = json.loads((ROOT / "bench" / "limits"
                             / f"{cell['name']}.json").read_text())
        ref = refs.load(cfg["program"])
        assert set(limits) == {refs.check_of(ref, o) for o in ref.OUTPUTS}
    for m in spec["per_layer"]:
        assert (ROOT / "bench" / "metrics" / f"{m['name']}.py").is_file()
    for c in spec["configs"]:
        cfg = json.loads((ROOT / c["file"]).read_text())
        assert cfg["source"] == c["source"] and cfg["reduced"] == c["reduced"]
        assert (ROOT / cfg["reference"]).is_file()


def test_an_unknown_traffic_kind_is_an_error():
    with pytest.raises(KeyError, match="no traffic kind"):
        traffic.kind("no_such_kind")


def test_call_ms_by_tenth_splits_the_windows_calls():
    call = traffic.kind("call")
    ends = np.cumsum([0.002] * 10 + [0.004] * 10)
    got = call.tenths_ms(ends.tolist())
    assert got == pytest.approx([2.0] * 5 + [4.0] * 5)
