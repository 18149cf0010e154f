"""Empirical autotune mode (DESIGN.md §8): measured-cost search,
hardware calibration, the measured-cost cache layer, mode validation
and the cache-routed ``compile_all``."""
import json
import math
import os
import subprocess
import sys

import numpy as np
import pytest

from repro.blas import elementary_lib as lib
from repro.core import (FusionCompiler, HardwareModel, PlanCache,
                        autotune_combination, bandwidth_sweep,
                        best_combination, build_plan, calibrate_hardware,
                        codegen, enumerate_combinations, graph_signature,
                        measure_group, measure_program, synthetic_inputs)
from repro.core import autotune as autotune_mod
from repro.programs import BLAS, make_inputs

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _tuned_compiler(cache, budget=3):
    """Small-budget, short-measurement compiler for fast tests."""
    return FusionCompiler(cache=cache, autotune_budget=budget,
                          autotune_reps=1, autotune_warmup=1)


# ---------------------------------------------------------------------------
# hardware calibration
# ---------------------------------------------------------------------------

class TestCalibration:
    def test_constants_finite_positive(self):
        hw = calibrate_hardware()
        assert isinstance(hw, HardwareModel)
        assert hw.name.startswith("calibrated_")
        for v in (hw.peak_flops, hw.hbm_bw, hw.launch_overhead_s,
                  hw.f32_scale):
            assert math.isfinite(v) and v > 0, hw
        # policy constants are not measured
        assert hw.min_tile == HardwareModel().min_tile
        assert hw.vmem_bytes == HardwareModel().vmem_bytes

    def test_memoized_per_platform(self):
        assert calibrate_hardware() is calibrate_hardware()

    def test_classmethod_and_compiler_string(self):
        hw = HardwareModel.calibrate()
        assert hw is calibrate_hardware()
        cc = FusionCompiler(hw="calibrate", cache=None)
        assert cc.hw is hw

    def test_unknown_hw_string_rejected(self):
        with pytest.raises(ValueError, match="calibrate"):
            FusionCompiler(hw="cpu", cache=None)

    def test_constants_stable_for_cache_keys(self):
        """Calibrated constants are rounded to 2 significant figures so
        repr(hw) — which feeds compiler cache keys — has no excess
        precision that run-to-run jitter would perturb."""
        hw = calibrate_hardware()
        for v in (hw.peak_flops, hw.hbm_bw, hw.launch_overhead_s):
            assert float(f"{v:.1e}") == v, v

    def test_calibration_adopts_first_published_record(self, tmp_path,
                                                       monkeypatch):
        """A process that loses the publish race (here: forced to
        re-measure against a store that already has a record) adopts
        the first-written constants — plan keys stay fleet-aligned."""
        import hashlib

        import jax
        cache = PlanCache(disk_dir=str(tmp_path))
        dev = jax.devices()[0]
        key = hashlib.sha256(repr(
            ("calibration", jax.default_backend(),
             getattr(dev, "device_kind", "?"),
             jax.__version__)).encode()).hexdigest()
        cache.put_measurement(key, {
            "kind": "calibration", "name": "calibrated_other",
            "peak_flops": 1.0e11, "hbm_bw": 5.0e9,
            "launch_overhead_s": 1.0e-5})
        monkeypatch.setattr(autotune_mod, "_CALIBRATED", {})
        hw = calibrate_hardware(force=True, cache=cache)
        assert (hw.name, hw.peak_flops, hw.hbm_bw, hw.launch_overhead_s) \
            == ("calibrated_other", 1.0e11, 5.0e9, 1.0e-5)

    def test_calibration_shared_through_cache(self, tmp_path, monkeypatch):
        """A process sharing the cache dir adopts the published
        calibration record instead of re-measuring, so its
        HardwareModel — and hence its plan-cache keys — are identical
        to the first calibrator's."""
        cache = PlanCache(disk_dir=str(tmp_path))
        hw1 = calibrate_hardware(force=True, cache=cache)
        assert cache.stats.meas_writes == 1
        # a "fresh process": empty memo, fresh cache on the same dir
        monkeypatch.setattr(autotune_mod, "_CALIBRATED", {})
        c2 = PlanCache(disk_dir=str(tmp_path))
        hw2 = calibrate_hardware(cache=c2)
        assert hw2 == hw1
        assert c2.stats.meas_disk_hits == 1 and c2.stats.meas_writes == 0


# ---------------------------------------------------------------------------
# the measured-cost search
# ---------------------------------------------------------------------------

class TestMeasuredSearch:
    @pytest.mark.parametrize("name", list(BLAS))
    def test_winner_never_slower_than_best_plan(self, name):
        """Acceptance criterion: the autotuned plan's measured runtime
        is <= the ``mode='best'`` plan's on every BLAS sequence.
        Candidate 0 of the predicted-order stream IS the best plan, and
        the winner is the measured argmin over a set containing it, so
        this holds within a single measurement pass by construction —
        the assert locks the construction."""
        seq = BLAS[name]
        cc = _tuned_compiler(cache=None)
        g = cc.trace(seq.script, seq.shapes(128))
        space = cc.space(g)
        combo, plan, report = autotune_combination(
            space, hw=cc.hw, budget=3, reps=1, warmup=1)
        assert report.candidates[0].t_pred == pytest.approx(
            best_combination(space).t_pred, abs=1e-15)
        assert report.winner.t_meas <= report.candidates[0].t_meas
        assert combo.t_pred == pytest.approx(
            report.winner.t_pred, abs=1e-15)
        assert report.measured_speedup >= 1.0
        # the winner covers the whole graph
        covered = sorted(i for im in combo.impls for i in im.fusion.key)
        assert covered == list(range(len(g.calls)))

    @pytest.mark.parametrize("name", ["AXPYDOT", "GEMVER", "BiCGK"])
    def test_autotune_mode_numerics(self, name):
        seq = BLAS[name]
        cc = _tuned_compiler(cache=PlanCache())
        prog = cc.compile(seq.script, seq.shapes(256), mode="autotune")
        assert cc.last_autotune is not None
        inputs = make_inputs(seq, 256, seed=3)
        out = prog(**inputs)
        out = out if isinstance(out, tuple) else (out,)
        for o, r in zip(out, seq.reference(**inputs)):
            np.testing.assert_allclose(np.asarray(o), r,
                                       rtol=1e-4, atol=1e-3)

    def test_candidates_never_compiled_whole(self, monkeypatch):
        """Per-group autotune times groups in isolation — it never
        compiles candidate whole-programs.  ``codegen.compile_plan``
        runs exactly once per autotune compile: for the winner."""
        from repro.core import codegen
        calls = {"n": 0}
        real = codegen.compile_plan

        def counting(*a, **k):
            calls["n"] += 1
            return real(*a, **k)

        monkeypatch.setattr(codegen, "compile_plan", counting)
        seq = BLAS["BiCGK"]
        cc = _tuned_compiler(PlanCache())
        prog = cc.compile(seq.script, seq.shapes(256), mode="autotune")
        assert calls["n"] == 1
        inputs = make_inputs(seq, 256, seed=7)
        out = prog(**inputs)
        for o, r in zip(out, seq.reference(**inputs)):
            np.testing.assert_allclose(np.asarray(o), r,
                                       rtol=1e-4, atol=1e-3)

    def test_report_candidates_in_predicted_order(self):
        seq = BLAS["GEMVER"]
        cc = _tuned_compiler(cache=None, budget=4)
        g = cc.trace(seq.script, seq.shapes(128))
        space = cc.space(g)
        _, _, report = autotune_combination(space, budget=4, reps=1)
        preds = [c.t_pred for c in report.candidates]
        assert preds == sorted(preds)
        assert [c.rank_pred for c in report.candidates] == list(
            range(len(preds)))
        # every candidate is accounted for; at least the first needed a
        # fresh timing (a later one may be fully covered by groups the
        # earlier candidates measured — the mix-and-match transfer)
        assert report.n_measured + report.n_cached == len(report.candidates)
        assert report.n_measured >= 1
        assert report.n_groups_measured >= 1
        for c in report.candidates:
            assert c.n_groups >= 1
            assert 0 <= c.n_groups_cached <= c.n_groups


# ---------------------------------------------------------------------------
# measured-cost cache layer
# ---------------------------------------------------------------------------

class TestMeasuredCostCache:
    def test_second_autotune_compile_measures_nothing(self, monkeypatch):
        """Acceptance criterion: a second autotune compile of the same
        program performs zero measurements (plan-layer hit)."""
        cache = PlanCache()
        seq = BLAS["BiCGK"]
        _tuned_compiler(cache).compile(seq.script, seq.shapes(256),
                                       mode="autotune")

        def boom(*a, **k):
            raise AssertionError("measured on a warm cache")

        monkeypatch.setattr(autotune_mod, "measure_program", boom)
        monkeypatch.setattr(autotune_mod, "measure_callable", boom)
        # a *different* compiler instance: program layer still keys the
        # same request; the plan layer covers even a program-key miss
        _tuned_compiler(cache).compile(seq.script, seq.shapes(256),
                                       mode="autotune")
        assert cache.stats.plan_hits + cache.stats.program_hits >= 1

    def test_disk_measurements_reused_across_compilers(self, tmp_path,
                                                       monkeypatch):
        """Per-group disk records are reused by a fresh compiler +
        fresh cache: with the plan entries gone, the autotune search
        re-runs but every group is served from the measured-cost
        table — zero new measurements (``group_table_hit_rate == 1.0``,
        the PR acceptance gate)."""
        seq = BLAS["GEMVER"]
        c1 = PlanCache(disk_dir=str(tmp_path))
        _tuned_compiler(c1).compile(seq.script, seq.shapes(256),
                                    mode="autotune")
        n_rec = c1.stats.meas_writes          # one write per fused group
        assert n_rec >= 2
        meas_files = [f for f in os.listdir(tmp_path)
                      if f.endswith(".meas.json")]
        assert len(meas_files) == n_rec
        for f in meas_files:
            rec = json.loads((tmp_path / f).read_text())
            assert rec["kind"] == "group"
            assert rec["t_meas"] > 0 and math.isfinite(rec["t_meas"])
            assert rec["traffic_bytes"] > 0 and rec["flops"] >= 0
        # drop the plans so the search itself must re-run
        for f in os.listdir(tmp_path):
            if f.endswith(".plan.json"):
                os.unlink(tmp_path / f)

        def boom(*a, **k):
            raise AssertionError("re-measured a cached group")

        monkeypatch.setattr(autotune_mod, "measure_program", boom)
        monkeypatch.setattr(autotune_mod, "measure_callable", boom)
        c2 = PlanCache(disk_dir=str(tmp_path))
        cc2 = _tuned_compiler(c2)
        prog = cc2.compile(seq.script, seq.shapes(256), mode="autotune")
        assert cc2.last_autotune.group_table_hit_rate == 1.0
        assert cc2.last_autotune.n_groups_measured == 0
        assert c2.stats.meas_disk_hits == n_rec
        assert c2.stats.meas_writes == 0
        inputs = make_inputs(seq, 256, seed=5)
        out = prog(**inputs)
        for o, r in zip(out, seq.reference(**inputs)):
            np.testing.assert_allclose(np.asarray(o), r,
                                       rtol=1e-4, atol=1e-3)

    def test_bigger_budget_measures_only_new_candidates(self, tmp_path,
                                                        monkeypatch):
        """The budget is a cache-key component (deeper search != shallow
        search), but measurements are shared per candidate — growing
        the budget re-measures nothing already in the table."""
        seq = BLAS["GEMVER"]
        cache = PlanCache(disk_dir=str(tmp_path))
        _tuned_compiler(cache, budget=2).compile(
            seq.script, seq.shapes(256), mode="autotune")
        n_rec = cache.stats.meas_writes       # groups of candidates 0..1
        assert n_rec >= 2

        calls = {"n": 0}
        real = autotune_mod.measure_callable

        def counting(*a, **k):
            calls["n"] += 1
            return real(*a, **k)

        monkeypatch.setattr(autotune_mod, "measure_callable", counting)
        cc4 = _tuned_compiler(cache, budget=4)
        cc4.compile(seq.script, seq.shapes(256), mode="autotune")
        rep = cc4.last_autotune
        assert rep is not None                        # plan key differs
        assert rep.n_cached >= 2       # candidates 0..1 fully table-served
        assert calls["n"] == rep.n_groups_measured    # only new groups
        assert rep.n_groups_cached >= n_rec
        assert cache.stats.meas_writes == n_rec + rep.n_groups_measured

    def test_wrong_schema_dict_entry_healed(self, tmp_path):
        """Regression: a dict record missing a finite t_meas (schema
        drift) must not crash the search or poison its key — it is
        dropped and re-measured once."""
        seq = BLAS["VADD"]
        cache = PlanCache(disk_dir=str(tmp_path))
        _tuned_compiler(cache, budget=2).compile(
            seq.script, seq.shapes(256), mode="autotune")
        n_rec = cache.stats.meas_writes
        # corrupt every measurement into valid-JSON wrong-shape dicts
        for f in os.listdir(tmp_path):
            if f.endswith(".meas.json"):
                (tmp_path / f).write_text('{"schema": 2}')
            elif f.endswith(".plan.json"):
                os.unlink(tmp_path / f)
        c2 = PlanCache(disk_dir=str(tmp_path))
        cc2 = _tuned_compiler(c2, budget=2)
        cc2.compile(seq.script, seq.shapes(256), mode="autotune")
        rep = cc2.last_autotune
        assert rep.n_measured == len(rep.candidates)   # healed, re-measured
        assert rep.n_groups_cached == 0
        assert c2.stats.meas_writes == n_rec           # republished
        for f in os.listdir(tmp_path):
            if f.endswith(".meas.json"):
                rec = json.loads((tmp_path / f).read_text())
                assert rec["kind"] == "group" and rec["t_meas"] > 0

    def test_non_dict_disk_entry_dropped_and_republished(self, tmp_path):
        """Regression: a valid-JSON but non-dict .meas.json must be
        unlinked on read (like a corrupt one), or first-writer-wins
        would keep the bad file and the key would re-measure forever
        fleet-wide."""
        cache = PlanCache(disk_dir=str(tmp_path))
        path = tmp_path / "deadbeef.meas.json"
        path.write_text("[1, 2, 3]")               # parses, wrong shape
        assert cache.get_measurement("deadbeef") is None
        assert not path.exists()
        cache.put_measurement("deadbeef", {"t_meas": 1e-6})
        assert cache.stats.meas_writes == 1        # republished
        c2 = PlanCache(disk_dir=str(tmp_path))
        assert c2.get_measurement("deadbeef")["t_meas"] == 1e-6

    def test_autotune_budget_in_config_key(self):
        cc2 = _tuned_compiler(None, budget=2)
        cc4 = _tuned_compiler(None, budget=4)
        assert (cc2._config_key("jnp", cc2._mode_key("autotune"))
                != cc4._config_key("jnp", cc4._mode_key("autotune")))
        # non-autotune modes are budget-independent (plans still shared)
        assert (cc2._config_key("jnp", cc2._mode_key("best"))
                == cc4._config_key("jnp", cc4._mode_key("best")))

    def test_legacy_program_records_still_serve(self, monkeypatch):
        """Schema coexistence (DESIGN.md §8): whole-program records
        written by the previous table schema (no ``kind`` field) still
        serve program-level lookups exactly — a candidate they cover is
        never re-measured, and the report says where its time came
        from."""
        seq = BLAS["VADD"]
        cc = _tuned_compiler(cache=None, budget=2)
        g = cc.trace(seq.script, seq.shapes(256))
        space = cc.space(g)
        combos = enumerate_combinations(space, limit=2)
        cache = PlanCache()
        fp = autotune_mod.hw_fingerprint(cc.backend, cc.interpret)
        sig = graph_signature(g)
        for i, combo in enumerate(combos):
            plan = build_plan(g, combo, backend=cc.backend)
            mk = autotune_mod.measurement_key(
                sig, autotune_mod.combination_key(plan), fp)
            cache.put_measurement(
                mk, {"t_meas": (i + 1) * 1e-6, "reps": 1, "warmup": 1})

        def boom(*a, **k):
            raise AssertionError("measured despite legacy program records")

        monkeypatch.setattr(autotune_mod, "measure_program", boom)
        monkeypatch.setattr(autotune_mod, "measure_callable", boom)
        _, _, report = autotune_combination(
            space, hw=cc.hw, backend=cc.backend, interpret=cc.interpret,
            cache=cache, budget=2, reps=1)
        assert all(c.from_cache and c.source == "program"
                   for c in report.candidates)
        assert report.n_measured == 0
        assert report.winner_index == 0        # legacy 1e-6 < 2e-6
        assert report.winner.t_meas == pytest.approx(1e-6)

    def test_group_records_filter_other_kinds(self, tmp_path):
        """All three record generations share one measurement namespace
        (one cache dir); ``group_records`` — the refit training set —
        must return only the per-group generation."""
        cache = PlanCache(disk_dir=str(tmp_path))
        cache.put_measurement("aaa", {"t_meas": 1e-6, "reps": 1,
                                      "warmup": 1})       # legacy program
        cache.put_measurement("bbb", {"kind": "calibration",
                                      "name": "calibrated_x",
                                      "peak_flops": 1e11, "hbm_bw": 5e9,
                                      "launch_overhead_s": 1e-5})
        grec = {"kind": "group", "t_meas": 2e-6, "sig": "s",
                "traffic_bytes": 100, "flops": 10}
        cache.put_measurement("ccc", grec)
        recs = cache.group_records()
        assert recs == [grec]
        # a fresh cache on the same dir sees only the disk copy, and
        # enumeration is read-only (all three files still present)
        assert PlanCache(disk_dir=str(tmp_path)).group_records() == [grec]
        files = [f for f in os.listdir(tmp_path)
                 if f.endswith(".meas.json")]
        assert len(files) == 3


# ---------------------------------------------------------------------------
# differential oracle: per-group sums vs whole-program ground truth
# ---------------------------------------------------------------------------

class TestDifferentialOracle:
    #: stated tolerance — the sum of per-group timings and the
    #: whole-program timing must agree within this factor.  The two
    #: disagree by (a) XLA optimizing across group boundaries when the
    #: whole program jits as one executable and (b) residual per-call
    #: dispatch cost, both bounded well inside 4x once sizes are large
    #: enough that streaming compute dominates dispatch (the sizes
    #: below put >= ~1MB of traffic in every group).
    TOL = 4.0

    @pytest.mark.parametrize("name,n", [
        ("AXPYDOT", 1 << 20), ("BiCGK", 768), ("GEMVER", 768)])
    def test_sum_of_group_times_tracks_whole_program(self, name, n):
        seq = BLAS[name]
        cc = FusionCompiler(cache=None)
        g = cc.trace(seq.script, seq.shapes(n))
        space = cc.space(g)
        combo = best_combination(space)
        plan = build_plan(g, combo, backend=cc.backend)
        prog = codegen.compile_plan(g, plan, hw=cc.hw,
                                    interpret=cc.interpret)
        t_whole = measure_program(prog, synthetic_inputs(g),
                                  reps=3, inner=4)
        t_sum = sum(measure_group(g, im, backend=cc.backend,
                                  interpret=cc.interpret, reps=3, inner=4)
                    for im in combo.impls)
        assert t_whole > 0 and t_sum > 0
        ratio = t_sum / t_whole
        assert 1 / self.TOL < ratio < self.TOL, (
            f"{name}: sum-of-groups {t_sum*1e6:.1f}us vs whole "
            f"{t_whole*1e6:.1f}us (ratio {ratio:.2f})")


# ---------------------------------------------------------------------------
# cross-program transfer: the point of localized group signatures
# ---------------------------------------------------------------------------

def _chain_script(g, a, b, c, s):
    """Structurally AXPYDOT's chain (axmy -> ew_mul -> sum_reduce) under
    different input/output names, traced as a different program."""
    t = g.apply(lib.axmy, s, a, b, name="t")
    m = g.apply(lib.ew_mul, t, c)
    rr = g.apply(lib.sum_reduce, m, name="rr")
    return t, rr


class TestGroupTransfer:
    def test_group_records_transfer_across_programs(self, monkeypatch):
        """A group table populated by AXPYDOT serves a *different*
        program sharing the same fused chain: zero new measurements
        (localized signatures make group records program-independent)."""
        n = 256
        cache = PlanCache()
        seq = BLAS["AXPYDOT"]
        cc = _tuned_compiler(cache)
        cc.compile(seq.script, seq.shapes(n), mode="autotune")
        assert len(cache.group_records()) >= 1

        def boom(*a, **k):
            raise AssertionError("measured: group table should transfer")

        monkeypatch.setattr(autotune_mod, "measure_program", boom)
        monkeypatch.setattr(autotune_mod, "measure_callable", boom)
        cc2 = _tuned_compiler(cache)
        g2 = cc2.trace(_chain_script,
                       {"a": (n,), "b": (n,), "c": (n,), "s": ()})
        # a genuinely different program (graph signatures differ: input
        # names are the call ABI) ...
        g1 = cc.trace(seq.script, seq.shapes(n))
        assert graph_signature(g2) != graph_signature(g1)
        # ... yet every group is served from AXPYDOT's table
        _, _, report = autotune_combination(
            cc2.space(g2), hw=cc2.hw, backend=cc2.backend,
            interpret=cc2.interpret, cache=cache, budget=3, reps=1)
        assert report.n_groups_measured == 0
        assert report.group_table_hit_rate == 1.0
        assert report.n_groups_cached >= 1
        assert all(c.from_cache and c.source == "groups"
                   for c in report.candidates)


# ---------------------------------------------------------------------------
# calibration bandwidth sweep (DESIGN.md §8)
# ---------------------------------------------------------------------------

class TestBandwidthSweep:
    def test_sweep_finite_positive_stably_keyed(self):
        sizes = (1 << 14, 1 << 15, 1 << 16)
        s1 = bandwidth_sweep(reps=1, sizes=sizes)
        # keys derive from sizes alone (bytes moved: read + write), so
        # two sweeps key identically even though values jitter
        assert sorted(s1) == [2 * 4 * n for n in sizes]
        for bw in s1.values():
            assert math.isfinite(bw) and bw > 0
        s2 = bandwidth_sweep(reps=1, sizes=sizes)
        assert sorted(s2) == sorted(s1)

    def test_default_sweep_has_at_least_three_sizes(self):
        assert len(autotune_mod.BW_SWEEP_SIZES) >= 3

    def test_calibration_record_carries_sweep(self, tmp_path, monkeypatch):
        """The published calibration record embeds the per-size sweep
        (string byte-count keys — JSON-stable), so a fleet can audit
        the roofline fit its constants came from."""
        monkeypatch.setattr(autotune_mod, "_CALIBRATED", {})
        cache = PlanCache(disk_dir=str(tmp_path))
        hw = calibrate_hardware(force=True, cache=cache)
        assert math.isfinite(hw.hbm_bw) and hw.hbm_bw > 0
        files = [f for f in os.listdir(tmp_path)
                 if f.endswith(".meas.json")]
        assert len(files) == 1
        rec = json.loads((tmp_path / files[0]).read_text())
        assert rec["kind"] == "calibration"
        sweep = rec["bw_sweep"]
        assert len(sweep) >= 3
        assert list(sweep) == sorted(sweep, key=int)
        for k, v in sweep.items():
            assert k == str(int(k))
            assert math.isfinite(v) and v > 0


AUTOTUNE_WARM_SCRIPT = """
import json
from repro.programs import BLAS
from repro.core import FusionCompiler, PlanCache

cache = PlanCache()   # REPRO_PLAN_CACHE_DIR from the environment
cc = FusionCompiler(cache=cache, autotune_budget=2, autotune_reps=1,
                    autotune_warmup=1)
for name in ("AXPYDOT", "VADD"):
    seq = BLAS[name]
    cc.compile(seq.script, seq.shapes(64), mode="autotune")
print(json.dumps(cache.stats.as_dict()))
"""


def test_autotune_concurrent_writers(tmp_path, monkeypatch):
    """Two processes autotuning into one shared cache dir (the fleet
    case) leave a consistent store: every entry parses, no temp litter,
    and a fresh compiler autotunes from it with zero measurements."""
    d = str(tmp_path / "plans")
    env = dict(os.environ, REPRO_PLAN_CACHE_DIR=d)
    env["PYTHONPATH"] = os.path.join(REPO, "src") + (
        os.pathsep + env["PYTHONPATH"] if env.get("PYTHONPATH") else "")
    procs = [subprocess.Popen([sys.executable, "-c", AUTOTUNE_WARM_SCRIPT],
                              stdout=subprocess.PIPE,
                              stderr=subprocess.PIPE, text=True, env=env)
             for _ in range(2)]
    for p in procs:
        out, err = p.communicate(timeout=600)
        assert p.returncode == 0, err[-3000:]

    files = os.listdir(d)
    assert not [f for f in files if f.endswith(".tmp")], files
    meas = [f for f in files if f.endswith(".meas.json")]
    assert len(meas) >= 2
    for f in meas:
        rec = json.loads(open(os.path.join(d, f)).read())
        assert rec["t_meas"] > 0

    def boom(*a, **k):
        raise AssertionError("measured despite a warm fleet cache")

    monkeypatch.setattr(autotune_mod, "measure_program", boom)
    monkeypatch.setattr(autotune_mod, "measure_callable", boom)
    cache = PlanCache(disk_dir=d)
    cc = _tuned_compiler(cache, budget=2)
    for name in ("AXPYDOT", "VADD"):
        seq = BLAS[name]
        cc.compile(seq.script, seq.shapes(64), mode="autotune")
    assert cache.stats.disk_hits == 2          # plans from disk
    assert cache.stats.meas_writes == 0        # nothing re-measured


# ---------------------------------------------------------------------------
# batched / sharded wiring
# ---------------------------------------------------------------------------

class TestEngineWiring:
    def test_compile_batched_autotune_shares_plan(self, monkeypatch):
        """The batched path accepts mode='autotune' and shares the plan
        found by the unbatched path (identical plan keys)."""
        cache = PlanCache()
        cc = _tuned_compiler(cache)
        seq = BLAS["VADD"]
        cc.compile(seq.script, seq.shapes(256), mode="autotune")

        def boom(*a, **k):
            raise AssertionError("batched compile re-measured")

        monkeypatch.setattr(autotune_mod, "measure_program", boom)
        monkeypatch.setattr(autotune_mod, "measure_callable", boom)
        prog = cc.compile_batched(seq.script, seq.shapes(256),
                                  mode="autotune", max_batch=4)
        w, y, z = (np.random.default_rng(0)
                   .standard_normal((4, 256)).astype(np.float32)
                   for _ in range(3))
        out = prog(w=w, y=y, z=z)
        np.testing.assert_allclose(np.asarray(out), w + y + z,
                                   rtol=1e-5, atol=1e-5)

    def test_serving_engine_autotune_mode(self):
        from repro.serving import ServingEngine
        engine = ServingEngine(compiler=_tuned_compiler(PlanCache()),
                               max_batch=4, min_bucket=64, mode="autotune")
        engine.warm("AXPYDOT", [100], trace_batches=False)
        seq = BLAS["AXPYDOT"]
        engine.submit("AXPYDOT", 100, make_inputs(seq, 100, seed=1))
        (res,) = engine.drain()
        z, r = seq.reference(**make_inputs(seq, 100, seed=1))
        np.testing.assert_allclose(res.outputs[0], z, rtol=1e-4, atol=1e-4)
        np.testing.assert_allclose(res.outputs[1], r, rtol=1e-3, atol=1e-3)


# ---------------------------------------------------------------------------
# mode validation (bugfix: bools were integer combination indices)
# ---------------------------------------------------------------------------

class TestModeValidation:
    @pytest.mark.parametrize("bad", [True, False])
    def test_bool_mode_rejected(self, bad):
        cc = FusionCompiler(cache=None)
        seq = BLAS["VADD"]
        with pytest.raises(ValueError, match="valid modes.*best"):
            cc.compile(seq.script, seq.shapes(128), mode=bad)

    def test_unknown_string_mode_rejected(self):
        cc = FusionCompiler(cache=None)
        seq = BLAS["VADD"]
        with pytest.raises(ValueError,
                           match="'best', 'unfused', 'autotune'"):
            cc.compile(seq.script, seq.shapes(128), mode="bogus")

    def test_search_rejects_bool_directly(self):
        cc = FusionCompiler(cache=None)
        seq = BLAS["VADD"]
        space = cc.space(cc.trace(seq.script, seq.shapes(128)))
        with pytest.raises(ValueError, match="valid modes"):
            cc.search(space, True)

    def test_integer_modes_still_work(self):
        cc = FusionCompiler(cache=None)
        seq = BLAS["VADD"]
        prog = cc.compile(seq.script, seq.shapes(128), mode=1)
        inputs = make_inputs(seq, 128, seed=2)
        np.testing.assert_allclose(
            np.asarray(prog(**inputs)),
            seq.reference(**inputs)[0], rtol=1e-5, atol=1e-5)

    def test_out_of_range_and_negative_ranks_rejected(self):
        """Out-of-range ranks used to clamp to the last combination —
        silently, and caching a duplicate plan under the wrong key."""
        cc = FusionCompiler(cache=None)
        seq = BLAS["SSCAL"]                  # exactly 1 combination
        with pytest.raises(ValueError, match="out of range"):
            cc.compile(seq.script, seq.shapes(128), mode=5)
        with pytest.raises(ValueError, match=">= 0"):
            cc.compile(seq.script, seq.shapes(128), mode=-1)


# ---------------------------------------------------------------------------
# compile_all routed through the caches (bugfix: bypassed both layers)
# ---------------------------------------------------------------------------

class TestCompileAll:
    def test_records_stats_and_reuses_cache(self):
        cache = PlanCache()
        cc = FusionCompiler(cache=cache)
        seq = BLAS["GEMVER"]       # n=256: >= 4 legal combinations
        res1 = cc.compile_all(seq.script, seq.shapes(256), limit=4)
        assert len(res1) == 4
        assert cache.stats.plan_misses == 4      # visible to telemetry
        ts = [c.t_pred for c, _ in res1]
        assert ts == sorted(ts)

        res2 = cc.compile_all(seq.script, seq.shapes(256), limit=4)
        assert cache.stats.program_hits == 4     # fully served from cache
        assert [c.t_pred for c, _ in res2] == ts
        assert all(p2 is p1 for (_, p1), (_, p2) in zip(res1, res2))

    def test_shares_keys_with_integer_mode_compile(self):
        cache = PlanCache()
        cc = FusionCompiler(cache=cache)
        seq = BLAS["BiCGK"]
        res = cc.compile_all(seq.script, seq.shapes(128), limit=3)
        before = cache.stats.program_hits
        prog = cc.compile(seq.script, seq.shapes(128), mode=1)
        assert cache.stats.program_hits == before + 1
        assert prog is res[1][1]

    def test_truncates_at_space_size(self):
        cc = FusionCompiler(cache=PlanCache())
        seq = BLAS["SSCAL"]                  # tiny space
        res = cc.compile_all(seq.script, seq.shapes(128), limit=50)
        n = len(res)
        assert 0 < n < 50
        # warm pass returns the same truncated list, still cache-served
        assert len(cc.compile_all(seq.script, seq.shapes(128),
                                  limit=50)) == n

    def test_programs_run(self):
        cc = FusionCompiler(cache=PlanCache())
        seq = BLAS["AXPYDOT"]
        res = cc.compile_all(seq.script, seq.shapes(128), limit=3)
        inputs = make_inputs(seq, 128, seed=4)
        want = seq.reference(**inputs)
        for combo, prog in res:
            out = prog(**inputs)
            for o, r in zip(out, want):
                np.testing.assert_allclose(np.asarray(o), r,
                                           rtol=1e-4, atol=1e-3)
