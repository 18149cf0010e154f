"""The plan-based compilation pipeline: DP search equivalence and
scaling, ExecutionPlan serialization/rebinding, the plan/kernel cache,
and the whole-program jit runtime (DESIGN.md §3–§5)."""
import time

import numpy as np
import pytest

from repro.blas import make_synthetic_chain
from repro.core import (FusionCompiler, PlanCache, build_plan, build_space,
                        codegen, exhaustive_best_combination, graph_signature,
                        scheduler, trace)
from repro.core.plan import ExecutionPlan
from repro.core.predictor import V5E
from repro.programs import BLAS, make_inputs


def _space(name, n=256):
    seq = BLAS[name]
    g = trace(seq.script, seq.shapes(n))
    return g, build_space(g)


# ---------------------------------------------------------------------------
# DP search (DESIGN.md §3)
# ---------------------------------------------------------------------------

class TestDPSearch:
    @pytest.mark.parametrize("name", list(BLAS))
    def test_dp_matches_exhaustive(self, name):
        """The bitmask DP finds exactly the exhaustive optimum on every
        seed sequence (acceptance criterion)."""
        _, space = _space(name)
        dp = scheduler.best_combination(space)
        ex = exhaustive_best_combination(space)
        assert dp.t_pred == pytest.approx(ex.t_pred, rel=0, abs=1e-15)
        covered = sorted(i for im in dp.impls for i in im.fusion.key)
        assert covered == list(range(len(space.graph.calls)))

    @pytest.mark.parametrize("name", ["BiCGK", "GEMVER", "AXPYDOT"])
    def test_beam_matches_on_small_graphs(self, name):
        """Forcing the beam regime on small graphs still finds the
        optimum (wide-enough beam == exact)."""
        _, space = _space(name)
        beam = scheduler.best_combination(space, exact_threshold=0)
        ex = exhaustive_best_combination(space)
        assert beam.t_pred == pytest.approx(ex.t_pred, rel=0, abs=1e-15)

    def test_enumeration_sorted_and_starts_at_best(self):
        _, space = _space("GEMVER")
        combos = scheduler.enumerate_combinations(space, limit=50)
        ts = [c.t_pred for c in combos]
        assert ts == sorted(ts)
        assert ts[0] == pytest.approx(
            scheduler.best_combination(space).t_pred, abs=1e-15)
        # no duplicates: (partition, impl choice) pairs are unique
        seen = set()
        for c in combos:
            key = tuple((tuple(sorted(im.fusion.key)), im.order, im.blocks)
                        for im in c.impls)
            assert key not in seen
            seen.add(key)

    def test_enumeration_prefix_is_stable(self):
        """Asking for k best yields the same prefix as asking for k+m."""
        _, space = _space("GESUMMV")
        a = scheduler.enumerate_combinations(space, limit=5)
        b = scheduler.enumerate_combinations(space, limit=15)
        assert [c.t_pred for c in a] == [c.t_pred for c in b[:5]]

    def test_scales_to_20_plus_calls(self):
        """A ≥20-call graph — infeasible for the seed's exhaustive DFS
        (hundreds of thousands of partitions) — searches in < 5 s
        (acceptance criterion)."""
        script, shapes, _ = make_synthetic_chain(22)
        g = trace(script, shapes(512))
        assert len(g.calls) >= 20
        t0 = time.perf_counter()
        space = build_space(g)
        combo = scheduler.best_combination(space)
        elapsed = time.perf_counter() - t0
        assert elapsed < 5.0, f"search took {elapsed:.1f}s"
        covered = sorted(i for im in combo.impls for i in im.fusion.key)
        assert covered == list(range(len(g.calls)))

    def test_synthetic_chain_numerics(self):
        script, shapes, reference = make_synthetic_chain(21)
        cc = FusionCompiler(cache=None)
        prog = cc.compile(script, shapes(256))
        rng = np.random.default_rng(0)
        inputs = {k: (rng.standard_normal(v) * 0.1).astype(np.float32)
                  for k, v in shapes(256).items()}
        got = prog(**inputs)
        want = reference(**inputs)[0]
        np.testing.assert_allclose(np.asarray(got), want, rtol=1e-4, atol=1e-4)


# ---------------------------------------------------------------------------
# ExecutionPlan (DESIGN.md §4)
# ---------------------------------------------------------------------------

class TestExecutionPlan:
    @pytest.mark.parametrize("name", ["BiCGK", "GEMVER", "AXPYDOT", "SGEMVT"])
    def test_json_roundtrip_and_rebind(self, name):
        g, space = _space(name)
        combo = scheduler.best_combination(space)
        plan = build_plan(g, combo, backend="jnp")
        plan2 = ExecutionPlan.from_json(plan.to_json())
        assert plan2 == plan

        # rebind against a FRESH trace of the same script (the disk-cache
        # cold-process path) and check numerics against the oracle
        seq = BLAS[name]
        g2 = trace(seq.script, seq.shapes(256))
        assert graph_signature(g2) == plan.signature
        prog = codegen.compile_plan(g2, plan2, hw=V5E)
        inputs = make_inputs(seq, 256, seed=7)
        out = prog(**inputs)
        out = out if isinstance(out, tuple) else (out,)
        for o, r in zip(out, seq.reference(**inputs)):
            np.testing.assert_allclose(np.asarray(o), r, rtol=1e-4, atol=1e-3)

    def test_rebound_impls_match_search(self):
        g, space = _space("GEMVER")
        combo = scheduler.best_combination(space)
        plan = build_plan(g, combo, backend="jnp")
        impls = plan.bind(g, V5E)
        assert sum(i.t_pred for i in impls) == pytest.approx(combo.t_pred)

    def test_signature_distinguishes_shapes_and_dtypes(self):
        seq = BLAS["BiCGK"]
        s1 = graph_signature(trace(seq.script, seq.shapes(256)))
        s2 = graph_signature(trace(seq.script, seq.shapes(512)))
        s3 = graph_signature(trace(seq.script, seq.shapes(256),
                                   dtype=np.float64))
        assert len({s1, s2, s3}) == 3
        # deterministic across traces
        assert s1 == graph_signature(trace(seq.script, seq.shapes(256)))


# ---------------------------------------------------------------------------
# plan/kernel cache (DESIGN.md §5)
# ---------------------------------------------------------------------------

class TestCache:
    def test_second_compile_is_cached_no_research(self, monkeypatch):
        """Acceptance criterion: a second identical compile never
        re-traces or re-searches."""
        cache = PlanCache()
        cc = FusionCompiler(cache=cache)
        seq = BLAS["BiCGK"]
        p1 = cc.compile(seq.script, seq.shapes(512))

        def boom(*a, **k):
            raise AssertionError("search ran on a cached compile")

        monkeypatch.setattr(scheduler, "best_combination", boom)
        monkeypatch.setattr(cc, "trace", boom)
        p2 = cc.compile(seq.script, seq.shapes(512))
        assert p2 is p1
        assert cache.stats.program_hits == 1

    def test_key_miss_on_different_shape_mode_backend(self):
        cache = PlanCache()
        cc = FusionCompiler(cache=cache)
        seq = BLAS["BiCGK"]
        cc.compile(seq.script, seq.shapes(256))
        cc.compile(seq.script, seq.shapes(512))            # shape miss
        cc.compile(seq.script, seq.shapes(256), mode="unfused")  # mode miss
        assert cache.stats.program_hits == 0
        assert cache.stats.program_misses == 3

    def test_plan_layer_shared_across_compilers(self):
        """Two compiler instances sharing a cache: the second skips the
        search via the plan layer even though its program layer entry
        was populated by the first (same keys)."""
        cache = PlanCache()
        seq = BLAS["GEMVER"]
        FusionCompiler(cache=cache).compile(seq.script, seq.shapes(256))
        FusionCompiler(cache=cache).compile(seq.script, seq.shapes(256))
        assert cache.stats.program_hits == 1
        assert cache.stats.plan_misses == 1

    def test_disk_layer_cold_process(self, tmp_path, monkeypatch):
        """A cold process (empty in-memory cache, same disk dir) loads
        the plan from disk and never searches."""
        seq = BLAS["GEMVER"]
        c1 = PlanCache(disk_dir=str(tmp_path))
        FusionCompiler(cache=c1).compile(seq.script, seq.shapes(256))
        assert c1.stats.disk_writes == 1

        c2 = PlanCache(disk_dir=str(tmp_path))
        cc2 = FusionCompiler(cache=c2)

        def boom(*a, **k):
            raise AssertionError("search ran despite disk plan cache")

        monkeypatch.setattr(scheduler, "best_combination", boom)
        prog = cc2.compile(seq.script, seq.shapes(256))
        assert c2.stats.disk_hits == 1
        inputs = make_inputs(seq, 256, seed=2)
        out = prog(**inputs)
        for o, r in zip(out, seq.reference(**inputs)):
            np.testing.assert_allclose(np.asarray(o), r, rtol=1e-4, atol=1e-3)

    def test_lru_eviction(self):
        cache = PlanCache(capacity=2)
        cache.put_program("a", 1)
        cache.put_program("b", 2)
        cache.put_program("c", 3)           # evicts "a"
        assert cache.get_program("a") is None
        assert cache.get_program("b") == 2
        assert cache.get_program("c") == 3

    def test_unstable_closure_skips_program_layer(self):
        """A script closing over an object with only an identity repr
        (address-reuse aliasing risk) must not be served from the
        program cache — the plan layer (keyed on the actual trace)
        still works."""
        from repro.core.elementary import make_map

        class Opaque:            # default repr embeds the memory address
            pass

        def make_script(scale):
            op = make_map("scaled", lambda x: scale * x, arity=1)
            anchor = Opaque()

            def script(g, a):
                assert anchor is not None   # keep the opaque closure cell
                return (g.apply(op, a),)
            return script

        cache = PlanCache()
        cc = FusionCompiler(cache=cache)
        p1 = cc.compile(make_script(2.0), {"a": (256,)})
        p2 = cc.compile(make_script(3.0), {"a": (256,)})
        assert p2 is not p1
        assert cache.stats.program_hits == 0 and cache.stats.program_misses == 0
        x = np.arange(256, dtype=np.float32)
        np.testing.assert_allclose(np.asarray(p1(a=x)), 2.0 * x)
        np.testing.assert_allclose(np.asarray(p2(a=x)), 3.0 * x)

    def test_equal_closures_alias_one_program_entry(self):
        """Structural closure fingerprints: two closures built at
        different addresses but capturing equal content (nested
        function, container, dataclass) must hash to ONE program-cache
        entry — while a nested closure capturing a different value must
        not alias it."""
        import dataclasses

        from repro.core.elementary import make_map

        @dataclasses.dataclass
        class Cfg:
            gain: float
            tags: tuple

        def make_script(scale, bias):
            def shift(x):
                return x + bias              # nested closure cell
            cfg = Cfg(gain=scale, tags=("a", {"k": 1}))
            op = make_map("cfged", lambda x: cfg.gain * shift(x), arity=1)

            def script(g, a):
                return (g.apply(op, a),)
            return script

        cache = PlanCache()
        cc = FusionCompiler(cache=cache)
        p1 = cc.compile(make_script(2.0, 1.0), {"a": (256,)})
        p2 = cc.compile(make_script(2.0, 1.0), {"a": (256,)})
        assert p2 is p1                      # equal content -> one entry
        assert cache.stats.program_hits == 1
        # a nested closure cell with different CONTENT must miss (the
        # pre-structural fingerprint keyed functions on bytecode only,
        # which would alias these)
        p3 = cc.compile(make_script(2.0, 5.0), {"a": (256,)})
        assert p3 is not p1
        x = np.arange(256, dtype=np.float32)
        np.testing.assert_allclose(np.asarray(p1(a=x)), 2.0 * (x + 1.0))
        np.testing.assert_allclose(np.asarray(p3(a=x)), 2.0 * (x + 5.0))

    def test_cache_disabled(self):
        cc = FusionCompiler(cache=None)
        seq = BLAS["VADD"]
        p1 = cc.compile(seq.script, seq.shapes(256))
        p2 = cc.compile(seq.script, seq.shapes(256))
        assert p1 is not p2


# ---------------------------------------------------------------------------
# whole-program jit runtime (DESIGN.md §4)
# ---------------------------------------------------------------------------

class TestWholeProgramRuntime:
    def test_steady_state_is_one_dispatch(self):
        """After warmup, repeat calls never re-enter the per-group
        Python sub-functions — dispatch is a single jitted call
        (acceptance criterion)."""
        from repro.core.elementary import make_map
        calls = {"n": 0}

        def f_add(x, y):
            calls["n"] += 1
            return x + y

        add = make_map("counted_add", f_add, arity=2)

        def script(g, a, b):
            t = g.apply(add, a, b)
            return (g.apply(add, t, a),)

        cc = FusionCompiler(cache=None)
        prog = cc.compile(script, {"a": (256,), "b": (256,)})
        rng = np.random.default_rng(0)
        inputs = {k: rng.standard_normal(256).astype(np.float32)
                  for k in ("a", "b")}
        prog.block_until_ready(prog(**inputs))     # trace + compile
        traced = calls["n"]
        assert traced > 0
        for _ in range(5):
            prog.block_until_ready(prog(**inputs))
        assert calls["n"] == traced, "Python group loop ran on the hot path"

    def test_program_is_vmappable(self):
        """The program fn is pure/positional — batch it with vmap (the
        serving case)."""
        import jax
        seq = BLAS["VADD"]
        cc = FusionCompiler(cache=None)
        prog = cc.compile(seq.script, seq.shapes(128))
        batched = jax.vmap(lambda w, y, z: prog.fn(w, y, z))
        rng = np.random.default_rng(0)
        w, y, z = (rng.standard_normal((4, 128)).astype(np.float32)
                   for _ in range(3))
        (out,) = batched(w, y, z)
        np.testing.assert_allclose(np.asarray(out), w + y + z,
                                   rtol=1e-5, atol=1e-5)

    def test_block_until_ready_non_array_leaves(self):
        """Regression: tree-mapping block_until_ready over Python
        scalars must not crash."""
        seq = BLAS["AXPYDOT"]
        cc = FusionCompiler(cache=None)
        prog = cc.compile(seq.script, seq.shapes(256))
        out = prog(**make_inputs(seq, 256))
        got = prog.block_until_ready((out[0], 3.14, None, "x"))
        assert got[1] == 3.14 and got[3] == "x"

    def test_dtype_threaded(self):
        """Codegen no longer hardcodes float32: a float64 trace yields
        float64 outputs (jnp backend; x64 off truncates to f32 values
        but dtype plumbing is what's under test via the plan)."""
        seq = BLAS["VADD"]
        g = trace(seq.script, seq.shapes(128), dtype=np.float64)
        assert all(v.dtype == np.float64 for v in g.inputs)
        assert all(c.out.dtype == np.float64 for c in g.calls)
        space = build_space(g)
        combo = scheduler.best_combination(space)
        plan = build_plan(g, combo, backend="jnp")
        assert plan.dtype == "float64"
