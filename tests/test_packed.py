"""Cross-sequence packed dispatch (DESIGN.md §9): PackedPlan merging +
canonical order, packed codegen bitwise-equal to the unpacked batched
path (all BLAS sequences, reduce- and map-rooted mixes, single-
member packs, heterogeneous batch sizes), order-independent pack
caching (memory and disk), the engine's pack-aware drain (cold-member
fallback, pack warm, queue-wait telemetry), and the ``bucket_of``
``min_bucket`` validation."""
import numpy as np
import pytest

from repro.core import (FusionCompiler, PackedPlan, PlanCache,
                        build_packed_plan, build_plan, canonical_pack_order,
                        pack_signature, plan_fingerprint)
from repro.programs import BLAS, make_inputs
from repro.serving import ServingEngine, bucket_of

BUCKET = 128


def _engine(max_batch=4, max_pack=8, **kw):
    return ServingEngine(compiler=FusionCompiler(cache=PlanCache()),
                         max_batch=max_batch, min_bucket=64,
                         max_pack=max_pack, **kw)


def _members(names, n=BUCKET):
    return [(BLAS[nm].script, BLAS[nm].shapes(n)) for nm in names]


def _batched_inputs(name, n, batch, seed=0):
    return {k: np.stack([np.asarray(v) for v in vs]) for k, vs in
            {k: [make_inputs(BLAS[name], n, seed=seed + i)[k]
                 for i in range(batch)]
             for k in BLAS[name].shapes(n)}.items()}


# ---------------------------------------------------------------------------
# PackedPlan: canonical order, merging, serialization
# ---------------------------------------------------------------------------

class TestPackedPlan:
    def _plans(self, names):
        cc = FusionCompiler(cache=None)
        plans = []
        for nm in names:
            g = cc.trace(BLAS[nm].script, BLAS[nm].shapes(BUCKET))
            plans.append(build_plan(g, cc.search(cc.space(g), "best"),
                                    "jnp"))
        return plans

    def test_canonical_order_is_fingerprint_sorted(self):
        plans = self._plans(["VADD", "AXPYDOT", "WAXPBY"])
        order = canonical_pack_order(plans)
        fps = [plan_fingerprint(plans[i]) for i in order]
        assert fps == sorted(fps)
        packed = build_packed_plan(plans)
        assert [plan_fingerprint(p) for p in packed.members] == sorted(
            plan_fingerprint(p) for p in plans)

    def test_constructor_rejects_non_canonical_order(self):
        plans = self._plans(["VADD", "AXPYDOT"])
        packed = build_packed_plan(plans)
        if len({plan_fingerprint(p) for p in plans}) == 2:
            with pytest.raises(ValueError, match="canonical"):
                PackedPlan(members=tuple(reversed(packed.members)))

    def test_signature_order_independent(self):
        plans = self._plans(["VADD", "AXPYDOT", "SSCAL"])
        a = build_packed_plan(plans).signature
        b = build_packed_plan(list(reversed(plans))).signature
        assert a == b
        assert a == pack_signature([plan_fingerprint(p) for p in plans])

    def test_offsets_and_merged_routing(self):
        plans = self._plans(["AXPYDOT", "VADD"])
        packed = build_packed_plan(plans)
        assert packed.n_members == 2
        assert packed.n_inputs == sum(len(p.input_names)
                                      for p in packed.members)
        assert packed.n_outputs == sum(len(p.outputs)
                                       for p in packed.members)
        flat = packed.merged_groups()
        assert len(flat) == sum(len(p.groups) for p in packed.members)
        # every rebased input ref lands inside the global tables
        for m, gp in flat:
            for kind, *rest in gp.inputs:
                if kind == "input":
                    assert 0 <= rest[0] < packed.n_inputs
                else:
                    assert 0 <= rest[0] < len(flat)

    def test_json_round_trip(self):
        packed = build_packed_plan(self._plans(["AXPYDOT", "VADD", "SSCAL"]))
        back = PackedPlan.from_json(packed.to_json())
        assert back.signature == packed.signature
        assert back.to_json() == packed.to_json()
        assert "members" in packed.describe() or packed.describe()


# ---------------------------------------------------------------------------
# packed codegen: bitwise parity with the unpacked batched path
# ---------------------------------------------------------------------------

class TestPackedCodegen:
    def _parity(self, names, n=BUCKET, batches=None, max_batch=4):
        cc = FusionCompiler(cache=PlanCache())
        batches = batches or [2] * len(names)
        dispatch = cc.compile_packed(_members(names, n), max_batch=max_batch)
        member_inputs = [_batched_inputs(nm, n, b, seed=13 * i)
                         for i, (nm, b) in enumerate(zip(names, batches))]
        packed_outs = dispatch(member_inputs)
        for nm, inputs, outs in zip(names, member_inputs, packed_outs):
            seq = BLAS[nm]
            prog = cc.compile_batched(seq.script, seq.shapes(n),
                                      max_batch=max_batch)
            ref = prog(**inputs)
            if not isinstance(ref, tuple):
                ref = (ref,)
            assert len(outs) == len(ref)
            for o, r in zip(outs, ref):
                np.testing.assert_array_equal(np.asarray(o), np.asarray(r))

    def test_all_registry_sequences_bitwise_equal(self):
        """Every BLAS sequence, packed together, bit-for-bit the
        per-sequence batched dispatch."""
        self._parity(list(BLAS))

    def test_reduce_and_map_rooted_mix(self):
        # AXPYDOT/ATAX reduce-rooted, VADD/SSCAL map-rooted
        self._parity(["AXPYDOT", "VADD", "ATAX", "SSCAL"])

    def test_single_member_pack(self):
        self._parity(["GEMVER"])

    def test_heterogeneous_batch_sizes(self):
        self._parity(["AXPYDOT", "VADD", "WAXPBY"], batches=[4, 1, 2])

    def test_dispatch_unpermutes_to_caller_order(self):
        names = ["WAXPBY", "AXPYDOT"]
        cc = FusionCompiler(cache=PlanCache())
        dispatch = cc.compile_packed(_members(names))
        member_inputs = [_batched_inputs(nm, BUCKET, 2, seed=5 * i)
                         for i, nm in enumerate(names)]
        outs = dispatch(member_inputs)
        # WAXPBY has 1 output (w), AXPYDOT has 2 (z, r): caller order
        assert len(outs[0]) == 1 and len(outs[1]) == 2


# ---------------------------------------------------------------------------
# pack caching: order-independent program reuse, disk round-trip
# ---------------------------------------------------------------------------

class TestPackCache:
    def test_reordered_members_hit_program_cache(self):
        cc = FusionCompiler(cache=PlanCache())
        d1 = cc.compile_packed(_members(["AXPYDOT", "VADD", "SSCAL"]))
        hits0 = cc.cache.stats.program_hits
        d2 = cc.compile_packed(_members(["SSCAL", "AXPYDOT", "VADD"]))
        assert cc.cache.stats.program_hits == hits0 + 1
        assert d2.program is d1.program
        # and the reordered view still routes outputs to caller order
        a = _batched_inputs("AXPYDOT", BUCKET, 2, seed=1)
        v = _batched_inputs("VADD", BUCKET, 2, seed=2)
        s = _batched_inputs("SSCAL", BUCKET, 2, seed=3)
        o1 = d1([a, v, s])
        o2 = d2([s, a, v])
        for x, y in zip(o1[0], o2[1]):
            np.testing.assert_array_equal(np.asarray(x), np.asarray(y))

    def test_packed_plan_disk_cache(self, tmp_path):
        members = _members(["AXPYDOT", "VADD"])
        c1 = FusionCompiler(cache=PlanCache(disk_dir=str(tmp_path)))
        c1.compile_packed(members)
        assert c1.cache.stats.pack_writes >= 1
        assert list(tmp_path.glob("*.pack.json"))
        # a fresh process (new compiler, same disk dir) reloads the
        # merged pack without rebuilding it
        c2 = FusionCompiler(cache=PlanCache(disk_dir=str(tmp_path)))
        d = c2.compile_packed(members)
        assert c2.cache.stats.pack_disk_hits >= 1
        outs = d([_batched_inputs("AXPYDOT", BUCKET, 2),
                  _batched_inputs("VADD", BUCKET, 2)])
        assert len(outs) == 2


# ---------------------------------------------------------------------------
# engine: pack-aware drain
# ---------------------------------------------------------------------------

def _mixed_workload(names, n=100, per=4, seed=0):
    return [(nm, n, make_inputs(BLAS[nm], n, seed=seed + i))
            for i, nm in enumerate(names * per)]


def _assert_bitwise(results_a, results_b):
    """Every request's outputs identical, matched by rid."""
    a = {r.rid: r for r in results_a}
    b = {r.rid: r for r in results_b}
    assert set(a) == set(b)
    for rid in a:
        assert len(a[rid].outputs) == len(b[rid].outputs)
        for x, y in zip(a[rid].outputs, b[rid].outputs):
            np.testing.assert_array_equal(x, y)


class TestEnginePacking:
    def test_mixed_drain_bitwise_equals_unpacked_all_registry(self):
        """All 11 BLAS sequences mixed in one drain: the packed
        engine's outputs are bitwise those of a max_pack=1 engine."""
        names = list(BLAS)
        workload = _mixed_workload(names, per=2)
        packed = _engine(max_batch=2, max_pack=8)
        unpacked = _engine(max_batch=2, max_pack=1)
        for e in (packed, unpacked):        # warm so the drain packs
            for nm in names:
                e.warm(nm, [100], trace_batches=False, trace_packs=False)
        rp, ru = packed.serve(workload), unpacked.serve(workload)
        assert packed.n_packed_dispatches >= 1
        assert unpacked.n_packed_dispatches == 0
        assert packed.n_dispatches < unpacked.n_dispatches
        _assert_bitwise(rp, ru)

    def test_packed_gate_mix(self):
        """The packing gate: 32 requests over all 11 BLAS sequences at
        the dispatch-bound buckets 64/100/128, every key warmed and its
        packs pre-traced.  The packed engine forms packs, needs at most
        half the unpacked engine's dispatches, and returns its outputs
        bit for bit."""
        names, sizes = list(BLAS), (64, 100, 128)
        workload = []
        for i in range(32):
            nm, n = names[i % len(names)], sizes[i // len(names) % len(sizes)]
            workload.append((nm, n, make_inputs(BLAS[nm], n, seed=i)))
        packed = _engine(max_batch=8, max_pack=8)
        unpacked = _engine(max_batch=8, max_pack=1)
        for e in (packed, unpacked):
            for nm in names:
                e.warm(nm, sizes, trace_packs=False)
            e.warm_packs()
        rp, ru = packed.serve(workload), unpacked.serve(workload)
        assert packed.n_packed_dispatches >= 1
        assert unpacked.n_dispatches / packed.n_dispatches >= 2.0
        _assert_bitwise(rp, ru)

    def test_cold_member_falls_back_to_unpacked(self):
        engine = _engine(max_batch=4, max_pack=8)
        workload = _mixed_workload(["AXPYDOT", "VADD"], per=2)
        engine.serve(workload)             # drain 1: all members cold
        assert engine.n_packed_dispatches == 0
        engine.serve(workload)             # drain 2: warm -> packed
        assert engine.n_packed_dispatches == 1
        assert engine.n_packed_members == 2

    def test_singleton_rounds_stay_unpacked(self):
        """One warm key per drain never forms a pack (min 2 members)."""
        engine = _engine(max_batch=4, max_pack=8)
        workload = _mixed_workload(["VADD"], per=2)
        engine.serve(workload)
        engine.serve(workload)
        assert engine.n_packed_dispatches == 0

    def test_max_pack_one_disables_packing(self):
        engine = _engine(max_batch=4, max_pack=1)
        workload = _mixed_workload(["AXPYDOT", "VADD"], per=2)
        engine.serve(workload)
        engine.serve(workload)
        assert engine.n_packed_dispatches == 0
        with pytest.raises(ValueError, match="max_pack"):
            _engine(max_pack=0)

    def test_warm_packs_covers_hot_path(self):
        """After warm(trace_packs=True) over the key set, serving mixed
        traffic adds no pack entries and no pack-bucket misses — the
        hot path never traces."""
        names = ["AXPYDOT", "VADD", "WAXPBY"]
        engine = _engine(max_batch=4, max_pack=8)
        for nm in names:
            engine.warm(nm, [100], trace_packs=False)
        warmed = engine.warm_packs()
        assert warmed == [(("AXPYDOT", 128), ("VADD", 128),
                           ("WAXPBY", 128))]
        n_packs = len(engine._packs)
        misses0 = sum(b.misses for k, b in
                      engine.compiler.cache.stats.buckets.items()
                      if k.startswith("pack/"))
        engine.serve(_mixed_workload(names, per=4))
        assert engine.n_packed_dispatches >= 1
        assert len(engine._packs) == n_packs
        misses1 = sum(b.misses for k, b in
                      engine.compiler.cache.stats.buckets.items()
                      if k.startswith("pack/"))
        assert misses1 == misses0

    def test_pack_telemetry_in_stats(self):
        engine = _engine(max_batch=4, max_pack=8)
        workload = _mixed_workload(["AXPYDOT", "VADD"], per=2)
        engine.serve(workload)
        engine.serve(workload)
        st = engine.stats()
        assert st["max_pack"] == 8
        assert st["n_packed_dispatches"] == 1
        assert st["n_packed_members"] == 2
        assert st["packs"] == ["AXPYDOT/128+VADD/128"]


# ---------------------------------------------------------------------------
# queue-wait telemetry (submit -> dispatch)
# ---------------------------------------------------------------------------

class TestQueueWait:
    def test_request_results_carry_queue_wait(self):
        engine = _engine()
        results = engine.serve(_mixed_workload(["VADD", "SSCAL"], per=2))
        assert all(r.queue_wait_s >= 0.0 for r in results)
        assert all(r.queue_wait_s <= r.latency_s for r in results)

    def test_cache_stats_percentiles(self):
        engine = _engine()
        engine.serve(_mixed_workload(["VADD"], per=4))
        qw = engine.compiler.cache.stats.queue_wait_percentiles()
        assert qw["count"] == 4
        assert 0.0 <= qw["p50_ms"] <= qw["p99_ms"]
        st = engine.stats()
        assert st["queue_wait"]["count"] == 4
        assert "queue_wait" in st["cache"]
        assert "queue_waits" not in st["cache"]


# ---------------------------------------------------------------------------
# bucket_of validation (min_bucket must be a power of two)
# ---------------------------------------------------------------------------

def test_bucket_of_validates_min_bucket():
    assert bucket_of(200, min_bucket=64) == 256
    assert bucket_of(3, min_bucket=1) == 4
    for bad in (0, -4, 3, 100, 1000):
        with pytest.raises(ValueError, match="power of two"):
            bucket_of(200, min_bucket=bad)
