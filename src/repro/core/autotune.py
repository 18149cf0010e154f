"""Empirical autotuning (paper §5.2) — DESIGN.md §8.

The paper's headline speedups come from its *empirical search* mode:
candidates are enumerated in predicted order but the winner is chosen by
**measuring** them.  This module is that loop for our compiler:

* ``measure_program`` — one timed sample with the timing discipline the
  serving benchmarks learned the hard way (warmup dispatches,
  ``block_until_ready``, a ``gc.collect()`` flush before every rep so a
  cyclic-GC pass over ~100k live jax objects can't land inside the timed
  window, min-of-reps);
* ``autotune_combination`` — pull the ``budget`` best combinations from
  the exact nondecreasing-``t_pred`` A* stream
  (``scheduler.iter_combinations``, DESIGN.md §3), time each **per
  fused group** (KBLAS-style per-kernel tables), cost every candidate
  as the sum of its group timings, pick the measured winner;
* a **per-group measured-cost table** content-addressed by ``(group
  signature, grid order, blocks, hardware/backend fingerprint)`` and
  persisted through the ``PlanCache`` disk machinery (DESIGN.md
  §5/§8).  Group signatures are *localized* (``plan.group_signature``),
  so timings transfer between any two programs sharing a fusion — a
  candidate whose groups are all in the table is costed from the store
  without compiling or timing anything, and a fleet measures each
  distinct group once.  Whole-program records from the previous schema
  still serve as an exact fallback (one cache dir, two generations);
* ``calibrate_hardware`` — micro-benchmarks (streaming bandwidth from
  a ≥3-size sweep, dispatch overhead, f32 flop rate) that replace
  ``HardwareModel``'s hardcoded v5e constants with numbers from the
  machine actually running, so ``t_pred`` (and hence the candidate
  *ordering* the budget is spent on) is meaningful off-TPU too.  The
  accumulated group table feeds ``HardwareModel.refit`` — regression
  over measured groups — closing the loop from measurement back into
  the predictor.
"""
from __future__ import annotations

import dataclasses
import gc
import hashlib
import math
import time
from typing import Any, Mapping

import numpy as np

from . import codegen, scheduler
from .cache import PlanCache
from .graph import Graph
from .plan import (ExecutionPlan, build_plan, graph_signature,
                   group_signature, topo_group_order)
from .predictor import V5E, HardwareModel, Impl, _round_sig
from .scheduler import Combination, OptimizationSpace

#: default measurement discipline (overridable per call / per compiler)
MEAS_REPS = 3
MEAS_WARMUP = 1
#: pipelined calls per timed rep when measuring one group: a blocked
#: single call carries the full host sync latency (~hundreds of us on
#: CPU jax), which would make a sum of per-group times overcount the
#: whole program wildly; `inner` unblocked calls amortize it down to
#: the per-dispatch cost the whole-program path actually pays
GROUP_INNER = 8


# ---------------------------------------------------------------------------
# timing discipline
# ---------------------------------------------------------------------------

def synthetic_inputs(g: Graph, seed: int = 0) -> dict[str, np.ndarray]:
    """Concrete random inputs matching a trace's input signature —
    what autotune measures candidates on when the caller brings none."""
    rng = np.random.default_rng(seed)
    out: dict[str, np.ndarray] = {}
    for v in g.inputs:
        if v.shape == ():
            out[v.name] = np.dtype(v.dtype).type(rng.uniform(0.5, 1.5))
        else:
            out[v.name] = rng.standard_normal(v.shape).astype(v.dtype)
    return out


def measure_program(prog, inputs: Mapping[str, Any], *,
                    reps: int = MEAS_REPS, warmup: int = MEAS_WARMUP,
                    inner: int = 1) -> float:
    """Wall-clock seconds per call of ``prog(**inputs)``, min-of-reps.

    Warmup runs absorb jit tracing/compilation; every timed rep flushes
    the cyclic GC first and blocks on the result, so what's timed is a
    complete dispatch+execute and nothing else.  ``inner > 1`` pipelines
    that many unblocked calls per rep and divides — jax executes an
    in-order stream, so blocking the last output waits for all — which
    amortizes the host sync latency out of the per-call figure (the
    regime per-group records are summed in)."""
    inner = max(inner, 1)
    for _ in range(max(warmup, 1)):
        prog.block_until_ready(prog(**inputs))
    best = math.inf
    for _ in range(max(reps, 1)):
        gc.collect()
        t0 = time.perf_counter()
        out = None
        for _ in range(inner):
            out = prog(**inputs)
        prog.block_until_ready(out)
        best = min(best, time.perf_counter() - t0)
    return best / inner


def measure_callable(fn, args: tuple, *, reps: int = MEAS_REPS,
                     warmup: int = MEAS_WARMUP, inner: int = 1) -> float:
    """``measure_program`` for a bare (jitted) positional callable —
    the per-group timing primitive.  Same discipline: warmup, GC flush,
    min-of-reps, optional pipelined ``inner`` calls per rep."""
    import jax
    inner = max(inner, 1)
    for _ in range(max(warmup, 1)):
        jax.block_until_ready(fn(*args))
    best = math.inf
    for _ in range(max(reps, 1)):
        gc.collect()
        t0 = time.perf_counter()
        out = None
        for _ in range(inner):
            out = fn(*args)
        jax.block_until_ready(out)
        best = min(best, time.perf_counter() - t0)
    return best / inner


def group_inputs(f, seed: int = 0) -> tuple:
    """Concrete random positional inputs matching one fusion's external
    input signature — what a group is timed on.  Timings are value-
    independent (dense map/reduce kernels), so synthetic data is as
    good as the program's."""
    rng = np.random.default_rng(seed)
    vals = []
    for v in f.external_inputs:
        if v.shape == ():
            vals.append(np.dtype(v.dtype).type(rng.uniform(0.5, 1.5)))
        else:
            vals.append(rng.standard_normal(v.shape).astype(v.dtype))
    return tuple(vals)


def measure_group(g: Graph, impl: Impl, *, backend: str = "jnp",
                  hw: HardwareModel = V5E,
                  interpret: bool = False, reps: int = MEAS_REPS,
                  warmup: int = MEAS_WARMUP, inner: int = GROUP_INNER,
                  seed: int = 0) -> float:
    """Time ONE fused group in isolation: jit the group's kernel (the
    same executor codegen would emit for it inside a whole program) on
    synthetic inputs.  Routed through ``measure_callable`` so tests can
    intercept every fresh measurement at one seam."""
    import jax
    if backend == "pallas":
        fn = codegen._group_pallas_fn(g, impl, hw=hw, interpret=interpret)
    else:
        fn = codegen._group_dense_fn(impl.fusion)
    return measure_callable(jax.jit(fn), group_inputs(impl.fusion, seed),
                            reps=reps, warmup=warmup, inner=inner)


# ---------------------------------------------------------------------------
# measured-cost table keys
# ---------------------------------------------------------------------------

def combination_key(plan: ExecutionPlan) -> str:
    """Content address of one combination *choice*: which calls fuse
    into which groups, with which grid order and block sizes.  Derived
    from the plan (deterministic topo order), so it is stable across
    re-traces and processes."""
    payload = repr(tuple((gp.call_indices, gp.order_pos, gp.blocks)
                         for gp in plan.groups))
    return hashlib.sha256(payload.encode()).hexdigest()


def hw_fingerprint(backend: str = "jnp", interpret: bool = False) -> str:
    """Fingerprint of the measuring environment.  Two hosts with the
    same fingerprint are interchangeable for the measured-cost table
    (same compiler backend + jax platform/device kind/version), which is
    what lets a fleet share one table."""
    import jax
    dev = jax.devices()[0]
    return repr((backend, bool(interpret), jax.default_backend(),
                 getattr(dev, "device_kind", "?"), jax.__version__))


def measurement_key(signature: str, combo_key: str, fingerprint: str) -> str:
    """Whole-*program* measured-cost key — the previous table schema,
    still consulted as an exact fallback so caches written by older
    releases keep serving (schema coexistence, DESIGN.md §8)."""
    payload = repr((signature, combo_key, fingerprint))
    return hashlib.sha256(payload.encode()).hexdigest()


def group_key(gsig: str, order_pos, blocks, fingerprint: str) -> str:
    """Per-*group* measured-cost key: localized group signature + the
    impl choice (grid order, block sizes) + environment fingerprint.
    Program-independent by construction — any two programs tracing a
    structurally identical group share this address, which is the
    transfer property the table exists for."""
    payload = repr(("group", gsig, tuple(order_pos), tuple(blocks),
                    fingerprint))
    return hashlib.sha256(payload.encode()).hexdigest()


def _finite_time(x) -> bool:
    return (isinstance(x, (int, float)) and not isinstance(x, bool)
            and math.isfinite(x) and x > 0)


# ---------------------------------------------------------------------------
# the autotune loop
# ---------------------------------------------------------------------------

@dataclasses.dataclass(frozen=True)
class CandidateTiming:
    """One costed candidate (``rank_pred`` = position in the predicted
    order, i.e. 0 is the model's pick).  ``t_meas`` is the sum of the
    candidate's per-group timings unless ``source == "program"`` (a
    whole-program record from the previous table schema served it
    exactly)."""

    rank_pred: int
    t_pred: float
    t_meas: float
    from_cache: bool                   # no fresh measurement was needed
    key: str                           # combination_key digest
    source: str = "groups"             # "groups" | "program" | "measured"
    n_groups: int = 0
    n_groups_cached: int = 0           # group lookups served by the table

    def describe(self) -> str:
        src = self.source if self.from_cache else "measured"
        return (f"#{self.rank_pred} t_pred={self.t_pred*1e6:.2f}us "
                f"t_meas={self.t_meas*1e6:.2f}us "
                f"({src}, {self.n_groups_cached}/{self.n_groups} "
                f"groups cached)")


@dataclasses.dataclass
class AutotuneReport:
    """What one autotune pass did — candidates in predicted order.

    ``n_measured``/``n_cached`` count *candidates* (needed fresh group
    measurements / served entirely from the table);
    ``n_groups_measured``/``n_groups_cached`` count individual group
    timings, and ``group_table_hit_rate`` is the fraction of group
    lookups the table answered — 1.0 on a warm table means the pass
    measured nothing."""

    budget: int
    candidates: list[CandidateTiming]
    winner_index: int                  # into ``candidates``
    n_measured: int                    # candidates needing fresh timings
    n_cached: int                      # candidates served from the table
    n_groups_measured: int = 0         # fresh group timings this pass
    n_groups_cached: int = 0           # group lookups served by the table

    @property
    def winner(self) -> CandidateTiming:
        return self.candidates[self.winner_index]

    @property
    def group_table_hit_rate(self) -> float:
        total = self.n_groups_measured + self.n_groups_cached
        return self.n_groups_cached / total if total else 1.0

    @property
    def measured_speedup(self) -> float:
        """Measured winner vs the predicted-best candidate (== the
        ``mode="best"`` plan): >= 1.0 by construction."""
        return self.candidates[0].t_meas / max(self.winner.t_meas, 1e-12)

    def describe(self) -> str:
        lines = [f"autotune budget={self.budget}: winner #{self.winner_index}"
                 f" ({self.n_measured} measured, {self.n_cached} cached,"
                 f" group hit rate {self.group_table_hit_rate:.2f},"
                 f" {self.measured_speedup:.2f}x vs predicted best)"]
        lines += ["  " + c.describe() for c in self.candidates]
        return "\n".join(lines)


def _valid_group_record(rec) -> bool:
    return (isinstance(rec, dict) and rec.get("kind") == "group"
            and _finite_time(rec.get("t_meas")))


def autotune_combination(space: OptimizationSpace, *,
                         hw: HardwareModel = V5E, backend: str = "jnp",
                         interpret: bool = False,
                         cache: PlanCache | None = None,
                         budget: int = 8, reps: int = MEAS_REPS,
                         warmup: int = MEAS_WARMUP,
                         inner: int = GROUP_INNER,
                         inputs: Mapping[str, Any] | None = None,
                         seed: int = 0
                         ) -> tuple[Combination, ExecutionPlan, AutotuneReport]:
    """Measured-cost search over the ``budget`` best-predicted
    combinations; returns ``(winner combination, its plan, report)``.

    Candidates come from the exact nondecreasing-``t_pred`` stream, so
    candidate 0 is exactly the ``mode="best"`` plan — the measured
    winner is therefore never slower than it (same measurement pass).

    Costing is **per group** (DESIGN.md §8): each candidate's fused
    groups are looked up in the per-group measured-cost table (keyed by
    localized group signature + impl choice + environment fingerprint)
    and only the missing ones are timed — in isolation, pipelined
    (``inner``), published back to ``cache``.  A candidate's ``t_meas``
    is the sum of its group timings; since candidates of one program
    overwhelmingly share groups, a budget-``k`` pass times far fewer
    than ``k`` whole programs, and the records transfer to *any* other
    program sharing a fusion.  Whole-program records written by the
    previous schema still serve as an exact per-candidate fallback.
    ``inputs`` is accepted for back-compat but only shapes matter now —
    groups are timed on synthetic data matching their signature.

    Raises:
      ValueError: no legal combination covers the graph.
    """
    del inputs  # shapes are in the trace; groups time on synthetic data
    g = space.graph
    combos = scheduler.enumerate_combinations(space, limit=max(1, budget))
    if not combos:
        raise ValueError(
            "no legal combination covers the graph (the optimization "
            "space enumerated empty — every fusion impl may have been "
            "pruned, e.g. by the VMEM budget)")
    fp = hw_fingerprint(backend, interpret)
    sig = graph_signature(g)

    plans, cands = [], []
    n_measured = n_cached = n_gmeas = n_gcached = 0
    # pass-local memo: groups shared across candidates (or already timed
    # this pass) are never re-measured even without a cache
    local: dict[str, float] = {}
    winner_i, winner_t = 0, math.inf
    for i, combo in enumerate(combos):
        plan = build_plan(g, combo, backend=backend)
        ck = combination_key(plan)
        impls = topo_group_order(g, combo)     # same order as plan.groups
        keyed = [(group_key(group_signature(g, im.fusion), gp.order_pos,
                            gp.blocks, fp), im)
                 for gp, im in zip(plan.groups, impls)]

        times: dict[str, float] = {}
        missing = []
        for k, im in keyed:
            t = local.get(k)
            if t is None and cache is not None:
                rec = cache.get_measurement(k)
                if rec is not None and not _valid_group_record(rec):
                    # wrong-schema record (version drift): drop it from
                    # memory and disk so the republish below heals the
                    # key instead of poisoning it for every sharing
                    # process
                    cache.drop_measurement(k)
                    rec = None
                if rec is not None:
                    t = float(rec["t_meas"])
            if t is None:
                missing.append((k, im))
            else:
                times[k] = t
        n_hit = len(keyed) - len(missing)

        source, from_cache = "groups", True
        if missing and cache is not None:
            # exact whole-program record from the previous table schema
            mk = measurement_key(sig, ck, fp)
            rec = cache.get_measurement(mk)
            if rec is not None and not _finite_time(rec.get("t_meas")):
                cache.drop_measurement(mk)
                rec = None
            if rec is not None:
                t_meas = float(rec["t_meas"])
                source = "program"
                n_gcached += n_hit
                missing = None                 # served; skip measuring
        if missing is not None:
            for k, im in missing:
                t = measure_group(g, im, backend=backend, hw=hw,
                                  interpret=interpret, reps=reps,
                                  warmup=warmup, inner=inner, seed=seed)
                rec = {"kind": "group", "t_meas": t,
                       "sig": group_signature(g, im.fusion),
                       "traffic_bytes": im.traffic_bytes,
                       "flops": im.flops,
                       "grid_steps": im.grid_steps,
                       "elems": "+".join(c.elem.name
                                         for c in im.fusion.calls),
                       "reps": reps, "warmup": warmup, "inner": inner}
                if cache is not None:
                    cache.put_measurement(k, rec)
                local[k] = times[k] = t
                n_gmeas += 1
            if missing:
                source, from_cache = "measured", False
            t_meas = sum(times[k] for k, _ in keyed)
            n_gcached += n_hit
        for k, _ in keyed:                     # warm the pass-local memo
            if k in times:
                local.setdefault(k, times[k])

        if from_cache:
            n_cached += 1
        else:
            n_measured += 1
        plans.append(plan)
        cands.append(CandidateTiming(
            rank_pred=i, t_pred=combo.t_pred, t_meas=t_meas,
            from_cache=from_cache, key=ck, source=source,
            n_groups=len(keyed), n_groups_cached=n_hit))
        if t_meas < winner_t:
            winner_i, winner_t = i, t_meas

    report = AutotuneReport(budget=budget, candidates=cands,
                            winner_index=winner_i, n_measured=n_measured,
                            n_cached=n_cached, n_groups_measured=n_gmeas,
                            n_groups_cached=n_gcached)
    return combos[winner_i], plans[winner_i], report


# ---------------------------------------------------------------------------
# hardware calibration
# ---------------------------------------------------------------------------

#: streaming-bandwidth sweep: f32 element counts spanning ~a decade
#: (2 MiB / 8 MiB / 32 MiB arrays), so the roofline is fitted from a
#: size *sweep* — one averaged point would fold cache-hierarchy and
#: fixed-overhead effects into the bandwidth number (DESIGN.md §8)
BW_SWEEP_SIZES = (512 * 1024, 2 * 1024 * 1024, 8 * 1024 * 1024)


def bandwidth_sweep(backend: str | None = None, *, reps: int = 3,
                    sizes=BW_SWEEP_SIZES) -> dict[int, float]:
    """Streaming bandwidth at each of ``sizes`` f32 element counts:
    jitted elementwise add (2 bytes moved per element byte), min-of-
    ``reps``, blocked.  Returns ``{bytes_moved: bytes/s}`` — keys
    derive deterministically from ``sizes`` (stable across runs and
    hosts), values carry the jitter."""
    import jax
    import jax.numpy as jnp

    platform = backend or jax.default_backend()
    dev = jax.devices(platform)[0]
    out: dict[int, float] = {}
    with jax.default_device(dev):
        add1 = jax.jit(lambda x: x + 1.0)
        for n in sizes:
            xs = jnp.zeros((int(n),), jnp.float32)
            jax.block_until_ready(add1(xs))           # warm this shape
            best = math.inf
            for _ in range(max(reps, 1)):
                gc.collect()
                t0 = time.perf_counter()
                jax.block_until_ready(add1(xs))
                best = min(best, time.perf_counter() - t0)
            moved = 2 * 4 * int(n)
            out[moved] = moved / max(best, 1e-9)
    return out


_CALIBRATED: dict[str, HardwareModel] = {}


def calibrate_hardware(backend: str | None = None, *, force: bool = False,
                       reps: int = 3,
                       cache: PlanCache | None = None) -> HardwareModel:
    """Micro-benchmark the running machine into a ``HardwareModel``.

    Three measurements (each min-of-``reps``, jit-warmed, blocked):

    * **streaming bandwidth** — elementwise adds over a ≥3-size array
      sweep (``bandwidth_sweep``), roofline-fitted: least squares of
      time against bytes moved, whose slope inverts to ``hbm_bw`` (the
      intercept absorbs fixed per-dispatch cost instead of polluting
      the bandwidth, the way a single averaged size would);
    * **dispatch overhead** — a pipeline of tiny jitted calls, time per
      call → ``launch_overhead_s``;
    * **flop rate** — a 384x384 f32 matmul → ``peak_flops`` (stored
      with ``f32_scale=1.0``: on the machines this runs on, f32 *is*
      the measured rate, and ``flops_scale`` keeps sub-4-byte dtypes at
      the same peak).

    ``backend`` selects the jax platform (default: the default
    backend).  Results are memoized per platform and rounded to 2
    significant figures so the constants — which feed compiler cache
    keys — are stable across runs.  They are additionally published to
    the measurement layer of ``cache`` (default: the process-wide
    cache, hence ``REPRO_PLAN_CACHE_DIR`` when set), keyed on the
    platform fingerprint, and the store's **first-written** record
    always wins — a process that loses the publish race (or calibrated
    earlier against a different cache) adopts the winner's constants.
    Every process/host sharing the cache dir therefore calibrates once
    and uses *identical* constants, keeping their plan-cache keys
    aligned; without this, run-to-run jitter crossing a rounding
    boundary would fork the fleet's plan keys.  ``force=True``
    re-measures, but a persisted record still governs what is returned
    (delete the record to truly re-calibrate a shared store).
    ``min_tile`` and ``vmem_bytes`` keep their defaults: they encode
    layout/pruning policy, not speed.
    """
    import jax
    import jax.numpy as jnp

    platform = backend or jax.default_backend()
    dev = jax.devices(platform)[0]
    if cache is None:
        from .cache import default_cache
        cache = default_cache()
    cal_key = hashlib.sha256(repr(
        ("calibration", platform, getattr(dev, "device_kind", "?"),
         jax.__version__)).encode()).hexdigest()

    def from_record(rec) -> HardwareModel | None:
        if not isinstance(rec, dict) or rec.get("kind") != "calibration":
            return None
        try:
            pf, bw, lo = (float(rec[k]) for k in
                          ("peak_flops", "hbm_bw", "launch_overhead_s"))
        except (KeyError, TypeError, ValueError):
            return None
        if not all(math.isfinite(v) and v > 0 for v in (pf, bw, lo)):
            return None
        return HardwareModel(
            name=str(rec.get("name", f"calibrated_{platform}")),
            peak_flops=pf, f32_scale=1.0, hbm_bw=bw,
            vmem_bytes=V5E.vmem_bytes, launch_overhead_s=lo,
            min_tile=V5E.min_tile)

    sweep: dict[int, float] | None = None     # set when THIS process measures

    def record_of(hw: HardwareModel) -> dict:
        rec = {"kind": "calibration", "name": hw.name,
               "peak_flops": hw.peak_flops, "hbm_bw": hw.hbm_bw,
               "launch_overhead_s": hw.launch_overhead_s}
        if sweep:
            # diagnostic payload: per-size bandwidths behind the fit,
            # keyed by bytes moved (stable strings — JSON object keys)
            rec["bw_sweep"] = {str(k): sweep[k] for k in sorted(sweep)}
        return rec

    def adopt(hw: HardwareModel) -> HardwareModel:
        """Publish, then converge on the store's first-written record:
        if another process won the disk race, *its* constants stand —
        everyone sharing the dir ends on identical plan-cache keys."""
        cache.put_measurement(cal_key, record_of(hw))
        if cache.disk_dir:
            cache.forget_measurement(cal_key)   # local copy masks disk
            got = from_record(cache.get_measurement(cal_key))
            if got is not None:
                hw = got
            else:                               # unreadable dir: local wins
                cache.put_measurement(cal_key, record_of(hw))
        memo = _CALIBRATED.get(platform)
        if memo != hw:                          # keep object identity stable
            _CALIBRATED[platform] = hw
        return _CALIBRATED[platform]

    if not force:
        memo = _CALIBRATED.get(platform)
        rec = cache.get_measurement(cal_key)
        got = from_record(rec)
        if got is not None:
            if memo != got:
                _CALIBRATED[platform] = got
            return _CALIBRATED[platform]
        if rec is not None:
            cache.drop_measurement(cal_key)     # schema drift: heal the key
        if memo is not None:
            return adopt(memo)                  # share with this cache too

    def best_of(fn, *args):
        jax.block_until_ready(fn(*args))                   # warm the jit
        best = math.inf
        for _ in range(max(reps, 1)):
            gc.collect()
            t0 = time.perf_counter()
            jax.block_until_ready(fn(*args))
            best = min(best, time.perf_counter() - t0)
        return best

    # streaming bandwidth: a >=3-size sweep, roofline-fitted — least
    # squares of time against bytes moved; the slope inverts to the
    # sustained bandwidth, the intercept soaks up fixed dispatch cost
    sweep = bandwidth_sweep(platform, reps=reps)
    moved = np.array(sorted(sweep), dtype=np.float64)
    t_sizes = np.array([b / sweep[b] for b in sorted(sweep)])
    slope = np.linalg.lstsq(
        np.stack([moved, np.ones_like(moved)], axis=1),
        t_sizes, rcond=None)[0][0]
    if math.isfinite(slope) and slope > 0:
        hbm_bw = 1.0 / float(slope)
    else:
        # degenerate fit (all sizes cache-resident / jitter-dominated):
        # the largest size's direct measurement is the safest estimate
        hbm_bw = sweep[max(sweep)]

    with jax.default_device(dev):
        # dispatch overhead: per-call cost of a pipeline of tiny calls
        tiny = jax.jit(lambda x: x + 1.0)
        xt = jnp.zeros((8,), jnp.float32)
        tiny(xt).block_until_ready()
        n_calls = 200
        best = math.inf
        for _ in range(max(reps, 1)):
            gc.collect()
            y = xt
            t0 = time.perf_counter()
            for _ in range(n_calls):
                y = tiny(y)
            y.block_until_ready()
            best = min(best, time.perf_counter() - t0)
        launch = best / n_calls

        # f32 flop rate: one square matmul
        m = 384
        a = jnp.ones((m, m), jnp.float32)
        mm = jax.jit(lambda x: x @ x)
        t_mm = best_of(mm, a)
        flops = 2.0 * m ** 3 / max(t_mm, 1e-9)

    return adopt(HardwareModel(
        name=f"calibrated_{platform}",
        peak_flops=_round_sig(flops),
        f32_scale=1.0,
        hbm_bw=_round_sig(hbm_bw),
        vmem_bytes=V5E.vmem_bytes,
        launch_overhead_s=_round_sig(launch),
        min_tile=V5E.min_tile,
    ))
