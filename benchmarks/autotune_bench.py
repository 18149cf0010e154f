"""Autotune benchmark: prediction quality before and after the
predictor learns from the per-group measured-cost table (DESIGN.md §8;
the paper's Table 4/5 analogue for ``mode="autotune"``).

For each sequence, three phases against one **ground truth** — the
whole-program wall time of every candidate in the budget, measured with
the pipelined discipline (``measure_program(..., inner=...)``):

1. **analytic** — Spearman rank correlation of the calibrated model's
   ``t_pred`` against ground truth, and where the measured winner sat
   in the predicted order (``winner_rank``, 1-based — the paper's "how
   deep must empirical search go");
2. **per-group table** — run ``autotune_combination`` twice against a
   fresh ``PlanCache``: the cold pass populates the group table (its
   hit rate reflects intra-program group sharing), the warm pass must
   be served entirely from it (``group_table_hit_rate == 1.0``, zero
   new measurements — the PR-8 acceptance gate);
3. **refit** — ``HardwareModel.refit`` regresses over the accumulated
   group records, then every candidate is re-costed by the two-phase
   predictor (``predict_combination``: table hit -> measured group
   time, miss -> the refit regression), which is exactly how a warm
   autotune pass costs candidates in production.  ``spearman_refit`` /
   ``winner_rank_refit`` score that predictor; ``spearman_refit_model``
   scores the bare regression with the table withheld (transfer
   regime: every group unseen).

``--emit-json`` writes ``BENCH_autotune.json``, the tracked snapshot.

    PYTHONPATH=src python -m benchmarks.autotune_bench [--quick] \
        [--emit-json [PATH]]
"""
from __future__ import annotations

import argparse
import json
import sys

import numpy as np

SEQUENCES = ("AXPYDOT", "BiCGK", "SGEMV", "GEMVER", "VADD", "WAXPBY")


def spearman(a, b) -> float:
    """Spearman rank correlation (average ranks for ties)."""
    def ranks(x):
        x = np.asarray(x, dtype=np.float64)
        order = np.argsort(x, kind="stable")
        r = np.empty(len(x))
        r[order] = np.arange(len(x), dtype=np.float64)
        # average tied groups so identical predictions share a rank
        for v in np.unique(x):
            m = x == v
            r[m] = r[m].mean()
        return r

    ra, rb = ranks(a), ranks(b)
    if ra.std() == 0 or rb.std() == 0:
        return 1.0 if len(ra) <= 1 else 0.0
    return float(np.corrcoef(ra, rb)[0, 1])


def winner_rank(t_pred, winner: int) -> int:
    """1-based position of the measured winner in a predictor's
    ordering (stable sort, so ties keep enumeration order)."""
    order = np.argsort(np.asarray(t_pred, dtype=np.float64), kind="stable")
    return int(np.where(order == winner)[0][0]) + 1


def run_sequence(name: str, n: int = 1024, budget: int = 8,
                 reps: int = 3, inner: int = 8, seed: int = 0) -> dict:
    from repro.blas import REGISTRY
    from repro.core import (FusionCompiler, PlanCache, autotune_combination,
                            build_plan, enumerate_combinations,
                            measure_program, predict_combination,
                            synthetic_inputs)
    from repro.core import codegen

    seq = REGISTRY[name]
    cc = FusionCompiler(hw="calibrate", cache=None)
    g = cc.trace(seq.script, seq.shapes(n))
    space = cc.space(g)
    combos = enumerate_combinations(space, limit=budget)
    inputs = synthetic_inputs(g, seed)

    # ground truth: every candidate compiled whole-program and timed
    # with the same pipelined discipline per-group records are summed in
    t_true = []
    for combo in combos:
        plan = build_plan(g, combo, backend=cc.backend)
        prog = codegen.compile_plan(g, plan, hw=cc.hw,
                                    interpret=cc.interpret)
        t_true.append(measure_program(prog, inputs, reps=reps, inner=inner))
    winner = int(np.argmin(t_true))

    # phase 1: analytic predictor (calibrated constants, no table)
    t_analytic = [c.t_pred for c in combos]

    # phase 2: populate the per-group table cold, then verify the warm
    # pass is fully table-served
    cache = PlanCache()
    kw = dict(hw=cc.hw, backend=cc.backend, interpret=cc.interpret,
              cache=cache, budget=budget, reps=reps, inner=inner, seed=seed)
    _, _, rep_cold = autotune_combination(space, **kw)
    _, _, rep_warm = autotune_combination(space, **kw)

    # phase 3: refit from the table, re-cost every candidate
    records = cache.group_records()
    hw_refit = cc.hw.refit(records)
    t_refit = [predict_combination(g, c, hw_refit, backend=cc.backend,
                                   interpret=cc.interpret, cache=cache)
               for c in combos]
    t_refit_model = [predict_combination(g, c, hw_refit, cache=None)
                     for c in combos]

    return {
        "name": name, "n": n, "budget": budget,
        "n_candidates": len(combos),
        "spearman_analytic": spearman(t_analytic, t_true),
        "spearman_refit": spearman(t_refit, t_true),
        "spearman_refit_model": spearman(t_refit_model, t_true),
        "winner_rank_analytic": winner_rank(t_analytic, winner),
        "winner_rank_refit": winner_rank(t_refit, winner),
        "group_table_hit_rate_cold": rep_cold.group_table_hit_rate,
        "group_table_hit_rate_warm": rep_warm.group_table_hit_rate,
        "n_groups_measured_cold": rep_cold.n_groups_measured,
        "n_groups_measured_warm": rep_warm.n_groups_measured,
        "n_group_records": len(records),
        "hw_refit": repr(hw_refit),
        "t_true_us": [t * 1e6 for t in t_true],
        "t_pred_analytic_us": [t * 1e6 for t in t_analytic],
        "t_pred_refit_us": [t * 1e6 for t in t_refit],
    }


def run_all(quick: bool = False, emit_json: str | None = None) -> list[dict]:
    n = 256 if quick else 1024
    budget = 4 if quick else 8
    reps = 2 if quick else 3
    inner = 8
    rows = []
    for name in SEQUENCES:
        r = run_sequence(name, n=n, budget=budget, reps=reps, inner=inner)
        rows.append(r)
        print(f"T4E_{r['name']},{r['n_candidates']},"
              f"spearman_analytic={r['spearman_analytic']:.2f} "
              f"spearman_refit={r['spearman_refit']:.2f} "
              f"winner_rank={r['winner_rank_analytic']}"
              f"->{r['winner_rank_refit']} "
              f"warm_hit_rate={r['group_table_hit_rate_warm']:.2f}")
    mean_a = float(np.mean([r["spearman_analytic"] for r in rows]))
    mean_r = float(np.mean([r["spearman_refit"] for r in rows]))
    print(f"T4E_mean,,spearman_analytic={mean_a:.3f} "
          f"spearman_refit={mean_r:.3f}")
    if emit_json:
        from repro.core import HardwareModel
        with open(emit_json, "w") as f:
            json.dump({
                "n": n, "budget": budget, "reps": reps, "inner": inner,
                "hw": repr(HardwareModel.calibrate()),
                "mean_spearman_analytic": mean_a,
                "mean_spearman_refit": mean_r,
                "note": "t_true is XLA-on-CPU wall time (min-of-reps, GC "
                        "flushed, inner-pipelined); sub-millisecond "
                        "candidates jitter on shared containers — trust "
                        "the rank trends.  spearman_refit scores the "
                        "two-phase predictor (group table hit -> measured "
                        "time, miss -> refit regression), the costing "
                        "path a warm autotune pass actually uses; "
                        "spearman_refit_model withholds the table "
                        "(transfer regime).  warm hit rate must be 1.0: "
                        "a second pass against the table measures "
                        "nothing.",
                "sequences": rows}, f, indent=1)
        print(f"BENCH_json,{len(rows)},written:{emit_json}", file=sys.stderr)
    return rows


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--quick", action="store_true",
                    help="smaller sizes / budget / reps")
    ap.add_argument("--emit-json", nargs="?", const="BENCH_autotune.json",
                    default=None, metavar="PATH",
                    help="write the per-sequence report to PATH "
                         "(default BENCH_autotune.json)")
    args = ap.parse_args()
    print("name,n_candidates,derived")
    run_all(quick=args.quick, emit_json=args.emit_json)


if __name__ == "__main__":
    from repro.launch import enable_compile_cache
    enable_compile_cache()
    main()
