"""repro.core — the paper's contribution: a fusion compiler for
map/reduce elementary functions (Filipovič et al., 2013)."""
from .autotune import (AutotuneReport, CandidateTiming, autotune_combination,
                       bandwidth_sweep, calibrate_hardware, group_key,
                       impl_group_key, measure_callable, measure_group,
                       measure_program, predict_combination, synthetic_inputs)
from .cache import BucketStats, CacheStats, PlanCache, default_cache
from .codegen import (BatchedProgram, CompiledProgram, PackedDispatch,
                      PackedProgram, compile_plan_packed)
from .compiler import MODES, CompileReport, FusionCompiler
from .elementary import (ArgSpec, Elementary, Kind, Monoid, make_map,
                         make_nested_map, make_nested_map_reduce, make_reduce,
                         make_tensor_map, make_tensor_map_reduce)
from .fusion import Fusion, analyse_group, enumerate_fusions, saves_traffic
from .graph import CallNode, Graph, Var, trace
from .plan import (ExecutionPlan, GroupPlan, PackedPlan, build_packed_plan,
                   build_plan, canonical_pack_order, graph_signature,
                   group_signature, pack_signature, plan_fingerprint)
from .predictor import V5E, HardwareModel, Impl, enumerate_impls
from .scheduler import (Combination, OptimizationSpace, best_combination,
                        build_space, enumerate_combinations,
                        exhaustive_best_combination, iter_combinations,
                        unfused_combination)

__all__ = [
    "ArgSpec", "AutotuneReport", "BatchedProgram", "BucketStats",
    "CacheStats", "CallNode", "CandidateTiming",
    "Combination", "CompileReport", "CompiledProgram",
    "Elementary", "ExecutionPlan", "Fusion", "FusionCompiler", "Graph",
    "GroupPlan", "HardwareModel", "Impl", "Kind", "MODES", "Monoid",
    "OptimizationSpace", "PackedDispatch", "PackedPlan", "PackedProgram",
    "PlanCache", "V5E", "Var", "analyse_group",
    "autotune_combination", "bandwidth_sweep", "best_combination",
    "build_packed_plan", "build_plan", "build_space",
    "calibrate_hardware", "canonical_pack_order", "compile_plan_packed",
    "default_cache", "group_key", "group_signature",
    "impl_group_key", "pack_signature", "plan_fingerprint",
    "predict_combination",
    "enumerate_combinations", "enumerate_fusions", "enumerate_impls",
    "exhaustive_best_combination", "graph_signature", "iter_combinations",
    "make_map", "make_nested_map", "make_nested_map_reduce", "make_reduce",
    "make_tensor_map", "make_tensor_map_reduce", "measure_callable",
    "measure_group", "measure_program", "saves_traffic",
    "synthetic_inputs", "trace",
    "unfused_combination",
]
