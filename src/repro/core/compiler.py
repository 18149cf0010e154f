"""Facade: the source-to-source fusion compiler (paper §4).

Typical use::

    from repro.core import compiler
    cc = compiler.FusionCompiler()                 # v5e cost model
    prog = cc.compile(script, {"A": (4096, 4096), "p": (4096,), "r": (4096,)})
    q, s = prog(A=A, p=p, r=r)

``compile`` runs the pipeline stages (DESIGN.md §1): parse/trace,
optimization-space generation + combination search, plan construction,
code generation — with two cache layers short-circuiting repeat work:

* a **program cache** hit (same script/shapes/dtype/backend/mode in this
  process) returns the finished ``CompiledProgram`` — no re-trace, no
  re-search, no re-codegen;
* a **plan cache** hit (same traced graph, possibly from disk across
  processes) skips space generation and search, the expensive stages.
"""
from __future__ import annotations

import dataclasses
import hashlib
import logging
import os
import time
from typing import Callable, Sequence

import numpy as np

from . import autotune, codegen, graph, scheduler
from .cache import PlanCache, default_cache
from .diagnostics import (KNOWN_BACKENDS, VerificationError, diag,
                          raise_if_errors)
from .plan import (build_packed_plan, build_plan, canonical_pack_order,
                   graph_signature, pack_signature, plan_fingerprint)
from .predictor import V5E, HardwareModel
from .scheduler import Combination, OptimizationSpace

log = logging.getLogger("repro.compiler")

#: search modes with names (integer ranks are also accepted)
MODES = ("best", "unfused", "autotune")

#: env var switching every compiler to the FULL verification pass
#: (graph-bound plan checks on every compile) — the test suite sets it
VERIFY_ENV = "REPRO_VERIFY"


def _env_verify() -> bool:
    return os.environ.get(VERIFY_ENV, "").strip().lower() not in (
        "", "0", "false", "no")


@dataclasses.dataclass
class CompileReport:
    n_fusions: int
    n_impls: int
    n_combinations: int
    t_trace_s: float
    t_space_s: float
    t_codegen_s: float
    best: Combination
    unfused: Combination

    @property
    def predicted_speedup(self) -> float:
        return self.unfused.t_pred / self.best.t_pred


class FusionCompiler:
    def __init__(self, hw: HardwareModel | str = V5E, backend: str = "jnp",
                 interpret: bool = False, max_impls_per_fusion: int = 64,
                 dtype=np.float32,
                 cache: PlanCache | bool | None = True,
                 autotune_budget: int = 8,
                 autotune_reps: int = autotune.MEAS_REPS,
                 autotune_warmup: int = autotune.MEAS_WARMUP,
                 verify: bool | None = None):
        """``hw`` takes a HardwareModel or the string ``"calibrate"``
        (micro-benchmark this machine, ``HardwareModel.calibrate``).
        ``autotune_budget`` is how many predicted-best candidates
        ``mode="autotune"`` measures; it is part of the autotune cache
        keys (a bigger budget is a different — more thorough — search),
        while reps/warmup are measurement discipline only.

        ``interpret=True`` runs ``backend="pallas"`` kernels in the
        Pallas interpreter (any platform); the default compiles them
        with Mosaic, which needs a TPU — a Pallas compile elsewhere
        raises instead of falling back.

        ``verify`` selects the static-verification depth (DESIGN.md
        §11).  ``False``/default: the cheap always-on subset still runs
        on every cache-served plan (structural + signature — a corrupt
        entry is dropped and recompiled, never executed).  ``True`` (or
        env ``REPRO_VERIFY=1`` when ``None``): every compile
        additionally runs the full graph-bound pass — fusion
        re-analysis, routing reconstruction, pallas phase/VMEM
        contracts — and raises ``VerificationError`` on any error
        diagnostic."""
        self.verify = _env_verify() if verify is None else bool(verify)
        self._check_backend(backend)
        if cache is True:
            self.cache: PlanCache | None = default_cache()
        else:
            self.cache = cache or None
        if isinstance(hw, str):
            if hw != "calibrate":
                raise ValueError(f"unknown hw {hw!r}: pass a HardwareModel "
                                 "or the string 'calibrate'")
            # calibrate against THIS compiler's cache, so a fleet
            # sharing plans through it shares the constants too
            hw = autotune.calibrate_hardware(cache=self.cache)
        self.hw = hw
        self.backend = backend
        self.interpret = interpret
        self.max_impls = max_impls_per_fusion
        self.dtype = np.dtype(dtype)
        self.autotune_budget = autotune_budget
        self.autotune_reps = autotune_reps
        self.autotune_warmup = autotune_warmup
        #: report of the most recent autotune *search* this compiler ran
        #: (None until one runs; cache-served compiles don't update it)
        self.last_autotune: autotune.AutotuneReport | None = None

    @staticmethod
    def _check_backend(backend: str):
        """RPL401 — reject unknown backends at the API boundary instead
        of threading them through to a late codegen failure."""
        if backend not in KNOWN_BACKENDS:
            raise VerificationError.single(
                "RPL401", "config.backend",
                f"unknown backend {backend!r}",
                f"valid backends: {', '.join(KNOWN_BACKENDS)}")

    # -- stages ------------------------------------------------------------
    def trace(self, script: Callable, input_shapes: dict[str, Sequence[int]]
              ) -> graph.Graph:
        return graph.trace(script, input_shapes, dtype=self.dtype)

    def space(self, g: graph.Graph) -> OptimizationSpace:
        return scheduler.build_space(g, self.hw, self.max_impls)

    def search(self, space: OptimizationSpace, mode,
               backend: str | None = None) -> Combination:
        """Pick a combination: ``'best'`` / ``'unfused'`` / an integer
        rank into the predicted-order stream / ``'autotune'`` (measure
        the top ``autotune_budget`` candidates and take the measured
        winner — DESIGN.md §8)."""
        self._mode_key(mode)            # validate (bools, unknown strings)
        if mode == "best":
            return scheduler.best_combination(space)
        if mode == "unfused":
            return scheduler.unfused_combination(space)
        if mode == "autotune":
            combo, _ = self._autotune(space, backend or self.backend)
            return combo
        if mode < 0:
            raise VerificationError.single(
                "RPL402", "config.mode",
                f"combination index must be >= 0, got {mode}")
        combos = scheduler.enumerate_combinations(space, limit=mode + 1)
        if not combos:
            raise VerificationError.single(
                "RPL220", "scheduler",
                "no legal combination covers the graph (the "
                "optimization space enumerated empty — every fusion "
                "impl may have been pruned, e.g. by the VMEM budget)")
        if mode >= len(combos):
            # silently clamping would also cache a duplicate plan under
            # this index's key, corrupting compile_all's index<->plan
            # correspondence
            raise VerificationError.single(
                "RPL402", "config.mode",
                f"combination index {mode} out of range: the space has "
                f"only {len(combos)} legal combination(s)")
        return combos[mode]

    def _autotune(self, space: OptimizationSpace, backend: str):
        """One call site for the measured-cost search (used by both
        ``search`` and ``_plan_for``); records ``last_autotune``."""
        combo, plan, report = autotune.autotune_combination(
            space, hw=self.hw, backend=backend, interpret=self.interpret,
            cache=self.cache, budget=self.autotune_budget,
            reps=self.autotune_reps, warmup=self.autotune_warmup)
        self.last_autotune = report
        return combo, plan

    def refit_hardware(self) -> HardwareModel:
        """Recalibrate this compiler's cost model from the cache's
        accumulated per-group measurement records
        (``HardwareModel.refit``, DESIGN.md §8) and adopt the result.

        With no cache or an empty/too-small group table this is a
        strict no-op (``self.hw`` unchanged, later compiles produce
        bit-identical plans).  When the refit *does* change the
        constants, the model's repr — a component of every plan and
        program cache key — changes with it, so subsequent compiles
        search fresh plans under the better predictor instead of
        silently reusing analytic-era entries."""
        if self.cache is not None:
            self.hw = self.hw.refit(self.cache.group_records())
        return self.hw

    # -- cache keys --------------------------------------------------------
    def _mode_key(self, mode):
        """Validate ``mode`` and return its cache-key form.

        ``'autotune'`` keys as ``('autotune', budget)`` — a bigger
        budget is a deeper search, so it must not alias a shallower
        one.  Bools are rejected explicitly: ``isinstance(True, int)``
        holds, so they would otherwise silently select combination
        index 0/1."""
        if isinstance(mode, bool) or not isinstance(mode, (str, int)):
            raise VerificationError.single(
                "RPL402", "config.mode",
                f"bad mode {mode!r}: valid modes are "
                f"{', '.join(repr(m) for m in MODES)}, or an integer "
                f"rank into the predicted-order combination stream")
        if mode == "autotune":
            return ("autotune", self.autotune_budget)
        if isinstance(mode, str) and mode not in MODES:
            raise VerificationError.single(
                "RPL402", "config.mode",
                f"unknown mode {mode!r}: valid modes are "
                f"{', '.join(repr(m) for m in MODES)}, or an integer "
                f"rank into the predicted-order combination stream")
        return mode

    def _config_key(self, backend: str, mode_key) -> str:
        # full hw repr, not just .name: custom models keep the default name
        return repr((backend, mode_key, self.hw, self.interpret,
                     self.max_impls))

    @classmethod
    def _cell_fingerprint(cls, val, _seen: set | None = None) -> tuple | None:
        """Stable *content* fingerprint of one closure-cell value, or
        None when the value has no address-free identity (default
        object reprs embed a reusable memory address; large ndarray
        reprs elide).

        Recurses structurally: containers fingerprint element-wise,
        dataclass instances field-wise, and functions by bytecode +
        consts + names + their OWN closure cells — so two structurally
        equal closures built at different addresses alias to one
        program-cache entry, while a nested closure whose captured
        value differs can never alias (the earlier bytecode-only
        function fingerprint let it)."""
        if _seen is None:
            _seen = set()
        if id(val) in _seen:
            return ("cycle",)
        code = getattr(val, "__code__", None)
        if code is not None:
            _seen.add(id(val))
            consts = tuple(c.co_code if hasattr(c, "co_code") else repr(c)
                           for c in code.co_consts)
            cells = getattr(val, "__closure__", None) or ()
            prints = [cls._cell_fingerprint(c.cell_contents, _seen)
                      for c in cells]
            if any(p is None for p in prints):
                return None
            return ("fn", code.co_code, repr(consts), repr(code.co_names),
                    repr(prints))
        if isinstance(val, np.ndarray):
            return ("arr", val.shape, str(val.dtype),
                    hashlib.sha256(np.ascontiguousarray(val).tobytes())
                    .hexdigest())
        if isinstance(val, (int, float, complex, str, bytes, bool,
                            type(None))):
            return ("lit", repr(val))
        if isinstance(val, (tuple, list)):
            _seen.add(id(val))
            items = [cls._cell_fingerprint(v, _seen) for v in val]
            if any(p is None for p in items):
                return None
            return (type(val).__name__, repr(items))
        if isinstance(val, dict):
            _seen.add(id(val))
            pairs = []
            for k, v in val.items():
                kp = cls._cell_fingerprint(k, _seen)
                vp = cls._cell_fingerprint(v, _seen)
                if kp is None or vp is None:
                    return None
                pairs.append((kp, vp))
            pairs.sort(key=repr)
            return ("dict", repr(pairs))
        if isinstance(val, (set, frozenset)):
            items = [cls._cell_fingerprint(v, _seen) for v in val]
            if any(p is None for p in items):
                return None
            items.sort(key=repr)
            return ("set", repr(items))
        if dataclasses.is_dataclass(val) and not isinstance(val, type):
            _seen.add(id(val))
            fields = []
            for f in dataclasses.fields(val):
                fp = cls._cell_fingerprint(getattr(val, f.name), _seen)
                if fp is None:
                    return None
                fields.append((f.name, fp))
            return ("dc", type(val).__module__, type(val).__qualname__,
                    repr(fields))
        r = repr(val)
        return None if " at 0x" in r else ("repr", r)

    def _program_key(self, script: Callable,
                     input_shapes: dict[str, Sequence[int]],
                     backend: str, mode_key) -> str | None:
        """Pre-trace content address of a compile request, or None when
        the script is not safely addressable (a closure cell without a
        stable fingerprint) — the caller then skips the program layer
        and relies on the plan layer, which keys on the actual trace."""
        code = getattr(script, "__code__", None)
        if code is not None:
            consts = tuple(c.co_code if hasattr(c, "co_code") else repr(c)
                           for c in code.co_consts)
            ident = (getattr(script, "__module__", ""),
                     getattr(script, "__qualname__", ""),
                     code.co_code, repr(consts), repr(code.co_names))
            cells = getattr(script, "__closure__", None) or ()
            prints = [self._cell_fingerprint(c.cell_contents) for c in cells]
            if any(p is None for p in prints):
                return None
            ident += (repr(prints),)
        else:
            ident = (repr(script),)
        payload = repr((ident,
                        sorted((k, tuple(v)) for k, v in input_shapes.items()),
                        str(self.dtype), self._config_key(backend, mode_key)))
        return hashlib.sha256(payload.encode()).hexdigest()

    def _plan_key(self, g: graph.Graph, backend: str, mode_key) -> str:
        payload = repr((graph_signature(g),
                        self._config_key(backend, mode_key)))
        return hashlib.sha256(payload.encode()).hexdigest()

    # -- shared plan resolution ---------------------------------------------
    def _verify_served_plan(self, plan, g: graph.Graph,
                            plan_key: str | None) -> bool:
        """The always-on safety net (DESIGN.md §11): every cache-served
        plan — in-memory or disk-deserialized, possibly written by
        another process — is verified BEFORE codegen can execute it.
        Default depth is the quick subset (structural + signature +
        coverage, microseconds); under ``verify`` it is the full
        graph-bound pass.  A rejected plan is *healed*: dropped from
        memory and disk (so first-writer-wins can republish) and the
        caller recompiles — never raises, never executes the bad plan.
        """
        from ..analysis.checks import verify_plan, verify_plan_quick
        diags = (verify_plan(plan, g, hw=self.hw) if self.verify
                 else verify_plan_quick(plan, g))
        errors = [d for d in diags if d.is_error]
        if not errors:
            return True
        log.warning(
            "cache-served plan rejected by static verification; healing "
            "(drop + recompile): %s",
            "; ".join(d.format() for d in errors))
        if self.cache is not None and plan_key is not None:
            self.cache.drop_plan(plan_key)
        return False

    def _plan_for(self, g: graph.Graph, mode, backend: str, mode_key):
        """Plan-cache-consulting search shared by every entry point
        (unbatched / batched / sharded — they key plans identically, so
        a plan found by one is a hit for all).  A plan-layer hit for
        ``mode='autotune'`` performs zero measurements — the winner was
        already decided (possibly by another process via the disk
        layer)."""
        cache = self.cache
        plan = plan_key = None
        if cache is not None:
            plan_key = self._plan_key(g, backend, mode_key)
            plan = cache.get_plan(plan_key)
            if plan is not None and \
                    not self._verify_served_plan(plan, g, plan_key):
                plan = None                      # healed: fall through
        if plan is None:
            space = self.space(g)
            if mode == "autotune":
                _, plan = self._autotune(space, backend)
            else:
                combo = self.search(space, mode, backend=backend)
                plan = build_plan(g, combo, backend=backend)
            if self.verify:
                # a freshly searched plan failing the full pass is a
                # compiler bug, not a stale cache entry — surface it
                # (and never publish it to the cache)
                from ..analysis.checks import verify_plan
                raise_if_errors(verify_plan(plan, g, hw=self.hw))
            if cache is not None:
                cache.put_plan(plan_key, plan)
        return plan

    @staticmethod
    def _bucket_label(input_shapes: dict[str, Sequence[int]]) -> str:
        dims = [d for v in input_shapes.values() for d in v]
        return str(max(dims)) if dims else "scalar"

    # -- main entry points ---------------------------------------------------
    def compile(self, script: Callable, input_shapes: dict[str, Sequence[int]],
                mode: str = "best", backend: str | None = None,
                report: bool = False):
        """Compile a sequence script into one jitted whole-program
        function (pipeline stages: DESIGN.md §1; caching: §5).

        Args:
          script: a sequence script ``(g, **vars) -> outputs`` built
            from elementary calls (e.g. ``REGISTRY["GEMVER"].script``).
          input_shapes: ``{input name: shape tuple}`` — the trace is
            shape-specialized, like the paper's generated CUDA.
          mode: ``'best'`` (predicted-best combination, bitmask-DP /
            beam search), ``'unfused'`` (CUBLAS-style one-kernel-per-
            call baseline), ``'autotune'`` (measure the top
            ``autotune_budget`` predicted candidates and take the
            measured winner — the paper's §5.2 empirical search,
            DESIGN.md §8; measurements persist in the cache's
            measured-cost table, so a repeat compile measures nothing),
            or an integer rank into the ``t_pred``-sorted combination
            stream.
          backend: ``'jnp'`` or ``'pallas'`` (defaults to the
            compiler's).
          report: diagnostic path — always runs the full pipeline
            (bypassing both cache layers) and returns
            ``(program, CompileReport)``.

        Returns:
          A ``CompiledProgram``; calling it with keyword inputs runs
          the whole sequence as a single XLA dispatch.

        Raises:
          ValueError: unknown or bool ``mode``, or an integer rank with
            no matching combination (empty space, negative, or past the
            number of legal combinations).

        Example::

            cc = FusionCompiler()
            prog = cc.compile(REGISTRY["AXPYDOT"].script,
                              REGISTRY["AXPYDOT"].shapes(1024))
            z, r = prog(w=w, v=v, u=u, alpha=np.float32(0.3))
        """
        backend = backend or self.backend
        self._check_backend(backend)
        mode_key = self._mode_key(mode)
        if report:
            return self._compile_report(script, input_shapes, mode, backend)

        cache = self.cache
        pkey = None
        if cache is not None:
            pkey = self._program_key(script, input_shapes, backend, mode_key)
            if pkey is not None:
                prog = cache.get_program(pkey)
                if prog is not None:
                    return prog

        g = self.trace(script, input_shapes)
        plan = self._plan_for(g, mode, backend, mode_key)
        prog = codegen.compile_plan(g, plan, hw=self.hw,
                                    interpret=self.interpret)
        if cache is not None and pkey is not None:
            cache.put_program(pkey, prog)
        return prog

    def compile_batched(self, script, input_shapes: dict[str, Sequence[int]],
                        max_batch: int = 8, mode: str = "best",
                        backend: str | None = None,
                        bucket: str | None = None) -> codegen.BatchedProgram:
        """Batched variant of :meth:`compile` for the serving engine.

        Args:
          script, input_shapes, mode, backend: as :meth:`compile`; the
            shapes describe ONE request — the returned program adds a
            leading batch axis to every input and output (scalars
            become ``(b,)`` vectors), executing a whole shape bucket of
            requests as ONE dispatch (vmap horizontal fusion,
            DESIGN.md §6).
          max_batch: advisory batch-size cap recorded on the program
            (jit re-traces per distinct batch size; the serving engine
            quantizes sizes to powers of two up to this).
          bucket: label for this compile in ``cache.stats.buckets``
            (per-bucket hit/latency telemetry); defaults to the largest
            input dimension, e.g. ``"1024"``.

        Returns:
          A ``BatchedProgram``.  The *plan* cache layer is shared with
          the unbatched path (same trace, same search, same key), so a
          bucket that was ever compiled — batched or not, this process
          or a previous one via the disk layer — never re-searches; the
          *program* layer keys the batched wrapper separately.

        Raises:
          ValueError: as :meth:`compile`.

        Example::

            prog = cc.compile_batched(seq.script, seq.shapes(1024))
            z, r = prog(w=W, v=V, u=U, alpha=np.ones(8, np.float32))
            # W/V/U: (8, 1024); z: (8, 1024); r: (8,)
        """
        backend = backend or self.backend
        self._check_backend(backend)
        mode_key = self._mode_key(mode)
        bucket = bucket or self._bucket_label(input_shapes)
        t0 = time.perf_counter()
        cache = self.cache
        pkey = None
        if cache is not None:
            pkey = self._program_key(script, input_shapes, backend,
                                     ("batched", mode_key, max_batch))
            if pkey is not None:
                prog = cache.get_program(pkey)
                if prog is not None:
                    cache.stats.record_bucket(
                        bucket, hit=True, seconds=time.perf_counter() - t0)
                    return prog

        g = self.trace(script, input_shapes)
        plan = self._plan_for(g, mode, backend, mode_key)
        prog = codegen.compile_plan_batched(g, plan, max_batch=max_batch,
                                            hw=self.hw,
                                            interpret=self.interpret)
        if cache is not None:
            if pkey is not None:
                cache.put_program(pkey, prog)
            cache.stats.record_bucket(
                bucket, hit=False, seconds=time.perf_counter() - t0)
        return prog

    def compile_packed(self, members, max_batch: int = 8, mode: str = "best",
                       backend: str | None = None, bucket: str | None = None
                       ) -> codegen.PackedDispatch:
        """Multi-graph packed compile (DESIGN.md §9): N member scripts
        become ONE jitted dispatch — the cross-sequence horizontal
        fusion a mixed serving drain needs.

        Args:
          members: sequence of ``(script, input_shapes)`` pairs, one
            per pack member.  Each member runs the normal per-graph
            pipeline (trace → plan, sharing the plan cache with every
            other entry point), so its fusion decisions are exactly
            the unpacked ones; only the dispatch is merged.
          max_batch, mode, backend: as :meth:`compile_batched`; every
            member input is batched, and members may carry different
            batch sizes at call time.
          bucket: label for ``cache.stats.buckets`` telemetry
            (defaults to a ``pack/``-prefixed member list).

        Returns:
          A ``codegen.PackedDispatch`` — a thin caller-order view over
          the cached canonical ``PackedProgram``.  Program and packed-
          plan layers are keyed on the *sorted* member plan
          fingerprints, so any compile of the same member mix — in any
          order, any process via the disk layer — is a cache hit; only
          the permutation is rebuilt.

        Raises:
          ValueError: empty member list, or as :meth:`compile` per
            member.

        Example::

            axpy, vadd = REGISTRY["AXPYDOT"], REGISTRY["VADD"]
            pack = cc.compile_packed([(axpy.script, axpy.shapes(256)),
                                      (vadd.script, vadd.shapes(256))])
            (z, r), (x,) = pack([axpy_batch, vadd_batch])  # ONE dispatch
        """
        if not members:
            raise ValueError("compile_packed needs at least one member")
        backend = backend or self.backend
        self._check_backend(backend)
        mode_key = self._mode_key(mode)
        t0 = time.perf_counter()
        cache = self.cache

        graphs, plans = [], []
        for script, input_shapes in members:
            g = self.trace(script, input_shapes)
            plans.append(self._plan_for(g, mode, backend, mode_key))
            graphs.append(g)

        perm = canonical_pack_order(plans)
        sorted_graphs = [graphs[i] for i in perm]
        sorted_plans = [plans[i] for i in perm]
        psig = pack_signature([plan_fingerprint(p) for p in plans])
        config = self._config_key(backend, mode_key)
        bucket = bucket or f"pack/{psig[:12]}"

        prog = pkey = None
        if cache is not None:
            pkey = hashlib.sha256(
                repr((psig, config, ("packed", max_batch))).encode()
            ).hexdigest()
            prog = cache.get_program(pkey)
            if prog is not None:
                cache.stats.record_bucket(
                    bucket, hit=True, seconds=time.perf_counter() - t0)
                return codegen.PackedDispatch(program=prog, perm=perm)

        packed = None
        if cache is not None:
            pack_plan_key = hashlib.sha256(
                repr((psig, config, "pack-plan")).encode()).hexdigest()
            packed = cache.get_packed_plan(pack_plan_key)
            if packed is not None and [plan_fingerprint(p)
                                       for p in packed.members] != \
                    [plan_fingerprint(p) for p in sorted_plans]:
                packed = None         # foreign entry under our key: rebuild
            if packed is not None:
                # always-on pack verification (DESIGN.md §11): member
                # structure + offset rebasing; under ``verify`` also the
                # full per-member graph-bound pass.  Heal on rejection.
                from ..analysis.checks import verify_pack
                errors = [d for d in verify_pack(
                    packed, sorted_graphs if self.verify else None,
                    hw=self.hw) if d.is_error]
                if errors:
                    log.warning(
                        "cache-served packed plan rejected by static "
                        "verification; healing (drop + rebuild): %s",
                        "; ".join(d.format() for d in errors))
                    cache.drop_packed_plan(pack_plan_key)
                    packed = None
        if packed is None:
            packed = build_packed_plan(plans)
            if self.verify:
                from ..analysis.checks import verify_pack
                raise_if_errors([d for d in verify_pack(
                    packed, sorted_graphs, hw=self.hw) if d.is_error])
            if cache is not None:
                cache.put_packed_plan(pack_plan_key, packed)
        prog = codegen.compile_plan_packed(sorted_graphs, packed,
                                           max_batch=max_batch, hw=self.hw,
                                           interpret=self.interpret)
        if cache is not None:
            if pkey is not None:
                cache.put_program(pkey, prog)
            cache.stats.record_bucket(
                bucket, hit=False, seconds=time.perf_counter() - t0)
        return codegen.PackedDispatch(program=prog, perm=perm)

    def compile_sharded(self, script, input_shapes: dict[str, Sequence[int]],
                        mesh, axis: str = "data", max_batch: int = 8,
                        mode: str = "best", backend: str | None = None,
                        bucket: str | None = None) -> codegen.BatchedProgram:
        """Sharded variant of :meth:`compile_batched` for multi-device
        serving (DESIGN.md §7): the vmap-lifted whole-program function
        is additionally ``shard_map``-lifted over the ``axis`` replicas
        of ``mesh``, so one global batch executes as contiguous
        per-replica row blocks with no cross-replica communication.

        Args:
          script, input_shapes, max_batch, mode, backend, bucket: as
            :meth:`compile_batched`.
          mesh: mesh holding the replica axis (``launch.mesh.
            make_data_mesh()`` for a pure replica mesh).
          axis: the mesh axis to spread the batch over.

        Returns:
          A ``BatchedProgram`` whose batch sizes must be multiples of
          the replica count (``ShardedServingEngine`` quantizes its
          dispatches to guarantee this).  When ``axis`` has size 1 this
          is exactly :meth:`compile_batched` (single-device fallback).
          The plan layer is shared with both other entry points; the
          program layer keys on the mesh topology as well, so fleets
          with heterogeneous meshes don't alias programs.

        Raises:
          ValueError: as :meth:`compile`, or when ``mesh`` lacks
            ``axis``.
        """
        from ..dist.sharding import mesh_axis_sizes, mesh_fingerprint, \
            shard_program

        backend = backend or self.backend
        self._check_backend(backend)
        mode_key = self._mode_key(mode)
        bucket = bucket or self._bucket_label(input_shapes)
        sizes = mesh_axis_sizes(mesh)
        if axis not in sizes:
            raise ValueError(f"mesh {tuple(sizes)} has no {axis!r} axis")
        if sizes[axis] == 1:
            return self.compile_batched(script, input_shapes,
                                        max_batch=max_batch, mode=mode,
                                        backend=backend, bucket=bucket)
        t0 = time.perf_counter()
        cache = self.cache
        pkey = None
        if cache is not None:
            pkey = self._program_key(
                script, input_shapes, backend,
                ("sharded", mode_key, max_batch, axis,
                 mesh_fingerprint(mesh)))
            if pkey is not None:
                prog = cache.get_program(pkey)
                if prog is not None:
                    cache.stats.record_bucket(
                        bucket, hit=True, seconds=time.perf_counter() - t0)
                    return prog
        base = self.compile_batched(script, input_shapes,
                                    max_batch=max_batch, mode=mode,
                                    backend=backend, bucket=bucket)
        prog = shard_program(base, mesh, axis)
        if cache is not None and pkey is not None:
            cache.put_program(pkey, prog)
        return prog

    def _compile_report(self, script, input_shapes, mode, backend):
        t0 = time.perf_counter()
        g = self.trace(script, input_shapes)
        t1 = time.perf_counter()
        space = self.space(g)
        combo = self.search(space, mode, backend=backend)
        t2 = time.perf_counter()
        plan = build_plan(g, combo, backend=backend)
        prog = codegen.compile_plan(g, plan, hw=self.hw,
                                    interpret=self.interpret)
        t3 = time.perf_counter()
        rep = CompileReport(
            n_fusions=len(space.fusions), n_impls=space.n_impls,
            n_combinations=len(scheduler.enumerate_combinations(space,
                                                                limit=5000)),
            t_trace_s=t1 - t0, t_space_s=t2 - t1, t_codegen_s=t3 - t2,
            best=scheduler.best_combination(space),
            unfused=scheduler.unfused_combination(space))
        return prog, rep

    def compile_all(self, script: Callable,
                    input_shapes: dict[str, Sequence[int]],
                    limit: int = 256, backend: str | None = None):
        """Compile the ``limit`` best combinations (predicted order) —
        the raw material of empirical search (paper §5.2; the managed
        version is ``mode="autotune"``).

        Routed through the shared cache machinery: candidate ``i`` uses
        the same program/plan keys as ``compile(..., mode=i)``, so a
        repeat ``compile_all`` — or a prior integer-mode compile — is
        served from cache, every consultation lands in ``cache.stats``,
        and the optimization space is only rebuilt when some candidate
        actually misses both layers.

        Returns:
          ``[(Combination, CompiledProgram), ...]`` — at most ``limit``
          entries, fewer when the space has fewer legal combinations.
        """
        backend = backend or self.backend
        self._check_backend(backend)
        cache = self.cache
        g = self.trace(script, input_shapes)
        space = combos = None
        out = []
        for i in range(limit):
            mode_key = self._mode_key(i)
            prog = pkey = None
            if cache is not None:
                pkey = self._program_key(script, input_shapes, backend,
                                         mode_key)
                if pkey is not None:
                    prog = cache.get_program(pkey)
            if prog is None:
                plan = plan_key = None
                if cache is not None:
                    plan_key = self._plan_key(g, backend, mode_key)
                    plan = cache.get_plan(plan_key)
                if plan is None:
                    if combos is None:
                        space = self.space(g)
                        combos = scheduler.enumerate_combinations(
                            space, limit=limit)
                    if i >= len(combos):
                        break
                    plan = build_plan(g, combos[i], backend=backend)
                    if cache is not None:
                        cache.put_plan(plan_key, plan)
                prog = codegen.compile_plan(g, plan, hw=self.hw,
                                            interpret=self.interpret)
                if cache is not None and pkey is not None:
                    cache.put_program(pkey, prog)
            impls = tuple(prog.group_impls)
            out.append((Combination(impls=impls,
                                    t_pred=sum(im.t_pred for im in impls)),
                        prog))
        return out

    def oracle(self, script: Callable, input_shapes: dict[str, Sequence[int]]
               ) -> Callable:
        g = self.trace(script, input_shapes)

        def run(**inputs):
            return codegen.execute_dense(g, inputs)

        return run
