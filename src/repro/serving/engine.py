"""Batched serving engine (DESIGN.md §6).

The paper's fusion win is amortizing memory traffic across *calls* that
share data; a serving workload offers the same win across *requests*.
Batching concurrent requests of one sequence is horizontal fusion in the
sense of Li et al. (PAPERS.md): N requests of the same shape bucket
execute as ONE dispatch of a ``jax.vmap``-lifted whole-program function.

The engine takes ``(sequence, n, inputs)`` requests off a queue and:

1. **buckets** — rounds ``n`` up to the next power of two (floor
   ``min_bucket``), so heterogeneous sizes collapse onto a handful of
   compiled shapes; at most one plan is ever searched per
   ``(sequence, bucket)`` (the plan cache key), and at most one XLA
   program per ``(sequence, bucket, batch-size-class)``;
2. **pads** — fills each input up to the bucket shape with a
   *reduction-safe* value: the identity of the graph's reduction monoid
   in the input's dtype (0 for SUM, ±inf / iinfo bounds for MAX/MIN —
   ``Monoid.identity_for``), so padded lanes are invisible to the
   reductions and the unpadded slice of every output is exactly what an
   unpadded run would produce; graphs with no safe identity (mixed
   monoids, non-zero-preserving maps into reductions — LM decode
   attention is both) fall back to *per-lane masking*: the script is
   re-traced through ``core.masking`` with an extra ``_mask`` input and
   every reduction ignores padded lanes explicitly (DESIGN.md §10);
3. **groups** — same-``(sequence, bucket)`` requests form batches of up
   to ``max_batch`` (batch sizes rounded to powers of two to bound jit
   re-traces), executed by a ``BatchedProgram``;
4. **packs** — the per-``(sequence, bucket)`` batches pending in one
   drain cycle are packed, equal batch-size classes together, into a
   single *multi-graph* dispatch (``FusionCompiler.compile_packed``,
   DESIGN.md §9): one jitted call executes several different sequences'
   batches side by side, bitwise-equal to dispatching them separately.
   ``max_pack`` bounds members per pack (1 disables packing); a key
   whose program is still cold dispatches unpacked this cycle so the
   pack trace never serializes behind a fresh member compile;
5. **overlaps** — all batches are dispatched before any result is
   materialized, so host-side batch assembly of batch *k+1* runs while
   the device executes batch *k* (JAX async dispatch).

Outputs are sliced back to each request's true ``n`` before delivery.

``ShardedServingEngine`` (DESIGN.md §7) keeps the same pipeline but
``shard_map``s every dispatch over the ``data`` axis of a device mesh,
spreading a global batch across replicas as contiguous row blocks.
"""
from __future__ import annotations

import collections
import dataclasses
import time
from typing import Any, Mapping, Sequence

import numpy as np

from ..core import FusionCompiler
from ..core.codegen import BatchedProgram, PackedDispatch
from ..core.elementary import Monoid
from ..core.graph import Graph, trace
from ..core.masking import (MASK_INPUT, mask_row, masked_wrapper,
                            padded_dims)


# ---------------------------------------------------------------------------
# shape buckets
# ---------------------------------------------------------------------------

def bucket_of(n: int, min_bucket: int = 128) -> int:
    """Next power of two >= n, floored at ``min_bucket``.

    ``min_bucket`` must itself be a power of two: a non-pow2 floor
    would silently yield non-pow2 buckets (e.g. floor 100 → buckets
    100, 200, 400 …), fragmenting the plan cache across nearby sizes
    instead of collapsing them."""
    if n <= 0:
        raise ValueError(f"request size must be positive, got {n}")
    if min_bucket < 1 or (min_bucket & (min_bucket - 1)):
        raise ValueError(
            f"min_bucket must be a power of two, got {min_bucket} "
            "(valid form: 1, 2, 4, 8, ...)")
    b = min_bucket
    while b < n:
        b *= 2
    return b


def _pow2_batch(k: int, max_batch: int) -> int:
    """Round a batch size up to a power of two, capped at ``max_batch``."""
    b = 1
    while b < k:
        b *= 2
    return min(b, max_batch)


# ---------------------------------------------------------------------------
# reduction-safe padding
# ---------------------------------------------------------------------------

def input_pad_values(g: Graph) -> dict[str, Any]:
    """Safe pad value per graph input.

    Padded lanes must be invisible to every reduction that (transitively)
    consumes them, so inputs are padded with the reduction monoid's
    identity, in each input's own dtype (``Monoid.identity_for`` —
    integer MAX/MIN graphs pad with iinfo bounds, not float ±inf) —
    see DESIGN.md §6.

    * SUM graphs pad with 0, which is sound through chains of
      ``pad_safe`` (zero-preserving) maps: the BLAS library is all
      multilinear in its array arguments (``a*x+y``, ``w-a*v``, ``A@x``
      partials, rank-2 updates, ...), so all-zero lanes stay zero on
      the way into the reduction.  A non-``pad_safe`` call (``exp``
      maps 0 to 1) feeding a reduction voids that invariant.
    * MAX/MIN graphs pad with their identity, which is NOT preserved by
      arbitrary maps (``a*x`` with ``a<0`` flips -inf to +inf;
      ``w - a*v`` on two -inf lanes is NaN), so the identity is only
      accepted when every reduction reads graph inputs *directly*.
    * A graph mixing different monoids has no single safe pad value.

    Every rejection raises ``ValueError`` mentioning "mask": the
    serving engine catches it and re-traces the script through the
    per-lane masking rewrite (``core.masking``, DESIGN.md §10).
    """
    monoids = {c.elem.monoid for c in g.calls if c.elem.is_reduction}
    if len(monoids) > 1:
        raise ValueError(
            f"graph mixes reduction monoids "
            f"{sorted(m.value for m in monoids)}: no single padding "
            "identity is reduction-safe — mask instead")
    if monoids and monoids != {Monoid.SUM}:
        unsafe = [c for c in g.calls if c.elem.is_reduction
                  and any(not a.is_input for a in c.args)]
        if unsafe:
            names = ", ".join(c.elem.name for c in unsafe)
            raise ValueError(
                f"non-SUM reduction(s) ({names}) consume computed "
                "values: identity padding is not preserved through "
                "maps — mask instead")
    else:
        # SUM-only: identity padding is sound iff every call on a path
        # into a reduction is zero-preserving (pad_safe)
        feeding: set = set()
        for c in reversed(g.calls):
            if c.elem.is_reduction or c.out in feeding:
                feeding.update(c.args)
        unsafe = [c for c in g.calls
                  if not c.elem.pad_safe and c.out in feeding]
        if unsafe:
            names = ", ".join(sorted({c.elem.name for c in unsafe}))
            raise ValueError(
                f"non-pad_safe call(s) ({names}) feed a reduction: "
                "zero padding is not preserved through them — mask "
                "instead")
    m = next(iter(monoids)) if monoids else Monoid.SUM
    return {v.name: m.identity_for(v.dtype) for v in g.inputs}


def pad_to_shape(x: np.ndarray, shape: Sequence[int], fill: float) -> np.ndarray:
    """Embed ``x`` at the origin of a ``fill``-initialized ``shape``."""
    x = np.asarray(x)
    shape = tuple(shape)
    if x.shape == shape:
        return x
    if x.ndim != len(shape) or any(a > b for a, b in zip(x.shape, shape)):
        raise ValueError(f"cannot pad {x.shape} to {shape}")
    out = np.full(shape, fill, dtype=x.dtype)
    out[tuple(slice(s) for s in x.shape)] = x
    return out


# ---------------------------------------------------------------------------
# requests
# ---------------------------------------------------------------------------

@dataclasses.dataclass
class Request:
    rid: int
    sequence: str
    n: int
    inputs: Mapping[str, Any]
    t_submit: float = 0.0          # perf_counter at submission


@dataclasses.dataclass
class RequestResult:
    rid: int
    sequence: str
    n: int
    bucket: int
    batch_size: int                # real requests in the dispatch
    outputs: tuple[np.ndarray, ...]  # sliced back to the request's n
    latency_s: float
    queue_wait_s: float = 0.0      # submit -> dispatch wait


# ---------------------------------------------------------------------------
# the engine
# ---------------------------------------------------------------------------

class ServingEngine:
    """Single-device batched serving engine (DESIGN.md §6).

    Args:
      compiler: the ``FusionCompiler`` to build bucket programs with
        (defaults to a fresh one sharing the process-wide plan cache).
      max_batch: largest requests-per-dispatch; batch sizes quantize to
        powers of two up to this, bounding jit re-traces.
      min_bucket: floor of the power-of-two shape buckets.
      registry: ``{name: Program}`` of servable sequences (defaults to
        the paper's ``programs.BLAS``).
      mode: search mode for bucket compiles (``"best"`` default;
        ``"autotune"`` measures the compiler's ``autotune_budget`` top
        candidates per bucket at warm/compile time — DESIGN.md §8 —
        and serves the measured winner thereafter).
      max_pack: most ``(sequence, bucket)`` batches merged into one
        packed dispatch per drain round (DESIGN.md §9); ``1`` disables
        packing and restores one dispatch per batch.
      backend: ``'jnp'`` or ``'pallas'`` — per-engine override passed
        through to every bucket/pack compile; ``None`` (default) uses
        the compiler's own backend.  Masked programs compile on either
        backend (the masking elementaries are ordinary maps).

    Example::

        engine = ServingEngine(max_batch=8)
        engine.warm("GEMVER", [1000, 2048])
        engine.submit("GEMVER", 1000, inputs)   # any request size
        (result,) = engine.drain()              # sliced back to n=1000
    """

    def __init__(self, compiler: FusionCompiler | None = None,
                 max_batch: int = 8, min_bucket: int = 128,
                 registry: Mapping[str, Any] | None = None,
                 mode: str = "best", max_pack: int = 8,
                 backend: str | None = None):
        if registry is None:
            from ..programs import BLAS
            registry = BLAS
        if max_pack < 1:
            raise ValueError(f"max_pack must be >= 1, got {max_pack}")
        if backend is not None:
            # RPL401 at the engine boundary: an unknown backend would
            # otherwise surface requests deep inside a bucket compile
            FusionCompiler._check_backend(backend)
        self.compiler = compiler or FusionCompiler()
        self.max_batch = max_batch
        self.min_bucket = min_bucket
        self.mode = mode
        self.max_pack = max_pack
        #: per-engine backend override ('jnp' / 'pallas'); None defers
        #: to the compiler's own default
        self.backend = backend
        self.registry = registry
        self._programs: dict[tuple[str, int], BatchedProgram] = {}
        # (script, shapes, pad values, masked?) per key — the masked
        # fallback decision, made once per (sequence, bucket)
        self._specs: dict[tuple[str, int], tuple] = {}
        self._pad_values: dict[tuple[str, int], dict[str, Any]] = {}
        self._packs: dict[tuple[tuple[str, int], ...], PackedDispatch] = {}
        self._queue: list[Request] = []
        self._rid = 0
        # engine-side telemetry (compile telemetry lives on cache.stats)
        self.n_requests = 0
        self.n_dispatches = 0
        self.n_padded_rows = 0     # dummy rows added by pow2 rounding
        self.n_packed_dispatches = 0   # dispatches that were packs
        self.n_packed_members = 0      # member batches those packs carried

    # -- compilation --------------------------------------------------------
    def bucket_of(self, n: int) -> int:
        return bucket_of(n, self.min_bucket)

    def _compile_specs(self, sequence: str, bucket: int) -> tuple:
        """``(script, shapes, pad_values, masked)`` for one key.

        Decides — once per ``(sequence, bucket)`` — how padded lanes
        stay invisible to the graph's reductions:

        1. a registry entry carrying explicit ``pad_values`` is taken
           at its word;
        2. otherwise ``input_pad_values`` analyzes a trace for a
           whole-graph identity (DESIGN.md §6);
        3. when the analysis refuses (mixed monoids, map-into-MAX,
           non-``pad_safe`` maps into SUM), the script is re-wrapped
           through the per-lane masking rewrite (``core.masking``,
           DESIGN.md §10): the shape dict gains the rank-1 ``_mask``
           input and every input simply zero-fills.
        """
        key = (sequence, bucket)
        spec = self._specs.get(key)
        if spec is None:
            seq = self.registry[sequence]
            shapes = seq.shapes(bucket)
            explicit = getattr(seq, "pad_values", None)
            if explicit is not None:
                spec = (seq.script, shapes, dict(explicit), False)
            else:
                try:
                    pads = input_pad_values(
                        trace(seq.script, shapes, dtype=self.compiler.dtype))
                    spec = (seq.script, shapes, pads, False)
                except ValueError:
                    dims = padded_dims(shapes, seq.shapes(bucket * 2))
                    script, shapes = masked_wrapper(seq.script, shapes, dims)
                    spec = (script, shapes, {n: 0.0 for n in shapes}, True)
            self._specs[key] = spec
        return spec

    def _get_program(self, sequence: str, bucket: int
                     ) -> tuple[BatchedProgram, dict[str, Any]]:
        key = (sequence, bucket)
        prog = self._programs.get(key)
        if prog is None:
            script, shapes, pads, _ = self._compile_specs(sequence, bucket)
            prog = self.compiler.compile_batched(
                script, shapes, max_batch=self.max_batch,
                mode=self.mode, backend=self.backend,
                bucket=f"{sequence}/{bucket}")
            self._pad_values[key] = pads
            self._programs[key] = prog
        return prog, self._pad_values[key]

    def _get_pack(self, members: tuple[tuple[str, int], ...]) -> PackedDispatch:
        """Packed dispatch for an ordered tuple of (sequence, bucket)
        member keys; memoized per exact member tuple (the compiler's
        program cache additionally collapses reordered mixes)."""
        dispatch = self._packs.get(members)
        if dispatch is None:
            dispatch = self.compiler.compile_packed(
                [self._compile_specs(s, b)[:2] for s, b in members],
                max_batch=self.max_batch, mode=self.mode,
                backend=self.backend,
                bucket="pack/" + "+".join(f"{s}/{b}" for s, b in members))
            self._packs[members] = dispatch
        return dispatch

    def _form_packs(self, units: list, cold: set) -> tuple[list, list]:
        """Split drain units — ``(key, chunk, batch)`` triples — into
        packs (lists of >= 2 units sharing a batch-size class) and
        leftovers dispatched unpacked.

        Per batch class the formation is round-robin: one unit per
        sorted ``(sequence, bucket)`` key per round, rounds chunked at
        ``max_pack``.  Uniform traffic over the warmed key set thus
        repeats ONE composition every round — the composition
        ``warm()`` pre-traces.  Cold keys (``cold``) always dispatch
        unpacked this cycle."""
        if self.max_pack < 2:
            return [], list(units)
        singles = [u for u in units if u[0] in cold]
        by_batch: dict[int, list] = {}
        for u in units:
            if u[0] not in cold:
                by_batch.setdefault(u[2], []).append(u)
        packs = []
        for batch in sorted(by_batch):
            fifo: dict[tuple[str, int], list] = {}
            for u in by_batch[batch]:
                fifo.setdefault(u[0], []).append(u)
            while fifo:
                rnd = [fifo[k].pop(0) for k in sorted(fifo)]
                for k in [k for k, q in fifo.items() if not q]:
                    del fifo[k]
                for i in range(0, len(rnd), self.max_pack):
                    part = rnd[i:i + self.max_pack]
                    if len(part) >= 2:
                        packs.append(part)
                    else:
                        singles.extend(part)
        return packs, singles

    def _dispatch_batch(self, k: int) -> int:
        """Quantized dispatch size for ``k`` queued requests."""
        return _pow2_batch(k, self.max_batch)

    def _trace_sizes(self) -> list[int]:
        """Every batch-size class ``_dispatch_batch`` can produce."""
        sizes, bs = {self.max_batch}, 1
        while bs < self.max_batch:
            sizes.add(bs)
            bs *= 2
        return sorted(sizes)

    def _note_dispatch(self, n_real: int, batch: int) -> None:
        """Telemetry hook: one dispatch of ``batch`` rows, ``n_real``
        of them real requests (subclasses track replica routing)."""

    @staticmethod
    def _dummy_inputs(graph, bs: int) -> dict[str, np.ndarray]:
        """Zero-filled warm-up batch; the ``_mask`` input (if the
        program is masked) gets all-ones so warm-up lanes are all valid
        — an all-masked row would divide by an empty softmax sum."""
        return {v.name: (np.ones if v.name == MASK_INPUT else np.zeros)(
                    (bs,) + v.shape, v.dtype)
                for v in graph.inputs}

    def warm(self, sequence: str, ns: Sequence[int],
             trace_batches: bool = True,
             trace_packs: bool = True) -> list[int]:
        """Pre-compile every bucket the sizes ``ns`` map to; returns the
        bucket list.  ``trace_batches`` additionally executes a dummy
        dispatch at every batch-size class ``drain`` can produce, so
        serving never pays a jit trace either.  ``trace_packs`` does the
        same for the packed dispatches a drain over ALL warmed keys
        would form (re-run after the last ``warm`` call for full
        coverage — the compositions depend on the whole warmed set)."""
        buckets = sorted({self.bucket_of(n) for n in ns})
        for b in buckets:
            prog, _ = self._get_program(sequence, b)
            if not trace_batches:
                continue
            for bs in self._trace_sizes():
                dummy = self._dummy_inputs(prog.graph, bs)
                prog.block_until_ready(prog(**dummy))
        if trace_packs:
            self.warm_packs(trace_batches=trace_batches)
        return buckets

    def warm_packs(self, trace_batches: bool = True) -> list[tuple]:
        """Pre-build the pack compositions a drain over every warmed
        ``(sequence, bucket)`` key would form — sorted keys, chunked at
        ``max_pack``, exactly ``_form_packs``'s round shape — and (with
        ``trace_batches``) execute each at every batch-size class, so a
        warmed engine serving mixed traffic over the warmed set never
        jit-traces a pack on the hot path.  Returns the member tuples
        warmed."""
        if self.max_pack < 2:
            return []
        keys = sorted(self._programs)
        warmed = []
        for i in range(0, len(keys), self.max_pack):
            members = tuple(keys[i:i + self.max_pack])
            if len(members) < 2:
                continue
            dispatch = self._get_pack(members)
            warmed.append(members)
            if not trace_batches:
                continue
            for bs in self._trace_sizes():
                member_inputs = [
                    self._dummy_inputs(self._programs[key].graph, bs)
                    for key in members]
                dispatch.block_until_ready(dispatch(member_inputs))
        return warmed

    # -- request intake -----------------------------------------------------
    def submit(self, sequence: str, n: int, inputs: Mapping[str, Any],
               rid: int | None = None) -> Request:
        if sequence not in self.registry:
            raise KeyError(f"unknown sequence {sequence!r}; "
                           f"choose from {', '.join(self.registry)}")
        if rid is None:
            rid = self._rid
        self._rid = max(self._rid, rid) + 1
        req = Request(rid=rid, sequence=sequence, n=n, inputs=inputs,
                      t_submit=time.perf_counter())
        self._queue.append(req)
        self.n_requests += 1
        return req

    # -- execution ----------------------------------------------------------
    def _assemble(self, chunk: list[Request], sequence: str, bucket: int,
                  batch: int, pad_vals: dict[str, Any]) -> dict[str, np.ndarray]:
        _, shapes, _, masked = self._compile_specs(sequence, bucket)
        self.n_padded_rows += batch - len(chunk)
        out = {}
        for name, shape in shapes.items():
            if masked and name == MASK_INPUT:
                # synthesized, not taken from the request: 1.0 on the
                # first n lanes, 0.0 on padding
                rows = [mask_row(shape[0], r.n) for r in chunk]
            else:
                rows = [pad_to_shape(np.asarray(r.inputs[name]), shape,
                                     pad_vals[name]) for r in chunk]
            # fill the pow2-rounded batch by repeating row 0: real data,
            # so no NaN/inf can leak out of speculative lanes
            rows += [rows[0]] * (batch - len(rows))
            out[name] = np.stack(rows)
        return out

    def _record_waits(self, chunk: list[Request], t_disp: float) -> list[float]:
        """Submit -> dispatch wait per request, mirrored into the cache
        telemetry window (``CacheStats.queue_wait_percentiles``)."""
        waits = [max(0.0, t_disp - r.t_submit) for r in chunk]
        cache = self.compiler.cache
        if cache is not None:
            for w in waits:
                cache.stats.record_queue_wait(w)
        return waits

    def drain(self) -> list[RequestResult]:
        """Execute everything queued: group by (sequence, bucket), chunk
        into batches, pack same-batch-class batches across sequences
        (``max_pack`` per dispatch), dispatch ALL of it (async), then
        materialize."""
        queue, self._queue = self._queue, []
        groups: dict[tuple[str, int], list[Request]] = collections.OrderedDict()
        for req in queue:
            groups.setdefault((req.sequence, self.bucket_of(req.n)),
                              []).append(req)

        # cold keys (no compiled program yet) dispatch unpacked this
        # cycle: packing them would stall the whole pack behind a fresh
        # member compile; by the next drain they are warm and packable
        cold = {key for key in groups if key not in self._programs}

        # resolve every program before dispatching anything: a compile
        # failure for one group (e.g. an unpaddable graph) must not drop
        # the other queued requests
        try:
            progs = {key: self._get_program(*key) for key in groups}
        except Exception:
            self._queue = queue + self._queue
            raise

        units = []                       # (key, chunk, batch) triples
        for key, reqs in groups.items():
            for i in range(0, len(reqs), self.max_batch):
                chunk = reqs[i:i + self.max_batch]
                units.append((key, chunk, self._dispatch_batch(len(chunk))))
        packs, singles = self._form_packs(units, cold)

        in_flight = []
        for pack_units in packs:
            dispatch = self._get_pack(tuple(u[0] for u in pack_units))
            member_inputs = [
                self._assemble(chunk, key[0], key[1], batch, progs[key][1])
                for key, chunk, batch in pack_units]
            t_disp = time.perf_counter()
            outs_list = dispatch(member_inputs)   # async dispatch — no block
            self.n_dispatches += 1
            self.n_packed_dispatches += 1
            self.n_packed_members += len(pack_units)
            for (key, chunk, batch), outs in zip(pack_units, outs_list):
                self._note_dispatch(len(chunk), batch)
                waits = self._record_waits(chunk, t_disp)
                in_flight.append((key[0], key[1], chunk, batch,
                                  tuple(outs), waits))
        for key, chunk, batch in singles:
            prog, pad_vals = progs[key]
            args = self._assemble(chunk, key[0], key[1], batch, pad_vals)
            t_disp = time.perf_counter()
            outs = prog(**args)          # async dispatch — no block
            if not isinstance(outs, tuple):
                outs = (outs,)
            self.n_dispatches += 1
            self._note_dispatch(len(chunk), batch)
            waits = self._record_waits(chunk, t_disp)
            in_flight.append((key[0], key[1], chunk, batch, outs, waits))

        results: list[RequestResult] = []
        for sequence, bucket, chunk, batch, outs, waits in in_flight:
            host = [np.asarray(o) for o in outs]    # blocks until ready
            t_done = time.perf_counter()
            for i, req in enumerate(chunk):
                sliced = tuple(
                    o[i][tuple(slice(req.n) if d == bucket else slice(None)
                               for d in o.shape[1:])]
                    for o in host)
                results.append(RequestResult(
                    rid=req.rid, sequence=req.sequence, n=req.n,
                    bucket=bucket, batch_size=len(chunk), outputs=sliced,
                    latency_s=t_done - req.t_submit,
                    queue_wait_s=waits[i]))
        return results

    def serve(self, requests: Sequence[tuple[str, int, Mapping[str, Any]]],
              rate_hz: float | None = None) -> list[RequestResult]:
        """Serve a workload of ``(sequence, n, inputs)`` tuples.

        ``rate_hz=None`` is closed-loop: everything is queued up front
        and drained in maximal batches.  A rate simulates an open-loop
        arrival process (one request every ``1/rate_hz`` seconds): the
        engine batches whatever has arrived each round, so batch sizes —
        and the latency/throughput trade — follow the offered load.
        """
        if rate_hz is None:
            for sequence, n, inputs in requests:
                self.submit(sequence, n, inputs)
            return self.drain()

        results: list[RequestResult] = []
        t0 = time.perf_counter()
        for i, (sequence, n, inputs) in enumerate(requests):
            t_arrival = t0 + i / rate_hz
            wait = t_arrival - time.perf_counter()
            if wait > 0:
                # the arrival gap: drain what's queued (overlapping with
                # the gap) or idle until the next request lands
                if self._queue:
                    results.extend(self.drain())
                wait = t_arrival - time.perf_counter()
                if wait > 0:
                    time.sleep(wait)
            self.submit(sequence, n, inputs)
        while self._queue:
            results.extend(self.drain())
        return results

    # -- telemetry ----------------------------------------------------------
    def stats(self) -> dict:
        cache = self.compiler.cache
        occupancy = (self.n_requests / (self.n_requests + self.n_padded_rows)
                     if self.n_requests else 0.0)
        return {
            "n_requests": self.n_requests,
            "n_dispatches": self.n_dispatches,
            "n_padded_rows": self.n_padded_rows,
            "batch_occupancy": occupancy,
            "max_pack": self.max_pack,
            "n_packed_dispatches": self.n_packed_dispatches,
            "n_packed_members": self.n_packed_members,
            "programs": sorted(f"{s}/{b}" for s, b in self._programs),
            "packs": sorted("+".join(f"{s}/{b}" for s, b in key)
                            for key in self._packs),
            "queue_wait": (cache.stats.queue_wait_percentiles()
                           if cache is not None else None),
            "cache": cache.stats.as_dict() if cache is not None else None,
        }


# ---------------------------------------------------------------------------
# sharded serving (DESIGN.md §7)
# ---------------------------------------------------------------------------

def replica_fill(n_real: int, batch: int, n_replicas: int) -> list[int]:
    """Real rows landing on each replica of a sharded dispatch.

    A dispatch of ``batch`` rows splits into contiguous blocks of
    ``batch // n_replicas``: replica ``j`` executes rows
    ``[j*batch/R, (j+1)*batch/R)``.  The first ``n_real`` rows are real
    requests, the rest padding, so the fill is front-loaded — with an
    uneven queue (``n_real`` not a multiple of the block) one replica
    runs partially full and later replicas may run pure padding.

    >>> replica_fill(5, 8, 4)      # 5 requests, 2-row blocks
    [2, 2, 1, 0]
    """
    per = batch // n_replicas
    return [max(0, min(per, n_real - j * per)) for j in range(n_replicas)]


class ShardedServingEngine(ServingEngine):
    """Multi-device serving: the §6 engine with every dispatch
    ``shard_map``-spread over the ``data`` axis of a mesh
    (DESIGN.md §7).

    Same bucketing, padding and batching as ``ServingEngine`` — the
    differences are (1) programs come from
    ``FusionCompiler.compile_sharded``, so one global batch executes as
    contiguous per-replica row blocks with no cross-replica
    communication, and (2) dispatch sizes quantize to
    ``n_replicas * 2**i`` so every replica gets an equal block
    (``replica_fill`` describes the routing; ``stats()['replica_rows']``
    tracks it).  On a 1-device mesh this degrades to exactly the base
    engine (same programs, same keys).

    Numerics: per-replica blocks of >= 2 rows produce bitwise-identical
    results to a single-device dispatch of the same global batch; 1-row
    blocks make XLA lower batched matmuls differently (correct within
    f32 roundoff, not bit-identical) — keep ``max_batch >= 2 *
    n_replicas`` when bit-stability across engine configs matters
    (tests/test_dist.py pins both properties).

    Packing is disabled (``max_pack`` is pinned to 1): packed programs
    are plain batched functions, not ``shard_map``-lowered, so a packed
    dispatch would silently bypass the mesh — DESIGN.md §9 records
    sharded packing as an open edge.

    Args:
      mesh: mesh with the replica axis (default:
        ``launch.mesh.make_data_mesh()`` over all local devices).
      axis: replica axis name (default ``"data"``).
      compiler, max_batch, min_bucket, registry, mode: as
        ``ServingEngine``; ``max_batch`` rounds up so it is
        ``n_replicas`` times a power of two.
    """

    def __init__(self, mesh=None, *, compiler: FusionCompiler | None = None,
                 max_batch: int = 8, min_bucket: int = 128,
                 registry: Mapping[str, Any] | None = None,
                 axis: str = "data", mode: str = "best",
                 backend: str | None = None):
        from ..dist.sharding import mesh_axis_sizes
        if mesh is None:
            from ..launch.mesh import make_data_mesh
            mesh = make_data_mesh()
        sizes = mesh_axis_sizes(mesh)
        if axis not in sizes:
            raise ValueError(f"mesh {tuple(sizes)} has no {axis!r} axis")
        self.mesh = mesh
        self.axis = axis
        self.n_replicas = sizes[axis]
        # per-replica row blocks are powers of two; global batch sizes
        # are n_replicas * block, so shard_map splits evenly
        self.rows_cap = _pow2_batch(
            max(1, -(-max_batch // self.n_replicas)), max_batch)
        super().__init__(compiler=compiler,
                         max_batch=self.n_replicas * self.rows_cap,
                         min_bucket=min_bucket, registry=registry,
                         mode=mode, max_pack=1, backend=backend)
        self.replica_rows = [0] * self.n_replicas

    def _get_program(self, sequence: str, bucket: int
                     ) -> tuple[BatchedProgram, dict[str, Any]]:
        if self.n_replicas == 1:             # single-device fallback
            return super()._get_program(sequence, bucket)
        key = (sequence, bucket)
        prog = self._programs.get(key)
        if prog is None:
            script, shapes, pads, _ = self._compile_specs(sequence, bucket)
            prog = self.compiler.compile_sharded(
                script, shapes, mesh=self.mesh,
                axis=self.axis, max_batch=self.max_batch,
                mode=self.mode, backend=self.backend,
                bucket=f"{sequence}/{bucket}")
            self._pad_values[key] = pads
            self._programs[key] = prog
        return prog, self._pad_values[key]

    def _dispatch_batch(self, k: int) -> int:
        rows = _pow2_batch(max(1, -(-k // self.n_replicas)), self.rows_cap)
        return self.n_replicas * rows

    def _trace_sizes(self) -> list[int]:
        # rows_cap itself may be non-pow2 (a capped max_batch), so seed
        # the set with it, exactly as the base class seeds max_batch
        rows, r = {self.rows_cap}, 1
        while r < self.rows_cap:
            rows.add(r)
            r *= 2
        return [self.n_replicas * x for x in sorted(rows)]

    def _note_dispatch(self, n_real: int, batch: int) -> None:
        for j, c in enumerate(replica_fill(n_real, batch, self.n_replicas)):
            self.replica_rows[j] += c

    def stats(self) -> dict:
        from ..dist.sharding import mesh_axis_sizes
        st = super().stats()
        st["mesh"] = dict(mesh_axis_sizes(self.mesh))
        st["n_replicas"] = self.n_replicas
        st["replica_rows"] = list(self.replica_rows)
        return st
