"""Code generation: combinations → executable JAX programs (paper §4.3).

Two backends:

* ``jnp`` — each fused group becomes one separately ``jax.jit``-compiled
  function (kernel boundary == jit boundary == the paper's global
  barrier).  Inside a group XLA fuses the glued elementary functions; the
  *decision* of what lives in one kernel is the compiler's, exactly as in
  the paper.  This backend runs anywhere (CPU container included).
* ``pallas`` — each fused group becomes ONE ``pl.pallas_call`` with
  explicit BlockSpec VMEM tiling.  The kernel body is produced by gluing
  elementary ``fn`` routines over a VMEM namespace (Algorithm 1/2):
  loads are synthesized BlockSpecs (invariant loads = index maps that
  ignore grid axes, the paper's line-4 hoisting), reductions either
  accumulate into revisited output blocks (reduce axes innermost — the
  paper's "accumulable outputs") or emit per-grid-cell partials combined
  after the kernel (the paper's "extra kernel" finalization §3.2.2(i)).

TPUs have no atomics, so the paper's ``atomicAdd`` variant (iii) is not
available — this is a documented hardware adaptation (DESIGN.md §2).

Execution model (DESIGN.md §4): codegen consumes an ``ExecutionPlan``
and emits ONE jitted whole-program function.  Groups become
sub-functions inlined into it; values are routed by the plan's index
table (no Var dictionaries, no per-group Python dispatch on the hot
path).  On the ``jnp`` backend an ``optimization_barrier`` between
groups keeps XLA from fusing across the compiler's chosen kernel
boundaries, so the fused/unfused comparison stays meaningful; on the
``pallas`` backend each group is one opaque ``pallas_call`` anyway.

Multi-graph programs (DESIGN.md §9): ``compile_plan_packed`` emits ONE
jitted dispatch over several member graphs — the members' disjoint
routing tables merged by offset rebasing, each member's groups kept as
separate sub-functions (fusion decisions preserved), member boundaries
fenced with ``optimization_barrier`` so the packed path stays
bitwise-equal to the unpacked one.
"""
from __future__ import annotations

import dataclasses
import functools
import re
from typing import Any, Callable, Sequence

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from . import tracing
from .diagnostics import UnsupportedGroupError, VerificationError
from .elementary import Monoid, col
from .fusion import (ACC, DIV, MAX, Fusion, call_phases,
                     consumed_reductions, local_reductions)
from .graph import Graph, Var
from .plan import ExecutionPlan, PackedPlan, build_plan
from .predictor import (V5E, Carrier, HardwareModel, Impl, accumulable,
                        carrier_swapped, online_accumulators,
                        operand_carrier, reduce_roots_of)
from .scheduler import Combination

#: scoped VMEM Mosaic may use per kernel beyond the predictor's budget
#: (``hw.vmem_bytes``): the kernel body's temporaries, which the block
#: count in ``cost_impl`` cannot see (64 + 36 = 100 of the v5e's 128 MiB)
VMEM_HEADROOM_BYTES = 36 * 1024 * 1024


# ---------------------------------------------------------------------------
# dense reference (oracle): evaluate the whole graph, no kernel structure
# ---------------------------------------------------------------------------

def execute_dense(g: Graph, env: dict[str, Any]):
    vals: dict[Var, Any] = {v: jnp.asarray(env[v.name]) for v in g.inputs}
    for c in g.calls:
        vals[c.out] = c.elem.fn(*[vals[a] for a in c.args])
    outs = tuple(vals[v] for v in g.outputs)
    return outs[0] if len(outs) == 1 else outs


# ---------------------------------------------------------------------------
# group executors
# ---------------------------------------------------------------------------

def group_label(i: int, f: Fusion) -> str:
    """The name of group ``i`` of a plan: its position and its calls'
    names, identifier-safe (``g0_rank2_update_gemtv``).  The group's
    ``named_scope`` and its Pallas kernel carry it into the device trace,
    where it stays the same from one compile of a plan to the next."""
    names = "_".join(c.elem.name for c in f.calls)
    return re.sub(r"\W", "_", f"g{i}_{names}")


def _group_dense_fn(f: Fusion) -> Callable:
    """Pure function (ext_inputs...) -> (outputs...) for one fused group."""

    def run(*ext_vals):
        vals = dict(zip(f.external_inputs, ext_vals))
        for c in f.calls:
            vals[c.out] = c.elem.fn(*[vals[a] for a in c.args])
        return tuple(vals[v] for v in f.outputs)

    run.__name__ = "fused_" + "_".join(c.elem.name for c in f.calls)
    return run


def _monoid_sum(monoid: Monoid, x, axes):
    if monoid is Monoid.SUM:
        return jnp.sum(x, axis=axes)
    if monoid is Monoid.MAX:
        return jnp.max(x, axis=axes)
    return jnp.min(x, axis=axes)


def _broadcast(x, src: tuple[int, ...], dst: tuple[int, ...]):
    """Block ``x`` over axis roots ``src`` shaped to broadcast onto a
    block over ``dst`` (the cases ``fusion.broadcastable`` admits)."""
    if len(src) == 1 and len(dst) == 2 and src[0] == dst[0]:
        return col(x)
    return x


def _rescaled(acc, alpha):
    """A running sum carried to a new running max: ``acc`` times
    ``alpha = exp(m_old - m_new)``, broadcast onto it."""
    return acc * alpha


def _require_pallas_platform(interpret: bool) -> None:
    """Compiled Pallas kernels need a TPU; elsewhere the caller must ask
    for the interpreter explicitly — it is never a silent fallback."""
    if not interpret and jax.default_backend() != "tpu":
        raise RuntimeError(
            f"the pallas backend compiles Mosaic kernels, which need a "
            f"TPU, but JAX's default backend is "
            f"{jax.default_backend()!r}; pass interpret=True to run the "
            f"kernels in the Pallas interpreter instead")


def _group_pallas_fn(g: Graph, impl: Impl, hw: HardwareModel = V5E,
                     interpret: bool = False,
                     name: str | None = None) -> Callable:
    """Build the single pallas_call for one fused group.

    Groups whose reductions are only *produced* (never consumed inside),
    or consumed block-local, compile to the single-sweep kernel.  A
    consumed reduction is block-local when the impl's blocks hold each
    of its reduce axes whole (``fusion.local_reductions``): its value is
    finished inside every grid step and consumed in that step, with no
    scratch, phase or ``pl.when``.  A member over fewer axes than the
    group computes once per step on its natural block, every axis it
    lacks one whole block, so an output of it, written through its own
    index map, is never revisited.  Groups consuming any other finished
    reduction in-kernel (fusion rule 2) get a leading *phase*
    grid axis: during phase p the consumed reductions assigned to phase
    p accumulate into VMEM scratch buffers; from phase p+1 on, their
    finished values are read back from scratch by the consuming calls.
    Map values are recomputed every phase (rematerialization), and
    every side effect — output write, scratch or output accumulation —
    is gated on its call's phase with ``pl.when``, so an unfinished
    accumulator is never observable.  This requires every phased
    reduction to be ``accumulable`` under the impl's grid order (reduce
    axes an innermost suffix), and every axis in ``Fusion.whole_roots``
    to be one whole block; ``enumerate_impls`` emits only such impls,
    and a hand-built plan violating either raises
    ``NotImplementedError`` (RPL214) — the group-split contract
    (DESIGN.md §2).

    Every value crosses the kernel boundary — BlockSpec, partials
    array — in its ``predictor.operand_carrier`` form (rank >= 2,
    lane-dense vectors, ``(1, 1)`` scalars, a narrow matrix with its
    last two dims swapped as XLA stores it), the layout the predictor's
    block legality and VMEM count assume; the body takes blocks back to
    the elementaries' natural order and ranks, except in a depth-1
    group, whose elementwise body runs on the carrier blocks.  Scratch
    stays in VMEM and holds its carrier in the value's natural order.
    An online-softmax group (``fusion.online_roles``) compiles to one
    sweep over its streamed axis, every other axis one whole block: VMEM
    scratch carries a running max (started at the dtype's lowest finite
    value, so ``exp(m_old - m_new)`` is never ``nan``) and a running sum
    per reduction over the axis.  Each step takes the max to the block's,
    rescales every sum by ``exp(m_old - m_new)`` before adding the
    block's part, and feeds a division's numerator on in its place; the
    last step divides each sum by its divisor's finished sum and writes
    the outputs (DESIGN.md §2).

    ``interpret=False`` compiles Mosaic kernels and needs a TPU.
    ``name`` names the kernel (``group_label``).
    """
    _require_pallas_platform(interpret)
    f = impl.fusion
    order, spatial_grid = impl.order, impl.grid
    online = f.stream_root is not None
    pos = {r: i for i, r in enumerate(order)}
    blk = {r: b for r, b in zip(order, impl.blocks)}
    group_names = "+".join(c.elem.name for c in f.calls)

    whole = impl.whole_roots
    split = [r for r in f.whole_roots if r not in whole]
    if split:
        raise UnsupportedGroupError.single(
            "RPL214", f"plan.group[{group_names}]",
            f"pallas backend cannot emit group [{group_names}] with grid "
            f"{spatial_grid} over order {order}: axes {split} must be one "
            f"whole block, as a member lacks them or a consumed "
            f"reduction must finish inside a grid step")
    local_idx = {c.idx for c in local_reductions(f, g, whole)}
    consumed = tuple(c for c in consumed_reductions(f, g)
                     if c.idx not in local_idx)   # the phased ones
    consumed_idx = {c.idx for c in consumed}
    phase_of, n_phases = call_phases(f, g, whole)
    multi = n_phases > 1
    gofs = 1 if multi else 0                 # leading phase grid axis
    grid = ((n_phases,) + spatial_grid) if multi else spatial_grid

    for c in consumed:
        if not accumulable(c.out, f, g, order, spatial_grid):
            raise UnsupportedGroupError.single(
                "RPL214", f"plan.group[{group_names}]",
                f"pallas backend cannot emit group [{group_names}]: "
                f"reduction '{c.elem.name}' is consumed in-kernel but its "
                f"reduce axes are not the innermost suffix of grid order "
                f"{order}, so no scratch accumulator can carry its "
                f"finished value; use an accumulable order "
                f"(enumerate_impls only emits those) or split the group")
    if online and any(n > 1 for r, n in zip(order, spatial_grid)
                      if r != f.stream_root):
        raise UnsupportedGroupError.single(
            "RPL214", f"plan.group[{group_names}]",
            f"pallas backend cannot emit online-softmax group "
            f"[{group_names}] with grid {spatial_grid}: every axis but the "
            f"streamed one must be one whole block")

    # every value a call reads must be resolvable inside the kernel: an
    # external input, an earlier map output, or a consumed reduction's
    # scratch.  Anything else is a group shape this backend cannot emit
    # — raise a clear error at build time, not a KeyError from the env
    # dict mid-trace.
    resolvable = set(f.external_inputs)
    for c in f.calls:
        bad = sorted({a.producer.elem.name for a in c.args
                      if a not in resolvable and a.producer is not None})
        if bad:
            raise UnsupportedGroupError.single(
                "RPL214", f"plan.group[{group_names}]",
                f"pallas backend cannot emit group [{group_names}]: call "
                f"'{c.elem.name}' consumes the output of {bad}, which "
                f"never becomes visible inside the kernel")
        if (online or not c.elem.is_reduction
                or c.idx in consumed_idx | local_idx):
            resolvable.add(c.out)

    def roots_of(v: Var) -> tuple[int, ...]:
        return tuple(g.axis_root(a) for a in v.axis_ids)

    def natural_block(v: Var) -> tuple[int, ...]:
        return tuple(blk[r] for r in roots_of(v))

    def carrier(v: Var) -> Carrier:
        return operand_carrier(v.shape, natural_block(v), v.dtype, hw)

    def to_carrier(v: Var, x, shape: tuple[int, ...]):
        """``x``, in ``v``'s natural order, laid out as ``shape``, which
        holds ``v``'s carrier (behind leading unit dims, for partials)."""
        if carrier(v).swapped:
            x = jnp.swapaxes(x, -1, -2)
        return jnp.reshape(x, shape)

    # a depth-1 group holds only vectors over its one axis and scalars,
    # and its calls are elementwise maps and whole-block reductions, so
    # where every vector has the same carrier block the body computes on
    # that 2-D block as it is: Mosaic lays a rank-1 block out a sublane
    # per vreg, which at a 2**20-element block takes it 10 s to compile
    vec_blocks = {carrier(v).block for v in (*f.external_inputs,
                                             *(c.out for c in f.calls))
                  if len(v.shape) == 1}
    flat = f.depth == 1 and len(vec_blocks) == 1

    def is_row(v: Var) -> bool:
        """A vector carried as one ``(1, n)`` row (blocks move along its
        lanes) rather than a lane-dense view (blocks of whole rows)."""
        return len(v.shape) == 1 and carrier(v).block[0] == 1

    def make_index_map(v: Var, lead_roots: tuple[int, ...] = ()):
        vroots = roots_of(v)
        row = is_row(v)

        def index_map(*gids):
            gids = gids[gofs:]               # the phase axis moves no blocks
            lead = tuple(gids[pos[r]] for r in lead_roots)
            if not vroots:                   # (1, 1) scalar carrier
                body = (0, 0)
            elif len(vroots) == 1:
                gid = gids[pos[vroots[0]]]
                body = (0, gid) if row else (gid, 0)
            else:
                body = tuple(gids[pos[r]] for r in vroots)
                if carrier(v).swapped:
                    body = body[:-2] + (body[-1], body[-2])
            return lead + body
        return index_map

    def load(v: Var, ref, idx=Ellipsis, scratch: bool = False):
        """A carrier block (a ``scratch`` buffer's, in natural order)
        read back in the elementaries' natural order and rank (as it
        is, in a ``flat`` body)."""
        if v.shape == ():
            return ref[0, 0]
        x = ref[idx]
        if carrier(v).swapped and not scratch:
            x = jnp.swapaxes(x, -1, -2)
        return jnp.reshape(x, carrier(v).block if flat else natural_block(v))

    # ---- input specs ------------------------------------------------------
    in_specs = []
    for v in f.external_inputs:
        in_specs.append(pl.BlockSpec(carrier(v).block, make_index_map(v)))

    # ---- output specs -----------------------------------------------------
    out_specs, out_shapes, out_mode = [], [], []
    # out_mode: ('map',), ('acc', reduce_pos), ('partial', lead_axes)
    for v in f.outputs:
        shape, block, _ = carrier(v)
        rr = reduce_roots_of(v, f, g)
        if not rr or accumulable(v, f, g, order, spatial_grid) or online:
            out_specs.append(pl.BlockSpec(block, make_index_map(v)))
            out_shapes.append(jax.ShapeDtypeStruct(shape, v.dtype))
            out_mode.append(("acc", tuple(pos[r] for r in rr)) if rr
                            else ("map", None))
        else:
            # one carrier block per grid cell along the reduce axes,
            # combined after the kernel
            lead = tuple(spatial_grid[pos[r]] for r in rr)
            out_specs.append(pl.BlockSpec(
                (1,) * len(rr) + block, make_index_map(v, lead_roots=rr)))
            out_shapes.append(jax.ShapeDtypeStruct(lead + shape, v.dtype))
            out_mode.append(("partial", tuple(range(len(rr)))))

    # ---- scratch accumulators for consumed reductions ---------------------
    # full-size carrier buffers: the finished value of phase p, read back
    # via dynamic block slices from phase p+1 on
    scratch_shapes, scratch_at = [], {}
    for c in consumed + online_accumulators(f):
        scratch_at[c.idx] = len(scratch_shapes)
        scratch_shapes.append(pltpu.VMEM(carrier(c.out).natural.shape,
                                         c.out.dtype))

    def scratch_index(v: Var):
        """The current grid cell's block of a scratch carrier."""
        vroots = roots_of(v)
        if not vroots:
            return (slice(None), slice(None))

        def along(r, width):
            # a whole-axis block sits at offset 0: a static slice, as
            # Mosaic refuses a dynamic lane offset it cannot prove is a
            # multiple of 128 (a (1, 16) per-head vector)
            if spatial_grid[pos[r]] == 1:
                return slice(None)
            return pl.ds(pl.program_id(gofs + pos[r]) * width, width)

        if len(vroots) == 1:
            rows, lanes = carrier(v).block
            if is_row(v):
                return (slice(None), along(vroots[0], lanes))
            return (along(vroots[0], rows), slice(None))
        return tuple(along(r, blk[r]) for r in vroots)

    n_in = len(f.external_inputs)
    n_out = len(f.outputs)
    out_index = {v: i for i, v in enumerate(f.outputs)}

    def kernel(*refs):
        in_refs = refs[:n_in]
        out_refs = refs[n_in:n_in + n_out]
        scratch_refs = refs[n_in + n_out:]
        phase = pl.program_id(0) if multi else None
        env: dict[Var, Any] = {}
        for v, ref in zip(f.external_inputs, in_refs):
            env[v] = load(v, ref)
        for c in f.calls:
            val = c.elem.fn(*[env[a] for a in c.args])
            gate = (phase == phase_of[c.idx]) if multi else None
            if c.idx in consumed_idx:
                # accumulate into scratch during this call's phase; the
                # (possibly partial) value is read back from scratch, so
                # consumers at later phases see the finished reduction
                sref = scratch_refs[scratch_at[c.idx]]
                idx = scratch_index(c.out)
                cval = jnp.reshape(val, carrier(c.out).natural.block
                                   ).astype(sref.dtype)
                rr = reduce_roots_of(c.out, f, g)
                is_first = functools.reduce(
                    jnp.logical_and,
                    [pl.program_id(gofs + pos[r]) == 0 for r in rr])

                @pl.when(gate & is_first)
                def _init_scratch(sref=sref, idx=idx, cval=cval):
                    sref[idx] = cval

                @pl.when(gate & jnp.logical_not(is_first))
                def _acc_scratch(sref=sref, idx=idx, cval=cval,
                                 m=c.elem.monoid):
                    sref[idx] = m.combine(sref[idx], cval)

                env[c.out] = load(c.out, sref, idx, scratch=True)
            elif not c.elem.is_reduction or c.idx in local_idx:
                # a block-local reduction is finished in this step
                env[c.out] = val
            if c.out in out_index:
                i = out_index[c.out]
                mode, aux = out_mode[i]
                ref = out_refs[i]
                cval = to_carrier(c.out, val, ref.shape).astype(ref.dtype)
                if mode == "map" or mode == "partial":
                    if multi:
                        @pl.when(gate)
                        def _write(ref=ref, cval=cval):
                            ref[...] = cval
                    else:
                        ref[...] = cval
                else:  # acc
                    is_first = functools.reduce(
                        jnp.logical_and,
                        [pl.program_id(p + gofs) == 0 for p in aux])
                    if multi:
                        is_first = gate & is_first
                        not_first = gate & jnp.logical_not(is_first)
                    else:
                        not_first = jnp.logical_not(is_first)

                    @pl.when(is_first)
                    def _init(ref=ref, cval=cval):
                        ref[...] = cval

                    @pl.when(not_first)
                    def _accum(ref=ref, cval=cval, m=c.elem.monoid):
                        ref[...] = m.combine(ref[...], cval)

    def online_kernel(*refs):
        in_refs = refs[:n_in]
        out_refs = refs[n_in:n_in + n_out]
        run = {c.out: refs[n_in + n_out + scratch_at[c.idx]]
               for c in online_accumulators(f)}
        step = pl.program_id(pos[f.stream_root])

        @pl.when(step == 0)
        def _start():
            for c, role in zip(f.calls, f.roles):
                if c.out in run:
                    ref = run[c.out]
                    first = jnp.finfo(ref.dtype).min if role == MAX else 0
                    ref[...] = jnp.full(ref.shape, first, ref.dtype)

        # a vector input carried as a row stays one: a contraction of it
        # then yields rows too, which Mosaic reduces and broadcasts,
        # where it refuses the relayout of a rank-1 contraction output
        env: dict[Var, Any] = {}
        for v, ref in zip(f.external_inputs, in_refs):
            env[v] = ref[...] if is_row(v) else load(v, ref)
        divisor: dict[Var, Var] = {}
        for c, role in zip(f.calls, f.roles):
            args = [env[a] for a in c.args]
            if role == MAX:
                m_old = load(c.out, run[c.out], scratch=True)
                val = jnp.maximum(m_old, c.elem.fn(*args))
                alpha, m_roots = jnp.exp(m_old - val), roots_of(c.out)
            elif role == DIV:
                num, den = c.elem.div_args
                val = args[num]
                divisor[c.out] = c.args[den]
            elif role == ACC:
                acc = load(c.out, run[c.out], scratch=True)
                val = _rescaled(acc, _broadcast(
                    alpha, m_roots, roots_of(c.out))) + c.elem.fn(*args)
            else:
                val = c.elem.fn(*args)
            if c.out in run:
                ref = run[c.out]
                ref[...] = jnp.reshape(val, ref.shape).astype(ref.dtype)
            env[c.out] = val

        @pl.when(step == spatial_grid[pos[f.stream_root]] - 1)
        def _finish():
            for v, ref in zip(f.outputs, out_refs):
                val = env[v]
                for a in v.producer.args:
                    if a in divisor:
                        den = divisor[a]
                        val = val / _broadcast(env[den], roots_of(den),
                                               roots_of(v))
                ref[...] = to_carrier(v, val, ref.shape).astype(ref.dtype)

    call = pl.pallas_call(
        online_kernel if online else kernel, grid=grid, in_specs=in_specs,
        out_specs=out_specs,
        out_shape=tuple(out_shapes), interpret=interpret,
        scratch_shapes=tuple(scratch_shapes), name=name,
        compiler_params=pltpu.CompilerParams(
            vmem_limit_bytes=hw.vmem_bytes + VMEM_HEADROOM_BYTES),
    )

    def run(*ext_vals):
        # a swapped carrier is a swapaxes of the input, which XLA lowers
        # to a bitcast of the array as it stores it
        vals = [to_carrier(v, jnp.asarray(x, v.dtype), carrier(v).shape)
                for v, x in zip(f.external_inputs, ext_vals)]
        raw = call(*vals)
        outs = []
        for v, r, (mode, aux) in zip(f.outputs, raw, out_mode):
            if mode == "partial":
                r = _monoid_sum(v.producer.elem.monoid, r, aux)
            if carrier(v).swapped:
                r = jnp.swapaxes(r, -1, -2)
            outs.append(jnp.reshape(r, v.shape))
        return tuple(outs)

    run.__name__ = "pallas_" + "_".join(c.elem.name for c in f.calls)
    return run


# ---------------------------------------------------------------------------
# whole-program executor
# ---------------------------------------------------------------------------

@dataclasses.dataclass
class CompiledProgram:
    """Executable for one plan: a single jitted whole-program function.

    Steady-state dispatch is ONE call into XLA — the per-group Python
    loop runs only once, at trace time.  ``fn`` is vmap/batch-friendly:
    it is a pure positional function over the graph inputs."""

    graph: Graph
    plan: ExecutionPlan
    group_impls: list[Impl]        # topological order, bound to `graph`
    fn: Callable                   # jitted (*input_vals) -> tuple(outputs)
    #: the values a Pallas kernel takes or gives with their last two dims
    #: swapped (``predictor.carrier_swapped``), in plan order
    transposed_operands: tuple[str, ...] = ()

    @property
    def n_groups(self) -> int:
        return len(self.plan.groups)

    @property
    def group_labels(self) -> list[str]:
        """Each group's name in a device trace, in ``group_impls`` order."""
        return [group_label(i, im.fusion)
                for i, im in enumerate(self.group_impls)]

    @property
    def grid_steps(self) -> int:
        """Grid steps one call runs, summed over the plan's groups."""
        return sum(im.grid_steps for im in self.group_impls)

    @property
    def input_passes(self) -> dict[str, int]:
        """How many times one call streams each input from HBM: the sum,
        over the plan's groups that read it, of the group's
        ``n_phases`` (a multi-phase kernel passes over its inputs once a
        phase).  A block a grid order fetches again within one pass is
        charged in ``Impl.traffic_bytes``, not here."""
        passes = dict.fromkeys(self.plan.input_names, 0)
        for im in self.group_impls:
            for v in im.fusion.external_inputs:
                if v.is_input:
                    passes[v.name] += im.n_phases
        return passes

    @property
    def local_reductions(self) -> tuple[str, ...]:
        """The reductions consumed in the grid step that finishes them
        (block-local, ``fusion.local_reductions``): their output names,
        in plan order.  Each spares its group a phase, a pass over its
        inputs, that ``input_passes`` would otherwise count."""
        return tuple(c.out.name for im in self.group_impls
                     for c in local_reductions(im.fusion, self.graph,
                                               im.whole_roots))

    def __call__(self, **inputs):
        with tracing.span(tracing.DISPATCH):
            outs = self.fn(*_gather_args(self.plan, inputs))
        return outs[0] if len(outs) == 1 else outs

    def block_until_ready(self, result):
        return jax.tree_util.tree_map(
            lambda x: x.block_until_ready()
            if hasattr(x, "block_until_ready") else x, result)


@dataclasses.dataclass
class BatchedProgram:
    """vmap-batched executable for one plan: a whole bucket of same-shape
    requests in ONE dispatch (horizontal fusion across requests).

    Every input carries a leading batch axis — scalars become ``(b,)``
    vectors — and every output comes back with the same leading axis.
    The batch size is not baked in; jit re-traces per distinct ``b``, so
    callers should quantize batch sizes (the serving engine rounds to
    powers of two up to ``max_batch``)."""

    graph: Graph
    plan: ExecutionPlan
    max_batch: int
    fn: Callable                   # jitted vmapped (*batched_inputs) -> tuple
    raw_fn: Callable | None = None  # un-jitted vmapped program — what
    #                                 dist.sharding.shard_program lifts

    @property
    def n_groups(self) -> int:
        return len(self.plan.groups)

    def __call__(self, **inputs):
        outs = self.fn(*_gather_args(self.plan, inputs))
        return outs[0] if len(outs) == 1 else outs

    def block_until_ready(self, result):
        return jax.tree_util.tree_map(
            lambda x: x.block_until_ready()
            if hasattr(x, "block_until_ready") else x, result)


def _gather_args(plan: ExecutionPlan, inputs: dict) -> list:
    unexpected = sorted(set(inputs) - set(plan.input_names))
    if unexpected:
        raise TypeError(
            f"unexpected inputs {unexpected}; "
            f"program takes {sorted(plan.input_names)}")
    args = []
    for name in plan.input_names:
        if name not in inputs:
            raise KeyError(f"missing input {name}")
        args.append(inputs[name])
    return args


def _program_fn(plan: ExecutionPlan, impls: list[Impl], fns: list[Callable],
                backend: str, barrier: bool = True) -> Callable:
    """The whole program as one pure function, values routed by the
    plan's index table (plan.GroupPlan.inputs / plan.outputs).

    ``barrier=False`` drops the inter-group ``optimization_barrier`` —
    desirable for serving, where XLA fusing across the chosen kernel
    boundaries is pure upside.  Each group runs in a ``named_scope`` of
    its ``group_label``."""
    labels = [group_label(i, im.fusion) for i, im in enumerate(impls)]

    def read(ref, inputs, group_outs):
        if ref[0] == "input":
            return inputs[ref[1]]
        return group_outs[ref[1]][ref[2]]

    def program(*input_vals):
        inputs = dict(zip(plan.input_names, input_vals))
        group_outs: list[tuple] = []
        for gp, fn, label in zip(plan.groups, fns, labels):
            with jax.named_scope(label):
                outs = fn(*[read(r, inputs, group_outs) for r in gp.inputs])
            if barrier and backend == "jnp" and len(plan.groups) > 1:
                # kernel boundary: stop XLA fusing across groups
                outs = jax.lax.optimization_barrier(outs)
            group_outs.append(outs)
        return tuple(read(r, inputs, group_outs) for r in plan.outputs)

    program.__name__ = "program_" + plan.signature[:8]
    return program


def _group_fns(g: Graph, plan: ExecutionPlan, impls: list[Impl],
               hw: HardwareModel, interpret: bool) -> list[Callable]:
    fns = []
    for i, im in enumerate(impls):
        if plan.backend == "jnp":
            fns.append(_group_dense_fn(im.fusion))
        elif plan.backend == "pallas":
            fns.append(_group_pallas_fn(g, im, hw=hw, interpret=interpret,
                                        name=group_label(i, im.fusion)))
        else:
            raise VerificationError.single(
                "RPL401", "plan.backend",
                f"unknown backend {plan.backend}")
    return fns


def _transposed_operands(plan: ExecutionPlan, impls: list[Impl],
                         hw: HardwareModel) -> tuple[str, ...]:
    """The names of the values some Pallas kernel of the plan carries
    swapped, each once, in plan order (none on the jnp backend)."""
    if plan.backend != "pallas":
        return ()
    names: dict[str, None] = {}
    for im in impls:
        for v in im.fusion.external_inputs + im.fusion.outputs:
            if carrier_swapped(v.shape, v.dtype, hw):
                names[v.name] = None
    return tuple(names)


def compile_plan(g: Graph, plan: ExecutionPlan, hw: HardwareModel = V5E,
                 interpret: bool = False, jit: bool = True) -> CompiledProgram:
    """ExecutionPlan -> executable (one jitted whole-program function)."""
    impls = plan.bind(g, hw)
    fns = _group_fns(g, plan, impls, hw, interpret)
    program = _program_fn(plan, impls, fns, plan.backend)
    return CompiledProgram(
        graph=g, plan=plan, group_impls=impls,
        fn=jax.jit(program) if jit else program,
        transposed_operands=_transposed_operands(plan, impls, hw))


def compile_plan_batched(g: Graph, plan: ExecutionPlan, max_batch: int = 8,
                         hw: HardwareModel = V5E, interpret: bool = False,
                         jit: bool = True) -> BatchedProgram:
    """ExecutionPlan -> vmap-batched executable (one dispatch per batch).

    The whole-program function is pure and positional, so ``jax.vmap``
    lifts it to a batch of requests wholesale — the serving engine's
    horizontal fusion.  Inter-group barriers are dropped (see
    ``_program_fn``)."""
    impls = plan.bind(g, hw)
    fns = _group_fns(g, plan, impls, hw, interpret)
    program = _program_fn(plan, impls, fns, plan.backend, barrier=False)
    batched = jax.vmap(program)
    batched.__name__ = "batched_" + plan.signature[:8]
    return BatchedProgram(graph=g, plan=plan, max_batch=max_batch,
                          fn=jax.jit(batched) if jit else batched,
                          raw_fn=batched)


# ---------------------------------------------------------------------------
# packed multi-graph programs (DESIGN.md §9)
# ---------------------------------------------------------------------------

@dataclasses.dataclass
class PackedProgram:
    """One jitted dispatch over SEVERAL member graphs (DESIGN.md §9) —
    the cross-sequence horizontal fusion of a mixed serving drain.

    Members are in the pack's canonical order.  Every member input is
    batched (leading batch axis, scalars as ``(b,)``); members may
    carry *different* batch sizes — jit re-traces per distinct shape
    mix, so callers should quantize (the serving engine packs equal
    batch-size classes).  Outputs come back per member, batched,
    bitwise-equal to what each member's own ``BatchedProgram`` would
    produce: inter-member ``optimization_barrier``s keep XLA from
    fusing across pack members, so each member's compiled form is the
    unpacked one."""

    graphs: tuple[Graph, ...]
    packed: PackedPlan
    member_impls: tuple[tuple[Impl, ...], ...]
    max_batch: int
    fn: Callable             # jitted (*concat inputs) -> tuple(concat outputs)

    @property
    def n_members(self) -> int:
        return self.packed.n_members

    @property
    def n_groups(self) -> int:
        return sum(len(p.groups) for p in self.packed.members)

    def gather(self, member_inputs: Sequence) -> list:
        """Concatenated positional args from per-member input dicts
        (canonical member order)."""
        if len(member_inputs) != self.n_members:
            raise ValueError(f"pack has {self.n_members} members, "
                             f"got {len(member_inputs)} input dicts")
        args = []
        for p, inputs in zip(self.packed.members, member_inputs):
            args.extend(_gather_args(p, dict(inputs)))
        return args

    def split(self, outs: tuple) -> list[tuple]:
        """Concatenated outputs -> one tuple per member."""
        offs = self.packed.output_offsets + (self.packed.n_outputs,)
        return [tuple(outs[offs[m]:offs[m + 1]])
                for m in range(self.n_members)]

    def __call__(self, member_inputs: Sequence) -> list[tuple]:
        return self.split(self.fn(*self.gather(member_inputs)))

    def block_until_ready(self, result):
        return jax.tree_util.tree_map(
            lambda x: x.block_until_ready()
            if hasattr(x, "block_until_ready") else x, result)


@dataclasses.dataclass
class PackedDispatch:
    """Caller-order view of a (cached, canonical-order) PackedProgram.

    ``compile_packed`` returns one of these per call: the heavy
    ``PackedProgram`` is shared through the program cache keyed on the
    sorted member fingerprints, while ``perm`` records how THIS
    caller's member order maps onto the canonical order — so a drain
    cycle that sees the same sequence mix in a different arrival order
    reuses the program and only the thin permutation differs."""

    program: PackedProgram
    perm: tuple[int, ...]          # perm[k] = caller index of canonical k

    @property
    def n_members(self) -> int:
        return self.program.n_members

    def __call__(self, member_inputs: Sequence) -> list[tuple]:
        """Run the pack: ``member_inputs[i]`` is member *i*'s input
        dict in the caller's order; returns per-member output tuples in
        the same order."""
        canon = self.program([member_inputs[i] for i in self.perm])
        outs: list = [None] * len(self.perm)
        for k, i in enumerate(self.perm):
            outs[i] = canon[k]
        return outs

    def block_until_ready(self, result):
        return self.program.block_until_ready(result)


def _packed_program_fn(packed: PackedPlan, fns: list[Callable],
                       backend: str) -> Callable:
    """The whole pack as one pure function over concatenated batched
    inputs: the members' disjoint routing tables merged by offset
    rebasing (``PackedPlan.merged_groups``), each group vmap-lifted
    over its member's batch axis.

    Barrier policy: member boundaries get an ``optimization_barrier``
    (jnp backend, >1 member) so XLA cannot fuse across pack members —
    each member's compiled form stays the unpacked ``BatchedProgram``
    one, which is what makes the packed path bitwise-equal to the
    unpacked path.  *Within* a member the batched convention applies
    (no inter-group barriers, as in ``compile_plan_batched``)."""
    flat = packed.merged_groups()
    out_refs = packed.merged_outputs()
    member_of_group = [m for m, _ in flat]
    batched_fns = [jax.vmap(fn) for fn in fns]

    def read(ref, input_vals, group_outs):
        if ref[0] == "input":
            return input_vals[ref[1]]
        return group_outs[ref[1]][ref[2]]

    def program(*input_vals):
        group_outs: list[tuple] = []
        for (m, gp), fn in zip(flat, batched_fns):
            outs = fn(*[read(r, input_vals, group_outs) for r in gp.inputs])
            # member boundary barrier: the last group of each member
            # fences its outputs so XLA keeps pack members' kernels
            # independent (bitwise parity with the unpacked path)
            gi = len(group_outs)
            last_of_member = (gi + 1 == len(flat)
                              or member_of_group[gi + 1] != m)
            if (last_of_member and backend == "jnp"
                    and packed.n_members > 1):
                outs = jax.lax.optimization_barrier(outs)
            group_outs.append(outs)
        return tuple(read(r, input_vals, group_outs) for r in out_refs)

    program.__name__ = "packed_" + packed.signature[:8]
    return program


def compile_plan_packed(graphs: Sequence[Graph], packed: PackedPlan,
                        max_batch: int = 8, hw: HardwareModel = V5E,
                        interpret: bool = False, jit: bool = True
                        ) -> PackedProgram:
    """PackedPlan -> executable: ONE jitted whole-program function over
    N member graphs (DESIGN.md §9).

    ``graphs`` must align with ``packed.members`` (canonical order);
    each member plan binds to its graph exactly as in ``compile_plan``,
    so per-graph fusion decisions are preserved — the pack only merges
    the dispatch."""
    if len(graphs) != packed.n_members:
        raise ValueError(f"pack has {packed.n_members} members, "
                         f"got {len(graphs)} graphs")
    member_impls, fns = [], []
    for g, plan in zip(graphs, packed.members):
        impls = plan.bind(g, hw)
        member_impls.append(tuple(impls))
        fns.extend(_group_fns(g, plan, impls, hw, interpret))
    program = _packed_program_fn(packed, fns, packed.members[0].backend
                                 if packed.members else "jnp")
    return PackedProgram(graphs=tuple(graphs), packed=packed,
                         member_impls=tuple(member_impls),
                         max_batch=max_batch,
                         fn=jax.jit(program) if jit else program)


def compile_combination(g: Graph, combo: Combination, backend: str = "jnp",
                        interpret: bool = False, jit: bool = True,
                        hw: HardwareModel = V5E) -> CompiledProgram:
    plan = build_plan(g, combo, backend=backend)
    return compile_plan(g, plan, hw=hw, interpret=interpret, jit=jit)
