"""Implementation enumeration + performance prediction (paper §4.2).

For each Fusion we enumerate *implementations* — the TPU analogue of the
paper's (calling order, routine variant, block size, serial iterations):

* a **grid order**: permutation of the fusion's iteration axes
  (outermost→innermost).  The innermost axes act as the paper's "serial
  iterations"; reductions whose reduce axes form the innermost suffix can
  accumulate in VMEM ("accumulable outputs"), otherwise they emit
  per-grid-cell partials combined by a follow-up step (the paper's
  "extra kernel" reduction finalization, §3.2.2(i)).
* **block sizes** per axis (must divide the axis size and respect the
  128-lane / 8-sublane TPU tiling of every operand that axis indexes,
  the analogue of the paper's 32-element granularity — see
  ``block_granules`` and ``operand_carrier``).

The predicted runtime is the paper's model:  ``t = max(t_transfer,
t_compute) + t_launch`` assuming full overlap of DMA and compute
(§4.2 "we assume full overlap of computation and data transfers"),
plus what the grid costs: a fixed price per grid step and the one
block fetch and write-back the pipeline cannot overlap.  Without the
grid terms every block size of a 1-D group costs the same, and the
smallest legal block wins.  Dominated implementations (no faster and
no smaller in VMEM) are pruned, as the paper prunes implementations
using more on-chip memory.
"""
from __future__ import annotations

import dataclasses
import itertools
import math
from typing import NamedTuple

import numpy as np

from .fusion import (ACC, MAX, Fusion, call_phases, consumed_reductions,
                     local_reductions, reduce_roots)
from .graph import CallNode, Graph, Var

#: a refit needs at least this many group records before the regression
#: is better-determined than the analytic constants it would replace
REFIT_MIN_RECORDS = 3


def _round_sig(x: float, sig: int = 2) -> float:
    """Round to ``sig`` significant figures.  Measured constants enter
    cache keys (via ``repr(HardwareModel)``); coarse rounding keeps the
    keys stable across the run-to-run jitter of micro-benchmarks."""
    if x == 0 or not math.isfinite(x):
        return x
    return round(x, -int(math.floor(math.log10(abs(x)))) + (sig - 1))


@dataclasses.dataclass(frozen=True)
class HardwareModel:
    """Machine constants feeding ``t_pred`` (defaults: one TPU v5e core).

    The defaults are datasheet numbers and are wrong on anything that is
    not a v5e — most notably the CPU containers CI runs on.  Use
    :meth:`calibrate` to micro-benchmark the machine actually running
    (DESIGN.md §8) when predicted times must be meaningful, e.g. for the
    empirical autotune mode's candidate ordering."""

    name: str = "tpu_v5e"
    peak_flops: float = 197e12          # bf16; f32 ~ 98 TF/s, see scale below
    f32_scale: float = 0.5              # MXU f32 derate
    hbm_bw: float = 819e9               # bytes/s
    vmem_bytes: int = 64 * 1024 * 1024  # usable VMEM budget (of 128 MiB)
    launch_overhead_s: float = 2e-6     # per-kernel dispatch cost
    # fixed cost of one Pallas grid step, measured on a v5e: AXPYDOT at
    # n=2**26 ran 524288 steps of (1, 128) blocks in 142046.9 us, 0.271
    # us a step, far above the 512 B per operand each step moves
    grid_step_s: float = 2.7e-7
    # minimum efficient tile (sublane, lane) for f32
    min_tile: tuple[int, int] = (8, 128)

    def flops_scale(self, dtype) -> float:
        """Compute-rate derate for ``dtype`` relative to ``peak_flops``.

        Sub-4-byte types (bf16/f16/int8) run at peak, 4-byte at
        ``f32_scale``, 8-byte at half that again — the MXU pattern."""
        size = np.dtype(dtype).itemsize
        if size <= 2:
            return 1.0
        if size <= 4:
            return self.f32_scale
        return self.f32_scale / 2.0

    def min_tile_for(self, dtype) -> tuple[int, int]:
        """Minimum efficient (sublane, lane) tile for ``dtype``.

        The lane count is fixed; sublanes scale inversely with itemsize
        so the packed tile stays the same size in bytes: f32 (8, 128),
        bf16 (16, 128), int8 (32, 128)."""
        size = max(1, np.dtype(dtype).itemsize)
        return (max(1, self.min_tile[0] * 4 // size), self.min_tile[1])

    def group_cost(self, traffic_bytes: float, flops: float,
                   dtype=np.float32, steps: int = 0,
                   fill_bytes: float = 0.0) -> float:
        """Predicted seconds for one fused group given its §5 features
        — the paper's roofline: ``max(traffic/bw, flops/rate) +
        launch`` — plus its grid: ``steps`` grid steps at
        ``grid_step_s`` each, and ``fill_bytes`` (one step's blocks in
        and out, the pipeline's first fetch and last write-back) over
        the bandwidth.  This is the formula ``cost_impl`` charges per
        group and the feature map ``refit`` regresses against, kept in
        one place so the two can never drift."""
        t_transfer = traffic_bytes / self.hbm_bw
        t_compute = flops / (self.peak_flops * self.flops_scale(dtype))
        return (max(t_transfer, t_compute) + self.launch_overhead_s
                + steps * self.grid_step_s + fill_bytes / self.hbm_bw)

    @classmethod
    def calibrate(cls, backend: str | None = None,
                  force: bool = False) -> "HardwareModel":
        """Micro-benchmark the running machine into a HardwareModel:
        streaming bandwidth, per-dispatch overhead and f32 flop rate
        replace the hardcoded v5e constants (memoized per platform; see
        ``core.autotune.calibrate_hardware``)."""
        from .autotune import calibrate_hardware
        return calibrate_hardware(backend=backend, force=force)

    def refit(self, records,
              min_records: int = REFIT_MIN_RECORDS) -> "HardwareModel":
        """Recalibrate the roofline coefficients from a per-group
        measured-cost store (DESIGN.md §8).

        Least-squares over the group feature vector ``[traffic_bytes,
        flops, 1]`` against measured seconds: the slopes invert to an
        *effective* bandwidth and flop rate (what the machine actually
        sustained on fused groups — micro-benchmark peaks never are),
        the intercept is the per-dispatch overhead.  ``grid_step_s``
        stays analytic: a record that carries ``grid_steps`` has that
        many steps' cost taken off its ``t_meas`` first (a record left
        with no time is skipped); one without the field is regressed
        as measured.

        Strict fallback semantics, so the result is always a usable
        model:

        * an empty / too-small store (< ``min_records`` valid group
          records) is a **no-op returning ``self``** — plans compiled
          against the refit model are bit-identical to analytic ones;
        * any coefficient that regresses non-finite or non-positive
          (collinear features, noise-dominated store) individually
          falls back to this model's analytic value — the returned
          constants are finite and positive whatever the store holds.

        Only records with ``kind == "group"`` and finite positive
        ``t_meas`` / finite non-negative features participate; foreign
        schemas (whole-program records, calibration records) are
        skipped, which is what lets old and new cache generations
        coexist in one store.
        """
        rows = []
        for rec in records:
            if not isinstance(rec, dict) or rec.get("kind") != "group":
                continue
            try:
                t = float(rec["t_meas"])
                tr = float(rec.get("traffic_bytes", math.nan))
                fl = float(rec.get("flops", math.nan))
                t -= float(rec.get("grid_steps", 0)) * self.grid_step_s
            except (KeyError, TypeError, ValueError):
                continue
            if not (math.isfinite(t) and t > 0 and math.isfinite(tr)
                    and tr >= 0 and math.isfinite(fl) and fl >= 0):
                continue
            rows.append((tr, fl, t))
        if len(rows) < max(min_records, 2):
            return self

        X = np.array([[r[0], r[1], 1.0] for r in rows], dtype=np.float64)
        y = np.array([r[2] for r in rows], dtype=np.float64)
        try:
            coef, *_ = np.linalg.lstsq(X, y, rcond=None)
        except np.linalg.LinAlgError:
            return self
        inv_bw, inv_rate, overhead = (float(v) for v in coef)

        def usable(v: float) -> bool:
            return math.isfinite(v) and v > 0

        hbm_bw = self.hbm_bw
        if usable(inv_bw) and usable(1.0 / inv_bw):
            hbm_bw = _round_sig(1.0 / inv_bw, 3)
        peak_flops, f32_scale = self.peak_flops, self.f32_scale
        if usable(inv_rate) and usable(1.0 / inv_rate):
            # the regression measured the *charged* rate directly, so
            # the refit model carries it at scale 1.0
            peak_flops, f32_scale = _round_sig(1.0 / inv_rate, 3), 1.0
        launch = self.launch_overhead_s
        if usable(overhead) and usable(_round_sig(overhead, 3)):
            launch = _round_sig(overhead, 3)

        if (hbm_bw, peak_flops, f32_scale, launch) == (
                self.hbm_bw, self.peak_flops, self.f32_scale,
                self.launch_overhead_s):
            return self
        name = self.name if self.name.endswith("+refit") \
            else self.name + "+refit"
        return dataclasses.replace(
            self, name=name, hbm_bw=hbm_bw, peak_flops=peak_flops,
            f32_scale=f32_scale, launch_overhead_s=launch)


V5E = HardwareModel()


def fusion_dtype(f: "Fusion") -> np.dtype:
    """The dtype the cost model charges a fusion at: the widest dtype
    streamed over HBM (inputs or outputs) — mixed-precision fusions are
    dominated by their widest stream."""
    vs = tuple(f.external_inputs) + tuple(f.outputs)
    if not vs:
        return np.dtype(np.float32)
    return max((np.dtype(v.dtype) for v in vs), key=lambda d: d.itemsize)


@dataclasses.dataclass(frozen=True)
class Impl:
    """One concrete implementation of a Fusion."""

    fusion: Fusion
    order: tuple[int, ...]              # axis roots, outermost -> innermost
    blocks: tuple[int, ...]             # block size per axis in `order`
    traffic_bytes: float = 0.0
    flops: float = 0.0
    vmem_bytes: float = 0.0
    t_transfer: float = 0.0
    t_compute: float = 0.0
    t_pred: float = 0.0
    n_phases: int = 1                   # leading phase axis (1: none)

    @property
    def grid(self) -> tuple[int, ...]:
        sizes = dict(zip(self.fusion.axis_roots, self.fusion.axis_sizes))
        return tuple(-(-sizes[a] // b) for a, b in zip(self.order, self.blocks))

    @property
    def grid_steps(self) -> int:
        """Grid steps the group's kernel runs in one call, the phase
        axis of a multi-phase group included."""
        return self.n_phases * math.prod(self.grid)

    @property
    def whole_roots(self) -> tuple[int, ...]:
        """The axis roots this implementation blocks whole (grid extent
        1), in grid order."""
        return whole_roots(self.order, self.grid)

    def block_of(self, root: int) -> int:
        return self.blocks[self.order.index(root)]

    def describe(self) -> str:
        return (f"{self.fusion!r} order={self.order} blocks={self.blocks} "
                f"grid={self.grid} steps={self.grid_steps} "
                f"traffic={self.traffic_bytes/1e6:.2f}MB "
                f"flops={self.flops/1e6:.2f}MF vmem={self.vmem_bytes/1e3:.0f}KB "
                f"t={self.t_pred*1e6:.2f}us")


def whole_roots(order: tuple[int, ...], grid: tuple[int, ...]
                ) -> tuple[int, ...]:
    """The roots of ``order`` whose grid extent is 1: one whole block."""
    return tuple(r for r, n in zip(order, grid) if n == 1)


def _divisor_blocks(size: int, minimum: int, maximum: int | None = None) -> list[int]:
    """Candidate block sizes: divisors of ``size`` that are multiples of
    ``minimum`` (TPU tiling), plus the full size."""
    maximum = maximum or size
    out = []
    b = minimum
    while b <= min(size, maximum):
        if size % b == 0:
            out.append(b)
        b *= 2
    if size <= maximum and size not in out:
        out.append(size)
    return out or [size]


# ---------------------------------------------------------------------------
# operand layout at the Pallas kernel boundary (shared with codegen)
# ---------------------------------------------------------------------------

def _round_up(x: int, m: int) -> int:
    return -(-x // m) * m


class Carrier(NamedTuple):
    """A value's array and block shapes at the kernel boundary, and
    whether its last two dims are stored swapped (``swapped``)."""

    shape: tuple[int, ...]
    block: tuple[int, ...]
    swapped: bool = False

    @property
    def natural(self) -> "Carrier":
        """The same carrier in the value's own dim order: what a VMEM
        scratch buffer, which never crosses to HBM, holds."""
        if not self.swapped:
            return self
        return Carrier(_swap_last(self.shape), _swap_last(self.block))


def _swap_last(dims: tuple[int, ...]) -> tuple[int, ...]:
    return (*dims[:-2], dims[-1], dims[-2])


def carrier_swapped(shape: tuple[int, ...], dtype, hw: HardwareModel) -> bool:
    """Whether a value of rank >= 2 is carried with its last two dims
    swapped: exactly when the swapped shape pads to fewer (sublane,
    lane) tiles than the natural one, a tie keeping the natural order.
    XLA's TPU layout makes the same choice for an array it stores (a
    float32 (131072, 64) is laid out ``{0,1}``), so a carrier in that
    order reaches the kernel as a bitcast, where the natural order
    costs a padded relayout copy before every call."""
    if len(shape) < 2:
        return False
    return padded_bytes(_swap_last(shape), dtype, hw) < padded_bytes(
        shape, dtype, hw)


def operand_carrier(shape: tuple[int, ...], block: tuple[int, ...], dtype,
                    hw: HardwareModel) -> Carrier:
    """The ``Carrier`` a value takes at the kernel boundary.

    Mosaic tiles the last two dims of every operand (sublane, lane) and
    refuses rank-1 blocks under a leading batch axis, so values are
    carried as arrays of rank >= 2:

    * a scalar is a ``(1, 1)`` array;
    * a vector blocked in whole tiles (``b`` a multiple of ``sublane *
      lane`` elements) is a lane-dense ``(n / lane, lane)`` view, blocked
      ``(b / lane, lane)``;
    * any other vector is one ``(1, n)`` row, blocked ``(1, b)`` (``b``
      a multiple of the lane count, or the whole row);
    * rank >= 2 values keep their shape and block, the last two dims
      swapped in both where ``carrier_swapped`` says so (a (n, 64)
      cache is carried (64, n), its long axis on the lanes).

    The kernel takes blocks back to the elementaries' natural order and
    rank, so they stay block-polymorphic."""
    if not shape:
        return Carrier((1, 1), (1, 1))
    if len(shape) == 1:
        sub, lane = hw.min_tile_for(dtype)
        (n,), (b,) = shape, block
        if b % (sub * lane) == 0:
            return Carrier((n // lane, lane), (b // lane, lane))
        return Carrier((1, n), (1, b))
    if carrier_swapped(shape, dtype, hw):
        return Carrier(_swap_last(tuple(shape)), _swap_last(tuple(block)),
                       True)
    return Carrier(tuple(shape), tuple(block))


def padded_bytes(shape: tuple[int, ...], dtype, hw: HardwareModel) -> int:
    """VMEM bytes of a carrier-shaped (rank >= 2) buffer: the last two
    dims round up to the (sublane, lane) tile, as Mosaic lays them out."""
    sub, lane = hw.min_tile_for(dtype)
    *lead, s, l = shape
    return (np.dtype(dtype).itemsize * math.prod(lead)
            * _round_up(s, sub) * _round_up(l, lane))


def block_granules(f: Fusion, g: Graph, hw: HardwareModel) -> dict[int, int]:
    """Per axis root, the multiple every block of that axis must be
    (the full axis is always legal): the strictest tiling rule of any
    value the fusion touches, in each value's carrier order.

    The last dim of a value takes the lane count (a vector's only dim is
    the lane dim of its carrier), the second-to-last dim of a rank >= 2
    value the sublane count; a value carried swapped across the kernel
    boundary (``carrier_swapped``) takes them the other way round.  All
    are powers of two, so the max is the lcm."""
    gran = {r: 1 for r in f.axis_roots}
    carried = set(f.external_inputs + f.outputs)
    for v in f.external_inputs + f.outputs + f.internal_vars:
        roots = [g.axis_root(a) for a in v.axis_ids]
        if not roots:
            continue
        sub, lane = hw.min_tile_for(v.dtype)
        swapped = v in carried and carrier_swapped(v.shape, v.dtype, hw)
        last_two = [lane, sub] if swapped else [sub, lane]
        rules = [1] * (len(roots) - 2) + last_two[-len(roots):]
        for r, m in zip(roots, rules):
            gran[r] = max(gran[r], m)
    return gran


def block_is_legal(size: int, block: int, granule: int) -> bool:
    """The block rule ``enumerate_impls`` applies (and the verifier
    re-checks): the whole axis, or a divisor that is a granule multiple."""
    return block == size or (0 < block < size and size % block == 0
                             and block % granule == 0)


def var_streams(v: Var, g: Graph, order: tuple[int, ...], grid: tuple[int, ...]) -> int:
    """How many times ``v`` is streamed from HBM for a given grid order.

    An input indexed by axis subset S is re-fetched whenever an axis
    outside S, ordered *outer* than the innermost axis of S that the
    grid splits, advances (Pallas refetches a block when its index map
    output changes; an axis of one whole block never changes it).
    """
    s_roots = {g.axis_root(a) for a in v.axis_ids}
    split = [i for i, (r, n) in enumerate(zip(order, grid))
             if r in s_roots and n > 1]
    if not split:
        return 1
    n = 1
    for i, r in enumerate(order[:max(split)]):
        if r not in s_roots:
            n *= grid[i]
    return n


def reduce_roots_of(v: Var, f: Fusion, g: Graph) -> tuple[int, ...]:
    """Fusion axes over which output ``v`` is reduced, in the fusion's
    order: those its producing call reduces over (an axis only other
    calls of the fusion iterate is no reduce axis of ``v``)."""
    rr = reduce_roots(v.producer, g)
    return tuple(r for r in f.axis_roots if r in rr)


def accumulable(v: Var, f: Fusion, g: Graph, order: tuple[int, ...],
                grid: tuple[int, ...]) -> bool:
    """True iff v's reduce axes that the ``grid`` splits are the
    innermost suffix of the split axes in the grid order — the in-VMEM
    accumulation case; else partials + combine.  An axis of one whole
    block never moves a block, so its place in the order is no matter."""
    order = tuple(r for r, n in zip(order, grid) if n > 1)
    rr = set(reduce_roots_of(v, f, g)) & set(order)
    if not rr:
        return True
    k = len(rr)
    return set(order[-k:]) == rr


def online_accumulators(f: Fusion) -> tuple[CallNode, ...]:
    """An online-softmax group's calls that keep a running value in VMEM
    scratch across its sweep: the max and every sum over the streamed
    axis (none for any other group)."""
    return tuple(c for c, r in zip(f.calls, f.roles) if r in (MAX, ACC))


def cost_impl(f: Fusion, g: Graph, order: tuple[int, ...],
              blocks: tuple[int, ...], hw: HardwareModel) -> Impl:
    sizes = dict(zip(f.axis_roots, f.axis_sizes))
    grid = tuple(-(-sizes[a] // b) for a, b in zip(order, blocks))
    blk = dict(zip(order, blocks))

    # a consumed reduction whose reduce axes are each one whole block
    # finishes inside every grid step and is consumed there (block-local,
    # DESIGN.md §2); any other forces a leading phase grid axis: the
    # kernel re-streams every input and recomputes every map value once
    # per phase (rematerialization), so inputs and flops are charged
    # n_phases times, and the reduction holds its FULL finished value in
    # a VMEM scratch accumulator
    whole = whole_roots(order, grid)
    local = local_reductions(f, g, whole)
    phased = tuple(c for c in consumed_reductions(f, g) if c not in local)
    n_phases = call_phases(f, g, whole)[1] if phased else 1

    # ---- traffic ----------------------------------------------------------
    traffic = 0.0
    for v in f.external_inputs:
        traffic += v.nbytes * var_streams(v, g, order, grid) * n_phases
    for v in f.outputs:
        rr = reduce_roots_of(v, f, g)
        if (not rr or accumulable(v, f, g, order, grid)
                or f.stream_root is not None):
            traffic += v.nbytes
        else:
            nparts = math.prod(grid[order.index(r)] for r in rr)
            traffic += v.nbytes * (2 * nparts + 1)  # write parts, read parts, write final

    # ---- flops ------------------------------------------------------------
    flops = n_phases * sum(c.elem.flops(c.axis_sizes) for c in f.calls)

    # ---- VMEM footprint (double-buffered blocks) ---------------------------
    # every buffer counts at its padded carrier size — what codegen's
    # BlockSpecs and scratch shapes actually occupy (a partials block
    # only adds leading unit dims, which pad nothing)
    def carrier(v: Var) -> Carrier:
        block = tuple(blk[g.axis_root(a)] for a in v.axis_ids)
        return operand_carrier(v.shape, block, v.dtype, hw)

    def block_bytes(v: Var) -> float:
        return padded_bytes(carrier(v).block, v.dtype, hw)

    # one block of every input and output: what a step moves, and what
    # the pipeline fetches before its first step and writes back after
    # its last, with no other transfer to hide behind
    fill = sum(block_bytes(v) for v in f.external_inputs + f.outputs)

    vmem = 0.0
    for v in f.external_inputs:
        vmem += 2 * block_bytes(v)
    for v in f.outputs:
        vmem += 2 * block_bytes(v)
    for v in f.internal_vars:
        # computed inside the kernel, so held in the value's own order
        vmem += padded_bytes(carrier(v).natural.block, v.dtype, hw)
    for c in phased + online_accumulators(f):
        # full-size scratch accumulator carrying a phased reduction's
        # finished value, or an online group's running max or sum (a
        # block-local reduction is one block, counted as internal above)
        vmem += padded_bytes(carrier(c.out).natural.shape, c.out.dtype, hw)

    dt = fusion_dtype(f)
    t_t = traffic / hw.hbm_bw
    t_c = flops / (hw.peak_flops * hw.flops_scale(dt))
    t = hw.group_cost(traffic, flops, dt, steps=n_phases * math.prod(grid),
                      fill_bytes=fill)
    return Impl(fusion=f, order=order, blocks=blocks, traffic_bytes=traffic,
                flops=flops, vmem_bytes=vmem, t_transfer=t_t, t_compute=t_c,
                t_pred=t, n_phases=n_phases)


def enumerate_impls(f: Fusion, g: Graph, hw: HardwareModel = V5E,
                    max_impls: int = 64) -> list[Impl]:
    """All (order × block) implementations of a fusion, pruned.

    Block sizes per axis come from ``block_granules``, so every emitted
    block is one Mosaic accepts for every operand the axis indexes.
    Pruning (paper §4.2): drop implementations that exceed the VMEM
    budget (the occupancy analogue) and Pareto-dominated ones on
    ``(t_pred, vmem_bytes)``: one that is no faster and needs no less
    VMEM than another goes; a faster one that needs more VMEM stays.
    The survivors come fastest first.

    Every axis in ``Fusion.whole_roots`` is one whole block (fusion
    rules 1-2).  A consumed reduction whose reduce axes the blocks hold
    whole is block-local and needs no particular order; every other
    consumed reduction needs a phase, and only grid orders under which
    it is ``accumulable`` (reduce axes an innermost suffix) are emitted
    — the orders the multi-phase pallas kernel can actually emit.  Rule
    2's chain condition guarantees at least one such order exists; if
    VMEM pruning still empties the list (a whole axis too long for
    VMEM, say), ``build_space`` drops the fusion and the partition
    search covers its calls with smaller groups (the group-split
    fallback, DESIGN.md §2).
    """
    roots, sizes = f.axis_roots, f.axis_sizes
    depth = len(roots)
    gran = block_granules(f, g, hw)
    consumed = consumed_reductions(f, g)
    cands: list[Impl] = []
    if f.stream_root is not None:
        # one sweep over the streamed axis, innermost; every other axis
        # one whole block, so the contractions over them finish in a step
        t = f.stream_root
        order = tuple(r for r in roots if r != t) + (t,)
        whole = tuple(sizes[roots.index(r)] for r in order[:-1])
        for b in _divisor_blocks(sizes[roots.index(t)], gran[t],
                                 maximum=1 << 16):
            cands.append(cost_impl(f, g, order, whole + (b,), hw))
    elif depth == 1:
        for b in _divisor_blocks(sizes[0], gran[roots[0]], maximum=1 << 22):
            cands.append(cost_impl(f, g, roots, (b,), hw))
    else:
        blocks_per_axis = [
            [sizes[k]] if roots[k] in f.whole_roots else
            _divisor_blocks(sizes[k], gran[roots[k]], maximum=1 << 16)
            for k in range(depth)
        ]
        seen = set()
        for order in itertools.permutations(range(depth)):
            o_roots = tuple(roots[i] for i in order)
            for bs in itertools.product(*(blocks_per_axis[i] for i in order)):
                grid = tuple(-(-sizes[roots.index(r)] // b)
                             for r, b in zip(o_roots, bs))
                # where a whole axis sits in the order changes nothing:
                # cost and kernel are those of the order without it
                key = (tuple(r for r, n in zip(o_roots, grid) if n > 1),
                       frozenset(zip(o_roots, bs)))
                if key in seen:
                    continue
                seen.add(key)
                local = local_reductions(f, g, whole_roots(o_roots, grid))
                if any(c not in local
                       and not accumulable(c.out, f, g, o_roots, grid)
                       for c in consumed):
                    continue  # the phase kernel cannot carry the value
                cands.append(cost_impl(f, g, o_roots, bs, hw))

    cands = [c for c in cands if c.vmem_bytes <= hw.vmem_bytes]
    if not cands:
        return []
    return _pareto_time_vmem(cands, max_impls)


def _pareto_time_vmem(cands: list[Impl], max_impls: int) -> list[Impl]:
    """The candidates no other beats on both ``t_pred`` and
    ``vmem_bytes``, fastest first, at most ``max_impls``.  Sorted by
    time then VMEM, a candidate is dominated exactly when an earlier
    survivor needs no more VMEM than it (ties included)."""
    kept: list[Impl] = []
    for c in sorted(cands, key=lambda c: (c.t_pred, c.vmem_bytes)):
        if any(k.vmem_bytes <= c.vmem_bytes for k in kept):
            continue
        kept.append(c)
        if len(kept) >= max_impls:
            break
    return kept
