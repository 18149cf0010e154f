"""Elementary functions — the unit the fusion compiler operates on.

The paper (Filipovič et al.) restricts fusible kernels to ``map``,
``reduce`` and their nested (depth-2) combinations.  We model all of them
with a single *blocked iteration-space* abstraction:

* every elementary function iterates over a set of named axes
  (depth 1: ``('i',)``; depth 2: ``('i', 'j')``);
* every argument is indexed by a subset of those axes (``()`` means the
  argument is a broadcast scalar / "invariant" in the paper's terms);
* the output is indexed by a subset of the axes; axes missing from the
  output are *reduce axes* — the output is accumulated over them with the
  elementary's monoid (``+`` by default).

This covers the paper's taxonomy exactly:

==========================  =========  ==========  ============
paper's kind                axes       out axes    reduce axes
==========================  =========  ==========  ============
map                         (i,)       (i,)        —
reduce                      (i,)       ()          (i,)
nested map (mapped map)     (i, j)     (i, j)      —
mapped reduce               (i, j)     (i,)/(j,)   (j,)/(i,)
==========================  =========  ==========  ============

Past the paper, a depth-3 or deeper map-reduce
(``make_tensor_map_reduce``) maps over every axis but one and reduces
over that one, its operands indexed by subsets of the axes: a
contraction such as every head of a decode step against one shared
cache, ``s[h, t] = sum_c q[h, c] k[t, c]``, whose cache operand ``k`` is
invariant over the head axis, or at depth 4 a batch of experts, each
projecting its own tokens, ``g[e, c, i] = sum_h x[e, c, h] w[e, i, h]``.

The per-element first-order function ``fn`` is written *block-
polymorphically*: it receives jnp arrays whose shapes are either the full
operands (dense / XLA backend) or VMEM-resident blocks (Pallas backend)
and must compute the same thing for both.  This is the analogue of the
paper's requirement that a routine works for any block size chosen by the
compiler (macros ``*_BY`` etc.).
"""
from __future__ import annotations

import dataclasses
import enum
import math
from typing import Any, Callable, Sequence

import jax
import jax.numpy as jnp
import numpy as np


class Kind(enum.Enum):
    MAP = "map"                      # depth-1, no reduce axes
    REDUCE = "reduce"                # depth-1, output ()
    NESTED_MAP = "nested_map"        # depth >= 2, no reduce axes
    NESTED_MAP_REDUCE = "nested_map_reduce"  # depth >= 2, one reduce axis


class Monoid(enum.Enum):
    SUM = "sum"
    MAX = "max"
    MIN = "min"

    @property
    def identity(self) -> float:
        """Float identity (legacy; dtype-blind — ``-inf`` is wrong for
        integer MAX/MIN).  Prefer :meth:`identity_for`."""
        return {"sum": 0.0, "max": -jnp.inf, "min": jnp.inf}[self.value]

    def identity_for(self, dtype):
        """The monoid identity as a scalar of ``dtype``.

        Floats keep 0 / -inf / +inf; integer MAX/MIN use the dtype's
        ``iinfo`` bounds (there is no integer infinity — padding an
        int32 MAX reduce with float -inf would be a cast error, and
        with 0 would be wrong for all-negative data)."""
        dtype = np.dtype(dtype)
        if self is Monoid.SUM:
            return dtype.type(0)
        if dtype.kind in "iu":
            info = np.iinfo(dtype)
            return dtype.type(info.min if self is Monoid.MAX else info.max)
        return dtype.type(-np.inf if self is Monoid.MAX else np.inf)

    def combine(self, a, b):
        if self is Monoid.SUM:
            return a + b
        if self is Monoid.MAX:
            return jnp.maximum(a, b)
        return jnp.minimum(a, b)


@dataclasses.dataclass(frozen=True)
class ArgSpec:
    """How one argument is indexed by the elementary's iteration axes.

    ``axes`` is a tuple of axis *positions* into the elementary's formal
    axis list, in the order they appear as array dimensions.  E.g. for a
    depth-2 function with formal axes ``('i', 'j')``:

    * ``axes=(0, 1)`` — a matrix indexed ``[i, j]`` (tile per grid cell)
    * ``axes=(1,)``   — a vector indexed ``[j]`` (invariant over ``i``)
    * ``axes=()``     — a scalar, invariant everywhere
    """

    axes: tuple[int, ...]


@dataclasses.dataclass(frozen=True)
class Elementary:
    """A fusible elementary function (paper §4.3).

    ``fn(*blocks) -> block`` is the compute routine; load/store routines
    are synthesized by the code generator from the ArgSpecs (BlockSpec
    index maps on the Pallas backend).
    """

    name: str
    kind: Kind
    formal_axes: tuple[str, ...]
    in_specs: tuple[ArgSpec, ...]
    out_axes: tuple[int, ...]          # positions of formal axes kept in output
    fn: Callable[..., Any]
    monoid: Monoid = Monoid.SUM
    flops_per_point: float = 1.0       # arithmetic ops per iteration-space point
    # element granularity per axis: the paper uses 32-subvectors / 32x32
    # tiles; block sizes must be multiples of this.
    elem: tuple[int, ...] = ()
    # True when all-zero lanes of the array arguments yield zero output
    # lanes (the function is zero-preserving, e.g. multilinear maps).
    # Zero-padding a serving batch is only reduction-safe through chains
    # of pad_safe calls; ``exp``/``rsqrt`` (zero maps to 1 / inf) must
    # set False so the engine falls back to per-lane masking.
    pad_safe: bool = True
    # What the online softmax (fusion.online_roles, DESIGN.md §2) may
    # read off a call; each is a claim about ``fn`` in real arithmetic.
    # ``exp_sub_args=(x, m)``: a map computing exp(args[x] - args[m]),
    # args[m] broadcast over the axes of args[x] it lacks.
    exp_sub_args: tuple[int, int] | None = None
    # ``div_args=(num, den)``: a map computing args[num] / args[den],
    # args[den] broadcast likewise.
    div_args: tuple[int, int] | None = None
    # the arguments ``fn`` is linear in, each with the others held fixed:
    # scaling one by a factor constant over the reduce axes scales the
    # output by it.
    linear_args: tuple[int, ...] = ()

    def __post_init__(self):
        depth = len(self.formal_axes)
        # the paper stops at depth 2.  Depths 3 and 4 are carried through
        # every layer downstream: trace axes, fusion legality (a group's
        # calls share one axis set, so a deeper call shares a group with
        # a shallower one only in an online softmax or where the axes it
        # lacks are whole blocks), impl enumeration over all grid orders,
        # and codegen's index maps.  MLA_DECODE_ATTN's and
        # KDA_DECODE_STEP's contractions exercise depth 3, MOE_EXPERTS's
        # expert projections depth 4 (five axis roots in one group).
        # Nothing deeper is exercised by any program or test.
        assert depth >= 1, "elementary needs at least one iteration axis"
        for spec in self.in_specs:
            assert all(0 <= a < depth for a in spec.axes)
        assert all(0 <= a < depth for a in self.out_axes)
        if not self.elem:
            object.__setattr__(self, "elem", (1,) * depth)

    @property
    def depth(self) -> int:
        return len(self.formal_axes)

    @property
    def reduce_axes(self) -> tuple[int, ...]:
        return tuple(a for a in range(self.depth) if a not in self.out_axes)

    @property
    def is_reduction(self) -> bool:
        return bool(self.reduce_axes)

    def flops(self, axis_sizes: Sequence[int]) -> float:
        return self.flops_per_point * math.prod(axis_sizes)


def _as_f32(x):
    return jnp.asarray(x, jnp.float32)


def col(v):
    """``v[..., :, None]`` — a vector as a column, for broadcasting down
    the rows of a matrix block.  Spelled as the transpose of the row
    view: Mosaic lowers that inside a Pallas kernel, while it refuses
    the direct reshape of a lane-major vector into a column."""
    return jnp.swapaxes(v[..., None, :], -1, -2)


# ---------------------------------------------------------------------------
# Constructors for the common kinds (convenience API used by libraries).
# ---------------------------------------------------------------------------

def make_map(name: str, fn: Callable, arity: int, *, scalar_args: Sequence[int] = (),
             flops_per_point: float = 1.0, pad_safe: bool = True,
             **props) -> Elementary:
    """Depth-1 map over lists; ``scalar_args`` are broadcast () arguments.
    ``props`` (here and in the constructors below): the online-softmax
    properties of ``fn`` (``exp_sub_args``, ``div_args``,
    ``linear_args``)."""
    specs = tuple(
        ArgSpec(() if i in set(scalar_args) else (0,)) for i in range(arity)
    )
    return Elementary(
        name=name, kind=Kind.MAP, formal_axes=("i",), in_specs=specs,
        out_axes=(0,), fn=fn, flops_per_point=flops_per_point,
        pad_safe=pad_safe, **props,
    )


def make_reduce(name: str, monoid: Monoid = Monoid.SUM, *,
                flops_per_point: float = 1.0) -> Elementary:
    def fn(x):
        if monoid is Monoid.SUM:
            return jnp.sum(x)
        if monoid is Monoid.MAX:
            return jnp.max(x)
        return jnp.min(x)

    return Elementary(
        name=name, kind=Kind.REDUCE, formal_axes=("i",),
        in_specs=(ArgSpec((0,)),), out_axes=(), fn=fn, monoid=monoid,
        flops_per_point=flops_per_point,
        linear_args=(0,) if monoid is Monoid.SUM else (),
    )


def make_nested_map(name: str, fn: Callable, in_axes: Sequence[Sequence[int]], *,
                    flops_per_point: float = 1.0, elem: tuple[int, int] = (8, 128),
                    pad_safe: bool = True, **props) -> Elementary:
    """Depth-2 map producing a matrix indexed (i, j)."""
    return Elementary(
        name=name, kind=Kind.NESTED_MAP, formal_axes=("i", "j"),
        in_specs=tuple(ArgSpec(tuple(a)) for a in in_axes), out_axes=(0, 1),
        fn=fn, flops_per_point=flops_per_point, elem=elem, pad_safe=pad_safe,
        **props,
    )


def make_tensor_map(name: str, fn: Callable, in_axes: Sequence[Sequence[int]],
                    depth: int, *, flops_per_point: float = 1.0,
                    pad_safe: bool = True) -> Elementary:
    """Depth-``depth`` map producing a rank-``depth`` tensor.

    Extension past the paper's depth-2 taxonomy (batched matrix maps
    etc.); ``in_axes`` follows the ``make_nested_map`` convention."""
    return Elementary(
        name=name, kind=Kind.NESTED_MAP,
        formal_axes=tuple(f"a{k}" for k in range(depth)),
        in_specs=tuple(ArgSpec(tuple(a)) for a in in_axes),
        out_axes=tuple(range(depth)), fn=fn,
        flops_per_point=flops_per_point, pad_safe=pad_safe,
    )


def make_nested_map_reduce(name: str, fn: Callable,
                           in_axes: Sequence[Sequence[int]],
                           out_axis: int, *, monoid: Monoid = Monoid.SUM,
                           flops_per_point: float = 2.0,
                           elem: tuple[int, int] = (8, 128),
                           **props) -> Elementary:
    """Depth-2 map over ``out_axis`` of a reduce over the other axis.

    E.g. gemv (out_axis=0, reduce over j):  y_i = sum_j A_ij x_j
         gemtv (out_axis=1, reduce over i): s_j = sum_i A_ij r_i
    ``fn`` must compute the *partial* reduction over the block it is given
    (e.g. ``A_blk @ x_blk``); the compiler accumulates partials with the
    monoid across blocks — the paper's "accumulable output" (Alg. 1).
    """
    return Elementary(
        name=name, kind=Kind.NESTED_MAP_REDUCE, formal_axes=("i", "j"),
        in_specs=tuple(ArgSpec(tuple(a)) for a in in_axes), out_axes=(out_axis,),
        fn=fn, monoid=monoid, flops_per_point=flops_per_point, elem=elem,
        **props,
    )


def make_tensor_map_reduce(name: str, fn: Callable,
                           in_axes: Sequence[Sequence[int]],
                           reduce_axis: int, *, depth: int = 3,
                           **props) -> Elementary:
    """Depth-``depth`` map over every axis but ``reduce_axis`` of a sum
    over that one.  ``in_axes`` indexes each operand by a subset of the
    axes, as in ``make_nested_map``; the output keeps the other axes in
    order.  E.g. with axes ``(h, t, c)``:

    scores (reduce_axis=2, ``in_axes=[(0, 2), (1, 2)]``): s_ht = sum_c q_hc k_tc
    values (reduce_axis=1, ``in_axes=[(0, 1), (1, 2)]``): o_hc = sum_t w_ht v_tc

    and at depth 4, with axes ``(e, c, i, h)``:

    expert projection (reduce_axis=3, ``in_axes=[(0, 1, 3), (0, 2, 3)]``):
    g_eci = sum_h x_ech w_eih

    ``fn`` computes the partial sum over the blocks it is given, as in
    ``make_nested_map_reduce``; a multiply and an add per point."""
    return Elementary(
        name=name, kind=Kind.NESTED_MAP_REDUCE,
        formal_axes=tuple(f"a{k}" for k in range(depth)),
        in_specs=tuple(ArgSpec(tuple(a)) for a in in_axes),
        out_axes=tuple(a for a in range(depth) if a != reduce_axis),
        fn=fn, flops_per_point=2.0, **props)


# ---------------------------------------------------------------------------
# Non-multilinear map primitives (the ops an LM decode step needs).
#
# ``pad_safe=False``: a zero lane maps to 1.0 (exp) or inf (rsqrt), so
# zero-padding is NOT reduction-safe through these — graphs routing them
# into a reduction must be served through per-lane masking
# (``core.masking``) instead of whole-graph identity padding.
# ---------------------------------------------------------------------------

exp_map = make_map("exp", jnp.exp, arity=1, flops_per_point=1,
                   pad_safe=False)
rsqrt_map = make_map("rsqrt", lambda x: jax.lax.rsqrt(x), arity=1,
                     flops_per_point=1, pad_safe=False)
# exp(x - m) with a broadcast (reduce-finished) max — the softmax core;
# a zero lane maps to exp(-m), not zero
exp_sub = make_map("exp_sub", lambda x, m: jnp.exp(x - m), arity=2,
                   scalar_args=(1,), flops_per_point=2, pad_safe=False,
                   exp_sub_args=(0, 1))
