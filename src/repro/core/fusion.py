"""Fusion legality + optimization-space generation (paper §3.2, §4.2).

A *fusion* is a subset of the call DAG that can be glued into one kernel.
Legality rules, transposed from CUDA thread blocks to Pallas grids:

1. **One iteration space.**  A fusion iterates over the union of its
   calls' unified axes (paper: same thread-block-to-data mapping; also
   "never fuse different nesting depths", §3.2.3).  A call over a
   subset of them is admitted only if every axis it lacks is one whole
   block (``Fusion.whole_roots``): it then computes once per grid step
   on its natural block, and an output of it is never revisited.
2. **Reduce consumption: a block-local or a phase barrier.**  The
   *finished* result of a reduction is only available once its reduce
   axes complete, which on CUDA meant a global barrier (= kernel
   boundary, §3.2.2).  On the Pallas backend that barrier becomes local
   wherever it can (DESIGN.md §2):

   * *block-local*: under an implementation whose blocks hold each of
     the reduction's reduce axes whole (grid extent 1), the reduction
     finishes inside every grid step and is consumed in the same step
     (``local_reductions``);
   * *phase*: otherwise a leading phase grid axis; phase p accumulates
     the reduction into a VMEM scratch buffer, phase p+1 reads the
     finished value back.  That requires a grid order with every
     phased reduction's reduce axes as an innermost suffix, so the
     consumed reduce-axis sets must form a chain under inclusion.

   A fusion whose calls share one axis set and whose consumed
   reduce-axis sets form a chain needs nothing more (either barrier
   serves).  Any other fusion — a call over fewer axes, or a broken
   chain — is legal only with every consumed reduction block-local, so
   their reduce axes join ``whole_roots``; where no block that size
   fits VMEM, ``enumerate_impls`` yields nothing and the partition
   search covers those calls with smaller fusions (the documented
   *group-split*).
3. **Convexity.**  No path from a fusion member to another fusion member
   may leave the fusion (the outside node could not be scheduled).
4. **Connectivity / usefulness.**  Members must be connected through
   shared data (an internal edge or a shared input array); anything else
   spares no memory transfers and is pruned (§4.2).

**Online softmax.**  A group that holds a softmax chain over one axis t
(``online_roles``) takes rule 1 over the union of its calls' axes and
replaces rule 2: its MAX over t is consumed in the same sweep over t,
as a running max that rescales every sum it feeds (DESIGN.md §2).
"""
from __future__ import annotations

import dataclasses
import itertools
from typing import Iterable

from .elementary import Monoid
from .graph import CallNode, Graph, Var

#: the roles of an online-softmax group's calls (``online_roles``)
PRE, MAX, EXP, DIV, ACC = "pre", "max", "exp", "div", "acc"


@dataclasses.dataclass(frozen=True)
class Fusion:
    """A legal fusible subgraph: frozenset of call indices."""

    calls: tuple[CallNode, ...]            # topo order
    axis_roots: tuple[int, ...]            # unified iteration axes (sorted)
    axis_sizes: tuple[int, ...]
    internal_vars: tuple[Var, ...]         # stay in VMEM
    external_inputs: tuple[Var, ...]       # streamed from HBM
    outputs: tuple[Var, ...]               # written to HBM
    # an online-softmax group: the one axis its kernel streams, and each
    # call's role (``online_roles``); None and () for any other group
    stream_root: int | None = None
    roles: tuple[str, ...] = ()
    # the axes every implementation must block whole (rules 1-2): each
    # axis a member lacks, and, where the group needs them block-local,
    # its consumed reductions' reduce axes (sorted; () for most groups)
    whole_roots: tuple[int, ...] = ()

    @property
    def key(self) -> frozenset:
        return frozenset(c.idx for c in self.calls)

    @property
    def depth(self) -> int:
        return len(self.axis_roots)

    def __repr__(self):
        names = "+".join(c.elem.name for c in self.calls)
        return f"Fusion[{names}]"


def broadcastable(src: tuple[int, ...], dst: tuple[int, ...]) -> bool:
    """True iff a block over axis roots ``src`` broadcasts onto one over
    ``dst`` the way the online kernel spells it (``codegen._broadcast``):
    a scalar, the same axes, or one axis of a two-axis block."""
    return (not src or src == dst
            or (len(src) == 1 and len(dst) == 2 and src[0] in dst))


def online_roles(g: Graph, members: list[CallNode],
                 idxset: set[int]) -> tuple[int, tuple[str, ...]] | None:
    """``(t, roles)`` if ``members`` (topo order) form an online-softmax
    group over the axis root t, else None.

    The shape, read off the graph and the elementaries' properties:

    * ``max``: the one MAX reduction consumed in the group, over t alone;
    * ``exp``: maps ``exp(x - m)`` (``exp_sub_args``) of the max;
    * ``div``: maps ``num / den`` (``div_args``) of an ``exp`` output by
      an ``acc`` output;
    * ``acc``: SUM reductions over t alone, linear in their one argument
      that is an ``exp`` or ``div`` output;
    * ``pre``: every other call, reading none of these and not reducing
      over t.

    Every call iterates over t, the group writes only ``acc`` outputs,
    and the max's (and each divisor's) axes broadcast onto each ``acc``
    output.  The kernel keeps a running max m and a running sum per
    ``acc``; each t block rescales the sums by ``exp(m_old - m_new)``,
    and the divisions wait for the last block (DESIGN.md §2)."""
    maxes = [c for c in members if c.elem.is_reduction
             and c.elem.monoid is Monoid.MAX
             and any(cc.idx in idxset for cc in g.consumers(c.out))]
    if len(maxes) != 1:
        return None
    mx = maxes[0]

    def roots(v: Var) -> tuple[int, ...]:
        return tuple(g.axis_root(a) for a in v.axis_ids)

    def reduced(c: CallNode) -> set[int]:
        return set(g.call_axis_roots(c)) - set(roots(c.out))

    if len(reduced(mx)) != 1:
        return None
    (t,) = reduced(mx)
    role: dict[Var, str] = {}         # the role of each member's output
    divisor: dict[Var, Var] = {}      # div output -> its den
    for c in members:
        if t not in g.call_axis_roots(c):
            return None
        late = [i for i, a in enumerate(c.args) if role.get(a, PRE) != PRE]
        e = c.elem
        if c is mx:
            r = MAX if not late else None
        elif not late:
            r = PRE if t not in reduced(c) else None
        elif e.exp_sub_args and not e.is_reduction:
            x, m = e.exp_sub_args
            r = (EXP if late == [m] and c.args[m] is mx.out
                 and t in roots(c.args[x]) else None)
        elif e.div_args and not e.is_reduction:
            num, den = e.div_args
            r = (DIV if sorted(late) == sorted((num, den))
                 and role[c.args[num]] == EXP and role[c.args[den]] == ACC
                 else None)
            if r:
                divisor[c.out] = c.args[den]
        elif (e.monoid is Monoid.SUM and reduced(c) == {t}
              and len(late) == 1):
            a = c.args[late[0]]
            r = (ACC if late[0] in e.linear_args and role[a] in (EXP, DIV)
                 and c.args.count(a) == 1
                 and broadcastable(roots(mx.out), roots(c.out))
                 and (a not in divisor or broadcastable(
                     roots(divisor[a]), roots(c.out))) else None)
        else:
            r = None
        if r is None:
            return None
        role[c.out] = r
        consumed_outside = any(cc.idx not in idxset
                               for cc in g.consumers(c.out))
        if r != ACC and (g.escapes(c.out) or consumed_outside):
            return None
    return t, tuple(role[c.out] for c in members)


def consumed_reductions(f: Fusion, g: Graph) -> tuple[CallNode, ...]:
    """Reduction members whose output is consumed *inside* ``f``: the
    calls whose finished value the kernel must have before a consumer
    reads it, block-local or through a phase (rule 2).  An
    online-softmax group has none: its running max and sums are read
    unfinished."""
    if f.stream_root is not None:
        return ()
    idxset = {c.idx for c in f.calls}
    return tuple(c for c in f.calls if c.elem.is_reduction
                 and any(cc.idx in idxset for cc in g.consumers(c.out)))


def reduce_roots(c: CallNode, g: Graph) -> frozenset[int]:
    """The axis roots call ``c`` reduces over: those it iterates and
    its output lacks."""
    return (frozenset(g.call_axis_roots(c))
            - {g.axis_root(a) for a in c.out.axis_ids})


def local_reductions(f: Fusion, g: Graph,
                     whole: Iterable[int] | None = None
                     ) -> tuple[CallNode, ...]:
    """The consumed reductions that are *block-local* when the axis
    roots ``whole`` are each one whole block (default: the fusion's own
    ``whole_roots``): each of their reduce axes is whole, so the value
    finishes inside every grid step and is consumed in that step."""
    whole = set(f.whole_roots if whole is None else whole)
    return tuple(c for c in consumed_reductions(f, g)
                 if reduce_roots(c, g) <= whole)


def call_phases(f: Fusion, g: Graph, whole: Iterable[int] | None = None
                ) -> tuple[dict[int, int], int]:
    """Phase assignment for a (possibly multi-phase) kernel body, where
    the axis roots ``whole`` are each one whole block (default: the
    fusion's ``whole_roots``, what every implementation holds).

    ``phase(c)`` is the max over c's in-fusion producers p of
    ``phase(p) + 1`` if p is a consumed reduction that is not
    block-local (its finished value only becomes visible one full grid
    sweep later) else ``phase(p)``; calls fed only by external inputs
    are phase 0.  Returns ``(call idx -> phase, n_phases)``;
    ``n_phases == 1`` means the group needs no phase axis (the
    single-sweep kernel)."""
    local = {c.idx for c in local_reductions(f, g, whole)}
    phased = {c.idx for c in consumed_reductions(f, g)} - local
    producer = {c.out: c for c in f.calls}
    phase: dict[int, int] = {}
    for c in f.calls:
        p = 0
        for a in c.args:
            pc = producer.get(a)
            if pc is not None:
                p = max(p, phase[pc.idx] + (1 if pc.idx in phased else 0))
        phase[c.idx] = p
    n_phases = 1 + (max(phase.values()) if phase else 0)
    return phase, n_phases


def _reachability(g: Graph) -> dict[int, set[int]]:
    """call idx -> set of call idxs reachable (downstream)."""
    reach: dict[int, set[int]] = {c.idx: set() for c in g.calls}
    for c in reversed(g.calls):
        for consumer in g.consumers(c.out):
            reach[c.idx].add(consumer.idx)
            reach[c.idx] |= reach[consumer.idx]
    return reach


def analyse_group(g: Graph, members: Iterable[CallNode],
                  reach: dict[int, set[int]] | None = None) -> Fusion | None:
    """Return a Fusion if ``members`` is legal, else None."""
    members = sorted(set(members), key=lambda c: c.idx)
    if not members:
        return None
    idxset = {c.idx for c in members}

    call_roots = [tuple(sorted(g.call_axis_roots(c))) for c in members]
    if any(len(set(roots)) != len(roots) for roots in call_roots):
        return None  # degenerate: same axis twice
    online = online_roles(g, members, idxset)
    root_to_size = {}
    for c in members:
        for r, s in zip(g.call_axis_roots(c), c.axis_sizes):
            root_to_size[r] = s

    # rule 1: the union of the calls' axes; a call over fewer of them
    # needs each axis it lacks whole, and an online-softmax group's one
    # consumed MAX needs no phase
    ref_roots = tuple(sorted(root_to_size))
    whole: set[int] = set()
    if online is None:
        for roots in call_roots:
            whole |= set(ref_roots) - set(roots)
        # rule 2: a consumed reduction is block-local under blocks that
        # hold its reduce axes whole, else it needs a phase, and one
        # grid order must put every phased reduce-axis set as an
        # innermost suffix — i.e. the sets form a chain under inclusion.
        # A group that is uniform (no axis missing) and chained may use
        # either; any other makes every consumed reduction block-local,
        # and where no such block fits VMEM, enumerate_impls yields
        # nothing and the search splits the group (DESIGN.md §2)
        consumed_sets = sorted(
            (reduce_roots(c, g) for c in members if c.elem.is_reduction
             and any(cc.idx in idxset for cc in g.consumers(c.out))),
            key=len)
        chained = all(small <= big for small, big
                      in zip(consumed_sets, consumed_sets[1:]))
        if whole or not chained:
            for rr in consumed_sets:
                whole |= rr

    # rule 3: convexity
    if reach is None:
        reach = _reachability(g)
    for p in members:
        for c in members:
            if p.idx >= c.idx:
                continue
            for mid in g.calls:
                if mid.idx in idxset:
                    continue
                if mid.idx in reach[p.idx] and c.idx in reach[mid.idx]:
                    return None

    # rule 4: connectivity via shared vars
    if len(members) > 1:
        adj: dict[int, set[int]] = {c.idx: set() for c in members}
        var_users: dict[Var, list[int]] = {}
        for c in members:
            touched = list(c.args) + [c.out]
            for v in touched:
                var_users.setdefault(v, []).append(c.idx)
        for users in var_users.values():
            for a, b in itertools.combinations(set(users), 2):
                adj[a].add(b)
                adj[b].add(a)
        seen = {members[0].idx}
        stack = [members[0].idx]
        while stack:
            x = stack.pop()
            for y in adj[x]:
                if y not in seen:
                    seen.add(y)
                    stack.append(y)
        if len(seen) != len(members):
            return None

    # classify vars
    produced = {c.out for c in members}
    internal, outputs = [], []
    for c in members:
        v = c.out
        consumed_outside = any(cc.idx not in idxset for cc in g.consumers(v))
        if g.escapes(v) or consumed_outside:
            outputs.append(v)
        else:
            internal.append(v)
    ext_inputs: list[Var] = []
    seen_vars = set()
    for c in members:
        for a in c.args:
            if a not in produced and a not in seen_vars:
                seen_vars.add(a)
                ext_inputs.append(a)

    stream_root, roles = online or (None, ())
    return Fusion(
        calls=tuple(members),
        axis_roots=ref_roots,
        axis_sizes=tuple(root_to_size[r] for r in ref_roots),
        internal_vars=tuple(internal),
        external_inputs=tuple(ext_inputs),
        outputs=tuple(outputs),
        stream_root=stream_root,
        roles=roles,
        whole_roots=tuple(sorted(whole)),
    )


def saves_traffic(f: Fusion, g: Graph) -> bool:
    """Paper §4.2: prune fusions which do not spare memory transfers.

    A fusion spares traffic iff it has an internal var (store+load saved)
    or two members share an external input (load saved).
    """
    if len(f.calls) == 1:
        return True  # singleton "fusion" == unfused kernel, always kept
    if f.internal_vars:
        return True
    produced = {c.out for c in f.calls}
    for c in f.calls:
        if any(a in produced for a in c.args):
            return True  # consumer reads producer via VMEM (even if the
            #              value also escapes to HBM, its reload is spared)
    use_count: dict[Var, int] = {}
    for c in f.calls:
        for a in set(c.args):
            use_count[a] = use_count.get(a, 0) + 1
    return any(n > 1 for n in use_count.values())


def enumerate_fusions(g: Graph, max_size: int = 8) -> list[Fusion]:
    """All legal fusions (incl. singletons), traffic-sparing ones only.

    Scripts are small (the paper's largest, GEMVER, has a handful of
    calls), so for n <= 16 we exhaustively test every subset; beyond that
    we grow connected subsets breadth-first.
    """
    reach = _reachability(g)
    calls = g.calls
    n = len(calls)
    out: list[Fusion] = []
    if n <= 16:
        for r in range(1, min(max_size, n) + 1):
            for combo in itertools.combinations(calls, r):
                f = analyse_group(g, combo, reach)
                if f is not None and saves_traffic(f, g):
                    out.append(f)
        return out
    # BFS growth fallback for large graphs (may miss exotic convex sets
    # reachable only through non-convex intermediates; acceptable heuristic)
    seen: set[frozenset] = set()
    frontier: list[tuple[CallNode, ...]] = []
    for c in calls:
        f = analyse_group(g, (c,), reach)
        assert f is not None
        out.append(f)
        seen.add(f.key)
        frontier.append((c,))
    while frontier:
        nxt: list[tuple[CallNode, ...]] = []
        for grp in frontier:
            if len(grp) >= max_size:
                continue
            for c in calls:
                if c in grp:
                    continue
                cand = tuple(sorted(set(grp) | {c}, key=lambda x: x.idx))
                key = frozenset(x.idx for x in cand)
                if key in seen:
                    continue
                seen.add(key)
                f = analyse_group(g, cand, reach)
                if f is None:
                    continue
                nxt.append(cand)
                if saves_traffic(f, g):
                    out.append(f)
        frontier = nxt
    return out
