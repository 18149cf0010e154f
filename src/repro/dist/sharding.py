"""Sharding conventions for the model zoo and the serving engine
(DESIGN.md §7).

Pspec builders return **pytrees of ``jax.sharding.NamedSharding``**
matching the structure of the abstract trees they are given, ready to be
passed straight to ``jax.jit(in_shardings=...)``:

* ``param_pspecs`` / ``opt_pspecs`` — FSDP/ZeRO-3: every tensor is
  sharded over the data-parallel axes (``pod`` x ``data``) on its
  largest evenly-divisible dimension; when ``cfg.fsdp_only`` is False
  (MoE archs) a second dimension is additionally sharded over ``model``.
* ``batch_pspecs`` — the leading global-batch dimension over the
  data-parallel axes, everything else replicated.
* ``cache_pspecs`` — KV/SSM cache leaves are ``(layers, batch, ...)``;
  the batch dimension shards over data-parallel axes and the head
  dimension over ``model`` when it divides evenly (serving keeps TP).

A dimension that does not divide its axis product stays replicated —
the builders never emit an uneven sharding, so any mesh from
``launch.mesh`` is safe.

``shard_program`` lifts a compiled ``BatchedProgram`` with ``shard_map``
so one global request batch executes as per-replica row blocks on the
``data`` axis — the sharded serving engine's dispatch path.

Every helper takes an explicit ``mesh``; ``current_mesh()`` additionally
picks up the ambient mesh set by ``jax.sharding.set_mesh`` or a
``with mesh:`` context.
"""
from __future__ import annotations

from typing import Any, Callable, Sequence

import jax
from jax.sharding import NamedSharding, PartitionSpec as P


# ---------------------------------------------------------------------------
# mesh helpers
# ---------------------------------------------------------------------------

def current_mesh(mesh=None):
    """The mesh to shard over: ``mesh`` if given, else the ambient one.

    Checks, in order: the explicit argument, the (abstract) mesh
    installed by ``jax.sharding.set_mesh``, and the mesh of a
    ``with mesh:`` context (which jax records only as the
    thread-resource mesh).  Returns ``None`` when no mesh is active.
    """
    if mesh is not None:
        return mesh
    m = jax.sharding.get_abstract_mesh()
    if m.axis_names:
        return m
    from jax.interpreters import pxla
    m = pxla.thread_resources.env.physical_mesh
    return m if m.axis_names else None


def mesh_axis_sizes(mesh) -> dict[str, int]:
    """``{axis name: size}`` for a concrete or abstract mesh."""
    return dict(mesh.shape)


def dp_axes(mesh) -> tuple[str, ...]:
    """The data-parallel axes present in ``mesh`` (``pod`` and/or
    ``data``), in mesh order."""
    return tuple(a for a in mesh.axis_names if a in ("pod", "data"))


def axis_product(mesh, axes: Sequence[str]) -> int:
    sizes = mesh_axis_sizes(mesh)
    out = 1
    for a in axes:
        out *= sizes[a]
    return out


def mesh_fingerprint(mesh) -> str:
    """Stable content key of a mesh (program-cache component: the same
    plan shard_map-lifted over different meshes is a different XLA
    program).  Includes the device identities, not just the topology —
    two ('data', 4) meshes over disjoint device subsets must not alias
    (an abstract mesh has no devices and keys on topology alone)."""
    ids = None
    devs = getattr(mesh, "devices", None)
    if devs is not None:
        try:
            ids = tuple(int(d.id) for d in devs.flat)
        except (AttributeError, TypeError):
            ids = None
    return repr((tuple(mesh_axis_sizes(mesh).items()), ids))


def shard_map_unchecked(f: Callable, mesh, in_specs, out_specs) -> Callable:
    """``jax.shard_map`` with replication checking off: bodies here are
    per-shard programs whose unmentioned-axis replication is true by
    construction."""
    return jax.shard_map(f, mesh=mesh, in_specs=in_specs,
                         out_specs=out_specs, check_vma=False)


# ---------------------------------------------------------------------------
# pspec builders
# ---------------------------------------------------------------------------

def _is_abstract_leaf(x) -> bool:
    return hasattr(x, "shape") and hasattr(x, "dtype")


def _fsdp_entry(shape, dp: tuple[str, ...], dpn: int,
                model_n: int, use_model: bool) -> P:
    """FSDP spec for one tensor: dp axes on the largest divisible dim,
    optionally ``model`` on the largest remaining divisible dim."""
    spec: list[Any] = [None] * len(shape)
    order = sorted(range(len(shape)), key=lambda i: -shape[i])
    if dp and dpn > 1:
        for i in order:
            if shape[i] % dpn == 0 and shape[i] >= dpn:
                spec[i] = dp if len(dp) > 1 else dp[0]
                break
    if use_model and model_n > 1:
        for i in order:
            if spec[i] is None and shape[i] % model_n == 0 \
                    and shape[i] >= model_n:
                spec[i] = "model"
                break
    return P(*spec)


def param_pspecs(cfg, params, mesh) -> Any:
    """``NamedSharding`` tree for a parameter tree.

    Args:
      cfg: the ``ModelConfig`` (``cfg.fsdp_only`` selects pure FSDP vs
        FSDP + a second ``model``-axis dimension, the MoE default).
      params: pytree of arrays / ``ShapeDtypeStruct``s
        (``models.abstract_params(cfg)``).
      mesh: a mesh from ``launch.mesh`` with ``data`` (and optionally
        ``pod`` / ``model``) axes.

    Returns:
      A pytree with the same structure whose leaves are
      ``NamedSharding``s, usable directly as ``jit`` in/out shardings.

    Example::

        mesh = make_mesh((2, 2, 2), ("pod", "data", "model"))
        aps = models.abstract_params(cfg)
        pspecs = sharding.param_pspecs(cfg, aps, mesh)
        jax.jit(step, in_shardings=(pspecs, ...)).lower(aps, ...)
    """
    dp = dp_axes(mesh)
    dpn = axis_product(mesh, dp)
    sizes = mesh_axis_sizes(mesh)
    model_n = sizes.get("model", 1)
    use_model = not getattr(cfg, "fsdp_only", True)

    def leaf(a):
        return NamedSharding(mesh, _fsdp_entry(tuple(a.shape), dp, dpn,
                                               model_n, use_model))

    return jax.tree_util.tree_map(leaf, params, is_leaf=_is_abstract_leaf)


def opt_pspecs(cfg, opt_state, mesh, params=None) -> Any:
    """``NamedSharding`` tree for an AdamW optimizer state.

    Moments follow the same FSDP rule as their parameters (int8
    block-quantized moments are ``{"q", "scale"}`` dicts whose leaves
    shard independently); the scalar ``step`` is replicated.

    Args:
      cfg: the ``ModelConfig``.
      opt_state: pytree from ``optim.abstract_opt_state(cfg, params)``.
      mesh: the mesh to shard over.
      params: accepted for signature symmetry with the launcher; the
        rule derives everything from the moment shapes themselves.

    Returns:
      A matching pytree of ``NamedSharding``s.
    """
    del params
    return param_pspecs(cfg, opt_state, mesh)


def batch_pspecs(cfg, batch, mesh) -> Any:
    """``NamedSharding`` tree for a data batch: the leading global-batch
    dimension shards over the data-parallel axes, everything else is
    replicated.  Scalars (and batch dims that don't divide) replicate.
    """
    del cfg
    dp = dp_axes(mesh)
    dpn = axis_product(mesh, dp)

    def leaf(a):
        shape = tuple(a.shape)
        if not shape or not dp or dpn <= 1 or shape[0] % dpn or shape[0] < dpn:
            return NamedSharding(mesh, P())
        return NamedSharding(
            mesh, P(dp if len(dp) > 1 else dp[0],
                    *(None,) * (len(shape) - 1)))

    return jax.tree_util.tree_map(leaf, batch, is_leaf=_is_abstract_leaf)


# cache leaves are (layers, batch, ...); the axis that may additionally
# shard over `model` is the head dim of KV leaves / the SSD head dim.
_CACHE_MODEL_DIM = {"k": 3, "v": 3, "xk": 3, "xv": 3, "state": 2}


def cache_pspecs(cfg, cache, mesh) -> Any:
    """``NamedSharding`` tree for a decode cache
    (``models.abstract_cache``).

    Cache leaves are ``(layers, batch, ...)``: the batch dimension
    shards over the data-parallel axes; KV/SSM head dimensions shard
    over ``model`` when they divide evenly (serving keeps tensor
    parallelism for the cache even on FSDP-trained archs — the cache
    dominates decode memory).
    """
    del cfg
    dp = dp_axes(mesh)
    dpn = axis_product(mesh, dp)
    model_n = mesh_axis_sizes(mesh).get("model", 1)

    def leaf(name: str, a):
        shape = tuple(a.shape)
        spec: list[Any] = [None] * len(shape)
        if len(shape) > 1 and dp and dpn > 1 and shape[1] % dpn == 0 \
                and shape[1] >= dpn:
            spec[1] = dp if len(dp) > 1 else dp[0]
        hd = _CACHE_MODEL_DIM.get(name)
        if hd is not None and hd < len(shape) and model_n > 1 \
                and shape[hd] % model_n == 0 and shape[hd] >= model_n:
            spec[hd] = "model"
        return NamedSharding(mesh, P(*spec))

    return {k: leaf(k, v) for k, v in cache.items()}


# ---------------------------------------------------------------------------
# sharded serving: shard_map-lift a batched whole-program function
# ---------------------------------------------------------------------------

def shard_program(prog, mesh, axis: str = "data"):
    """Lift a ``BatchedProgram`` over the ``axis`` replicas of ``mesh``.

    The batched whole-program function is pure and positional with a
    leading batch dimension on every input and output, so
    ``shard_map`` splits a global batch into contiguous per-replica row
    blocks — replica ``r`` executes rows ``[r*b/R, (r+1)*b/R)`` as one
    local dispatch, with no cross-replica communication (requests are
    independent).  The global batch size must be a multiple of the
    replica count; the sharded serving engine quantizes its dispatch
    sizes accordingly (``ShardedServingEngine``).

    Args:
      prog: a ``BatchedProgram`` from ``FusionCompiler.compile_batched``
        (must carry ``raw_fn``, the un-jitted vmapped program).
      mesh: mesh holding the replica axis.
      axis: the mesh axis to spread the batch over (default ``data``).

    Returns:
      A new ``BatchedProgram`` whose ``fn`` is the jitted shard_mapped
      program.  If ``axis`` has size 1 the input program is returned
      unchanged (single-device fallback).

    Raises:
      ValueError: if ``prog`` has no ``raw_fn`` or ``mesh`` lacks
        ``axis``.
    """
    from ..core.codegen import BatchedProgram

    sizes = mesh_axis_sizes(mesh)
    if axis not in sizes:
        raise ValueError(f"mesh {tuple(sizes)} has no {axis!r} axis")
    if sizes[axis] == 1:
        return prog
    if getattr(prog, "raw_fn", None) is None:
        raise ValueError("program carries no raw_fn; compile it with "
                         "FusionCompiler.compile_batched")
    spec = P(axis)
    fn = shard_map_unchecked(
        prog.raw_fn, mesh,
        in_specs=(spec,) * len(prog.plan.input_names),
        out_specs=(spec,) * len(prog.plan.outputs))
    return BatchedProgram(graph=prog.graph, plan=prog.plan,
                          max_batch=prog.max_batch, fn=jax.jit(fn),
                          raw_fn=prog.raw_fn)
