"""Expert-parallel MoE via ``shard_map`` (DESIGN.md §7, perf item P10).

``models.common.moe_layer`` relies on GSPMD constraint propagation to
place the expert-parallel collectives.  This module is the *explicit*
formulation: routing/dispatch/combine run replicated (they are cheap,
token-proportional index math), and the expensive expert FFN runs inside
a ``shard_map`` whose specs partition experts over the ``model`` mesh
axis:

* **EP path** (``n_experts % model == 0``): each device owns
  ``E / model`` experts and their ``(D, F)`` weights; the dispatch
  buffer ``(G, E, C, D)`` splits along the expert dim.
* **Replica path** (``model % n_experts == 0``): every expert is
  replicated over ``r = model / E`` devices; the capacity dim pads to a
  multiple of ``r`` and splits, so each replica computes a disjoint
  contiguous slot block of its expert.  Zero-padded slots are exact:
  the FFN maps zero tokens to zero outputs (no biases) and padded slots
  are sliced off before combine.

Both paths produce bit-for-bit the same per-slot FFN math as the GSPMD
layer (same routing, same capacity ``C``, same contractions), so
``moe_layer_ep`` is numerically interchangeable with ``moe_layer`` and
differentiable end to end (``shard_map`` transposes the sharded FFN;
gradients of replicated inputs psum over the mesh automatically).

Group-batch sharding: the token group dim ``G`` additionally splits over
the data-parallel axes when it divides evenly.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from .sharding import (axis_product, current_mesh, dp_axes, mesh_axis_sizes,
                       shard_map_unchecked)


def supported(cfg, mesh=None) -> bool:
    """Can ``moe_layer_ep`` run ``cfg`` on the (ambient) mesh?

    True when the mesh has a ``model`` axis of size > 1 and the expert
    count divides it or is divided by it (EP / replica path).  False
    otherwise — callers fall back to the GSPMD ``moe_layer``.
    """
    mesh = current_mesh(mesh)
    if mesh is None or not getattr(cfg, "n_experts", 0) or cfg.topk < 1:
        return False
    mp = mesh_axis_sizes(mesh).get("model", 1)
    if mp <= 1:
        return False
    E = cfg.n_experts
    return E % mp == 0 or mp % E == 0


def moe_layer_ep(cfg, x, p, mesh=None):
    """Expert-parallel MoE layer; drop-in for
    ``models.common.moe_layer``.

    Args:
      cfg: ``ModelConfig`` with MoE fields (``n_experts``, ``topk``,
        ``capacity_factor``, ``d_ff_moe``, optional shared experts).
      x: ``(G, Tg, D)`` group-batched tokens.
      p: param dict — ``router (D, E)``, ``wg``/``wu`` ``(E, D, F)``,
        ``wd (E, F, D)``, optional ``wg_s``/``wu_s``/``wd_s``.
      mesh: mesh to partition over; defaults to the ambient mesh
        (``jax.sharding.set_mesh`` or ``with mesh:``).

    Returns:
      ``(y, aux)``: ``(G, Tg, D)`` outputs and the scalar Switch-style
      load-balance loss, exactly as ``moe_layer``.

    Raises:
      ValueError: when no mesh is active or ``supported(cfg, mesh)`` is
        False (expert count incompatible with the ``model`` axis).
    """
    mesh = current_mesh(mesh)
    if mesh is None or not supported(cfg, mesh):
        raise ValueError(
            "moe_layer_ep needs an active mesh whose 'model' axis size "
            "divides (or is divided by) n_experts; guard calls with "
            "moe_ep.supported(cfg)")

    G, Tg, D = x.shape
    E, k = cfg.n_experts, cfg.topk
    C = max(8, int(Tg * k / E * cfg.capacity_factor))
    C = min(C, Tg * k)

    # -- routing + dispatch (replicated; identical math to moe_layer) -------
    logits = jnp.einsum("gtd,de->gte", x.astype(jnp.float32),
                        p["router"].astype(jnp.float32))
    probs = jax.nn.softmax(logits, axis=-1)               # (G, Tg, E)
    gate, idx = jax.lax.top_k(probs, k)                   # (G, Tg, k)
    gate = gate / jnp.maximum(jnp.sum(gate, -1, keepdims=True), 1e-9)

    A = Tg * k
    flat_e = idx.reshape(G, A)
    flat_t = jnp.broadcast_to(
        jnp.repeat(jnp.arange(Tg), k)[None], (G, A))
    flat_g = gate.reshape(G, A)
    order = jnp.argsort(flat_e, axis=-1, stable=True)
    se = jnp.take_along_axis(flat_e, order, axis=-1)
    st = jnp.take_along_axis(flat_t, order, axis=-1)
    sg = jnp.take_along_axis(flat_g, order, axis=-1)
    counts = jnp.sum(jax.nn.one_hot(se, E, dtype=jnp.int32), axis=1)
    starts = jnp.cumsum(counts, axis=-1) - counts
    rank = jnp.arange(A)[None] - jnp.take_along_axis(starts, se, axis=-1)
    keep = rank < C
    slot = se * C + jnp.where(keep, rank, 0)              # (G, A)

    gid = jnp.arange(G)[:, None]
    gathered = jnp.where(keep[..., None], x[gid, st], 0)
    xe = jnp.zeros((G, E * C, D), x.dtype).at[gid, slot].add(gathered)
    xe = xe.reshape(G, E, C, D)

    # -- expert FFN (shard_mapped over the model axis) -----------------------
    mp = mesh_axis_sizes(mesh)["model"]
    dp = dp_axes(mesh)
    dpn = axis_product(mesh, dp)
    gax = (dp if len(dp) > 1 else dp[0]) \
        if dp and dpn > 1 and G % dpn == 0 and G >= dpn else None

    def ffn(xe_l, wg_l, wu_l, wd_l):
        h = jnp.einsum("gecd,edf->gecf", xe_l, wg_l)
        if cfg.act == "swiglu":
            h = jax.nn.silu(h) * jnp.einsum("gecd,edf->gecf", xe_l, wu_l)
        else:
            h = jax.nn.gelu(h)
        return jnp.einsum("gecf,efd->gecd", h, wd_l)

    run = shard_map_unchecked(
        ffn, mesh,
        in_specs=(P(gax, "model", None, None), P("model", None, None),
                  P("model", None, None), P("model", None, None)),
        out_specs=P(gax, "model", None, None))

    if E % mp == 0:                                       # EP path
        ye = run(xe, p["wg"], p["wu"], p["wd"])
    else:                                                 # replica path
        r = mp // E
        C_pad = -(-C // r) * r
        xe_p = jnp.pad(xe, ((0, 0), (0, 0), (0, C_pad - C), (0, 0)))
        xe_s = xe_p.reshape(G, E * r, C_pad // r, D)
        rep = lambda w: jnp.repeat(w, r, axis=0)
        ye = run(xe_s, rep(p["wg"]), rep(p["wu"]), rep(p["wd"]))
        ye = ye.reshape(G, E, C_pad, D)[:, :, :C]

    # -- combine (replicated; identical math to moe_layer) -------------------
    ye = ye.reshape(G, E * C, D)
    contrib = ye[gid, slot]
    contrib = jnp.where(keep[..., None], contrib, 0) \
        * sg[..., None].astype(x.dtype)
    out = jnp.zeros((G, Tg, D), x.dtype).at[gid, st].add(contrib)

    if cfg.n_shared_experts:
        from ..models.common import mlp
        xs = x.reshape(G * Tg, D)
        out = out + mlp(cfg, xs, p.get("wg_s"), p["wu_s"], p["wd_s"]
                        ).reshape(G, Tg, D)
    me = jnp.mean(probs, axis=(0, 1))
    ce = jnp.mean(jax.nn.one_hot(idx[..., 0], E), axis=(0, 1))
    aux = E * jnp.sum(me * ce)
    return out, aux
