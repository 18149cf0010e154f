"""Elementary functions for LM decode-step workloads.

These extend ``blas.elementary_lib`` with the non-multilinear pieces a
decoder step needs: the rmsnorm scale map, softmax stages, attention
contractions and the precision-matched AdamW moment updates.

Bitwise discipline (DESIGN.md §10): every ``fn`` body is written so the
fused whole-program XLA computation reproduces the corresponding
``repro.kernels.ref`` / ``repro.models`` oracle *bit for bit* on CPU
XLA.  Two non-obvious consequences:

* the value contraction is phrased as the reference's 4-D einsum with
  unit head/group dims — a 2-D contraction of the same numbers can
  lower to a differently-associated loop whose low bits diverge (the
  score contraction is 2-D: see ``attn_score``);
* AdamW takes ``1 - beta`` and the bias corrections as *inputs*
  (``omb*``, ``c*``) rather than computing them from ``beta`` in f32:
  ``f32(0.9)``-derived ``1 - b`` is 0.100000024 while the reference's
  python-float path rounds 0.1 once — feeding the pre-rounded scalars
  makes both sides multiply by the identical constant.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

from repro.core.elementary import (Monoid, make_map, make_nested_map_reduce)

# --- rmsnorm -----------------------------------------------------------------

# y_i = x_i * rsqrt(ss * inv_d + eps) * gamma_i with the reduce-finished
# sum-of-squares ``ss`` and exact 1/n as broadcast scalars.  pad_safe:
# the rsqrt is of a *scalar* — zero lanes of x/gamma still map to zero,
# so zero-padded serving stays reduction-safe downstream.
rms_scale = make_map(
    "rms_scale",
    lambda ss, inv_d, x, gamma:
        x * jax.lax.rsqrt(ss * inv_d + jnp.float32(1e-6)) * gamma,
    arity=4, scalar_args=(0, 1), flops_per_point=4)

# --- softmax stages ----------------------------------------------------------

# e_i = exp(x_i - m): re-exported from core so scripts and tests have one
# import site for the decode-step map set.
from repro.core.elementary import exp_map, exp_sub, rsqrt_map  # noqa: E402,F401

# w_i = e_i / z with the reduce-finished normalizer z broadcast
div_by = make_map(
    "div_by", lambda z, e: e / z, arity=2, scalar_args=(0,),
    flops_per_point=1)

# --- attention contractions --------------------------------------------------

# s_s = sum_d K_sd q_d — the decode score row.  A plain 2-D contraction:
# the reference's 4-D GQA phrasing (below) pads every K row of a Pallas
# block to its own (8, 128) tile, tens of KiB of VMEM per row.
attn_score = make_nested_map_reduce(
    "attn_score",
    lambda K, q: jnp.einsum("...sd,...d->...s", K, q, precision="highest"),
    in_axes=[(0, 1), (1,)], out_axis=0, flops_per_point=2)

# o_d = sum_s w_s V_sd — the weighted value sum, phrased as the
# reference's GQA einsum with unit h/g dims (see module docstring).
attn_out = make_nested_map_reduce(
    "attn_out",
    lambda V, w: jnp.einsum(
        "...hgs,...shd->...hgd",
        w[..., None, None, :], V[..., :, None, :],
        precision="highest")[..., 0, 0, :],
    in_axes=[(0, 1), (0,)], out_axis=1, flops_per_point=2)

# --- AdamW (precision-matched variants of repro.optim.fused) -----------------

ema_pm = make_map(
    "ema_pm", lambda b, omb, m, g: b * m + omb * g, arity=4,
    scalar_args=(0, 1), flops_per_point=3)
ema_sq_pm = make_map(
    "ema_sq_pm", lambda b, omb, v, g: b * v + omb * (g * g), arity=4,
    scalar_args=(0, 1), flops_per_point=4)

# the direction and lr-apply maps are shared with the optimizer verbatim
from repro.optim.fused import adam_dir, apply_lr  # noqa: E402,F401

ALL = {e.name: e for e in [
    rms_scale, exp_map, exp_sub, rsqrt_map, div_by, attn_score, attn_out,
    ema_pm, ema_sq_pm, adam_dir, apply_lr,
]}
