"""Elementary functions for LM decode-step workloads.

These extend ``blas.elementary_lib`` with the non-multilinear pieces a
decoder step needs: the rmsnorm scale map, softmax stages, attention
contractions, latent attention's (MLA) per-head stages, KDA's state
step, the routed experts' projections and gated activation, and the
precision-matched AdamW moment updates.

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

import math

import jax
import jax.numpy as jnp

from repro.core.elementary import (Monoid, col, make_map, make_nested_map,
                                   make_nested_map_reduce, make_tensor_map,
                                   make_tensor_map_reduce)

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
    flops_per_point=1, div_args=(1, 0))

# --- attention contractions --------------------------------------------------

# s_s = sum_d K_sd q_d — the decode score row.  A plain 2-D contraction:
# the reference's 4-D GQA phrasing (below) pads every K row of a Pallas
# block to its own (8, 128) tile, tens of KiB of VMEM per row.
attn_score = make_nested_map_reduce(
    "attn_score",
    lambda K, q: jnp.einsum("...sd,...d->...s", K, q, precision="highest"),
    in_axes=[(0, 1), (1,)], out_axis=0, flops_per_point=2)

# o_d = sum_s w_s V_sd — the weighted value sum, phrased as the
# reference's GQA einsum with unit h/g dims (see module docstring).  V's
# unit head dim leads: behind s it pads every V row of a Pallas block to
# its own (8, 128) tile, and a 4096-row block then asks Mosaic for 147 MB
# of VMEM on a v5e, which has 128.
attn_out = make_nested_map_reduce(
    "attn_out",
    lambda V, w: jnp.einsum(
        "...hgs,...hsd->...hgd",
        w[..., None, None, :], V[..., None, :, :],
        precision="highest")[..., 0, 0, :],
    in_axes=[(0, 1), (0,)], out_axis=1, flops_per_point=2,
    linear_args=(0, 1))

# --- latent attention (MLA), absorbed form ----------------------------------
#
# DeepSeek-V2 (arXiv:2405.04434, section 2.1): every head reads one shared
# latent cache ckv (n, rank) and one shared rotary key cache kr (n, rope).
# Axes: h (heads), t (cache positions), c (latent or rotary width).

#: DeepSeek-V2-Lite's published widths (its config.json)
MLA_HEADS, MLA_RANK, MLA_ROPE, MLA_NOPE = 16, 512, 64, 128

#: The softmax scale DeepSeek's code applies: (qk_nope_head_dim +
#: qk_rope_head_dim) ** -0.5, times mscale ** 2 where YaRN's mscale =
#: 0.1 * mscale_all_dim * ln(factor) + 1, with rope_scaling's factor 40
#: and mscale_all_dim 0.707: 192 ** -0.5 * 1.2608 ** 2 = 0.1147214
MLA_SCALE = (MLA_NOPE + MLA_ROPE) ** -0.5 * (
    0.1 * 0.707 * math.log(40) + 1.0) ** 2

# s_ht = sum_c q_hc k_tc over (h, t, c): the score of every head against
# one cache row block, the cache invariant over h (latent and rotary parts)
mla_score = make_tensor_map_reduce(
    "mla_score",
    lambda q, k: jnp.einsum("...hc,...tc->...ht", q, k,
                            precision="highest"),
    in_axes=[(0, 2), (1, 2)], reduce_axis=2)

# s = scale * (latent score + rotary score)
mla_logits = make_nested_map(
    "mla_logits", lambda a, b: MLA_SCALE * (a + b),
    in_axes=[(0, 1), (0, 1)], flops_per_point=2)

# softmax over t, per head: max, exp(s - max), sum, divide
mla_max = make_nested_map_reduce(
    "mla_max", lambda s: jnp.max(s, axis=-1), in_axes=[(0, 1)],
    out_axis=0, monoid=Monoid.MAX, flops_per_point=1)
mla_exp_sub = make_nested_map(
    "mla_exp_sub", lambda s, m: jnp.exp(s - col(m)),
    in_axes=[(0, 1), (0,)], flops_per_point=2, pad_safe=False,
    exp_sub_args=(0, 1))
mla_sum = make_nested_map_reduce(
    "mla_sum", lambda e: jnp.sum(e, axis=-1), in_axes=[(0, 1)],
    out_axis=0, flops_per_point=1, linear_args=(0,))
mla_div = make_nested_map(
    "mla_div", lambda e, z: e / col(z), in_axes=[(0, 1), (0,)],
    flops_per_point=1, div_args=(0, 1))

# o_hc = sum_t p_ht ckv_tc over (h, t, c): the weighted latent rows
mla_value = make_tensor_map_reduce(
    "mla_value",
    lambda p, v: jnp.einsum("...ht,...tc->...hc", p, v,
                            precision="highest"),
    in_axes=[(0, 1), (1, 2)], reduce_axis=1, linear_args=(0, 1))

# --- Kimi Delta Attention (KDA), one decode step ----------------------------
#
# Kimi Linear (arXiv:2510.26692): every (session, head) row b keeps a
# (key, value) state S, gated per key channel and corrected by a delta
# rule each token.  Axes: b (rows), i (key width), j (value width).  The
# contractions over i are VPU broadcasts and sublane sums: per row they
# are matrix-vector products, which an MXU pass at M = 1 would waste.

#: Kimi-Linear-48B-A3B's published KDA head width (linear_attn_config)
KDA_DIM = 128
#: the eps of the L2 norm of q and k
KDA_EPS = 1e-6


def _kda_contract(x, S):
    """sum_i x_bi S_bij over an (i, j) block of every row."""
    return jnp.sum(col(x) * S, axis=-2)


# ss_b = sum_i x_bi^2, the squared norm of a row of q or k
kda_sumsq = make_nested_map_reduce(
    "kda_sumsq", lambda x: jnp.sum(x * x, axis=-1), in_axes=[(0, 1)],
    out_axis=0, flops_per_point=2)
# the L2-normed key, and the query normed and scaled by KDA_DIM**-0.5
kda_knorm = make_nested_map(
    "kda_knorm", lambda x, ss: x * jax.lax.rsqrt(col(ss) + KDA_EPS),
    in_axes=[(0, 1), (0,)], flops_per_point=2)
kda_qnorm = make_nested_map(
    "kda_qnorm",
    lambda x, ss: x * (jax.lax.rsqrt(col(ss) + KDA_EPS) * KDA_DIM ** -0.5),
    in_axes=[(0, 1), (0,)], flops_per_point=3)


def _softplus(f):
    """log(1 + exp(f)) as max(f, 0) + log1p(t), t = exp(-|f|), which
    cannot overflow.  One Newton step on exp(y) = 1 + t refines the
    log1p, which a Mosaic kernel on a v5e computes far less exactly
    than exp."""
    t = jnp.exp(-jnp.abs(f))
    y = jnp.log1p(t)
    return jnp.maximum(f, 0.0) + y + ((1.0 + t) * jnp.exp(-y) - 1.0)


# the decay alpha_bi = exp(-exp(a_log_b) softplus(f_bi)), in (0, 1)
kda_gate = make_nested_map(
    "kda_gate",
    lambda f, a_log: jnp.exp(-jnp.exp(col(a_log)) * _softplus(f)),
    in_axes=[(0, 1), (0,)], flops_per_point=10, pad_safe=False)
# the gated key kn_bi alpha_bi, which reads the state
kda_ka = make_nested_map(
    "kda_ka", lambda kn, alpha: kn * alpha, in_axes=[(0, 1), (0, 1)],
    flops_per_point=1)
# u_bj = sum_i ka_bi S_bij: the gated state read at the key
kda_u = make_tensor_map_reduce(
    "kda_u", _kda_contract, in_axes=[(0, 1), (0, 1, 2)], reduce_axis=1,
    linear_args=(0, 1))
# w_bj = sigmoid(b_b) (v_bj - u_bj): the write, at strength beta in (0, 1)
kda_w = make_nested_map(
    "kda_w", lambda b, v, u: jax.nn.sigmoid(col(b)) * (v - u),
    in_axes=[(0,), (0, 1), (0, 1)], flops_per_point=2)
# S'_bij = alpha_bi S_bij + kn_bi w_bj: the decayed state and the
# rank-1 delta-rule update, the one rank-3 value a kernel writes
kda_snew = make_tensor_map(
    "kda_snew", lambda S, alpha, kn, w: col(alpha) * S + col(kn) * (
        w[..., None, :]),
    in_axes=[(0, 1, 2), (0, 1), (0, 1), (0, 2)], depth=3, flops_per_point=3)
# o_bj = sum_i qn_bi S'_bij: the new state read out at the query
kda_o = make_tensor_map_reduce(
    "kda_o", _kda_contract, in_axes=[(0, 1), (0, 1, 2)], reduce_axis=1,
    linear_args=(0, 1))

# --- routed experts (MoE), the experts held on one chip ---------------------
#
# DeepSeek-V2 (arXiv:2405.04434, section 2.2): each expert is
# down(silu(gate(x)) * up(x)), its weights in the nn.Linear layout
# (out, in).  Axes: e (experts held), c (the tokens dispatched to each),
# i (expert width), h (hidden), d (hidden, as the down projection's
# output).  At M = tokens per expert the contractions are batched MXU
# matmuls, one batch entry per expert.

#: DeepSeek-V2-Lite's published widths (hidden_size, moe_intermediate_size)
MOE_HIDDEN, MOE_WIDTH = 2048, 1408


def _expert_contract(x, w):
    """sum_k x_eck w_ejk for every expert e of the block: each expert's
    tokens against its own (out, in) weight."""
    return jnp.einsum("...eck,...ejk->...ecj", x, w, precision="highest")


# g_eci = sum_h x_ech w_eih over (e, c, i, h): the gate and the up
# projection, which share their input x
moe_proj = make_tensor_map_reduce(
    "moe_proj", _expert_contract, in_axes=[(0, 1, 3), (0, 2, 3)],
    reduce_axis=3, depth=4, linear_args=(0, 1))
# a_eci = r_ec silu(g_eci) u_eci: the gated activation, each token's row
# scaled by its combine weight r (routed_scaling_factor 1)
moe_act = make_tensor_map(
    "moe_act", lambda r, g, u: col(r) * (g * jax.nn.sigmoid(g)) * u,
    in_axes=[(0, 1), (0, 1, 2), (0, 1, 2)], depth=3, flops_per_point=7)
# y_ecd = sum_i a_eci w_edi over (e, c, d, i): the down projection
moe_down = make_tensor_map_reduce(
    "moe_down", _expert_contract, in_axes=[(0, 1, 3), (0, 2, 3)],
    reduce_axis=3, depth=4, linear_args=(0, 1))

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
    mla_score, mla_logits, mla_max, mla_exp_sub, mla_sum, mla_div, mla_value,
    kda_sumsq, kda_knorm, kda_qnorm, kda_gate, kda_ka, kda_u, kda_w, kda_snew,
    kda_o, moe_proj, moe_act, moe_down, ema_pm, ema_sq_pm, adam_dir,
    apply_lr,
]}
