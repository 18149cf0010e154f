"""LM decode-step workloads as registered programs (DESIGN.md §10).

Model-derived sequences, the first four validated *bitwise* against the
repo's reference implementations (``repro.kernels.ref`` /
``repro.models.common``) when served through the fusion pipeline:

* ``LM_RMSNORM`` — square → sum → scale; the norm a decoder applies
  before every sublayer (oracle: ``kernels.ref.rmsnorm``).
* ``LM_BLOCK`` — rmsnorm → matvec → residual add; one projection of a
  decoder sublayer at batch size 1.
* ``LM_DECODE_ATTN`` — score → softmax → weighted value sum over a
  ragged KV length; the first registered *mixed-monoid* graph (a MAX
  reduce feeding SUM reduces), servable only through per-lane masking
  (oracle: ``kernels.ref.decode_attention`` at Hq = Hkv = 1).
* ``FUSED_ADAMW`` — the optimizer step of ``repro.optim.fused`` with
  precision-matched scalar inputs (oracle: ``kernels.ref.adamw``).
* ``MLA_DECODE_ATTN`` — DeepSeek-V2-Lite's latent attention (MLA) at
  decode, absorbed form, at its published widths: 16 heads scored
  against one shared latent cache, softmax per head, the latent rows
  weighted (oracles: the float64 ``reference``, and
  ``mla_unabsorbed_reference``, the architecture's own equations).
* ``KDA_DECODE_STEP`` — Kimi Linear's Kimi Delta Attention (KDA) at
  decode, at its published head width 128: each row's gated delta-rule
  state read, updated by a rank-1 term and written back, the one
  program with a rank-3 output (oracles: the float64 ``reference``, and
  ``kda_recurrent_reference``, the paper's recurrence over tokens).
* ``MOE_EXPERTS`` — DeepSeek-V2-Lite's routed experts at decode: the 8
  of 64 experts one chip holds under expert parallelism, each
  ``down(silu(gate(x)) * up(x))`` on the tokens dispatched to it, at
  the published widths (oracles: the float64 ``reference``, and
  ``moe_layer_reference``, the whole MoE layer with its router and
  shared experts, through ``moe_dispatch`` and ``moe_combine``).

Size notes (pinned empirically, see DESIGN.md §10): matvec-bearing
graphs (``LM_BLOCK``, ``LM_DECODE_ATTN``) are bitwise against the
references at multiple-of-8 sizes (XLA CPU tiles the contraction in
8-lane chunks; interior remainders re-associate the low bits) and
allclose elsewhere; the map/reduce-only graphs are bitwise at every
size.  The attention head dim is 48 — deliberately NOT a power of two,
so the serving engine's output slicing (dims equal to the bucket) can
never mistake the head axis for the padded axis.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np

from repro.blas import elementary_lib as lib

from . import model_lib as mlib
from .registry import MODELS, Program, register

#: Attention head dim — kept off the pow2 bucket grid (see module doc).
HEAD_DIM = 48


def _register(prog: Program) -> Program:
    return register(prog, MODELS)


# --- LM_RMSNORM:  y = x * rsqrt(mean(x^2) + eps) * gamma ---------------------

def _rmsnorm_script(g, x, gamma, inv_d):
    sq = g.apply(lib.ew_mul, x, x, name="sq")
    ss = g.apply(lib.sum_reduce, sq, name="ss")
    y = g.apply(mlib.rms_scale, ss, inv_d, x, gamma, name="y")
    return (y,)


def _rmsnorm_ref(x, gamma, inv_d):
    ss = np.sum(x * x)
    return (x / np.sqrt(ss * inv_d + 1e-6) * gamma,)


def _rmsnorm_inputs(n, seed=0, dtype=np.float32):
    rng = np.random.default_rng(seed)
    dtype = np.dtype(dtype)
    return {
        "x": rng.standard_normal(n).astype(dtype),
        "gamma": rng.standard_normal(n).astype(dtype),
        # exact 1/n in f32 — the same constant XLA folds jnp.mean into,
        # so sum * inv_d reproduces the reference's mean bit for bit
        "inv_d": np.float32(1.0) / np.float32(n),
    }


_register(Program(
    "LM_RMSNORM", "M", _rmsnorm_script,
    lambda n: {"x": (n,), "gamma": (n,), "inv_d": ()},
    _rmsnorm_ref,
    lambda n: 6.0 * n,
    inputs=_rmsnorm_inputs))


# --- LM_BLOCK:  out = x + W @ rmsnorm(x) -------------------------------------
#
# The residual stream enters as its own input ``x_res`` (callers pass
# the same array as ``x``).  Adding ``x`` itself would unify the
# matvec's output-row axis with its column axis in the trace's
# union-find (same-thread-block-mapping, paper §3.2.1), collapsing the
# square W onto ONE iteration axis — a diagonal blocking no backend
# implements, so the call would be unschedulable (fusion rule 1's
# degenerate-axis check).  DESIGN.md §10 records the edge.

def _block_script(g, x, x_res, gamma, W, inv_d):
    sq = g.apply(lib.ew_mul, x, x, name="sq")
    ss = g.apply(lib.sum_reduce, sq, name="ss")
    y = g.apply(mlib.rms_scale, ss, inv_d, x, gamma, name="y")
    t = g.apply(lib.gemv_t, W, y, name="t")
    out = g.apply(lib.ew_add, x_res, t, name="out")
    return (out,)


def _block_ref(x, x_res, gamma, W, inv_d):
    (y,) = _rmsnorm_ref(x, gamma, inv_d)
    return (x_res + W @ y,)


def _block_inputs(n, seed=0, dtype=np.float32):
    rng = np.random.default_rng(seed)
    dtype = np.dtype(dtype)
    out = _rmsnorm_inputs(n, seed=seed, dtype=dtype)
    out["x_res"] = out["x"]
    out["W"] = rng.standard_normal((n, n)).astype(dtype)
    return out


_register(Program(
    "LM_BLOCK", "M", _block_script,
    lambda n: {"x": (n,), "x_res": (n,), "gamma": (n,), "W": (n, n),
               "inv_d": ()},
    _block_ref,
    lambda n: 2.0 * n * n + 7.0 * n,
    inputs=_block_inputs))


# --- LM_DECODE_ATTN:  o = softmax(K q * scale) @ V ---------------------------

def _attn_script(g, q, K, V, scale):
    s_raw = g.apply(mlib.attn_score, K, q, name="s_raw")
    s = g.apply(lib.scal, scale, s_raw, name="s")
    mx = g.apply(lib.max_reduce, s, name="mx")
    e = g.apply(mlib.exp_sub, s, mx, name="e")
    z = g.apply(lib.sum_reduce, e, name="z")
    w = g.apply(mlib.div_by, z, e, name="w")
    o = g.apply(mlib.attn_out, V, w, name="o")
    return (o,)


def _attn_ref(q, K, V, scale):
    s = (K @ q) * scale
    e = np.exp(s - np.max(s))
    w = e / np.sum(e)
    return (w @ V,)


def _attn_inputs(n, seed=0, dtype=np.float32):
    rng = np.random.default_rng(seed)
    dtype = np.dtype(dtype)
    return {
        "q": rng.standard_normal(HEAD_DIM).astype(dtype),
        "K": rng.standard_normal((n, HEAD_DIM)).astype(dtype),
        "V": rng.standard_normal((n, HEAD_DIM)).astype(dtype),
        "scale": np.float32(1.0) / np.sqrt(np.float32(HEAD_DIM)),
    }


_register(Program(
    "LM_DECODE_ATTN", "M", _attn_script,
    lambda n: {"q": (HEAD_DIM,), "K": (n, HEAD_DIM), "V": (n, HEAD_DIM),
               "scale": ()},
    _attn_ref,
    lambda n: 4.0 * HEAD_DIM * n + 6.0 * n,
    inputs=_attn_inputs))


# --- FUSED_ADAMW:  one optimizer step over a flat parameter vector -----------

#: The hyperparameters ``_adamw_inputs`` instantiates (step pre-baked
#: into c1/c2) — tests compare against ``kernels.ref.adamw`` with these.
ADAMW_HYPERS = dict(lr=1e-3, beta1=0.9, beta2=0.95, eps=1e-8,
                    weight_decay=0.01, step=3)


def _adamw_script(g, p, grad, m, v, lr, b1, omb1, b2, omb2, eps, wd, c1, c2):
    m2 = g.apply(mlib.ema_pm, b1, omb1, m, grad, name="m2")
    v2 = g.apply(mlib.ema_sq_pm, b2, omb2, v, grad, name="v2")
    u = g.apply(mlib.adam_dir, c1, c2, eps, wd, m2, v2, p, name="u")
    p2 = g.apply(mlib.apply_lr, lr, p, u, name="p2")
    return p2, m2, v2


def _adamw_ref(p, grad, m, v, lr, b1, omb1, b2, omb2, eps, wd, c1, c2):
    m2 = b1 * m + omb1 * grad
    v2 = b2 * v + omb2 * (grad * grad)
    u = (m2 * c1) / (np.sqrt(v2 * c2) + eps) + wd * p
    return p - lr * u, m2, v2


def _adamw_inputs(n, seed=0, dtype=np.float32):
    rng = np.random.default_rng(seed)
    dtype = np.dtype(dtype)
    h = ADAMW_HYPERS
    b1, b2, step = h["beta1"], h["beta2"], h["step"]
    return {
        "p": rng.standard_normal(n).astype(dtype),
        "grad": rng.standard_normal(n).astype(dtype),
        "m": rng.standard_normal(n).astype(dtype),
        # the second moment is a running mean of squares: non-negative
        "v": np.abs(rng.standard_normal(n)).astype(dtype),
        "lr": np.float32(h["lr"]),
        "b1": np.float32(b1),
        # 1-beta and the bias corrections rounded from python floats —
        # the reference's constant-folding path (module docstring of
        # model_lib explains why f32-computed variants diverge)
        "omb1": np.float32(1.0 - b1),
        "b2": np.float32(b2),
        "omb2": np.float32(1.0 - b2),
        "eps": np.float32(h["eps"]),
        "wd": np.float32(h["weight_decay"]),
        "c1": np.float32(1.0 / (1.0 - b1 ** step)),
        "c2": np.float32(1.0 / (1.0 - b2 ** step)),
    }


_register(Program(
    "FUSED_ADAMW", "M", _adamw_script,
    lambda n: {"p": (n,), "grad": (n,), "m": (n,), "v": (n,),
               "lr": (), "b1": (), "omb1": (), "b2": (), "omb2": (),
               "eps": (), "wd": (), "c1": (), "c2": ()},
    _adamw_ref,
    lambda n: 15.0 * n,
    inputs=_adamw_inputs,
    # pure maps — no reduction constrains the pad; declare it rather
    # than re-deriving (exercises the explicit-identity path)
    pad_values={"p": 0.0, "grad": 0.0, "m": 0.0, "v": 0.0, "lr": 0.0,
                "b1": 0.0, "omb1": 0.0, "b2": 0.0, "omb2": 0.0,
                "eps": 0.0, "wd": 0.0, "c1": 0.0, "c2": 0.0}))


# --- MLA_DECODE_ATTN:  o_lat = softmax(scale (q_lat ckv^T + q_rope kr^T)) ckv

#: DeepSeek-V2-Lite's softmax scale (``model_lib`` derives it)
MLA_SCALE = mlib.MLA_SCALE


def _mla_script(g, q_lat, q_rope, ckv, kr):
    s_lat = g.apply(mlib.mla_score, q_lat, ckv, name="s_lat")
    s_rope = g.apply(mlib.mla_score, q_rope, kr, name="s_rope")
    s = g.apply(mlib.mla_logits, s_lat, s_rope, name="s")
    mx = g.apply(mlib.mla_max, s, name="mx")
    e = g.apply(mlib.mla_exp_sub, s, mx, name="e")
    z = g.apply(mlib.mla_sum, e, name="z")
    p = g.apply(mlib.mla_div, e, z, name="p")
    o_lat = g.apply(mlib.mla_value, p, ckv, name="o_lat")
    return (o_lat,)


def _mla_ref(q_lat, q_rope, ckv, kr):
    s = MLA_SCALE * (q_lat @ ckv.T + q_rope @ kr.T)
    e = np.exp(s - np.max(s, axis=1, keepdims=True))
    return ((e / np.sum(e, axis=1, keepdims=True)) @ ckv,)


def mla_program(heads: int = mlib.MLA_HEADS, rank: int = mlib.MLA_RANK,
                rope: int = mlib.MLA_ROPE,
                name: str = "MLA_DECODE_ATTN") -> Program:
    """Absorbed MLA decode attention for one session of ``n`` cached
    positions, every one valid (decode happens at the last position).

    Inputs: the absorbed queries ``q_lat[h] = W_UK[h] q_nope[h]``
    ``(heads, rank)`` and the rotated ``q_rope`` ``(heads, rope)``; the
    latent cache ``ckv`` ``(n, rank)`` and the rotated key cache ``kr``
    ``(n, rope)``, as DeepSeek's cache stores ``k_pe``.  Output: the
    weighted latent rows ``o_lat`` ``(heads, rank)``; ``W_UV[h]^T
    o_lat[h]`` is head h's output (``W_UK[h]``, ``W_UV[h]``: ``(rank,
    128)``, as in ``mla_unabsorbed_reference``).  The defaults are DeepSeek-V2-Lite's;
    smaller widths are for tests."""
    return Program(
        name, "M", _mla_script,
        lambda n: {"q_lat": (heads, rank), "q_rope": (heads, rope),
                   "ckv": (n, rank), "kr": (n, rope)},
        _mla_ref,
        lambda n: heads * n * (4.0 * rank + 2.0 * rope + 7.0))


_register(mla_program())


def mla_unabsorbed_reference(q_nope, q_rope, ckv, kr, w_uk, w_uv):
    """MLA decode attention as the architecture writes it, float32
    ``jax.numpy`` at full matmul precision, no compiler: head h's keys
    are ``[ckv W_UK[h] ; kr]`` and its values ``ckv W_UV[h]``, attended
    by the query ``[q_nope[h] ; q_rope[h]]`` with an ordinary softmax.

    ``q_nope`` ``(h, nope)``, ``q_rope`` ``(h, rope)``, ``ckv`` ``(n,
    rank)``, ``kr`` ``(n, rope)``, ``w_uk`` ``(h, rank, nope)``, ``w_uv``
    ``(h, rank, v)``; returns every head's output ``(h, v)``."""
    with jax.default_matmul_precision("highest"):
        h, n = q_nope.shape[0], ckv.shape[0]
        keys = jnp.concatenate(
            [jnp.einsum("tc,hcd->htd", ckv, w_uk),
             jnp.broadcast_to(kr, (h, n, kr.shape[-1]))], axis=-1)
        q = jnp.concatenate([q_nope, q_rope], axis=-1)
        p = jax.nn.softmax(MLA_SCALE * jnp.einsum("hd,htd->ht", q, keys),
                           axis=-1)
        values = jnp.einsum("tc,hcd->htd", ckv, w_uv)
        return jnp.einsum("ht,htd->hd", p, values)


# --- KDA_DECODE_STEP:  one decode step of Kimi Delta Attention ---------------

def _kda_script(g, S, q, k, v, f, a_log, b):
    qss = g.apply(mlib.kda_sumsq, q, name="qss")
    kss = g.apply(mlib.kda_sumsq, k, name="kss")
    qn = g.apply(mlib.kda_qnorm, q, qss, name="qn")
    kn = g.apply(mlib.kda_knorm, k, kss, name="kn")
    alpha = g.apply(mlib.kda_gate, f, a_log, name="alpha")
    ka = g.apply(mlib.kda_ka, kn, alpha, name="ka")
    u = g.apply(mlib.kda_u, ka, S, name="u")
    w = g.apply(mlib.kda_w, b, v, u, name="w")
    S_new = g.apply(mlib.kda_snew, S, alpha, kn, w, name="S_new")
    o = g.apply(mlib.kda_o, qn, S_new, name="o")
    return S_new, o


def _kda_ref(S, q, k, v, f, a_log, b):
    d = q.shape[-1]
    qn = q / np.sqrt(np.sum(q * q, axis=-1, keepdims=True) + mlib.KDA_EPS)
    qn = qn * d ** -0.5
    kn = k / np.sqrt(np.sum(k * k, axis=-1, keepdims=True) + mlib.KDA_EPS)
    alpha = np.exp(-np.exp(a_log)[:, None] * np.logaddexp(f, 0.0))
    beta = 1.0 / (1.0 + np.exp(-b))
    gated = alpha[:, :, None] * S
    u = np.einsum("bi,bij->bj", kn, gated)
    S_new = gated + kn[:, :, None] * (beta[:, None] * (v - u))[:, None, :]
    return S_new, np.einsum("bi,bij->bj", qn, S_new)


# One decode step of KDA for ``n`` rows, each one (session, head): heads
# and sessions are independent, so both are flattened into the row axis.
# Inputs: the state ``S`` (n, 128, 128), key by value; this token's q, k,
# v and gate pre-activation f (n, 128), after the projections, the short
# convolution and SiLU (f with ``dt_bias`` added); the head's decay
# ``a_log`` and the token's write pre-activation ``b`` (n,).  Outputs:
# the new state ``S_new`` and the read-out ``o`` (n, 128).
_KDA_D = mlib.KDA_DIM
_register(Program(
    "KDA_DECODE_STEP", "M", _kda_script,
    lambda n: {"S": (n, _KDA_D, _KDA_D), "q": (n, _KDA_D), "k": (n, _KDA_D),
               "v": (n, _KDA_D), "f": (n, _KDA_D), "a_log": (n,), "b": (n,)},
    _kda_ref,
    lambda n: n * (7.0 * _KDA_D * _KDA_D + 23.0 * _KDA_D)))


def kda_recurrent_reference(q, k, v, f, a_log, b, S0):
    """KDA over T tokens as arXiv:2510.26692 writes it, float32
    ``jax.numpy`` at full matmul precision, no compiler: per token,

        S_t = (I - beta_t k_t k_t^T) Diag(alpha_t) S_{t-1} + beta_t k_t v_t^T
        o_t = S_t^T q_t

    with q and k L2-normed (eps 1e-6) and q scaled by ``head_dim**-0.5``,
    ``alpha_t = exp(-exp(A_log) softplus(f_t))`` and ``beta_t =
    sigmoid(b_t)``.

    ``q``, ``k``, ``v``, ``f`` ``(T, n, d)``, ``b`` ``(T, n)``, ``a_log``
    ``(n,)`` (a per-head parameter), ``S0`` ``(n, d, d)``; returns every
    token's ``o`` ``(T, n, d)`` and the final state.  Departures from the
    published layer (the cut ``KDA_DECODE_STEP`` makes too): q, k and v
    arrive after the projections, the short convolution and SiLU; the
    gate's low-rank projection and ``dt_bias`` are folded into ``f``;
    the gated output RMSNorm and ``o_proj`` are left out."""
    with jax.default_matmul_precision("highest"):
        d = q.shape[-1]
        eye = jnp.eye(d, dtype=jnp.float32)

        def l2norm(x):
            return x * jax.lax.rsqrt(
                jnp.sum(x * x, axis=-1, keepdims=True) + mlib.KDA_EPS)

        def step(S, tok):
            q_t, k_t, v_t, f_t, b_t = tok
            q_t, k_t = l2norm(q_t) * d ** -0.5, l2norm(k_t)
            alpha = jnp.exp(-jnp.exp(a_log)[:, None] * jax.nn.softplus(f_t))
            beta = jax.nn.sigmoid(b_t)[:, None, None]
            erase = eye - beta * jnp.einsum("ni,nk->nik", k_t, k_t)
            S = (jnp.einsum("nik,nkj->nij", erase, alpha[:, :, None] * S)
                 + beta * jnp.einsum("ni,nj->nij", k_t, v_t))
            return S, jnp.einsum("nij,ni->nj", S, q_t)

        S, o = jax.lax.scan(step, jnp.asarray(S0, jnp.float32),
                            tuple(jnp.asarray(x, jnp.float32)
                                  for x in (q, k, v, f, b)))
        return o, S


# --- MOE_EXPERTS:  the routed experts one chip holds, at decode --------------

def _moe_script(g, x, r, w_gate, w_up, w_down):
    gg = g.apply(mlib.moe_proj, x, w_gate, name="gg")
    uu = g.apply(mlib.moe_proj, x, w_up, name="uu")
    a = g.apply(mlib.moe_act, r, gg, uu, name="a")
    y = g.apply(mlib.moe_down, a, w_down, name="y")
    return (y,)


def _moe_ref(x, r, w_gate, w_up, w_down):
    gg = np.matmul(x, np.swapaxes(w_gate, 1, 2))
    uu = np.matmul(x, np.swapaxes(w_up, 1, 2))
    a = r[:, :, None] * gg / (1.0 + np.exp(-gg)) * uu
    return (np.matmul(a, np.swapaxes(w_down, 1, 2)),)


def _moe_inputs(experts, hidden, width):
    """Tokens N(0, 1), combine weights U(0, 1) as router probabilities
    are, and weights N(0, 1 / fan_in) as a trained layer's are scaled,
    so that every activation is of order 1."""
    def make(n, seed=0, dtype=np.float32):
        rng = np.random.default_rng(seed)

        def normal(shape, fan_in):
            return (rng.standard_normal(shape) / np.sqrt(fan_in)).astype(
                dtype)
        return {"x": normal((experts, n, hidden), 1),
                "r": rng.uniform(size=(experts, n)).astype(dtype),
                "w_gate": normal((experts, width, hidden), hidden),
                "w_up": normal((experts, width, hidden), hidden),
                "w_down": normal((experts, hidden, width), width)}
    return make


def moe_program(experts: int = 8, hidden: int = mlib.MOE_HIDDEN,
                width: int = mlib.MOE_WIDTH,
                name: str = "MOE_EXPERTS") -> Program:
    """The routed experts one chip holds under expert parallelism, on
    the tokens dispatched to them: ``n`` tokens per held expert.

    Inputs: the dispatch buffer ``x`` ``(experts, n, hidden)`` and the
    combine weights ``r`` ``(experts, n)`` (``moe_dispatch``), and each
    expert's ``w_gate``, ``w_up`` ``(experts, width, hidden)`` and
    ``w_down`` ``(experts, hidden, width)``, in the ``nn.Linear``
    layout.  Output: ``y[e, c] = r[e, c] w_down[e] (silu(w_gate[e]
    x[e, c]) * w_up[e] x[e, c])`` ``(experts, n, hidden)``, which
    ``moe_combine`` adds back to the tokens.  The defaults are one chip's
    share of DeepSeek-V2-Lite's 64 experts over 8 chips at the published
    widths; smaller widths are for tests."""
    return Program(
        name, "M", _moe_script,
        lambda n: {"x": (experts, n, hidden), "r": (experts, n),
                   "w_gate": (experts, width, hidden),
                   "w_up": (experts, width, hidden),
                   "w_down": (experts, hidden, width)},
        _moe_ref,
        lambda n: experts * n * width * (6.0 * hidden + 7.0),
        inputs=_moe_inputs(experts, hidden, width))


_register(moe_program())

#: DeepSeek-V2-Lite's router: 64 routed experts, 6 a token
MOE_EXPERTS_ALL, MOE_TOP_K = 64, 6


def _route(x_tokens, router_w):
    """The softmax over every routed expert and its greedy top 6:
    ``(weights, experts)``, each ``(tokens, 6)``, the weights not
    renormalised (``norm_topk_prob`` false, ``routed_scaling_factor``
    1)."""
    probs = jax.nn.softmax(x_tokens @ router_w.T, axis=-1)
    return jax.lax.top_k(probs, MOE_TOP_K)


def moe_dispatch(x_tokens, router_w, held):
    """Route ``x_tokens`` ``(T, hidden)`` over every expert of
    ``router_w`` ``(experts, hidden)`` and group the tokens routed to
    the ``held`` experts into ``MOE_EXPERTS``'s dispatch buffer, as an
    expert-parallel dispatch delivers it, with room for every token in
    each expert (a token picks an expert once).

    Returns ``(x, r, slots)``: ``x`` ``(len(held), T, hidden)``, expert
    ``held[k]``'s tokens in token order in row block ``k``; ``r``
    ``(len(held), T)``, each row's router weight; ``slots`` ``(len(held),
    T)``, each row's token index.  Rows beyond an expert's count are zero
    and point at token ``T``, which ``moe_combine`` drops."""
    with jax.default_matmul_precision("highest"):
        x_tokens = jnp.asarray(x_tokens, jnp.float32)
        n_tok = x_tokens.shape[0]
        weights, experts = _route(x_tokens, jnp.asarray(router_w,
                                                        jnp.float32))
        held = jnp.asarray(held)
        hit = experts[:, :, None] == held[None, None, :]   # (T, 6, held)
        w_tok = jnp.sum(jnp.where(hit, weights[:, :, None], 0.0), axis=1)
        routed = jnp.any(hit, axis=1)                        # (T, held)
        row = jnp.where(routed, jnp.cumsum(routed, axis=0) - 1, n_tok)
        cols = jnp.arange(held.shape[0])
        slots = jnp.full((held.shape[0], n_tok), n_tok).at[
            cols[None, :], row].set(jnp.arange(n_tok)[:, None], mode="drop")
        x_pad = jnp.concatenate(
            [x_tokens, jnp.zeros((1, x_tokens.shape[1]), jnp.float32)])
        w_pad = jnp.concatenate([w_tok, jnp.zeros((1, held.shape[0]))])
        return x_pad[slots], w_pad[slots, cols[:, None]], slots


def moe_combine(y, slots, n_tokens: int):
    """Add each row of ``y`` ``(experts, capacity, hidden)`` back to its
    token ``slots[e, c]``: ``(n_tokens, hidden)``, rows pointing at
    ``n_tokens`` dropped."""
    out = jnp.zeros((n_tokens + 1, y.shape[-1]), jnp.float32)
    return out.at[slots].add(jnp.asarray(y, jnp.float32))[:n_tokens]


def _swiglu(x, w_gate, w_up, w_down):
    """One expert's MLP on rows ``x``: down(silu(gate(x)) * up(x))."""
    return (jax.nn.silu(x @ w_gate.T) * (x @ w_up.T)) @ w_down.T


def moe_layer_reference(x, router_w, experts, shared):
    """DeepSeek-V2's MoE layer (arXiv:2405.04434 section 2.2) on tokens
    ``x`` ``(T, hidden)``, float32 ``jax.numpy`` at full matmul
    precision, no compiler: the softmax router over every expert of
    ``router_w`` ``(experts, hidden)``, its greedy top 6 weights not
    renormalised and scaled by 1, each routed expert's
    ``down(silu(gate(x)) * up(x))`` weighted, plus the shared experts.

    ``experts`` is ``(w_gate, w_up, w_down)`` of every routed expert,
    ``(experts, width, hidden)`` twice and ``(experts, hidden, width)``;
    ``shared`` the same of the shared experts taken as one MLP (2 x 1408
    = 2816 wide for DeepSeek-V2-Lite), ``(width, hidden)`` twice and
    ``(hidden, width)``.  Every expert is computed on every token and
    weighted by its router weight, zero where it is not among the token's
    top 6.  Departures from the published layer: the residual add and the
    norms around the layer stay outside, as the auxiliary losses do."""
    with jax.default_matmul_precision("highest"):
        x = jnp.asarray(x, jnp.float32)
        weights, picked = _route(x, jnp.asarray(router_w, jnp.float32))
        w_gate, w_up, w_down = experts
        out = _swiglu(x, *shared)
        for e in range(w_gate.shape[0]):
            w_e = jnp.sum(jnp.where(picked == e, weights, 0.0), axis=1)
            out = out + w_e[:, None] * _swiglu(x, w_gate[e], w_up[e],
                                               w_down[e])
        return out
