"""Names the program gives its work in a profiler trace.

* Host span ``DISPATCH`` (``repro.dispatch``): ``CompiledProgram.__call__``,
  from gathering the arguments to the jitted call's return.
* Group labels (``codegen.group_label``, e.g. ``g0_rank2_update_gemtv``):
  each fused group's ``jax.named_scope`` and, on the Pallas backend, its
  kernel's name, so the device trace names a group's kernel the same way
  whatever the plan's signature.  A model program's elementaries share a
  prefix its labels carry: ``mla_`` (``MLA_DECODE_ATTN``), ``kda_``
  (``KDA_DECODE_STEP``: ``g0_kda_sumsq_kda_qnorm``,
  ``g1_kda_sumsq_kda_knorm_kda_gate_kda_ka_kda_u_kda_w_kda_snew_kda_o``).

Counters of one call, which a plan fixes (no trace needed):

* ``CompiledProgram.grid_steps``: grid steps, summed over the groups.
* ``CompiledProgram.input_passes``: ``{input name: passes}``, the times
  the call streams each input from HBM, the sum of ``n_phases`` over the
  groups that read it (MLA's latent cache ``ckv``: 1 in the online-softmax
  plan, 2 in the split plan, one pass to score and one to weight; KDA's
  state ``S``: 1, read at the key, updated and read out in one sweep).
* ``CompiledProgram.local_reductions``: the reductions consumed in the
  grid step that finishes them, block-local (KDA's ``qss``, ``kss`` and
  ``u``; GEMVER's ``t`` at 16384; ATAX's ``t``).
* ``CompiledProgram.transposed_operands``: the values a Pallas kernel
  carries with their last two dims swapped, as XLA stores them
  (``predictor.carrier_swapped``): MLA's ``kr``, decode attention's
  ``K`` and ``V``; none on the BLAS programs.

A span is a ``jax.profiler.TraceAnnotation``: with no profiler running it
costs well under a microsecond, so it is always on.
"""
import jax

DISPATCH = "repro.dispatch"


def span(name: str):
    """A host span named ``name`` on the profiler's clock."""
    return jax.profiler.TraceAnnotation(name)
