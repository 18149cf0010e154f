"""repro.programs — the generalized program registry (DESIGN.md §10).

One namespace for every workload the fusion pipeline serves: the
paper's 11 BLAS sequences (``BLAS``) and the LM decode-step workloads
(``MODELS``), all visible in the combined ``REGISTRY``.
"""
from .registry import BLAS, MODELS, REGISTRY, Program, make_inputs, register
from . import blas as _blas_programs    # noqa: F401  (registers BLAS)
from . import models as _model_programs  # noqa: F401  (registers MODELS)
from .models import ADAMW_HYPERS, HEAD_DIM

__all__ = ["BLAS", "MODELS", "REGISTRY", "Program", "register", "make_inputs",
           "ADAMW_HYPERS", "HEAD_DIM"]
