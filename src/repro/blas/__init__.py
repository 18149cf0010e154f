"""repro.blas — fusible BLAS elementary-function library and synthetic
call chains.  The paper's 11 sequences register in ``repro.programs``
(``programs.BLAS``)."""
from . import elementary_lib
from .synthetic import make_synthetic_chain

__all__ = ["elementary_lib", "make_synthetic_chain"]
