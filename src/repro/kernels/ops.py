"""Deprecated kernel entry point — dispatch now lives behind ``repro.api``.

The logical→physical mapping this module used to hard-code is the registry
(``repro.core.registry``): the Pallas kernel modules (``vsr``, ``csc``,
``spmv`` via ``vsr``, ``bsr``) self-register under the "pallas"/"bsr"
backends, the XLA lowerings in ``repro.core.spmm`` under "xla", and the
facade resolves ``(logical_kernel, backend)`` per call.  See DESIGN.md
§2 for why the GPU 2x2 space collapses to 2x1 on TPU (rs_pr/nb_sr share their
neighbours' binaries).

``spmm`` below survives as a thin deprecation shim so external callers keep
working one release; new code should ``sparse(...)`` once and ``@`` per
operand.
"""
from __future__ import annotations

import warnings

import jax

from repro.core.registry import default_backend
from repro.core.selector import PreparedMatrix, SelectorThresholds

from .bsr import spmm_bsr
from .csc import spmm_csc


def use_pallas_default() -> bool:
    return jax.default_backend() == "tpu"


def spmm(prep, x: jax.Array, *, impl: str | None = None,
         th: SelectorThresholds = SelectorThresholds(),
         force_pallas: bool = False, interpret: bool | None = None) -> jax.Array:
    """Deprecated: use ``repro.api.sparse`` (``m = sparse(csr); m @ x``)."""
    warnings.warn("repro.kernels.spmm is deprecated; use repro.api.sparse",
                  DeprecationWarning, stacklevel=2)
    from repro.api import sparse
    m = prep._matrix if isinstance(prep, PreparedMatrix) else sparse(prep)
    backend = "pallas" if force_pallas else default_backend()
    return m.with_thresholds(th).matmul(x, impl=impl, backend=backend,
                                        interpret=interpret)


__all__ = [
    "spmm", "spmm_csc", "spmm_bsr", "use_pallas_default",
]
