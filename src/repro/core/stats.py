"""Low-cost sparse-matrix statistics driving the adaptive selector (paper §2.2).

The paper's selection rules read only three numbers from the matrix:
``avg_row`` (mean row length), ``stdv_row`` (row-length standard deviation)
and their ratio ``cv = stdv_row / avg_row`` (coefficient of variation — the
skew signal of Insight 2/3).  All are O(M) over the indptr, i.e. "low-cost"
in the paper's sense: no pass over the nonzeros is needed.
"""
from __future__ import annotations

import dataclasses

import numpy as np

from .formats import CSR
from .guardrails import HEALTH


@dataclasses.dataclass(frozen=True)
class MatrixStats:
    m: int
    k: int
    nnz: int
    avg_row: float      # mean nonzeros per row
    stdv_row: float     # std of nonzeros per row
    cv: float           # stdv_row / avg_row (0 if avg_row == 0)
    max_row: int
    empty_rows: int
    density: float

    @property
    def skewed(self) -> bool:
        """Paper Insight 2: high CV == imbalanced nonzero distribution."""
        return self.cv > 1.0


@HEALTH.span("repro.plan.stats")
def matrix_stats(csr: CSR) -> MatrixStats:
    indptr = np.asarray(csr.indptr)
    lens = np.diff(indptr).astype(np.float64)
    m, k = csr.shape
    nnz = int(indptr[-1])
    avg = float(lens.mean()) if m else 0.0
    std = float(lens.std()) if m else 0.0
    return MatrixStats(
        m=m,
        k=k,
        nnz=nnz,
        avg_row=avg,
        stdv_row=std,
        cv=(std / avg) if avg > 0 else 0.0,
        max_row=int(lens.max()) if m else 0,
        empty_rows=int((lens == 0).sum()),
        density=nnz / float(max(m * k, 1)),
    )
