"""Adaptive kernel selection (paper §2.2, Fig. 4) + threshold persistence.

Decision tree, from three low-cost statistics (avg_row, stdv_row, N):

  1. Insight 1 — N picks the reduction style: parallel-reduction for SpMV and
     small-N SpMM (N <= n_threshold, paper: 4), sequential for larger N.
  2. Insight 2 — on the sequential side, workload-balancing pays off when the
     row-length distribution is skewed: cv = stdv_row/avg_row > cv_threshold.
  3. Insight 3 — large avg_row means lots of total work → occupancy waves
     self-balance → WB unnecessary.  On the parallel side, *small* avg_row is
     the WB trigger (short rows idle PR lanes, §2.1.1).

The thresholds are data, not constants: the paper derives them empirically on
SuiteSparse; we re-derive them for this backend with ``calibrate`` over the
R-MAT suite and persist the result as JSON (``save_thresholds``).  A persisted
calibration is auto-loaded by ``repro.core.plan.plan`` when the
``REPRO_THRESHOLDS`` environment variable points at it (format in DESIGN.md
§4).  Defaults below are the calibrated CPU-XLA values; the paper's GPU values
are kept for reference.

The old eager front door (``PreparedMatrix`` / ``adaptive_spmm``) survives only
as deprecation shims over the plan/execute subsystem in ``repro.core.plan``.
"""
from __future__ import annotations

import dataclasses
import json
import os
import warnings
from typing import Callable

import numpy as np

from .formats import CSR
from .stats import MatrixStats, matrix_stats

#: environment variable naming a calibrated-thresholds JSON file to auto-load
THRESHOLDS_ENV = "REPRO_THRESHOLDS"


@dataclasses.dataclass(frozen=True)
class TileGeometry:
    """One point in the Pallas NB kernel's tuning space.

    ``tile`` is the nnz quota per BalancedCOO tile (the paper's warp quota),
    ``wb`` the fused kernel's output-block row height (sublane-aligned), and
    ``tile_n`` the dense-column block width (lane-aligned) of the fused
    chain and attention kernels; the nnz-balanced and row-split SpMMs take
    theirs from the call's N (``kernels/vsr.gather_width``).  The winning
    geometry shifts with sparsity pattern and N (Hu et al., PAPERS.md), so
    geometries are *measured* per (pattern, N-bucket, backend) by
    ``repro.kernels.tune.autotune_geometry`` and persisted on
    ``SelectorThresholds.geometries`` next to the selector cutoffs."""

    tile: int = 512
    wb: int = 64
    tile_n: int = 128

    def validate(self) -> "TileGeometry":
        if self.tile < 1:
            raise ValueError(f"tile must be >= 1, got {self.tile}")
        if self.wb < 8 or self.wb % 8:
            raise ValueError(f"wb must be a positive multiple of 8 "
                             f"(sublanes), got {self.wb}")
        if self.tile_n < 128 or self.tile_n % 128:
            raise ValueError(f"tile_n must be a positive multiple of 128 "
                             f"(lanes), got {self.tile_n}")
        return self

    def as_tuple(self) -> tuple:
        return (int(self.tile), int(self.wb), int(self.tile_n))


#: upper edges of the dense-width buckets geometry entries key on; widths
#: above the last edge share one "nbig" bucket.
N_BUCKET_EDGES = (1, 4, 32, 128)


def n_bucket(n: "int | None") -> str:
    """Coarse dense-width bucket label for geometry keys (``None`` → the
    wildcard bucket, matched when no width hint is available)."""
    if n is None:
        return "any"
    for edge in N_BUCKET_EDGES:
        if n <= edge:
            return f"n{edge}"
    return "nbig"


def geometry_key(backend: str, fingerprint: str, n: "int | None") -> str:
    """Key of one autotuned-geometry entry: backend x pattern x N-bucket."""
    return f"{backend}|{fingerprint[:12]}|{n_bucket(n)}"


@dataclasses.dataclass(frozen=True)
class SelectorThresholds:
    n_threshold: int = 4        # N <= this → parallel reduction (paper: 4)
    pr_avg_row: float = 32.0    # PR side: avg_row < this → workload-balance
    sr_cv: float = 0.5          # SR side: cv > this → workload-balance
    # sharded backend (DESIGN.md §4.1): cv > this → nnz-balanced tile-split
    # partitioning, else row-split by M.  Same CV signal as Insight 2, one
    # level up: skewed rows make equal-row shards unequal-work shards.
    partition_cv: float = 1.0
    # sharded psum plans with dense width N >= this chunk the width axis and
    # replace the trailing blocking psum with a compute-overlapped
    # collective-permute ring (DESIGN.md §7); below it one fused psum wins.
    # Measured per backend by ``kernels/tune.autotune_overlap``.
    overlap_min_n: int = 512
    # quantized-plan crossover (DESIGN.md §8): a ``quant=`` plan request is
    # honoured only at dense width N >= this — below it the per-element
    # dequant ALU cost outweighs the value-stream byte savings.  1 = always
    # honour; ``kernels/tune.QUANT_NEVER`` = never.  Measured per backend by
    # ``kernels/tune.autotune_quant``.
    quant_min_n: int = 1
    # fused-chain crossover (DESIGN.md §9): a Pallas SDDMM→SpMM chain runs
    # fused only at dense width N >= this — the fused kernel recomputes edge
    # scores once per column block, so at tiny N the recompute can cost more
    # than the 2*nnz edge-value bytes it saves.  1 = always fuse;
    # ``kernels/tune.CHAIN_NEVER`` = never (unfused two-kernel pair).
    # Measured per backend by ``kernels/tune.autotune_chain``.
    chain_fuse_min_n: int = 1
    # block-sparse attention crossover (DESIGN.md §10): the fused Pallas
    # attention chain runs only at sequence length >= this — short sequences
    # amortize the visit-schedule setup poorly and the unfused XLA path (or
    # plain dense attention) wins.  1 = always fuse;
    # ``kernels/tune.ATTN_NEVER`` = never.  Measured per backend by
    # ``kernels/tune.autotune_attention``.
    attn_fuse_min_seq: int = 1
    # autotuned tile geometries: sorted ((geometry_key, (tile, wb, tile_n)),
    # ...) — a tuple-of-tuples so thresholds stay hashable (they ride
    # ``PlanMeta`` static aux and the ``PlanCache`` key, which is how a
    # recalibrated geometry invalidates cached plans).
    geometries: tuple = ()

    PAPER_GPU = None  # filled below

    # -- geometry table -----------------------------------------------------
    def geometry_for(self, fingerprint: str, n: "int | None",
                     backend: str) -> "TileGeometry | None":
        """The measured geometry for this (pattern, N, backend), trying the
        exact N-bucket first and the wildcard ("any") entry second."""
        if not self.geometries:
            return None
        table = dict(self.geometries)
        for key in (geometry_key(backend, fingerprint, n),
                    geometry_key(backend, fingerprint, None)):
            if key in table:
                return TileGeometry(*table[key])
        return None

    def with_geometry(self, key: str, geom: TileGeometry) -> "SelectorThresholds":
        table = dict(self.geometries)
        table[key] = geom.validate().as_tuple()
        return dataclasses.replace(self, geometries=tuple(sorted(table.items())))

    # -- persistence (DESIGN.md §4) -----------------------------------------
    def to_json(self) -> str:
        d = {"version": 1,
             "n_threshold": int(self.n_threshold),
             "pr_avg_row": float(self.pr_avg_row),
             "sr_cv": float(self.sr_cv),
             "partition_cv": float(self.partition_cv)}
        # each schema is a strict superset of the one before, so a file
        # takes the lowest version that holds every non-default field, and
        # older readers keep loading what they can: v2 geometries and the
        # overlap cutoff, v3 quantization, v4 chain fusion, v5 attention
        version = max((v for v, needed in (
            (2, self.geometries or self.overlap_min_n != 512),
            (3, self.quant_min_n != 1),
            (4, self.chain_fuse_min_n != 1),
            (5, self.attn_fuse_min_seq != 1)) if needed), default=1)
        if version >= 2:
            d["version"] = version
            d["overlap_min_n"] = int(self.overlap_min_n)
            d["geometries"] = {k: list(v) for k, v in self.geometries}
        for v, name in ((3, "quant_min_n"), (4, "chain_fuse_min_n"),
                        (5, "attn_fuse_min_seq")):
            if version >= v:
                d[name] = int(getattr(self, name))
        return json.dumps(d, indent=2)

    @classmethod
    def from_json(cls, text: str) -> "SelectorThresholds":
        d = json.loads(text)
        # keys this reader does not know are ignored: v2-v5 files of older
        # releases still carry a retired spill-window bound
        if d.get("version", 1) not in (1, 2, 3, 4, 5):
            raise ValueError(f"unsupported thresholds version {d.get('version')!r}")
        geoms = tuple(sorted((str(k), tuple(int(x) for x in v))
                             for k, v in d.get("geometries", {}).items()))
        th = cls(n_threshold=int(d["n_threshold"]),
                 pr_avg_row=float(d["pr_avg_row"]),
                 sr_cv=float(d["sr_cv"]),
                 # absent in pre-sharding calibrations; default keeps them valid
                 partition_cv=float(d.get("partition_cv", 1.0)),
                 overlap_min_n=int(d.get("overlap_min_n", 512)),
                 # pre-quantization (v1/v2) files: always honour quant=
                 quant_min_n=int(d.get("quant_min_n", 1)),
                 # pre-chain (v1-v3) files: always fuse
                 chain_fuse_min_n=int(d.get("chain_fuse_min_n", 1)),
                 # pre-attention (v1-v4) files: always fuse
                 attn_fuse_min_seq=int(d.get("attn_fuse_min_seq", 1)),
                 geometries=geoms)
        th.validate()
        return th

    def validate(self) -> "SelectorThresholds":
        """Reject numerically nonsensical thresholds (negative cutoffs,
        NaN/inf — JSON happily carries both) with ``ValueError`` so corrupt
        calibrations get the same warn-and-fallback treatment as corrupt
        JSON in ``default_thresholds``."""
        if self.n_threshold < 0:
            raise ValueError(f"n_threshold must be >= 0, got {self.n_threshold}")
        for name in ("pr_avg_row", "sr_cv", "partition_cv"):
            v = float(getattr(self, name))
            if not np.isfinite(v):
                raise ValueError(f"{name} must be finite, got {v!r}")
            if v < 0:
                raise ValueError(f"{name} must be >= 0, got {v!r}")
        if self.overlap_min_n < 1:
            raise ValueError(f"overlap_min_n must be >= 1, "
                             f"got {self.overlap_min_n}")
        if self.quant_min_n < 1:
            raise ValueError(f"quant_min_n must be >= 1, "
                             f"got {self.quant_min_n}")
        if self.chain_fuse_min_n < 1:
            raise ValueError(f"chain_fuse_min_n must be >= 1, "
                             f"got {self.chain_fuse_min_n}")
        if self.attn_fuse_min_seq < 1:
            raise ValueError(f"attn_fuse_min_seq must be >= 1, "
                             f"got {self.attn_fuse_min_seq}")
        for key, vals in self.geometries:
            if len(vals) != 3:
                raise ValueError(f"geometry {key!r} must be (tile, wb, "
                                 f"tile_n), got {vals!r}")
            TileGeometry(*vals).validate()
        return self


SelectorThresholds.PAPER_GPU = SelectorThresholds(n_threshold=4, pr_avg_row=32.0, sr_cv=0.5)


def save_thresholds(th: SelectorThresholds, path: str) -> None:
    with open(path, "w") as f:
        f.write(th.to_json() + "\n")


def load_thresholds(path: str) -> SelectorThresholds:
    with open(path) as f:
        return SelectorThresholds.from_json(f.read())


def default_thresholds() -> SelectorThresholds:
    """Calibrated thresholds from ``$REPRO_THRESHOLDS`` when set (and
    readable), else the built-in defaults.  Read per call — the file is tiny
    and tests/calibration loops repoint the variable at runtime."""
    path = os.environ.get(THRESHOLDS_ENV)
    if path:
        try:
            return load_thresholds(path)
        except (OSError, ValueError, KeyError) as e:
            warnings.warn(f"could not load thresholds from {path!r}: {e}; "
                          "falling back to defaults", stacklevel=2)
    return SelectorThresholds()


def select_kernel(stats: MatrixStats, n: int,
                  th: SelectorThresholds = SelectorThresholds()) -> str:
    """Paper Fig. 4: map (sparsity stats, N) → one of the four kernels."""
    if n <= th.n_threshold:
        # parallel reduction; WB when rows are short (idle-lane waste, §2.1.1)
        return "nb_pr" if stats.avg_row < th.pr_avg_row else "rs_pr"
    # sequential reduction; WB when row lengths are skewed relative to the
    # mean (Insights 2+3 combined into the CV metric)
    return "nb_sr" if stats.cv > th.sr_cv else "rs_sr"


def select_partition(stats: MatrixStats,
                     th: SelectorThresholds = SelectorThresholds()) -> str:
    """Partitioner for the sharded backend (DESIGN.md §4.1): the CV rule one
    level up — uniform rows shard by rows ("row"), skewed rows shard by
    nonzeros ("nnz", the BalancedCOO tile split)."""
    return "nnz" if stats.cv > th.partition_cv else "row"


# ---------------------------------------------------------------------------
# deprecation shims: thin aliases over the repro.api facade
# ---------------------------------------------------------------------------

class PreparedMatrix:
    """Deprecated: use ``repro.api.sparse`` — substrates are built lazily,
    per the selected kernel, instead of both eagerly.  This shim wraps the
    facade's ``SparseMatrix`` so legacy ``.ell`` / ``.balanced`` / ``.stats``
    accessors keep working (each access builds that substrate on first
    touch)."""

    def __init__(self, matrix):
        from repro.api import SparseMatrix
        if not isinstance(matrix, SparseMatrix):  # a bare PlanBuilder
            matrix = SparseMatrix(matrix)
        self._matrix = matrix

    @classmethod
    def from_csr(cls, csr: CSR, tile: int = 512) -> "PreparedMatrix":
        warnings.warn("PreparedMatrix.from_csr is deprecated; use "
                      "repro.api.sparse (lazy substrates, cached plans)",
                      DeprecationWarning, stacklevel=2)
        from repro.api import sparse
        return cls(sparse(csr, tile=tile))

    @property
    def _plan(self):
        return self._matrix.plan

    @property
    def csr(self) -> CSR:
        return self._matrix.plan.csr

    @property
    def stats(self) -> MatrixStats:
        return self._matrix.stats

    @property
    def ell(self):
        return self._matrix.plan.substrate("ell")

    @property
    def balanced(self):
        return self._matrix.plan.substrate("balanced")


def adaptive_spmm(prep, x, th: SelectorThresholds = SelectorThresholds(),
                  impl: str | None = None):
    """Deprecated front door: ``repro.api.sparse(csr) @ x`` is the
    replacement.  ``impl`` overrides the rule (oracle/ablation mode)."""
    warnings.warn("adaptive_spmm is deprecated; use repro.api.sparse "
                  "(m = sparse(csr); m @ x)", DeprecationWarning, stacklevel=2)
    from repro.api import sparse
    m = prep._matrix if isinstance(prep, PreparedMatrix) else sparse(prep)
    return m.with_thresholds(th).matmul(x, impl=impl)


# ---------------------------------------------------------------------------
# offline calibration (paper §2.2 method, §3.2 metric)
# ---------------------------------------------------------------------------

def calibrate(
    matrices: dict[str, CSR],
    ns: tuple[int, ...],
    time_fn: Callable[[str, object, int], float] | None = None,
    times: dict | None = None,
    # 1<<30 = "never switch to sequential reduction": on this backend (XLA
    # CPU / TPU) the PR/SR crossover of paper Insight 1 may not exist — the
    # grid is allowed to learn that (see EXPERIMENTS.md §Selection).
    n_grid: tuple[int, ...] = (2, 4, 8, 1 << 30),
    avg_grid: tuple[float, ...] = (8.0, 16.0, 32.0, 64.0),
    cv_grid: tuple[float, ...] = (0.25, 0.5, 1.0, 2.0),
    save_to: str | None = None,
) -> tuple[SelectorThresholds, dict]:
    """Re-derive thresholds for this backend by grid search against measured
    kernel times.  Either ``time_fn(kernel_name, plan, n) -> seconds`` or a
    precomputed ``times[(matrix_name, n, kernel_name)] -> seconds``.

    Returns (best thresholds, report) where report carries the oracle/selected
    geomean ratio per candidate — the §3.2 'performance loss vs optimal'.
    ``save_to`` persists the winner as JSON so ``plan()`` auto-loads it via
    ``$REPRO_THRESHOLDS``."""
    from .plan import plan
    from .registry import MATMUL_KERNELS

    plans = {k: plan(v) for k, v in matrices.items()}
    if times is None:
        assert time_fn is not None
        times = {}
        for mname, p in plans.items():
            for n in ns:
                for kname in MATMUL_KERNELS:
                    times[(mname, n, kname)] = time_fn(kname, p, n)

    def loss(th: SelectorThresholds) -> float:
        ratios = []
        for mname, p in plans.items():
            for n in ns:
                chosen = times[(mname, n, select_kernel(p.stats, n, th))]
                oracle = min(times[(mname, n, k)] for k in MATMUL_KERNELS)
                ratios.append(chosen / oracle)
        return float(np.exp(np.mean(np.log(ratios))))  # geomean slowdown

    best, best_loss = None, np.inf
    for nt in n_grid:
        for ag in avg_grid:
            for cg in cv_grid:
                th = SelectorThresholds(nt, ag, cg)
                l = loss(th)
                if l < best_loss:
                    best, best_loss = th, l
    report = {
        "geomean_slowdown_vs_oracle": best_loss,
        "times": {f"{m}|n={n}|{k}": t for (m, n, k), t in times.items()},
    }
    if save_to is not None:
        save_thresholds(best, save_to)
    return best, report
