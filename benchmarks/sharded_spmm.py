"""Sharded-SpMM sweep: single-device vs row-split vs nnz-balanced across
R-MAT skew levels, on a mesh over the host's local devices — plus the
overlap-vs-psum ablation of DESIGN.md §7 for tile-split (psum) plans: the
width-chunked collective-permute ring against one trailing blocking psum
(``SelectorThresholds.overlap_min_n``).

Run with virtual devices to see real partitioning behaviour on CPU::

    XLA_FLAGS=--xla_force_host_platform_device_count=8 \
        python -m benchmarks.run --only sharded_spmm

Columns: time per call for each strategy plus which partitioner the
stats-driven rule (``SelectorThresholds.partition_cv``) would pick — on a
single real device all three collapse to the same math, so the interesting
output there is the *choice*, not the timing."""
from __future__ import annotations

import numpy as np
import jax
import jax.numpy as jnp

from repro.api import SelectorThresholds, sparse
from repro.core import matrix_stats, rmat
from repro.core.selector import select_partition
from repro.kernels import OVERLAP_NEVER
from repro.launch.mesh import make_local_mesh
from . import common
from .common import csv_row, time_fn

SKEWS = {"uniform": (0.25, 0.25, 0.25), "mild": (0.45, 0.22, 0.22),
         "skewed": (0.57, 0.19, 0.19)}


def run(full: bool = False, n: int = 8):
    scale, ef = (5, 4) if common.QUICK else ((12, 16) if full else (8, 8))
    # wide enough that the ring actually chunks (>= chunk width 128 + 1)
    n_wide = 160 if common.QUICK else 256
    mesh = make_local_mesh(jax.device_count(), 1)
    rng = np.random.default_rng(0)
    rows = [csv_row(f"sharded_spmm/devices", float(jax.device_count()), "")]
    for skew_name, (a, b, c) in SKEWS.items():
        csr = rmat(scale, ef, a, b, c, seed=17)
        x = jnp.asarray(rng.standard_normal((csr.shape[1], n)).astype(np.float32))
        stats = matrix_stats(csr)
        chosen = select_partition(stats)
        m_one = sparse(csr, n_hint=n)
        t_one = time_fn(lambda: m_one @ x)
        times = {}
        for kind in ("row", "nnz"):
            m_sh = m_one.shard(mesh, kind=kind)
            times[kind] = time_fn(lambda: m_sh @ x)
        name = f"sharded_spmm/rmat_s{scale}_e{ef}_{skew_name}"
        rows.append(csv_row(
            f"{name}/single", t_one * 1e6, f"cv={stats.cv:.2f}"))
        for kind in ("row", "nnz"):
            mark = " (chosen)" if kind == chosen else ""
            rows.append(csv_row(f"{name}/{kind}", times[kind] * 1e6,
                                f"vs_single={t_one/times[kind]:.2f}x{mark}"))

        # --- overlap (chunked ppermute ring) vs one blocking psum ----------
        xw = jnp.asarray(rng.standard_normal((csr.shape[1], n_wide))
                         .astype(np.float32))
        m_ring = sparse(csr, cache=False,
                        thresholds=SelectorThresholds(overlap_min_n=1)
                        ).shard(mesh, kind="nnz")
        m_psum = sparse(csr, cache=False,
                        thresholds=SelectorThresholds(
                            overlap_min_n=OVERLAP_NEVER)).shard(mesh,
                                                                kind="nnz")
        t_ring = time_fn(lambda: m_ring.matmul(xw, impl="nb_pr"))
        t_psum = time_fn(lambda: m_psum.matmul(xw, impl="nb_pr"))
        rows.append(csv_row(f"{name}/nnz_n{n_wide}/overlap_ring",
                            t_ring * 1e6,
                            f"vs_psum={t_psum/t_ring:.2f}x"))
        rows.append(csv_row(f"{name}/nnz_n{n_wide}/blocking_psum",
                            t_psum * 1e6, ""))
    return rows


def main():
    for r in run():
        print(r)


if __name__ == "__main__":
    main()
