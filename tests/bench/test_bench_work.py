"""bench/work.py against hand counts for the benchmark's shapes."""
import sys
from pathlib import Path

import pytest

ROOT = Path(__file__).resolve().parents[2]
sys.path.insert(0, str(ROOT))

from bench import work  # noqa: E402

V5E = {"flops_per_s": 197e12, "hbm_bytes_per_s": 819e9}
M = 1 << 20          # graph500-s20 vertices
NNZ = 16_086_312     # its coalesced edge count (graph_seed 1)


def test_peaks_table_has_the_v5e_and_refuses_unknown_kinds():
    assert work.peaks("TPU v5 lite")["flops_per_s"] == 197e12
    assert work.peaks("TPU v5 lite")["hbm_bytes_per_s"] == 819e9
    with pytest.raises(KeyError):
        work.peaks("TPU v9 imaginary")


def test_spmm_n128_by_hand():
    w = work.spmm(M, M, NNZ, 128)
    assert w["flops"] == 2 * NNZ * 128
    csr = (M + 1) * 4 + NNZ * 4 + NNZ * 4
    assert w["bytes"] == csr + M * 128 * 4 + M * 128 * 4
    t, bound = work.least_time(w["flops"], w["bytes"], V5E)
    assert bound == "memory"
    assert t == pytest.approx(w["bytes"] / 819e9)
    assert 1.2e-3 < t < 1.8e-3          # ~1.2 GB at 819 GB/s


def test_spmv_counts_a_1d_x_as_one_column():
    assert work.spmm(M, M, NNZ, 0) == work.spmm(M, M, NNZ, 1)
    w = work.spmm(M, M, NNZ, 0)
    assert w["bytes"] == (M + 1) * 4 + NNZ * 8 + 2 * M * 4


def test_least_time_names_the_bound():
    assert work.least_time(197e12, 1.0, V5E) == (1.0, "compute")
    assert work.least_time(1.0, 819e9, V5E) == (1.0, "memory")

