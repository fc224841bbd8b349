"""The plain reference of bench/reference against the library and a dense
product, at small sizes on the CPU, and the seeded graph generator."""
import sys
from pathlib import Path

import jax
import jax.numpy as jnp
import numpy as np
import pytest

ROOT = Path(__file__).resolve().parents[2]
sys.path.insert(0, str(ROOT))

from bench import graph  # noqa: E402
from bench.reference import spmm as spmm_ref  # noqa: E402

SEED = 2**31 + 3


def _csr(indptr, indices, data, n):
    from repro.core.formats import CSR
    return CSR(jnp.asarray(indptr), jnp.asarray(indices), jnp.asarray(data),
               (n, n))


def test_graph_is_seeded_sorted_and_coalesced():
    a = graph.kronecker_csr(SEED, scale=9, edge_factor=16)
    b = graph.kronecker_csr(SEED, scale=9, edge_factor=16)
    c = graph.kronecker_csr(SEED + 1, scale=9, edge_factor=16)
    indptr, cols, vals, n = a
    assert n == 512 and indptr[0] == 0 and len(cols) == indptr[-1]
    assert all(np.array_equal(x, y) for x, y in zip(a[:3], b[:3]))
    assert not np.array_equal(a[1], c[1])
    rows = np.repeat(np.arange(n), np.diff(indptr))
    key = rows.astype(np.int64) * n + cols
    assert np.all(np.diff(key) > 0)          # sorted, no duplicate edge
    assert cols.min() >= 0 and cols.max() < n
    assert 0.5 * 16 * n < len(cols) <= 16 * n   # duplicates coalesced
    assert np.all(vals > 0)
    lens = np.diff(indptr)
    assert lens.std() / lens.mean() > 1.0     # power-law skew


@pytest.mark.parametrize("n", [0, 16])
def test_spmm_reference_matches_the_library(n):
    from repro import api
    indptr, cols, vals, m = graph.kronecker_csr(SEED, scale=8, edge_factor=8)
    x = jax.random.normal(jax.random.PRNGKey(1), (m,) if n == 0 else (m, n))
    want = spmm_ref.make(indptr, cols, vals, m)(x)
    got = api.sparse(_csr(indptr, cols, vals, m)) @ x
    assert spmm_ref.rel_err(got, want) < 1e-5
    control = spmm_ref.make(indptr, cols, vals, m, jnp.bfloat16)(x)
    assert spmm_ref.rel_err(control, want) > 1e-4


@pytest.mark.parametrize("chunk", [7, 256, 1 << 21])
@pytest.mark.parametrize("n", [0, 5])
def test_spmm_reference_in_blocks_matches_a_dense_product(chunk, n):
    indptr, cols, vals, m = graph.kronecker_csr(SEED, scale=7, edge_factor=8)
    dense = np.zeros((m, m), np.float64)
    rows = np.repeat(np.arange(m), np.diff(indptr))
    dense[rows, cols] = vals
    x = np.asarray(jax.random.normal(jax.random.PRNGKey(4),
                                     (m,) if n == 0 else (m, n)))
    got = spmm_ref.make(indptr, cols, vals, m, chunk=chunk)(jnp.asarray(x))
    assert got.shape == x.shape
    np.testing.assert_allclose(got, dense @ x, rtol=1e-5, atol=1e-5)
