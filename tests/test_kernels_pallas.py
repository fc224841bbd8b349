"""Pallas kernels vs ref.py oracle: shape/dtype sweeps in interpret mode
(the per-kernel allclose deliverable)."""
import numpy as np
import pytest
import jax.numpy as jnp

from repro.core import (csr_from_dense, csr_to_balanced, csr_to_bsr,
                        csr_to_ell, rmat)
from repro.kernels import spmm_bsr, spmm_csc, spmm_vsr_fused, spmv_vsr_fused
from repro.kernels.ref import (ref_spmm_balanced, ref_spmm_bsr, ref_spmm_csr,
                               ref_spmm_ell)

from conftest import random_csr

SHAPES = [(16, 16), (100, 80), (257, 129), (64, 300)]
DENSITIES = [0.02, 0.15, 0.5]


def _mats(rng, shapes=SHAPES, densities=DENSITIES):
    for m, k in shapes:
        for d in densities:
            csr, a = random_csr(rng, m, k, d)
            if csr.nnz:
                yield csr, a


@pytest.mark.parametrize("dtype", [np.float32])
@pytest.mark.parametrize("n", [1, 4, 20, 128])
def test_vsr_sweep(rng, n, dtype):
    for csr, a in _mats(rng):
        bal = csr_to_balanced(csr, tile=128)
        x = rng.standard_normal((csr.shape[1], n)).astype(dtype)
        got = np.asarray(spmm_vsr_fused(bal, jnp.asarray(x), interpret=True))
        ref = np.asarray(ref_spmm_balanced(bal, jnp.asarray(x)))
        np.testing.assert_allclose(got, ref, atol=2e-3)


@pytest.mark.parametrize("n", [1, 4, 20, 128])
@pytest.mark.parametrize("tm,tw", [(8, 32), (16, 128)])
def test_csc_sweep(rng, n, tm, tw):
    for csr, a in _mats(rng, shapes=[(100, 80), (257, 129)]):
        ell = csr_to_ell(csr)
        x = rng.standard_normal((csr.shape[1], n)).astype(np.float32)
        got = np.asarray(spmm_csc(ell, jnp.asarray(x), tm=tm, tw=tw, interpret=True))
        ref = np.asarray(ref_spmm_ell(ell, jnp.asarray(x)))
        np.testing.assert_allclose(got, ref, atol=2e-3)


@pytest.mark.parametrize("bm,bk", [(8, 16), (8, 128)])
def test_bsr_sweep(rng, bm, bk):
    for csr, a in _mats(rng, shapes=[(64, 300), (100, 80)], densities=[0.05, 0.3]):
        bsr = csr_to_bsr(csr, bm=bm, bk=bk)
        x = rng.standard_normal((csr.shape[1], 20)).astype(np.float32)
        got = np.asarray(spmm_bsr(bsr, jnp.asarray(x), interpret=True))
        ref = np.asarray(ref_spmm_bsr(bsr, jnp.asarray(x)))[: csr.shape[0]]
        np.testing.assert_allclose(got, ref, atol=2e-3)


def test_spmv_sweep(rng):
    for csr, a in _mats(rng):
        bal = csr_to_balanced(csr, tile=128)
        x = rng.standard_normal(csr.shape[1]).astype(np.float32)
        got = np.asarray(spmv_vsr_fused(bal, jnp.asarray(x), interpret=True))
        ref = np.asarray(ref_spmm_csr(csr, jnp.asarray(x)))
        np.testing.assert_allclose(got, ref, atol=2e-3)


def test_vsr_bf16(rng):
    csr, a = random_csr(rng, 64, 64, 0.2)
    bal = csr_to_balanced(csr, tile=64)
    x = rng.standard_normal((64, 8)).astype(np.float32)
    got = np.asarray(spmm_vsr_fused(
        csr_to_balanced(csr, tile=64), jnp.asarray(x, jnp.bfloat16),
        interpret=True)).astype(np.float32)
    np.testing.assert_allclose(got, a @ x, atol=0.15, rtol=0.05)


def test_skewed_rmat_kernels():
    """Skewed matrices are where VSR earns its keep — verify on R-MAT."""
    csr = rmat(8, 8, seed=3)
    rng = np.random.default_rng(0)
    x = rng.standard_normal((csr.shape[1], 16)).astype(np.float32)
    ref = np.asarray(ref_spmm_csr(csr, jnp.asarray(x)))
    got_v = np.asarray(spmm_vsr_fused(csr_to_balanced(csr, tile=128),
                                      jnp.asarray(x), interpret=True))
    got_c = np.asarray(spmm_csc(csr_to_ell(csr), jnp.asarray(x), interpret=True))
    np.testing.assert_allclose(got_v, ref, atol=2e-3)
    np.testing.assert_allclose(got_c, ref, atol=2e-3)
