"""Block-sparse (BSR) SpMM with scalar-prefetch block gather — the TPU-native
granule.

Not in the paper's 2x2 (the paper targets unstructured CSR on GPUs) but the
natural endpoint of its hardware-adaptation story: once the balancing unit
grew from a 32-lane warp to an MXU tile, the *profitable* sparsity granule on
TPU is an (bm, bk) dense block, and the per-lane gathers become **block
gathers driven from the BlockSpec index_map** via scalar prefetch: the column
ids of each block row are prefetched to SMEM, and X's index_map reads them to
DMA exactly the needed (bk, TN) dense slab per step. Used by the models layer
for block-sparse weights and sliding-window attention masks.

Substrate: block-ELL (padded blocks-per-row) built host-side from BSR;
padding blocks are all-zero so gathering X block 0 for them is harmless.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.core import registry
from repro.core.formats import BSR


def bsr_to_blockell(bsr: BSR) -> tuple[np.ndarray, np.ndarray, int]:
    """Pad per-row block lists to uniform width WB. Returns (blocks, bcols, wb):
    blocks (Mb, WB, bm, bk), bcols (Mb, WB)."""
    indptr = np.asarray(bsr.indptr)
    bcol = np.asarray(bsr.indices)
    blocks = np.asarray(bsr.blocks)
    mb = len(indptr) - 1
    bm, bk = bsr.block_shape
    wb = max(1, int(np.diff(indptr).max()) if mb else 1)
    out_blocks = np.zeros((mb, wb, bm, bk), blocks.dtype)
    out_bcols = np.zeros((mb, wb), np.int32)
    for i in range(mb):
        s, e = indptr[i], indptr[i + 1]
        out_blocks[i, : e - s] = blocks[s:e]
        out_bcols[i, : e - s] = bcol[s:e]
    return out_blocks, out_bcols, wb


def _bsr_kernel(bcols_ref, blocks_ref, x_ref, o_ref):
    w = pl.program_id(2)

    @pl.when(w == 0)
    def _init():
        o_ref[...] = jnp.zeros_like(o_ref)

    a = blocks_ref[0, 0]                 # (bm, bk)
    x = x_ref[...]                       # (bk, TN) — gathered via index_map
    o_ref[...] += jnp.dot(a.astype(jnp.float32), x.astype(jnp.float32),
                          preferred_element_type=jnp.float32).astype(o_ref.dtype)


@functools.partial(jax.jit, static_argnames=("wb", "bm", "bk", "tile_n", "interpret"))
def _bsr_call(bcols_flat, blocks, x, *, wb, bm, bk, tile_n, interpret):
    mb = blocks.shape[0]
    k, n_pad = x.shape
    grid = (mb, n_pad // tile_n, wb)
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=1,
        grid=grid,
        in_specs=[
            pl.BlockSpec((1, 1, bm, bk), lambda i, j, w, bc: (i, w, 0, 0)),
            # the block gather: X's row-block index comes from prefetched bcols
            pl.BlockSpec((bk, tile_n), lambda i, j, w, bc: (bc[i * wb + w], j)),
        ],
        out_specs=pl.BlockSpec((bm, tile_n), lambda i, j, w, bc: (i, j)),
    )
    return pl.pallas_call(
        _bsr_kernel,
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((mb * bm, n_pad), jnp.float32),
        name="repro_bsr",
        interpret=interpret,
    )(bcols_flat, blocks, x)


def spmm_bsr(bsr: BSR, x: jax.Array, *, tile_n: int = 128,
             interpret: bool | None = None,
             blockell: tuple | None = None) -> jax.Array:
    """``blockell`` = (blocks, bcols_flat, wb) precomputed by
    ``bsr_to_blockell`` at plan time (skips the host-side padding pass)."""
    if interpret is None:
        interpret = jax.default_backend() != "tpu"
    x2 = x[:, None] if x.ndim == 1 else x
    m, k_logical = bsr.shape
    bm, bk = bsr.block_shape
    if blockell is None:
        blocks, bcols, wb = bsr_to_blockell(bsr)
        blocks, bcols_flat = jnp.asarray(blocks), jnp.asarray(bcols.reshape(-1))
    else:
        blocks, bcols_flat, wb = blockell
    k, n = x2.shape
    kb_pad = -(-k // bk) * bk
    n_pad = -(-n // tile_n) * tile_n
    xp = jnp.pad(x2, ((0, kb_pad - k), (0, n_pad - n)))
    y = _bsr_call(bcols_flat, blocks, xp,
                  wb=wb, bm=bm, bk=bk, tile_n=tile_n, interpret=interpret)
    y = y[:m, :n].astype(x2.dtype)
    return y[:, 0] if x.ndim == 1 else y


# ---------------------------------------------------------------------------
# registry: the block-granule backend.  All four logical kernels resolve to
# the one MXU block-gather binary — block granularity subsumes both the
# balancing and the reduction-style axes (DESIGN.md §2).  Values are baked
# into the dense blocks at plan time.  The prep hook bakes only the
# *arrangement* (a block-ELL gather map over the pattern); block values flow
# through a traceable gather, so live value streams and the block-level
# custom VJP in ``core/plan`` work (DESIGN.md §3 rule 3).
# ---------------------------------------------------------------------------

def _prep_bell(bsr: BSR) -> dict:
    """Block-ELL prep, two artifacts: the fully-baked padded blockell (the
    zero-cost forward for plan-baked values) and the pattern-only gather map
    (per-(block-row, slot) source block index + validity) that re-pads *live*
    block values traceably."""
    indptr = np.asarray(bsr.indptr)
    bcol = np.asarray(bsr.indices)
    mb = len(indptr) - 1
    wb = max(1, int(np.diff(indptr).max()) if mb else 1)
    slot = np.arange(wb)[None, :]
    src = indptr[:-1, None] + slot
    valid = slot < np.diff(indptr)[:, None]
    src = np.where(valid, src, 0)
    bcols = np.zeros((mb, wb), np.int32)
    bcols[valid] = bcol[src[valid]]
    baked, _, _ = bsr_to_blockell(bsr)
    return {"blockell": (jnp.asarray(baked), jnp.asarray(bcols.reshape(-1)), wb),
            "bell_src": jnp.asarray(src.astype(np.int32)),
            "bell_valid": jnp.asarray(valid)}


def _bsr_entry(bsr: BSR, x, *, interpret: bool | None = None,
               blockell: tuple | None = None, bell_src=None, bell_valid=None,
               live: bool = False):
    if blockell is None:
        return spmm_bsr(bsr, x, interpret=interpret)
    if not live:
        return spmm_bsr(bsr, x, interpret=interpret, blockell=blockell)
    # live block values (stream override / grads): re-pad through the
    # pattern-only gather map instead of the baked arrangement
    if bsr.nblocks == 0:
        shape = (bsr.shape[0],) if x.ndim == 1 else (bsr.shape[0], x.shape[1])
        return jnp.zeros(shape, x.dtype)
    _, bcols_flat, wb = blockell
    blocks = jnp.take(bsr.blocks, bell_src, axis=0)     # (Mb, WB, bm, bk)
    blocks = jnp.where(bell_valid[..., None, None], blocks, 0)
    return spmm_bsr(bsr, x, interpret=interpret,
                    blockell=(blocks, bcols_flat, wb))


for _logical in registry.MATMUL_KERNELS:
    registry.register(_logical, "bsr", "bsr", _bsr_entry, prep=_prep_bell)
