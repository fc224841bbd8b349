"""CSC — Coalesced Sparse-row Caching SpMM (paper §2.1.3), TPU-adapted.

GPU original: a warp cooperatively loads ``warp_size`` nonzeros of one sparse
row into shared memory with one coalesced transaction, then every lane walks
the cached nonzeros *sequentially* while owning a distinct dense column —
sequential reduction with parallel (coalesced) loading.

TPU adaptation (see DESIGN.md §2):
  * row-split: the grid assigns whole ``wb``-row output blocks; a block's
    nonzeros are cut into row-aligned tiles (no tile crosses a block), and
    a heavy block simply takes more consecutive grid steps accumulating into
    the same output block — the imbalance row-split leaves on the table,
    which the adaptive selector weighs against the nb_* kernels.
  * shared-memory staging → the tile's index/value slab is DMA'd HBM→VMEM
    (SMEM for the column ids) once per grid step, and the dense rows it
    names are fetched by the same DMA row gather as VSR (``kernels/vsr.py``).
  * "each lane owns a dense column" → the lane dimension of the ``(wb,
    w)`` output block carries ``w`` dense columns (``vsr.gather_width``:
    all of N where the gather slots fit VMEM); the walk over
    the cached nonzeros is one segment-one-hot matmul, so every column
    advances in lockstep — the CSC schedule on the MXU.

With row-aligned tiles the fused VSR kernel *is* the row-split kernel: every
tile belongs to exactly one output block, so its visit schedule is one visit
per tile.  Two tilings feed it:

  * ``plan_row_tiles`` (the plan-time prep of the registered ``rs_*``
    entries) cuts the CSR-ordered nonzero stream per block — storage is the
    nonzeros plus at most one tile of padding per block, whatever the row
    skew;
  * ``ell_row_tiles`` reshapes an ELL slab in graph (``spmm_csc``, and the
    sharded row-split backend's per-shard ELL substrates).  ELL pads every
    row to the longest one, so it is only for matrices whose longest row is
    short.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np

from repro.core import registry
from repro.core.formats import ELL, BalancedCOO
from repro.core.selector import TileGeometry

from .spmv import spmv_vsr_fused
from .vsr import spmm_vsr_fused


def plan_row_tiles(bal: BalancedCOO, wb: int) -> dict:
    """Host-side row-split tiling of a row-major nonzero stream.

    Cuts the valid entries of ``bal`` (in stream order) per ``wb``-row
    output block into tiles of ``bal.tile`` slots; every block gets at least
    one tile so every output block is written.  Returns the tiles' global
    row ids (sentinel ``m`` on padding) and column ids, ``rt_src`` — each
    slot's index into the flattened ``bal`` slab (-1 on padding), which is
    how baked or live values reach the tiles — and the one-visit-per-tile
    schedule."""
    rows = np.asarray(bal.rows).reshape(-1)
    cols = np.asarray(bal.cols).reshape(-1)
    m = bal.shape[0]
    t = bal.tile
    mb = max(1, -(-m // wb))
    src = np.nonzero(rows < m)[0]
    blk = rows[src] // wb
    counts = np.bincount(blk, minlength=mb)
    per_block = np.maximum(1, -(-counts // t))
    first_tile = np.concatenate([[0], np.cumsum(per_block)[:-1]])
    first_entry = np.concatenate([[0], np.cumsum(counts)[:-1]])
    rank = np.arange(len(src)) - first_entry[blk]
    slot = (first_tile[blk] + rank // t) * t + rank % t
    n_tiles = int(per_block.sum())
    rt_rows = np.full(n_tiles * t, m, np.int32)
    rt_cols = np.zeros(n_tiles * t, np.int32)
    rt_src = np.full(n_tiles * t, -1, np.int32)
    rt_rows[slot] = rows[src]
    rt_cols[slot] = cols[src]
    rt_src[slot] = src
    vb = np.repeat(np.arange(mb, dtype=np.int32), per_block)
    vs = np.zeros(n_tiles, np.int32)
    vs[first_tile] = 1
    shp = (n_tiles, t)
    return {"rt_rows": jnp.asarray(rt_rows.reshape(shp)),
            "rt_cols": jnp.asarray(rt_cols.reshape(shp)),
            "rt_src": jnp.asarray(rt_src.reshape(shp)),
            "visit_tile": jnp.arange(n_tiles, dtype=jnp.int32),
            "visit_block": jnp.asarray(vb), "visit_start": jnp.asarray(vs)}


def ell_row_tiles(ell: ELL, tm: int, tw: int):
    """An ELL slab as row-aligned tiles, in graph: ``tm``-row blocks, each
    cut into ``tw``-wide column chunks (tile = one ``(tm, tw)`` chunk, so
    ``T = tm * tw``).  Returns the tiled ``BalancedCOO`` and its regular
    visit schedule (chunk ``c`` of block ``b`` is visit ``b * nw + c``)."""
    m, w = ell.cols.shape
    m_pad, w_pad = -(-m // tm) * tm, -(-w // tw) * tw
    mb, nw = m_pad // tm, w_pad // tw
    pad = ((0, m_pad - m), (0, w_pad - w))
    row_ids = jnp.arange(m_pad, dtype=jnp.int32)
    rows = jnp.broadcast_to(jnp.where(row_ids < m, row_ids, m)[:, None],
                            (m_pad, w_pad))

    def tiles(a):
        return a.reshape(mb, tm, nw, tw).transpose(0, 2, 1, 3).reshape(
            mb * nw, tm * tw)

    bal = BalancedCOO(tiles(rows), tiles(jnp.pad(ell.cols, pad)),
                      tiles(jnp.pad(ell.vals, pad)), ell.shape)
    vt = jnp.arange(mb * nw, dtype=jnp.int32)
    return bal, (vt, vt // nw, (vt % nw == 0).astype(jnp.int32))


def _run_tiles(bal: BalancedCOO, x, schedule, *, wb, interpret):
    vt, vb, vs = schedule
    if x.ndim == 1:
        return spmv_vsr_fused(bal, x, interpret=interpret, wb=wb,
                              visit_tile=vt, visit_block=vb, visit_start=vs)
    return spmm_vsr_fused(bal, x, interpret=interpret, wb=wb,
                          visit_tile=vt, visit_block=vb, visit_start=vs)


def spmm_csc(ell: ELL, x: jax.Array, *, tm: int = 8, tw: int = 128,
             interpret: bool | None = None) -> jax.Array:
    """RS+SR SpMM on the ELL substrate. ``tm`` is the output block height
    (a multiple of 8); the ELL width is walked in ``tw``-wide chunks,
    rounded so a tile (``tm * tw`` slots) fills whole 128-lane vregs."""
    lane_rows = 128 // np.gcd(tm, 128)
    tw = -(-min(tw, ell.width) // lane_rows) * lane_rows
    bal, schedule = ell_row_tiles(ell, tm, tw)
    return _run_tiles(bal, x, schedule, wb=tm, interpret=interpret)


# ---------------------------------------------------------------------------
# registry: the Pallas physical kernel for the row-split logical pair.  The
# segment reduction is one MXU matmul per tile either way, so rs_sr and
# rs_pr collapse onto the same binary on TPU (DESIGN.md §2).  The entries
# read the balanced substrate (the CSR-ordered stream) and re-tile it per
# output block at plan time; handed an ELL (the sharded row-split backend's
# per-shard substrate) they tile it in graph.
# ---------------------------------------------------------------------------

def _prep_row_tiles(bal: BalancedCOO, *,
                    geometry: TileGeometry | None = None) -> dict:
    geom = (geometry or TileGeometry()).validate()
    return {**plan_row_tiles(bal, geom.wb), "wb": geom.wb}


def _pallas_rs(sub, x, scales=None, *, interpret: bool | None = None,
               rt_rows=None, rt_cols=None, rt_src=None, visit_tile=None,
               visit_block=None, visit_start=None, wb: int | None = None,
               quant: str | None = None):
    if isinstance(sub, ELL):
        return spmm_csc(sub, x, interpret=interpret)
    if rt_src is None:
        opts = _prep_row_tiles(sub)
        return _pallas_rs(sub, x, scales, interpret=interpret, **opts)
    vals = sub.vals
    if scales is not None:      # baked int8/fp8 stream: decode per tile
        vals = vals.astype(jnp.float32) * scales[:, None]
    flat = vals.reshape(-1)
    rt_vals = jnp.where(rt_src >= 0, jnp.take(flat, jnp.maximum(rt_src, 0)),
                        0).astype(flat.dtype)
    tiles = BalancedCOO(rt_rows, rt_cols, rt_vals, sub.shape)
    return _run_tiles(tiles, x, (visit_tile, visit_block, visit_start),
                      wb=wb, interpret=interpret)


registry.register("rs_sr", "pallas", "balanced", _pallas_rs,
                  prep=_prep_row_tiles)
registry.register("rs_pr", "pallas", "balanced", _pallas_rs,
                  prep=_prep_row_tiles)
