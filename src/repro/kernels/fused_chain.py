"""Fused SDDMM→SpMM chain — graph-attention message passing on one schedule.

GNN training needs the SpMM's dual: SDDMM, sampling ``A @ B^T`` at the
pattern's nonzeros (edge scores from endpoint features).  Attention-style
message passing then transforms the scores per row (masked softmax) and
immediately feeds them back into an SpMM over the *same* pattern.  Run as two
kernels, the edge-score stream makes an HBM round trip: ``nnz`` f32 written by
the SDDMM, ``nnz`` read back by the SpMM — pure traffic, no flops
(``kernels/tune.modeled_traffic_chain`` charges exactly this).

The fused kernel eliminates it.  The observation making fusion natural here is
that the SDDMM's *output* pattern is the SpMM's *input* pattern, so one
``plan_visits`` schedule (kernels/vsr.py) drives both: each tile DMA-gathers
its endpoint feature rows and the X rows it aggregates (``A``, ``B`` and
``X`` stay in HBM — the VDL row gather of kernels/vsr.py), computes its
scores on the spot, applies the transform, and accumulates ``w * X[cols]``
into the revisited ``(wb, tile_n)`` output block — scores live only in VMEM.
The trade is FusedMM's: scores are recomputed once per column block (``nb``
times), swapping ``2*nnz`` value bytes of HBM for endpoint-row gathers and
dot recompute.

Masked softmax needs row totals before any weight can be formed, so it runs
two passes over the same schedule (the revisited-block accumulation of
the SpMM): pass 1 folds each visit's per-row ``(max, sum-of-exp)`` into
``(wb, 1)`` stat columns with the online-softmax update (exchanged as
``(mb, wb)`` blocks), pass 2 reads the
finished stats alongside each visit.  Stats are ``2 * m`` floats of traffic —
independent of nnz — vs. the ``2 * nnz`` the unfused pair moves.  Empty rows
keep ``(SOFTMAX_NEG, 0)`` and produce all-zero weights; the ``-1e30`` sentinel
(never ``-inf``) keeps ``exp`` finite everywhere it is *selected* from.

The sharded nnz-split backend reuses pass 1 per shard and merges stats with
``pmax`` / rescaled ``psum`` before pass 2 (core/shard.py), which is why
``chain_stats_pallas`` is exposed separately from ``chain_pallas``.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.core import registry
from repro.core.formats import BalancedCOO
from repro.core.selector import TileGeometry
from repro.core.spmm import SOFTMAX_EPS, SOFTMAX_NEG

from .vsr import (GATHER_UNROLL, _pad_n, _prep_visits, accumulate_visit,
                  as_tiles, block_hits, column_blocks, gather_width,
                  hbm_spec, mxu_dot, new_tile, plan_visits, segment_onehot,
                  start_row_gather, tile_spec, wait_row_gather)

#: per-row transforms the chain supports between its SDDMM and SpMM halves
CHAIN_TRANSFORMS: tuple[str, ...] = ("identity", "scale", "softmax")


def _pad_2d(a: jax.Array, row_mult: int = 8, col_mult: int = 128) -> jax.Array:
    """A feature matrix as the f32, sublane/lane-padded operand the row
    gathers read.  Row padding is inert (gather indices stay below the true
    row count) and zero column padding adds nothing to the score dots."""
    a = a.astype(jnp.float32)
    r, c = a.shape
    rp = -(-r // row_mult) * row_mult
    cp = -(-c // col_mult) * col_mult
    if rp != r or cp != c:
        a = jnp.pad(a, ((0, rp - r), (0, cp - c)))
    return a


def gather_endpoints(rows_s, cols_s, a_hbm, b_hbm, ag, bg, sems, m):
    """DMA-gather a tile's endpoint feature rows — ``A[rows]`` and
    ``B[cols]`` (the VDL idiom: one row copy per side covers the whole
    feature dim; ``A``/``B`` stay in HBM).  ``rows_s``/``cols_s`` are the
    tile's SMEM index blocks; sentinel rows read row ``m - 1`` (masked
    downstream)."""
    start_row_gather(a_hbm, rows_s, ag, sems.at[0], clamp=m - 1)
    start_row_gather(b_hbm, cols_s, bg, sems.at[1])
    wait_row_gather(ag, sems.at[0])
    wait_row_gather(bg, sems.at[1])


def tile_scores(ag, bg):
    """In-kernel SDDMM for one nnz-tile: the row-wise dots of the gathered
    endpoint rows, as lane-major ``(1, T)`` f32 scores (an NT matmul
    against ones — the row sums land lane-major, no relayout)."""
    prod = ag[...] * bg[...]                         # (T, d) or (T, 1, d)
    prod = prod.reshape(prod.shape[0], prod.shape[-1])
    ones = jnp.ones((8, prod.shape[1]), jnp.float32)
    e = jax.lax.dot_general(ones, prod, (((1,), (1,)), ((), ())),
                            preferred_element_type=jnp.float32,
                            precision=jax.lax.Precision.HIGHEST)
    return e[0:1]


def gather_scratch(t: int, d: int, n_extra: int = 0, tile_n: int = 0):
    """Scratch of the chain-family kernels: the gathered A and B rows,
    optionally the gathered X rows, and one DMA semaphore per gather."""
    shapes = [pltpu.VMEM((t, d), jnp.float32), pltpu.VMEM((t, d), jnp.float32)]
    if n_extra:
        shapes.append(pltpu.VMEM((t, tile_n), jnp.float32))
    return shapes + [pltpu.SemaphoreType.DMA((2 + n_extra,))]


def row_stats(z, rows, base, wb: int, m: int):
    """Per-visit softmax statistics of a tile's scores over the output rows
    ``[base, base + wb)``: ``(max, sum-of-exp)`` as ``(wb, 1)`` columns;
    rows the tile does not touch get the identity ``(NEG, 0)``."""
    sel = block_hits(rows, base, wb, m)
    zt = jnp.where(sel, z, SOFTMAX_NEG)
    m_tile = jnp.max(zt, axis=1, keepdims=True)
    s_tile = jnp.sum(jnp.where(sel, jnp.exp(zt - m_tile), 0.0), axis=1,
                     keepdims=True)
    return m_tile, s_tile


def fold_stats(rm_ref, rs_ref, m_tile, s_tile, start):
    """Online-softmax fold across a block's consecutive visits; padding
    visits (``start == 2``) take neither branch."""

    @pl.when(start == 1)
    def _():
        rm_ref[...] = m_tile
        rs_ref[...] = s_tile

    @pl.when(start == 0)
    def _():
        m_old = rm_ref[...]
        m_new = jnp.maximum(m_old, m_tile)
        rm_ref[...] = m_new
        rs_ref[...] = (rs_ref[...] * jnp.exp(m_old - m_new)
                       + s_tile * jnp.exp(m_tile - m_new))


def softmax_weights(z, rows, base, rm, rs, wb: int, m: int):
    """Per-edge softmax weights from finished ``(wb, 1)`` row statistics:
    each edge reads its row's (max, sum) through the block one-hot (a
    sublane reduction, lane-major result)."""
    sel = block_hits(rows, base, wb, m)
    rm_t = jnp.max(jnp.where(sel, rm, SOFTMAX_NEG), axis=0, keepdims=True)
    rs_t = jnp.sum(jnp.where(sel, rs, 0.0), axis=0, keepdims=True)
    valid = jnp.any(sel, axis=0, keepdims=True)
    zc = jnp.where(valid, z - rm_t, SOFTMAX_NEG)
    return jnp.exp(zc) / jnp.maximum(rs_t, SOFTMAX_EPS)


def stats_blocks(rm, rs, wb: int):
    """``(mb * wb, 1)`` kernel stat columns ↔ the ``(mb, wb)`` blocks the
    public stats API (and the sharded cross-shard merge) exchanges."""
    return rm.reshape(-1, wb), rs.reshape(-1, wb)


# ---------------------------------------------------------------------------
# standalone SDDMM: one grid step per (nnz-tile, feature block), scores
# accumulated tile-in-place over the feature blocks
# ---------------------------------------------------------------------------

def _sddmm_kernel(rows_s, cols_s, a_hbm, b_hbm, o_ref, ag, bg, sems, *, m):
    jd = pl.program_id(1)
    gather_endpoints(rows_s, cols_s, a_hbm.at[jd], b_hbm.at[jd], ag, bg,
                     sems, m)
    e = tile_scores(ag, bg)

    @pl.when(jd == 0)
    def _():
        o_ref[...] = e

    @pl.when(jd > 0)
    def _():
        o_ref[...] += e


@functools.partial(jax.jit, static_argnames=("m", "interpret"))
def _sddmm_call(rows, cols, a, b, *, m, interpret):
    n_tiles, t = rows.shape
    nd, _, _, dblk = a.shape
    # feature blocks innermost: a tile's output block is revisited on
    # consecutive grid steps, and its index blocks are not fetched again
    tile = lambda i, jd: (i, 0, 0)  # noqa: E731
    out = pl.pallas_call(
        functools.partial(_sddmm_kernel, m=m),
        grid=(n_tiles, nd),
        in_specs=[tile_spec(t, tile, pltpu.SMEM),
                  tile_spec(t, tile, pltpu.SMEM),
                  hbm_spec(),
                  hbm_spec()],
        out_specs=tile_spec(t, tile),
        out_shape=jax.ShapeDtypeStruct((n_tiles, 1, t), jnp.float32),
        scratch_shapes=[pltpu.VMEM((t, 1, dblk), jnp.float32),
                        pltpu.VMEM((t, 1, dblk), jnp.float32),
                        pltpu.SemaphoreType.DMA((2,))],
        name="repro_sddmm",
        interpret=interpret,
    )(as_tiles(rows), as_tiles(cols), a, b)
    return jnp.where(rows < m, out.reshape(n_tiles, t), 0.0)


def _feature_blocks(a: jax.Array, dblk: int) -> jax.Array:
    """``(rows, d)`` → ``(nd, rows, 1, dblk)`` f32, features zero-padded to
    ``nd * dblk``: feature block ``j`` of a row is one contiguous copy of
    ``a[j, row]``, whatever ``dblk`` is (``start_row_gather``)."""
    a = _pad_2d(jnp.asarray(a), row_mult=1, col_mult=dblk)
    r, d = a.shape
    return a.reshape(r, d // dblk, 1, dblk).transpose(1, 0, 2, 3)


def sddmm_pallas(rows, cols, a, b, *, interpret: bool | None = None,
                 shape=None, **_opts):
    """Pallas SDDMM over a balanced slab: f32 edge scores shaped like
    ``rows`` (sentinel entries score 0).  Needs no visit schedule — scores
    are tile-local — so it works on traced patterns with no prep hook.
    Features wider than ``gather_width`` allows (both gathered ``(T,
    block)`` slabs within ``GATHER_BYTES``) are cut into equal blocks whose
    partial scores add up in the tile's output block."""
    if interpret is None:
        interpret = jax.default_backend() != "tpu"
    m = int(shape[0])
    dblk = gather_width(rows.shape[-1], a.shape[1])
    return _sddmm_call(rows, cols, _feature_blocks(a, dblk),
                       _feature_blocks(b, dblk), m=m, interpret=interpret)


def sddmm_work(rows, a) -> tuple[str, dict]:
    """The per-call work of ``sddmm_pallas`` (host-side, from shapes):
    ``visits`` and ``tile_loads`` are its grid steps, one gather of both
    endpoint slabs each (tiles × feature blocks); ``rows_gathered`` the row
    segments those gathers copy, ``2 T`` a load; ``gather_unroll`` the row
    copies one iteration of the gathers' issue loop starts."""
    n_tiles, t = rows.shape
    d = a.shape[1]
    loads = n_tiles * -(-d // gather_width(t, d))
    return "repro_sddmm", {"visits": loads, "tile_loads": loads,
                           "rows_gathered": 2 * t * loads,
                           "gather_unroll": GATHER_UNROLL}


# ---------------------------------------------------------------------------
# fused chain pass 1 (softmax only): online row (max, sum-of-exp) over visits
# ---------------------------------------------------------------------------

def _chain_stats_kernel(vt_ref, vb_ref, vs_ref, rows_ref, rows_s, cols_s,
                        a_hbm, b_hbm, rm_ref, rs_ref, ag, bg, sems, *, m, wb,
                        alpha):
    v = pl.program_id(0)

    @pl.when(new_tile(vt_ref, v))
    def _():
        gather_endpoints(rows_s, cols_s, a_hbm, b_hbm, ag, bg, sems, m)

    z = alpha * tile_scores(ag, bg)
    m_tile, s_tile = row_stats(z, rows_ref[...], vb_ref[v] * wb, wb, m)
    fold_stats(rm_ref, rs_ref, m_tile, s_tile, vs_ref[v])


@functools.partial(jax.jit, static_argnames=("m", "wb", "alpha", "interpret"))
def _chain_stats_call(vt, vb, vs, rows, cols, a, b, *, m, wb, alpha,
                      interpret):
    n_tiles, t = rows.shape
    d = a.shape[1]
    mb = -(-m // wb)
    n_visits = vt.shape[0]
    tile = lambda v, vt, *pf: (vt[v], 0, 0)  # noqa: E731
    stat = pl.BlockSpec((wb, 1), lambda v, vt, vb, *pf: (vb[v], 0))
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=3,
        grid=(n_visits,),
        in_specs=[tile_spec(t, tile), tile_spec(t, tile, pltpu.SMEM),
                  tile_spec(t, tile, pltpu.SMEM),
                  hbm_spec(),
                  hbm_spec()],
        out_specs=[stat, stat],
        scratch_shapes=gather_scratch(t, d),
    )
    rt, ct = as_tiles(rows), as_tiles(cols)
    rm, rs = pl.pallas_call(
        functools.partial(_chain_stats_kernel, m=m, wb=wb, alpha=alpha),
        grid_spec=grid_spec,
        out_shape=[jax.ShapeDtypeStruct((mb * wb, 1), jnp.float32),
                   jax.ShapeDtypeStruct((mb * wb, 1), jnp.float32)],
        name="repro_chain_stats",
        interpret=interpret,
    )(vt, vb, vs, rt, rt, ct, a, b)
    return stats_blocks(rm, rs, wb)


# ---------------------------------------------------------------------------
# fused chain pass 2: recompute scores, transform, accumulate w * X[cols]
# ---------------------------------------------------------------------------

def _chain_kernel(vt_ref, vb_ref, vs_ref, *refs, m, wb, tile_n, transform,
                  alpha):
    if transform == "softmax":
        (rows_ref, rows_s, cols_s, a_hbm, b_hbm, rm_ref, rs_ref, x_hbm, o_ref,
         ag, bg, xg, sems) = refs
    else:
        (rows_ref, rows_s, cols_s, a_hbm, b_hbm, x_hbm, o_ref,
         ag, bg, xg, sems) = refs
    j, v = pl.program_id(0), pl.program_id(1)
    rows = rows_ref[...]
    base = vb_ref[v] * wb

    # a new tile fetches its endpoint rows and, for this column block, the
    # X rows its column ids name (VDL) — all three gathers in flight at once
    @pl.when(new_tile(vt_ref, v))
    def _():
        start_row_gather(x_hbm.at[j], cols_s, xg, sems.at[2])
        gather_endpoints(rows_s, cols_s, a_hbm, b_hbm, ag, bg, sems, m)
        wait_row_gather(xg, sems.at[2])

    e = tile_scores(ag, bg)

    # per-row transform, in register — the edge weight never leaves VMEM
    if transform == "identity":
        w = e
    elif transform == "scale":
        w = alpha * e
    else:
        w = softmax_weights(alpha * e, rows, base, rm_ref[...], rs_ref[...],
                            wb, m)

    # SpMM half: the weighted one-hot segment matmul on the MXU
    s = segment_onehot(rows, w, base, wb, m)
    accumulate_visit(o_ref, mxu_dot(s, xg[...]), vs_ref[v])


@functools.partial(jax.jit, static_argnames=("m", "wb", "tile_n", "transform",
                                             "alpha", "interpret"))
def _chain_apply_call(vt, vb, vs, rows, cols, a, b, x, rm, rs, *, m, wb,
                      tile_n, transform, alpha, interpret):
    n_tiles, t = rows.shape
    d = a.shape[1]
    k, n_pad = x.shape
    nb = n_pad // tile_n
    mb = -(-m // wb)
    n_visits = vt.shape[0]
    tile = lambda j, v, vt, *pf: (vt[v], 0, 0)  # noqa: E731
    rt, ct = as_tiles(rows), as_tiles(cols)
    in_specs = [tile_spec(t, tile), tile_spec(t, tile, pltpu.SMEM),
                tile_spec(t, tile, pltpu.SMEM),
                hbm_spec(),
                hbm_spec()]
    ops = [rt, rt, ct, a, b]
    if transform == "softmax":
        stat = pl.BlockSpec((wb, 1), lambda j, v, vt, vb, *pf: (vb[v], 0))
        in_specs += [stat, stat]
        ops += [rm.reshape(-1, 1), rs.reshape(-1, 1)]
    in_specs.append(hbm_spec())
    ops.append(column_blocks(x, tile_n))
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=3,
        # visits iterate innermost so each output block's visits stay
        # consecutive grid steps — the revisited-block accumulation contract
        grid=(nb, n_visits),
        in_specs=in_specs,
        out_specs=pl.BlockSpec((wb, tile_n),
                               lambda j, v, vt, vb, *pf: (vb[v], j)),
        scratch_shapes=gather_scratch(t, d, 1, tile_n),
    )
    out = pl.pallas_call(
        functools.partial(_chain_kernel, m=m, wb=wb, tile_n=tile_n,
                          transform=transform, alpha=alpha),
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((mb * wb, n_pad), jnp.float32),
        name="repro_chain_apply",
        interpret=interpret,
    )(vt, vb, vs, *ops)
    return out[:m]


# ---------------------------------------------------------------------------
# public entry points
# ---------------------------------------------------------------------------

def chain_stats_pallas(rows, cols, a, b, *, interpret: bool | None = None,
                       shape=None, alpha=None, wb: int | None = None,
                       visit_tile=None, visit_block=None, visit_start=None,
                       **_opts):
    """Pass 1 alone: ``(mb, wb)`` row (max, sum-of-exp) blocks.  The sharded
    nnz-split backend calls this per shard and merges before pass 2."""
    if interpret is None:
        interpret = jax.default_backend() != "tpu"
    m = int(shape[0])
    wb = TileGeometry().wb if wb is None else wb
    al = 1.0 if alpha is None else float(alpha)
    ap = _pad_2d(jnp.asarray(a))
    bp = _pad_2d(jnp.asarray(b))
    return _chain_stats_call(visit_tile, visit_block, visit_start, rows, cols,
                             ap, bp, m=m, wb=wb, alpha=al, interpret=interpret)


def chain_pallas(rows, cols, a, b, x, *, interpret: bool | None = None,
                 shape=None, transform: str = "identity", alpha=None,
                 visit_tile=None, visit_block=None, visit_start=None,
                 wb: int | None = None, tile_n: int | None = None,
                 stats=None, **_opts):
    """Fused SDDMM→``transform``→SpMM over one visit schedule: edge scores
    never touch HBM.  The schedule may be precomputed (``_prep_chain`` at
    plan time) so the call stays traceable; ``stats`` substitutes externally
    combined softmax statistics (the sharded backend's cross-shard merge)."""
    if transform not in CHAIN_TRANSFORMS:
        raise ValueError(f"unknown chain transform {transform!r}; "
                         f"expected one of {CHAIN_TRANSFORMS}")
    if interpret is None:
        interpret = jax.default_backend() != "tpu"
    geom = TileGeometry()
    wb = geom.wb if wb is None else wb
    tile_n = geom.tile_n if tile_n is None else tile_n
    m = int(shape[0])
    al = 1.0 if alpha is None else float(alpha)
    if visit_tile is None or visit_block is None or visit_start is None:
        bal = BalancedCOO(rows, cols, jnp.zeros(rows.shape, jnp.float32),
                          (m, int(shape[1])))
        visit_tile, visit_block, visit_start = map(
            jnp.asarray, plan_visits(bal, wb))
    x2 = x[:, None] if x.ndim == 1 else x
    n = x2.shape[1]
    xp = _pad_n(x2, tile_n)
    ap = _pad_2d(jnp.asarray(a))
    bp = _pad_2d(jnp.asarray(b))
    rm = rs = None
    if transform == "softmax":
        if stats is None:
            rm, rs = _chain_stats_call(visit_tile, visit_block, visit_start,
                                       rows, cols, ap, bp, m=m, wb=wb,
                                       alpha=al, interpret=interpret)
        else:
            rm, rs = stats
    y = _chain_apply_call(visit_tile, visit_block, visit_start, rows, cols,
                          ap, bp, xp, rm, rs, m=m, wb=wb, tile_n=tile_n,
                          transform=transform, alpha=al, interpret=interpret)
    y = y[:, :n].astype(x2.dtype)
    return y[:, 0] if x.ndim == 1 else y


def _prep_chain(bal: BalancedCOO, *, geometry: TileGeometry | None = None
                ) -> dict:
    """Prep hook of the chain and attention kernels: the visit schedule,
    and the geometry's column-block width ``tile_n``, which these kernels
    still take from the plan."""
    geom = (geometry or TileGeometry()).validate()
    return dict(_prep_visits(bal, geometry=geom), tile_n=geom.tile_n)


registry.register("sddmm", "pallas", "balanced", sddmm_pallas)
registry.register("chain", "pallas", "balanced", chain_pallas,
                  prep=_prep_chain)
