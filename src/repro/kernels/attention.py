"""Fused block-sparse attention chain — SDDMM QK^T → masked softmax → SpMM·V.

The attention sibling of ``fused_chain`` (DESIGN.md §10): per-(batch, head)
block-sparse attention *is* the PR 7 chain with ``transform="softmax"`` and
``alpha = head_dim**-0.5`` — QK^T at the mask's nonzeros is an SDDMM, the
probability-weighted sum over V is an SpMM over the same pattern, and one
``plan_visits`` schedule drives both.  What earns attention its own logical
kernel (``attn_chain``) is the *additive bias hook*: relative-position or
ALiBi-style per-edge biases enter the softmax as ``z = scale * e + bias``,
so the bias stream rides the same balanced slab layout as the pattern and is
read once per pass — scores themselves never touch HBM.

Structure is identical to ``fused_chain``: pass 1 folds per-visit row
``(max, sum-of-exp)`` into ``(mb, wb)`` stat blocks with the online-softmax
update; pass 2 recomputes scores per column block, forms the weights in
register, and accumulates ``w * V[cols]`` into the revisited output block.
``attn_stats_pallas`` is exposed separately for the sharded cross-shard
stats merge.  Rows the mask leaves empty keep ``(SOFTMAX_NEG, 0)`` stats and
produce exact-zero output rows.
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
from .fused_chain import (_pad_2d, _prep_chain, fold_stats, gather_endpoints,
                          gather_scratch, row_stats, softmax_weights,
                          stats_blocks, tile_scores)
from .vsr import (_pad_n, accumulate_visit, as_tiles,
                  column_blocks, hbm_spec, mxu_dot, new_tile, plan_visits,
                  segment_onehot, start_row_gather, tile_spec,
                  wait_row_gather)


# ---------------------------------------------------------------------------
# pass 1: online row (max, sum-of-exp) of scale * QK^T + bias over visits
# ---------------------------------------------------------------------------

def _attn_stats_kernel(vt_ref, vb_ref, vs_ref, rows_ref, rows_s, cols_s,
                       q_hbm, k_hbm, bias_ref, rm_ref, rs_ref, qg, kg, sems,
                       *, m, wb, scale):
    v = pl.program_id(0)

    @pl.when(new_tile(vt_ref, v))
    def _():
        gather_endpoints(rows_s, cols_s, q_hbm, k_hbm, qg, kg, sems, m)

    z = scale * tile_scores(qg, kg) + bias_ref[...].astype(jnp.float32)
    m_tile, s_tile = row_stats(z, rows_ref[...], vb_ref[v] * wb, wb, m)
    fold_stats(rm_ref, rs_ref, m_tile, s_tile, vs_ref[v])


@functools.partial(jax.jit, static_argnames=("m", "wb", "scale", "interpret"))
def _attn_stats_call(vt, vb, vs, rows, cols, q, k, bias, *, m, wb, scale,
                     interpret):
    n_tiles, t = rows.shape
    d = q.shape[1]
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
                  hbm_spec(),
                  tile_spec(t, tile)],
        out_specs=[stat, stat],
        scratch_shapes=gather_scratch(t, d),
    )
    rt = as_tiles(rows)
    rm, rs = pl.pallas_call(
        functools.partial(_attn_stats_kernel, m=m, wb=wb, scale=scale),
        grid_spec=grid_spec,
        out_shape=[jax.ShapeDtypeStruct((mb * wb, 1), jnp.float32),
                   jax.ShapeDtypeStruct((mb * wb, 1), jnp.float32)],
        name="repro_attn_stats",
        interpret=interpret,
    )(vt, vb, vs, rt, rt, as_tiles(cols), q, k, as_tiles(bias))
    return stats_blocks(rm, rs, wb)


# ---------------------------------------------------------------------------
# pass 2: recompute scores, weight in register, accumulate w * V[cols]
# ---------------------------------------------------------------------------

def _attn_kernel(vt_ref, vb_ref, vs_ref, rows_ref, rows_s, cols_s, q_hbm,
                 k_hbm, bias_ref, rm_ref, rs_ref, x_hbm, o_ref, qg, kg, xg,
                 sems, *, m, wb, tile_n, scale):
    j, v = pl.program_id(0), pl.program_id(1)
    rows = rows_ref[...]
    base = vb_ref[v] * wb

    @pl.when(new_tile(vt_ref, v))
    def _():
        start_row_gather(x_hbm.at[j], cols_s, xg, sems.at[2])
        gather_endpoints(rows_s, cols_s, q_hbm, k_hbm, qg, kg, sems, m)
        wait_row_gather(xg, sems.at[2])

    # attention weight in register — the score never leaves VMEM
    z = scale * tile_scores(qg, kg) + bias_ref[...].astype(jnp.float32)
    w = softmax_weights(z, rows, base, rm_ref[...], rs_ref[...], wb, m)
    s = segment_onehot(rows, w, base, wb, m)
    accumulate_visit(o_ref, mxu_dot(s, xg[...]), vs_ref[v])


@functools.partial(jax.jit, static_argnames=("m", "wb", "tile_n", "scale",
                                             "interpret"))
def _attn_apply_call(vt, vb, vs, rows, cols, q, k, bias, x, rm, rs, *, m, wb,
                     tile_n, scale, interpret):
    n_tiles, t = rows.shape
    d = q.shape[1]
    kx, n_pad = x.shape
    nb = n_pad // tile_n
    mb = -(-m // wb)
    n_visits = vt.shape[0]
    tile = lambda j, v, vt, *pf: (vt[v], 0, 0)  # noqa: E731
    stat = pl.BlockSpec((wb, 1), lambda j, v, vt, vb, *pf: (vb[v], 0))
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=3,
        # visits innermost: each output block's visits are consecutive grid
        # steps — the revisited-block accumulation contract
        grid=(nb, n_visits),
        in_specs=[tile_spec(t, tile), tile_spec(t, tile, pltpu.SMEM),
                  tile_spec(t, tile, pltpu.SMEM),
                  hbm_spec(),
                  hbm_spec(),
                  tile_spec(t, tile), stat, stat,
                  hbm_spec()],
        out_specs=pl.BlockSpec((wb, tile_n),
                               lambda j, v, vt, vb, *pf: (vb[v], j)),
        scratch_shapes=gather_scratch(t, d, 1, tile_n),
    )
    rt = as_tiles(rows)
    out = pl.pallas_call(
        functools.partial(_attn_kernel, m=m, wb=wb, tile_n=tile_n,
                          scale=scale),
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((mb * wb, n_pad), jnp.float32),
        name="repro_attn_apply",
        interpret=interpret,
    )(vt, vb, vs, rt, rt, as_tiles(cols), q, k, as_tiles(bias),
      rm.reshape(-1, 1), rs.reshape(-1, 1), column_blocks(x, tile_n))
    return out[:m]


# ---------------------------------------------------------------------------
# public entry points
# ---------------------------------------------------------------------------

def attn_stats_pallas(rows, cols, q, k, bias, *, interpret: bool | None = None,
                      shape=None, scale=1.0, wb: int | None = None,
                      visit_tile=None, visit_block=None, visit_start=None,
                      **_opts):
    """Pass 1 alone: ``(mb, wb)`` row (max, sum-of-exp) blocks of
    ``scale * QK^T + bias``.  The sharded backend calls this per shard and
    merges before pass 2."""
    if interpret is None:
        interpret = jax.default_backend() != "tpu"
    m = int(shape[0])
    wb = TileGeometry().wb if wb is None else wb
    qp = _pad_2d(jnp.asarray(q))
    kp = _pad_2d(jnp.asarray(k))
    return _attn_stats_call(visit_tile, visit_block, visit_start, rows, cols,
                            qp, kp, bias, m=m, wb=wb, scale=float(scale),
                            interpret=interpret)


def attn_chain_pallas(rows, cols, q, k, bias, x, *,
                      interpret: bool | None = None, shape=None, scale=1.0,
                      visit_tile=None, visit_block=None, visit_start=None,
                      wb: int | None = None, tile_n: int | None = None,
                      stats=None, **_opts):
    """Fused block-sparse attention over one visit schedule: scores are
    formed, biased, softmaxed and consumed entirely in VMEM.  ``bias`` is a
    balanced slab shaped like ``rows`` (pass zeros for no bias); ``stats``
    substitutes externally merged softmax statistics."""
    if interpret is None:
        interpret = jax.default_backend() != "tpu"
    geom = TileGeometry()
    wb = geom.wb if wb is None else wb
    tile_n = geom.tile_n if tile_n is None else tile_n
    m = int(shape[0])
    if visit_tile is None or visit_block is None or visit_start is None:
        bal = BalancedCOO(rows, cols, jnp.zeros(rows.shape, jnp.float32),
                          (m, int(shape[1])))
        visit_tile, visit_block, visit_start = map(
            jnp.asarray, plan_visits(bal, wb))
    x2 = x[:, None] if x.ndim == 1 else x
    n = x2.shape[1]
    xp = _pad_n(x2, tile_n)
    qp = _pad_2d(jnp.asarray(q))
    kp = _pad_2d(jnp.asarray(k))
    if stats is None:
        rm, rs = _attn_stats_call(visit_tile, visit_block, visit_start, rows,
                                  cols, qp, kp, bias, m=m, wb=wb,
                                  scale=float(scale), interpret=interpret)
    else:
        rm, rs = stats
    y = _attn_apply_call(visit_tile, visit_block, visit_start, rows, cols,
                         qp, kp, bias, xp, rm, rs, m=m, wb=wb, tile_n=tile_n,
                         scale=float(scale), interpret=interpret)
    y = y[:, :n].astype(x2.dtype)
    return y[:, 0] if x.ndim == 1 else y


registry.register("attn_chain", "pallas", "balanced", attn_chain_pallas,
                  prep=_prep_chain)
