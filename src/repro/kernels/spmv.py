"""VSR SpMV — the paper's shuffle-network segment scan, on the VPU.

For N=1 the one-hot MXU matmul of ``vsr.py`` would light up 1/128 of the
systolic array (paper Insight 1 in reverse), so SpMV keeps the *literal* VSR
algorithm: a log-depth prefix network whose combine rule is "add if row ids
match" (paper Fig. 2(e)), realized with lane rotates (``pltpu.roll``) — the
TPU analogue of ``__shfl_up_sync`` — followed by a segment-head dump.

Per visit of a tile of T nonzeros (lane-major ``(1, T)`` vectors):
  1. p = vals * x[cols]                      (x[cols] gathered by XLA: at
     N=1 a dense row is one scalar, the same bytes as the column ids)
  2. log2(T) shift-and-add-if-same-row steps → p[i] = inclusive segment sum
  3. segment *ends* (next row differs) dump their sum into the visit's
     ``(wb, 1)`` output block.

Like the SpMM (``kernels/vsr.py``), the SpMV walks the host-side visit
schedule and accumulates its segment-head dumps directly into revisited
output blocks: the grid is sequential, so rows that cross tiles sum in
VMEM, with no partials buffer and no post-kernel combine.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.core.formats import BalancedCOO
from repro.core.selector import TileGeometry
from .vsr import (accumulate_visit, as_tiles, plan_visits, segment_onehot,
                  tile_spec)


def _shift_prev(a, d, idx):
    """``a[t - d]`` at lane ``t`` (wrapping) — the ``__shfl_up_sync`` of the
    prefix network, as a lane rotate.  The rotate direction is read off the
    rotated lane iota, so the network does not depend on which way the
    hardware rotate turns."""
    t = a.shape[-1]
    up = pltpu.roll(a, d, 1)
    down = pltpu.roll(a, t - d, 1)
    ok = pltpu.roll(idx, d, 1) == (idx - d) % t
    return jnp.where(ok, up, down)


def _segment_scan(p, rows):
    """Inclusive per-row prefix sums of a ``(1, T)`` tile: log2(T) rounds of
    shift-and-add-if-same-row (paper Fig. 2(e)).  Returns the scanned
    values and the segment-*end* mask (last nonzero of each row run)."""
    t = p.shape[-1]
    idx = jax.lax.broadcasted_iota(jnp.int32, p.shape, 1)
    d = 1
    while d < t:
        take = (idx >= d) & (_shift_prev(rows, d, idx) == rows)
        p = p + jnp.where(take, _shift_prev(p, d, idx), 0.0)
        d *= 2
    r_next = _shift_prev(rows, t - 1, idx)     # rows[t + 1]
    is_end = (idx == t - 1) | (r_next != rows)
    return p, is_end


def _head_dump(p, rows, is_end, base, w: int, m: int):
    """Segment heads of the tile summed onto the output rows
    ``[base, base + w)`` as a ``(w, 1)`` column."""
    keep = jnp.where(is_end, p, 0.0)
    s = segment_onehot(rows, keep, base, w, m)
    return jnp.sum(s, axis=1, keepdims=True)


def _gathered(x, cols):
    """The N=1 dense-row loading: ``x[cols]`` as an ``(n_tiles, 1, T)``
    f32 stream.  At N=1 a gathered row is one scalar, so the XLA-side
    gather moves exactly the bytes a per-nonzero DMA would — the same size
    as the column-id stream it replaces — and the kernel streams it."""
    with jax.named_scope("repro.spmv.gather_x"):
        return as_tiles(jnp.take(x.astype(jnp.float32), cols, axis=0))


# ---------------------------------------------------------------------------
# the kernel: segment-head dumps accumulate into revisited output blocks
# ---------------------------------------------------------------------------

def _spmv_fused_kernel(vt_ref, vb_ref, vs_ref, *refs, m, wb, quant):
    # with ``quant`` the per-tile scale rides the scalar-prefetch path as a
    # fourth prefetch operand, indexed by the visit's tile id
    if quant:
        sc_ref, rows_ref, xg_ref, vals_ref, o_ref = refs
    else:
        rows_ref, xg_ref, vals_ref, o_ref = refs
    v = pl.program_id(0)
    rows = rows_ref[...]                                   # (1, T)
    vals = vals_ref[...].astype(jnp.float32)
    if quant:
        vals = vals * sc_ref[vt_ref[v]]
    # the shuffle prefix network runs un-masked (rows never straddle output
    # blocks); the block restriction applies only to the head dump
    p = jnp.where(rows < m, vals * xg_ref[...], 0.0)
    p, is_end = _segment_scan(p, rows)
    # sequential-grid accumulation: boundary-crossing rows are dumped once
    # per visiting tile and summed here, in VMEM, instead of spilling
    accumulate_visit(o_ref, _head_dump(p, rows, is_end, vb_ref[v] * wb, wb, m),
                     vs_ref[v])


@functools.partial(jax.jit, static_argnames=("m", "wb", "interpret"))
def _spmv_fused_call(vt, vb, vs, rows, cols, vals, x, scales=None, *, m, wb,
                     interpret):
    n_tiles, t = rows.shape
    mb = -(-m // wb)
    n_visits = vt.shape[0]
    quant = scales is not None
    # ``*pf`` so the same index maps serve both scalar-prefetch arities
    tile = lambda v, vt, *pf: (vt[v], 0, 0)  # noqa: E731
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=4 if quant else 3,
        grid=(n_visits,),
        in_specs=[tile_spec(t, tile)] * 3,
        out_specs=pl.BlockSpec((wb, 1), lambda v, vt, vb, *pf: (vb[v], 0)),
    )
    prefetch = (vt, vb, vs, scales) if quant else (vt, vb, vs)
    y = pl.pallas_call(
        functools.partial(_spmv_fused_kernel, m=m, wb=wb, quant=quant),
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((mb * wb, 1), jnp.float32),
        name="repro_spmv_fused",
        interpret=interpret,
    )(*prefetch, as_tiles(rows), _gathered(x, cols), as_tiles(vals))
    return y[:m, 0]


def spmv_vsr_fused(bal: BalancedCOO, x: jax.Array, *,
                   interpret: bool | None = None, wb: int | None = None,
                   visit_tile: jax.Array | None = None,
                   visit_block: jax.Array | None = None,
                   visit_start: jax.Array | None = None,
                   scales: jax.Array | None = None) -> jax.Array:
    """NB+PR SpMV: the shuffle-network segment scan with
    segment heads accumulated straight into revisited output blocks.  The
    visit schedule may be precomputed (``plan_visits`` at plan time) so the
    call stays traceable when ``bal`` carries traced values.  ``scales``:
    per-tile dequant scales for quantized value streams."""
    if interpret is None:
        interpret = jax.default_backend() != "tpu"
    assert x.ndim == 1, "spmv_vsr_fused is the N=1 path"
    wb = TileGeometry().wb if wb is None else wb
    if visit_tile is None or visit_block is None or visit_start is None:
        vt, vb, vs = plan_visits(bal, wb)
        visit_tile, visit_block, visit_start = map(jnp.asarray, (vt, vb, vs))
    y = _spmv_fused_call(visit_tile, visit_block, visit_start,
                         bal.rows, bal.cols, bal.vals, x, scales,
                         m=bal.shape[0], wb=wb, interpret=interpret)
    return y.astype(x.dtype)
