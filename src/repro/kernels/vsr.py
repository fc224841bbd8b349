"""VSR — Vectorized Segment Reduction SpMM (paper §2.1.1), TPU-adapted.

GPU original: each warp takes a fixed quota of nonzeros (workload-balancing),
computes per-lane partial products, segment-reduces them with a SIMD-shuffle
prefix network keyed on row ids, and dumps segment heads with atomics.

TPU adaptation (see DESIGN.md §2):
  * warp → nnz-tile of ``T`` nonzeros; each grid step owns exactly one tile —
    equal work per step is the workload-balancing invariant.
  * shuffle network → **one-hot segment matmul on the MXU**: with per-tile
    local row ids ``l[T]`` and partial products ``P[T, N]``, the segment sums
    are ``S @ P`` where ``S[w, t] = (l[t] == w)`` — the same algebra the
    shuffle tree computes, expressed as the 128x128-systolic-friendly op.
  * atomics → accumulation into revisited output blocks (DESIGN.md §6):
    the TPU grid is *sequential*, so row-ordered nnz-tiles can accumulate
    directly into the output blocks they share.  A host-side visit schedule
    (``plan_visits``) lists, per tile, the ``wb``-row output blocks its rows
    land in; the kernel walks visits in order, initialising a block on its
    first visit (``pl.when``) and read-modify-writing it while consecutive
    visits share the block — boundary-crossing rows simply accumulate across
    visits, in VMEM.  Because no two grid steps run at once, no partials
    buffer and no post-kernel combine are needed.
  * VDL (§2.1.2) is a DMA row gather: ``X`` stays in HBM whatever its row
    count, and each nonzero of a tile copies the ``w``-wide slice of the
    dense row its column id names into a ``(T, w)`` VMEM scratch — one
    logical load covers ``w`` output columns (the V→N limit of float4).
    ``w = gather_width(T, N, wb)`` is all of N wherever the two gather
    slots fit ``GATHER_BYTES``, so each nonzero's row is one copy.
    The column ids ride an SMEM block so the scalar core can address the
    copies, ``GATHER_UNROLL`` of them an iteration of its issue loop; all
    ``T`` copies are in flight before one wait retires them (a
    DMA semaphore counts bytes, so a descriptor the size of the scratch
    covers every row copy).  The fused kernel double-buffers the gather:
    two ``(T, w)`` slots, and on a tile's first visit it starts the
    next tile's copies (their column ids come in a second SMEM block, one
    tile ahead) before it waits on its own, which the previous tile
    started, so the copies fly while the tile reduces.

Layout: the index/value streams are ``(n_tiles, 1, T)`` so one tile is a
legal ``(1, T)`` block; T is kept a multiple of 128 (lane width),
``wb`` a multiple of 8 (sublanes); N is padded to column blocks of
``gather_width`` by the wrapper and gathered operands are f32.  ``(T, wb)``
come from the measured tile geometry (``repro.kernels.tune``).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.core import registry
from repro.core.formats import BalancedCOO
from repro.core.guardrails import HEALTH
from repro.core.selector import TileGeometry


@HEALTH.span("repro.plan.visits")
def plan_visits(bal: BalancedCOO, wb: int
                ) -> tuple[np.ndarray, np.ndarray, np.ndarray]:
    """Host-side prep for the fused path: the (tile, output-block) visit
    schedule.

    Returns ``(visit_tile, visit_block, visit_start)``, each ``(V,)`` int32:
    visit v loads nnz-tile ``visit_tile[v]`` and accumulates the rows landing
    in output block ``visit_block[v]`` (rows ``[b*wb, (b+1)*wb)``);
    ``visit_start[v]`` flags the block's first visit (initialise vs.
    accumulate; the sharded backend additionally pads stacked schedules with
    ``visit_start == 2`` no-op visits — neither kernel branch fires, see
    ``core/shard.stack_visit_schedules``).  Because the nonzero stream is
    row-ordered, ``visit_block``
    is non-decreasing, so every output block's visits are consecutive grid
    steps — the revisited-block accumulation contract.  Blocks no tile
    touches (empty-row bands, row padding) get a fully-masked dummy visit so
    every output block is written exactly once.

    ``V = n_tiles + (block crossings) + (empty blocks)``: a skewed or gappy
    row costs *its own* tiles extra visits instead of inflating a global
    window for every tile — the per-tile window metadata of DESIGN.md §6."""
    rows = np.asarray(bal.rows)
    m = bal.shape[0]
    mb = max(1, -(-m // wb))
    n_tiles, t = rows.shape
    tids = np.repeat(np.arange(n_tiles, dtype=np.int64), t)
    rf = rows.reshape(-1)
    valid = rf < m
    keys = np.unique(tids[valid] * mb + rf[valid] // wb)
    vt = (keys // mb).astype(np.int32)
    vb = (keys % mb).astype(np.int32)
    covered = np.zeros(mb, bool)
    covered[vb] = True
    missing = np.nonzero(~covered)[0].astype(np.int32)
    if len(missing):
        # dummy visits: a tile cannot intersect an uncovered block (its rows'
        # blocks are covered by construction), so block-range masking zeroes
        # the whole contribution and the first-visit store writes zeros.
        # Each dummy borrows the *neighbouring* visit's tile id: consecutive
        # grid steps with an unchanged input-block index are not re-fetched
        # by the pipeline, so empty blocks cost one output write, not a DMA.
        vt = np.concatenate([vt, np.zeros(len(missing), np.int32)])
        vb = np.concatenate([vb, missing])
        dummy = np.concatenate([np.zeros(len(vt) - len(missing), bool),
                                np.ones(len(missing), bool)])
        order = np.argsort(vb, kind="stable")
        vt, vb, dummy = vt[order], vb[order], dummy[order]
        real_idx = np.nonzero(~dummy)[0]
        if len(real_idx):
            pos = np.searchsorted(real_idx, np.nonzero(dummy)[0])
            pos = np.minimum(pos, len(real_idx) - 1)
            vt[dummy] = vt[real_idx[pos]]
    vs = np.ones(len(vb), np.int32)
    if len(vb) > 1:
        vs[1:] = (vb[1:] != vb[:-1]).astype(np.int32)
    return vt, vb, vs


# ---------------------------------------------------------------------------
# shared kernel pieces: dense-row loading, segment one-hot, block accumulation
# ---------------------------------------------------------------------------

#: row copies one iteration of ``start_row_gather``'s issue loop starts.
#: Mosaic lowers a ``fori_loop`` only at unroll 1 or whole, so the groups
#: are written out by hand.
GATHER_UNROLL = 16

#: bytes of VMEM a row-gather kernel's two gathered ``(T, w)`` f32 slabs may
#: take together: the fused SpMM's two slots, the SDDMM's two endpoint slabs
GATHER_BYTES = 4 << 20


def gather_width(t: int, n: int, wb: int = 0) -> int:
    """Columns a row copy of a ``T``-nonzero tile gathers: all of ``n``
    (lane-padded) where two ``(t, n)`` f32 slabs fit ``GATHER_BYTES``, so
    each nonzero's row is one copy; else ``n`` cut into the fewest equal
    multiples of 128 that fit, so the last block pads the least.

    ``wb``: the fused SpMM's output-block height.  Its pipeline holds two
    ``(wb, w)`` f32 output blocks beside the two gather slots, so the width
    fits whichever pair is taller: the VMEM that grows with ``w`` stays
    within twice ``GATHER_BYTES`` at any geometry."""
    lanes = max(1, -(-n // 128))
    fit = max(1, GATHER_BYTES // (2 * 4 * max(t, wb)) // 128)
    blocks = -(-lanes // fit)
    return -(-lanes // blocks) * 128


def start_row_gather(src_hbm, idx_ref, dst, sem, *, clamp=None, row0=0):
    """Start the DMA row gather ``dst[row0 + i] = src[idx[i]]`` for each of
    the ``T`` ids ``i`` of ``idx_ref`` into the VMEM scratch ``dst``
    (``(rows, w)``; ``src`` is ``(rows, w)``).

    This is the TPU form of the paper's vectorized dense-row loading (VDL):
    ``src`` stays in HBM (``hbm_spec``) whatever its row count, and each
    nonzero fetches exactly the dense row its column id names, one DMA per
    row, all in flight at once.  A copy spans the full row width (the
    tiled HBM layout takes a single-row slice only at full width, and only
    128 lanes wide — column blocks come from ``column_blocks``).
    ``idx_ref`` is the tile's index block in SMEM (``(1, T)``); ``clamp``
    caps sentinel ids (padding rows of a row-indexed gather) to a valid
    row; ``row0`` offsets the rows written (one slot of a multi-tile
    scratch).

    The scalar core issues one descriptor per copy, and that issue sets the
    gather's pace, so the copies go out in groups of ``GATHER_UNROLL``:
    each iteration of the issue loop reads a group's ids at static offsets
    from its base, then starts the group's copies on ``sem``, and a static
    tail starts the ``T % GATHER_UNROLL`` copies left over.

    A ``(rows, 1, w)`` ``src`` (and ``(rows, 1, w)`` ``dst``) holds each
    row as a tile row of its own, so a row of any width ``w`` is one
    contiguous copy (the SDDMM gathers its feature rows so, and the fused
    SpMM its column blocks wider than 128)."""
    unit = len(src_hbm.shape) == 3
    t = idx_ref.shape[-1]

    def issue(base, count):
        ids = [idx_ref[0, base + k] for k in range(count)]
        if clamp is not None:
            ids = [jnp.minimum(r, clamp) for r in ids]
        for k, r in enumerate(ids):
            if unit:
                src, to = src_hbm.at[r], dst.at[row0 + base + k]
            else:
                src = src_hbm.at[pl.ds(r, 1)]
                to = dst.at[pl.ds(row0 + base + k, 1)]
            pltpu.make_async_copy(src, to, sem).start()

    def group(g, carry):
        issue(pl.multiple_of(g * GATHER_UNROLL, GATHER_UNROLL), GATHER_UNROLL)
        return carry

    groups = t // GATHER_UNROLL
    if groups:
        jax.lax.fori_loop(0, groups, group, 0)
    issue(groups * GATHER_UNROLL, t % GATHER_UNROLL)


def wait_row_gather(dst, sem):
    """Wait for the ``T`` row copies ``start_row_gather`` put in flight on
    ``sem``, with one wait: a DMA semaphore counts the bytes its copies
    land, so a descriptor the size of the whole ``(T, w)`` scratch retires
    all ``T`` row-sized copies at once (the source of a wait-only
    descriptor is never read)."""
    pltpu.make_async_copy(dst, dst, sem).wait()


def column_blocks(x: jax.Array, tile_n: int) -> jax.Array:
    """``(K, nb * tile_n)`` dense operand → ``(nb, K, tile_n)`` f32, so the
    row gather of column block ``j`` reads full-width rows of ``x[j]``."""
    k, n_pad = x.shape
    with jax.named_scope("repro.spmm.column_blocks"):
        return x.astype(jnp.float32).reshape(k, n_pad // tile_n, tile_n
                                             ).transpose(1, 0, 2)


def new_tile(vt_ref, v):
    """True on the first visit of a tile in the current sweep: consecutive
    visits of one tile (block crossings, padding visits) reuse its gathered
    rows in VMEM instead of fetching them again."""
    return (v == 0) | (vt_ref[v] != vt_ref[jnp.maximum(v - 1, 0)])


def block_hits(rows, base, wb: int, m: int):
    """``(wb, T)`` mask: nonzero ``t`` of a tile (lane-major ``(1, T)`` row
    ids) lands in output row ``base + r``; sentinel rows ``>= m`` never do."""
    row_iota = jax.lax.broadcasted_iota(jnp.int32, (wb, rows.shape[-1]), 0)
    return ((rows - base) == row_iota) & (rows < m)


def segment_onehot(rows, weights, base, wb: int, m: int):
    """The segment-reduction operator of one tile restricted to the output
    rows ``[base, base + wb)``: ``S[r, t] = weights[t]`` where nonzero ``t``
    lands in row ``base + r``, else 0.  ``S @ P`` is the shuffle-tree
    segment sum as one MXU matmul."""
    return jnp.where(block_hits(rows, base, wb, m), weights, 0.0)


def mxu_dot(a, b):
    """f32 matmul at full precision inside a kernel."""
    return jnp.dot(a, b, preferred_element_type=jnp.float32,
                   precision=jax.lax.Precision.HIGHEST)


def accumulate_visit(o_ref, contrib, start):
    """Sequential-grid accumulation into a revisited output block: the first
    visit (``start == 1``) initialises it, later visits (``0``) add, and
    padding visits (``2``, stacked sharded schedules) take neither branch."""

    @pl.when(start == 1)
    def _():
        o_ref[...] = contrib

    @pl.when(start == 0)
    def _():
        o_ref[...] += contrib


def tile_spec(t: int, index_map, memory_space=None):
    """BlockSpec of one ``(1, T)`` tile of an ``(n_tiles, 1, T)`` stream.
    The unit middle dim makes the block's last two dims equal the array's,
    which is what the TPU (8, 128) block rule accepts for a single row."""
    if memory_space is None:
        return pl.BlockSpec((None, 1, t), index_map)
    return pl.BlockSpec((None, 1, t), index_map, memory_space=memory_space)


def hbm_spec():
    """An operand left in HBM for the row gathers (never staged to VMEM,
    however small — the gathers slice it one row at a time)."""
    return pl.BlockSpec(memory_space=pltpu.HBM)


def as_tiles(a: jax.Array) -> jax.Array:
    """``(n_tiles, T)`` stream → the ``(n_tiles, 1, T)`` layout of
    ``tile_spec``."""
    return a.reshape(a.shape[0], 1, a.shape[-1])


# ---------------------------------------------------------------------------
# fused kernel: accumulation over revisited output blocks
# ---------------------------------------------------------------------------

def _vsr_fused_kernel(vt_ref, vb_ref, vs_ref, *refs, m, wb, quant):
    # with ``quant`` the per-tile scale rides the scalar-prefetch path as a
    # fourth prefetch operand (next to the visit schedule): the value stream
    # stays int8/fp8 all the way into VMEM and is rescaled *in register* —
    # no dequantized copy ever exists in HBM (DESIGN.md §8).
    if quant:
        sc_ref, *refs = refs
    (rows_ref, cols_ref, next_cols_ref, vals_ref, x_hbm, o_ref, xg, sem,
     pending_ref) = refs
    j, v = pl.program_id(0), pl.program_id(1)
    cur = vt_ref[v]
    t = rows_ref.shape[-1]

    # dense-row loading (VDL), double-buffered: tile ``i``'s X rows land in
    # slot ``i % 2`` of ``xg`` (rows ``[(i % 2) T, (i % 2 + 1) T)``) while
    # the tile before it reduces out of the other slot.
    def gather(idx_ref, tile):
        start_row_gather(x_hbm.at[j], idx_ref, xg, sem.at[tile % 2],
                         row0=tile % 2 * t)

    def wait(tile):
        wait_row_gather(xg.at[pl.ds(tile % 2 * t, t)], sem.at[tile % 2])

    first = new_tile(vt_ref, v)
    # the tile whose rows are in flight (none on a sweep's first visit)
    pending = jnp.where(v > 0, pending_ref[0], -1)
    # prefetch the next tile, unless this sweep never visits it
    prefetch = first & (cur + 1 <= vt_ref[pl.num_programs(1) - 1])

    @pl.when(first & (pending >= 0) & (pending != cur))
    def _():                          # the schedule skipped that tile: drain
        wait(pending)

    @pl.when(first & (pending != cur))
    def _():
        gather(cols_ref, cur)

    @pl.when(prefetch)
    def _():
        gather(next_cols_ref, cur + 1)

    @pl.when(first)
    def _():
        pending_ref[0] = jnp.where(prefetch, cur + 1, -1)
        wait(cur)

    vals = vals_ref[...].astype(jnp.float32)              # (1, T)
    if quant:
        vals = vals * sc_ref[cur]                         # in-register dequant
    # segment reduction as one weighted one-hot matmul on the MXU, restricted
    # to this visit's (wb, w) output block — boundary-crossing rows
    # accumulate across consecutive visits in VMEM, no partials array.
    s = segment_onehot(rows_ref[...], vals, vb_ref[v] * wb, wb, m)
    xs = xg[pl.ds(pl.multiple_of(cur % 2 * t, t), t)]
    xs = xs.reshape(t, xs.shape[-1])                      # unit rows: (T, w)
    accumulate_visit(o_ref, mxu_dot(s, xs), vs_ref[v])


@functools.partial(jax.jit,
                   static_argnames=("m", "wb", "tile_n", "interpret"))
def _vsr_fused_call(vt, vb, vs, rows, cols, vals, x, scales=None, *, m, wb,
                    tile_n, interpret):
    n_tiles, t = rows.shape
    k, n_pad = x.shape
    nb = n_pad // tile_n
    mb = -(-m // wb)
    n_visits = vt.shape[0]
    quant = scales is not None
    # ``*pf`` so the same index maps serve the 3- and 4-operand scalar-
    # prefetch arities (scales prepend when the stream is quantized).
    tile = lambda j, v, vt, *pf: (vt[v], 0, 0)  # noqa: E731
    # the column ids of the tile after this visit's, for the prefetch: the
    # pipeline fetches this block a step ahead like any other
    next_tile = lambda j, v, vt, *pf: (  # noqa: E731
        jnp.minimum(vt[v] + 1, n_tiles - 1), 0, 0)
    # the tiled HBM layout takes a single-row slice only 128 lanes wide; a
    # wider block holds each row as a tile row of its own, one contiguous
    # copy a row (``start_row_gather``'s unit copies)
    row = (tile_n,) if tile_n == 128 else (1, tile_n)
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=4 if quant else 3,
        grid=(nb, n_visits),
        in_specs=[
            tile_spec(t, tile),
            tile_spec(t, tile, pltpu.SMEM),
            tile_spec(t, next_tile, pltpu.SMEM),
            tile_spec(t, tile),
            hbm_spec(),
        ],
        out_specs=pl.BlockSpec((wb, tile_n),
                               lambda j, v, vt, vb, *pf: (vb[v], j)),
        scratch_shapes=[pltpu.VMEM((2 * t, *row), jnp.float32),
                        pltpu.SemaphoreType.DMA((2,)),
                        pltpu.SMEM((1,), jnp.int32)],
    )
    prefetch = (vt, vb, vs, scales) if quant else (vt, vb, vs)
    out = pl.pallas_call(
        functools.partial(_vsr_fused_kernel, m=m, wb=wb, quant=quant),
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((mb * wb, n_pad), jnp.float32),
        name="repro_vsr_fused",
        interpret=interpret,
    )(*prefetch, as_tiles(rows), as_tiles(cols), as_tiles(cols),
      as_tiles(vals), column_blocks(x, tile_n).reshape(nb, k, *row))
    with jax.named_scope("repro.spmm.unpad"):
        return out[:m]


# ---------------------------------------------------------------------------
# public entry points
# ---------------------------------------------------------------------------

def _pad_n(x2: jax.Array, tile_n: int) -> jax.Array:
    k, n = x2.shape
    n_pad = -(-n // tile_n) * tile_n
    return jnp.pad(x2, ((0, 0), (0, n_pad - n))) if n_pad != n else x2


def spmm_vsr_fused(bal: BalancedCOO, x: jax.Array, *,
                   wb: int | None = None,
                   interpret: bool | None = None,
                   visit_tile: jax.Array | None = None,
                   visit_block: jax.Array | None = None,
                   visit_start: jax.Array | None = None,
                   scales: jax.Array | None = None) -> jax.Array:
    """NB+PR SpMM over the visit schedule: no partials buffer, no
    post-kernel combine.  The column blocks are ``gather_width`` wide: one
    block wherever the tile's two gather slots of all of N fit VMEM.

    The visit schedule may be precomputed (``plan_visits`` at plan time) so
    the call stays traceable when ``bal`` carries traced values.  With a
    quantized value stream (int8/fp8 ``bal.vals``) pass the matching
    per-tile ``scales`` — dequant happens in register (DESIGN.md §8)."""
    if interpret is None:
        interpret = jax.default_backend() != "tpu"
    wb = TileGeometry().wb if wb is None else wb
    x2 = x[:, None] if x.ndim == 1 else x
    n = x2.shape[1]
    if visit_tile is None or visit_block is None or visit_start is None:
        vt, vb, vs = plan_visits(bal, wb)
        visit_tile, visit_block, visit_start = map(jnp.asarray, (vt, vb, vs))
    width = gather_width(bal.rows.shape[-1], n, wb)
    y = _vsr_fused_call(visit_tile, visit_block, visit_start,
                        bal.rows, bal.cols, bal.vals, _pad_n(x2, width),
                        scales, m=bal.shape[0], wb=wb, tile_n=width,
                        interpret=interpret)
    with jax.named_scope("repro.spmm.unpad"):
        y = y[:, :n].astype(x2.dtype)
    return y[:, 0] if x.ndim == 1 else y


# ---------------------------------------------------------------------------
# registry: the Pallas physical kernels for the nnz-balanced logical pair.
# On TPU the in-tile reduction-style split collapses (DESIGN.md §2): both
# nb_sr and nb_pr resolve to this binary; N=1 takes the VPU SpMV variant.
# ---------------------------------------------------------------------------

def _prep_visits(bal: BalancedCOO, *, geometry: TileGeometry | None = None
                 ) -> dict:
    """Prep hook of the visit-schedule kernels: the visit schedule and the
    output-block height it was cut for.  ``geometry`` is the plan's
    autotuned ``TileGeometry`` (``None`` → defaults); the column blocks
    follow the call's N (``gather_width``)."""
    geom = (geometry or TileGeometry()).validate()
    vt, vb, vs = plan_visits(bal, geom.wb)
    return {"visit_tile": jnp.asarray(vt), "visit_block": jnp.asarray(vb),
            "visit_start": jnp.asarray(vs), "wb": geom.wb}


def _pallas_nb(bal: BalancedCOO, x: jax.Array, scales: jax.Array | None = None,
               *, interpret: bool | None = None,
               visit_tile: jax.Array | None = None,
               visit_block: jax.Array | None = None,
               visit_start: jax.Array | None = None,
               wb: int | None = None, quant: str | None = None):
    # Quantized-plan dispatch (DESIGN.md §8): a *baked* substrate arrives
    # already int8/fp8 with its plan-aux scales riding the custom-VJP extras
    # (positional ``scales``); a *live* float stream on a quantized plan
    # (``with_values``) re-quantizes in graph with fresh per-tile scales —
    # either way only the narrow stream crosses HBM into the kernel.
    from repro.core import quant as quant_mod
    if quant_mod.is_quantized_dtype(bal.vals.dtype):
        if scales is None:
            raise ValueError("quantized value stream needs per-tile scales")
    elif quant is not None:
        q, scales = quant_mod.quantize_stream(bal.vals, quant)
        bal = BalancedCOO(bal.rows, bal.cols, q, bal.shape)
    else:
        scales = None
    if x.ndim == 1:
        from .spmv import spmv_vsr_fused
        return spmv_vsr_fused(bal, x, interpret=interpret, wb=wb,
                              visit_tile=visit_tile, visit_block=visit_block,
                              visit_start=visit_start, scales=scales)
    return spmm_vsr_fused(bal, x, interpret=interpret, wb=wb,
                          visit_tile=visit_tile, visit_block=visit_block,
                          visit_start=visit_start, scales=scales)


def _nb_work(bal: BalancedCOO, x: jax.Array, *,
             visit_tile: jax.Array | None = None,
             visit_block: jax.Array | None = None,
             wb: int | None = None, **_) -> tuple[str, dict]:
    """The per-call work of the kernel ``_pallas_nb`` binds, counted from
    the visit schedule and the operands of the call (host-side; with no
    schedule, from the one the kernel builds with ``plan_visits``).

    ``visits``: grid steps along the schedule; ``tile_loads``: runs of
    equal ``visit_tile`` (``new_tile`` gathers on each); ``prefetched_loads``
    (SpMM only): the tile loads whose rows were started while the tile
    before them reduced, counted per column-block sweep like
    ``tile_loads``; ``dummy_visits``: visits whose tile has no row in
    their block (empty output blocks; counted where the row stream is
    concrete and row-ordered, as ``csr_to_balanced`` builds it);
    ``rows_gathered``: for N > 1 the DMA row gather's ``(tile_loads +
    drained prefetches) × T × column blocks``, for N = 1 the ``cols.size``
    elements XLA gathers from ``x`` (``spmv._gathered``);
    ``column_blocks`` (N > 1): the column-block sweeps of the call, N over
    ``gather_width``; ``gather_unroll`` (N > 1): the row copies one
    iteration of the gather's issue loop starts (``GATHER_UNROLL``)."""
    n_tiles, t = bal.cols.shape
    n = 1 if x.ndim == 1 else x.shape[1]
    m = bal.shape[0]
    wb = TileGeometry().wb if wb is None else wb
    if visit_tile is None or visit_block is None:
        visit_tile, visit_block, _ = plan_visits(bal, wb)
    name = "repro_spmv_fused" if x.ndim == 1 else "repro_vsr_fused"
    vt = np.asarray(visit_tile)
    vb = np.asarray(visit_block).astype(np.int64)
    visits = len(vt)
    runs = vt[np.r_[True, vt[1:] != vt[:-1]]] if visits else vt
    tile_loads = len(runs)
    prefetched, drained = None, 0
    if x.ndim > 1:
        # as ``_vsr_fused_kernel`` runs: each tile load starts tile
        # i + 1 unless the sweep's last visit is on an earlier tile;
        # the next load uses it, or drains it when the schedule skips
        # that tile
        started = runs[:-1] + 1 <= vt[-1:]
        used = started & (runs[1:] == runs[:-1] + 1)
        prefetched = int(np.count_nonzero(used))
        drained = int(np.count_nonzero(started & ~used))
    dummy = None
    if not isinstance(bal.rows, jax.core.Tracer):
        rf = np.asarray(bal.rows).reshape(-1)
        # queries in the stream's dtype: a mixed-dtype search would
        # copy the whole row stream to the wider type
        lo = np.searchsorted(rf, (vb * wb).astype(rf.dtype))
        hi = np.searchsorted(rf, np.minimum((vb + 1) * wb, m)
                             .astype(rf.dtype))
        start = vt.astype(np.int64) * t
        hits = np.minimum(hi, start + t) - np.maximum(lo, start)
        dummy = int(np.count_nonzero(hits <= 0))
    if x.ndim == 1:
        rows = n_tiles * t
    else:
        blocks = -(-n // gather_width(t, n, wb))
        rows = (tile_loads + drained) * t * blocks
    work = {"visits": visits, "tile_loads": tile_loads, "rows_gathered": rows}
    if x.ndim > 1:
        work["column_blocks"] = blocks
        work["gather_unroll"] = GATHER_UNROLL
    if prefetched is not None:
        work["prefetched_loads"] = prefetched
    if dummy is not None:
        work["dummy_visits"] = dummy
    return name, work


registry.register("nb_pr", "pallas", "balanced", _pallas_nb, prep=_prep_visits,
                  work=_nb_work)
registry.register("nb_sr", "pallas", "balanced", _pallas_nb, prep=_prep_visits,
                  work=_nb_work)
