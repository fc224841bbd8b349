"""Sharded execution backend: partition-aware SpMM over shard_map.

The paper's adaptive story — pick workload-balancing vs. parallel-reduction
from cheap matrix statistics — extends one level up (Bharadwaj et al. and
Dai et al., PAPERS.md): the same ``MatrixStats`` that select a *kernel*
select a *partitioning* of the matrix across devices.

Two partitioners produce a ``ShardSpec``:

* **row-split** (``kind="row"``): shard s owns an equal slice of rows.  The
  cheap choice for uniform matrices — every shard's output rows are disjoint,
  so the cross-shard reduction is a **concat** (expressed as the shard_map
  ``out_specs`` along the shard axis; no collective at all).
* **nnz-balanced** (``kind="nnz"``): the BalancedCOO principle applied across
  devices — the row-major nonzero stream is cut into per-device quotas that
  differ by at most one nonzero, then each quota is tiled exactly like
  ``csr_to_balanced`` (same ``row == M`` sentinel padding).  Shards span row
  boundaries, so every shard computes a partial over the full output and the
  reduction is a **psum**.

The selection rule is the CV threshold one level up: ``cv > partition_cv`` →
nnz-balanced (skewed rows make equal-row shards unequal-work shards), else
row-split (``SelectorThresholds.partition_cv``, persisted with the rest of
the calibration — DESIGN.md §4.1).

Registry entries under backend ``"sharded"`` wrap the existing xla/pallas
kernels: each shard rebuilds its local inner substrate (ELL for ``rs_*``,
BalancedCOO for ``nb_*``) inside ``shard_map`` and runs it through the same
per-substrate-family custom VJPs as the single-device path, so the whole
thing stays jit-able and differentiable (the transpose of the replicated
dense operand is the ``psum`` of per-shard ``Aᵀ·g`` cotangents, which
shard_map derives automatically).  ``execute`` remains the single
interception point; per-shard substrates build lazily through the plan's
substrate cache.

Two multi-chip hot-path refinements (DESIGN.md §7): Pallas NB inners run
the *fused* visit-schedule kernels by default — ragged per-shard schedules
pad with no-op visits and stack ``(n_shards, max_visits)``
(``stack_visit_schedules``), so each shard pays only its own boundary
crossings plus free padding steps — and ``psum`` plans at ``N >=
thresholds.overlap_min_n`` replace the trailing blocking psum with a
width-chunked ``ppermute`` ring whose per-slab collectives overlap the next
slab's compute (``_overlapped_ring``).
"""
from __future__ import annotations

import dataclasses
import functools
import hashlib
from collections import OrderedDict
from typing import Any, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax import shard_map
from jax.sharding import NamedSharding, PartitionSpec as P

from . import registry
from .formats import BUILD_COUNTS, CSR, BalancedCOO, row_ids_from_indptr
from .selector import SelectorThresholds, default_thresholds, select_partition
from .stats import MatrixStats


# ---------------------------------------------------------------------------
# the partition spec
# ---------------------------------------------------------------------------

@dataclasses.dataclass(frozen=True)
class ShardSpec:
    """Static description of one partitioning of a sparse matrix.

    ``bounds`` are row boundaries for ``kind="row"`` and nonzero-stream
    boundaries for ``kind="nnz"`` (length ``n_shards + 1``); ``m_pad`` is the
    per-shard padded row count for row-split (shards stack only when equal)."""

    kind: str            # "row" | "nnz"
    axis: str            # mesh axis the shards map onto
    n_shards: int
    reduction: str       # "concat" (disjoint output rows) | "psum" (partials)
    bounds: Tuple[int, ...]
    m_pad: int = 0


def default_shard_axis(mesh) -> str:
    """The mesh axis with the most devices (ties → first in mesh order)."""
    names = list(mesh.axis_names)
    return max(names, key=lambda a: (mesh.shape[a], -names.index(a)))


def make_shard_spec(stats: MatrixStats, mesh, *, axis: str | None = None,
                    kind: str | None = None,
                    thresholds: SelectorThresholds | None = None) -> ShardSpec:
    """Stats-driven partitioner choice (the Fig. 4 shape, one level up)."""
    axis = axis or default_shard_axis(mesh)
    if axis not in mesh.axis_names:
        raise ValueError(f"mesh has no axis {axis!r}; axes: {mesh.axis_names}")
    n = int(mesh.shape[axis])
    kind = kind or select_partition(stats, thresholds or default_thresholds())
    if kind == "row":
        m_pad = max(1, -(-stats.m // n))
        bounds = tuple(min(s * m_pad, stats.m) for s in range(n + 1))
        return ShardSpec("row", axis, n, "concat", bounds, m_pad)
    if kind == "nnz":
        bounds = tuple((s * stats.nnz) // n for s in range(n + 1))
        return ShardSpec("nnz", axis, n, "psum", bounds, 0)
    raise ValueError(f"unknown partitioner kind {kind!r}; expected row|nnz")


# ---------------------------------------------------------------------------
# the sharded substrate: stacked per-shard inner formats + stream gather map
# ---------------------------------------------------------------------------

@dataclasses.dataclass(frozen=True)
class ShardedSubstrate:
    """Per-shard inner substrates stacked on a leading shard dim.

    ``src`` maps every value slot back into the global CSR nonzero stream
    (-1 for padding) — the hook that lets live value streams (trainable
    sparse weights) ride the sharded backend differentiably.

    Quantized plans (DESIGN.md §8) stack per-shard dequant ``scales``
    ``(n_shards, n_tiles)`` exactly like the visit schedules — sliced per
    shard inside shard_map and threaded to the inner kernel as a tensor
    argument; ``quant`` names the mode the codes were produced under."""

    _meta_fields = ("spec", "mesh", "inner_backend", "inner_kind",
                    "inner_shape", "shape", "quant")

    rows: Any            # (n, T, tile) for balanced; None for ell
    cols: Any            # (n, T, tile) balanced | (n, Ms, w) ell
    vals: Any
    lens: Any            # (n, Ms) for ell; None for balanced
    src: Any             # int32, same shape as vals; -1 = padding
    scales: Any          # (n, T) f32 per-tile dequant scales; None unquantized
    spec: ShardSpec
    mesh: Any
    inner_backend: str
    inner_kind: str      # "ell" | "balanced"
    inner_shape: Tuple[int, int]
    shape: Tuple[int, int]
    quant: str | None = None


jax.tree_util.register_dataclass(
    ShardedSubstrate,
    data_fields=["rows", "cols", "vals", "lens", "src", "scales"],
    meta_fields=list(ShardedSubstrate._meta_fields))


def _ell_slab(starts, lens, w, indices, data, nnz):
    """One shard's ELL arrays from per-row global stream starts + lengths."""
    j = np.arange(w, dtype=np.int64)[None, :]
    src = starts[:, None].astype(np.int64) + j
    valid = j < lens[:, None]
    if nnz:
        idx = np.clip(src, 0, nnz - 1)
        cols = np.where(valid, indices[idx], 0).astype(np.int32)
        vals = np.where(valid, data[idx], 0).astype(data.dtype)
    else:
        cols = np.zeros(src.shape, np.int32)
        vals = np.zeros(src.shape, data.dtype)
    return cols, vals, np.where(valid, src, -1).astype(np.int32)


def _bal_slab(b0, b1, row_off, sentinel, n_tiles, tile, rows_g, indices, data):
    """One shard's BalancedCOO arrays from a nonzero-stream slice [b0, b1) —
    the same tiling rule as ``csr_to_balanced`` (fixed quota, sentinel pad)."""
    q = b1 - b0
    pad = n_tiles * tile - q
    rows = np.concatenate([rows_g[b0:b1] - row_off,
                           np.full(pad, sentinel, np.int32)]).astype(np.int32)
    cols = np.concatenate([indices[b0:b1], np.zeros(pad, np.int32)]).astype(np.int32)
    vals = np.concatenate([data[b0:b1], np.zeros(pad, data.dtype)])
    src = np.concatenate([np.arange(b0, b1, dtype=np.int32),
                          np.full(pad, -1, np.int32)])
    shp = (n_tiles, tile)
    return rows.reshape(shp), cols.reshape(shp), vals.reshape(shp), src.reshape(shp)


def build_sharded_substrate(csr: CSR, spec: ShardSpec, mesh, *,
                            inner_kind: str, tile: int,
                            inner_backend: str,
                            quant: str | None = None) -> ShardedSubstrate:
    """Host-side construction of all per-shard substrates, stacked.

    ``quant``: quantize the stacked balanced value slab per (shard, tile)
    — one f32 scale per nnz-tile, stacked ``(n_shards, n_tiles)`` like the
    visit schedules.  Falls back to the unquantized slab (``scales=None``)
    when any tile's dynamic range fails ``core/quant.check_tile_range``;
    ELL inners never quantize (the mode is an NB-family feature)."""
    indptr = np.asarray(csr.indptr)
    indices = np.asarray(csr.indices)
    data = np.asarray(csr.data)
    m, k = csr.shape
    nnz = len(data)
    n = spec.n_shards
    BUILD_COUNTS[inner_kind] += n

    rows_s = cols_s = vals_s = lens_s = src_s = None
    if spec.kind == "row":
        inner_shape = (spec.m_pad, k)
        if inner_kind == "ell":
            w = max(1, int(np.diff(indptr).max()) if m else 1)
            cs, vs, ss, ls = [], [], [], []
            for s in range(n):
                r0, r1 = spec.bounds[s], spec.bounds[s + 1]
                starts = np.concatenate([indptr[r0:r1],
                                         np.full(spec.m_pad - (r1 - r0), nnz)])
                lens = np.concatenate([np.diff(indptr[r0:r1 + 1]),
                                       np.zeros(spec.m_pad - (r1 - r0), np.int64)])
                c, v, sr = _ell_slab(starts, lens, w, indices, data, nnz)
                cs.append(c); vs.append(v); ss.append(sr)
                ls.append(lens.astype(np.int32))
            cols_s, vals_s, src_s = np.stack(cs), np.stack(vs), np.stack(ss)
            lens_s = np.stack(ls)
        else:
            quotas = [int(indptr[spec.bounds[s + 1]] - indptr[spec.bounds[s]])
                      for s in range(n)]
            n_tiles = max(1, -(-max(quotas) // tile)) if quotas else 1
            rows_g = row_ids_from_indptr(indptr, nnz)
            rs, cs, vs, ss = [], [], [], []
            for s in range(n):
                b0, b1 = int(indptr[spec.bounds[s]]), int(indptr[spec.bounds[s + 1]])
                r, c, v, sr = _bal_slab(b0, b1, spec.bounds[s], spec.m_pad,
                                        n_tiles, tile, rows_g, indices, data)
                rs.append(r); cs.append(c); vs.append(v); ss.append(sr)
            rows_s, cols_s, vals_s, src_s = map(np.stack, (rs, cs, vs, ss))
    else:  # nnz-balanced
        inner_shape = (m, k)
        if inner_kind == "ell":
            ws, per = [], []
            for s in range(n):
                b0, b1 = spec.bounds[s], spec.bounds[s + 1]
                starts = np.clip(indptr[:-1], b0, b1)
                lens = np.clip(indptr[1:], b0, b1) - starts
                per.append((starts, lens))
                ws.append(int(lens.max()) if m else 0)
            w = max(1, max(ws) if ws else 1)
            cs, vs, ss, ls = [], [], [], []
            for starts, lens in per:
                c, v, sr = _ell_slab(starts, lens, w, indices, data, nnz)
                cs.append(c); vs.append(v); ss.append(sr)
                ls.append(lens.astype(np.int32))
            cols_s, vals_s, src_s = np.stack(cs), np.stack(vs), np.stack(ss)
            lens_s = np.stack(ls)
        else:
            quotas = [spec.bounds[s + 1] - spec.bounds[s] for s in range(n)]
            n_tiles = max(1, -(-max(quotas) // tile)) if quotas else 1
            rows_g = row_ids_from_indptr(indptr, nnz)
            rs, cs, vs, ss = [], [], [], []
            for s in range(n):
                r, c, v, sr = _bal_slab(spec.bounds[s], spec.bounds[s + 1], 0, m,
                                        n_tiles, tile, rows_g, indices, data)
                rs.append(r); cs.append(c); vs.append(v); ss.append(sr)
            rows_s, cols_s, vals_s, src_s = map(np.stack, (rs, cs, vs, ss))

    scales_s = None
    vals_j = None if vals_s is None else jnp.asarray(vals_s)
    if quant is not None and inner_kind == "balanced" and vals_j is not None:
        from . import quant as quant_mod
        if quant_mod.check_tile_range(vals_s, context="sharded substrate"):
            vals_j, scales_s = quant_mod.quantize_stream(vals_j, quant)
        else:
            quant = None
    else:
        quant = None

    as_j = lambda a: None if a is None else jnp.asarray(a)
    return ShardedSubstrate(
        rows=as_j(rows_s), cols=as_j(cols_s), vals=vals_j,
        lens=as_j(lens_s), src=as_j(src_s), scales=scales_s,
        spec=spec, mesh=mesh, inner_backend=inner_backend,
        inner_kind=inner_kind, inner_shape=tuple(inner_shape),
        shape=tuple(csr.shape), quant=quant)


def place_shards(a, mesh, axis: str):
    """Put a per-shard stack (leading dim = shards) on the mesh, shard ``s``
    on the devices of position ``s`` along ``axis`` — built host-side, it
    would otherwise sit on the default device and be scattered again on
    every call."""
    return jax.device_put(a, NamedSharding(mesh, P(axis)))


def place_substrate(sub: ShardedSubstrate) -> ShardedSubstrate:
    """``sub`` with every per-shard stack placed on its mesh."""
    return dataclasses.replace(sub, **{
        f: place_shards(getattr(sub, f), sub.mesh, sub.spec.axis)
        for f in ("rows", "cols", "vals", "lens", "src", "scales")
        if getattr(sub, f) is not None})


# ---------------------------------------------------------------------------
# shard_map kernel wrappers (the "sharded" backend entries)
# ---------------------------------------------------------------------------

# stable inner-kernel callables: the custom VJPs key retraces on the identity
# of their static (bound_fn, shape) tuple, so bind per (entry, interpret,
# static opts, tensor-opt names).  Bounded-LRU like PlanCache — geometry
# sweeps and interpret toggles must not grow process memory without bound.
_INNER_BOUND_CAP = 256
_INNER_BOUND: "OrderedDict" = OrderedDict()


def _make_inner(entry: registry.KernelEntry, interpret, statics: dict = {},
                tensor_keys: tuple = ()):
    """Identity-cached inner-kernel callable for the shard_map body.

    ``statics`` (``wb``/``tile_n``, ``quant``) bake into the partial;
    ``tensor_keys`` name the per-shard prep artifacts (visit schedules,
    scales) the callable takes as trailing *tensor* arguments — those are
    sliced inside shard_map and must not be baked into the (static) fn."""
    key = (entry, interpret, tuple(sorted(statics.items())), tensor_keys)
    fn = _INNER_BOUND.get(key)
    if fn is not None:
        _INNER_BOUND.move_to_end(key)
        return fn
    if entry.prep is None and not statics and not tensor_keys:
        fn = functools.partial(entry.fn, interpret=interpret)
    else:
        def fn(sub, x, *tensors, _f=entry.fn, _st=dict(statics),
               _tk=tensor_keys):
            return _f(sub, x, interpret=interpret, **_st,
                      **dict(zip(_tk, tensors)))
    _INNER_BOUND[key] = fn
    while len(_INNER_BOUND) > _INNER_BOUND_CAP:
        _INNER_BOUND.popitem(last=False)
    return fn


#: visit_start code marking a *padding* visit in a stacked schedule: neither
#: the init (1) nor the accumulate (0) branch of the fused kernels fires, so
#: the step is a pure no-op — it re-points at the previous visit's (tile,
#: block) pair, so the pipeline re-fetches nothing and flushes nothing.
VISIT_PAD = 2


def stack_visit_schedules(schedules) -> tuple:
    """Pad ragged per-shard ``plan_visits`` schedules to one dense stack.

    ``schedules``: [(visit_tile, visit_block, visit_start), ...] per shard.
    Each is padded to the longest shard's visit count with ``VISIT_PAD``
    no-op visits that borrow the shard's *last* (tile, block) pair — an
    unchanged BlockSpec index between consecutive grid steps costs no DMA,
    and the PAD code skips both ``pl.when`` branches, so padding costs only
    the grid step itself.  Returns ``(vt, vb, vs)`` each ``(n_shards,
    max_visits)`` int32 — low-skew shards stop paying the worst shard's
    schedule beyond those free steps."""
    vmax = max(len(vt) for vt, _, _ in schedules)
    vts, vbs, vss = [], [], []
    for vt, vb, vs in schedules:
        pad = vmax - len(vt)
        vts.append(np.concatenate([vt, np.full(pad, vt[-1], np.int32)]))
        vbs.append(np.concatenate([vb, np.full(pad, vb[-1], np.int32)]))
        vss.append(np.concatenate([vs, np.full(pad, VISIT_PAD, np.int32)]))
    return np.stack(vts), np.stack(vbs), np.stack(vss)


#: the prep opts of a visit-schedule kernel: per-shard tensors, then the
#: geometry statics every shard must agree on
_SCHEDULE_KEYS = ("visit_tile", "visit_block", "visit_start")
_GEOMETRY_KEYS = ("wb", "tile_n")


def _stack_prep_opts(per_shard: list, mesh, axis: str) -> dict:
    """Stack per-shard visit schedules into one sharded opts dict.

    The ragged schedules pad with no-op visits and stack on a leading shard
    dim (``stack_visit_schedules``), placed on the mesh like the substrate;
    the geometry statics (``wb``/``tile_n``) must agree across shards (one
    plan, one geometry)."""
    stacked = stack_visit_schedules(
        [tuple(np.asarray(o[k]) for k in _SCHEDULE_KEYS) for o in per_shard])
    out = {k: place_shards(a, mesh, axis)
           for k, a in zip(_SCHEDULE_KEYS, stacked)}
    for k in _GEOMETRY_KEYS:
        vals = {int(o[k]) for o in per_shard if o.get(k) is not None}
        if len(vals) > 1:
            raise ValueError(f"per-shard prep disagrees on {k!r}: {vals}")
        if vals:
            out[k] = vals.pop()
    return out


def _sharded_prep(sub: ShardedSubstrate, *, _logical: str,
                  geometry=None, overlap_min_n=None) -> dict:
    """Run the inner entry's host-side prep per shard; stack the artifacts.

    The visit schedules are per-shard ragged (visit counts differ), so
    they are padded with no-op visits and stacked (``_stack_prep_opts``)
    — the sharded inner path is the same visit-schedule kernel as
    single-device."""
    inner = registry.resolve(_logical, sub.inner_backend)
    if inner.prep is None or inner.substrate != sub.inner_kind:
        # prep-less inners (the XLA reference) and inners handed a shard
        # format other than the one their prep reads (Pallas rs_* on the
        # row-split ELL shards tiles them in graph) still get the overlap
        # cutoff: the ring wraps the reduction, not the kernel
        return ({} if overlap_min_n is None
                else {"overlap_min_n": int(overlap_min_n)})
    from .plan import _prep_context_kwargs
    ctx = _prep_context_kwargs(inner.prep, {"geometry": geometry})
    # one bulk device→host transfer, then per-shard host-side slicing — N
    # round trips through np.asarray made plan build O(n_shards) transfers
    rows_h = np.asarray(sub.rows)
    cols_h = np.asarray(sub.cols)
    vals_h = np.asarray(sub.vals)
    # every emitted opt must have a stacking rule — silently dropping an
    # opt a future prep depends on would run the kernel without it
    stackable = set(_SCHEDULE_KEYS + _GEOMETRY_KEYS)
    per_shard = []
    for s in range(sub.spec.n_shards):
        local = BalancedCOO(rows_h[s], cols_h[s], vals_h[s], sub.inner_shape)
        opts = dict(inner.prep(local, **ctx))
        if not set(_SCHEDULE_KEYS) <= set(opts) or set(opts) - stackable:
            raise ValueError(f"sharded backend cannot stack prep opts "
                             f"{sorted(opts)} of ({_logical!r}, "
                             f"{sub.inner_backend!r})")
        per_shard.append(opts)
    stacked = _stack_prep_opts(per_shard, sub.mesh, sub.spec.axis)
    if overlap_min_n is not None:
        stacked["overlap_min_n"] = int(overlap_min_n)
    return stacked


# ---------------------------------------------------------------------------
# width-chunked collective-permute ring: compute/collective overlap for psum
# ---------------------------------------------------------------------------

def _ring_psum(y, axis: str, n_shards: int):
    """All-reduce ``y`` over ``axis`` as an (n-1)-step shift-add ring.

    After step t, a shard holds the sum of its own and its t nearest
    upstream neighbours' partials; after n-1 steps every shard holds the
    full sum — same result as ``lax.psum``, but built from ``ppermute``
    steps that the latency-hiding scheduler can overlap with independent
    compute (the next width chunk's kernel call)."""
    if n_shards <= 1:
        return y
    perm = [(i, (i + 1) % n_shards) for i in range(n_shards)]
    acc = y
    for _ in range(n_shards - 1):
        acc = jax.lax.ppermute(acc, axis, perm=perm) + y
    return acc


def _overlapped_ring(run_chunk, x, chunk_w: int, axis: str, n_shards: int):
    """Width-chunked all-reduce with compute/collective overlap.

    ``run_chunk(x_slice)`` computes this shard's partial output slab for one
    width chunk (the kernel emits output slabs per chunk, the ``spmm_rs_pr``
    slab shape).  Chunk j+1's kernel call is issued *before* chunk j's ring
    drains — the two are data-independent, so each slab's permutes hide
    behind the next slab's compute (collective-matmul style) instead of one
    trailing blocking psum over the full width."""
    n = x.shape[1]
    n_chunks = -(-n // chunk_w)
    pad = n_chunks * chunk_w - n
    if pad:
        x = jnp.pad(x, ((0, 0), (0, pad)))
    part = run_chunk(x[:, :chunk_w])
    outs = []
    for j in range(n_chunks):
        nxt = (run_chunk(x[:, (j + 1) * chunk_w:(j + 2) * chunk_w])
               if j + 1 < n_chunks else None)
        outs.append(_ring_psum(part, axis, n_shards))
        part = nxt
    y = jnp.concatenate(outs, axis=1)
    return y[:, :n] if pad else y


def _sharded_exec(sub: ShardedSubstrate, x, *, _logical: str,
                  interpret=None, visit_tile=None, visit_block=None,
                  visit_start=None, wb=None, overlap_min_n=None,
                  quant: str | None = None):
    """Run the inner kernel per shard under shard_map; reduce per the spec.

    With stacked visit schedules in the prep opts the inner path is the
    visit-schedule NB kernel, each shard walking its own schedule.
    ``reduction == "psum"`` plans at ``N >= overlap_min_n`` replace the
    trailing blocking psum with the width-chunked ``ppermute`` ring."""
    from .vjp import _exec_balanced, _exec_ell

    spec = sub.spec
    inner = registry.resolve(_logical, sub.inner_backend)
    statics, tensor_keys, tensors = {}, (), []
    if visit_tile is not None:
        statics = {} if wb is None else {"wb": wb}
        tensor_keys = _SCHEDULE_KEYS
        tensors = [visit_tile, visit_block, visit_start]
    if sub.inner_kind == "balanced" and sub.scales is not None:
        # quantized plan: per-shard scales prepend the tensor list so they
        # land in ``extra[0]`` of the balanced custom VJP (the backward's
        # dequant convention, core/vjp.py); the inner wrapper receives them
        # as its ``scales=`` keyword via the tensor_keys zip
        statics["quant"] = sub.quant
        tensor_keys = ("scales",) + tensor_keys
        tensors = [sub.scales] + tensors
    elif quant is not None and sub.inner_kind == "balanced":
        # live float slab on a quantized request (the pattern entry): the
        # inner kernels re-quantize in graph with fresh per-shard-tile scales
        statics["quant"] = quant
    bound = _make_inner(inner, interpret, statics, tensor_keys)

    if sub.inner_kind == "balanced":
        ops = [sub.rows, sub.cols, sub.vals]
    else:
        ops = [sub.cols, sub.lens, sub.vals]
    ops += tensors
    in_specs = (P(spec.axis),) * len(ops) + (P(),)
    out_specs = P(spec.axis) if spec.reduction == "concat" else P()

    # overlap decision (DESIGN.md §7): chunk the width axis and ring-reduce
    # only where there is a collective to hide and enough width to chunk;
    # a chunk is one lane-wide column block of the inner kernel
    chunk_w = 128
    chunked = (spec.reduction == "psum" and spec.n_shards > 1
               and overlap_min_n is not None and x.ndim == 2
               and x.shape[1] >= max(int(overlap_min_n), chunk_w + 1))

    def local(*args):
        *shard_args, xx = args
        shard_args = [a[0] for a in shard_args]  # drop the leading shard dim

        def run(xc):
            if sub.inner_kind == "balanced":
                rows, cols, vals = shard_args[:3]
                extra = tuple(shard_args[3:])
                return _exec_balanced((bound, sub.inner_shape), rows, cols,
                                      vals.reshape(-1), xc, *extra)
            cols, lens, vals = shard_args[:3]
            return _exec_ell((bound, sub.inner_shape), cols, lens, vals, xc)

        if spec.reduction != "psum":
            return run(xx)
        if chunked:
            return _overlapped_ring(run, xx, chunk_w, spec.axis,
                                    spec.n_shards)
        return jax.lax.psum(run(xx), spec.axis)

    y = shard_map(local, mesh=sub.mesh, in_specs=in_specs,
                  out_specs=out_specs, check_vma=False)(*ops, x)
    if spec.reduction == "concat":
        y = y[: sub.shape[0]]  # strip the per-shard row padding
    return y


for _logical in registry.MATMUL_KERNELS:
    _sub_kind = "shard_ell" if _logical.startswith("rs") else "shard_balanced"
    registry.register(_logical, "sharded", _sub_kind,
                      functools.partial(_sharded_exec, _logical=_logical),
                      prep=functools.partial(_sharded_prep, _logical=_logical))


# ---------------------------------------------------------------------------
# sharded SDDMM + fused chain (DESIGN.md §9)
# ---------------------------------------------------------------------------
# Both take the *stacked* pattern arrays with GLOBAL row ids as primals (the
# plan layer lifts row-split local rows; see plan._chain_pattern) so the flat
# segment-sum backwards of core/vjp.py stay correct — the conversion back to
# shard-local ids happens here, inside shard_map.  Following Bharadwaj et al.
# (PAPERS.md), the SDDMM and the chain's SpMM half share one co-partitioning:
# the same ShardSpec, the same stacked visit schedules, the same replicated
# dense operands.

def _row_shard_operands(spec: ShardSpec, a):
    """Row-split helpers: per-shard global row offsets ``(n_shards,)`` and
    the A operand padded to ``n_shards * m_pad`` rows and stacked per shard
    (shard s owns global rows ``[s * m_pad, (s + 1) * m_pad)``)."""
    S, m_pad = spec.n_shards, spec.m_pad
    offs = jnp.arange(S, dtype=jnp.int32) * m_pad
    a_pad = jnp.pad(a, ((0, S * m_pad - a.shape[0]), (0, 0)))
    return offs, a_pad.reshape(S, m_pad, a.shape[1])


def _sddmm_sharded(rows, cols, a, b, *, interpret=None, shape=None,
                   mesh=None, spec=None, inner_backend=None, **_opts):
    """Per-shard SDDMM under shard_map: each shard scores its own slab with
    the single-device kernel; the stacked score slabs concat back out (the
    plan layer scatters them to the global stream through ``sub.src``)."""
    m, k = (int(s) for s in shape)
    inner = registry.resolve("sddmm", inner_backend)
    row_split = spec.kind == "row"
    if row_split:
        offs, a_sh = _row_shard_operands(spec, a)
        inner_shape = (spec.m_pad, k)
        ops = (rows, cols, offs, a_sh, b)
        in_specs = (P(spec.axis),) * 4 + (P(),)
    else:
        inner_shape = (m, k)
        ops = (rows, cols, a, b)
        in_specs = (P(spec.axis),) * 2 + (P(), P())

    def local(*args):
        if row_split:
            rg, cg, off, a_s, bb = args
            rg, cg, off, a_s = rg[0], cg[0], off[0], a_s[0]
            rl = jnp.where(rg < m, rg - off, inner_shape[0])
        else:
            rg, cg, a_s, bb = args
            rl, cg = rg[0], cg[0]
        return inner.fn(rl, cg, a_s, bb, interpret=interpret,
                        shape=inner_shape)

    out = shard_map(local, mesh=mesh, in_specs=in_specs,
                    out_specs=P(spec.axis), check_vma=False)(*ops)
    return out.reshape(rows.shape)


def _chain_sharded(rows, cols, a, b, x, *, interpret=None, shape=None,
                   transform: str = "identity", alpha=None, mesh=None,
                   spec=None, inner_backend=None, visit_tile=None,
                   visit_block=None, visit_start=None, wb=None, tile_n=None,
                   overlap_min_n=None,
                   **_opts):
    """Sharded fused SDDMM→transform→SpMM.

    Row-split shards own disjoint rows, so the softmax statistics are
    shard-local and the reduction is the concat ``out_specs``.  nnz-split
    shards span rows: pass 1 runs per shard and the statistics merge with
    the online-softmax collectives (``pmax`` of row maxes, ``psum`` of
    rescaled sums) before pass 2; output partials psum — or, at ``N >=
    overlap_min_n``, ride the width-chunked ``ppermute`` ring with the
    stats computed once outside the chunk loop (they are X-independent)."""
    from .spmm import chain_stats_xla, chain_xla
    m, k = (int(s) for s in shape)
    row_split = spec.kind == "row"
    fused = inner_backend == "pallas" and visit_tile is not None
    if fused:
        from repro.kernels.fused_chain import chain_pallas, chain_stats_pallas

    x2 = x[:, None] if x.ndim == 1 else x
    ops = [rows, cols]
    specs = [P(spec.axis), P(spec.axis)]
    if row_split:
        offs, a_sh = _row_shard_operands(spec, a)
        ops += [offs, a_sh]
        specs += [P(spec.axis), P(spec.axis)]
        inner_shape = (spec.m_pad, k)
    else:
        ops.append(a)
        specs.append(P())
        inner_shape = (m, k)
    ops += [b, x2]
    specs += [P(), P()]
    if fused:
        ops += [visit_tile, visit_block, visit_start]
        specs += [P(spec.axis)] * 3

    chunk_w = tile_n if tile_n is not None else 128
    chunked = (spec.reduction == "psum" and spec.n_shards > 1
               and overlap_min_n is not None and x.ndim == 2
               and x.shape[1] >= max(int(overlap_min_n), chunk_w + 1))

    def local(*args):
        it = iter(args)
        rg = next(it)[0]
        cg = next(it)[0]
        if row_split:
            off = next(it)[0]
            a_s = next(it)[0]
            rl = jnp.where(rg < m, rg - off, inner_shape[0])
        else:
            a_s = next(it)
            rl = rg
        bb = next(it)
        xx = next(it)
        if fused:
            vt = next(it)[0]
            vb = next(it)[0]
            vs = next(it)[0]

        stats = None
        if transform == "softmax" and not row_split and spec.n_shards > 1:
            # cross-shard softmax merge: each shard's (max, sum) over its
            # own nonzeros fold into the global per-row statistics
            if fused:
                rm_l, rs_l = chain_stats_pallas(
                    rl, cg, a_s, bb, interpret=interpret, shape=inner_shape,
                    alpha=alpha, wb=wb, visit_tile=vt, visit_block=vb,
                    visit_start=vs)
            else:
                rm_l, rs_l = chain_stats_xla(rl, cg, a_s, bb,
                                             shape=inner_shape, alpha=alpha)
            rm_g = jax.lax.pmax(rm_l, spec.axis)
            rs_g = jax.lax.psum(rs_l * jnp.exp(rm_l - rm_g), spec.axis)
            stats = (rm_g, rs_g)

        def run(xc):
            if fused:
                return chain_pallas(rl, cg, a_s, bb, xc, interpret=interpret,
                                    shape=inner_shape, transform=transform,
                                    alpha=alpha, visit_tile=vt,
                                    visit_block=vb, visit_start=vs, wb=wb,
                                    tile_n=tile_n, stats=stats)
            return chain_xla(rl, cg, a_s, bb, xc, shape=inner_shape,
                             transform=transform, alpha=alpha, stats=stats)

        if spec.reduction != "psum":
            return run(xx)
        if chunked:
            return _overlapped_ring(run, xx, chunk_w, spec.axis,
                                    spec.n_shards)
        return jax.lax.psum(run(xx), spec.axis)

    out_specs = P(spec.axis) if spec.reduction == "concat" else P()
    y = shard_map(local, mesh=mesh, in_specs=tuple(specs),
                  out_specs=out_specs, check_vma=False)(*ops)
    if spec.reduction == "concat":
        y = y[:m]    # strip the per-shard row padding
    return y[:, 0] if x.ndim == 1 else y


registry.register("sddmm", "sharded", "shard_balanced", _sddmm_sharded)
registry.register("chain", "sharded", "shard_balanced", _chain_sharded,
                  prep=functools.partial(_sharded_prep, _logical="chain"))


# ---------------------------------------------------------------------------
# plan-free sharded entry for trainable patterns (sparse-weight layers)
# ---------------------------------------------------------------------------

# stacked per-shard prep artifacts keyed by pattern content (bounded LRU):
# a sparse-weight layer presents the same pattern every step, so the fused
# schedule stacking runs once per (pattern, mesh split), not per call
_PATTERN_PREP_CAP = 64
_PATTERN_PREP: "OrderedDict" = OrderedDict()


def execute_pattern_sharded(rows, cols, vals, shape, x, *, mesh,
                            axis: str | None = None, impl: str = "nb_pr",
                            backend: str | None = None,
                            interpret=None, quant: str | None = None):
    """Tile-split a bare BalancedCOO-layout pattern across ``axis``.

    The pattern is already nnz-balanced (fixed quota per tile), so an equal
    share of tiles per device IS the nnz partitioner; partials psum.  When
    rows/cols are *concrete* (the sparse-weight layer steady state) and the
    resolved inner backend has a prep hook (Pallas NB), the per-shard visit
    schedules are built host-side, stacked, and the fused inner kernel runs
    inside shard_map — same hot path as planned sharded execution.  Traced
    patterns (scanned per-layer) fall back to the prep-free XLA reference
    and bump ``demote:traced_pattern_to_xla``."""
    axis = axis or default_shard_axis(mesh)
    n = int(mesh.shape[axis])
    traced = isinstance(rows, jax.core.Tracer)
    backend = backend or registry.default_backend()
    entry = registry.resolve(impl, backend)
    if entry.prep is not None and traced:
        from .guardrails import HEALTH
        HEALTH.bump("demote:traced_pattern_to_xla")
        backend, entry = "xla", registry.resolve(impl, "xla")
    if entry.substrate != "balanced":
        raise ValueError(f"execute_pattern_sharded needs a balanced-substrate "
                         f"kernel; {impl!r} consumes {entry.substrate!r}")
    t, tile = rows.shape
    v2 = vals.reshape(t, tile)
    per = -(-t // n)
    pad = per * n - t
    m = int(shape[0])
    if pad:
        rows = jnp.concatenate([rows, jnp.full((pad, tile), m, rows.dtype)])
        cols = jnp.concatenate([cols, jnp.zeros((pad, tile), cols.dtype)])
        v2 = jnp.concatenate([v2, jnp.zeros((pad, tile), v2.dtype)])
    rs = rows.reshape(n, per, tile)
    cs = cols.reshape(n, per, tile)
    vs = v2.reshape(n, per, tile)

    opts: dict = {}
    if entry.prep is not None:
        with jax.ensure_compile_time_eval():
            r_h = np.asarray(rs)
            c_h = np.asarray(cs)
        digest = hashlib.sha1(r_h.tobytes()).hexdigest()
        key = (entry, tuple(shape), r_h.shape, digest)
        opts = _PATTERN_PREP.get(key)
        if opts is None:
            per_shard = [dict(entry.prep(BalancedCOO(
                r_h[s], c_h[s], np.zeros(r_h[s].shape, np.float32),
                tuple(shape)))) for s in range(n)]
            opts = _stack_prep_opts(per_shard, mesh, axis)
            _PATTERN_PREP[key] = opts
            while len(_PATTERN_PREP) > _PATTERN_PREP_CAP:
                _PATTERN_PREP.popitem(last=False)
        else:
            _PATTERN_PREP.move_to_end(key)

    spec = ShardSpec("nnz", axis, n, "psum",
                     bounds=tuple(0 for _ in range(n + 1)))
    sub = ShardedSubstrate(
        rows=rs, cols=cs, vals=vs, lens=None, src=None, scales=None,
        spec=spec, mesh=mesh, inner_backend=backend, inner_kind="balanced",
        inner_shape=tuple(shape), shape=tuple(shape))
    return _sharded_exec(sub, x, _logical=impl, interpret=interpret,
                         quant=quant, **opts)
