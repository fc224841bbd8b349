"""Measured tile-geometry autotuner and crossover calibrations (DESIGN.md §6).

The paper derives its *selector* thresholds empirically; the same argument
applies one level down, to the Pallas NB kernels' tile geometry: the winning
``(T, wb, tile_n)`` shifts with sparsity pattern and dense width N (Hu et
al., "Heuristic Adaptability to Input Dynamics for SpMM on GPUs",
PAPERS.md), so the geometry is a **measured, per-plan decision**, not a
constant.

``autotune_geometry`` runs a small timed sweep over candidate geometries for
one pattern and folds the winners — keyed by ``(backend, pattern
fingerprint, N-bucket)`` — into ``SelectorThresholds.geometries``, the same
persistence channel as the selector cutoffs (``save_thresholds`` /
``$REPRO_THRESHOLDS``).  ``plan()`` consults that table on every build, and
because thresholds are part of the ``PlanCache`` key, a retuned geometry
invalidates exactly the plans it changes: distinct geometries ⇒ distinct
cache entries, same geometry ⇒ a hit.
"""
from __future__ import annotations

import time

import jax
import jax.numpy as jnp
import numpy as np

from repro.core import registry
from repro.core.cache import pattern_fingerprint
from repro.core.formats import CSR, csr_to_balanced
from repro.core.plan import execute, plan
from repro.core.selector import (SelectorThresholds, TileGeometry,
                                 default_thresholds, geometry_key)

from .vsr import plan_visits

#: the default measured sweep: nnz quota x output-block rows.  ``tile_n``
#: stays at 128: the fused SpMM takes its column-block width from the call's
#: N (``vsr.gather_width``), and only the fused chain and attention kernels
#: read the geometry's.
DEFAULT_CANDIDATES = (
    TileGeometry(tile=256, wb=32, tile_n=128),
    TileGeometry(tile=256, wb=64, tile_n=128),
    TileGeometry(tile=512, wb=32, tile_n=128),
    TileGeometry(tile=512, wb=64, tile_n=128),
    TileGeometry(tile=512, wb=128, tile_n=128),
    TileGeometry(tile=1024, wb=64, tile_n=128),
)


def _timed_execute(p, n: int, impl: str, interpret, repeats: int) -> float:
    """Shared measurement harness: jit the plan's execute at width ``n``,
    compile outside the timed region, return mean seconds per call."""
    k = p.csr.shape[1]
    x = jnp.ones((k, n) if n > 1 else (k,), jnp.float32)
    f = jax.jit(lambda xx: execute(p, xx, impl=impl, interpret=interpret))
    jax.block_until_ready(f(x))          # compile outside the timed region
    t0 = time.perf_counter()
    for _ in range(max(1, repeats)):
        jax.block_until_ready(f(x))
    return (time.perf_counter() - t0) / max(1, repeats)


def measure_geometry(csr: CSR, n: int, geom: TileGeometry, *,
                     backend: str | None = None,
                     thresholds: SelectorThresholds | None = None,
                     impl: str = "nb_pr",
                     interpret: bool | None = None,
                     quant: str | None = None,
                     repeats: int = 2) -> float:
    """Seconds per call of the NB kernel under one forced geometry."""
    backend = backend or registry.default_backend()
    th = thresholds if thresholds is not None else default_thresholds()
    p = plan(csr, backend=backend, thresholds=th, geometry=geom, n_hint=n,
             quant=quant)
    return _timed_execute(p, n, impl, interpret, repeats)


def autotune_geometry(csr: CSR, *, ns: tuple = (8, 128),
                      backend: str | None = None,
                      thresholds: SelectorThresholds | None = None,
                      candidates: tuple | None = None,
                      impl: str = "nb_pr",
                      interpret: bool | None = None,
                      quant: str | None = None,
                      repeats: int = 2,
                      include_wildcard: bool = True) -> SelectorThresholds:
    """Measured sweep over candidate geometries for one sparsity pattern.

    Returns thresholds extended with one geometry entry per N-bucket (and a
    wildcard entry covering un-hinted plans when ``include_wildcard``).
    ``quant`` re-tunes under a quantized value stream — shrinking the stream
    shifts the arithmetic-intensity balance, so the winning geometry can
    move (typically toward larger ``tile``: more nonzeros amortize each
    dense-block DMA once the stream is cheap).
    Timing in interpret mode is correctness-grade, not perf-grade — run on
    TPU (or pass precise ``candidates``) before persisting fleet-wide."""
    backend = backend or registry.default_backend()
    th = thresholds if thresholds is not None else default_thresholds()
    cands = tuple(candidates if candidates is not None else DEFAULT_CANDIDATES)
    fp = pattern_fingerprint(csr)
    log_times = {g: [] for g in cands}
    for n in ns:
        times = {g: measure_geometry(csr, n, g, backend=backend,
                                     thresholds=th, impl=impl,
                                     interpret=interpret, quant=quant,
                                     repeats=repeats)
                 for g in cands}
        best = min(times, key=times.get)
        th = th.with_geometry(geometry_key(backend, fp, n), best)
        for g, t in times.items():
            log_times[g].append(np.log(max(t, 1e-12)))
    if include_wildcard and cands:
        overall = min(cands, key=lambda g: float(np.mean(log_times[g])))
        th = th.with_geometry(geometry_key(backend, fp, None), overall)
    return th


# ---------------------------------------------------------------------------
# overlap crossover: when does the chunked ppermute ring beat one psum?
# ---------------------------------------------------------------------------

#: ``overlap_min_n`` sentinel for "the ring never wins on this backend"
OVERLAP_NEVER = 1 << 30


def measure_overlap(csr: CSR, mesh, n: int, *, chunked: bool,
                    thresholds: SelectorThresholds | None = None,
                    impl: str = "nb_pr", shard_kind: str = "nnz",
                    inner_backend: str | None = None,
                    interpret: bool | None = None,
                    repeats: int = 2) -> float:
    """Seconds per sharded psum-plan call with the reduction forced to the
    chunked ``ppermute`` ring (``chunked=True``) or one blocking psum."""
    import dataclasses
    th = thresholds if thresholds is not None else default_thresholds()
    th = dataclasses.replace(th,
                             overlap_min_n=1 if chunked else OVERLAP_NEVER)
    p = plan(csr, backend="sharded", mesh=mesh, shard_kind=shard_kind,
             thresholds=th, inner_backend=inner_backend, n_hint=n)
    return _timed_execute(p, n, impl, interpret, repeats)


def autotune_overlap(csr: CSR, mesh, *, ns: tuple = (256, 512, 1024),
                     thresholds: SelectorThresholds | None = None,
                     impl: str = "nb_pr", shard_kind: str = "nnz",
                     inner_backend: str | None = None,
                     interpret: bool | None = None,
                     repeats: int = 2) -> SelectorThresholds:
    """Measure the overlap crossover: the smallest dense width at which the
    width-chunked ring beats the blocking psum becomes ``overlap_min_n``
    (``OVERLAP_NEVER`` when the ring never wins — e.g. a single-device mesh,
    where there is no collective to hide).  Widths at or below the ring's
    chunk width (128 columns) cannot chunk — both runs
    would execute the identical blocking psum and the comparison would be
    pure noise — so they are skipped.  Timing off-TPU is correctness-grade;
    run on a real pod before persisting fleet-wide."""
    import dataclasses
    th = thresholds if thresholds is not None else default_thresholds()
    for n in sorted(n for n in ns if n > 128):
        kw = dict(thresholds=th, impl=impl, shard_kind=shard_kind,
                  inner_backend=inner_backend, interpret=interpret,
                  repeats=repeats)
        if (measure_overlap(csr, mesh, n, chunked=True, **kw)
                < measure_overlap(csr, mesh, n, chunked=False, **kw)):
            return dataclasses.replace(th, overlap_min_n=int(n))
    return dataclasses.replace(th, overlap_min_n=OVERLAP_NEVER)


# ---------------------------------------------------------------------------
# chain traffic + fuse crossover: SDDMM->SpMM with edge scores kept in VMEM
# ---------------------------------------------------------------------------

#: ``chain_fuse_min_n`` sentinel for "the fused chain never wins"
CHAIN_NEVER = 1 << 30


def modeled_traffic_chain(csr: CSR, n: int, d: int, *,
                          transform: str = "softmax",
                          geometry: TileGeometry | None = None,
                          dtype_bytes: int = 4,
                          index_bytes: int = 4) -> dict:
    """Per-call modeled HBM bytes of the SDDMM→(transform)→SpMM chain under
    both executions (DESIGN.md §9).

    * **unfused** (two kernels): the SDDMM writes every edge score to HBM
      (``nnz·dtype``), the transform reads and rewrites the stream
      (softmax: 2·nnz·dtype more), and the SpMM's value stream reads it back
      — the irreducible **edge-value round-trip is 2·nnz·dtype** (one write
      + one read) even before per-visit stream re-loads.
    * **fused** (one kernel): edge scores are recomputed per column block
      and consumed in-register — **0 edge-value HBM bytes**.  The price is
      the FusedMM trade: the ``A``/``B`` feature gathers are re-charged per
      column-block pass (``nb``×) plus once more for the softmax stats pass,
      and softmax row stats round-trip as two ``(m,)`` f32 vectors.

    ``d`` is the feature width of ``A (m,d)`` / ``B (k,d)``; ``n`` the dense
    width of ``X (k,n)``.  Flops count both kernels: ``2·nnz·(d+n)``.
    """
    geom = (geometry or TileGeometry()).validate()
    bal = csr_to_balanced(csr, tile=geom.tile)
    m, k = csr.shape
    nnz = int(csr.nnz)
    vt, _, _ = plan_visits(bal, geom.wb)
    n_tiles, t = bal.rows.shape
    n_visits = int(len(vt))
    stream_runs = int(1 + np.count_nonzero(vt[1:] != vt[:-1])) if n_visits else 0
    nb = max(1, -(-n // geom.tile_n))
    n_pad = nb * geom.tile_n
    mb = max(1, -(-m // geom.wb))
    softmax = transform == "softmax"

    idx_load = t * 2 * index_bytes                    # rows+cols, per tile load
    ab_pass = (m + k) * d * dtype_bytes               # A and B resident once
    xblock = k * geom.tile_n * dtype_bytes            # one (K, tile_n) block
    out = mb * geom.wb * n_pad * dtype_bytes          # blocks flushed once
    stats_vec = 2 * mb * geom.wb * 4                  # rm + rs, f32

    # -- unfused: SDDMM pass + transform round-trip + fused-NB SpMM pass
    edge_rt = 2 * nnz * dtype_bytes                   # SDDMM write + SpMM read
    transform_rt = 2 * nnz * dtype_bytes if softmax else 0
    unfused = (n_tiles * idx_load + ab_pass           # SDDMM: stream + A,B
               + stream_runs * nb * idx_load          # SpMM stream re-loads
               + nb * xblock + out                    # one pass over X, flush
               + edge_rt + transform_rt)

    # -- fused: (stats pass when softmax) + apply pass; edge values stay VMEM
    stats_pass = (stream_runs * idx_load + ab_pass + stats_vec) if softmax else 0
    stats_reload = n_visits * nb * 2 * geom.wb * 4 if softmax else 0
    fused = (stats_pass
             + stream_runs * nb * idx_load            # pattern re-read per pass
             + ab_pass                                # A,B resident once
             + nb * xblock + out + stats_reload)

    flops = 2 * nnz * (d + n)
    return {
        "fused_bytes": int(fused),
        "unfused_bytes": int(unfused),
        "fused_edge_value_bytes": 0,
        "unfused_edge_value_bytes": int(edge_rt),
        "unfused_transform_bytes": int(transform_rt),
        "transform": transform,
        "n_tiles": int(n_tiles),
        "n_visits": n_visits,
        "stream_runs": stream_runs,
        "flops": int(flops),
        "fused_ai": flops / max(fused, 1),
        "unfused_ai": flops / max(unfused, 1),
        "bytes_reduction": unfused / max(fused, 1),
    }


def measure_chain(csr: CSR, n: int, d: int, *, fused: bool,
                  transform: str = "softmax",
                  backend: str = "pallas",
                  thresholds: SelectorThresholds | None = None,
                  interpret: bool | None = None,
                  repeats: int = 2) -> float:
    """Seconds per chain call with the fuse gate forced open
    (``fused=True`` → the one-kernel Pallas chain) or shut (``fused=False``
    → the gate falls back to the unfused XLA pair)."""
    import dataclasses
    from repro.core.plan import execute_chain
    th = thresholds if thresholds is not None else default_thresholds()
    th = dataclasses.replace(th, chain_fuse_min_n=1 if fused else CHAIN_NEVER)
    p = plan(csr, backend=backend, thresholds=th, n_hint=n,
             chain_op=transform)
    m, k = csr.shape
    a = jnp.ones((m, d), jnp.float32) * 0.01
    b = jnp.ones((k, d), jnp.float32) * 0.01
    x = jnp.ones((k, n), jnp.float32)
    f = jax.jit(lambda aa, bb, xx: execute_chain(
        p, aa, bb, xx, transform=transform, interpret=interpret))
    jax.block_until_ready(f(a, b, x))     # compile outside the timed region
    t0 = time.perf_counter()
    for _ in range(max(1, repeats)):
        jax.block_until_ready(f(a, b, x))
    return (time.perf_counter() - t0) / max(1, repeats)


def autotune_chain(csr: CSR, *, ns: tuple = (8, 32, 128), d: int = 32,
                   transform: str = "softmax",
                   backend: str = "pallas",
                   thresholds: SelectorThresholds | None = None,
                   interpret: bool | None = None,
                   repeats: int = 2) -> SelectorThresholds:
    """Measure the chain-fusion crossover: the smallest dense width at which
    the one-kernel fused chain beats the unfused SDDMM+SpMM pair becomes
    ``chain_fuse_min_n`` (``CHAIN_NEVER`` when fusion never wins).  At tiny N
    the fused kernel's per-column-block score recompute (the FusedMM trade)
    can cost more than the edge-value round-trip it avoids; as N grows the
    recompute amortizes while the unfused round-trip stays ``2·nnz·dtype``.
    Timing off-TPU is correctness-grade; run on real hardware before
    persisting fleet-wide."""
    import dataclasses
    th = thresholds if thresholds is not None else default_thresholds()
    for n in sorted(ns):
        kw = dict(transform=transform, backend=backend, thresholds=th,
                  interpret=interpret, repeats=repeats)
        if (measure_chain(csr, n, d, fused=True, **kw)
                < measure_chain(csr, n, d, fused=False, **kw)):
            return dataclasses.replace(th, chain_fuse_min_n=int(n))
    return dataclasses.replace(th, chain_fuse_min_n=CHAIN_NEVER)


# ---------------------------------------------------------------------------
# attention crossover: when does the fused sparse-softmax chain win?
# ---------------------------------------------------------------------------

#: ``attn_fuse_min_seq`` sentinel for "the fused attention chain never wins"
ATTN_NEVER = 1 << 30


def modeled_traffic_attention(mask, head_dim: int = 64, *,
                              geometry: TileGeometry | None = None,
                              dtype_bytes: int = 4,
                              index_bytes: int = 4) -> dict:
    """Per-call modeled HBM bytes of block-sparse attention under both
    executions (DESIGN.md §10): the chain model with ``transform="softmax"``
    and Q/K/V all ``head_dim`` wide, plus the block-granularity view the
    ISSUE's acceptance metric names — the unfused path materializes every
    active score block (``2·nnz_blocks·bs²·dtype`` for the write + read of
    the score round-trip), the fused path materializes none.  ``mask`` is an
    ``AttentionMask`` (or anything with ``.csr``/``.nnz_blocks``/``.spec``)."""
    csr = mask.csr
    base = modeled_traffic_chain(csr, head_dim, head_dim,
                                 transform="softmax", geometry=geometry,
                                 dtype_bytes=dtype_bytes,
                                 index_bytes=index_bytes)
    bs = int(mask.spec.block)
    nnz_blocks = int(mask.nnz_blocks)
    base.update({
        "seq": int(mask.seq),
        "block": bs,
        "nnz_blocks": nnz_blocks,
        "fused_score_bytes": 0,
        "unfused_score_bytes": int(2 * nnz_blocks * bs * bs * dtype_bytes),
    })
    return base


def measure_attention(mask, d: int, *, fused: bool,
                      backend: str = "pallas",
                      thresholds: SelectorThresholds | None = None,
                      interpret: bool | None = None,
                      repeats: int = 2) -> float:
    """Seconds per attention call with the fuse gate forced open
    (``fused=True`` → the one-kernel Pallas attention chain) or shut
    (``fused=False`` → the unfused XLA SDDMM+softmax+SpMM reference)."""
    import dataclasses
    from repro.core.plan import execute_attention
    th = thresholds if thresholds is not None else default_thresholds()
    th = dataclasses.replace(th,
                             attn_fuse_min_seq=1 if fused else ATTN_NEVER)
    csr = mask.csr
    p = plan(csr, backend=backend, thresholds=th, n_hint=d, chain_op="attn")
    m, k = csr.shape
    q = jnp.ones((m, d), jnp.float32) * 0.01
    kk = jnp.ones((k, d), jnp.float32) * 0.01
    v = jnp.ones((k, d), jnp.float32)
    f = jax.jit(lambda qq, kq, vv: execute_attention(
        p, qq, kq, vv, interpret=interpret))
    jax.block_until_ready(f(q, kk, v))    # compile outside the timed region
    t0 = time.perf_counter()
    for _ in range(max(1, repeats)):
        jax.block_until_ready(f(q, kk, v))
    return (time.perf_counter() - t0) / max(1, repeats)


def autotune_attention(specs, *, d: int = 64,
                       backend: str = "pallas",
                       thresholds: SelectorThresholds | None = None,
                       interpret: bool | None = None,
                       repeats: int = 2) -> SelectorThresholds:
    """Measure the fused-attention crossover over a sweep of specs (sorted
    by sequence length): the smallest ``seq`` at which the fused Pallas
    chain beats the unfused reference becomes ``attn_fuse_min_seq``
    (``ATTN_NEVER`` when fusion never wins).  Short sequences amortize the
    visit-schedule setup and per-column-block recompute poorly; as ``seq``
    grows the deleted score round-trip (``2·nnz_blocks·bs²·dtype``)
    dominates.  Timing off-TPU is correctness-grade; run on real hardware
    before persisting fleet-wide."""
    import dataclasses
    from repro.attention import build_mask
    th = thresholds if thresholds is not None else default_thresholds()
    for spec in sorted(specs, key=lambda s: s.seq):
        mask = build_mask(spec)
        kw = dict(backend=backend, thresholds=th, interpret=interpret,
                  repeats=repeats)
        if (measure_attention(mask, d, fused=True, **kw)
                < measure_attention(mask, d, fused=False, **kw)):
            return dataclasses.replace(th, attn_fuse_min_seq=int(spec.seq))
    return dataclasses.replace(th, attn_fuse_min_seq=ATTN_NEVER)


# ---------------------------------------------------------------------------
# quant crossover: when does the narrowed value stream pay for its dequant?
# ---------------------------------------------------------------------------

#: ``quant_min_n`` sentinel for "quantization never wins on this backend"
QUANT_NEVER = 1 << 30


def measure_quant(csr: CSR, n: int, *, quant: str | None = "int8",
                  backend: str | None = None,
                  thresholds: SelectorThresholds | None = None,
                  impl: str = "nb_pr",
                  interpret: bool | None = None,
                  repeats: int = 2) -> float:
    """Seconds per NB-plan call with the value stream quantized to ``quant``
    (``None`` measures the unquantized baseline with identical thresholds)."""
    import dataclasses
    backend = backend or registry.default_backend()
    th = thresholds if thresholds is not None else default_thresholds()
    # force the gate open so the requested mode is what actually runs
    th = dataclasses.replace(th, quant_min_n=1)
    p = plan(csr, backend=backend, thresholds=th, n_hint=n, quant=quant)
    return _timed_execute(p, n, impl, interpret, repeats)


def autotune_quant(csr: CSR, *, ns: tuple = (8, 32, 128),
                   quant: str = "int8",
                   backend: str | None = None,
                   thresholds: SelectorThresholds | None = None,
                   impl: str = "nb_pr",
                   interpret: bool | None = None,
                   repeats: int = 2) -> SelectorThresholds:
    """Measure the quantization crossover: the smallest dense width at which
    the quantized plan beats the unquantized one becomes ``quant_min_n``
    (``QUANT_NEVER`` when it never wins).  At tiny N the stream narrowing
    saves little absolute traffic while the in-register dequant adds VPU
    work per visit; as N grows the dequant amortizes across the widening
    accumulate and the byte saving dominates — the same measured-crossover
    shape as ``autotune_overlap``.  Timing off-TPU is correctness-grade;
    run on real hardware before persisting fleet-wide."""
    import dataclasses
    th = thresholds if thresholds is not None else default_thresholds()
    for n in sorted(ns):
        kw = dict(backend=backend, thresholds=th, impl=impl,
                  interpret=interpret, repeats=repeats)
        if (measure_quant(csr, n, quant=quant, **kw)
                < measure_quant(csr, n, quant=None, **kw)):
            return dataclasses.replace(th, quant_min_n=int(n))
    return dataclasses.replace(th, quant_min_n=QUANT_NEVER)
