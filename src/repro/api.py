"""The public facade: first-class sparse operands over plan/execute.

One front door for every consumer in the repo (models, serving, training,
benchmarks, examples) and for external users::

    from repro import api

    A = api.sparse(dense_or_csr)          # plan once (cached by topology)
    y = A @ x                             # adaptive SpMM, jit/grad friendly
    y = A.with_values(stream) @ x         # live (trainable) value stream
    y = A.shard(mesh) @ x                 # partition-aware shard_map backend
    art = A.finalize(n=x.shape[1])        # frozen pytree PlanArtifact

    with api.use_backend("pallas"):       # scoped defaults, no kwarg threading
        y = api.sparse(dense) @ x

Internals (``repro.core.plan``) stay importable for the library itself and
its tests, but everything outside ``src/repro`` and ``tests`` must come
through here — CI enforces the boundary (``tools/check_api_boundary.py``).

Planning is cached in a topology-keyed bounded LRU (``PlanCache``): two
``sparse()`` calls over matrices sharing a sparsity pattern share one plan
(substrates, prep artifacts, compiled executables), and only the value
stream differs per call.  That is the paper's offline-profile /
online-dispatch split made ambient.
"""
from __future__ import annotations

import contextlib
import threading
import time as _time

import jax
import jax.numpy as jnp
import numpy as np

from repro.attention import (AttentionMask, AttentionSpec, SparseAttention,
                             attention_plan, bigbird, build_mask,
                             dense_attention, from_block_mask,
                             scoped_plan_cache, sliding_window,
                             sparse_attention)
from repro.core.cache import (DEFAULT_CACHE, PlanCache, cached_plan,
                              pattern_fingerprint, plan_key)
from repro.core.formats import CSR, csr_from_dense
from repro.core.guardrails import (HEALTH, NumericFault, PatternError,
                                   grad_scope, inspect_csr, plan_digest,
                                   repair_csr, sentinel_scope, validate_csr)
from repro.core.plan import (PlanArtifact, PlanBuildError, PlanBuilder,
                             execute, execute_attention, execute_chain,
                             execute_pattern, execute_sddmm, pattern_prep,
                             plan)
from repro.core.registry import backend_scope, default_backend
from repro.core.selector import (SelectorThresholds, TileGeometry,
                                 default_thresholds, geometry_key,
                                 load_thresholds, save_thresholds)
from repro.core.selector import calibrate as calibrate  # noqa: F401 (re-export)
from repro.core.stats import MatrixStats
from repro.runtime.faults import (FaultInjector, FaultSpec, InjectedFault,
                                  inject_faults)
from repro.runtime.retry import RetryPolicy, TaskOutcome, run_with_retry
from repro.serve import Request, ServeEngine

__all__ = [
    "SparseMatrix", "sparse", "sparse_chain", "sddmm", "pattern_matmul",
    "pattern_prep",
    "use_backend", "use_mesh",
    "calibrate", "calibrate_backend", "autotune_geometry", "autotune_overlap",
    "autotune_quant", "autotune_chain", "autotune_attention", "cache_stats",
    "clear_cache", "PlanArtifact", "PlanBuilder", "PlanCache",
    "SelectorThresholds", "TileGeometry", "geometry_key",
    "execute", "save_thresholds", "load_thresholds",
    # block-sparse attention (DESIGN.md §10)
    "AttentionMask", "AttentionSpec", "SparseAttention", "attention_plan",
    "bigbird", "build_mask", "dense_attention", "from_block_mask",
    "scoped_plan_cache", "sliding_window", "sparse_attention",
    # serving hardening (DESIGN.md §11)
    "Request", "ServeEngine", "FaultInjector", "FaultSpec", "InjectedFault",
    "RetryPolicy", "TaskOutcome", "run_with_retry", "PlanBuildError",
    # core guardrails (DESIGN.md §12)
    "PatternError", "NumericFault", "validate_csr", "inspect_csr",
    "repair_csr", "plan_digest", "sentinel_scope", "grad_scope",
    "inject_faults", "health", "reset_health", "configure_guardrails",
]


# ---------------------------------------------------------------------------
# scoped defaults
# ---------------------------------------------------------------------------

#: the training/pattern entry of the facade: differentiable SpMM over a bare
#: BalancedCOO-layout pattern with live values (no CSR, no plan object).
pattern_matmul = execute_pattern

use_backend = backend_scope

_MESH = threading.local()


@contextlib.contextmanager
def use_mesh(mesh, axis: str | None = None):
    """Make ``mesh`` the default for ``sparse()`` in the dynamic extent —
    matrices plan onto the sharded backend without threading ``mesh=``
    through every call site.  ``axis`` optionally pins the shard axis."""
    stack = getattr(_MESH, "stack", None)
    if stack is None:
        stack = _MESH.stack = []
    stack.append((mesh, axis))
    try:
        yield
    finally:
        stack.pop()


def scoped_mesh() -> tuple:
    stack = getattr(_MESH, "stack", None)
    return stack[-1] if stack else (None, None)


# ---------------------------------------------------------------------------
# the operand
# ---------------------------------------------------------------------------

class SparseMatrix:
    """First-class sparse operand: a (possibly cache-shared) plan plus this
    matrix's value stream.

    The plan is keyed by *topology* — pattern, shape, backend, mesh,
    thresholds — so matrices that differ only in values share substrates,
    prep artifacts, and compiled executables; ``_values`` (when set) rides
    ``execute(vals=...)`` as a live, differentiable stream.  Instances are
    immutable: ``with_values`` / ``with_thresholds`` / ``shard`` return new
    handles."""

    def __init__(self, plan_obj: PlanBuilder,
                 values: jax.Array | None = None,
                 cache: PlanCache | None = None):
        self._plan = plan_obj
        self._values = values
        self._cache = cache

    # -- introspection ------------------------------------------------------
    @property
    def plan(self) -> PlanBuilder:
        return self._plan

    @property
    def shape(self) -> tuple:
        return tuple(self._plan.csr.shape)

    @property
    def nnz(self) -> int:
        return self._plan.csr.nnz

    @property
    def stats(self) -> MatrixStats:
        return self._plan.stats

    @property
    def backend(self) -> str:
        return self._plan.backend

    @property
    def values(self) -> jax.Array:
        """The effective CSR-ordered nonzero value stream."""
        return self._values if self._values is not None else self._plan.csr.data

    @property
    def dtype(self):
        return self.values.dtype

    def topology_key(self) -> str:
        return self._plan.topology_key()

    def __repr__(self) -> str:
        m, k = self.shape
        live = "live" if self._values is not None else "baked"
        return (f"SparseMatrix({m}x{k}, nnz={self.nnz}, "
                f"backend={self.backend!r}, values={live})")

    # -- execution ----------------------------------------------------------
    def matmul(self, x: jax.Array, *, impl: str | None = None,
               backend: str | None = None,
               interpret: bool | None = None,
               sentinel: str | None = None) -> jax.Array:
        """``A @ x`` with per-call overrides (oracle/ablation mode).
        ``sentinel`` opts this call into post-execute non-finite detection
        (``"raise"``/``"sanitize"``/``"fallback"``, DESIGN.md §12)."""
        return execute(self._plan, x, vals=self._values, impl=impl,
                       backend=backend, interpret=interpret,
                       sentinel=sentinel)

    def __matmul__(self, x: jax.Array) -> jax.Array:
        return self.matmul(x)

    def sddmm(self, a: jax.Array, b: jax.Array, *,
              backend: str | None = None,
              interpret: bool | None = None) -> jax.Array:
        """Sample ``a @ b.T`` at this operand's nonzero positions — the
        pattern-only SDDMM (DESIGN.md §9).  Returns the ``(nnz,)``
        CSR-ordered score stream; feed it to ``with_values`` to build an
        attention-weighted operand, or use ``chain`` to fuse the consuming
        SpMM.  This handle's values are not read — only the pattern."""
        return execute_sddmm(self._plan, a, b, backend=backend,
                             interpret=interpret)

    def chain(self, a: jax.Array, b: jax.Array, x: jax.Array, *,
              transform: str = "softmax", alpha: float | None = None,
              backend: str | None = None,
              interpret: bool | None = None) -> jax.Array:
        """The fused SDDMM→SpMM chain: score ``a @ b.T`` at the nonzero
        positions, transform per row (``identity`` / ``scale`` / masked
        ``softmax``), and immediately aggregate ``x`` — edge scores live in
        VMEM only, never HBM (DESIGN.md §9).  Differentiable w.r.t. ``a``,
        ``b``, and ``x``; the backward pass is itself an SDDMM+SpMM pair."""
        return execute_chain(self._plan, a, b, x, transform=transform,
                             alpha=alpha, backend=backend,
                             interpret=interpret)

    # -- derived operands ---------------------------------------------------
    def with_values(self, stream: jax.Array) -> "SparseMatrix":
        """Same pattern and plan, new CSR-ordered nonzero values.  The stream
        is a live tensor — differentiate through ``(A.with_values(v) @ x)``
        w.r.t. ``v`` and it flows like any other parameter."""
        stream = jnp.asarray(stream)
        if stream.size != self.nnz:
            raise ValueError(f"value stream has {stream.size} entries but "
                             f"the pattern has {self.nnz} nonzeros")
        return SparseMatrix(self._plan, values=stream.reshape(-1),
                            cache=self._cache)

    def with_thresholds(self, th: SelectorThresholds) -> "SparseMatrix":
        return SparseMatrix(self._plan.with_thresholds(th),
                            values=self._values, cache=self._cache)

    def shard(self, mesh=None, *, axis: str | None = None,
              kind: str | None = None,
              inner_backend: str | None = None,
              geometry: TileGeometry | None = None) -> "SparseMatrix":
        """Re-plan this operand onto the partition-aware sharded backend
        (``core/shard.py``): the stats-driven partitioner picks row-split or
        nnz-balanced per the CV rule.  ``mesh`` defaults to the ``use_mesh``
        scope.

        Tile geometries are tuned *per backend*, so this plan's resolved
        geometry carries over only when the sharded inner backend is the
        same backend it was resolved for; otherwise the re-plan consults the
        thresholds table keyed on the inner backend (explicit ``geometry=``
        always wins)."""
        if mesh is None:
            mesh, scoped_axis = scoped_mesh()
            axis = axis or scoped_axis
        if mesh is None:
            raise ValueError("shard() needs a mesh (argument or use_mesh scope)")
        if geometry is None:
            old = self._plan
            geom_backend = ((old.inner_backend or default_backend())
                            if old.backend == "sharded" else old.backend)
            lookup = inner_backend or default_backend()
            geometry = old.geometry if lookup == geom_backend else None
        p = _plan_maybe_cached(self._plan.csr, cache=self._cache,
                               backend="sharded", mesh=mesh,
                               thresholds=self._plan.thresholds,
                               tile=self._plan.tile,
                               bsr_block=self._plan.bsr_block,
                               geometry=geometry,
                               shard_axis=axis, shard_kind=kind,
                               inner_backend=inner_backend,
                               quant=self._plan.quant)
        return SparseMatrix(p, values=self._values, cache=self._cache)

    def finalize(self, n: int | None = None, *, impl: str | None = None,
                 kernels: tuple | None = None) -> PlanArtifact:
        """Freeze into a jit-safe pytree ``PlanArtifact``.

        The artifact bakes *this handle's* values: a live stream (cache-hit
        handle, ``with_values``) re-plans off the shared builder first, so
        ``execute(art, x)`` is value-correct without the caller streaming
        ``vals=`` — freezing is eager by contract, the rebuild is the cost
        of the bake."""
        p = self._plan
        if self._values is not None:
            csr = CSR(p.csr.indptr, p.csr.indices,
                      jnp.asarray(self._values).reshape(-1), p.csr.shape)
            spec = p.shard_spec
            p = plan(csr, thresholds=p.thresholds, backend=p.backend,
                     tile=p.tile, bsr_block=p.bsr_block, mesh=p.mesh,
                     geometry=p.geometry,
                     shard_axis=spec.axis if spec is not None else None,
                     shard_kind=spec.kind if spec is not None else None,
                     inner_backend=p.inner_backend, quant=p.quant)
        return p.finalize(n, impl=impl, kernels=kernels)


def _as_csr(a) -> tuple[CSR, "jax.Array | None"]:
    """Normalize sparse() input to (csr, live value stream or None) — a
    SparseMatrix input keeps its live values across the re-plan."""
    if isinstance(a, CSR):
        return a, None
    if isinstance(a, SparseMatrix):
        return a.plan.csr, a._values
    arr = np.asarray(a)
    if arr.ndim != 2:
        raise ValueError(f"sparse() takes a CSR or a dense 2-D array; "
                         f"got shape {arr.shape}")
    return csr_from_dense(arr), None


def _plan_maybe_cached(csr: CSR, *, cache: PlanCache | None, **kw) -> PlanBuilder:
    if cache is None:
        return plan(csr, **kw)
    return cached_plan(csr, cache=cache, **kw)


def sparse(a, *, backend: str | None = None, mesh=None,
           thresholds: SelectorThresholds | None = None,
           tile: int | None = None,
           bsr_block: tuple = (8, 128), n_hint: int | None = None,
           shard_axis: str | None = None, shard_kind: str | None = None,
           geometry: TileGeometry | None = None,
           quant: str | None = None, chain_op: str | None = None,
           validate: str | None = None,
           cache: "PlanCache | bool | None" = True) -> SparseMatrix:
    """Build a first-class sparse operand from a CSR or a dense 2-D array.

    Planning goes through the topology-keyed ``PlanCache`` (the process
    default for ``cache=True``, a specific instance, or ``cache=False`` to
    re-plan): a hit whose baked values differ from ``a``'s returns a handle
    that streams its own values at execute time, so reuse is always
    value-correct.  ``backend``/``mesh`` default to the ``use_backend`` /
    ``use_mesh`` scopes, then the platform default.

    ``geometry`` forces a Pallas ``TileGeometry``; by default the
    thresholds' autotuned table (``autotune_geometry``) decides, and
    ``tile=None`` takes the geometry's nnz quota.  Distinct geometries key
    distinct cache entries (DESIGN.md §6).

    ``quant`` (``"int8"`` or ``"fp8"``) stores the value stream quantized
    per tile with f32 scales; kernels dequantize in-register (DESIGN.md §8).
    A caller ``n_hint`` below the thresholds' measured ``quant_min_n``
    crossover drops it — narrow operands don't amortize the dequant — and a
    value distribution whose per-tile dynamic range breaks the error bound
    falls back to the unquantized plan with a warning.  Quantized and
    unquantized plans key distinct cache entries.

    ``chain_op`` tags the plan with the SDDMM→SpMM chain transform it will
    serve (``sparse_chain`` sets it automatically): chained and plain-SpMM
    plans over the same pattern key distinct cache entries, so retuning one
    never evicts the other's compiled executables.

    ``validate`` (DESIGN.md §12) runs the guardrail pattern policy before
    anything — fingerprinting, geometry lookup, caching — touches the CSR:
    ``"check"`` warns about unsorted/duplicate/out-of-range/non-finite
    defects, ``"repair"`` rebuilds through the canonical sort/coalesce/clip/
    zero pipeline (so the repaired matrix caches under its clean
    fingerprint), ``"strict"`` raises ``PatternError``."""
    csr, values = _as_csr(a)
    if validate is not None and validate != "off":
        csr, _ = validate_csr(csr, validate)
    if mesh is None:
        mesh, scoped_axis = scoped_mesh()
        shard_axis = shard_axis or scoped_axis
    resolved_backend = backend or ("sharded" if mesh is not None
                                   else default_backend())
    if quant is not None and n_hint is not None:
        # gate here, pre-cache, for the same reason geometry resolves here:
        # cached_plan never forwards n_hint, so plan() could not apply the
        # quant_min_n crossover itself on the cached path
        th_q = thresholds if thresholds is not None else default_thresholds()
        if n_hint < th_q.quant_min_n:
            quant = None
    if geometry is None:
        # resolve the autotuned geometry here, with the caller's n_hint, so
        # the cache keys on the *resolved* geometry (same bucket ⇒ same
        # entry) rather than on the raw hint — plan() would otherwise only
        # see n_hint=None through cached_plan
        th_resolved = (thresholds if thresholds is not None
                       else default_thresholds())
        if th_resolved.geometries:
            lookup_backend = (default_backend()
                              if resolved_backend == "sharded"
                              else resolved_backend)
            geometry = th_resolved.geometry_for(
                pattern_fingerprint(csr), n_hint, lookup_backend)
    cache_obj: PlanCache | None
    if cache is True:
        cache_obj = DEFAULT_CACHE
    elif cache is False:
        cache_obj = None
    else:
        cache_obj = cache
    p = _plan_maybe_cached(csr, cache=cache_obj, backend=resolved_backend,
                           mesh=mesh, thresholds=thresholds, tile=tile,
                           bsr_block=tuple(bsr_block), shard_axis=shard_axis,
                           shard_kind=shard_kind, geometry=geometry,
                           quant=quant, chain_op=chain_op)
    if values is None and p.csr is not csr:
        # cache hit from a pattern-equal matrix: keep OUR values live unless
        # they are bit-identical to the plan's baked stream
        with jax.ensure_compile_time_eval():
            same = np.array_equal(np.asarray(p.csr.data), np.asarray(csr.data))
        if not same:
            values = csr.data.reshape(-1)
    if n_hint is not None:
        entry = p.entry(p.select(n_hint))
        p.substrate(entry.substrate)
        p.kernel_opts(entry)
    return SparseMatrix(p, values=values, cache=cache_obj)


def sddmm(pattern, a, b, *, backend: str | None = None, mesh=None,
          interpret: bool | None = None, **plan_kw) -> jax.Array:
    """Sampled dense-dense matmul: ``(a @ b.T)`` at ``pattern``'s nonzero
    positions only, returned as the ``(nnz,)`` CSR-ordered stream.

    ``pattern`` is a CSR, a dense 2-D array (nonzeros define the pattern),
    or a ``SparseMatrix``; planning shares the same topology-keyed cache as
    ``sparse()``.  Differentiable w.r.t. ``a`` and ``b``."""
    A = pattern if isinstance(pattern, SparseMatrix) else (
        sparse(pattern, backend=backend, mesh=mesh, **plan_kw))
    return A.sddmm(a, b, backend=backend, interpret=interpret)


def sparse_chain(pattern, a, b, x, *, transform: str = "softmax",
                 alpha: float | None = None, backend: str | None = None,
                 mesh=None, interpret: bool | None = None,
                 **plan_kw) -> jax.Array:
    """The fused SDDMM→(transform)→SpMM chain over ``pattern``'s nonzeros:

        ``y[i] = sum_j  t(a[i] · b[j])[ij] * x[j]``   for (i,j) in pattern

    with ``t`` = ``identity``, ``scale`` (multiply by ``alpha``), or masked
    row ``softmax`` (graph attention).  On the Pallas backend the chain runs
    as one kernel — edge scores are computed, transformed, and consumed in
    VMEM without an HBM round-trip (DESIGN.md §9); the
    ``chain_fuse_min_n`` threshold (``autotune_chain``) gates fusion by
    dense width.  Plans are cached per ``(topology, transform)`` — the
    ``chain_op`` key segment.  Differentiable w.r.t. ``a``, ``b``, ``x``."""
    if isinstance(pattern, SparseMatrix):
        A = pattern
    else:
        A = sparse(pattern, backend=backend, mesh=mesh, chain_op=transform,
                   **plan_kw)
    return A.chain(a, b, x, transform=transform, alpha=alpha,
                   backend=backend, interpret=interpret)


# ---------------------------------------------------------------------------
# cache + guardrail observability
# ---------------------------------------------------------------------------

def cache_stats(cache: PlanCache | None = None) -> dict:
    return (cache or DEFAULT_CACHE).stats()


def clear_cache(cache: PlanCache | None = None) -> None:
    (cache or DEFAULT_CACHE).clear()


def health() -> dict:
    """Snapshot of the health registry (DESIGN.md §12):
    ``{"counters", "breakers", "spans", "compile", "work"}``.

    Counters include the named demotions that used to be silent warnings
    (``demote:quant_range``, ``demote:chain_fuse``, ``demote:attn_fuse``,
    ``demote:traced_pattern_to_xla``, ...), sentinel firings
    (``sentinel:<site>``), kernel reroutes
    (``kernel_reroute:<from>-><to>:<logical>``), and pattern
    validation/repair events.  Breakers carry state / consecutive failures /
    trips / recoveries per (backend, logical kernel).

    ``spans``: per span name (``repro.plan``, ``repro.plan.cache``,
    ``repro.plan.stats``, ``repro.plan.fingerprint``,
    ``repro.plan.substrate``, ``repro.plan.prep`` with its child
    ``repro.plan.visits``, and
    ``repro.execute.trace``, the Python side of ``execute``, which under
    ``jit`` runs while the step is traced): ``count``, ``total_s``,
    ``self_s`` (less the child spans), ``parents`` (enclosing span name →
    count) and ``args`` (``key=value`` → count; the substrate's ``kind``).
    ``compile``: backend compilations seen by a ``jax.monitoring``
    listener — ``count``, ``seconds`` (a persistent-cache read or a
    compile and cache write), ``cache_hits``, ``cache_misses``,
    ``retrieval_s`` and ``by_function`` (``jit(<name>)`` → count,
    seconds).  ``work``: per ``<kernel>:<topology[:12]>:n<N>``, the
    per-call work of the kernel ``execute`` bound, counted from its visit
    schedule and operands — ``visits``, ``dummy_visits``, ``tile_loads``,
    ``prefetched_loads`` (the fused SpMM's double-buffered gather),
    ``rows_gathered``, with ``kernel``, ``topology``, ``n`` and ``binds``
    (times ``execute`` bound it)."""
    return HEALTH.snapshot()


def reset_health() -> None:
    """Drop all counters, breakers, spans, compile and work records (tests /
    fresh epochs)."""
    HEALTH.reset()


def configure_guardrails(*, threshold: int = 3, cooldown_s: float = 30.0) -> None:
    """Set the circuit-breaker parameters: ``threshold`` consecutive kernel
    failures trip a breaker open; after ``cooldown_s`` seconds it half-opens
    and probes the primary backend once (DESIGN.md §12)."""
    HEALTH.configure(threshold=threshold, cooldown_s=cooldown_s)


# ---------------------------------------------------------------------------
# calibration against this backend (the calibrate-on-first-serve hook)
# ---------------------------------------------------------------------------

def autotune_geometry(csr_or_matrix, **kwargs) -> SelectorThresholds:
    """Measured sweep over Pallas tile geometries ``(T, wb, tile_n)`` for one
    sparsity pattern; returns thresholds whose ``geometries`` table carries
    the winners per N-bucket (see ``repro.kernels.tune`` for the knobs).
    Persist with ``save_thresholds`` and later ``sparse()`` calls pick the
    tuned geometry up automatically — and key cache entries on it."""
    from repro.kernels.tune import autotune_geometry as _tune
    csr = (csr_or_matrix.plan.csr if isinstance(csr_or_matrix, SparseMatrix)
           else csr_or_matrix)
    return _tune(csr, **kwargs)


def autotune_overlap(csr_or_matrix, mesh, **kwargs) -> SelectorThresholds:
    """Measure the sharded compute/collective overlap crossover on ``mesh``
    and return thresholds with the winning ``overlap_min_n`` (DESIGN.md §7;
    ``repro.kernels.tune.autotune_overlap`` for the knobs)."""
    from repro.kernels.tune import autotune_overlap as _tune
    csr = (csr_or_matrix.plan.csr if isinstance(csr_or_matrix, SparseMatrix)
           else csr_or_matrix)
    return _tune(csr, mesh, **kwargs)


def autotune_quant(csr_or_matrix, **kwargs) -> SelectorThresholds:
    """Measure the quantization crossover for one pattern and return
    thresholds with the winning ``quant_min_n`` — the smallest dense width
    at which the int8/fp8 value stream's traffic saving beats its in-kernel
    dequant cost (``QUANT_NEVER`` when it never does; DESIGN.md §8;
    ``repro.kernels.tune.autotune_quant`` for the knobs)."""
    from repro.kernels.tune import autotune_quant as _tune
    csr = (csr_or_matrix.plan.csr if isinstance(csr_or_matrix, SparseMatrix)
           else csr_or_matrix)
    return _tune(csr, **kwargs)


def autotune_chain(csr_or_matrix, **kwargs) -> SelectorThresholds:
    """Measure the chain-fusion crossover for one pattern and return
    thresholds with the winning ``chain_fuse_min_n`` — the smallest dense
    width at which the one-kernel fused SDDMM→SpMM chain beats the unfused
    two-kernel pair (``CHAIN_NEVER`` when it never does; DESIGN.md §9;
    ``repro.kernels.tune.autotune_chain`` for the knobs)."""
    from repro.kernels.tune import autotune_chain as _tune
    csr = (csr_or_matrix.plan.csr if isinstance(csr_or_matrix, SparseMatrix)
           else csr_or_matrix)
    return _tune(csr, **kwargs)


def autotune_attention(specs, **kwargs) -> SelectorThresholds:
    """Measure the fused-attention crossover over a set of
    ``AttentionSpec``s and return thresholds with the winning
    ``attn_fuse_min_seq`` — the smallest sequence length at which the fused
    Pallas attention chain beats the unfused SDDMM+softmax+SpMM reference
    (``ATTN_NEVER`` when it never does; DESIGN.md §10;
    ``repro.kernels.tune.autotune_attention`` for the knobs)."""
    from repro.kernels.tune import autotune_attention as _tune
    return _tune(specs, **kwargs)


def calibrate_backend(save_to: str | None = None, *,
                      matrices: dict | None = None,
                      ns: tuple = (1, 8), repeats: int = 2,
                      backend: str | None = None,
                      n_grid: tuple = (2, 4, 8, 1 << 30),
                      avg_grid: tuple = (8.0, 16.0, 32.0, 64.0),
                      cv_grid: tuple = (0.25, 0.5, 1.0, 2.0),
                      tune_geometry: bool = False,
                      geometry_candidates: tuple | None = None,
                      overlap_mesh=None,
                      overlap_ns: tuple = (256, 512, 1024),
                      tune_quant: bool = False,
                      quant_ns: tuple = (8, 32, 128)):
    """Measure the 2x2 kernel grid on *this* backend and grid-search selector
    thresholds (paper §2.2/§3.2), optionally persisting the winner where
    ``$REPRO_THRESHOLDS`` will auto-load it.  The runtime driver runs this as
    its background calibrate-on-first-serve job; defaults use two small R-MAT
    matrices (one uniform, one skewed) so the pass costs seconds.

    ``tune_geometry=True`` additionally runs the Pallas tile-geometry sweep
    (``repro.kernels.tune``) over the same matrices and folds the measured
    winners into the persisted thresholds' ``geometries`` table.
    ``overlap_mesh`` (a device mesh) additionally measures the sharded
    compute/collective overlap crossover (``autotune_overlap``) on that mesh
    and folds the measured ``overlap_min_n`` into the result.
    ``tune_quant=True`` additionally measures the int8 quantization
    crossover (``autotune_quant``) and folds the measured ``quant_min_n``
    in."""
    from repro.core.rmat import rmat
    from repro.core.selector import calibrate as grid_search

    if matrices is None:
        matrices = {"uniform": rmat(8, 8, a=0.25, b=0.25, c=0.25, seed=0),
                    "skewed": rmat(8, 8, seed=1)}

    def time_fn(kernel: str, p: PlanBuilder, n: int) -> float:
        x = jnp.ones((p.csr.shape[1], n) if n > 1 else (p.csr.shape[1],),
                     jnp.float32)
        with backend_scope(backend):
            f = jax.jit(lambda xx: execute(p, xx, impl=kernel,
                                           backend=backend))
            jax.block_until_ready(f(x))  # compile outside the timed region
            t0 = _time.perf_counter()
            for _ in range(repeats):
                jax.block_until_ready(f(x))
        return (_time.perf_counter() - t0) / repeats

    best, report = grid_search(matrices, ns, time_fn=time_fn, n_grid=n_grid,
                               avg_grid=avg_grid, cv_grid=cv_grid)
    if tune_geometry:
        from repro.kernels.tune import autotune_geometry as _tune
        tune_ns = tuple(n for n in ns if n > 1) or (8,)
        for csr in matrices.values():
            best = _tune(csr, ns=tune_ns, backend=backend, thresholds=best,
                         repeats=repeats, candidates=geometry_candidates)
        report["geometries"] = dict(best.geometries)
    if overlap_mesh is not None:
        from repro.core.stats import matrix_stats
        from repro.kernels.tune import autotune_overlap as _overlap
        # the overlap tax is worst where tile-split (psum) plans live:
        # pick the most skewed calibration matrix by CV, not dict order
        skewed = max(matrices.values(), key=lambda c: matrix_stats(c).cv)
        best = _overlap(skewed, overlap_mesh, ns=overlap_ns,
                        thresholds=best, inner_backend=backend,
                        repeats=repeats)
        report["overlap_min_n"] = int(best.overlap_min_n)
    if tune_quant:
        from repro.kernels.tune import autotune_quant as _quant
        # the quant crossover is traffic-bound: measure on the matrix with
        # the most nonzeros (largest value stream), where narrowing matters
        heavy = max(matrices.values(), key=lambda c: int(c.nnz))
        best = _quant(heavy, ns=quant_ns, backend=backend,
                      thresholds=best, repeats=repeats)
        report["quant_min_n"] = int(best.quant_min_n)
    if save_to is not None:
        save_thresholds(best, save_to)
    return best, report
