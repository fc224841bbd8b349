"""Plan/execute: the one dispatch path for every sparse kernel in the repo.

The paper's usage mode is offline-profile / online-dispatch; Dai et al.
(PAPERS.md) name the same split "offline plan, online execute".  This module
makes that split the architecture, in two layers (DESIGN.md §5):

* ``PlanBuilder`` (returned by ``plan(csr, ...)``) is the **host side**:
  compute the Fig. 4 statistics once, fix the thresholds (auto-loading a
  persisted calibration from ``$REPRO_THRESHOLDS``), pick the backend, build
  substrates (ELL / BalancedCOO / BSR / sharded stacks) **lazily** and run
  registry ``prep`` hooks on concrete arrays.  Builders are mutable caches
  and are *not* pytrees — they are closed over by jitted code, never traced.

* ``PlanArtifact`` (from ``PlanBuilder.finalize(...)``) is the **frozen,
  jit-safe artifact**: a registered pytree whose leaves are the device
  arrays (substrates, gather/scatter maps, shard stacks) and whose static
  aux (``PlanMeta``) carries stats, thresholds, backend, ShardSpec, and the
  pattern-topology fingerprint.  Artifacts pass through ``jit``, ``scan``
  carries, donation, and ``shard_map``; two artifacts over the same sparsity
  topology produce equal treedefs, so they hit the same compiled executable.

* ``execute(plan_or_artifact, x)`` is the **online** step: select the
  logical kernel from (stats, N), resolve the physical implementation
  through the backend-aware registry, and run it through a custom VJP
  (``core/vjp.py``) covering all four logical kernels.

* ``execute_pattern(rows, cols, vals, shape, x)`` is the training entry:
  sparse-weight layers own a static pattern and a live value stream, with no
  CSR in sight — same registry, same VJP.

The supported front door for library consumers is ``repro.api`` (the
``sparse()`` facade + ``PlanCache``); this module is the engine room.
"""
from __future__ import annotations

import dataclasses
import functools
import hashlib
import inspect
from collections import OrderedDict
from typing import Any, Callable

import jax
import jax.numpy as jnp
import numpy as np

from repro.runtime.faults import consult

from . import guardrails
from . import quant as quant_mod
from . import registry
from .formats import (BSR, CSR, ELL, BalancedCOO, csr_to_balanced, csr_to_bsr,
                      csr_to_ell, row_ids_from_indptr)
from .guardrails import HEALTH, NumericFault
from .selector import (SelectorThresholds, TileGeometry, default_thresholds,
                       select_kernel)
from .stats import MatrixStats, matrix_stats
from .vjp import (FLAT_BACKWARD, PatternBackward,
                  _exec_attn, _exec_balanced, _exec_bsr,  # noqa: F401 (re-export)
                  _exec_chain, _exec_ell, _exec_sddmm, _stream_to_balanced)


# ---------------------------------------------------------------------------
# bound-kernel plumbing: identity-stable callables for the custom-VJP statics
# ---------------------------------------------------------------------------


class PlanBuildError(RuntimeError):
    """A substrate construction failed.  Wraps the original exception with
    the substrate kind and pattern shape so async plan prep (serve engine,
    background calibration) can log/classify the failure without holding a
    reference to the half-built plan; ``__cause__`` keeps the original."""

    def __init__(self, kind: str, shape, cause: BaseException):
        super().__init__(f"building substrate {kind!r} for pattern shape "
                         f"{tuple(shape)} failed: "
                         f"{type(cause).__name__}: {cause}")
        self.kind = kind
        self.shape = tuple(shape)

#: content-addressed store of host-side prep artifacts.  ``PlanArtifact``
#: references prep opts by digest (a hashable static) instead of carrying the
#: bound callable, so two artifacts built from equal-topology matrices
#: resolve to the *same* partial object — which is what keeps their custom-VJP
#: statics equal and their jitted executes on one compiled executable.
#:
#: Both stores are LRU-bounded so topology churn (e.g. long-running MoE
#: serving planning fresh dispatch patterns) cannot grow process memory
#: without bound.  Eviction is safe for kernels already bound (the partial
#: captured its opts); an artifact whose digest was evicted *and* never
#: bound for the requested interpret mode raises the re-finalize error in
#: ``_bound_kernel`` — hot topologies re-touch their entries and stay in.
_STORE_CAP = 4096
_OPTS_STORE: "OrderedDict[str, dict]" = OrderedDict()
_BIND_CACHE: "OrderedDict" = OrderedDict()


def _lru_touch(store: OrderedDict, key, value=None):
    if key in store:
        store.move_to_end(key)
        return store[key]
    if value is not None:
        store[key] = value
        while len(store) > _STORE_CAP:
            store.popitem(last=False)
    return value


def _digest_value(h, v) -> None:
    """Fold one prep-opt value into the hash; opts may nest tuples of arrays
    and scalars (the BSR block-ELL bundle does)."""
    if isinstance(v, (bool, int, float, str, bytes, type(None))):
        h.update(repr(v).encode())
    elif isinstance(v, (tuple, list)):
        h.update(b"(")
        for item in v:
            _digest_value(h, item)
        h.update(b")")
    elif isinstance(v, dict):
        h.update(b"{")
        for k in sorted(v):
            h.update(str(k).encode())
            _digest_value(h, v[k])
        h.update(b"}")
    else:
        arr = np.asarray(v)
        h.update(str(arr.dtype).encode() + repr(arr.shape).encode())
        h.update(arr.tobytes())


def _opts_digest(opts: dict) -> str:
    h = hashlib.sha1()
    for k in sorted(opts):
        h.update(k.encode())
        _digest_value(h, opts[k])
    return h.hexdigest()


def _register_opts(opts: dict) -> str:
    digest = _opts_digest(opts)
    if digest not in _OPTS_STORE:
        _lru_touch(_OPTS_STORE, digest, dict(opts))
    else:
        _OPTS_STORE.move_to_end(digest)
    return digest


#: accepted-keyword cache for prep hooks (see ``_prep_context_kwargs``)
_PREP_KWARGS: dict = {}


#: plan-context kwargs a prep hook may opt into by declaring them
_PREP_CONTEXT_NAMES = ("geometry", "overlap_min_n")


def _prep_context_kwargs(prep, ctx: dict) -> dict:
    """Filter the plan-context kwargs (autotuned geometry, guard thresholds,
    the sharded overlap cutoff) down to the ones this prep hook declares.
    Prep hooks keep the minimal ``prep(substrate)`` signature unless they opt
    into context — the Pallas NB prep takes ``geometry=``, the
    sharded prep additionally ``overlap_min_n=``, the BSR prep nothing — so
    the registry contract stays backward compatible."""
    accepted = _PREP_KWARGS.get(prep)
    if accepted is None:
        try:
            params = inspect.signature(prep).parameters.values()
            if any(p.kind is inspect.Parameter.VAR_KEYWORD for p in params):
                accepted = _PREP_CONTEXT_NAMES
            else:
                accepted = tuple(p.name for p in params
                                 if p.kind in (inspect.Parameter.KEYWORD_ONLY,
                                               inspect.Parameter.POSITIONAL_OR_KEYWORD)
                                 and p.name in _PREP_CONTEXT_NAMES)
        except (TypeError, ValueError):
            accepted = ()
        _PREP_KWARGS[prep] = accepted
    return {k: v for k, v in ctx.items() if k in accepted and v is not None}


def _bound_kernel(entry: registry.KernelEntry, interpret, digest: str | None):
    """Identity-cached ``partial(entry.fn, interpret=..., **opts)``."""
    key = (entry, interpret, digest)
    fn = _lru_touch(_BIND_CACHE, key)
    if fn is None:
        opts = {} if digest is None else _lru_touch(_OPTS_STORE, digest)
        if opts is None:
            raise KeyError(
                f"prep artifacts for digest {digest!r} are not in this "
                "process's opts store; re-finalize the plan to restore them")
        fn = functools.partial(entry.fn, interpret=interpret, **opts)
        _lru_touch(_BIND_CACHE, key, fn)
    return fn


def _quant_logical(name: str, quant: str | None) -> str:
    """Selector override for quantized plans: the coded value stream lives in
    the *balanced* substrate, which only the NB kernels read — an rs_* pick
    would silently execute the float ELL/CSR values and never touch the
    int8/fp8 stream.  Pin the workload-balanced family, keep the paper's
    SR/PR reduction choice."""
    if quant is None:
        return name
    return {"rs_sr": "nb_sr", "rs_pr": "nb_pr"}.get(name, name)


# ---------------------------------------------------------------------------
# the frozen artifact
# ---------------------------------------------------------------------------

@dataclasses.dataclass(frozen=True)
class PlanMeta:
    """Hashable static half of a ``PlanArtifact`` (the pytree aux data).

    Everything jit needs to key a compiled executable on: equal metas (plus
    equal leaf avals) ⇒ equal treedefs ⇒ one trace.  ``topology`` is the
    pattern fingerprint — matrices sharing a sparsity pattern share it, and
    since ``MatrixStats`` reads only the pattern, their whole metas match."""

    shape: tuple
    nnz: int
    backend: str
    stats: MatrixStats
    thresholds: SelectorThresholds
    tile: int
    bsr_block: tuple
    topology: str
    prep: tuple = ()                 # ((logical, opts digest), ...)
    shard_spec: Any = None
    mesh: Any = None
    inner_backend: str | None = None
    geometry: Any = None             # autotuned TileGeometry, or None
    quant: str | None = None         # value-stream quant mode ("int8"/"fp8")
    chain_op: str | None = None      # chain transform the plan was keyed for


@dataclasses.dataclass(frozen=True)
class PlanArtifact:
    """Immutable, jit-safe plan: device arrays as pytree leaves, ``PlanMeta``
    as static aux.  Round-trips ``jax.tree_util.tree_flatten``, rides ``jit``
    arguments, ``scan`` carries, and donation; ``execute(artifact, x)`` does
    zero host-side work."""

    substrates: dict[str, Any]       # substrate kind -> format pytree
    aux: dict[str, Any]              # gather/scatter maps (lens/src/bsr maps)
    meta: PlanMeta

    # -- conveniences -------------------------------------------------------
    @property
    def shape(self) -> tuple:
        return self.meta.shape

    @property
    def backend(self) -> str:
        return self.meta.backend

    @property
    def stats(self) -> MatrixStats:
        return self.meta.stats

    @property
    def thresholds(self) -> SelectorThresholds:
        return self.meta.thresholds

    @property
    def topology(self) -> str:
        return self.meta.topology

    def select(self, n: int) -> str:
        return _quant_logical(
            select_kernel(self.meta.stats, n, self.meta.thresholds),
            self.meta.quant)

    def __matmul__(self, x):
        return execute(self, x)


jax.tree_util.register_dataclass(PlanArtifact,
                                 data_fields=["substrates", "aux"],
                                 meta_fields=["meta"])


# ---------------------------------------------------------------------------
# the host-side builder
# ---------------------------------------------------------------------------

@dataclasses.dataclass
class PlanBuilder:
    """Host-side half of the offline/online split: statistics + thresholds +
    lazily-built substrates + prep-hook caches.

    Not a pytree — builders live on the host side and are closed over (not
    traced) by jitted execute calls.  ``finalize`` packs the built state into
    a frozen ``PlanArtifact`` for code that must carry the plan *through*
    transformations."""

    csr: CSR
    stats: MatrixStats
    thresholds: SelectorThresholds
    backend: str
    tile: int = 512
    bsr_block: tuple = (8, 128)
    # autotuned Pallas tile geometry (kernels/tune.py); None → kernel defaults
    geometry: TileGeometry | None = None
    # sharded backend (core/shard.py): the mesh, the stats-chosen partition
    # spec, and the single-device backend whose kernels run per shard
    mesh: Any = None
    shard_spec: Any = None
    inner_backend: str | None = None
    # value-stream quantization (DESIGN.md §8): "int8"/"fp8" quantize the
    # balanced-family substrate per nnz-tile; demoted to None (with a
    # warning) when any tile's dynamic range would collapse small entries
    quant: str | None = None
    # SDDMM→SpMM chain transform this plan is keyed for (DESIGN.md §9).
    # Purely a cache-segmentation tag: ``execute_chain`` takes the transform
    # per call, but cached plans for different chain ops must not alias
    # (their prep/bound caches hold transform-specific partials).
    chain_op: str | None = None
    # default numeric-sentinel policy for executes of this plan (DESIGN.md
    # §12): None defers to the per-call argument / the ambient
    # ``guardrails.sentinel_scope``; "raise" additionally turns the quant
    # dynamic-range demotion into a ``NumericFault``
    sentinel: str | None = None
    _substrates: dict = dataclasses.field(default_factory=dict, repr=False)
    _quant_scales: Any = dataclasses.field(default=None, repr=False)
    _opts: dict = dataclasses.field(default_factory=dict, repr=False)
    _bound: dict = dataclasses.field(default_factory=dict, repr=False)
    _ell_lens: Any = dataclasses.field(default=None, repr=False)
    _ell_src: Any = dataclasses.field(default=None, repr=False)
    _bsr_map: Any = dataclasses.field(default=None, repr=False)
    _bsr_brow: Any = dataclasses.field(default=None, repr=False)
    _topology: str | None = dataclasses.field(default=None, repr=False)

    # -- substrates ---------------------------------------------------------
    def substrate(self, kind: str):
        """Build-and-cache the named substrate. Only ever called for the
        format the resolved kernel consumes — the laziness contract.
        ``ensure_compile_time_eval`` keeps construction concrete (host-side)
        even when the first touch happens inside a jit trace of ``execute``."""
        sub = self._substrates.get(kind)
        if sub is None:
            consult("plan_build")    # scoped fault site (runtime/faults.py)
            try:
                sub = self._build_substrate(kind)
            except (ValueError, NumericFault):
                raise    # usage errors / sentinel raises keep their type
            except Exception as e:
                raise PlanBuildError(kind, self.csr.shape, e) from e
            self._substrates[kind] = sub
        return sub

    def _build_substrate(self, kind: str):
        with HEALTH.span("repro.plan.substrate", kind=kind), \
                jax.ensure_compile_time_eval():
            if kind == "ell":
                sub = csr_to_ell(self.csr)
            elif kind == "balanced":
                sub = csr_to_balanced(self.csr, tile=self.tile)
                if self.quant is not None:
                    # per-tile quantization with the dynamic-range
                    # fallback: a blown-up tile demotes the *whole plan*
                    # to the unquantized stream (partial quantization
                    # would split the bound-kernel static per tile)
                    if quant_mod.check_tile_range(sub.vals):
                        q, sc = quant_mod.quantize_stream(sub.vals,
                                                          self.quant)
                        sub = BalancedCOO(sub.rows, sub.cols, q,
                                          sub.shape)
                        self._quant_scales = sc
                    elif self.sentinel == "raise":
                        raise NumericFault(
                            "quantized value stream exceeds the per-tile "
                            f"dynamic range ({self.quant!r}); plan with "
                            "quant=None or sentinel!='raise' to demote "
                            "instead")
                    else:
                        HEALTH.bump("demote:quant_range")
                        self.quant = None
            elif kind == "bsr":
                sub = csr_to_bsr(self.csr, *self.bsr_block)
            elif kind in ("shard_ell", "shard_balanced"):
                if self.mesh is None or self.shard_spec is None:
                    raise ValueError(
                        "sharded substrates need a plan built with "
                        "mesh=... (plan(csr, backend='sharded', mesh=m))")
                from . import shard as shard_mod
                sub = shard_mod.place_substrate(
                    shard_mod.build_sharded_substrate(
                        self.csr, self.shard_spec, self.mesh,
                        inner_kind=kind[len("shard_"):], tile=self.tile,
                        inner_backend=(self.inner_backend
                                       or registry.default_backend()),
                        quant=self.quant))
                if (self.quant is not None and kind == "shard_balanced"
                        and sub.scales is None):
                    HEALTH.bump("demote:quant_range")
                    self.quant = None    # range fallback fired per shard
            else:
                raise ValueError(f"unknown substrate {kind!r}")
        return sub

    @property
    def built_substrates(self) -> tuple[str, ...]:
        return tuple(sorted(self._substrates))

    # -- selection ----------------------------------------------------------
    def select(self, n: int) -> str:
        return _quant_logical(select_kernel(self.stats, n, self.thresholds),
                              self.quant)

    def with_thresholds(self, th: SelectorThresholds) -> "PlanBuilder":
        """Same matrix and substrate caches, different decision thresholds.
        Prep opts bake thresholds-derived context (the sharded
        ``overlap_min_n``), so the opts cache resets along with the bound
        kernels — sharing it would serve opts built under the old cutoffs
        (and alias new ones back into the original plan)."""
        if th == self.thresholds:
            return self
        return dataclasses.replace(self, thresholds=th, _opts={}, _bound={})

    # -- topology -----------------------------------------------------------
    def topology_key(self) -> str:
        """Pattern fingerprint (``core/cache.py``'s, the one definition of
        "sparsity topology") folded with this plan's layout knobs, values
        excluded.  The artifact's ``meta.topology``.  Keyed on the current
        ``quant`` mode (it changes substrate dtypes, hence treedefs) and
        recomputed if the dynamic-range fallback demotes it."""
        if self._topology is None or self._topology[0] != self.quant:
            from .cache import pattern_fingerprint
            with jax.ensure_compile_time_eval():
                fp = pattern_fingerprint(self.csr)
            digest = hashlib.sha1(
                (fp + repr((self.tile, tuple(self.bsr_block),
                            self.geometry, self.quant))).encode()
            ).hexdigest()
            self._topology = (self.quant, digest)
        return self._topology[1]

    def quant_scales(self):
        """Per-tile f32 dequant scales of the baked quantized substrate
        (plan aux; ``None`` unless the plan quantized a balanced substrate)."""
        if self.quant is not None:
            self.substrate("balanced")
        return self._quant_scales

    # -- resolution ---------------------------------------------------------
    def entry(self, name: str, backend: str | None = None) -> registry.KernelEntry:
        return registry.resolve(name, backend or self.backend)

    def kernel_opts(self, entry: registry.KernelEntry) -> dict:
        """Host-side prep artifacts for this (entry, matrix) pair, cached.
        Runs the entry's ``prep`` hook on the concrete substrate once — this
        is what keeps ``execute`` traceable for Pallas backends.

        The substrate builds *before* the cache key is read: quantized plans
        may demote ``self.quant`` there (dynamic-range fallback), and the key
        must reflect the post-fallback mode."""
        sub = self.substrate(entry.substrate)
        key = (entry.logical, entry.backend, self.quant)
        opts = self._opts.get(key)
        if opts is None:
            consult("substrate_prep")    # scoped fault site
            if entry.prep is None:
                opts = {}
            else:
                ctx = _prep_context_kwargs(
                    entry.prep, {"geometry": self.geometry,
                                 "overlap_min_n": self.thresholds.overlap_min_n})
                with HEALTH.span("repro.plan.prep"), \
                        jax.ensure_compile_time_eval():
                    opts = dict(entry.prep(sub, **ctx))
            if self.quant is not None and entry.substrate == "balanced":
                # static mode flag for the kernel wrappers: baked substrates
                # already carry int8/fp8 vals (scales ride the execute-time
                # extras, see _run_entry); live streams re-quantize in graph
                opts["quant"] = self.quant
            self._opts[key] = opts
        return opts

    def bound_kernel(self, entry: registry.KernelEntry, interpret: bool | None):
        """A stable (identity-cached) callable with interpret + prep opts
        baked in — used as the hashable static of the shared custom VJPs, so
        repeated executes of the same plan do not retrace."""
        opts = self.kernel_opts(entry)   # may demote self.quant; run first
        key = (entry.logical, entry.backend, interpret, self.quant)
        fn = self._bound.get(key)
        if fn is None:
            fn = functools.partial(entry.fn, interpret=interpret, **opts)
            self._bound[key] = fn
        return fn

    # -- ELL value-override support -----------------------------------------
    def ell_lens(self):
        """(M,) valid-entries-per-row — the ELL padding mask, O(M) from the
        indptr.  Needed by every ELL-family execute (grad masking)."""
        if self._ell_lens is None:
            with jax.ensure_compile_time_eval():
                lens = np.diff(np.asarray(self.csr.indptr)).astype(np.int32)
                self._ell_lens = jnp.asarray(lens)
        return self._ell_lens

    def ell_src(self):
        """(M, width) gather map from the CSR nonzero stream into the ELL
        slab — ``ell_vals = where(valid, stream[src], 0)``.  Only the
        live-value-stream path pays for this (it is width/avg_row times the
        size of ``ell_lens``)."""
        if self._ell_src is None:
            ell = self.substrate("ell")
            with jax.ensure_compile_time_eval():
                indptr = np.asarray(self.csr.indptr)
                j = np.arange(ell.width, dtype=np.int64)[None, :]
                src = np.minimum(indptr[:-1, None] + j, max(self.csr.nnz - 1, 0))
                self._ell_src = jnp.asarray(src.astype(np.int32))
        return self._ell_src

    # -- BSR value-override / gradient support ------------------------------
    def bsr_map(self):
        """(3, nnz) scatter map from the CSR nonzero stream into block slots
        (block id, in-block row, in-block col) — same block ordering as
        ``csr_to_bsr`` (sorted unique block keys).  Lets a live value stream
        rebuild the dense blocks differentiably."""
        if self._bsr_map is None:
            with jax.ensure_compile_time_eval():
                indptr = np.asarray(self.csr.indptr)
                indices = np.asarray(self.csr.indices)
                bm, bk = self.bsr_block
                kb = -(-self.csr.shape[1] // bk)
                rows = row_ids_from_indptr(indptr, self.csr.nnz)
                key = (rows // bm).astype(np.int64) * kb + indices // bk
                _, inv = np.unique(key, return_inverse=True)
                self._bsr_map = jnp.asarray(np.stack(
                    [inv.astype(np.int32), (rows % bm).astype(np.int32),
                     (indices % bk).astype(np.int32)]))
        return self._bsr_map

    def bsr_brow(self):
        """(nblocks,) block-row id per materialized block."""
        if self._bsr_brow is None:
            bsr = self.substrate("bsr")
            with jax.ensure_compile_time_eval():
                self._bsr_brow = jnp.asarray(row_ids_from_indptr(
                    np.asarray(bsr.indptr), bsr.nblocks))
        return self._bsr_brow

    # -- freezing -----------------------------------------------------------
    def finalize(self, n: int | None = None, *, impl: str | None = None,
                 kernels: tuple | None = None) -> PlanArtifact:
        """Pack the plan into a frozen ``PlanArtifact``.

        The artifact carries the substrates (and gather/scatter aux maps) for
        the logical kernels named by ``kernels``, or for the single kernel
        the selector picks at ``n`` (/ forced by ``impl``).  With none of the
        three, the artifact covers the whole 2x2 space — eager by design:
        freezing *is* the end of the lazy phase.  Host prep runs here, never
        at execute time."""
        if kernels is None:
            if impl is not None:
                kernels = (impl,)
            elif n is not None:
                kernels = (self.select(n),)
            else:
                kernels = registry.MATMUL_KERNELS
        for name in kernels:
            if name in ("sddmm", "chain", "attn_chain"):
                raise ValueError(
                    f"{name!r} cannot be finalized into a PlanArtifact; use "
                    "execute_sddmm/execute_chain/execute_attention on the "
                    "PlanBuilder")
        subs: dict[str, Any] = {}
        aux: dict[str, Any] = {}
        prep: list = []
        for name in kernels:
            entry = self.entry(name)
            subs[entry.substrate] = self.substrate(entry.substrate)
            opts = self.kernel_opts(entry)
            if opts:
                prep.append((entry.logical, _register_opts(opts)))
            if entry.substrate == "ell":
                aux["ell_lens"] = self.ell_lens()
                aux["ell_src"] = self.ell_src()
            elif entry.substrate == "bsr":
                aux["bsr_map"] = self.bsr_map()
                aux["bsr_brow"] = self.bsr_brow()
        if "balanced" in subs and self._quant_scales is not None:
            aux["quant_scales"] = self._quant_scales
        meta = PlanMeta(
            shape=tuple(self.csr.shape), nnz=self.csr.nnz,
            backend=self.backend, stats=self.stats,
            thresholds=self.thresholds, tile=self.tile,
            bsr_block=tuple(self.bsr_block), topology=self.topology_key(),
            prep=tuple(sorted(prep)), shard_spec=self.shard_spec,
            mesh=self.mesh, inner_backend=self.inner_backend,
            geometry=self.geometry, quant=self.quant,
            chain_op=self.chain_op)
        return PlanArtifact(substrates=subs, aux=aux, meta=meta)


#: PR-1 name for the builder; kept as an alias so existing call sites and
#: type checks keep working (the class was renamed, not changed).
SparsePlan = PlanBuilder


@HEALTH.span("repro.plan")
def plan(csr: CSR, *, n_hint: int | None = None,
         thresholds: SelectorThresholds | None = None,
         backend: str | None = None, tile: int | None = None,
         bsr_block: tuple = (8, 128), mesh: Any = None,
         shard_axis: str | None = None, shard_kind: str | None = None,
         inner_backend: str | None = None,
         geometry: TileGeometry | None = None,
         quant: str | None = None,
         chain_op: str | None = None,
         validate: str | None = None,
         sentinel: str | None = None) -> PlanBuilder:
    """Offline planning front door.

    ``n_hint``: anticipated N of the dense operand; when given, the substrate
    for the kernel the selector will pick is built eagerly (prep off the hot
    path), everything else stays lazy.  ``thresholds=None`` auto-loads a
    persisted calibration (``$REPRO_THRESHOLDS``) or falls back to defaults;
    ``backend=None`` picks the scoped override (``repro.api.use_backend``)
    or the platform default (Pallas on TPU, XLA elsewhere) — or ``"sharded"``
    when a ``mesh`` is given.

    Tile geometry (DESIGN.md §6): ``geometry`` forces an explicit
    ``TileGeometry``; with ``geometry=None`` the thresholds' autotuned table
    is consulted per (pattern fingerprint, ``n_hint`` bucket, backend) —
    ``kernels/tune.py`` is the producer.  ``tile=None`` takes the geometry's
    nnz quota (default 512); an explicit ``tile`` always wins.  The fused
    Pallas kernels size their work per ``wb``-row output block, so a tile
    spanning a long empty-row gap costs only its own visits (an empty-block
    dummy visit each), never a window sized by the worst tile.

    Sharded backend: ``mesh`` (required) names the device mesh; the
    partitioner is chosen from the matrix stats (``cv`` vs.
    ``thresholds.partition_cv`` — row-split below, nnz-balanced above) unless
    ``shard_kind`` forces one; ``shard_axis`` defaults to the largest mesh
    axis and ``inner_backend`` to the platform default single-device
    backend whose kernels run per shard.

    ``quant`` (DESIGN.md §8): ``"int8"``/``"fp8"`` store the balanced-family
    value stream quantized per nnz-tile with in-kernel dequant.  Gated by
    ``thresholds.quant_min_n`` (below it the dequant ALU cost beats the byte
    savings, so the plan stays unquantized); per-tile dynamic-range blowups demote
    to unquantized at substrate-build time (``core/quant.check_tile_range``).

    ``chain_op`` (DESIGN.md §9) tags the plan with the SDDMM→SpMM chain
    transform it will serve — a cache-segmentation key for ``PlanCache``, not
    a behavioural switch (``execute_chain`` takes the transform per call).

    ``validate`` (DESIGN.md §12): ``"check"``/``"repair"``/``"strict"`` run
    the pattern through ``guardrails.validate_csr`` before any substrate is
    baked — warn about / fix / reject unsorted rows, duplicate or
    out-of-range indices, non-finite values, and indptr damage.  ``None``
    (or ``"off"``) trusts the input, matching prior behaviour.  ``sentinel``
    sets the plan's default numeric-sentinel policy for ``execute``."""
    if validate is not None and validate != "off":
        csr, _ = guardrails.validate_csr(csr, validate)
    if sentinel is not None and sentinel not in guardrails.SENTINEL_POLICIES:
        raise ValueError(f"unknown sentinel policy {sentinel!r}; expected "
                         f"one of {guardrails.SENTINEL_POLICIES}")
    if backend is None:
        backend = "sharded" if mesh is not None else registry.default_backend()
    th = thresholds if thresholds is not None else default_thresholds()
    if quant is not None:
        if quant not in quant_mod.QUANT_MODES:
            raise ValueError(f"unknown quant mode {quant!r}; expected one of "
                             f"{quant_mod.QUANT_MODES}")
        if n_hint is not None and n_hint < th.quant_min_n:
            quant = None    # selector crossover: not worth it at this N
    stats = matrix_stats(csr)
    if geometry is None and th.geometries:
        from .cache import pattern_fingerprint
        with jax.ensure_compile_time_eval():
            fp = pattern_fingerprint(csr)
        lookup_backend = inner_backend or backend
        if backend == "sharded" and inner_backend is None:
            lookup_backend = registry.default_backend()
        geometry = th.geometry_for(fp, n_hint, lookup_backend)
    if tile is None:
        tile = geometry.tile if geometry is not None else 512
    spec = None
    if backend == "sharded":
        if mesh is None:
            raise ValueError("backend='sharded' needs mesh=... "
                             "(e.g. repro.launch.mesh.make_local_mesh)")
        from . import shard as shard_mod
        spec = shard_mod.make_shard_spec(stats, mesh, axis=shard_axis,
                                         kind=shard_kind, thresholds=th)
    p = PlanBuilder(
        csr=csr,
        stats=stats,
        thresholds=th,
        backend=backend,
        tile=tile,
        bsr_block=tuple(bsr_block),
        geometry=geometry,
        mesh=mesh,
        shard_spec=spec,
        inner_backend=inner_backend,
        quant=quant,
        chain_op=chain_op,
        sentinel=sentinel,
    )
    if n_hint is not None:
        entry = p.entry(p.select(n_hint))
        p.substrate(entry.substrate)
        p.kernel_opts(entry)
    return p


# ---------------------------------------------------------------------------
# online front doors
# ---------------------------------------------------------------------------

def _run_entry(entry: registry.KernelEntry, sub, bound, x, vals, nnz: int,
               get_aux):
    """Family dispatch shared by the builder and artifact execute paths.
    ``get_aux(name)`` supplies the gather/scatter maps (lazily built on the
    builder, prebuilt leaves on the artifact)."""
    if not entry.differentiable:
        # forward-only physical path: values stay baked, gradients are not
        # defined through it.
        if vals is not None:
            raise ValueError(f"backend {entry.backend!r} does not support "
                             "live value streams; use xla/pallas")
        return bound(sub, x)

    if entry.substrate in ("shard_ell", "shard_balanced"):
        # shard_map wrapper (core/shard.py): the per-substrate-family VJPs
        # run per shard inside; a live stream scatters into the per-shard
        # value slabs through the substrate's src map (each nonzero lands in
        # exactly one shard slot, so the gather transpose partitions dvals).
        if vals is not None:
            # live streams stay float even when the baked slab is int8/fp8:
            # the inner kernel re-quantizes in graph (fresh per-tile scales)
            tgt = sub.vals.dtype
            if quant_mod.is_quantized_dtype(tgt):
                tgt = jnp.promote_types(vals.dtype, jnp.float32)
            if nnz == 0:
                v = jnp.zeros(sub.vals.shape, tgt)
            else:
                v = jnp.where(sub.src >= 0,
                              jnp.take(vals.reshape(-1),
                                       jnp.clip(sub.src, 0, nnz - 1)),
                              0).astype(tgt)
            sub = dataclasses.replace(sub, vals=v)
        return bound(sub, x)

    if entry.substrate == "bsr":
        # block-granule family: live streams rebuild the dense blocks via the
        # plan's scatter map (live=True re-pads them through the pattern-only
        # gather; baked values ride the prep-time blockell for free);
        # _exec_bsr carries the block-level custom VJP either way.
        if vals is None:
            blocks = sub.blocks
        else:
            bmap = get_aux("bsr_map")
            blocks = jnp.zeros(sub.blocks.shape, sub.blocks.dtype).at[
                bmap[0], bmap[1], bmap[2]].add(
                vals.reshape(-1).astype(sub.blocks.dtype))
            bound = functools.partial(bound, live=True)
        return _exec_bsr((bound, sub.shape, sub.block_shape), sub.indptr,
                         sub.indices, get_aux("bsr_brow"), blocks, x)

    if entry.substrate == "balanced":
        v = sub.vals if vals is None else _stream_to_balanced(vals, sub)
        extra = ()
        if vals is None and quant_mod.is_quantized_dtype(sub.vals.dtype):
            # baked quantized substrate: the per-tile scales (plan aux) ride
            # the custom-VJP extras so the backward pass can dequantize the
            # stream for dX (the kernels receive them positionally)
            extra = (get_aux("quant_scales"),)
        return _exec_balanced((bound, sub.shape), sub.rows, sub.cols,
                              v.reshape(-1), x, *extra)
    if entry.substrate == "ell":
        lens = get_aux("ell_lens")
        if vals is None:
            v = sub.vals
        elif nnz == 0:
            v = jnp.zeros(sub.vals.shape, sub.vals.dtype)
        else:
            valid = jnp.arange(sub.width, dtype=jnp.int32)[None, :] < lens[:, None]
            v = jnp.where(valid, jnp.take(vals.reshape(-1), get_aux("ell_src")), 0)
            v = v.astype(sub.vals.dtype)
        return _exec_ell((bound, sub.shape), sub.cols, lens, v, x)
    raise ValueError(f"substrate {entry.substrate!r} has no differentiable path")


def _demoted_inner(p: PlanBuilder) -> PlanBuilder:
    """The sharded plan's one rung down the degradation ladder: the same
    matrix / spec / mesh with the per-shard kernels demoted to the xla
    reference.  Cached on the parent (``_opts`` is a host-side cache dict);
    every mutable cache is replaced with a fresh one — ``dataclasses.replace``
    would otherwise *share* the dicts, and the demoted replica's shard
    substrates (inner_backend='xla') must not alias the parent's."""
    cached = p._opts.get(("demoted_inner",))
    if cached is None:
        cached = dataclasses.replace(
            p, inner_backend="xla", _substrates={}, _quant_scales=None,
            _opts={}, _bound={}, _ell_lens=None, _ell_src=None,
            _bsr_map=None, _bsr_brow=None, _topology=None)
        p._opts[("demoted_inner",)] = cached
    return cached


def _record_work(entry: registry.KernelEntry, sub, opts: dict, x,
                 topology: Callable[[], str], memo: dict | None = None
                 ) -> None:
    """Record in ``HEALTH`` the per-call work of the kernel ``entry`` binds
    (its ``work`` hook, on this call's schedule and operands), keyed by
    kernel name, plan topology and N.  ``memo`` (a builder's) keeps the
    count for the next call of the same kernel and operand shape."""
    if entry.work is None:
        return
    n = 1 if x.ndim == 1 else int(x.shape[1])
    key = (entry.logical, entry.backend, x.ndim, n)
    record = None if memo is None else memo.get(key)
    if record is None:
        kernel, work = entry.work(sub, x, **opts)
        topo = topology()
        record = (f"{kernel}:{topo[:12]}:n{n}",
                  dict(work, kernel=kernel, topology=topo, n=n))
        if memo is not None:
            memo[key] = record
    HEALTH.record_work(*record)


def _builder_exec(p: PlanBuilder, name: str, backend: str | None, x, vals,
                  interpret):
    """The unguarded builder dispatch: resolve → substrate → bind → run."""
    entry = p.entry(name, backend)
    sub = p.substrate(entry.substrate)
    bound = p.bound_kernel(entry, interpret)
    _record_work(entry, sub, p.kernel_opts(entry), x, p.topology_key,
                 p._opts.setdefault(("work",), {}))
    builder_aux = {"ell_lens": p.ell_lens, "ell_src": p.ell_src,
                   "bsr_map": p.bsr_map, "bsr_brow": p.bsr_brow,
                   "quant_scales": p.quant_scales}
    return _run_entry(entry, sub, bound, x, vals, p.csr.nnz,
                      lambda name: builder_aux[name]())


@HEALTH.span("repro.execute.trace")
def execute(p: "PlanBuilder | PlanArtifact", x: jax.Array, *,
            vals: jax.Array | None = None, impl: str | None = None,
            backend: str | None = None,
            interpret: bool | None = None,
            sentinel: str | None = None) -> jax.Array:
    """Run the planned SpMV/SpMM: ``y = A @ x``.

    Accepts a ``PlanBuilder`` (host object, closed over by jit) or a
    ``PlanArtifact`` (pytree, may itself be a traced jit/scan argument).
    Differentiable w.r.t. ``x`` and (when given) ``vals`` — a live CSR-ordered
    nonzero stream overriding the values baked into the plan's substrates,
    which is how trainable sparse weights ride the adaptive dispatch.  ``impl``
    forces a logical kernel (oracle / ablation mode); ``backend`` overrides
    the plan's backend for this call (builders only — artifacts are frozen
    per backend); ``interpret`` is forwarded to Pallas backends.

    Guardrails (DESIGN.md §12): the dispatch runs under the per-(backend,
    logical-kernel) circuit breaker — kernel failures re-route one rung down
    the demotion ladder (pallas/bsr→xla; sharded demotes its inner backend)
    and trip the breaker after repeated failures.  ``sentinel`` opts into
    post-execute non-finite detection (``"raise"``/``"sanitize"``/
    ``"fallback"``; default: the plan's ``sentinel`` or the ambient
    ``guardrails.sentinel_scope``)."""
    if impl in ("sddmm", "chain"):
        raise ValueError(f"impl {impl!r} takes dense operands, not a value "
                         "stream; use execute_sddmm / execute_chain")
    if isinstance(p, PlanArtifact):
        return _execute_artifact(p, x, vals=vals, impl=impl, backend=backend,
                                 interpret=interpret, sentinel=sentinel)
    if vals is not None and vals.size != p.csr.nnz:
        raise ValueError(f"vals stream has {vals.size} entries but the "
                         f"matrix has {p.csr.nnz} nonzeros")
    n = 1 if x.ndim == 1 else x.shape[1]
    name = impl or p.select(n)
    eff = backend or p.backend
    policy = (sentinel if sentinel is not None
              else (p.sentinel or guardrails.active_sentinel()))
    fb, fb_name = None, None
    if eff == "sharded":
        if (p.inner_backend or registry.default_backend()) != "xla":
            fb = lambda: _builder_exec(_demoted_inner(p), name, None,  # noqa: E731
                                       x, vals, interpret)
            fb_name = "sharded/xla-inner"
    else:
        demoted = registry.DEMOTION.get(eff)
        if demoted is not None:
            fb = lambda: _builder_exec(p, name, demoted, x, vals,  # noqa: E731
                                       interpret)
            fb_name = demoted
    y = guardrails.guarded_call(
        name, eff, lambda: _builder_exec(p, name, backend, x, vals, interpret),
        fallback=fb, fallback_name=fb_name)
    return guardrails.apply_sentinel(y, policy, site=f"execute:{name}",
                                     fallback=fb)


def _execute_artifact(art: PlanArtifact, x, *, vals, impl, backend, interpret,
                      sentinel=None):
    meta = art.meta
    if backend is not None and backend != meta.backend:
        raise ValueError(
            f"PlanArtifact is frozen for backend {meta.backend!r}; "
            f"finalize a plan built with backend={backend!r} instead")
    if vals is not None and vals.size != meta.nnz:
        raise ValueError(f"vals stream has {vals.size} entries but the "
                         f"matrix has {meta.nnz} nonzeros")
    n = 1 if x.ndim == 1 else x.shape[1]
    name = impl or select_kernel(meta.stats, n, meta.thresholds)
    entry = registry.resolve(name, meta.backend)
    sub = art.substrates.get(entry.substrate)
    if sub is None:
        raise ValueError(
            f"artifact carries substrates {tuple(art.substrates)} but kernel "
            f"{name!r} needs {entry.substrate!r}; finalize with n=/impl=/"
            "kernels= covering it")

    def run(entry_, sub_):
        digest = dict(meta.prep).get(entry_.logical)
        bound = _bound_kernel(entry_, interpret, digest)
        _record_work(entry_, sub_, {} if digest is None
                     else _OPTS_STORE.get(digest, {}), x,
                     lambda: meta.topology)
        return _run_entry(entry_, sub_, bound, x, vals, meta.nnz,
                          lambda name: art.aux[name])

    # artifacts are frozen: a rung down exists only when the fallback
    # backend's substrate was finalized in (xla consumes the same ell/
    # balanced formats pallas does, so 2x2 artifacts usually carry it)
    fb = None
    demoted = registry.DEMOTION.get(meta.backend)
    if demoted is not None:
        try:
            fbe = registry.resolve(name, demoted)
            fbs = art.substrates.get(fbe.substrate)
        except KeyError:
            fbs = None
        if fbs is not None and (fbe.differentiable or vals is None):
            fb = lambda: run(fbe, fbs)   # noqa: E731
    policy = sentinel if sentinel is not None else guardrails.active_sentinel()
    y = guardrails.guarded_call(name, meta.backend, lambda: run(entry, sub),
                                fallback=fb, fallback_name=demoted)
    return guardrails.apply_sentinel(y, policy, site=f"execute:{name}",
                                     fallback=fb)


# ---------------------------------------------------------------------------
# SDDMM + fused chain entries (DESIGN.md §9)
# ---------------------------------------------------------------------------

def _chain_pattern(p: PlanBuilder, entry: registry.KernelEntry):
    """The (rows, cols) pattern arrays the sddmm/chain custom VJPs take as
    primals.  Single-device: the balanced slab's arrays.  Sharded row-split
    substrates carry shard-*local* row ids (sentinel ``m_pad``), which would
    corrupt the flat segment-sum backward — lift them to global ids here
    (global = local + shard row offset; sentinel → ``m``); the sharded
    wrapper converts back to local inside ``shard_map``."""
    key = ("chain_pattern", entry.substrate)
    pat = p._opts.get(key)
    if pat is None:
        m = int(p.csr.shape[0])
        if entry.substrate == "shard_balanced":
            sub = p.substrate("shard_balanced")
            spec = sub.spec
            with jax.ensure_compile_time_eval():
                if spec.kind == "row":
                    rl = np.asarray(sub.rows).astype(np.int64)
                    offs = (np.arange(spec.n_shards, dtype=np.int64)
                            * spec.m_pad)[:, None, None]
                    rg = np.where(rl < spec.m_pad, rl + offs, m)
                    rows = jnp.asarray(rg.astype(np.int32))
                else:
                    rows = sub.rows    # nnz split: already global
            pat = (rows, sub.cols)
        else:
            sub = p.substrate("balanced")
            pat = (sub.rows, sub.cols)
        p._opts[key] = pat
    return pat


def _chain_bound(p: PlanBuilder, entry: registry.KernelEntry, interpret,
                 extra: dict):
    """Identity-cached partial for the sddmm/chain kernels: bakes interpret,
    the matrix shape, the per-call statics (transform/alpha) and the prep
    opts.  The quantized-plan mode flag is stripped — chains take dense
    operands, there is no value stream to decode."""
    opts = {k: v for k, v in p.kernel_opts(entry).items() if k != "quant"}
    key = (entry.logical, entry.backend, interpret,
           tuple(sorted(extra.items())))
    fn = p._bound.get(key)
    if fn is None:
        if entry.substrate.startswith("shard"):
            sub = p.substrate(entry.substrate)
            extra = dict(extra, mesh=p.mesh, spec=sub.spec,
                         inner_backend=extra.pop("inner_backend",
                                                 sub.inner_backend))
        fn = functools.partial(entry.fn, interpret=interpret,
                               shape=tuple(p.csr.shape), **extra, **opts)
        p._bound[key] = fn
    return fn


def _chain_fallback(p: PlanBuilder, backend: str, run, extra: dict):
    """One rung down the degradation ladder for the chain-family entries
    (sddmm / chain / attention), as a ``(thunk, name)`` pair for
    ``guardrails.guarded_call`` — ``(None, None)`` at the bottom.  Single-
    device accelerated backends re-resolve on their ``registry.DEMOTION``
    target; a sharded plan demotes its per-shard inner backend through the
    ``inner_backend`` extra (the shard substrate is inner-agnostic, so no
    rebuild).  ``run(backend, extra)`` is the caller's dispatch closure."""
    if backend == "sharded":
        if (p.inner_backend or registry.default_backend()) != "xla" \
                and extra.get("inner_backend") != "xla":
            return (lambda: run("sharded", dict(extra, inner_backend="xla")),
                    "sharded/xla-inner")
        return None, None
    demoted = registry.DEMOTION.get(backend)
    if demoted is None:
        return None, None
    return (lambda: run(demoted, dict(extra))), demoted


def execute_sddmm(p: PlanBuilder, a: jax.Array, b: jax.Array, *,
                  backend: str | None = None,
                  interpret: bool | None = None) -> jax.Array:
    """Sampled dense-dense matmul over the plan's pattern:
    ``e[i] = <A[row_i], B[col_i]>`` for every nonzero, returned as the
    CSR-ordered ``(nnz,)`` f32 edge-score stream.  Differentiable w.r.t.
    ``a`` and ``b`` (the backward is a pair of segment-sums over the same
    pattern — SpMM-shaped, per DESIGN.md §9)."""
    if isinstance(p, PlanArtifact):
        raise TypeError("execute_sddmm needs a PlanBuilder; PlanArtifacts "
                        "do not carry the chain kernels")
    m, k = (int(s) for s in p.csr.shape)
    a = jnp.asarray(a)
    b = jnp.asarray(b)
    if a.ndim != 2 or b.ndim != 2 or a.shape[1] != b.shape[1]:
        raise ValueError(f"sddmm needs A (m, d) and B (k, d); got "
                         f"{a.shape} and {b.shape}")
    if a.shape[0] != m or b.shape[0] != k:
        raise ValueError(f"operand rows {a.shape[0]}/{b.shape[0]} do not "
                         f"match the pattern shape {(m, k)}")
    eff = backend or p.backend

    def run(bk, ex):
        entry = p.entry("sddmm", bk)
        rows, cols = _chain_pattern(p, entry)
        bound = _chain_bound(p, entry, interpret, dict(ex))
        slab = _exec_sddmm((bound, (m, k)), rows, cols, a, b)
        nnz = p.csr.nnz
        if entry.substrate == "shard_balanced":
            # stacked per-shard slabs scatter back to the global stream
            # through the substrate's src map (each nonzero lands in
            # exactly one slot)
            sub = p.substrate("shard_balanced")
            src = sub.src.reshape(-1)
            e = jnp.where(src >= 0, slab.reshape(-1), 0.0)
            return jax.ops.segment_sum(e, jnp.where(src >= 0, src, nnz),
                                       num_segments=nnz + 1)[:nnz]
        # balanced tiling is row-major over the CSR stream: flatten-and-trim
        # restores CSR order
        return slab.reshape(-1)[:nnz]

    fb, fb_name = _chain_fallback(p, eff, run, {})
    return guardrails.guarded_call("sddmm", eff, lambda: run(backend, {}),
                                   fallback=fb, fallback_name=fb_name)


def execute_chain(p: PlanBuilder, a: jax.Array, b: jax.Array, x: jax.Array,
                  *, transform: str = "identity", alpha=None,
                  backend: str | None = None,
                  interpret: bool | None = None) -> jax.Array:
    """Fused SDDMM→``transform``→SpMM over the plan's pattern:
    ``y = T(mask(A @ B^T)) @ X`` where the mask is the sparsity pattern and
    ``T`` is identity / ``alpha``-scale / masked row softmax of
    ``alpha * scores``.  On the Pallas backend the edge scores never touch
    HBM (kernels/fused_chain.py); the xla lowering is the unfused two-kernel
    reference.  Differentiable w.r.t. ``a``, ``b`` and ``x`` — the backward
    is itself an SDDMM (for dW) plus segment-sums (core/vjp.py)."""
    if isinstance(p, PlanArtifact):
        raise TypeError("execute_chain needs a PlanBuilder; PlanArtifacts "
                        "do not carry the chain kernels")
    if transform not in ("identity", "scale", "softmax"):
        raise ValueError(f"unknown chain transform {transform!r}; expected "
                         "'identity', 'scale' or 'softmax'")
    m, k = (int(s) for s in p.csr.shape)
    a = jnp.asarray(a)
    b = jnp.asarray(b)
    x = jnp.asarray(x)
    if a.ndim != 2 or b.ndim != 2 or a.shape[1] != b.shape[1]:
        raise ValueError(f"chain needs A (m, d) and B (k, d); got "
                         f"{a.shape} and {b.shape}")
    if a.shape[0] != m or b.shape[0] != k:
        raise ValueError(f"operand rows {a.shape[0]}/{b.shape[0]} do not "
                         f"match the pattern shape {(m, k)}")
    if x.ndim not in (1, 2) or x.shape[0] != k:
        raise ValueError(f"chain needs X (k,) or (k, n) with k={k}; "
                         f"got {x.shape}")
    n = 1 if x.ndim == 1 else x.shape[1]
    backend = backend or p.backend
    al = None if alpha is None else float(alpha)
    extra: dict = {"transform": transform, "alpha": al}
    # fused-chain crossover (thresholds.chain_fuse_min_n): below the cutoff
    # the per-column-block score recompute costs more than the 2*nnz edge
    # bytes it saves, so run the unfused two-kernel xla reference instead
    if backend == "pallas" and n < p.thresholds.chain_fuse_min_n:
        HEALTH.bump("demote:chain_fuse")
        backend = "xla"
    elif backend == "sharded":
        inner = p.inner_backend or registry.default_backend()
        if inner == "pallas" and n < p.thresholds.chain_fuse_min_n:
            HEALTH.bump("demote:chain_fuse")
            extra["inner_backend"] = "xla"

    def run(bk, ex):
        entry = p.entry("chain", bk)
        rows, cols = _chain_pattern(p, entry)
        bound = _chain_bound(p, entry, interpret, dict(ex))
        return _exec_chain((bound, (m, k), transform, al), rows, cols, a, b, x)

    fb, fb_name = _chain_fallback(p, backend, run, extra)
    return guardrails.guarded_call("chain", backend,
                                   lambda: run(backend, extra),
                                   fallback=fb, fallback_name=fb_name)


def execute_attention(p: PlanBuilder, q: jax.Array, k: jax.Array,
                      v: jax.Array, *, scale: float | None = None,
                      bias: jax.Array | None = None,
                      backend: str | None = None,
                      interpret: bool | None = None) -> jax.Array:
    """Block-sparse attention over the plan's pattern (DESIGN.md §10):
    ``y = softmax_mask(scale * QK^T + bias) @ V`` where the mask is the
    sparsity pattern.  ``scale`` defaults to ``head_dim**-0.5``; ``bias``
    is an optional additive per-edge stream in CSR nonzero order (``(nnz,)``
    — relative-position / ALiBi hooks).  Without a bias this *is* the
    softmax chain, so it rides the ``chain`` registry entries — including
    the sharded cross-shard softmax merge; with a bias it dispatches the
    ``attn_chain`` kernels (fused Pallas / unfused XLA).  Differentiable
    w.r.t. ``q``, ``k``, ``v`` and ``bias``.  Rows the mask leaves empty
    produce exact-zero output rows."""
    if isinstance(p, PlanArtifact):
        raise TypeError("execute_attention needs a PlanBuilder; "
                        "PlanArtifacts do not carry the chain kernels")
    m, kdim = (int(s) for s in p.csr.shape)
    q = jnp.asarray(q)
    k = jnp.asarray(k)
    v = jnp.asarray(v)
    if q.ndim != 2 or k.ndim != 2 or q.shape[1] != k.shape[1]:
        raise ValueError(f"attention needs Q (m, d) and K (k, d); got "
                         f"{q.shape} and {k.shape}")
    if q.shape[0] != m or k.shape[0] != kdim:
        raise ValueError(f"operand rows {q.shape[0]}/{k.shape[0]} do not "
                         f"match the pattern shape {(m, kdim)}")
    if v.ndim not in (1, 2) or v.shape[0] != kdim:
        raise ValueError(f"attention needs V (k,) or (k, n) with k={kdim}; "
                         f"got {v.shape}")
    sc = float(q.shape[1]) ** -0.5 if scale is None else float(scale)
    backend = backend or p.backend
    # fused-attention crossover (thresholds.attn_fuse_min_seq): short
    # sequences amortize the visit-schedule setup poorly — run the unfused
    # xla reference below the cutoff
    extra: dict = {}
    if backend == "pallas" and m < p.thresholds.attn_fuse_min_seq:
        HEALTH.bump("demote:attn_fuse")
        backend = "xla"
    elif backend == "sharded":
        inner = p.inner_backend or registry.default_backend()
        if inner == "pallas" and m < p.thresholds.attn_fuse_min_seq:
            HEALTH.bump("demote:attn_fuse")
            extra["inner_backend"] = "xla"
    if bias is None:
        # softmax chain with alpha = scale: reuse the chain entries (the
        # sharded one merges softmax stats across shards — grad-exact)
        def run(bk, ex):
            entry = p.entry("chain", bk)
            rows, cols = _chain_pattern(p, entry)
            bound = _chain_bound(p, entry, interpret,
                                 dict(ex, transform="softmax", alpha=sc))
            return _exec_chain((bound, (m, kdim), "softmax", sc),
                               rows, cols, q, k, v)

        fb, fb_name = _chain_fallback(p, backend, run, extra)
        return guardrails.guarded_call("chain", backend,
                                       lambda: run(backend, extra),
                                       fallback=fb, fallback_name=fb_name)
    if backend == "sharded":
        raise NotImplementedError(
            "sharded block-sparse attention does not support an additive "
            "bias stream yet; supported alternatives: (1) keep the bias and "
            "run unsharded — execute_attention(p, ..., backend='pallas') or "
            "'xla' on a single-device plan over the same pattern, or (2) "
            "keep the sharded plan and drop bias= (the no-bias path rides "
            "the sharded softmax chain, cross-shard merge included)")
    bias = jnp.asarray(bias)
    if bias.ndim != 1 or bias.shape[0] != p.csr.nnz:
        raise ValueError(f"bias must be a flat ({p.csr.nnz},) per-edge "
                         f"stream in CSR order; got {bias.shape}")
    # the flat stream rides the balanced slab layout (pure pad+reshape, so
    # the bias cotangent flows back to the flat stream automatically)
    slab = _stream_to_balanced(bias.astype(jnp.float32),
                               p.substrate("balanced"))

    def run_attn(bk, ex):
        entry = p.entry("attn_chain", bk)
        rows, cols = _chain_pattern(p, entry)
        bound = _chain_bound(p, entry, interpret, dict(ex, scale=sc))
        return _exec_attn((bound, (m, kdim), sc), rows, cols, q, k, slab, v)

    fb, fb_name = _chain_fallback(p, backend, run_attn, extra)
    return guardrails.guarded_call("attn_chain", backend,
                                   lambda: run_attn(backend, extra),
                                   fallback=fb, fallback_name=fb_name)


# module-level bound-kernel cache for the plan-free training entry
_PATTERN_BOUND: dict = {}
_PATTERN_BWD: dict = {}

#: prefix of the prep entries that describe the pattern's transpose
TRANSPOSE = "transpose_"
#: the transpose's arrays, in the order the kernel backward takes them; its
#: visit schedule follows, under ``TRANSPOSE`` + the forward schedule's keys
TRANSPOSE_ARRAYS = ("perm", "rows", "cols")


@dataclasses.dataclass(frozen=True)
class PatternWork:
    """What the kernels of one (stacked) pattern do per call, counted on the
    host when ``pattern_prep`` builds its schedules, and recorded in
    ``HEALTH`` (``<kernel>:<topology[:12]>:n<N>``) when a call binds them.

    ``forward`` and ``transpose``: the fused SpMM's work hook
    (``kernels/vsr._nb_work``) over the forward and the transposed
    schedules at one column block, summed over the stacked layers, as
    ``(key, value)`` pairs; at the call's N, ``column_blocks`` is the
    kernel's (``kernels/vsr.gather_width``) and ``rows_gathered`` scales
    with it.  ``tiles``: the forward's tiles over all layers, which
    the SDDMM of the value gradient loads once per feature block.
    ``tile`` and ``wb``: the geometry the kernels run at.  The
    topologies are digests of the stacked forward and transposed index
    streams, so the two SpMMs keep records of their own."""
    kernel: str
    topology: str
    forward: tuple
    transpose_topology: str
    transpose: tuple
    tiles: int
    tile: int
    wb: int

    def _record(self, kernel, topology, work, n):
        HEALTH.record_work(f"{kernel}:{topology[:12]}:n{n}",
                           dict(work, kernel=kernel, topology=topology, n=n))

    def _spmm(self, counts, n):
        from repro.kernels.vsr import gather_width
        blocks = -(-n // gather_width(self.tile, n, self.wb))
        work = dict(counts, column_blocks=blocks)
        work["rows_gathered"] *= blocks
        return work

    def record_forward(self, n: int) -> None:
        self._record(self.kernel, self.topology,
                     self._spmm(self.forward, n), n)

    def record_backward(self, n: int) -> None:
        """The kernels of the value and input gradients: the SDDMM on the
        forward's tiles and the SpMM over the transposed pattern."""
        from repro.kernels.fused_chain import sddmm_work
        kernel, work = sddmm_work(
            jax.ShapeDtypeStruct((self.tiles, self.tile), jnp.int32),
            jax.ShapeDtypeStruct((1, n), jnp.float32))
        self._record(kernel, self.topology, work, n)
        self._record(self.kernel, self.transpose_topology,
                     self._spmm(self.transpose, n), n)


def transpose_pattern(rows, cols, shape: tuple) -> tuple:
    """Host side: the balanced layout of ``Aᵀ`` for a balanced pattern of
    ``A`` (``(n_tiles, T)`` rows and cols; sentinel rows ``>= m``).

    Returns ``(perm, rows_t, cols_t)``, each ``(n_tiles_t, T)`` int32: the
    transposed stream holds the nonzeros ordered by column, then row (a
    row-ordered stream of ``Aᵀ``, as ``plan_visits`` needs), and its
    values are ``vals.reshape(-1)[perm]``; padding entries carry the
    sentinel row ``k`` and point at entry 0."""
    m, k = shape
    r = np.asarray(rows).reshape(-1)
    c = np.asarray(cols).reshape(-1)
    t = np.shape(rows)[-1]
    valid = np.flatnonzero(r < m)
    # the stream is row-ordered, so a stable sort on the column gives
    # (column, row) order
    order = valid[np.argsort(c[valid], kind="stable")]
    n_tiles = max(1, -(-len(order) // t))
    pad = n_tiles * t - len(order)
    perm = np.concatenate([order, np.zeros(pad, np.int64)]).astype(np.int32)
    rows_t = np.concatenate([c[order], np.full(pad, k)]).astype(np.int32)
    cols_t = np.concatenate([r[order], np.zeros(pad)]).astype(np.int32)
    return (perm.reshape(n_tiles, t), rows_t.reshape(n_tiles, t),
            cols_t.reshape(n_tiles, t))


def pattern_prep(rows, cols, shape: tuple) -> dict:
    """Host-side prep of a balanced pattern for ``execute_pattern(prep=...)``
    on the Pallas backend: the fused SpMM's visit schedule (default
    ``TileGeometry``) and the transposed pattern its backward runs on.

    ``rows``/``cols``: ``(n_tiles, T)``, or ``(L, n_tiles, T)`` for ``L``
    patterns of one shape stacked on a leading axis (the scanned layers of
    a model): every array of the result then carries that axis too, the
    visit schedules padded to one length with no-op visits, so a layer's
    slice is its own prep.  Entries: ``visit_tile``, ``visit_block``,
    ``visit_start`` (arrays), ``wb`` (the geometry's; the column blocks
    follow the call's N), ``work``
    (a ``PatternWork``), ``transpose_perm``, ``transpose_rows``,
    ``transpose_cols`` and ``transpose_visit_*`` — ``transpose_pattern``
    and the transposed pattern's schedule, built here once (span
    ``repro.plan.transpose``) so the custom VJP never builds it.
    Array entries are device arrays; pass them as call operands (a scan's
    ``xs``), not as constants."""
    from repro.kernels.vsr import _nb_work, plan_visits
    from .shard import stack_visit_schedules
    geom = TileGeometry()
    m, k = (int(d) for d in shape)
    rows_np, cols_np = np.asarray(rows), np.asarray(cols)
    stacked = rows_np.ndim == 3
    if not stacked:
        rows_np, cols_np = rows_np[None], cols_np[None]
    t = rows_np.shape[-1]

    def schedule(r, c, shp):
        bal = BalancedCOO(r, c, None, shp)
        vt, vb, vs = plan_visits(bal, geom.wb)
        # at 128 columns: one column block
        kernel, work = _nb_work(
            bal, jax.ShapeDtypeStruct((shp[1], 128), jnp.float32),
            visit_tile=vt, visit_block=vb, wb=geom.wb)
        return (vt, vb, vs), kernel, work

    def summed(works):
        # counts add up over the layers; the issue loop's unroll and the
        # column blocks are the kernel's own
        return tuple((key, works[0][key]
                      if key in ("gather_unroll", "column_blocks")
                      else sum(w[key] for w in works)) for key in works[0])

    def device(a):
        return jnp.asarray(a if stacked else a[0])

    fwd = [schedule(r, c, (m, k)) for r, c in zip(rows_np, cols_np)]
    prep = {f"visit_{name}": device(a) for name, a in zip(
        ("tile", "block", "start"),
        stack_visit_schedules([s for s, _, _ in fwd]))}
    prep["wb"] = geom.wb
    with HEALTH.span("repro.plan.transpose"):
        trans = [transpose_pattern(r, c, (m, k))
                 for r, c in zip(rows_np, cols_np)]
        # layers of different nnz: pad each to the most tiles with
        # sentinel-only tiles (no visit touches them)
        most = max(tr[0].shape[0] for tr in trans)
        trans = {name: np.stack([np.pad(tr[i], ((0, most - len(tr[i])),
                                                (0, 0)), constant_values=fill)
                                 for tr in trans])
                 for i, (name, fill) in enumerate(zip(TRANSPOSE_ARRAYS,
                                                      (0, k, 0)))}
    tsched = [schedule(rt, ct, (k, m))
              for rt, ct in zip(trans["rows"], trans["cols"])]
    for name, a in trans.items():
        prep[TRANSPOSE + name] = device(a)
    for name, a in zip(("tile", "block", "start"), stack_visit_schedules(
            [s for s, _, _ in tsched])):
        prep[f"{TRANSPOSE}visit_{name}"] = device(a)
    prep["work"] = PatternWork(
        kernel=fwd[0][1],
        topology=_opts_digest({"rows": rows_np, "cols": cols_np}),
        forward=summed([w for _, _, w in fwd]),
        transpose_topology=_opts_digest({"rows": trans["rows"],
                                         "cols": trans["cols"]}),
        transpose=summed([w for _, _, w in tsched]),
        tiles=int(np.prod(rows_np.shape[:2])), tile=t, wb=geom.wb)
    return prep


def _pattern_backward(entry, interpret, statics: dict, keys: tuple, work):
    """The identity-cached ``vjp.PatternBackward`` of a Pallas-backed
    pattern call whose prep carries the transpose: the value gradient on
    the SDDMM of the same backend, the input gradient on the forward's own
    kernel over the transposed pattern (bound without ``quant``: the
    backward sees the live float values)."""
    from .shard import _make_inner
    key = (entry, interpret, tuple(sorted(statics.items())), keys, work)
    kb = _PATTERN_BWD.get(key)
    if kb is None:
        if len(_PATTERN_BWD) >= 256:
            _PATTERN_BWD.clear()
        plain = {s: v for s, v in statics.items() if s != "quant"}
        kb = _PATTERN_BWD[key] = PatternBackward(
            len(keys), _make_inner(entry, interpret, plain, keys),
            functools.partial(registry.resolve("sddmm", entry.backend).fn,
                              interpret=interpret), work)
    return kb


def execute_pattern(rows: jax.Array, cols: jax.Array, vals: jax.Array,
                    shape: tuple, x: jax.Array, *, impl: str = "nb_pr",
                    backend: str | None = None,
                    interpret: bool | None = None,
                    mesh: Any = None,
                    shard_axis: str | None = None,
                    quant: str | None = None,
                    prep: dict | None = None) -> jax.Array:
    """Differentiable SpMM over a bare BalancedCOO-layout pattern — the
    training entry for sparse-weight layers (no CSR, values are live params).
    rows/cols may be traced (scanned per-layer patterns); they are real args
    with float0 cotangents.  A kernel that needs host-side prep (the Pallas
    NB visit schedule) cannot run its prep on a traced pattern: pass the
    pattern's precomputed prep opts as ``prep`` — their array entries ride
    the call as (possibly traced) kernel operands, the rest bind statically.
    Without ``prep`` a traced pattern falls back to the XLA reference and
    bumps the ``demote:traced_pattern_to_xla`` health counter.

    With a ``prep`` that carries the pattern's transpose (``pattern_prep``)
    the backward runs on the kernels too: the value gradient is the SDDMM of
    the cotangent and ``x`` on the forward's tiles, the input gradient the
    same SpMM over the transposed pattern (``core/vjp.PatternBackward``),
    and no intermediate holds ``nnz × N`` elements.  Any other
    Pallas-backed call takes the flat ``_coo_bwd`` and bumps
    ``demote:coo_bwd``.

    ``mesh`` (or ``backend="sharded"``) routes through the sharded backend:
    the pattern's tiles — already fixed-nnz quotas — split evenly across
    ``shard_axis`` and partials psum (core/shard.py).

    ``quant`` ("int8"/"fp8", DESIGN.md §8) re-quantizes the live value stream
    in graph with fresh per-tile scales, so only the narrow stream crosses
    HBM into the kernel — the same coded substrates ``plan(quant=...)``
    reaches, without a plan.  rs_* picks are pinned to their nb_* siblings
    (the coded stream lives in the balanced layout)."""
    if quant is not None:
        if quant not in quant_mod.QUANT_MODES:
            raise ValueError(f"unknown quant mode {quant!r}; expected one of "
                             f"{quant_mod.QUANT_MODES}")
        impl = _quant_logical(impl, quant)
    if mesh is not None or backend == "sharded":
        if mesh is None:
            raise ValueError("backend='sharded' needs mesh=...")
        from . import shard as shard_mod
        return shard_mod.execute_pattern_sharded(
            rows, cols, vals, tuple(shape), x, mesh=mesh, axis=shard_axis,
            impl=impl, interpret=interpret,
            backend=None if backend == "sharded" else backend, quant=quant)
    explicit = backend is not None
    backend = backend or registry.default_backend()
    entry = registry.resolve(impl, backend)
    if entry.substrate != "balanced":
        raise ValueError(f"execute_pattern needs a balanced-substrate kernel; "
                         f"({impl!r}, {backend!r}) consumes {entry.substrate!r}")
    if prep is not None and entry.prep is not None:
        from .shard import _make_inner
        work = prep.get("work")
        fwd = {k: v for k, v in prep.items()
               if k != "work" and not k.startswith(TRANSPOSE)}
        keys = tuple(sorted(k for k, v in fwd.items() if hasattr(v, "shape")))
        statics = {k: v for k, v in fwd.items() if k not in keys}
        if quant is not None:
            statics["quant"] = quant
        bound = _make_inner(entry, interpret, statics, keys)
        tkeys = tuple(TRANSPOSE + k for k in TRANSPOSE_ARRAYS + keys)
        transposed = all(k in prep for k in tkeys)
        if work is not None and x.ndim == 2:
            work.record_forward(int(x.shape[1]))
        extra = [prep[k] for k in keys]
        if transposed:
            kb = _pattern_backward(entry, interpret, statics, keys, work)
            extra += [prep[k] for k in tkeys]
        else:
            kb = FLAT_BACKWARD
        return _exec_balanced((bound, tuple(shape), kb), rows, cols,
                              vals.reshape(-1), x, *extra)
    if entry.prep is not None and isinstance(rows, jax.core.Tracer) and not explicit:
        # scanned per-layer patterns are traced and no prep was handed in;
        # the XLA reference can always take them, so fall back rather than
        # fail the train step — visibly, on the health counters.
        HEALTH.bump("demote:traced_pattern_to_xla")
        backend, entry = "xla", registry.resolve(impl, "xla")
    if entry.prep is not None:
        if isinstance(rows, jax.core.Tracer):
            raise ValueError(
                f"backend {backend!r} needs host-side prep ({impl!r}) and "
                "cannot take a traced pattern; pass concrete rows/cols or "
                "use backend='xla'")
        # key prep artifacts by pattern *content* — an id()-based key can be
        # reused by a new array after GC and serve stale visit schedules
        with jax.ensure_compile_time_eval():
            r = np.asarray(rows)
        digest = hashlib.sha1(r.tobytes()).hexdigest()
        key = (entry, interpret, quant, tuple(shape), r.shape, digest)
    else:
        key = (entry, interpret, quant)
    bound = _PATTERN_BOUND.get(key)
    if bound is None:
        if len(_PATTERN_BOUND) >= 256:   # bound the per-pattern cache
            _PATTERN_BOUND.clear()
        opts = {}
        if entry.prep is not None:
            opts = dict(entry.prep(BalancedCOO(
                rows, cols, jnp.zeros(rows.shape, vals.dtype), tuple(shape))))
        if quant is not None:
            # live-stream mode flag: the kernel wrappers quantize in graph
            opts["quant"] = quant
        bound = functools.partial(entry.fn, interpret=interpret, **opts)
        _PATTERN_BOUND[key] = bound
    static = (bound, tuple(shape))
    if entry.backend == "pallas":
        # no transpose was prepared for this call: the backward is the flat
        # one, and says so on the ``demote:coo_bwd`` counter
        static += (FLAT_BACKWARD,)
    return _exec_balanced(static, rows, cols, vals.reshape(-1), x)
