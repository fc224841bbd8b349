"""Topology-keyed plan caching: the online half of offline-plan/online-execute.

Input-dynamic workloads (MoE routing per decode tick, streaming graphs)
re-present the *same* sparsity topology far more often than they present a
new one — Hu et al. (arXiv:2202.08556, PAPERS.md) make exactly this point:
the dispatch decision must be a cheap reusable artifact, not a per-call
recomputation.  ``PlanCache`` is that artifact store: a bounded LRU mapping

    (pattern fingerprint, shape, backend, mesh signature, thresholds, ...)

to whatever the builder closure produces — a ``PlanBuilder``, a
``PlanArtifact``, or a backend-specific bundle (the serve engine stores MoE
dispatch/combine artifact pairs).  Hit/miss/eviction/build counters make
reuse observable: the serve regression tests assert *zero* new plan
constructions across decode ticks with a repeated expert topology, and the
``plan_cache`` micro-benchmark reports reuse vs re-plan per tick.
"""
from __future__ import annotations

import dataclasses
import hashlib
import threading
from collections import OrderedDict
from typing import Any, Callable, Optional

import numpy as np

from .formats import CSR
from .guardrails import HEALTH, plan_digest, validate_csr
from .selector import SelectorThresholds


# ---------------------------------------------------------------------------
# key components
# ---------------------------------------------------------------------------

@HEALTH.span("repro.plan.fingerprint")
def pattern_fingerprint(csr: CSR) -> str:
    """Sparsity-topology digest of a CSR: pattern + shape, values excluded —
    matrices differing only in values share a fingerprint (and a plan; value
    streams ride ``execute(vals=...)``)."""
    h = hashlib.sha1()
    h.update(np.asarray(csr.indptr).tobytes())
    h.update(np.asarray(csr.indices).tobytes())
    h.update(repr(tuple(csr.shape)).encode())
    return h.hexdigest()


def mesh_signature(mesh) -> Optional[tuple]:
    """Hashable identity of a device mesh (axis names, extents, device ids);
    None for single-device plans."""
    if mesh is None:
        return None
    return (tuple(mesh.axis_names),
            tuple(int(mesh.shape[a]) for a in mesh.axis_names),
            tuple(int(d.id) for d in np.asarray(mesh.devices).reshape(-1)))


def thresholds_version(th: Optional[SelectorThresholds]) -> tuple:
    """The thresholds' contribution to the key: recalibration must invalidate
    cached plans (their selector decisions are baked into artifacts).
    ``astuple`` folds in *every* field — including the v2 additions (the
    sharded overlap cutoff ``overlap_min_n``, the geometry table) — so a
    retuned overlap crossover or geometry invalidates exactly the plans
    whose prep opts it changes."""
    if th is None:
        return ()
    return dataclasses.astuple(th)


def plan_key(csr: CSR, *, backend: str, mesh=None,
             thresholds: SelectorThresholds | None = None,
             tile: int | None = None, bsr_block: tuple = (8, 128),
             extra: tuple = ()) -> tuple:
    """The canonical cache key for a ``plan()`` call.

    ``tile=None`` means "resolve from the thresholds' geometry table": with
    an empty table the resolution is always 512, so it keys as 512 (keeping
    auto and explicit-default spellings on one entry); with a non-empty
    table it keys as ``"auto"`` — the resolved quota is then a function of
    the thresholds, which are already in the key, so two auto-tiled calls
    with equal thresholds resolve identically.  An explicit geometry rides
    ``extra`` (``cached_plan`` forwards it with the other plan kwargs):
    distinct geometries ⇒ distinct entries, same geometry ⇒ a cache hit —
    the observability contract of the autotuner."""
    if tile is None and not (thresholds is not None and thresholds.geometries):
        tile = 512
    return ("plan", pattern_fingerprint(csr), tuple(csr.shape), backend,
            mesh_signature(mesh), thresholds_version(thresholds),
            "auto" if tile is None else int(tile), tuple(bsr_block), extra)


# ---------------------------------------------------------------------------
# the cache
# ---------------------------------------------------------------------------

class PlanCache:
    """Bounded-LRU store of plan artifacts with observable counters.

    ``get_or_build(key, build)`` is the one entry point: on a miss the
    ``build`` thunk runs (counted in ``builds``) and the result is inserted,
    evicting the least-recently-used entry past ``capacity``.  Thread-safe —
    the serve engine and a background calibration job may share one cache.

    Integrity (DESIGN.md §12): every entry is stored alongside a content
    digest (``guardrails.plan_digest``).  ``integrity="publish"`` (default)
    verifies an *existing* entry when a racing ``put_built`` re-publishes its
    key — a corrupted first copy is replaced instead of kept; ``"hit"``
    additionally verifies on every ``get``/``get_or_build`` hit, so a stale
    or mutated cached plan is dropped and rebuilt, never executed.
    Mismatches are counted in ``digest_mismatches``.  ``"off"`` skips
    digesting entirely.
    """

    def __init__(self, capacity: int = 128, *, integrity: str = "publish"):
        if capacity < 1:
            raise ValueError(f"capacity must be >= 1, got {capacity}")
        if integrity not in ("off", "publish", "hit"):
            raise ValueError(f"unknown integrity policy {integrity!r}; "
                             "expected 'off', 'publish' or 'hit'")
        self.capacity = capacity
        self.integrity = integrity
        self._entries: OrderedDict = OrderedDict()   # key -> (value, digest)
        self._lock = threading.RLock()
        self.hits = 0
        self.misses = 0
        self.evictions = 0
        self.builds = 0
        self.digest_mismatches = 0

    def _digest(self, value):
        return None if self.integrity == "off" else plan_digest(value)

    def _verify_hit(self, key) -> bool:
        """Under ``integrity="hit"``: drop-and-report a corrupted entry.
        Caller holds the lock.  Returns whether the entry survived."""
        if self.integrity != "hit":
            return True
        value, digest = self._entries[key]
        if plan_digest(value) == digest:
            return True
        self.digest_mismatches += 1
        del self._entries[key]
        return False

    def __len__(self) -> int:
        return len(self._entries)

    def __contains__(self, key) -> bool:
        with self._lock:
            return key in self._entries

    def get(self, key, default=None):
        """Peek + LRU-touch without building; counts a hit or a miss (a
        corrupted entry under ``integrity="hit"`` is dropped and missed)."""
        with self._lock:
            if key in self._entries and self._verify_hit(key):
                self.hits += 1
                self._entries.move_to_end(key)
                return self._entries[key][0]
            self.misses += 1
            return default

    def get_or_build(self, key, build: Callable[[], Any]):
        """Return the cached value for ``key``, building (and counting) it on
        a miss.  ``build`` runs outside the lock-held fast path but inside
        the lock overall — plan construction is host-side and the engine's
        per-tick caller is single-threaded; contention is the rare case.
        Under ``integrity="hit"`` a corrupted entry is rebuilt, never
        returned."""
        with self._lock:
            if key in self._entries and self._verify_hit(key):
                self.hits += 1
                self._entries.move_to_end(key)
                return self._entries[key][0]
            self.misses += 1
            value = build()
            self.builds += 1
            self._entries[key] = (value, self._digest(value))
            while len(self._entries) > self.capacity:
                self._entries.popitem(last=False)
                self.evictions += 1
            return value

    def put(self, key, value) -> None:
        with self._lock:
            self._entries[key] = (value, self._digest(value))
            self._entries.move_to_end(key)
            while len(self._entries) > self.capacity:
                self._entries.popitem(last=False)
                self.evictions += 1

    def put_built(self, key, value) -> None:
        """Publish a value that was *built outside the lock* (background plan
        prep: ``get_or_build`` holds the lock for the build's duration, which
        would stall every tick-side cache read behind a slow worker build —
        so workers build privately and the scheduler swaps the artifact in
        here).  Counts as a build; a racing duplicate keeps the first copy so
        compiled steps already closed over it stay valid — unless the first
        copy fails its digest check (``integrity`` != "off"), in which case
        the corrupted entry is replaced by the fresh build."""
        with self._lock:
            self.builds += 1
            if key in self._entries:
                old, digest = self._entries[key]
                if (self.integrity == "off"
                        or plan_digest(old) == digest):
                    self._entries.move_to_end(key)
                    return
                self.digest_mismatches += 1
            self._entries[key] = (value, self._digest(value))
            self._entries.move_to_end(key)
            while len(self._entries) > self.capacity:
                self._entries.popitem(last=False)
                self.evictions += 1

    def clear(self) -> None:
        """Drop entries; counters survive (they describe lifetime traffic)."""
        with self._lock:
            self._entries.clear()

    def reset_stats(self) -> None:
        with self._lock:
            self.hits = self.misses = self.evictions = self.builds = 0
            self.digest_mismatches = 0

    def stats(self) -> dict:
        with self._lock:
            return {"hits": self.hits, "misses": self.misses,
                    "evictions": self.evictions, "builds": self.builds,
                    "digest_mismatches": self.digest_mismatches,
                    "size": len(self._entries), "capacity": self.capacity}

    def __repr__(self) -> str:
        s = self.stats()
        return (f"PlanCache(size={s['size']}/{s['capacity']}, "
                f"hits={s['hits']}, misses={s['misses']}, "
                f"evictions={s['evictions']}, builds={s['builds']})")


#: process-default cache used by the ``repro.api`` facade.
DEFAULT_CACHE = PlanCache()


@HEALTH.span("repro.plan.cache")
def cached_plan(csr: CSR, *, cache: PlanCache | None = None,
                backend: str | None = None,
                thresholds: SelectorThresholds | None = None,
                mesh=None, tile: int | None = None,
                bsr_block: tuple = (8, 128),
                validate: str | None = None,
                **plan_kwargs):
    """``plan()`` through a ``PlanCache``: same topology + shape + backend +
    mesh + thresholds → the same ``PlanBuilder`` (whose lazily-built
    substrates and prep artifacts are therefore shared too).

    Values are *not* part of the key — a hit may return a plan baked with
    different values than ``csr.data``; callers that care (the facade does)
    compare and pass a live stream at execute time.

    ``validate`` runs the guardrail pattern policy *before* the key is
    computed, so a repaired matrix keys (and caches) under its canonical
    sorted/coalesced fingerprint — the same entry a pre-cleaned input hits."""
    if validate is not None and validate != "off":
        csr, _ = validate_csr(csr, validate)
    from . import registry
    from .plan import plan as build_plan
    from .selector import default_thresholds

    cache = cache if cache is not None else DEFAULT_CACHE
    th = thresholds if thresholds is not None else default_thresholds()
    resolved = backend or ("sharded" if mesh is not None
                           else registry.default_backend())
    # None kwargs are plan() defaults — drop them so explicit-default and
    # omitted spellings share a key
    plan_kwargs = {k: v for k, v in plan_kwargs.items() if v is not None}
    key = plan_key(csr, backend=resolved, mesh=mesh, thresholds=th,
                   tile=tile, bsr_block=bsr_block,
                   extra=tuple(sorted(plan_kwargs.items())))
    return cache.get_or_build(
        key, lambda: build_plan(csr, thresholds=th, backend=resolved,
                                tile=tile, bsr_block=bsr_block, mesh=mesh,
                                **plan_kwargs))
