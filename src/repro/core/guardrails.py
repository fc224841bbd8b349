"""Core execution guardrails (DESIGN.md §12): the failure story of the
plan/execute subsystem, in four pillars.

1. **Pattern validation & repair** (``validate_csr``): a CSR arriving from
   user code may be unsorted within rows, carry duplicate or out-of-range
   column indices, non-finite values, or an inconsistent indptr.  The
   ``validate=`` policy on ``api.sparse()``/``plan()`` decides what happens
   *before* a substrate is baked: ``"check"`` warns, ``"repair"`` rebuilds
   the matrix through the canonical sort/coalesce/clip/zero pipeline
   (``formats.csr_from_coo`` — exactly the reference a pre-sorted input
   would have produced), ``"strict"`` raises a typed ``PatternError``.

2. **Numeric sentinels** (``apply_sentinel``): opt-in post-execute
   non-finite detection on kernel outputs.  ``"raise"`` surfaces a
   ``NumericFault``, ``"sanitize"`` zeroes the poisoned lanes in graph,
   ``"fallback"`` re-executes through the demoted backend.  The VJP hook
   (``grad_scope``/``sanitize_grads``) extends the same policy to backward
   passes so training steps can skip-and-report instead of poisoning
   optimizer state (``train/step.py`` ``skip_nonfinite``).

3. **Backend degradation ladder** (``guarded_call`` + ``CircuitBreaker``):
   a per-(backend, logical-kernel) circuit breaker.  Kernel failures
   injected at the ``kernel_execute`` fault sites re-route the call down
   the demotion ladder (``registry.DEMOTION``: pallas→xla, bsr→xla, sharded
   demotes its inner backend) — gradient math is kernel-independent (one
   backward per substrate family, ``core/vjp.py``), so a rerouted forward
   yields grads bitwise-equal to the fallback backend's.  Repeated failures
   trip the breaker (skip the primary entirely); after ``cooldown_s`` the
   breaker half-opens and probes the primary once, closing on success.

4. **Plan integrity digests** (``plan_digest``): a content digest of a plan
   (pattern + value stream + layout knobs for builders; leaves + topology
   for artifacts) stored next to each ``PlanCache`` entry and checked on
   publication (and, under ``integrity="hit"``, on every hit) — a stale or
   corrupted cached plan is rebuilt, never executed.

Everything observable lands in the process ``HEALTH`` registry
(``api.health()`` / ``engine.metrics()["health"]``): breaker state/trips/
recoveries, reroutes, sentinel firings, pattern repairs, and the named
demotion counters for decisions that used to be silent ``warnings.warn``
calls (quant range fallback, the fuse crossovers, a traced pattern without
prep).  The registry also keeps the program's host spans, the XLA
compilations a ``jax.monitoring`` listener sees, and the per-call work of
each kernel ``execute`` binds (DESIGN.md §12.6).

Under ``jit`` the breaker decision and sentinel wiring bake at trace time
(the guard is host-side dispatch); eager execution — the fault-matrix test
mode — consults them per call.
"""
from __future__ import annotations

import contextlib
import dataclasses
import hashlib
import threading
import time
import warnings
from typing import Any, Callable

import jax
import jax.numpy as jnp
import numpy as np

from repro.runtime.faults import InjectedFault, active_injector


class PatternError(ValueError):
    """A sparsity pattern failed validation under ``validate="strict"`` (or
    was unrepairable).  ``issues`` carries the detected defect names."""

    def __init__(self, message: str, issues: tuple = ()):
        super().__init__(message)
        self.issues = tuple(issues)


class NumericFault(ArithmeticError):
    """A numeric sentinel fired under the ``"raise"`` policy: a kernel
    output (or a quantized value stream) left the representable regime."""


#: the ``validate=`` policies ``api.sparse()``/``plan()`` accept.
VALIDATE_POLICIES = ("off", "check", "repair", "strict")

#: the ``sentinel=`` policies ``execute()`` accepts ("off"/None disables).
SENTINEL_POLICIES = ("off", "raise", "sanitize", "fallback")


# ---------------------------------------------------------------------------
# pillar 1: pattern validation & repair
# ---------------------------------------------------------------------------

@dataclasses.dataclass(frozen=True)
class PatternReport:
    """What ``inspect_csr`` found.  ``issues`` is a tuple drawn from
    ``{"indptr", "length_mismatch", "out_of_range", "unsorted",
    "duplicates", "nonfinite"}``; empty means the pattern is well-formed."""

    issues: tuple = ()

    @property
    def ok(self) -> bool:
        return not self.issues


def inspect_csr(csr) -> PatternReport:
    """Detect, without repairing: inconsistent indptr, indices/data length
    mismatch, out-of-range columns, unsorted rows, in-row duplicates, and
    non-finite values.  Pure numpy, pattern-sized — cheap next to a
    substrate build."""
    indptr = np.asarray(csr.indptr)
    indices = np.asarray(csr.indices)
    data = np.asarray(csr.data)
    m, k = (int(s) for s in csr.shape)
    issues: list[str] = []
    if indices.shape[0] != data.shape[0]:
        issues.append("length_mismatch")
    nnz = int(min(indices.shape[0], data.shape[0]))
    indptr_ok = (indptr.ndim == 1 and indptr.shape[0] == m + 1
                 and (m == 0 or int(indptr[0]) == 0)
                 and bool(np.all(np.diff(indptr) >= 0))
                 and int(indptr[-1]) == indices.shape[0])
    if not indptr_ok:
        issues.append("indptr")
    if nnz and bool(np.any((indices[:nnz] < 0) | (indices[:nnz] >= k))):
        issues.append("out_of_range")
    if indptr_ok and nnz > 1:
        from .formats import row_ids_from_indptr
        rows = row_ids_from_indptr(indptr, nnz)
        same_row = rows[1:] == rows[:-1]
        step = indices[1:nnz].astype(np.int64) - indices[:nnz - 1]
        if bool(np.any(same_row & (step < 0))):
            issues.append("unsorted")
        if bool(np.any(same_row & (step == 0))):
            issues.append("duplicates")
        else:
            # duplicates hidden by unsorted order: check per-row multisets
            if "unsorted" in issues:
                key = rows.astype(np.int64) * max(k, 1) + indices[:nnz]
                if len(np.unique(key)) != nnz:
                    issues.append("duplicates")
    if nnz and not bool(np.all(np.isfinite(data[:nnz].astype(np.float64)))):
        issues.append("nonfinite")
    return PatternReport(tuple(issues))


def repair_csr(csr):
    """Rebuild a malformed CSR through the canonical pipeline: monotonicize
    and clip the indptr, truncate to the common indices/data length, drop
    out-of-range columns, zero non-finite values, then
    ``formats.csr_from_coo`` — which sorts by (row, col) and coalesces
    duplicates by summation.  The result is bit-identical to what a
    pre-sorted, pre-coalesced input would have produced."""
    from .formats import csr_from_coo, row_ids_from_indptr
    indptr = np.asarray(csr.indptr, np.int64).reshape(-1)
    indices = np.asarray(csr.indices).reshape(-1)
    data = np.asarray(csr.data).reshape(-1)
    m, k = (int(s) for s in csr.shape)
    n = int(min(indices.shape[0], data.shape[0]))
    indices, data = indices[:n], data[:n]
    if indptr.shape[0] < m + 1:
        tail = indptr[-1] if indptr.shape[0] else 0
        indptr = np.concatenate(
            [indptr, np.full(m + 1 - indptr.shape[0], tail, np.int64)])
    indptr = np.maximum.accumulate(np.clip(indptr[:m + 1], 0, n))
    indptr[0], indptr[m] = 0, n   # orphan trailing entries join the last row
    indptr = np.maximum.accumulate(indptr)
    rows = row_ids_from_indptr(indptr, n)
    good = (indices >= 0) & (indices < k)
    vals = np.where(np.isfinite(data.astype(np.float64)), data, 0)
    dtype = data.dtype if np.issubdtype(data.dtype, np.floating) else np.float32
    return csr_from_coo(rows[good], indices[good], vals[good], (m, k),
                        dtype=dtype)


def validate_csr(csr, policy: str = "check"):
    """Apply one ``validate=`` policy to a CSR; returns ``(csr, report)``.

    ``"off"`` skips detection entirely; ``"check"`` warns and returns the
    original; ``"repair"`` returns the rebuilt matrix (see ``repair_csr``);
    ``"strict"`` raises ``PatternError``.  Clean patterns pass through
    untouched under every policy."""
    if policy not in VALIDATE_POLICIES:
        raise ValueError(f"unknown validate policy {policy!r}; expected one "
                         f"of {VALIDATE_POLICIES}")
    if policy == "off":
        return csr, PatternReport()
    report = inspect_csr(csr)
    if report.ok:
        return csr, report
    HEALTH.bump("pattern_issues")
    detail = ", ".join(report.issues)
    if policy == "strict":
        raise PatternError(
            f"pattern failed validation ({detail}); pass validate='repair' "
            "to sort/coalesce/clip/zero it, or fix the CSR upstream",
            issues=report.issues)
    if policy == "check":
        warnings.warn(f"pattern has issues ({detail}); executing it as-is — "
                      "pass validate='repair' to fix, 'strict' to reject",
                      stacklevel=3)
        return csr, report
    HEALTH.bump("pattern_repairs")
    return repair_csr(csr), report


# ---------------------------------------------------------------------------
# pillar 3 support: circuit breakers + the health registry
# ---------------------------------------------------------------------------

class CircuitBreaker:
    """Closed → (``threshold`` consecutive failures) → open → (after
    ``cooldown_s``) → half-open probe → closed on success / open on failure.
    ``clock`` is injectable for deterministic tests; ``cooldown_s=0`` makes
    every post-trip call a probe."""

    def __init__(self, threshold: int = 3, cooldown_s: float = 30.0,
                 clock: Callable[[], float] = time.monotonic):
        self.threshold = int(threshold)
        self.cooldown_s = float(cooldown_s)
        self.clock = clock
        self.state = "closed"
        self.failures = 0            # consecutive
        self.trips = 0
        self.recoveries = 0
        self._opened_at = 0.0
        self._lock = threading.Lock()

    def allow(self) -> bool:
        """Whether the caller should attempt the primary backend now.  An
        open breaker half-opens (one probe) once the cooldown has elapsed."""
        with self._lock:
            if self.state == "open":
                if self.clock() - self._opened_at >= self.cooldown_s:
                    self.state = "half_open"
                    return True
                return False
            return True

    def record_failure(self) -> None:
        with self._lock:
            self.failures += 1
            if self.state == "half_open" or self.failures >= self.threshold:
                if self.state != "open":
                    self.trips += 1
                self.state = "open"
                self._opened_at = self.clock()

    def record_success(self) -> None:
        with self._lock:
            if self.state in ("open", "half_open"):
                self.recoveries += 1
            self.state = "closed"
            self.failures = 0

    def snapshot(self) -> dict:
        with self._lock:
            return {"state": self.state, "failures": self.failures,
                    "trips": self.trips, "recoveries": self.recoveries}


def _empty_compile() -> dict:
    return {"count": 0, "seconds": 0.0, "cache_hits": 0, "cache_misses": 0,
            "retrieval_s": 0.0, "by_function": {}}


class HealthRegistry:
    """Process-wide observability: named counters, the per-(backend,
    logical-kernel) breakers, host spans (``span``), XLA compilations (fed by
    a ``jax.monitoring`` listener) and the per-call work of the kernels
    ``execute`` binds (``record_work``).  ``api.health()`` and
    ``engine.metrics()["health"]`` are snapshots of this object."""

    def __init__(self):
        self._lock = threading.Lock()
        self._counters: dict[str, int] = {}
        self._breakers: dict[tuple, CircuitBreaker] = {}
        self._spans: dict[str, dict] = {}
        self._open = threading.local()      # per-thread stack of open spans
        self._compile = _empty_compile()
        self._work: dict[str, dict] = {}
        self._threshold = 3
        self._cooldown_s = 30.0
        self._clock: Callable[[], float] = time.monotonic

    def configure(self, *, threshold: int = 3, cooldown_s: float = 30.0,
                  clock: Callable[[], float] = time.monotonic) -> None:
        """Set the breaker parameters for breakers created *from now on* and
        re-arm existing ones (tests lower threshold/cooldown for determinism;
        ``reset()`` + ``configure()`` restores production defaults)."""
        with self._lock:
            self._threshold = int(threshold)
            self._cooldown_s = float(cooldown_s)
            self._clock = clock
            for br in self._breakers.values():
                br.threshold = int(threshold)
                br.cooldown_s = float(cooldown_s)
                br.clock = clock

    def breaker(self, backend: str, logical: str) -> CircuitBreaker:
        key = (backend, logical)
        with self._lock:
            br = self._breakers.get(key)
            if br is None:
                br = CircuitBreaker(self._threshold, self._cooldown_s,
                                    self._clock)
                self._breakers[key] = br
            return br

    def bump(self, counter: str, n: int = 1) -> None:
        with self._lock:
            self._counters[counter] = self._counters.get(counter, 0) + n

    def counter(self, name: str) -> int:
        with self._lock:
            return self._counters.get(name, 0)

    @contextlib.contextmanager
    def span(self, name: str, **args):
        """Time a piece of host work under ``name``.

        The span opens a ``jax.profiler.TraceAnnotation`` (``args`` ride
        along), so under an active profiler trace it lands on the host plane
        on the device ops' clock; and it always adds to the in-memory record
        of ``name``: count, total and self seconds (total less the time its
        child spans on the same thread cover), the names of the spans it
        opened under, and a count per ``key=value`` of ``args``.  Also a
        decorator (``@HEALTH.span("repro.plan")``)."""
        stack = getattr(self._open, "stack", None)
        if stack is None:
            stack = self._open.stack = []
        frame = [name, 0.0]                 # name, seconds of child spans
        stack.append(frame)
        t0 = time.perf_counter()
        try:
            with jax.profiler.TraceAnnotation(name, **args):
                yield
        finally:
            dt = time.perf_counter() - t0
            stack.pop()
            parent = stack[-1][0] if stack else None
            if stack:
                stack[-1][1] += dt
            with self._lock:
                rec = self._spans.get(name)
                if rec is None:
                    rec = self._spans[name] = {
                        "count": 0, "total_s": 0.0, "self_s": 0.0,
                        "parents": {}, "args": {}}
                rec["count"] += 1
                rec["total_s"] += dt
                rec["self_s"] += dt - frame[1]
                if parent is not None:
                    rec["parents"][parent] = rec["parents"].get(parent, 0) + 1
                for k, v in args.items():
                    kv = f"{k}={v}"
                    rec["args"][kv] = rec["args"].get(kv, 0) + 1

    def record_compile(self, seconds: float, fun_name: str | None) -> None:
        """One backend compilation of ``fun_name`` (persistent-cache
        retrieval, or compile and cache write, included)."""
        with self._lock:
            c = self._compile
            c["count"] += 1
            c["seconds"] += seconds
            if fun_name:
                f = c["by_function"].setdefault(fun_name,
                                                {"count": 0, "seconds": 0.0})
                f["count"] += 1
                f["seconds"] += seconds

    def record_cache(self, hit: bool, retrieval_s: float = 0.0) -> None:
        """A persistent compilation-cache hit (with the seconds it took to
        read) or miss."""
        with self._lock:
            self._compile["cache_hits" if hit else "cache_misses"] += 1
            self._compile["retrieval_s"] += retrieval_s

    def record_work(self, key: str, work: dict) -> None:
        """The per-call work of a bound kernel, as ``execute`` counted it
        when it bound the call; a later bind under the same key replaces the
        numbers and adds to ``binds``."""
        with self._lock:
            binds = self._work.get(key, {}).get("binds", 0)
            self._work[key] = dict(work, binds=binds + 1)

    def snapshot(self) -> dict:
        with self._lock:
            return {
                "counters": dict(self._counters),
                "breakers": {f"{b}:{l}": br.snapshot()
                             for (b, l), br in self._breakers.items()},
                "spans": {n: dict(r, parents=dict(r["parents"]),
                                  args=dict(r["args"]))
                          for n, r in self._spans.items()},
                "compile": dict(self._compile, by_function={
                    f: dict(v)
                    for f, v in self._compile["by_function"].items()}),
                "work": {k: dict(v) for k, v in self._work.items()},
            }

    def reset(self) -> None:
        """Drop counters, breakers, spans, compile and work records (tests;
        production code never calls this — lifetime counters are the
        point)."""
        with self._lock:
            self._counters.clear()
            self._breakers.clear()
            self._spans.clear()
            self._compile = _empty_compile()
            self._work.clear()


#: the process default every core hook writes to.
HEALTH = HealthRegistry()

#: the ``jax.monitoring`` events ``HEALTH``'s compile record reads.  JAX
#: times ``compile_or_get_cached`` as the backend compile, so its seconds
#: already hold a cache read (hit) or the compile and cache write (miss).
BACKEND_COMPILE_EVENT = "/jax/core/compile/backend_compile_duration"
CACHE_RETRIEVAL_EVENT = "/jax/compilation_cache/cache_retrieval_time_sec"
CACHE_MISS_EVENT = "/jax/compilation_cache/cache_misses"


def _on_duration(event: str, secs: float, **kw) -> None:
    if event == BACKEND_COMPILE_EVENT:
        HEALTH.record_compile(secs, kw.get("fun_name"))
    elif event == CACHE_RETRIEVAL_EVENT:
        HEALTH.record_cache(True, secs)


def _on_event(event: str, **_kw) -> None:
    if event == CACHE_MISS_EVENT:
        HEALTH.record_cache(False)


jax.monitoring.register_event_duration_secs_listener(_on_duration)
jax.monitoring.register_event_listener(_on_event)


#: kernel-failure types the degradation ladder catches and reroutes: the
#: faults the chaos harness injects (``runtime/faults.py``).  Everything else
#: propagates — a usage error (ValueError/TypeError/KeyError) is the
#: caller's bug, a sentinel's ``NumericFault`` is what the user asked for,
#: and a kernel that fails to lower or compile (``NotImplementedError`` from
#: Mosaic, a VMEM ``RESOURCE_EXHAUSTED``) or fails at run time is a bug in
#: the kernel: rerouting it to XLA would hide it behind a counter.
FAILURE_TYPES = (InjectedFault,)


def guarded_call(logical: str, backend: str, primary: Callable[[], Any], *,
                 fallback: Callable[[], Any] | None = None,
                 fallback_name: str | None = None,
                 registry: HealthRegistry | None = None):
    """One rung of the degradation ladder around a kernel dispatch.

    Consults the scoped fault injector at ``kernel_execute`` and
    ``kernel_execute:<backend>``, runs ``primary`` under the
    (backend, logical) breaker, and on a caught failure records it and
    re-routes through ``fallback`` (the next rung) — or re-raises when the
    ladder has no rung below (the xla reference).  A tripped breaker skips
    the primary entirely until its cooldown elapses, then probes it
    half-open.  Under jit this all happens at trace time."""
    reg = registry if registry is not None else HEALTH
    br = reg.breaker(backend, logical)
    if not br.allow():
        if fallback is not None:
            reg.bump(f"breaker_skip:{backend}:{logical}")
            return fallback()
        # bottom of the ladder: nothing to skip to — attempt anyway
    inj = active_injector()
    try:
        if inj is not None:
            inj.raise_if("kernel_execute")
            inj.raise_if(f"kernel_execute:{backend}")
        y = primary()
    except NumericFault:
        raise
    except FAILURE_TYPES:
        br.record_failure()
        if fallback is None:
            raise
        reg.bump(f"kernel_reroute:{backend}->{fallback_name or 'xla'}"
                 f":{logical}")
        return fallback()
    br.record_success()
    return y


# ---------------------------------------------------------------------------
# pillar 2: numeric sentinels
# ---------------------------------------------------------------------------

_SENTINEL = threading.local()


@contextlib.contextmanager
def sentinel_scope(policy: str | None):
    """Make ``policy`` the default ``sentinel=`` for every ``execute`` in
    the dynamic extent (explicit arguments win).  ``None`` is a no-op."""
    if policy is not None and policy not in SENTINEL_POLICIES:
        raise ValueError(f"unknown sentinel policy {policy!r}; expected one "
                         f"of {SENTINEL_POLICIES}")
    stack = getattr(_SENTINEL, "stack", None)
    if stack is None:
        stack = _SENTINEL.stack = []
    if policy is not None:
        stack.append(policy)
    try:
        yield
    finally:
        if policy is not None:
            stack.pop()


def active_sentinel() -> str | None:
    stack = getattr(_SENTINEL, "stack", None)
    return stack[-1] if stack else None


def apply_sentinel(y, policy: str | None, *, site: str,
                   fallback: Callable[[], Any] | None = None,
                   registry: HealthRegistry | None = None):
    """Post-execute non-finite guard on a kernel output.

    Eager outputs are checked on the host: a non-finite lane bumps the
    ``sentinel:<site>`` counter and the policy decides — ``"raise"`` a
    ``NumericFault``, ``"sanitize"`` zero the poisoned lanes, ``"fallback"``
    re-execute through the demoted backend (degrading to sanitize when the
    ladder has no rung below).  Traced outputs stay pure: ``"sanitize"`` is
    an in-graph ``where(isfinite)``, ``"fallback"`` a ``lax.cond`` that only
    pays the fallback when poisoned, ``"raise"`` a debug callback that
    surfaces at run time (no counters under trace — tracing must stay
    side-effect-free and retrace-stable)."""
    if policy in (None, "off"):
        return y
    if policy not in SENTINEL_POLICIES:
        raise ValueError(f"unknown sentinel policy {policy!r}; expected one "
                         f"of {SENTINEL_POLICIES}")
    if not jnp.issubdtype(jnp.result_type(y), jnp.inexact):
        return y
    reg = registry if registry is not None else HEALTH
    if isinstance(y, jax.core.Tracer):
        if policy == "sanitize":
            return jnp.where(jnp.isfinite(y), y, 0).astype(y.dtype)
        if policy == "raise":
            def _check(ok):
                if not bool(ok):
                    raise NumericFault(
                        f"non-finite kernel output at {site} (traced)")
            jax.debug.callback(_check, jnp.all(jnp.isfinite(y)))
            return y
        # fallback under trace: both branches are traced; the fallback
        # kernel only *runs* when the primary output is poisoned
        if fallback is None:
            return jnp.where(jnp.isfinite(y), y, 0).astype(y.dtype)
        return jax.lax.cond(jnp.all(jnp.isfinite(y)), lambda: y, fallback)
    finite = bool(np.all(np.isfinite(np.asarray(y))))
    if finite:
        return y
    reg.bump(f"sentinel:{site}")
    if policy == "raise":
        raise NumericFault(f"non-finite kernel output at {site}")
    if policy == "fallback" and fallback is not None:
        reg.bump(f"sentinel_fallback:{site}")
        return fallback()
    return jnp.where(jnp.isfinite(y), y, 0).astype(y.dtype)


# -- the VJP hook ----------------------------------------------------------

_GRAD = threading.local()


@contextlib.contextmanager
def grad_scope(policy: str | None):
    """Extend the sentinel to backward passes: inside the scope the shared
    custom-VJP backwards (``core/vjp.py``) pass their cotangents through
    ``sanitize_grads``.  Only ``"sanitize"`` acts in graph (``"raise"`` and
    ``"fallback"`` have no pure backward analogue — use
    ``train.step.TrainConfig(skip_nonfinite=True)`` for skip-and-report)."""
    if policy is not None and policy not in (None, "off", "sanitize"):
        raise ValueError("grad_scope supports 'sanitize' (or None/'off'); "
                         "use TrainConfig(skip_nonfinite=True) for "
                         "skip-and-report semantics")
    stack = getattr(_GRAD, "stack", None)
    if stack is None:
        stack = _GRAD.stack = []
    if policy is not None:
        stack.append(policy)
    try:
        yield
    finally:
        if policy is not None:
            stack.pop()


def active_grad_sentinel() -> str | None:
    stack = getattr(_GRAD, "stack", None)
    return stack[-1] if stack else None


def sanitize_grads(*cots):
    """Pass cotangents through the active grad sentinel: a no-op unless a
    ``grad_scope("sanitize")`` is active (decided host-side at trace time),
    in which case non-finite lanes zero in graph."""
    if active_grad_sentinel() != "sanitize":
        return cots if len(cots) != 1 else cots[0]
    out = tuple(jnp.where(jnp.isfinite(c), c, 0).astype(c.dtype)
                if jnp.issubdtype(jnp.result_type(c), jnp.inexact) else c
                for c in cots)
    return out if len(out) != 1 else out[0]


# ---------------------------------------------------------------------------
# pillar 4: plan integrity digests
# ---------------------------------------------------------------------------

def _fold_bytes(h, v) -> None:
    if isinstance(v, (bool, int, float, str, bytes, type(None))):
        h.update(repr(v).encode())
        return
    if isinstance(v, (tuple, list)):
        h.update(b"(")
        for item in v:
            _fold_bytes(h, item)
        h.update(b")")
        return
    if isinstance(v, dict):
        h.update(b"{")
        for key in sorted(v, key=repr):
            h.update(repr(key).encode())
            _fold_bytes(h, v[key])
        h.update(b"}")
        return
    try:
        arr = np.asarray(v)
        h.update(str(arr.dtype).encode() + repr(arr.shape).encode())
        h.update(arr.tobytes())
    except Exception:
        # opaque leaf (callable, lock, ...): identity-stable repr — digests
        # only need to match the *stored object*, and corruption means the
        # entry's arrays changed, which the array branch catches
        h.update(repr(v).encode())


def plan_digest(value) -> str:
    """Content digest of a cacheable plan value.

    ``PlanBuilder``-likes hash their *immutable identity* — the CSR triplet
    bytes plus the layout knobs fixed at plan time (backend, tile, bsr
    block, chain op).  Lazily-mutated state (built substrates, the quant
    mode the dynamic-range fallback may demote, memoized fingerprints) is
    excluded on purpose: it changes legitimately after caching.
    ``PlanArtifact``-likes hash their pytree leaves plus the topology key.
    Anything else (the serve engine's artifact bundles) hashes its flattened
    leaves.  Never raises — an undigestable leaf degrades to its repr."""
    h = hashlib.sha1()
    if hasattr(value, "csr") and hasattr(value, "backend") \
            and hasattr(value, "thresholds"):
        csr = value.csr
        for arr in (csr.indptr, csr.indices, csr.data):
            a = np.asarray(arr)
            h.update(str(a.dtype).encode())
            h.update(a.tobytes())
        _fold_bytes(h, (tuple(int(s) for s in csr.shape), value.backend,
                        int(value.tile), tuple(value.bsr_block),
                        value.chain_op, value.inner_backend))
        return h.hexdigest()
    if hasattr(value, "substrates") and hasattr(value, "meta"):
        for leaf in jax.tree_util.tree_leaves(value):
            _fold_bytes(h, leaf)
        _fold_bytes(h, (value.meta.topology, value.meta.backend))
        return h.hexdigest()
    for leaf in jax.tree_util.tree_leaves(value):
        _fold_bytes(h, leaf)
    return h.hexdigest()
