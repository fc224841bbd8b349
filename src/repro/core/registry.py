"""Backend-aware kernel registry: one table for the whole dispatch space.

The paper's 2x2 design space (row-split/nnz-balanced x sequential/parallel
reduction) gives four *logical* kernels.  Each logical kernel may have several
*physical* implementations — the XLA lowering in ``repro.core.spmm``, the
Pallas TPU kernels in ``repro.kernels``, the block-granule BSR path — and the
registry maps ``(logical_kernel, backend)`` onto one ``KernelEntry``.

Kernel modules self-register at import time (see the bottom of
``core/spmm.py``, ``kernels/vsr.py``, ``kernels/csc.py``, ``kernels/bsr.py``);
non-XLA backends are imported lazily on first resolve so importing
``repro.core`` never pulls in Pallas.

An entry's ``fn`` has the uniform signature::

    fn(substrate, x, *, interpret=None, **opts) -> y

where ``substrate`` is the format named by ``entry.substrate`` ("ell",
"balanced" or "bsr"), ``interpret`` is honoured by Pallas backends (ignored by
XLA), and ``opts`` are the static per-matrix artifacts produced by the entry's
optional ``prep`` hook.  ``prep(substrate) -> opts`` runs host-side once, at
plan time, on concrete arrays — hoisting work like ``plan_visits`` out of the
traced path so ``execute`` stays jit-able (see DESIGN.md §3).
"""
from __future__ import annotations

import contextlib
import dataclasses
import importlib
import threading
from typing import Callable, Optional

import jax

#: the paper's 2x2 SpMM space — the kernels ``execute`` dispatches between.
MATMUL_KERNELS: tuple[str, ...] = ("rs_sr", "rs_pr", "nb_sr", "nb_pr")

#: every logical kernel the registry knows: the 2x2 SpMM grid plus the GNN
#: training pair — ``sddmm`` (sample A @ B^T at the pattern's nonzeros) and
#: ``chain`` (SDDMM → per-row transform → SpMM, fused on Pallas).  The two
#: extras take raw pattern arrays, not substrates; ``execute_sddmm`` /
#: ``execute_chain`` in ``core/plan.py`` are their only call sites.
#: ``attn_chain`` is the chain's attention sibling — softmax with a score
#: scale and an additive per-edge bias slab (``execute_attention``).
LOGICAL_KERNELS: tuple[str, ...] = MATMUL_KERNELS + ("sddmm", "chain",
                                                     "attn_chain")

#: substrate format each *logical* kernel consumes on the reference (XLA)
#: backend; physical backends may substitute their own (BSR does, and the
#: sharded backend consumes per-shard stacks of the inner format).
SUBSTRATES: tuple[str, ...] = ("ell", "balanced", "bsr",
                               "shard_ell", "shard_balanced")

#: the degradation ladder (DESIGN.md §12): which backend a failing kernel
#: re-routes to.  One rung each — every accelerated backend falls back to
#: the XLA reference, which has no rung below (failures there propagate).
#: ``"sharded"`` maps to ``"xla"`` in the *inner* sense: the plan stays
#: sharded, its per-shard kernels demote (``core/plan.py`` handles the
#: demoted-inner rebuild; the mapping here just marks a rung exists).
DEMOTION: dict[str, str] = {"pallas": "xla", "bsr": "xla", "sharded": "xla"}


@dataclasses.dataclass(frozen=True)
class KernelEntry:
    logical: str                 # one of LOGICAL_KERNELS
    backend: str                 # "xla" | "pallas" | "bsr" | ...
    substrate: str               # one of SUBSTRATES
    fn: Callable                 # fn(substrate, x, *, interpret=None, **opts)
    prep: Optional[Callable] = None   # prep(substrate) -> opts dict (host-side)
    differentiable: bool = True  # eligible for the unified custom-VJP path
    # work(substrate, x, **opts) -> (kernel name, per-call counts); what
    # ``execute`` records in ``HEALTH`` when it binds the entry (host-side)
    work: Optional[Callable] = None


_REGISTRY: dict[tuple[str, str], KernelEntry] = {}

# module that registers each backend's kernels; imported on first resolve
_LAZY_BACKENDS: dict[str, str] = {
    "xla": "repro.core.spmm",
    "pallas": "repro.kernels",
    "bsr": "repro.kernels",
    "sharded": "repro.core.shard",
}


def register(logical: str, backend: str, substrate: str, fn: Callable, *,
             prep: Callable | None = None, differentiable: bool = True,
             work: Callable | None = None) -> KernelEntry:
    """Register (or replace) the physical implementation of a logical kernel."""
    if logical not in LOGICAL_KERNELS:
        raise ValueError(f"unknown logical kernel {logical!r}; "
                         f"expected one of {LOGICAL_KERNELS}")
    if substrate not in SUBSTRATES:
        raise ValueError(f"unknown substrate {substrate!r}; "
                         f"expected one of {SUBSTRATES}")
    entry = KernelEntry(logical, backend, substrate, fn, prep, differentiable,
                        work)
    _REGISTRY[(logical, backend)] = entry
    return entry


_LOADED_MODULES: set[str] = set()


def _ensure_backend_loaded(backend: str) -> None:
    # tracked by module, not by registry contents: a user pre-registering one
    # custom override must not suppress the import of the built-in entries
    mod = _LAZY_BACKENDS.get(backend)
    if mod is not None and mod not in _LOADED_MODULES:
        importlib.import_module(mod)
        _LOADED_MODULES.add(mod)  # only marked on successful import


def resolve(logical: str, backend: str) -> KernelEntry:
    """Look up the physical kernel for (logical, backend)."""
    _ensure_backend_loaded(backend)
    try:
        return _REGISTRY[(logical, backend)]
    except KeyError:
        avail = sorted(_REGISTRY)
        raise KeyError(
            f"no kernel registered for (logical={logical!r}, backend={backend!r}); "
            f"registered: {avail}") from None


def available(backend: str | None = None) -> tuple[KernelEntry, ...]:
    """All registered entries, optionally filtered by backend."""
    if backend is not None:
        _ensure_backend_loaded(backend)
    return tuple(e for e in _REGISTRY.values()
                 if backend is None or e.backend == backend)


def backends_for(logical: str) -> tuple[str, ...]:
    for b in _LAZY_BACKENDS:
        _ensure_backend_loaded(b)
    return tuple(b for (l, b) in _REGISTRY if l == logical)


# ---------------------------------------------------------------------------
# scoped backend override (the facade's ``use_backend`` context manager)
# ---------------------------------------------------------------------------

_SCOPE = threading.local()


@contextlib.contextmanager
def backend_scope(backend: str | None):
    """Make ``backend`` the default for every resolution in the dynamic
    extent: ``plan()``, ``execute_pattern``, and ``repro.api.sparse()`` all
    consult it when no explicit backend is passed.  ``None`` is a no-op scope
    (handy for plumbing optional config through).  Exposed to users as
    ``repro.api.use_backend``."""
    stack = getattr(_SCOPE, "stack", None)
    if stack is None:
        stack = _SCOPE.stack = []
    if backend is not None:
        stack.append(backend)
    try:
        yield
    finally:
        if backend is not None:
            stack.pop()


def scoped_backend() -> str | None:
    """Innermost ``backend_scope`` override, or None."""
    stack = getattr(_SCOPE, "stack", None)
    return stack[-1] if stack else None


def default_backend() -> str:
    """The scoped override when inside ``backend_scope``; otherwise Pallas
    compiles natively on TPU and everywhere else the XLA lowerings are the
    production path (Pallas interpret mode is a correctness harness)."""
    scoped = scoped_backend()
    if scoped is not None:
        return scoped
    return "pallas" if jax.default_backend() == "tpu" else "xla"
