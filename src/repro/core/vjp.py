"""The unified custom VJPs — one backward pair per substrate family.

Gradient math is kernel-independent (the VJP of ``Y = A·X`` is ``dA = G·Xᵀ``
restricted to the pattern, ``dX = Aᵀ·G``), so one backward pair per substrate
family serves every backend; the forward primal is whatever physical kernel
the registry resolved (DESIGN.md §3 rule 3).  Split out of ``core/plan.py``
so both the plan layer and the sharded backend (``core/shard.py``) can reach
the families without importing each other's front doors.

Each ``_exec_*`` takes a ``static`` tuple whose first element is the *bound*
physical kernel (prep opts + interpret baked in).  The static rides
``custom_vjp``'s ``nondiff_argnums``, so callers must pass an
identity-stable callable (see the bind caches in ``core/plan.py``) or every
call re-traces.
"""
from __future__ import annotations

import dataclasses
import functools
from typing import Any, Callable

import jax
import jax.numpy as jnp
import numpy as np

from .formats import BSR, ELL, BalancedCOO
from .guardrails import HEALTH, sanitize_grads


def _as_2d(a):
    return (a[:, None], True) if a.ndim == 1 else (a, False)


def _coo_bwd(rows, cols, valid, vals, x, g, shape):
    """Shared cotangent math for any COO-viewable substrate:
    dvals[e] = <g[row_e,:], x[col_e,:]> (masked), dx = Aᵀ·g.  The returned
    pair passes through the guardrail grad sentinel — a no-op unless a
    ``guardrails.grad_scope("sanitize")`` is active at trace time."""
    m, k = shape
    x2, _ = _as_2d(x)
    g2, _ = _as_2d(g)
    g_rows = jnp.take(g2, jnp.minimum(rows, m - 1), axis=0)
    g_rows = jnp.where(valid[:, None], g_rows, 0)
    x_cols = jnp.take(x2, cols, axis=0)
    dvals = jnp.sum(g_rows.astype(jnp.float32) * x_cols.astype(jnp.float32), axis=-1)
    p = vals.astype(jnp.float32)[:, None] * g_rows.astype(jnp.float32)
    dx = jax.ops.segment_sum(p, cols, num_segments=k)
    dx = dx.reshape(x.shape).astype(x.dtype)
    return sanitize_grads(dvals, dx)


def _float0(a):
    # integer pattern args get symbolic-zero (float0) cotangents
    return np.zeros(a.shape, jax.dtypes.float0)


def _zero_cot(a):
    """Zero cotangent matching what JAX expects for the primal's dtype:
    float0 for integer args (pattern arrays, visit schedules), a zeros
    array for inexact ones (the f32 per-tile scales quantized plans thread
    through the sharded ``extra`` slot)."""
    if jnp.issubdtype(jnp.result_type(a), jnp.inexact):
        return jnp.zeros(a.shape, a.dtype)
    return _float0(a)


def _value_cot(dvals, vals):
    """The value-stream cotangent: the analytical dA (straight-through for
    quantized forwards) cast back to the primal dtype — unless the primal is
    an integer-coded stream (baked int8 substrates), whose cotangent must be
    symbolic zero."""
    if jnp.issubdtype(jnp.result_type(vals), jnp.inexact):
        return dvals.reshape(vals.shape).astype(vals.dtype)
    return _float0(vals)


@functools.partial(jax.custom_vjp, nondiff_argnums=(0,))
def _exec_balanced(static, rows, cols, vals, x, *extra):
    """``extra``: integer per-matrix prep artifacts forwarded positionally to
    the bound kernel (float0 cotangents) — the sharded backend threads
    per-shard prep (stacked visit schedules, quantization scales) through
    here, since inside shard_map those are traced values and must not be
    baked into the static.  ``static`` is ``(bound_fn, shape)``, or for a
    pattern call ``(bound_fn, shape, PatternBackward)``: then the first
    ``n_fwd`` extras are the forward kernel's and the rest its transpose's."""
    bound_fn, shape = static[:2]
    if len(static) > 2:              # a pattern call: the forward's extras
        extra = extra[:static[2].n_fwd]
    bal = BalancedCOO(rows, cols, vals.reshape(rows.shape), tuple(shape))
    return bound_fn(bal, x, *extra)


def _exec_balanced_fwd(static, rows, cols, vals, x, *extra):
    return _exec_balanced(static, rows, cols, vals, x, *extra), (rows, cols, vals, x, extra)


@dataclasses.dataclass(frozen=True, eq=False)
class PatternBackward:
    """The backward of a Pallas-backed pattern call (``execute_pattern``),
    riding the balanced family's static as its third element.

    With ``spmm`` and ``sddmm`` bound it runs on the kernels:
    ``dvals = sddmm(rows, cols, G, X)`` on the forward's own tiles (sentinel
    entries score 0), and ``dX = Aᵀ·G`` as ``spmm`` over the transposed
    pattern, whose arrays follow the forward kernel's ``n_fwd`` extras
    (``perm``, ``rows``, ``cols``, then its visit schedule in the forward
    schedule's order) and whose values are the live stream permuted in
    graph.  Without them (``FLAT_BACKWARD``: no transpose was prepared) it
    falls to ``_coo_bwd`` and bumps ``demote:coo_bwd``.  ``work``: the
    pattern's ``PatternWork``, recording the backward kernels' work.
    Identity-hashed: the caller caches one per bound kernel."""
    n_fwd: int | None
    spmm: Callable | None = None
    sddmm: Callable | None = None
    work: Any = None


#: the backward of a Pallas-backed pattern call with no transpose: every
#: extra is the forward's, and the gradient is the flat ``_coo_bwd``
FLAT_BACKWARD = PatternBackward(None)


def _kernel_bwd(kb: PatternBackward, rows, cols, vals, x, g, shape, extra):
    m, k = shape
    perm, rows_t, cols_t, *sched = extra[kb.n_fwd:]
    x2, _ = _as_2d(x)
    g2, _ = _as_2d(g)
    if kb.work is not None:
        kb.work.record_backward(int(x2.shape[1]))
    dvals = kb.sddmm(rows, cols, g2, x2, shape=shape)
    vals_t = jnp.take(vals.reshape(-1), perm.reshape(-1)).reshape(rows_t.shape)
    dx = kb.spmm(BalancedCOO(rows_t, cols_t, vals_t, (k, m)), g2, *sched)
    dx = dx.reshape(x.shape).astype(x.dtype)
    return sanitize_grads(dvals, dx)


def _exec_balanced_bwd(static, res, g):
    shape = static[1]
    kb = static[2] if len(static) > 2 else None
    rows, cols, vals, x, extra = res
    from .quant import is_quantized_dtype
    if kb is not None:
        if kb.spmm is not None and not is_quantized_dtype(vals.dtype):
            dvals, dx = _kernel_bwd(kb, rows, cols, vals, x, g, shape, extra)
            return (_float0(rows), _float0(cols), _value_cot(dvals, vals),
                    dx, *(_zero_cot(e) for e in extra))
        HEALTH.bump("demote:coo_bwd")
    r, c = rows.reshape(-1), cols.reshape(-1)
    v = vals.reshape(-1)
    if is_quantized_dtype(vals.dtype) and extra:
        # baked quantized stream: by convention ``extra[0]`` carries the
        # per-tile f32 dequant scales (see core/plan._run_entry and the
        # sharded exec) — dX must see the decoded values, not the codes
        v = (vals.reshape(rows.shape).astype(jnp.float32)
             * extra[0][..., None]).reshape(-1)
    dvals, dx = _coo_bwd(r, c, r < shape[0], v, x, g, shape)
    return (_float0(rows), _float0(cols), _value_cot(dvals, vals), dx,
            *(_zero_cot(e) for e in extra))


_exec_balanced.defvjp(_exec_balanced_fwd, _exec_balanced_bwd)


@functools.partial(jax.custom_vjp, nondiff_argnums=(0,))
def _exec_ell(static, cols, lens, vals, x):
    bound_fn, shape = static
    return bound_fn(ELL(cols, vals, tuple(shape)), x)


def _exec_ell_fwd(static, cols, lens, vals, x):
    return _exec_ell(static, cols, lens, vals, x), (cols, lens, vals, x)


def _exec_ell_bwd(static, res, g):
    _, shape = static
    cols, lens, vals, x = res
    m, w = cols.shape
    g2, _ = _as_2d(g)
    rows = jnp.repeat(jnp.arange(m, dtype=jnp.int32), w)
    valid = (jnp.arange(w, dtype=jnp.int32)[None, :] < lens[:, None]).reshape(-1)
    dvals, dx = _coo_bwd(rows, cols.reshape(-1), valid, vals.reshape(-1),
                         x, g2, shape)
    return (_float0(cols), _float0(lens),
            dvals.reshape(vals.shape).astype(vals.dtype), dx)


_exec_ell.defvjp(_exec_ell_fwd, _exec_ell_bwd)


@functools.partial(jax.custom_vjp, nondiff_argnums=(0,))
def _exec_bsr(static, indptr, bcol, brow, blocks, x):
    """Block-granule family (DESIGN.md §3 rule 3): forward is the physical
    BSR kernel; backward is block-level — dA restricted to the *materialized
    blocks* (a superset of the CSR pattern; the stream gather in ``execute``
    masks it back down) and dX as a block-transpose segment reduction."""
    bound_fn, shape, block_shape = static
    return bound_fn(BSR(indptr, bcol, blocks, tuple(shape),
                        tuple(block_shape)), x)


def _exec_bsr_fwd(static, indptr, bcol, brow, blocks, x):
    return (_exec_bsr(static, indptr, bcol, brow, blocks, x),
            (indptr, bcol, brow, blocks, x))


def _exec_bsr_bwd(static, res, g):
    _, (m, k), (bm, bk) = static
    indptr, bcol, brow, blocks, x = res
    mb, kb = -(-m // bm), -(-k // bk)
    g2, _ = _as_2d(g)
    x2, _ = _as_2d(x)
    g3 = jnp.pad(g2.astype(jnp.float32),
                 ((0, mb * bm - m), (0, 0))).reshape(mb, bm, -1)
    x3 = jnp.pad(x2.astype(jnp.float32),
                 ((0, kb * bk - k), (0, 0))).reshape(kb, bk, -1)
    gb = jnp.take(g3, brow, axis=0)                     # (nb, bm, N)
    xb = jnp.take(x3, bcol, axis=0)                     # (nb, bk, N)
    dblocks = jnp.einsum("bmn,bkn->bmk", gb, xb).astype(blocks.dtype)
    p = jnp.einsum("bmk,bmn->bkn", blocks.astype(jnp.float32), gb)
    dx = jax.ops.segment_sum(p, bcol, num_segments=kb)
    dx = dx.reshape(kb * bk, -1)[:k].reshape(x.shape).astype(x.dtype)
    dblocks, dx = sanitize_grads(dblocks, dx)
    return (_float0(indptr), _float0(bcol), _float0(brow), dblocks, dx)


_exec_bsr.defvjp(_exec_bsr_fwd, _exec_bsr_bwd)


# ---------------------------------------------------------------------------
# the GNN pair: SDDMM and the SDDMM→transform→SpMM chain
# ---------------------------------------------------------------------------
#
# Both take *global* pattern arrays (row ids in [0, m), any sentinel >= m for
# padding) and the raw dense operands — no substrate, because the pattern IS
# the plan's pattern and the values are computed, not stored.  The forward is
# whatever physical kernel the registry resolved (fused Pallas, unfused XLA,
# the shard_map wrapper — the custom VJP wraps the *whole* sharded call, so
# cross-shard softmax stats never need a per-shard backward).  The backward
# is the analytic dual pair: dW is itself an SDDMM of (G, X), and dA is an
# SpMM with dE as the value stream — computed in flat XLA math so one
# backward serves every backend and layout.


@functools.partial(jax.custom_vjp, nondiff_argnums=(0,))
def _exec_sddmm(static, rows, cols, a, b):
    bound_fn, shape = static
    return bound_fn(rows, cols, a, b)


def _exec_sddmm_fwd(static, rows, cols, a, b):
    return _exec_sddmm(static, rows, cols, a, b), (rows, cols, a, b)


def _exec_sddmm_bwd(static, res, g):
    _, (m, k) = static
    rows, cols, a, b = res
    r, c = rows.reshape(-1), cols.reshape(-1)
    valid = r < m
    gf = jnp.where(valid, g.reshape(-1).astype(jnp.float32), 0.0)
    rr = jnp.where(valid, r, m)
    ag = jnp.take(a.astype(jnp.float32), jnp.where(valid, r, 0), axis=0)
    bg = jnp.take(b.astype(jnp.float32), c, axis=0)
    da = jax.ops.segment_sum(gf[:, None] * bg, rr, num_segments=m + 1)[:m]
    db = jax.ops.segment_sum(gf[:, None] * ag, c, num_segments=k)
    da, db = sanitize_grads(da, db)
    return (_float0(rows), _float0(cols),
            da.astype(a.dtype), db.astype(b.dtype))


_exec_sddmm.defvjp(_exec_sddmm_fwd, _exec_sddmm_bwd)


@functools.partial(jax.custom_vjp, nondiff_argnums=(0,))
def _exec_chain(static, rows, cols, a, b, x):
    bound_fn = static[0]
    return bound_fn(rows, cols, a, b, x)


def _exec_chain_fwd(static, rows, cols, a, b, x):
    return _exec_chain(static, rows, cols, a, b, x), (rows, cols, a, b, x)


def _exec_chain_bwd(static, res, g):
    """Recompute-and-differentiate: edge scores are *nowhere* in HBM (that is
    the point of the fused forward), so the backward recomputes E and W with
    flat segment ops, then applies the transform's jacobian — for softmax,
    dE = α·W∘(dW − rowsum(W∘dW))."""
    from .spmm import _sddmm_flat, _softmax_stats, chain_weights
    _, (m, k), transform, alpha = static
    rows, cols, a, b, x = res
    r, c = rows.reshape(-1), cols.reshape(-1)
    valid = r < m
    rr = jnp.where(valid, r, m)
    al = 1.0 if alpha is None else float(alpha)
    e = _sddmm_flat(r, c, a, b, valid)
    w = chain_weights(e, r, valid, m, transform, alpha)
    g2, _ = _as_2d(g)
    x2, _ = _as_2d(x)
    gr = jnp.take(g2.astype(jnp.float32), jnp.where(valid, r, 0), axis=0)
    gr = jnp.where(valid[:, None], gr, 0.0)
    xc = jnp.take(x2.astype(jnp.float32), c, axis=0)
    dw = jnp.sum(gr * xc, axis=-1)                       # SDDMM of (G, X)
    if transform == "identity":
        de = dw
    elif transform == "scale":
        de = al * dw
    else:                                                # masked softmax
        s = jax.ops.segment_sum(w * dw, rr, num_segments=m + 1)
        de = al * w * (dw - jnp.take(s, rr))
    de = jnp.where(valid, de, 0.0)
    ag = jnp.take(a.astype(jnp.float32), jnp.where(valid, r, 0), axis=0)
    bg = jnp.take(b.astype(jnp.float32), c, axis=0)
    da = jax.ops.segment_sum(de[:, None] * bg, rr, num_segments=m + 1)[:m]
    db = jax.ops.segment_sum(de[:, None] * ag, c, num_segments=k)
    dx = jax.ops.segment_sum(w[:, None] * gr, c, num_segments=k)
    dx = dx.reshape(x.shape).astype(x.dtype)
    da, db, dx = sanitize_grads(da, db, dx)
    return (_float0(rows), _float0(cols), da.astype(a.dtype),
            db.astype(b.dtype), dx)


_exec_chain.defvjp(_exec_chain_fwd, _exec_chain_bwd)


@functools.partial(jax.custom_vjp, nondiff_argnums=(0,))
def _exec_attn(static, rows, cols, q, k, bias, x):
    """Block-sparse attention family (DESIGN.md §10): forward is the bound
    physical ``attn_chain`` kernel (fused Pallas / unfused XLA); ``bias`` is
    the additive per-edge bias as a balanced slab shaped like ``rows``."""
    bound_fn = static[0]
    return bound_fn(rows, cols, q, k, bias, x)


def _exec_attn_fwd(static, rows, cols, q, k, bias, x):
    return _exec_attn(static, rows, cols, q, k, bias, x), (rows, cols, q, k,
                                                           bias, x)


def _exec_attn_bwd(static, res, g):
    """Recompute-and-differentiate, as in the chain backward: scores are
    VMEM-only in the forward, so W is recomputed flat and the softmax
    jacobian applied — dZ = W∘(dW − rowsum(W∘dW)), dE = scale·dZ,
    dBias = dZ (the bias enters Z additively)."""
    from .spmm import _sddmm_flat, attn_weights
    _, (m, kdim), scale = static
    rows, cols, q, k, bias, x = res
    r, c = rows.reshape(-1), cols.reshape(-1)
    valid = r < m
    rr = jnp.where(valid, r, m)
    sc = float(scale)
    e = _sddmm_flat(r, c, q, k, valid)
    bf = jnp.where(valid, bias.reshape(-1).astype(jnp.float32), 0.0)
    w = attn_weights(e, bf, r, valid, m, sc)
    g2, _ = _as_2d(g)
    x2, _ = _as_2d(x)
    gr = jnp.take(g2.astype(jnp.float32), jnp.where(valid, r, 0), axis=0)
    gr = jnp.where(valid[:, None], gr, 0.0)
    xc = jnp.take(x2.astype(jnp.float32), c, axis=0)
    dw = jnp.sum(gr * xc, axis=-1)                       # SDDMM of (G, X)
    s = jax.ops.segment_sum(w * dw, rr, num_segments=m + 1)
    dz = jnp.where(valid, w * (dw - jnp.take(s, rr)), 0.0)
    de = sc * dz
    qg = jnp.take(q.astype(jnp.float32), jnp.where(valid, r, 0), axis=0)
    kg = jnp.take(k.astype(jnp.float32), c, axis=0)
    dq = jax.ops.segment_sum(de[:, None] * kg, rr, num_segments=m + 1)[:m]
    dk = jax.ops.segment_sum(de[:, None] * qg, c, num_segments=kdim)
    dx = jax.ops.segment_sum(w[:, None] * gr, c, num_segments=kdim)
    dx = dx.reshape(x.shape).astype(x.dtype)
    dbias = dz.reshape(bias.shape).astype(
        bias.dtype if jnp.issubdtype(jnp.result_type(bias), jnp.inexact)
        else jnp.float32)
    dq, dk, dbias, dx = sanitize_grads(dq, dk, dbias, dx)
    return (_float0(rows), _float0(cols), dq.astype(q.dtype),
            dk.astype(k.dtype), dbias, dx)


_exec_attn.defvjp(_exec_attn_fwd, _exec_attn_bwd)


def _stream_to_balanced(stream: jax.Array, bal: BalancedCOO) -> jax.Array:
    """Pad the CSR-ordered nonzero stream to the tile grid (row-major order is
    preserved by construction, so this is a pure pad+reshape)."""
    flat = stream.reshape(-1)
    total = bal.n_tiles * bal.tile
    return jnp.pad(flat, (0, total - flat.shape[0])).reshape(bal.rows.shape)
