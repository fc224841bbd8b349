"""Shared neural layers for the architecture zoo — pure functions over
param pytrees (no framework dependency).

Design notes
------------
* Attention is **flash-style** (two-level block scan with online softmax):
  the S×S score matrix is never materialized, which is what makes the
  prefill_32k cells lowerable at sane memory. Pure JAX (lax.scan), so it
  lowers on any backend; a Pallas port is a recorded perf-iteration item.
* All matmuls accumulate in f32 (``preferred_element_type``) with bf16
  operands — the TPU-native mixed precision recipe.
* ``sparse_ffn_apply`` is the paper-as-a-feature: FFN weights stored as a
  BalancedCOO value stream and executed through the adaptive SpMM.
"""
from __future__ import annotations

import dataclasses
import functools
from typing import Optional

import jax
import jax.numpy as jnp
import numpy as np

from repro.api import pattern_matmul
from .sharding_ctx import constrain, constrain_gemm, sparse_shard


def rmsnorm(x: jax.Array, w: jax.Array, eps: float = 1e-5) -> jax.Array:
    x32 = x.astype(jnp.float32)
    var = jnp.mean(x32 * x32, axis=-1, keepdims=True)
    return (x32 * jax.lax.rsqrt(var + eps)).astype(x.dtype) * (1.0 + w.astype(x.dtype))


def dot(a: jax.Array, b: jax.Array) -> jax.Array:
    """bf16 x bf16 → f32-accumulated matmul, cast back to a.dtype.
    Weight-gathered in train/prefill cells (§Perf iterations 2-3)."""
    b = constrain_gemm(w=b)
    out = jnp.einsum("...ij,jk->...ik", a, b,
                     preferred_element_type=jnp.float32).astype(a.dtype)
    return constrain_gemm(out=out)


# ---------------------------------------------------------------------------
# rotary embeddings (standard + M-RoPE)
# ---------------------------------------------------------------------------

def _rope_cos_sin(positions: jax.Array, head_dim: int, theta: float):
    half = head_dim // 2
    freqs = 1.0 / (theta ** (jnp.arange(half, dtype=jnp.float32) / half))
    ang = positions[..., None].astype(jnp.float32) * freqs     # (..., S, half)
    return jnp.cos(ang), jnp.sin(ang)


def apply_rope(x: jax.Array, positions: jax.Array, theta: float) -> jax.Array:
    """x: (B, S, H, D); positions: (B, S). Half-rotation (llama) convention."""
    half = x.shape[-1] // 2
    cos, sin = _rope_cos_sin(positions, x.shape[-1], theta)    # (B, S, half)
    cos, sin = cos[:, :, None, :], sin[:, :, None, :]
    x1, x2 = x[..., :half].astype(jnp.float32), x[..., half:].astype(jnp.float32)
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin],
                           axis=-1).astype(x.dtype)


def apply_mrope(x: jax.Array, positions3: jax.Array, sections: tuple,
                theta: float) -> jax.Array:
    """Qwen2-VL M-RoPE. positions3: (B, S, 3) = (t, h, w) ids; ``sections``
    split head_dim//2 among the three. For text, t==h==w == position."""
    half = x.shape[-1] // 2
    assert sum(sections) == half, (sections, half)
    freqs = 1.0 / (theta ** (jnp.arange(half, dtype=jnp.float32) / half))
    # per-frequency section id → which of (t,h,w) drives it
    sec_id = jnp.repeat(jnp.arange(3), jnp.asarray(sections),
                        total_repeat_length=half)
    pos = jnp.take_along_axis(
        positions3.astype(jnp.float32),
        jnp.broadcast_to(sec_id[None, None, :], positions3.shape[:2] + (half,)),
        axis=-1)                                               # (B, S, half)
    ang = pos * freqs[None, None, :]
    cos, sin = jnp.cos(ang)[:, :, None, :], jnp.sin(ang)[:, :, None, :]
    x1, x2 = x[..., :half].astype(jnp.float32), x[..., half:].astype(jnp.float32)
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin],
                           axis=-1).astype(x.dtype)


# ---------------------------------------------------------------------------
# flash attention (pure JAX, block-scan online softmax)
# ---------------------------------------------------------------------------

def flash_attention(q: jax.Array, k: jax.Array, v: jax.Array, *,
                    causal: bool = True, window: int = 0,
                    q_offset: int | jax.Array = 0,
                    q_block: int = 512, kv_block: int = 1024) -> jax.Array:
    """q: (B, Hq, Sq, D), k/v: (B, Hk, Sk, D) with Hq % Hk == 0.

    Scans KV blocks per Q block carrying (max, sum, acc) — O(Sq·kv_block)
    live memory. ``window > 0`` adds sliding-window masking (local layers).
    ``q_offset``: absolute position of q[0] (prefill continuation / decode).
    """
    b, hq, sq, d = q.shape
    hk, sk = k.shape[1], k.shape[2]
    rep = hq // hk
    scale = 1.0 / np.sqrt(d)

    # §Perf iteration 1 (see EXPERIMENTS.md): pin attention to pure batch
    # sharding.  Unconstrained GSPMD sharded the score contraction over
    # `data` → an f32 all-reduce inside the q/kv scans (13 TB/dev on
    # prefill_32k).  Batch-pinned, the scans are collective-free.
    q = constrain(q, ("batch", None, None, None))
    k = constrain(k, ("batch", None, None, None))
    v = constrain(v, ("batch", None, None, None))

    q_block = min(q_block, sq)
    kv_block = min(kv_block, sk)
    nq = -(-sq // q_block)
    nk = -(-sk // kv_block)
    sq_p, sk_p = nq * q_block, nk * kv_block
    if sq_p != sq:
        q = jnp.pad(q, ((0, 0), (0, 0), (0, sq_p - sq), (0, 0)))
    if sk_p != sk:
        k = jnp.pad(k, ((0, 0), (0, 0), (0, sk_p - sk), (0, 0)))
        v = jnp.pad(v, ((0, 0), (0, 0), (0, sk_p - sk), (0, 0)))

    kq = k.reshape(b, hk, 1, nk, kv_block, d)
    vq = v.reshape(b, hk, 1, nk, kv_block, d)

    def per_qblock(qi, qb):
        # qb: (B, Hq, q_block, D) grouped → (B, Hk, rep*q_block? ) keep (B,Hk,rep,qblock,D)
        qg = qb.reshape(b, hk, rep, q_block, d).astype(jnp.float32) * scale
        q_pos = q_offset + qi * q_block + jnp.arange(q_block)

        def kv_step(carry, ki):
            m, l, acc = carry
            kb = jax.lax.dynamic_index_in_dim(kq, ki, axis=3, keepdims=False)
            vb = jax.lax.dynamic_index_in_dim(vq, ki, axis=3, keepdims=False)
            s = jnp.einsum("bhrqd,bhzkd->bhrqk", qg, kb.astype(jnp.float32),
                           preferred_element_type=jnp.float32)  # z==1 folded
            k_pos = ki * kv_block + jnp.arange(kv_block)
            mask = jnp.ones((q_block, kv_block), bool)
            mask &= (k_pos[None, :] < sk)                       # kv padding
            if causal:
                mask &= (k_pos[None, :] <= q_pos[:, None])
            if window > 0:
                mask &= (k_pos[None, :] > q_pos[:, None] - window)
            s = jnp.where(mask[None, None, None], s, -1e30)
            m_new = jnp.maximum(m, s.max(axis=-1))
            p = jnp.exp(s - m_new[..., None])
            corr = jnp.exp(m - m_new)
            l_new = l * corr + p.sum(axis=-1)
            acc_new = acc * corr[..., None] + jnp.einsum(
                "bhrqk,bhzkd->bhrqd", p, vb.astype(jnp.float32),
                preferred_element_type=jnp.float32)
            return (m_new, l_new, acc_new), None

        m0 = jnp.full((b, hk, rep, q_block), -jnp.inf, jnp.float32)
        l0 = jnp.zeros((b, hk, rep, q_block), jnp.float32)
        a0 = jnp.zeros((b, hk, rep, q_block, d), jnp.float32)
        (m, l, acc), _ = jax.lax.scan(kv_step, (m0, l0, a0), jnp.arange(nk))
        out = acc / jnp.maximum(l, 1e-30)[..., None]
        return out.reshape(b, hq, q_block, d)

    if nq == 1:
        out = per_qblock(0, q)
    else:
        qs = q.reshape(b, hq, nq, q_block, d).transpose(2, 0, 1, 3, 4)
        out = jax.lax.map(lambda args: per_qblock(args[0], args[1]),
                          (jnp.arange(nq), qs))
        out = out.transpose(1, 2, 0, 3, 4).reshape(b, hq, sq_p, d)
    return out[:, :, :sq].astype(q.dtype)


def decode_attention(q: jax.Array, k_cache: jax.Array, v_cache: jax.Array, *,
                     length: jax.Array | int, window: int = 0) -> jax.Array:
    """Single-token attention against a cache. q: (B, Hq, 1, D),
    k/v_cache: (B, Hk, L, D); ``length`` = #valid cache entries (the new
    token is already written at length-1)."""
    b, hq, _, d = q.shape
    hk, lmax = k_cache.shape[1], k_cache.shape[2]
    rep = hq // hk
    q = constrain(q, ("batch", None, None, None))
    # caches keep their input sharding (cache_seq over model: split-KV)
    qg = q.reshape(b, hk, rep, d).astype(jnp.float32) / np.sqrt(d)
    s = jnp.einsum("bhrd,bhld->bhrl", qg, k_cache.astype(jnp.float32),
                   preferred_element_type=jnp.float32)
    pos = jnp.arange(lmax)
    mask = pos[None, :] < length if jnp.ndim(length) == 0 else pos[None, :] < length[:, None]
    if window > 0:
        lo = (length if jnp.ndim(length) == 0 else length[:, None]) - window
        mask = mask & (pos[None, :] >= lo)
    s = jnp.where(mask[:, None, None, :] if mask.ndim == 2 else mask[None, None, None, :],
                  s, -1e30)
    p = jax.nn.softmax(s, axis=-1)
    out = jnp.einsum("bhrl,bhld->bhrd", p, v_cache.astype(jnp.float32),
                     preferred_element_type=jnp.float32)
    return out.reshape(b, hq, 1, d).astype(q.dtype)


# ---------------------------------------------------------------------------
# MLPs — dense and sparse (the paper's feature)
# ---------------------------------------------------------------------------

def mlp_apply(p: dict, x: jax.Array, act: str = "swiglu") -> jax.Array:
    if act == "swiglu":
        h = jax.nn.silu(dot(x, p["w_gate"])) * dot(x, p["w_up"])
    else:
        h = jax.nn.gelu(dot(x, p["w_up"]))
    return dot(h, p["w_down"])


@dataclasses.dataclass(frozen=True)
class SparsePattern:
    """Static (non-trainable) sparsity pattern of one pruned weight matrix,
    in BalancedCOO layout. rows/cols: (n_tiles, tile) int32.  ``prep``: the
    pattern's precomputed kernel prep opts (``core/plan.pattern_prep``: the
    fused kernel's visit schedule and the transposed pattern the kernel
    backward runs on), so a scanned — traced — pattern still reaches the
    Pallas kernels forward and backward (``execute_pattern(prep=...)``)."""
    rows: jax.Array
    cols: jax.Array
    shape: tuple
    prep: Optional[dict] = None

    @staticmethod
    def random(key, m: int, k: int, density: float, tile: int) -> "SparsePattern":
        nnz = max(int(m * k * density), 1)
        rng = np.random.default_rng(int(jax.random.randint(key, (), 0, 2**31 - 1)))
        flat = rng.choice(m * k, size=nnz, replace=False)
        flat.sort()
        rows, cols = (flat // k).astype(np.int32), (flat % k).astype(np.int32)
        n_tiles = -(-nnz // tile)
        pad = n_tiles * tile - nnz
        rows = np.concatenate([rows, np.full(pad, m, np.int32)])
        cols = np.concatenate([cols, np.zeros(pad, np.int32)])
        return SparsePattern(jnp.asarray(rows.reshape(n_tiles, tile)),
                             jnp.asarray(cols.reshape(n_tiles, tile)), (m, k))


def sparse_matmul(pattern: SparsePattern, vals: jax.Array, x: jax.Array, *,
                  mesh=None, shard_axis: str | None = None) -> jax.Array:
    """x @ W^T with W (m, k) sparse: computed as SpMM W · x^T through the
    unified plan/execute front door (differentiable w.r.t. vals and x).

    With a ``mesh`` (passed, or installed via the sharding ctx's
    ``__sparse_shard_axis__`` marker) the SpMM runs on the sharded backend:
    the pattern's tiles — fixed-nnz quotas — split across the axis and the
    partial products psum (core/shard.py)."""
    if mesh is None:
        mesh, shard_axis = sparse_shard()
    flat = x.reshape(-1, x.shape[-1])                           # (T, k)
    y = pattern_matmul(pattern.rows, pattern.cols, vals,
                       tuple(pattern.shape), flat.T,
                       mesh=mesh, shard_axis=shard_axis,
                       prep=None if mesh is not None else pattern.prep)  # (m, T)
    return y.T.reshape(x.shape[:-1] + (pattern.shape[0],)).astype(x.dtype)


def sparse_mlp_apply(patterns: dict, p: dict, x: jax.Array,
                     act: str = "swiglu") -> jax.Array:
    """FFN with pruned weight matrices executed through the paper's SpMM."""
    if act == "swiglu":
        h = (jax.nn.silu(sparse_matmul(patterns["gate"], p["v_gate"], x))
             * sparse_matmul(patterns["up"], p["v_up"], x))
    else:
        h = jax.nn.gelu(sparse_matmul(patterns["up"], p["v_up"], x))
    return sparse_matmul(patterns["down"], p["v_down"], h)
