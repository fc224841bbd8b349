"""Architecture assembly: param specs, block forwards, and the family
dispatch that turns a ModelConfig into train/prefill/decode functions.

Layer stacking uses ``lax.scan`` over stacked params (MaxText-style) so the
HLO stays one-block-sized regardless of depth — this is what keeps the 61-
and 80-layer dry-run compiles tractable and is also the substrate XLA uses
to overlap FSDP all-gathers with the previous layer's compute.

Heterogeneous-pattern families scan over *groups*:
  gemma3   groups of (5 local + 1 global) attention layers
  zamba2   groups of (shared_every mamba layers + 1 shared-weight attn block)
"""
from __future__ import annotations

import dataclasses
import functools
from typing import Any, Callable, Optional

import jax
import jax.numpy as jnp
import numpy as np

from .config import ModelConfig, MoEConfig, SSMConfig
from .layers import (SparsePattern, apply_mrope, apply_rope, decode_attention,
                     dot, flash_attention, mlp_apply, rmsnorm,
                     sparse_mlp_apply)
from .moe import moe_apply
from .params import ParamSpec
from .rwkv import rwkv6_channel_mix, rwkv6_time_mix
from .ssm import mamba2_mix

P = ParamSpec


# ---------------------------------------------------------------------------
# param specs
# ---------------------------------------------------------------------------

def _dt(cfg):
    return jnp.dtype(cfg.param_dtype)


def attn_specs(cfg: ModelConfig, cross: bool = False) -> dict:
    d, hd = cfg.d_model, cfg.head_dim
    h, hk = cfg.num_heads, cfg.num_kv_heads
    s = {
        "ln": P((d,), ("embed",), _dt(cfg), "zeros"),
        "wq": P((d, h * hd), ("embed", "heads"), _dt(cfg)),
        "wk": P((d, hk * hd), ("embed", "heads"), _dt(cfg)),
        "wv": P((d, hk * hd), ("embed", "heads"), _dt(cfg)),
        "wo": P((h * hd, d), ("heads", "embed"), _dt(cfg)),
    }
    del cross
    return s


def mlp_specs(cfg: ModelConfig) -> dict:
    d, f = cfg.d_model, cfg.d_ff
    if cfg.sparse_ffn is not None:
        sp = cfg.sparse_ffn
        tiles = lambda m, k: -(-max(int(m * k * sp.density), 1) // sp.tile)
        return {
            "ln": P((d,), ("embed",), _dt(cfg), "zeros"),
            "v_gate": P((tiles(f, d), sp.tile), ("tiles", "nnz"), _dt(cfg), scale=0.02),
            "v_up": P((tiles(f, d), sp.tile), ("tiles", "nnz"), _dt(cfg), scale=0.02),
            "v_down": P((tiles(d, f), sp.tile), ("tiles", "nnz"), _dt(cfg), scale=0.02),
        }
    s = {
        "ln": P((d,), ("embed",), _dt(cfg), "zeros"),
        "w_up": P((d, f), ("embed", "ff"), _dt(cfg)),
        "w_down": P((f, d), ("ff", "embed"), _dt(cfg)),
    }
    if cfg.act == "swiglu":
        s["w_gate"] = P((d, f), ("embed", "ff"), _dt(cfg))
    return s


def moe_specs(cfg: ModelConfig) -> dict:
    d, m = cfg.d_model, cfg.moe
    return {
        "ln": P((d,), ("embed",), _dt(cfg), "zeros"),
        "w_router": P((d, m.num_experts), ("embed", None), jnp.float32, scale=0.02),
        "w_gate": P((m.num_experts, d, m.d_ff_expert), ("experts", "embed", "ff"), _dt(cfg)),
        "w_up": P((m.num_experts, d, m.d_ff_expert), ("experts", "embed", "ff"), _dt(cfg)),
        "w_down": P((m.num_experts, m.d_ff_expert, d), ("experts", "ff", "embed"), _dt(cfg)),
    }


def mamba_specs(cfg: ModelConfig) -> dict:
    d, s = cfg.d_model, cfg.ssm
    di = s.expand * d
    n = s.d_state
    h = di // s.head_dim
    zdim = 2 * di + 2 * n + h
    return {
        "ln": P((d,), ("embed",), _dt(cfg), "zeros"),
        "w_in": P((d, zdim), ("embed", "ssm_in"), _dt(cfg)),
        "w_conv": P((s.conv_width, di + 2 * n), (None, "ssm_in"), _dt(cfg), scale=0.5),
        "dt_bias": P((h,), (None,), jnp.float32, "zeros"),
        "a_log": P((h,), (None,), jnp.float32, "zeros"),
        "d_skip": P((h,), (None,), jnp.float32, "ones"),
        "norm_w": P((di,), ("ssm_in",), _dt(cfg), "zeros"),
        "w_out": P((di, d), ("ssm_in", "embed"), _dt(cfg)),
    }


def rwkv_specs(cfg: ModelConfig) -> dict:
    d, f = cfg.d_model, cfg.d_ff
    r = 64  # decay-LoRA rank
    mus = {f"mu_{k}": P((d,), ("embed",), _dt(cfg), "zeros") for k in "rkvwg"}
    return {
        "ln1": P((d,), ("embed",), _dt(cfg), "zeros"),
        **mus,
        "w_r": P((d, d), ("embed", "heads"), _dt(cfg)),
        "w_k": P((d, d), ("embed", "heads"), _dt(cfg)),
        "w_v": P((d, d), ("embed", "heads"), _dt(cfg)),
        "w_g": P((d, d), ("embed", "heads"), _dt(cfg)),
        "w_decay_a": P((d, r), ("embed", None), _dt(cfg), scale=0.02),
        "w_decay_b": P((r, d), (None, "heads"), _dt(cfg), scale=0.02),
        "w0": P((d,), ("heads",), jnp.float32, "zeros"),
        "u_bonus": P((d,), ("heads",), jnp.float32, "zeros"),
        "ln_w": P((d,), ("heads",), jnp.float32, "ones"),
        "ln_b": P((d,), ("heads",), jnp.float32, "zeros"),
        "w_o": P((d, d), ("heads", "embed"), _dt(cfg)),
        "ln2": P((d,), ("embed",), _dt(cfg), "zeros"),
        "mu_ck": P((d,), ("embed",), _dt(cfg), "zeros"),
        "mu_cr": P((d,), ("embed",), _dt(cfg), "zeros"),
        "w_ck": P((d, f), ("embed", "ff"), _dt(cfg)),
        "w_cv": P((f, d), ("ff", "embed"), _dt(cfg)),
        "w_cr": P((d, d), ("embed", None), _dt(cfg)),
    }


def block_specs(cfg: ModelConfig, cross: bool = False) -> dict:
    """One decoder block for the family."""
    if cfg.family == "ssm" and cfg.ssm.kind == "rwkv6":
        return rwkv_specs(cfg)
    if cfg.family in ("ssm", "hybrid") and cfg.ssm and cfg.ssm.kind == "mamba2":
        return mamba_specs(cfg)
    s = {"attn": attn_specs(cfg)}
    if cross:
        s["xattn"] = attn_specs(cfg, cross=True)
    s["ffn"] = moe_specs(cfg) if cfg.moe else mlp_specs(cfg)
    return s


def _stack(specs: dict, n: int, axis_name: str) -> dict:
    return jax.tree_util.tree_map(
        lambda p: P((n,) + p.shape, (axis_name,) + p.logical, p.dtype, p.init, p.scale),
        specs, is_leaf=lambda x: isinstance(x, P))


def model_specs(cfg: ModelConfig) -> dict:
    d, v = cfg.d_model, cfg.vocab_size
    specs: dict = {
        "embed": P((v, d), ("vocab", "embed"), _dt(cfg), scale=0.02),
        "final_ln": P((d,), ("embed",), _dt(cfg), "zeros"),
    }
    if not cfg.tie_embeddings:
        specs["lm_head"] = P((d, v), ("embed", "vocab"), _dt(cfg), scale=0.02)

    if cfg.family == "audio":  # whisper enc-dec
        specs["enc_blocks"] = _stack(
            {"attn": attn_specs(cfg), "ffn": mlp_specs(cfg)}, cfg.encoder_layers, "layers")
        specs["enc_final_ln"] = P((d,), ("embed",), _dt(cfg), "zeros")
        specs["dec_blocks"] = _stack(block_specs(cfg, cross=True), cfg.num_layers, "layers")
        return specs

    if cfg.attn_pattern == "local_global":  # gemma3 grouped
        inner = cfg.local_per_global + 1
        groups = cfg.num_layers // inner
        specs["blocks"] = _stack(_stack(block_specs(cfg), inner, "inner"), groups, "groups")
        return specs

    if cfg.family == "hybrid":  # zamba2 grouped: shared_every mamba + shared attn
        groups = cfg.num_layers // cfg.shared_every
        specs["blocks"] = _stack(_stack(mamba_specs(cfg), cfg.shared_every, "inner"),
                                 groups, "groups")
        specs["shared_attn"] = {"attn": attn_specs(cfg), "ffn": mlp_specs(cfg)}
        return specs

    specs["blocks"] = _stack(block_specs(cfg), cfg.num_layers, "layers")
    return specs


def sparse_patterns(cfg: ModelConfig, seed: int = 17):
    """Static pruning patterns for sparse_ffn (one per layer, stacked).  Each
    stack carries the Pallas kernels' prep for every layer, its transpose
    included (``core/plan.pattern_prep``): scanned with the patterns, each
    layer's slice is its own prep."""
    if cfg.sparse_ffn is None:
        return None
    from repro.core.plan import pattern_prep
    sp = cfg.sparse_ffn
    d, f = cfg.d_model, cfg.d_ff
    keys = jax.random.split(jax.random.PRNGKey(seed), 3 * cfg.num_layers)
    pats = {"gate": [], "up": [], "down": []}
    for i in range(cfg.num_layers):
        pats["gate"].append(SparsePattern.random(keys[3 * i], f, d, sp.density, sp.tile))
        pats["up"].append(SparsePattern.random(keys[3 * i + 1], f, d, sp.density, sp.tile))
        pats["down"].append(SparsePattern.random(keys[3 * i + 2], d, f, sp.density, sp.tile))

    def stack(ps):
        rows = np.stack([np.asarray(p.rows) for p in ps])
        cols = np.stack([np.asarray(p.cols) for p in ps])
        return SparsePattern(jnp.asarray(rows), jnp.asarray(cols), ps[0].shape,
                             prep=pattern_prep(rows, cols, ps[0].shape))
    return {k: stack(v) for k, v in pats.items()}


# ---------------------------------------------------------------------------
# block forwards
# ---------------------------------------------------------------------------

def _split_heads(x, n, hd):
    b, s, _ = x.shape
    return x.reshape(b, s, n, hd)


def _block_sparse_spec(cfg: ModelConfig, seq: int, causal: bool):
    """The attention mask spec a block_sparse config implies at this
    sequence length: token window → block band (BigBird when global/random
    blocks are configured), dense-fallback blocks when no window is set.
    Specs are frozen and hashable, so every layer/head/call at one seq
    shares a single PlanCache entry."""
    from repro.attention import bigbird, dense_attention, sliding_window
    block = cfg.attn_block or 64
    if cfg.window > 0:
        wb = -(-cfg.window // block)  # token window, ceil to blocks
        if cfg.attn_global_blocks or cfg.attn_random_blocks:
            return bigbird(seq, wb, cfg.attn_global_blocks,
                           cfg.attn_random_blocks, block=block, causal=causal)
        return sliding_window(seq, wb, block=block, causal=causal)
    return dense_attention(seq, block=block, causal=causal)


def _block_sparse_attention(qt, kt, vt, cfg: ModelConfig, causal: bool):
    """Train/prefill attention through the fused sparse-softmax chain
    (DESIGN.md §10).  qt (B, H, S, hd), kt/vt (B, Hk, S, hd) → (B, H, S, hd);
    GQA repeats KV heads to match, the spec's plan is built once at trace
    time and shared across the whole (B, H) fan-out."""
    from repro.attention import sparse_attention
    b, h, s, hd = qt.shape
    hk = kt.shape[1]
    if h != hk:
        rep = h // hk
        kt = jnp.repeat(kt, rep, axis=1)
        vt = jnp.repeat(vt, rep, axis=1)
    spec = _block_sparse_spec(cfg, s, causal)
    out = sparse_attention(spec, qt.astype(jnp.float32),
                           kt.astype(jnp.float32), vt.astype(jnp.float32))
    return out.astype(qt.dtype)


def attn_apply(p: dict, x: jax.Array, cfg: ModelConfig, *, positions,
               cache=None, window: int = 0, causal: bool = True,
               memory=None, rope: bool = True):
    """Self- or cross-attention with optional KV cache.

    cache: dict(k, v, length) with k/v (B, Hk, L, hd); returns updated cache.
    memory: (B, Sm, D) for cross-attention (keys/values from memory).
    """
    b, s, _ = x.shape
    h, hk, hd = cfg.num_heads, cfg.num_kv_heads, cfg.head_dim
    xn = rmsnorm(x, p["ln"], cfg.norm_eps)
    q = _split_heads(dot(xn, p["wq"]), h, hd)
    kv_src = memory if memory is not None else xn
    k = _split_heads(dot(kv_src, p["wk"]), hk, hd)
    v = _split_heads(dot(kv_src, p["wv"]), hk, hd)

    if rope and memory is None:
        if cfg.mrope_sections:
            pos3 = jnp.broadcast_to(positions[..., None], positions.shape + (3,))
            q = apply_mrope(q, pos3, cfg.mrope_sections, cfg.rope_theta)
            k = apply_mrope(k, pos3, cfg.mrope_sections, cfg.rope_theta)
        else:
            q = apply_rope(q, positions, cfg.rope_theta)
            k = apply_rope(k, positions, cfg.rope_theta)

    qt = q.transpose(0, 2, 1, 3)
    kt = k.transpose(0, 2, 1, 3)
    vt = v.transpose(0, 2, 1, 3)

    if memory is not None:
        # cross-attention: no cache, full (non-causal) memory attention
        if s == 1:
            out = decode_attention(qt, kt, vt, length=kt.shape[2])
        else:
            out = flash_attention(qt, kt, vt, causal=False)
    elif cache is not None:
        lmax = cache["k"].shape[2]
        if s == 1:  # decode: rolling write for window caches
            idx = cache["length"] % lmax if window > 0 else cache["length"]
            if jnp.ndim(idx) == 0:
                newk = jax.lax.dynamic_update_slice_in_dim(cache["k"], kt.astype(cache["k"].dtype), idx, axis=2)
                newv = jax.lax.dynamic_update_slice_in_dim(cache["v"], vt.astype(cache["v"].dtype), idx, axis=2)
            else:
                # per-slot lengths (batched serving): each lane writes at its
                # own position; decode_attention masks each lane to its own
                # valid length below
                upd = jax.vmap(lambda c, u, i: jax.lax.dynamic_update_slice_in_dim(
                    c, u, i, axis=1))
                newk = upd(cache["k"], kt.astype(cache["k"].dtype), idx)
                newv = upd(cache["v"], vt.astype(cache["v"].dtype), idx)
            length = cache["length"] + 1
            valid = jnp.minimum(length, lmax) if window > 0 else length
            out = decode_attention(qt, newk, newv, length=valid, window=0)
            cache = dict(k=newk, v=newv, length=length)
        else:       # prefill: write the (rolled) suffix; slot of pos p = p % lmax
            keep = min(s, lmax)
            tail_k, tail_v = kt[:, :, -keep:], vt[:, :, -keep:]
            shift = (s - keep) % lmax
            if shift:
                tail_k = jnp.roll(tail_k, shift, axis=2)
                tail_v = jnp.roll(tail_v, shift, axis=2)
            newk = jax.lax.dynamic_update_slice_in_dim(
                cache["k"], tail_k.astype(cache["k"].dtype), 0, axis=2)
            newv = jax.lax.dynamic_update_slice_in_dim(
                cache["v"], tail_v.astype(cache["v"].dtype), 0, axis=2)
            cache = dict(k=newk, v=newv, length=cache["length"] + s)
            if cfg.attn_pattern == "block_sparse":
                out = _block_sparse_attention(qt, kt, vt, cfg, causal)
            else:
                out = flash_attention(qt, kt, vt, causal=causal, window=window)
    elif cfg.attn_pattern == "block_sparse":
        out = _block_sparse_attention(qt, kt, vt, cfg, causal)
    else:
        out = flash_attention(qt, kt, vt, causal=causal, window=window)

    out = out.transpose(0, 2, 1, 3).reshape(b, s, h * hd)
    return x + dot(out, p["wo"]), cache


def ffn_apply(p: dict, x: jax.Array, cfg: ModelConfig, patterns=None):
    xn = rmsnorm(x, p["ln"], cfg.norm_eps)
    if cfg.moe is not None and "w_router" in p:
        y, aux = moe_apply(p, xn, cfg.moe)
        return x + y, aux
    if cfg.sparse_ffn is not None and patterns is not None:
        return x + sparse_mlp_apply(patterns, p, xn, cfg.act), 0.0
    return x + mlp_apply(p, xn, cfg.act), 0.0


def dense_block_apply(p: dict, x, cfg, *, positions, cache=None, window=0,
                      causal=True, patterns=None):
    from .sharding_ctx import constrain
    x = constrain(x, ("batch", None, None))
    x, cache = attn_apply(p["attn"], x, cfg, positions=positions,
                          cache=cache, window=window, causal=causal)
    x = constrain(x, ("batch", None, None))
    x, aux = ffn_apply(p["ffn"], x, cfg, patterns=patterns)
    return x, cache, aux
