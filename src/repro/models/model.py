"""Model: turns a ModelConfig into concrete train/prefill/decode functions.

All functions are pure (params/caches in, values out) and jit/pjit-ready.
Cache layout per family:

  dense/moe/vlm  {"kv": {k,v: (L, B, Hk, Lmax, hd), length}}
  gemma3         {"local": (G, inner-1, B, Hk, window, hd)...,
                  "global": (G, 1, B, Hk, Lmax, hd)..., length}
  hybrid(zamba2) {"ssm": (G, inner, B, H, N, P), "conv": (G, inner, B, W-1, C),
                  "kv": (G, B, Hk, Lmax, hd)..., length}
  ssm(rwkv6)     {"wkv": (L, B, H, N, N), "tm_prev"/"cm_prev": (L, B, D), length}
  audio(whisper) {"kv": dec self (L, ...), "memory": (B, Sm, D), length}
"""
from __future__ import annotations

import dataclasses
import functools
from typing import Any, Optional

import jax
import jax.numpy as jnp
import numpy as np

from .config import ModelConfig
from .layers import dot, flash_attention, rmsnorm
from .model_loss import lm_loss  # noqa: F401  (split for file size)
from .params import ParamSpec, init_params
from .rwkv import rwkv6_channel_mix, rwkv6_time_mix
from .ssm import mamba2_mix
from .transformer import (attn_apply, dense_block_apply, ffn_apply,
                          model_specs, sparse_patterns)


def _remat(cfg, fn):
    if cfg.remat == "none":
        return fn
    policy = (jax.checkpoint_policies.nothing_saveable if cfg.remat == "full"
              else jax.checkpoint_policies.dots_with_no_batch_dims_saveable)
    return jax.checkpoint(fn, policy=policy)


def _tree_idx(tree, i):
    return jax.tree_util.tree_map(lambda a: a[i], tree)


@dataclasses.dataclass
class Model:
    cfg: ModelConfig

    def __post_init__(self):
        self.specs = model_specs(self.cfg)
        self.patterns = sparse_patterns(self.cfg)

    # ------------------------------------------------------------------ init
    def init(self, rng: jax.Array):
        return init_params(rng, self.specs)

    # ------------------------------------------------------------- embedding
    def _embed(self, params, tokens):
        from .sharding_ctx import constrain
        x = jnp.take(params["embed"], tokens, axis=0)
        if self.cfg.attn_pattern == "local_global":        # gemma convention
            x = x * jnp.asarray(np.sqrt(self.cfg.d_model), x.dtype)
        return constrain(x.astype(jnp.dtype(self.cfg.compute_dtype)),
                         ("batch", None, None))

    def _unembed_w(self, params):
        return (params["embed"].T if self.cfg.tie_embeddings
                else params["lm_head"])

    # ------------------------------------------------------------- backbones
    def _backbone_uniform(self, params, x, positions, caches=None,
                          sparse_inputs=None):
        """dense/moe/vlm stack via lax.scan; caches scanned when present.
        ``sparse_inputs``: the patterns' per-layer arrays as call operands
        (``sparse_inputs()``); by default they are closed over."""
        cfg = self.cfg
        pats = self.patterns
        pat_xs = (None if pats is None else
                  sparse_inputs if sparse_inputs is not None
                  else _pat_leaves(pats))

        def body(carry, xs):
            x, aux = carry
            if caches is not None and pats is not None:
                p, cache, pat = xs
            elif caches is not None:
                p, cache = xs
                pat = None
            elif pats is not None:
                p, pat = xs
                cache = None
            else:
                p, cache, pat = xs, None, None
            patd = ({name: dataclasses.replace(
                        pats[name], rows=pat[name][0], cols=pat[name][1],
                        prep={**pats[name].prep, **pat[name][2]})
                     for name in ("gate", "up", "down")}
                    if pat is not None else None)
            if cache is not None:
                cache = dict(cache, length=caches["length"])
            x, cache, a = dense_block_apply(p, x, cfg, positions=positions,
                                            cache=cache, patterns=patd)
            if cache is not None:
                cache.pop("length")
            return (x, aux + a), cache

        body = _remat(cfg, body) if caches is None else body
        xs: Any = params["blocks"]
        if caches is not None and pats is not None:
            xs = (xs, caches["kv"], pat_xs)
        elif caches is not None:
            xs = (xs, caches["kv"])
        elif pats is not None:
            xs = (xs, pat_xs)
        (x, aux), new_kv = jax.lax.scan(body, (x, 0.0), xs)
        new_caches = None
        if caches is not None:
            new_caches = dict(caches, kv=new_kv,
                              length=caches["length"] + x.shape[1])
        return x, new_caches, aux

    def _backbone_gemma(self, params, x, positions, caches=None):
        cfg = self.cfg
        inner = cfg.local_per_global + 1

        def body(carry, xs):
            x = carry
            if caches is None:
                pg = xs
                lc = gc = None
            else:
                pg, lc, gc = xs
            new_lc, new_gc = [], []
            for i in range(inner):
                is_global = (i == inner - 1)
                window = 0 if is_global else cfg.window
                cache = None
                if caches is not None:
                    cache = _tree_idx(gc, 0) if is_global else _tree_idx(lc, i)
                    cache = dict(cache, length=caches["length"])
                xi, cache, _ = dense_block_apply(
                    _tree_idx(pg, i), x, cfg, positions=positions,
                    cache=cache, window=window)
                x = xi
                if caches is not None:
                    cache.pop("length")
                    (new_gc if is_global else new_lc).append(cache)
            out = None
            if caches is not None:
                out = (jax.tree_util.tree_map(lambda *a: jnp.stack(a), *new_lc)
                       if len(new_lc) > 1 else
                       jax.tree_util.tree_map(lambda a: a[None], new_lc[0]),
                       jax.tree_util.tree_map(lambda a: a[None], new_gc[0]))
            return x, out

        body = _remat(cfg, body) if caches is None else body
        if caches is None:
            x, _ = jax.lax.scan(body, x, params["blocks"])
            return x, None, 0.0
        x, (new_lc, new_gc) = jax.lax.scan(
            body, x, (params["blocks"], caches["local"], caches["global"]))
        s = x.shape[1]
        new = dict(caches)
        new["local"], new["global"] = new_lc, new_gc
        new["length"] = caches["length"] + s
        return x, new, 0.0

    def _backbone_zamba(self, params, x, positions, caches=None):
        cfg = self.cfg
        inner = cfg.shared_every
        decode = x.shape[1] == 1 and caches is not None
        shared_p = params["shared_attn"]

        def body(carry, xs):
            x = carry
            if caches is None:
                pg = xs
                ssm_g = conv_g = kv_g = None
            else:
                pg, ssm_g, conv_g, kv_g = xs
            new_ssm, new_conv = [], []
            for i in range(inner):
                pi = _tree_idx(pg, i)
                st = None if caches is None else _tree_idx(ssm_g, i)
                cv = None if caches is None else _tree_idx(conv_g, i)
                y, (st, cv) = mamba2_mix(
                    pi, rmsnorm(x, pi["ln"], cfg.norm_eps), cfg.ssm, cfg.d_model,
                    state=st, conv_cache=cv, decode=decode)
                x = x + y
                if caches is not None:
                    new_ssm.append(st)
                    new_conv.append(cv)
            kv = None if caches is None else dict(kv_g, length=caches["length"])
            x, kv, _ = dense_block_apply(shared_p, x, cfg, positions=positions,
                                         cache=kv)
            if caches is None:
                return x, None
            kv.pop("length")
            return x, (jnp.stack(new_ssm), jnp.stack(new_conv), kv)

        body = _remat(cfg, body) if caches is None else body
        if caches is None:
            x, _ = jax.lax.scan(body, x, params["blocks"])
            return x, None, 0.0
        x, (new_ssm, new_conv, new_kv) = jax.lax.scan(
            body, x, (params["blocks"], caches["ssm"], caches["conv"], caches["kv"]))
        s = x.shape[1]
        return x, dict(caches, ssm=new_ssm, conv=new_conv, kv=new_kv,
                       length=caches["length"] + s), 0.0

    def _backbone_rwkv(self, params, x, positions, caches=None):
        cfg = self.cfg

        def body(carry, xs):
            x = carry
            if caches is None:
                p = xs
                wkv = tm_prev = cm_prev = None
            else:
                p, wkv, tm_prev, cm_prev = xs
            xn = rmsnorm(x, p["ln1"], cfg.norm_eps)
            y, (wkv, tm_prev) = rwkv6_time_mix(p, xn, cfg.num_heads,
                                               state=wkv, x_prev=tm_prev)
            x = x + y
            xn = rmsnorm(x, p["ln2"], cfg.norm_eps)
            y, cm_prev = rwkv6_channel_mix(p, xn, x_prev=cm_prev)
            x = x + y
            return x, None if caches is None else (wkv, tm_prev, cm_prev)

        body = _remat(cfg, body) if caches is None else body
        if caches is None:
            x, _ = jax.lax.scan(body, x, params["blocks"])
            return x, None, 0.0
        x, (wkv, tm, cm) = jax.lax.scan(
            body, x, (params["blocks"], caches["wkv"], caches["tm_prev"],
                      caches["cm_prev"]))
        s = x.shape[1]
        return x, dict(caches, wkv=wkv, tm_prev=tm, cm_prev=cm,
                       length=caches["length"] + s), 0.0

    def _encode_audio(self, params, frames):
        """Whisper encoder over stubbed frame embeddings (B, Sm, D)."""
        cfg = self.cfg
        x = frames.astype(jnp.dtype(cfg.compute_dtype))
        pos = jnp.arange(x.shape[1])[None]
        x = x + _sinusoid(x.shape[1], cfg.d_model, x.dtype)[None]
        for i in range(cfg.encoder_layers):
            p = _tree_idx(params["enc_blocks"], i)
            x, _, _ = dense_block_apply(p, x, cfg, positions=pos, causal=False)
        return rmsnorm(x, params["enc_final_ln"], cfg.norm_eps)

    def _backbone_whisper(self, params, x, positions, caches=None, memory=None):
        cfg = self.cfg
        x = x + _sinusoid_at(positions, cfg.d_model, x.dtype)
        new_kv = []
        for i in range(cfg.num_layers):
            p = _tree_idx(params["dec_blocks"], i)
            kv = None
            if caches is not None:
                kv = dict(_tree_idx(caches["kv"], i), length=caches["length"])
            x, kv = attn_apply(p["attn"], x, cfg, positions=positions,
                               cache=kv, rope=False)
            x, _ = attn_apply(p["xattn"], x, cfg, positions=positions,
                              memory=memory, rope=False)
            x, _ = ffn_apply(p["ffn"], x, cfg)
            if caches is not None:
                kv.pop("length")
                new_kv.append(kv)
        if caches is None:
            return x, None, 0.0
        s = x.shape[1]
        return x, dict(caches,
                       kv=jax.tree_util.tree_map(lambda *a: jnp.stack(a), *new_kv),
                       length=caches["length"] + s), 0.0

    def _backbone(self, params, x, positions, caches=None, memory=None,
                  sparse_inputs=None):
        fam = self.cfg.family
        if fam == "audio":
            return self._backbone_whisper(params, x, positions, caches, memory)
        if self.cfg.attn_pattern == "local_global":
            return self._backbone_gemma(params, x, positions, caches)
        if fam == "hybrid":
            return self._backbone_zamba(params, x, positions, caches)
        if fam == "ssm" and self.cfg.ssm.kind == "rwkv6":
            return self._backbone_rwkv(params, x, positions, caches)
        return self._backbone_uniform(params, x, positions, caches,
                                      sparse_inputs)

    # ------------------------------------------------------------ public fns
    def sparse_inputs(self):
        """The sparse-FFN patterns' arrays (rows, cols, kernel prep per
        layer) as a pytree to hand ``loss_fn`` as an operand — closed over
        instead, gigabytes of pattern would be baked into the compiled
        program as constants.  ``None`` without ``sparse_ffn``."""
        return None if self.patterns is None else _pat_leaves(self.patterns)

    def loss_fn(self, params, batch, sparse_inputs=None):
        """batch: tokens (B,S), labels (B,S) [-1 = pad]; audio adds frames.
        ``sparse_inputs``: ``self.sparse_inputs()`` passed as an operand."""
        cfg = self.cfg
        tokens = batch["tokens"]
        x = self._embed(params, tokens)
        positions = jnp.broadcast_to(jnp.arange(tokens.shape[1])[None], tokens.shape)
        memory = None
        if cfg.family == "audio":
            memory = self._encode_audio(params, batch["frames"])
        h, _, aux = self._backbone(params, x, positions, memory=memory,
                                   sparse_inputs=sparse_inputs)
        h = rmsnorm(h, params["final_ln"], cfg.norm_eps)
        loss, ntok = lm_loss(h, self._unembed_w(params), batch["labels"])
        aux_w = cfg.moe.router_aux_weight if cfg.moe else 0.0
        total = loss + aux_w * aux / max(cfg.num_layers, 1)
        return total, {"ce_loss": loss, "aux_loss": aux, "tokens": ntok}

    def prefill(self, params, batch, max_len: int):
        cfg = self.cfg
        tokens = batch["tokens"]
        b, s = tokens.shape
        caches = self.init_cache(b, max_len)
        memory = None
        if cfg.family == "audio":
            memory = self._encode_audio(params, batch["frames"])
            caches["memory"] = memory
        x = self._embed(params, tokens)
        positions = jnp.broadcast_to(jnp.arange(s)[None], (b, s))
        h, caches, _ = self._backbone(params, x, positions, caches=caches,
                                      memory=memory)
        h = rmsnorm(h[:, -1:], params["final_ln"], cfg.norm_eps)
        logits = jnp.einsum("bsd,dv->bsv", h.astype(jnp.float32),
                            self._unembed_w(params).astype(jnp.float32))
        return logits[:, 0], caches

    def decode_step(self, params, caches, tokens):
        """tokens (B, 1) → (logits (B, V), caches).

        ``caches["length"]`` may be a scalar (all lanes in lockstep) or a
        (B,) vector (batched serving: each slot at its own position, masked
        to its own length in attention)."""
        cfg = self.cfg
        b = tokens.shape[0]
        x = self._embed(params, tokens)
        lens = caches["length"]
        positions = (jnp.broadcast_to(lens[None, None], (b, 1))
                     if jnp.ndim(lens) == 0 else lens[:, None])
        memory = caches.get("memory") if cfg.family == "audio" else None
        h, caches, _ = self._backbone(params, x, positions, caches=caches,
                                      memory=memory)
        h = rmsnorm(h, params["final_ln"], cfg.norm_eps)
        logits = jnp.einsum("bsd,dv->bsv", h.astype(jnp.float32),
                            self._unembed_w(params).astype(jnp.float32))
        return logits[:, 0], caches

    # ---------------------------------------------------------------- caches
    def init_cache(self, batch: int, max_len: int):
        cfg = self.cfg
        dt = jnp.dtype(cfg.compute_dtype)
        hk, hd = cfg.num_kv_heads, cfg.head_dim
        length = jnp.zeros((), jnp.int32)

        def kv(n_lead, lmax):
            shape = tuple(n_lead) + (batch, hk, lmax, hd)
            return dict(k=jnp.zeros(shape, dt), v=jnp.zeros(shape, dt))

        if cfg.family == "audio":
            return {"kv": kv((cfg.num_layers,), max_len), "length": length}
        if cfg.attn_pattern == "local_global":
            inner = cfg.local_per_global + 1
            groups = cfg.num_layers // inner
            return {
                "local": kv((groups, inner - 1), min(cfg.window, max_len)),
                "global": kv((groups, 1), max_len),
                "length": length,
            }
        if cfg.family == "hybrid":
            s = cfg.ssm
            di = s.expand * cfg.d_model
            h = di // s.head_dim
            groups = cfg.num_layers // cfg.shared_every
            return {
                "ssm": jnp.zeros((groups, cfg.shared_every, batch, h, s.d_state,
                                  s.head_dim), jnp.float32),
                "conv": jnp.zeros((groups, cfg.shared_every, batch,
                                   s.conv_width - 1, di + 2 * s.d_state), dt),
                "kv": kv((groups,), max_len),
                "length": length,
            }
        if cfg.family == "ssm":  # rwkv6
            n = cfg.d_model // cfg.num_heads
            return {
                "wkv": jnp.zeros((cfg.num_layers, batch, cfg.num_heads, n, n),
                                 jnp.float32),
                "tm_prev": jnp.zeros((cfg.num_layers, batch, cfg.d_model), dt),
                "cm_prev": jnp.zeros((cfg.num_layers, batch, cfg.d_model), dt),
                "length": length,
            }
        return {"kv": kv((cfg.num_layers,), max_len), "length": length}


def _pat_leaves(pats):
    """The per-layer (scanned) arrays of each pattern: rows, cols and the
    array entries of its stacked kernel prep."""
    return {name: (p.rows, p.cols,
                   {k: v for k, v in p.prep.items() if hasattr(v, "shape")})
            for name, p in pats.items()}


def _sinusoid(s: int, d: int, dtype):
    pos = np.arange(s)[:, None]
    i = np.arange(d // 2)[None, :]
    ang = pos / (10000 ** (2 * i / d))
    return jnp.asarray(np.concatenate([np.sin(ang), np.cos(ang)], -1), dtype)


def _sinusoid_at(positions: jax.Array, d: int, dtype):
    i = jnp.arange(d // 2)[None, None, :]
    ang = positions[..., None] / (10000 ** (2 * i / d))
    return jnp.concatenate([jnp.sin(ang), jnp.cos(ang)], -1).astype(dtype)
