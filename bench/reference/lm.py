"""Plain reference of the sparse-FFN decoder's loss and gradients, and of
one AdamW step: a pre-norm decoder with RMSNorm, rotary position
embeddings, causal multi-head attention and a gated SiLU FFN whose three
weight matrices are pruned, then an RMSNorm and an untied head, scored by
the mean next-token cross-entropy.

It imports nothing of the system under test.  Every layer is written out
in ``jax.numpy`` in float32 and run under ``jax.default_matmul_precision(
"highest")``: no kernels, no scan over the layers, no cache.  Each pruned
matrix is its values scattered into a dense zero matrix at the pattern's
``(row, col)`` positions, so the gradient of a value is the dense weight
gradient at its position.  ``operand_dtype`` rounds every matmul operand
and the weights to a narrower type first (the control: the same computation
with bfloat16 operands); products and sums stay in float32.

Parameters come as the program lays them out (a nested dict of arrays):
``embed`` ``(V, D)``, ``lm_head`` ``(D, V)``, ``final_ln`` ``(D,)``, and
under ``blocks`` one leading layer axis on ``attn`` (``ln``, ``wq``,
``wk``, ``wv`` ``(D, H hd)``, ``wo`` ``(H hd, D)``) and ``ffn`` (``ln``,
``v_gate``, ``v_up``, ``v_down``: each a ``(n_tiles, T)`` stream of values
whose positions the pattern names).  A pattern is ``(rows, cols, (m, k))``
with ``rows``/``cols`` ``(L, n_tiles, T)``; entries with ``rows >= m`` are
padding.  ``gate``/``up`` are ``(d_ff, D)`` and ``down`` ``(D, d_ff)``:
the FFN computes ``x Wᵀ`` with each.

Departures from the published model, each the program's too:

* the RMSNorm gain is stored as ``w`` and applied as ``1 + w`` (a plain
  RMSNorm whose gain starts at one);
* attention is fully causal: the published 2047-token sliding window
  covers every position of a sequence of up to 2048 tokens, so it changes
  nothing there;
* packed documents attend across their EOS separators, as the program's
  attention does (no document mask);
* no dropout (the published rates are 0).

``adamw`` is one optimizer step as published (Loshchilov & Hutter,
arXiv:1711.05101: Adam's moments with bias correction and weight decay
decoupled from the gradient), after clipping the gradient to a global
norm, at a learning rate warmed up linearly and then decayed on a cosine.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np

FFN = ("gate", "up", "down")


def _rmsnorm(x, w, eps):
    var = jnp.mean(x * x, axis=-1, keepdims=True)
    return x * jax.lax.rsqrt(var + eps) * (1.0 + w)


def _rope(x, theta):
    """``x`` ``(B, S, H, hd)``; position ``s`` rotates the pairs
    ``(x[i], x[i + hd/2])`` by ``s / theta^(2i/hd)``."""
    half = x.shape[-1] // 2
    freqs = 1.0 / (theta ** (jnp.arange(half, dtype=jnp.float32) / half))
    ang = jnp.arange(x.shape[1], dtype=jnp.float32)[:, None] * freqs
    cos, sin = jnp.cos(ang)[None, :, None], jnp.sin(ang)[None, :, None]
    x1, x2 = x[..., :half], x[..., half:]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], -1)


def _dense(rows, cols, vals, shape):
    m = shape[0]
    valid = rows < m
    w = jnp.zeros(shape, jnp.float32)
    return w.at[jnp.where(valid, rows, 0), cols].add(
        jnp.where(valid, vals, 0.0))


def _loss(params, patterns, tokens, labels, *, heads, head_dim, theta, eps,
          operand_dtype):
    def rnd(a):
        return a.astype(operand_dtype).astype(jnp.float32)

    def mm(a, b):
        return jnp.matmul(rnd(a), rnd(b))

    blocks = params["blocks"]
    x = rnd(params["embed"])[tokens]                       # (B, S, D)
    b, s, _ = x.shape
    causal = jnp.tril(jnp.ones((s, s), bool))
    for i in range(blocks["attn"]["wq"].shape[0]):
        at = {k: v[i] for k, v in blocks["attn"].items()}
        xn = _rmsnorm(x, at["ln"], eps)
        q = _rope(mm(xn, at["wq"]).reshape(b, s, heads, head_dim), theta)
        k = _rope(mm(xn, at["wk"]).reshape(b, s, heads, head_dim), theta)
        v = mm(xn, at["wv"]).reshape(b, s, heads, head_dim)
        z = jnp.einsum("bqhd,bkhd->bhqk", rnd(q), rnd(k)) / np.sqrt(head_dim)
        p = jax.nn.softmax(jnp.where(causal, z, -jnp.inf), axis=-1)
        o = jnp.einsum("bhqk,bkhd->bqhd", rnd(p), rnd(v))
        x = x + mm(o.reshape(b, s, heads * head_dim), at["wo"])

        ff = blocks["ffn"]
        w = {n: _dense(patterns[n][0][i], patterns[n][1][i],
                       ff[f"v_{n}"][i], patterns[n][2]) for n in FFN}
        xn = _rmsnorm(x, ff["ln"][i], eps)
        h = jax.nn.silu(mm(xn, w["gate"].T)) * mm(xn, w["up"].T)
        x = x + mm(h, w["down"].T)
    x = _rmsnorm(x, params["final_ln"], eps)
    logits = mm(x, params["lm_head"])
    logz = jax.nn.logsumexp(logits, axis=-1)
    gold = jnp.take_along_axis(logits, jnp.maximum(labels, 0)[..., None],
                               axis=-1)[..., 0]
    mask = (labels >= 0).astype(jnp.float32)
    return jnp.sum((logz - gold) * mask) / jnp.maximum(jnp.sum(mask), 1.0)


class Reference:
    """``loss(params, batch)`` and ``gradients(params, batch)`` (the loss
    and its gradient with respect to every parameter, in the parameters'
    layout) of the model ``spec`` describes, on
    the ``patterns`` given (host arrays, passed to the jitted functions as
    arguments).  ``spec``: ``heads``, ``head_dim``, ``rope_theta``,
    ``rms_norm_eps``."""

    def __init__(self, spec: dict, patterns: dict,
                 operand_dtype=jnp.float32):
        shapes = {n: tuple(int(d) for d in patterns[n][2]) for n in FFN}
        self._arrays = {n: (jnp.asarray(patterns[n][0]),
                            jnp.asarray(patterns[n][1])) for n in FFN}
        fn = functools.partial(
            _loss, heads=int(spec["heads"]), head_dim=int(spec["head_dim"]),
            theta=float(spec["rope_theta"]), eps=float(spec["rms_norm_eps"]),
            operand_dtype=jnp.dtype(operand_dtype))

        def loss(params, arrays, tokens, labels):
            pats = {n: (*arrays[n], shapes[n]) for n in FFN}
            return fn(params, pats, tokens, labels)

        self._loss = jax.jit(loss)
        self._grad = jax.jit(jax.value_and_grad(loss))

    def loss(self, params, batch) -> float:
        with jax.default_matmul_precision("highest"):
            return float(self._loss(params, self._arrays, batch["tokens"],
                                    batch["labels"]))

    def gradients(self, params, batch) -> tuple:
        with jax.default_matmul_precision("highest"):
            return self._grad(params, self._arrays, batch["tokens"],
                              batch["labels"])


def learning_rate(hp: dict, step: int) -> float:
    """The rate of the ``step``-th update (counting from 1): linear warm-up
    over ``warmup_steps``, then a cosine from ``lr`` down to
    ``min_lr_ratio × lr`` at ``total_steps``."""
    warmup, total = hp["warmup_steps"], hp["total_steps"]
    if step < warmup:
        return hp["lr"] * step / max(warmup, 1)
    prog = min(max((step - warmup) / max(total - warmup, 1), 0.0), 1.0)
    lo = hp["min_lr_ratio"]
    return hp["lr"] * (lo + (1 - lo) * 0.5 * (1 + np.cos(np.pi * prog)))


@functools.partial(jax.jit, static_argnames=("hp",))
def _adamw(params, grads, m, v, lr, bc1, bc2, hp):
    hp = dict(hp)
    b1, b2 = hp["betas"]
    leaves = jax.tree_util.tree_leaves(grads)
    norm = jnp.sqrt(sum(jnp.sum(g * g) for g in leaves))
    scale = jnp.minimum(1.0, hp["clip_norm"] / (norm + 1e-9))

    def one(p, g, m, v):
        g = g * scale
        m = b1 * m + (1 - b1) * g
        v = b2 * v + (1 - b2) * g * g
        step = (m / bc1) / (jnp.sqrt(v / bc2) + hp["eps"])
        return p - lr * (step + hp["weight_decay"] * p)

    return jax.tree_util.tree_map(one, params, grads, m, v)


def adamw(params, grads, opt, hp: dict):
    """The parameters after one AdamW step in float32 from the optimizer
    state ``opt`` (``step``: updates made so far; ``m``, ``v``: the moments,
    laid out as ``params``) with the gradient ``grads``.  ``hp``: ``lr``,
    ``betas``, ``eps``, ``weight_decay``, ``clip_norm``, ``warmup_steps``,
    ``total_steps``, ``min_lr_ratio``.  Every leaf decays."""
    step = int(opt["step"]) + 1
    b1, b2 = hp["betas"]
    key = tuple(sorted((k, tuple(x) if isinstance(x, list) else x)
                       for k, x in hp.items()))
    return _adamw(params, grads, opt["m"], opt["v"],
                  np.float32(learning_rate(hp, step)),
                  np.float32(1 - b1 ** step), np.float32(1 - b2 ** step),
                  hp=key)


def rel_err(got, want) -> float:
    """Largest absolute error over the largest absolute reference value."""
    got = np.asarray(got, np.float64)
    want = np.asarray(want, np.float64)
    return float(np.max(np.abs(got - want)) /
                 max(float(np.max(np.abs(want))), 1e-30))
