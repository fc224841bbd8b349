"""Train-step builder: loss → grads → (optional microbatch accumulation)
→ clip → AdamW, as one pjit-able pure function over TrainState.

Grad accumulation scans over microbatches with a bf16 accumulator (half the
accumulator HBM of f32; the f32 path is the default for exactness — the
choice is a recorded §Perf lever)."""
from __future__ import annotations

import dataclasses
import functools
from typing import Any, Callable

import jax
import jax.numpy as jnp

from .optim import OptConfig, adamw_update, init_opt_state


@dataclasses.dataclass(frozen=True)
class TrainConfig:
    opt: OptConfig = OptConfig()
    microbatches: int = 1
    accum_dtype: str = "float32"
    #: scoped backend for the sparse layers' kernels during tracing (the
    #: facade's ``use_backend``); None keeps the platform default
    sparse_backend: str | None = None
    #: skip-and-report guardrail (DESIGN.md §12): when the loss or any grad
    #: leaf goes non-finite, keep the previous params/optimizer state for
    #: this step instead of poisoning them, and report it in the metrics
    #: (``skipped_nonfinite``).  Pure in-graph ``where`` — jit/pjit-safe.
    skip_nonfinite: bool = False


def make_train_step(loss_fn: Callable, tcfg: TrainConfig) -> Callable:
    """loss_fn(params, batch) -> (loss, metrics dict).

    Returns train_step(state, batch) -> (state, metrics) where
    state = {"params": ..., "opt": ...}.  A state built with
    ``init_state(..., consts=c)`` also carries non-trainable operands
    (``Model.sparse_inputs()``): they pass through unchanged and reach the
    loss as ``loss_fn(params, batch, c)``.  ``tcfg.sparse_backend`` pins the
    sparse-kernel backend for the whole step's trace through the facade's
    ``use_backend`` scope — no kwarg threading through model code."""
    if tcfg.sparse_backend is not None:
        from repro.api import use_backend
        inner_loss = loss_fn

        def loss_fn(params, batch, *consts):
            with use_backend(tcfg.sparse_backend):
                return inner_loss(params, batch, *consts)

    grad_fn = jax.value_and_grad(loss_fn, has_aux=True)

    def single(params, batch, *consts):
        (loss, metrics), grads = grad_fn(params, batch, *consts)
        return loss, metrics, grads

    def accumulate(params, batch, *consts):
        mb = tcfg.microbatches
        adt = jnp.dtype(tcfg.accum_dtype)
        split = jax.tree_util.tree_map(
            lambda x: x.reshape((mb, x.shape[0] // mb) + x.shape[1:]), batch)

        def body(carry, mbatch):
            acc, loss_acc = carry
            (loss, _), grads = grad_fn(params, mbatch, *consts)
            acc = jax.tree_util.tree_map(
                lambda a, g: a + g.astype(adt), acc, grads)
            return (acc, loss_acc + loss), None

        zeros = jax.tree_util.tree_map(
            lambda p: jnp.zeros(p.shape, adt), params)
        (acc, loss), _ = jax.lax.scan(body, (zeros, 0.0), split)
        grads = jax.tree_util.tree_map(lambda a: (a / mb).astype(adt), acc)
        return loss / mb, {}, grads

    def train_step(state, batch):
        params, opt = state["params"], state["opt"]
        consts = (state["consts"],) if "consts" in state else ()
        if tcfg.microbatches > 1:
            loss, metrics, grads = accumulate(params, batch, *consts)
        else:
            loss, metrics, grads = single(params, batch, *consts)
        new_params, new_opt, opt_metrics = adamw_update(params, grads, opt, tcfg.opt)
        out = {"loss": loss, **{k: v for k, v in metrics.items()
                                if jnp.ndim(v) == 0}, **opt_metrics}
        if tcfg.skip_nonfinite:
            leaf_ok = [jnp.all(jnp.isfinite(g)) for g in
                       jax.tree_util.tree_leaves(grads)
                       if jnp.issubdtype(jnp.result_type(g), jnp.inexact)]
            ok = jnp.logical_and(jnp.isfinite(loss),
                                 functools.reduce(jnp.logical_and, leaf_ok,
                                                  jnp.bool_(True)))
            keep = lambda new, old: jax.tree_util.tree_map(  # noqa: E731
                lambda n, o: jnp.where(ok, n, o), new, old)
            new_params = keep(new_params, params)
            new_opt = keep(new_opt, opt)
            out["skipped_nonfinite"] = jnp.where(ok, 0, 1)
        new_state = {"params": new_params, "opt": new_opt}
        if consts:
            new_state["consts"] = consts[0]
        return new_state, out

    return train_step


def init_state(params: Any, tcfg: TrainConfig, consts: Any = None) -> dict:
    """``consts``: non-trainable loss operands carried with the state (see
    ``make_train_step``); ``None`` leaves them out."""
    state = {"params": params, "opt": init_opt_state(params, tcfg.opt)}
    if consts is not None:
        state["consts"] = consts
    return state


def sparse_weight_shardings(params: Any, mesh, rules=None) -> Any:
    """NamedShardings for the sparse-FFN value streams (``v_gate``/``v_up``/
    ``v_down`` BalancedCOO tile stacks): tiles over the DP axes, nnz
    contiguous — the partition the sharded SpMM backend assumes
    (``launch.sharding_rules.SPARSE_WEIGHT_RULES``).  Dense leaves map to
    ``None`` (caller's layout); non-dividing tile counts fall back to
    replicated.  Feed to ``jax.device_put`` / pjit ``in_shardings`` for the
    train state's param subtree."""
    from jax.sharding import NamedSharding
    from repro.launch.sharding_rules import (SPARSE_WEIGHT_RULES,
                                             check_divisibility,
                                             partition_spec)
    rules = rules or SPARSE_WEIGHT_RULES

    def one(path, leaf):
        name = path[-1].key if hasattr(path[-1], "key") else str(path[-1])
        if not name.startswith("v_"):
            return None
        # value stream leaves are (..., tiles, nnz); leading axes (layer
        # stacking) stay unsharded
        logical = (None,) * (leaf.ndim - 2) + ("tiles", "nnz")
        spec = partition_spec(logical, rules, mesh)
        if not check_divisibility(leaf.shape, spec, mesh):
            return NamedSharding(mesh, partition_spec((), rules, mesh))
        return NamedSharding(mesh, spec)

    return jax.tree_util.tree_map_with_path(one, params)
