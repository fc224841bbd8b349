#!/usr/bin/env python3
"""Chip smoke test: drive the system's main paths once on a TPU and check
every result against an independent reference.

    python chip_smoke.py [--seed 0] [--phase spmm,gat,lm] [--chips 4]

One process, one chip, the library's normal entry points and defaults:

  spmm  Graph500 R-MAT scale 18 (edge factor 16, A=0.57 B=C=0.19) planned by
        ``repro.api.sparse(csr)``; the plan must land on Pallas.  Every
        logical kernel (nb_pr, nb_sr, rs_pr, rs_sr) at N = 1, 32, 128 and the
        SpMV on a 1-D x, each against an f32 ``segment_sum`` reference.  At
        N = 128, x alone is 128 MiB — the dense operand stays in HBM.
  gat   the graph-attention layer of ``examples/train_gat.py`` on the same
        graph with self-loops (d_in 128, d_head 64): 5 SGD steps of
        ``jax.jit(jax.value_and_grad(...))`` through the fused
        ``A.chain(..., transform="softmax")``; the loss must fall and the
        fused output must match the XLA backend.
  lm    llama3.2-1b at its published widths (16 layers, d_model 2048, 32/8
        heads, d_ff 8192, vocab 128256) with a pruned sparse FFN (density
        0.15, tile 512), f32 params and bf16 compute, 3 AdamW steps through
        ``make_train_step`` on one fixed batch of 1 x 256 tokens (sized from
        the compiled step's memory analysis: 13.6 GiB of 16 GB); the loss
        must be finite and fall.

``--chips 4`` runs only the sharded SpMM on a 2x2 mesh of four chips
(nnz-split without and with the collective-permute ring, row-split), each
against the one-chip Pallas result, and checks the shards sit on all four
devices.

After every phase the health counters must hold no kernel reroute, breaker
skip or demotion, and the phase's compiled program must contain a Pallas
kernel (``tpu_custom_call``).  Each phase prints its checks and wall times
(``block_until_ready``) on lines of their own; these are one-off readings,
not benchmark numbers.  The last line of a passing run is the JSON object
``{"ok": true, "device": {...}}``.  With no TPU the script exits non-zero
and prints no result.

The compile cache lives where ``JAX_COMPILATION_CACHE_DIR`` says, or else in
``.jax_cache/`` next to this file.  ``$REPRO_THRESHOLDS`` is ignored: the
run depends only on committed files.
"""
from __future__ import annotations

import argparse
import dataclasses
import json
import os
import sys
import time
from pathlib import Path

import jax
import jax.numpy as jnp
import numpy as np

ROOT = Path(__file__).resolve().parent
TOL = 1e-3            # max abs error / max abs reference
BAD_COUNTERS = ("kernel_reroute:", "breaker_skip:", "demote:")


class SmokeFailure(AssertionError):
    pass


def check(cond: bool, what: str) -> None:
    print(f"  check {'ok  ' if cond else 'FAIL'} {what}", flush=True)
    if not cond:
        raise SmokeFailure(what)


def timed(fn, *args):
    """(result, seconds) of one call, waited to completion."""
    t0 = time.perf_counter()
    out = jax.block_until_ready(fn(*args))
    return out, time.perf_counter() - t0


def rel_err(got, ref) -> float:
    got = jnp.asarray(got, jnp.float32)
    ref = jnp.asarray(ref, jnp.float32)
    return float(jnp.max(jnp.abs(got - ref)) / jnp.maximum(
        jnp.max(jnp.abs(ref)), 1e-30))


def has_kernel(compiled) -> bool:
    return "tpu_custom_call" in compiled.as_text()


def check_health(api, phase: str) -> None:
    bad = sorted(k for k in api.health()["counters"]
                 if k.startswith(BAD_COUNTERS))
    check(not bad, f"{phase}: no reroute/skip/demote counters {bad}")


def graph(scale: int, seed: int, self_loops: bool = False):
    """The Graph500 R-MAT graph (A=0.57, B=C=0.19), optionally with every
    self-loop added (softmax rows are then never empty)."""
    from repro.core import csr_from_coo, rmat
    csr = rmat(scale, 16, 0.57, 0.19, 0.19, seed=seed)
    if not self_loops:
        return csr
    indptr = np.asarray(csr.indptr)
    m = csr.shape[0]
    rows = np.repeat(np.arange(m), np.diff(indptr))
    diag = np.arange(m)
    return csr_from_coo(np.concatenate([rows, diag]),
                        np.concatenate([np.asarray(csr.indices), diag]),
                        np.ones(csr.nnz + m, np.float32), csr.shape)


def reference_spmm(csr):
    """The f32 definition ``y[r] = sum v * x[c]`` as one ``segment_sum`` —
    independent of every kernel and substrate of the library."""
    indptr = np.asarray(csr.indptr)
    rows = jnp.asarray(np.repeat(np.arange(csr.shape[0]), np.diff(indptr)))
    cols = jnp.asarray(csr.indices)
    vals = jnp.asarray(csr.data, jnp.float32)
    m = csr.shape[0]

    @jax.jit
    def ref(x):
        x2 = x[:, None] if x.ndim == 1 else x
        y = jax.ops.segment_sum(vals[:, None] * x2[cols], rows,
                                num_segments=m)
        return y[:, 0] if x.ndim == 1 else y
    return ref


# ---------------------------------------------------------------------------
# phases
# ---------------------------------------------------------------------------

def phase_spmm(api, seed: int, scale: int = 18) -> None:
    t0 = time.perf_counter()
    csr = graph(scale, seed)
    t_gen = time.perf_counter() - t0
    t0 = time.perf_counter()
    A = api.sparse(csr)
    A.plan.substrate("balanced")
    t_plan = time.perf_counter() - t0
    m, k = A.shape
    empty = float(np.mean(np.diff(np.asarray(csr.indptr)) == 0))
    print(f"spmm: R-MAT scale {scale}: {m} rows, nnz {A.nnz}, "
          f"{empty:.1%} empty rows; generate {t_gen:.2f} s, "
          f"plan {t_plan:.2f} s", flush=True)
    check(A.backend == "pallas", f"spmm: plan backend {A.backend!r} is pallas")
    ref = reference_spmm(csr)
    x = jax.random.normal(jax.random.PRNGKey(seed), (k, 128), jnp.float32)
    cases = [(impl, n) for impl in ("nb_pr", "nb_sr", "rs_pr", "rs_sr")
             for n in (1, 32, 128)] + [(None, 0)]
    for impl, n in cases:
        xi = x[:, 0] if n == 0 else x[:, :n]
        name = "spmv (1-D x, selector's pick)" if impl is None else \
            f"{impl} N={n}"
        want = ref(xi)
        fn = lambda v, impl=impl: A.matmul(v, impl=impl)  # noqa: E731
        _, t_first = timed(fn, xi)
        y, t = timed(fn, xi)
        err = rel_err(y, want)
        print(f"  {name}: first call {t_first:.3f} s, then {t:.4f} s, "
              f"rel err {err:.2e}", flush=True)
        check(y.shape == want.shape and err <= TOL,
              f"spmm: {name} matches the f32 reference (<= {TOL})")
    compiled = jax.jit(lambda v: A.matmul(v, impl="nb_pr")).lower(x).compile()
    check(has_kernel(compiled), "spmm: compiled nb_pr holds tpu_custom_call")
    check_health(api, "spmm")


def phase_gat(api, seed: int, scale: int = 18, d_in: int = 128,
              d_head: int = 64, steps: int = 5) -> None:
    t0 = time.perf_counter()
    csr = graph(scale, seed, self_loops=True)
    A = api.sparse(csr, chain_op="softmax")
    A.plan.substrate("balanced")
    print(f"gat: graph with self-loops, nnz {A.nnz}; built in "
          f"{time.perf_counter() - t0:.2f} s", flush=True)
    check(A.backend == "pallas", f"gat: plan backend {A.backend!r} is pallas")
    n = A.shape[0]
    keys = jax.random.split(jax.random.PRNGKey(seed + 1), 5)
    h = jax.random.normal(keys[0], (n, d_in), jnp.float32)
    target = jax.random.normal(keys[1], (n, d_head), jnp.float32)
    params = {w: 0.1 * jax.random.normal(kk, (d_in, d_head), jnp.float32)
              for w, kk in zip(("wq", "wk", "wv"), keys[2:])}
    alpha = 1.0 / float(np.sqrt(d_head))

    def forward(mat, p, h):
        q, kk, v = h @ p["wq"], h @ p["wk"], h @ p["wv"]
        return mat.chain(q, kk, v, transform="softmax", alpha=alpha)

    def loss_fn(p, h, target):
        err = forward(A, p, h) - target
        return jnp.mean(err * err)

    t0 = time.perf_counter()
    step = jax.jit(jax.value_and_grad(loss_fn)).lower(
        params, h, target).compile()
    print(f"  compile value_and_grad: {time.perf_counter() - t0:.2f} s",
          flush=True)
    check(has_kernel(step), "gat: compiled step holds tpu_custom_call")
    losses = []
    for i in range(steps):
        (loss, grads), t = timed(step, params, h, target)
        losses.append(float(loss))
        print(f"  step {i}: loss {losses[-1]:.6f}, {t:.3f} s", flush=True)
        check(bool(np.isfinite(losses[-1])), f"gat: step {i} loss finite")
        params = {w: params[w] - 0.5 * grads[w] for w in params}
    check(losses[-1] < losses[0],
          f"gat: loss falls {losses[0]:.6f} -> {losses[-1]:.6f}")
    fused, t = timed(jax.jit(lambda p, h: forward(A, p, h)), params, h)
    ref_mat = api.sparse(csr, backend="xla", chain_op="softmax")
    want = jax.jit(lambda p, h: forward(ref_mat, p, h))(params, h)
    err = rel_err(fused, want)
    print(f"  fused chain vs xla backend: rel err {err:.2e} "
          f"(fused forward {t:.3f} s)", flush=True)
    check(err <= TOL, f"gat: fused chain matches the xla backend (<= {TOL})")
    check_health(api, "gat")


def lm_config(**overrides):
    from repro.configs import get
    from repro.models.config import SparseFFNConfig
    return dataclasses.replace(
        get("llama3.2-1b"),
        sparse_ffn=SparseFFNConfig(density=0.15, tile=512),
        param_dtype="float32", compute_dtype="bfloat16", **overrides)


def phase_lm(api, seed: int, cfg=None, batch: int = 1, seq: int = 256,
             steps: int = 3) -> None:
    from repro.models import Model
    from repro.models.params import param_count
    from repro.train import OptConfig, TrainConfig, init_state, make_train_step
    cfg = cfg or lm_config()
    t0 = time.perf_counter()
    model = Model(cfg)
    print(f"lm: {cfg.name}, {cfg.num_layers} layers, d_model {cfg.d_model}, "
          f"d_ff {cfg.d_ff}, vocab {cfg.vocab_size}; "
          f"{param_count(model.specs) / 1e9:.3f}B params; patterns built in "
          f"{time.perf_counter() - t0:.2f} s", flush=True)
    tcfg = TrainConfig(opt=OptConfig(lr=1e-3, warmup_steps=0,
                                     total_steps=steps))
    state = init_state(model.init(jax.random.PRNGKey(seed)), tcfg,
                       consts=model.sparse_inputs())
    tokens = jax.random.randint(jax.random.PRNGKey(seed + 2), (batch, seq),
                                0, cfg.vocab_size, jnp.int32)
    labels = jnp.concatenate(
        [tokens[:, 1:], jnp.full((batch, 1), -1, jnp.int32)], axis=1)
    data = {"tokens": tokens, "labels": labels}
    t0 = time.perf_counter()
    step = jax.jit(make_train_step(model.loss_fn, tcfg),
                   donate_argnums=(0,)).lower(state, data).compile()
    mem = step.memory_analysis()
    print(f"  compile train step: {time.perf_counter() - t0:.2f} s; "
          f"arguments {mem.argument_size_in_bytes / 2**30:.2f} GiB, "
          f"temporaries {mem.temp_size_in_bytes / 2**30:.2f} GiB", flush=True)
    check(has_kernel(step), "lm: compiled step holds tpu_custom_call")
    losses = []
    for i in range(steps):
        (state, metrics), t = timed(step, state, data)
        losses.append(float(metrics["loss"]))
        print(f"  step {i}: loss {losses[-1]:.5f}, {t:.3f} s", flush=True)
        check(bool(np.isfinite(losses[-1])), f"lm: step {i} loss finite")
    check(losses[-1] < losses[0],
          f"lm: loss falls {losses[0]:.5f} -> {losses[-1]:.5f}")
    check_health(api, "lm")


def phase_sharded(api, seed: int, scale: int = 18, n: int = 256) -> None:
    """The spmm graph on a 2x2 mesh through ``A.shard(mesh)``, against the
    one-chip Pallas result.  N = 256 is two 128-wide chunks, so the ring
    (``overlap_min_n``) has something to overlap."""
    from repro.launch.mesh import make_local_mesh
    csr = graph(scale, seed)
    A = api.sparse(csr)
    check(A.backend == "pallas", f"sharded: one-chip plan {A.backend!r}")
    x = jax.random.normal(jax.random.PRNGKey(seed), (A.shape[1], n),
                          jnp.float32)
    one, t = timed(A.matmul, x)
    one, t = timed(A.matmul, x)
    print(f"sharded: one-chip pallas {A.plan.select(n)} N={n}: {t:.4f} s",
          flush=True)
    mesh = make_local_mesh(2, 2)
    th = A.plan.thresholds
    cases = (("nnz-split", "nnz", th.overlap_min_n),
             ("nnz-split + ring", "nnz", n),
             ("row-split", "row", th.overlap_min_n))
    for name, kind, overlap in cases:
        S = A.with_thresholds(dataclasses.replace(th, overlap_min_n=overlap)
                              ).shard(mesh, kind=kind)
        sub = S.plan.substrate(S.plan.entry(S.plan.select(n)).substrate)
        y, t_first = timed(S.matmul, x)
        y, t = timed(S.matmul, x)
        err = rel_err(np.asarray(y), np.asarray(one))
        devs = len(sub.cols.sharding.device_set)
        print(f"  {name}: {sub.spec.n_shards} shards on axis "
              f"{sub.spec.axis!r}, substrate on {devs} devices, output on "
              f"{len(y.sharding.device_set)}; first call {t_first:.3f} s, "
              f"then {t:.4f} s, rel err vs one chip {err:.2e}", flush=True)
        check(devs == 4, f"sharded: {name} substrate spans 4 devices")
        check(err <= TOL, f"sharded: {name} matches one chip (<= {TOL})")
    check_health(api, "sharded")


# ---------------------------------------------------------------------------
# entry point
# ---------------------------------------------------------------------------

PHASES = {"spmm": phase_spmm, "gat": phase_gat, "lm": phase_lm}


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--phase", default=",".join(PHASES),
                    help="comma list of phases (default: all)")
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1,
                    help="4: run only the sharded SpMM on a 2x2 mesh")
    args = ap.parse_args(argv)

    devices = jax.devices()
    dev = devices[0]
    if dev.platform != "tpu":
        print(f"chip_smoke: no TPU (JAX found {dev.platform!r})",
              file=sys.stderr)
        return 2
    if len(devices) < args.chips:
        print(f"chip_smoke: --chips {args.chips} but {len(devices)} devices",
              file=sys.stderr)
        return 2
    if "JAX_COMPILATION_CACHE_DIR" not in os.environ:
        jax.config.update("jax_compilation_cache_dir",
                          str(ROOT / ".jax_cache"))
    os.environ.pop("REPRO_THRESHOLDS", None)
    sys.path.insert(0, str(ROOT / "src"))
    from repro import api

    print(f"device: {dev.platform} {dev.device_kind} x{len(devices)}; "
          f"jax {jax.__version__}", flush=True)
    if args.chips == 4:
        run = [("sharded", phase_sharded)]
    else:
        run = [(p, PHASES[p]) for p in args.phase.split(",")]
    for name, phase in run:
        t0 = time.perf_counter()
        try:
            phase(api, args.seed)
        except SmokeFailure:
            return 1
        print(f"{name}: passed in {time.perf_counter() - t0:.1f} s",
              flush=True)
    print(json.dumps({"ok": True, "device": {
        "platform": dev.platform, "kind": dev.device_kind,
        "count": len(devices)}}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
