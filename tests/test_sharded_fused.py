"""Sharded fused visit-schedule path (core/shard.py, DESIGN.md §7):
stacked-schedule invariants, fused-vs-single-device parity (outputs and
grads), empty-row shards, a single-shard mesh, bf16,
the width-chunked ppermute ring, and the plan-free pattern entry's fused
routing.

Runs on however many devices the host exposes (1 locally; the CI
multi-device job forces 8 virtual CPU devices via
``XLA_FLAGS=--xla_force_host_platform_device_count=8``); schedule-stacking
invariants use a fake 8-shard mesh so raggedness is exercised regardless."""
import dataclasses

import numpy as np
import pytest
import jax
import jax.numpy as jnp

from repro.core import (SelectorThresholds, csr_from_dense, execute,
                        execute_pattern, make_shard_spec, matrix_stats, plan,
                        rmat)
from repro.core.formats import BalancedCOO
from repro.core import registry
from repro.core.shard import (VISIT_PAD, _INNER_BOUND, _INNER_BOUND_CAP,
                              _make_inner, _sharded_prep,
                              build_sharded_substrate, stack_visit_schedules)
from repro.core.registry import resolve
from repro.kernels.vsr import plan_visits
from repro.launch.mesh import make_local_mesh


def _mesh(n=None):
    return make_local_mesh(n or jax.device_count(), 1)


class _FakeMesh:
    """Spec/substrate-building only (axis_names + shape); never executed on."""

    def __init__(self, n):
        self.axis_names = ("data",)
        self.shape = {"data": n}


def _skewed_csr(seed=3):
    return rmat(6, 8, 0.57, 0.19, 0.19, seed=seed)


def _dense_of(csr):
    m, k = csr.shape
    a = np.zeros((m, k), np.float32)
    indptr = np.asarray(csr.indptr)
    rows = np.repeat(np.arange(m), np.diff(indptr))
    a[rows, np.asarray(csr.indices)] = np.asarray(csr.data)
    return a


# ---------------------------------------------------------------------------
# stacked-schedule invariants (host-side; fake 8-shard mesh)
# ---------------------------------------------------------------------------

def test_stacked_schedules_pad_with_noop_visits():
    csr = rmat(7, 8, 0.57, 0.19, 0.19, seed=3)
    # row-split on a skewed matrix: per-shard nnz (and therefore visit
    # counts) differ — the ragged case the padding exists for
    spec = make_shard_spec(matrix_stats(csr), _FakeMesh(8), kind="row")
    sub = build_sharded_substrate(csr, spec, _FakeMesh(8),
                                  inner_kind="balanced", tile=32,
                                  inner_backend="pallas")
    rows_h = np.asarray(sub.rows)
    cols_h = np.asarray(sub.cols)
    vals_h = np.asarray(sub.vals)
    per_shard = [plan_visits(BalancedCOO(rows_h[s], cols_h[s], vals_h[s],
                                         sub.inner_shape), 8)
                 for s in range(8)]
    vt, vb, vs = stack_visit_schedules(per_shard)
    vmax = max(len(t) for t, _, _ in per_shard)
    assert vt.shape == vb.shape == vs.shape == (8, vmax)
    for s, (t0, b0, s0) in enumerate(per_shard):
        v = len(t0)
        # the real prefix is the shard's own schedule, untouched
        np.testing.assert_array_equal(vt[s, :v], t0)
        np.testing.assert_array_equal(vb[s, :v], b0)
        np.testing.assert_array_equal(vs[s, :v], s0)
        # padding re-points at the last (tile, block) pair and is inert
        assert (vs[s, v:] == VISIT_PAD).all()
        assert (vt[s, v:] == t0[-1]).all()
        assert (vb[s, v:] == b0[-1]).all()
    # raggedness is real on this matrix: at least two shards disagree
    assert len({len(t) for t, _, _ in per_shard}) > 1


def test_sharded_prep_stacks_schedules_and_windows():
    csr = _skewed_csr()
    p = plan(csr, backend="sharded", mesh=_mesh(), shard_kind="nnz", tile=64,
             inner_backend="pallas")
    opts = p.kernel_opts(p.entry("nb_pr"))
    n = p.shard_spec.n_shards
    assert set(opts) == {"visit_tile", "visit_block", "visit_start",
                         "wb", "overlap_min_n"}
    assert opts["visit_tile"].shape[0] == n
    assert opts["visit_tile"].shape == opts["visit_block"].shape \
        == opts["visit_start"].shape


@pytest.mark.parametrize("bad", ["unknown_key", "no_schedule"])
def test_sharded_prep_rejects_unstackable_opt(monkeypatch, bad):
    """Every opt an inner prep emits needs a stacking rule, and the
    schedule must be there: the sharded prep raises rather than run the
    kernel without an opt it was meant to get."""
    entry = resolve("nb_pr", "pallas")

    def prep(bal, *, geometry=None):
        opts = entry.prep(bal, geometry=geometry)
        if bad == "unknown_key":
            return dict(opts, stray_opt=np.zeros(4, np.int32))
        return {"wb": opts["wb"]}

    monkeypatch.setitem(registry._REGISTRY, ("nb_pr", "pallas"),
                        dataclasses.replace(entry, prep=prep))
    csr = _skewed_csr()
    spec = make_shard_spec(matrix_stats(csr), _FakeMesh(4), kind="nnz")
    sub = build_sharded_substrate(csr, spec, _FakeMesh(4),
                                  inner_kind="balanced", tile=64,
                                  inner_backend="pallas")
    with pytest.raises(ValueError, match="cannot stack prep opts"):
        _sharded_prep(sub, _logical="nb_pr")


# ---------------------------------------------------------------------------
# parity: sharded fused vs single-device, outputs and grads
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("kind", ["row", "nnz"])
def test_sharded_fused_matches_spill_and_single_device(kind):
    csr = _skewed_csr()
    p_one = plan(csr)
    p_fused = plan(csr, backend="sharded", mesh=_mesh(), shard_kind=kind,
                   tile=64, inner_backend="pallas")
    rng = np.random.default_rng(0)
    for n in (1, 8):
        shape = (csr.shape[1],) if n == 1 else (csr.shape[1], n)
        x = jnp.asarray(rng.standard_normal(shape).astype(np.float32))
        want = np.asarray(execute(p_one, x, impl="nb_pr"))
        got_f = np.asarray(execute(p_fused, x, impl="nb_pr", interpret=True))
        np.testing.assert_allclose(got_f, want, atol=2e-3)


@pytest.mark.parametrize("kind", ["row", "nnz"])
def test_sharded_fused_grads_match(kind):
    csr = _skewed_csr(seed=5)
    p_one = plan(csr)
    p_fused = plan(csr, backend="sharded", mesh=_mesh(), shard_kind=kind,
                   tile=64, inner_backend="pallas")
    rng = np.random.default_rng(1)
    x = jnp.asarray(rng.standard_normal((csr.shape[1], 6)).astype(np.float32))

    def loss(p, interpret):
        return lambda v, xx: (execute(p, xx, vals=v, impl="nb_pr",
                                      interpret=interpret) ** 2).sum()

    gv, gx = jax.grad(loss(p_fused, True), argnums=(0, 1))(csr.data, x)
    rv, rx = jax.grad(loss(p_one, None), argnums=(0, 1))(csr.data, x)
    np.testing.assert_allclose(np.asarray(gv), np.asarray(rv), atol=1e-2)
    np.testing.assert_allclose(np.asarray(gx), np.asarray(rx), atol=1e-2)


def test_sharded_fused_empty_row_shard():
    """A whole band of empty rows (one shard's worth under row-split) must
    produce zeros, not NaNs or stale blocks — empty shards get all-sentinel
    tiles whose dummy visits write zero-initialised output blocks."""
    m, k = 64, 32
    a = np.zeros((m, k), np.float32)
    a[:8, :] = np.random.default_rng(0).standard_normal((8, k))  # top-heavy
    csr = csr_from_dense(a)
    x = jnp.asarray(np.random.default_rng(1)
                    .standard_normal((k, 4)).astype(np.float32))
    for kind in ("row", "nnz"):
        p = plan(csr, backend="sharded", mesh=_mesh(), shard_kind=kind,
                 tile=32, inner_backend="pallas")
        got = np.asarray(execute(p, x, impl="nb_pr", interpret=True))
        np.testing.assert_allclose(got, a @ np.asarray(x), atol=2e-3)


def test_sharded_fused_single_shard_mesh():
    csr = _skewed_csr(seed=7)
    mesh = _mesh(1) if jax.device_count() == 1 else jax.make_mesh(
        (1, 1), ("data", "model"), devices=np.asarray(jax.devices()[:1]),
        axis_types=(jax.sharding.AxisType.Auto,) * 2)
    p = plan(csr, backend="sharded", mesh=mesh, shard_kind="nnz", tile=64,
             inner_backend="pallas")
    x = jnp.asarray(np.random.default_rng(2)
                    .standard_normal((csr.shape[1], 4)).astype(np.float32))
    got = np.asarray(execute(p, x, impl="nb_pr", interpret=True))
    np.testing.assert_allclose(got, _dense_of(csr) @ np.asarray(x), atol=2e-3)


def test_sharded_fused_bf16():
    csr = _skewed_csr(seed=9)
    p = plan(csr, backend="sharded", mesh=_mesh(), shard_kind="nnz", tile=64,
             inner_backend="pallas")
    x = jnp.asarray(np.random.default_rng(3)
                    .standard_normal((csr.shape[1], 4))).astype(jnp.bfloat16)
    got = execute(p, x, impl="nb_pr", interpret=True)
    assert got.dtype == jnp.bfloat16
    want = _dense_of(csr) @ np.asarray(x, np.float32)
    np.testing.assert_allclose(np.asarray(got, np.float32), want,
                               rtol=0.1, atol=0.1)


# ---------------------------------------------------------------------------
# the width-chunked collective-permute ring (overlap path)
# ---------------------------------------------------------------------------

def test_overlap_ring_matches_blocking_psum():
    csr = _skewed_csr(seed=11)
    rng = np.random.default_rng(4)
    x = jnp.asarray(rng.standard_normal((csr.shape[1], 300))
                    .astype(np.float32))
    p_ref = plan(csr)
    want = np.asarray(execute(p_ref, x, impl="nb_pr"))
    ring = SelectorThresholds(overlap_min_n=1)
    for inner in ("xla", "pallas"):
        p = plan(csr, backend="sharded", mesh=_mesh(), shard_kind="nnz",
                 tile=64, inner_backend=inner, thresholds=ring)
        interp = True if inner == "pallas" else None
        got = np.asarray(execute(p, x, impl="nb_pr", interpret=interp))
        np.testing.assert_allclose(got, want, atol=5e-3)


def test_overlap_ring_grads_match():
    csr = _skewed_csr(seed=13)
    rng = np.random.default_rng(5)
    x = jnp.asarray(rng.standard_normal((csr.shape[1], 200))
                    .astype(np.float32))
    p_ref = plan(csr)
    p = plan(csr, backend="sharded", mesh=_mesh(), shard_kind="nnz", tile=64,
             thresholds=SelectorThresholds(overlap_min_n=1))
    gv, gx = jax.grad(lambda v, xx: (execute(p, xx, vals=v, impl="nb_pr")
                                     ** 2).sum(), argnums=(0, 1))(csr.data, x)
    rv, rx = jax.grad(lambda v, xx: (execute(p_ref, xx, vals=v, impl="nb_pr")
                                     ** 2).sum(), argnums=(0, 1))(csr.data, x)
    np.testing.assert_allclose(np.asarray(gv), np.asarray(rv),
                               rtol=2e-2, atol=2e-1)
    np.testing.assert_allclose(np.asarray(gx), np.asarray(rx),
                               rtol=2e-2, atol=2e-1)


def test_overlap_threshold_serializes_v2():
    from repro.core import load_thresholds, save_thresholds
    import json
    th = SelectorThresholds(overlap_min_n=256)
    assert json.loads(th.to_json())["version"] == 2
    # defaults stay v1 so pre-overlap readers keep loading
    assert json.loads(SelectorThresholds().to_json())["version"] == 1
    legacy = '{"version": 1, "n_threshold": 4, "pr_avg_row": 32.0, "sr_cv": 0.5}'
    assert SelectorThresholds.from_json(legacy).overlap_min_n == 512
    with pytest.raises(ValueError):
        SelectorThresholds(overlap_min_n=0).validate()


# ---------------------------------------------------------------------------
# the plan-free pattern entry routes through the fused inner kernel
# ---------------------------------------------------------------------------

def test_execute_pattern_sharded_fused_matches_and_grads():
    csr = _skewed_csr(seed=15)
    bal = plan(csr, tile=64).substrate("balanced")
    rng = np.random.default_rng(6)
    x = jnp.asarray(rng.standard_normal((csr.shape[1], 8)).astype(np.float32))
    mesh = _mesh()
    args = (bal.rows, bal.cols, bal.vals, bal.shape)
    y_ref = execute_pattern(*args, x, mesh=mesh)              # xla inner
    y_fused = execute_pattern(*args, x, mesh=mesh, backend="pallas",
                              interpret=True)
    np.testing.assert_allclose(np.asarray(y_fused), np.asarray(y_ref),
                               atol=2e-3)
    gv = jax.grad(lambda v: (execute_pattern(
        bal.rows, bal.cols, v, bal.shape, x, mesh=mesh, backend="pallas",
        interpret=True) ** 2).sum())(bal.vals)
    rv = jax.grad(lambda v: (execute_pattern(
        bal.rows, bal.cols, v, bal.shape, x, mesh=mesh) ** 2).sum())(bal.vals)
    np.testing.assert_allclose(np.asarray(gv), np.asarray(rv), atol=1e-2)


def test_execute_pattern_sharded_traced_falls_back():
    """A traced pattern cannot run host-side prep — the sharded pattern
    entry must fall back to the prep-free XLA inner, not crash."""
    csr = _skewed_csr(seed=17)
    bal = plan(csr, tile=64).substrate("balanced")
    rng = np.random.default_rng(7)
    x = jnp.asarray(rng.standard_normal((csr.shape[1], 4)).astype(np.float32))
    mesh = _mesh()
    want = execute_pattern(bal.rows, bal.cols, bal.vals, bal.shape, x,
                           mesh=mesh)

    @jax.jit
    def f(r, c, v, xx):
        return execute_pattern(r, c, v, bal.shape, xx, mesh=mesh,
                               backend="pallas")

    got = f(bal.rows, bal.cols, bal.vals, x)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), atol=2e-3)


# ---------------------------------------------------------------------------
# bounded caches
# ---------------------------------------------------------------------------

def test_inner_bound_cache_is_bounded():
    entry = resolve("nb_pr", "xla")
    before = dict(_INNER_BOUND)
    try:
        for i in range(_INNER_BOUND_CAP + 16):
            _make_inner(entry, None, {"wb": 8 * (i + 1), "tile_n": 128},
                        ("visit_tile",))
        assert len(_INNER_BOUND) <= _INNER_BOUND_CAP
        # LRU: re-touching keeps an entry alive
        statics = {"wb": 8, "tile_n": 128}
        fn = _make_inner(entry, None, statics, ("visit_tile",))
        assert _make_inner(entry, None, statics, ("visit_tile",)) is fn
    finally:
        _INNER_BOUND.clear()
        _INNER_BOUND.update(before)
