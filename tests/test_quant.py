"""Quantized value streams (DESIGN.md §8): int8/fp8 substrates with fused
in-kernel dequant.

Parity is asserted two ways, deliberately:

* **tight** against the *dequantized-dense* reference — the dense matmul of
  exactly the values the coded stream represents.  This isolates the kernel
  contract (dequantize in-register, accumulate in f32) from quantization
  error itself, so the tolerance is accumulation-order noise (~1e-5), and
  it holds for fp8 as well as int8.
* **loose** against the unquantized plan, bounded analytically: per-nonzero
  rounding error is at most half its tile's scale, so any output element
  errs by at most ``0.5 · max_scale · Σ|x[:, j]|``.

Plus the plumbing: straight-through grads (baked dX must see *decoded*
values), the dynamic-range fallback, thresholds v3 persistence (v2 files
still load), PlanCache segmentation, the quant_min_n gate, and the
train/compress delegation.
"""
import dataclasses
import json

import numpy as np
import pytest
import jax
import jax.numpy as jnp
from jax.sharding import Mesh

from repro import api
from repro.core import (SelectorThresholds, csr_from_dense, execute, plan,
                        rmat)
from repro.core import quant as qm
from repro.core.cache import PlanCache

from conftest import random_csr


def _cases(rng):
    """(name, csr) sweep: skew, empty-row bands, single row."""
    cases = [("skewed_rmat", rmat(6, 8, seed=3))]
    a = np.zeros((48, 40), np.float32)
    a[1, :7] = rng.standard_normal(7)
    a[30, 5] = 2.5                                    # rows 2..29 empty
    a[45:, :] = (rng.random((3, 40)) < 0.3) * rng.standard_normal((3, 40))
    cases.append(("empty_rows", csr_from_dense(a)))
    b = ((rng.random((1, 40)) < 0.5)
         * rng.standard_normal((1, 40))).astype(np.float32)
    cases.append(("single_row", csr_from_dense(b)))
    return cases


def _dequant_dense(p) -> np.ndarray:
    """The dense matrix the plan's coded stream actually represents."""
    sub = p.substrate("balanced")
    sc = p.quant_scales()
    v = np.asarray(qm.dequantize_stream(sub.vals, sc)).reshape(-1)
    r = np.asarray(sub.rows).reshape(-1)
    c = np.asarray(sub.cols).reshape(-1)
    m = r < p.csr.shape[0]
    dense = np.zeros(p.csr.shape, np.float32)
    np.add.at(dense, (r[m], c[m]), v[m])
    return dense


def _loose_bound(p, x) -> float:
    sc = np.asarray(p.quant_scales())
    x2 = x if x.ndim == 2 else x[:, None]
    return float(0.5 * sc.max() * np.abs(x2).sum(axis=0).max()) + 1e-6


# ---------------------------------------------------------------------------
# kernel parity: xla and pallas, SpMM and SpMV
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("n", [1, 128])
def test_quant_parity_xla(rng, n):
    for name, csr in _cases(rng):
        p = plan(csr, backend="xla", quant="int8")
        assert p.quant == "int8", name
        assert p.substrate("balanced").vals.dtype == jnp.int8, name
        x = rng.standard_normal((csr.shape[1], n)).astype(np.float32)
        xj = jnp.asarray(x[:, 0] if n == 1 else x)
        got = np.asarray(execute(p, xj, impl="nb_pr"))
        ref = _dequant_dense(p) @ np.asarray(xj)
        np.testing.assert_allclose(got, ref, atol=2e-4, err_msg=name)
        base = np.asarray(execute(plan(csr, backend="xla"), xj, impl="nb_pr"))
        assert np.abs(got - base).max() <= _loose_bound(p, x), name


@pytest.mark.parametrize("n", [1, 128])
def test_quant_parity_pallas_fused_and_spill(rng, n):
    """The Pallas NB kernels dequantize the coded stream in register
    (scales on the scalar-prefetch path), called directly and through
    ``execute``, and agree with the dequantized-dense reference."""
    from repro.kernels.spmv import spmv_vsr_fused
    from repro.kernels.vsr import spmm_vsr_fused
    for name, csr in _cases(rng):
        p = plan(csr, backend="pallas", tile=64, quant="int8")
        sub = p.substrate("balanced")
        sc = p.quant_scales()
        x = rng.standard_normal((csr.shape[1], n)).astype(np.float32)
        xj = jnp.asarray(x[:, 0] if n == 1 else x)
        ref = _dequant_dense(p) @ np.asarray(xj)
        fused = spmv_vsr_fused if n == 1 else spmm_vsr_fused
        got_f = fused(sub, xj, scales=sc, wb=16, interpret=True)
        np.testing.assert_allclose(np.asarray(got_f), ref, atol=2e-3,
                                   err_msg=name)
        got_e = np.asarray(execute(p, xj, impl="nb_pr", interpret=True))
        np.testing.assert_allclose(got_e, ref, atol=2e-3, err_msg=name)


def test_quant_pins_nb_family(rng):
    """A low-skew matrix the selector would route to rs_* must still execute
    the NB kernels under quant — rs reads the float ELL/CSR substrate and
    would silently never touch the coded stream (exact output = the bug)."""
    csr, a = random_csr(rng, 64, 64, 0.2)        # uniform: rs territory
    p = plan(csr, backend="xla", quant="int8")
    pf = plan(csr, backend="xla")
    for n in (1, 16, 128):
        assert p.select(n).startswith("nb_"), p.select(n)
        assert p.select(n)[-2:] == pf.select(n)[-2:]   # SR/PR choice kept
    x = rng.standard_normal((64, 16)).astype(np.float32)
    got = np.asarray(execute(p, jnp.asarray(x)))
    assert np.abs(got - a @ x).max() > 0           # quant error is real
    np.testing.assert_allclose(got, _dequant_dense(p) @ x, atol=2e-4)
    art = p.finalize(16)
    assert art.select(16).startswith("nb_")


def test_quant_bf16_accumulation(rng):
    """A bf16 dense operand through the quantized plan: dequant is f32
    in-register, so the error stays at bf16-input scale, not int8 scale."""
    csr, _ = random_csr(rng, 64, 64, 0.2)
    p = plan(csr, backend="xla", quant="int8")
    x = rng.standard_normal((64, 8)).astype(np.float32)
    xb = jnp.asarray(x).astype(jnp.bfloat16)
    got = np.asarray(execute(p, xb, impl="nb_pr"), np.float32)
    ref = _dequant_dense(p) @ np.asarray(xb, np.float32)
    np.testing.assert_allclose(got, ref, atol=0.1, rtol=0.05)


def test_fp8_parity(rng):
    csr, _ = random_csr(rng, 48, 40, 0.2)
    p = plan(csr, backend="xla", quant="fp8")
    assert p.quant == "fp8"
    assert p.substrate("balanced").vals.dtype == qm.FP8_DTYPE
    x = jnp.asarray(rng.standard_normal((40, 16)).astype(np.float32))
    got = np.asarray(execute(p, x, impl="nb_pr"))
    np.testing.assert_allclose(got, _dequant_dense(p) @ np.asarray(x),
                               atol=2e-4)


# ---------------------------------------------------------------------------
# the sharded backend
# ---------------------------------------------------------------------------

def _dequant_dense_sharded(sub, shape) -> np.ndarray:
    rows, cols = np.asarray(sub.rows), np.asarray(sub.cols)
    src = np.asarray(sub.src)
    vals, sc = np.asarray(sub.vals, np.float32), np.asarray(sub.scales)
    dense = np.zeros(shape, np.float32)
    for s in range(rows.shape[0]):
        v = (vals[s].reshape(sc[s].shape[0], -1) * sc[s][:, None]).reshape(-1)
        m = src[s].reshape(-1) >= 0
        np.add.at(dense, (rows[s].reshape(-1)[m], cols[s].reshape(-1)[m]),
                  v[m])
    return dense


@pytest.mark.parametrize("n", [1, 128])
def test_quant_parity_sharded(rng, n):
    mesh = Mesh(np.array(jax.devices()), ("shard",))
    csr = rmat(6, 8, seed=3)
    A = api.sparse(csr, quant="int8", mesh=mesh, cache=False)
    assert A.plan.quant == "int8"
    sub = A.plan.substrate(A.plan.entry(A.plan.select(n)).substrate)
    assert sub.vals.dtype == jnp.int8 and sub.scales is not None
    x = rng.standard_normal((csr.shape[1], n)).astype(np.float32)
    xj = jnp.asarray(x[:, 0] if n == 1 else x)
    got = np.asarray(A @ xj)
    ref = _dequant_dense_sharded(sub, csr.shape) @ np.asarray(xj)
    np.testing.assert_allclose(got, ref, atol=2e-4)


def test_quant_sharded_grads(rng):
    mesh = Mesh(np.array(jax.devices()), ("shard",))
    csr = rmat(6, 8, seed=3)
    A = api.sparse(csr, quant="int8", mesh=mesh, cache=False)
    x = jnp.asarray(rng.standard_normal((csr.shape[1], 8)).astype(np.float32))
    sub = A.plan.substrate(A.plan.entry(A.plan.select(8)).substrate)
    dense = _dequant_dense_sharded(sub, csr.shape)
    gx = jax.grad(lambda xx: (A @ xx).sum())(x)
    np.testing.assert_allclose(np.asarray(gx),
                               dense.T @ np.ones((csr.shape[0], 8),
                                                 np.float32), atol=2e-4)


# ---------------------------------------------------------------------------
# gradients (single-device)
# ---------------------------------------------------------------------------

def test_quant_baked_dx_sees_decoded_values(rng):
    """dX through a baked int8 plan must use scale·code, not the raw codes
    (a silent ~scaleX error otherwise) — extra[0] carries the scales."""
    csr, _ = random_csr(rng, 48, 40, 0.3)
    p = plan(csr, backend="xla", quant="int8")
    x = jnp.asarray(rng.standard_normal((40, 8)).astype(np.float32))
    g = jnp.asarray(rng.standard_normal((48, 8)).astype(np.float32))
    gx = jax.grad(lambda xx: (execute(p, xx, impl="nb_pr") * g).sum())(x)
    np.testing.assert_allclose(np.asarray(gx),
                               _dequant_dense(p).T @ np.asarray(g),
                               atol=2e-4)


def test_quant_live_values_straight_through(rng):
    """with_values on a quantized plan keeps the stream live: grads w.r.t.
    the float values flow straight through the in-graph re-quantization."""
    csr, a = random_csr(rng, 48, 40, 0.3)
    A = api.sparse(csr, quant="int8", cache=False)
    x = jnp.asarray(rng.standard_normal((40, 8)).astype(np.float32))

    def loss(v):
        return ((A.with_values(v) @ x) ** 2).sum()

    g = jax.grad(loss)(csr.data)
    assert g.shape == csr.data.shape
    assert bool(jnp.isfinite(g).all())
    # direction check against the unquantized analytic gradient
    g_ref = jax.grad(lambda v: ((api.sparse(csr, cache=False)
                                 .with_values(v) @ x) ** 2).sum())(csr.data)
    cos = float(jnp.vdot(g, g_ref)
                / jnp.maximum(jnp.linalg.norm(g) * jnp.linalg.norm(g_ref),
                              1e-9))
    assert cos > 0.95


# ---------------------------------------------------------------------------
# fallback, gating, persistence, cache keys
# ---------------------------------------------------------------------------

def test_dynamic_range_fallback(rng):
    """A tile mixing 1e30 with O(1) values breaks the error bound: the plan
    must warn, demote to unquantized, and match the float plan exactly."""
    a = (rng.random((32, 32)) < 0.3) * rng.standard_normal((32, 32))
    a = a.astype(np.float32)
    a[0, 0] = 1e30
    csr = csr_from_dense(a)
    p = plan(csr, backend="xla", quant="int8")
    with pytest.warns(UserWarning, match="dynamic range"):
        p.substrate("balanced")        # substrates build lazily
    assert p.quant is None
    assert p.substrate("balanced").vals.dtype == jnp.float32
    x = jnp.asarray(rng.standard_normal((32, 4)).astype(np.float32))
    np.testing.assert_array_equal(
        np.asarray(execute(p, x, impl="nb_pr")),
        np.asarray(execute(plan(csr, backend="xla"), x, impl="nb_pr")))


def test_quant_min_n_gate(rng):
    csr, _ = random_csr(rng, 32, 32, 0.3)
    th = dataclasses.replace(SelectorThresholds(), quant_min_n=64)
    low = api.sparse(csr, quant="int8", n_hint=8, thresholds=th, cache=False)
    assert low.plan.quant is None
    high = api.sparse(csr, quant="int8", n_hint=128, thresholds=th,
                      cache=False)
    assert high.plan.quant == "int8"


def test_unknown_mode_rejected(rng):
    csr, _ = random_csr(rng, 16, 16, 0.5)
    with pytest.raises(ValueError, match="quant"):
        plan(csr, quant="int4")


def test_thresholds_v3_roundtrip(tmp_path):
    th = dataclasses.replace(SelectorThresholds(), quant_min_n=32)
    path = tmp_path / "th.json"
    api.save_thresholds(th, str(path))
    d = json.loads(path.read_text())
    assert d["version"] == 3 and d["quant_min_n"] == 32
    assert api.load_thresholds(str(path)) == th
    # v2 files (no quant_min_n) still load, defaulting the gate open
    d.pop("quant_min_n")
    d["version"] = 2
    path.write_text(json.dumps(d))
    assert api.load_thresholds(str(path)).quant_min_n == 1
    # a default-gate thresholds object still writes the pre-quant format
    # (older readers keep working)
    api.save_thresholds(SelectorThresholds(), str(path))
    assert json.loads(path.read_text())["version"] < 3


def test_plan_cache_quant_segmentation(rng):
    csr, _ = random_csr(rng, 32, 32, 0.3)
    cache = PlanCache(capacity=8)
    api.sparse(csr, cache=cache)
    api.sparse(csr, quant="int8", cache=cache)     # distinct entry
    api.sparse(csr, quant="int8", cache=cache)     # hit
    s = cache.stats()
    assert s["size"] == 2 and s["builds"] == 2 and s["hits"] == 1


def test_no_host_dequant_materialized(rng):
    """The executing substrate stays coded end-to-end: int8 values, f32
    scales riding plan aux — dequant happens inside the kernel, not as a
    pre-kernel float copy of the stream."""
    csr, _ = random_csr(rng, 64, 64, 0.2)
    A = api.sparse(csr, quant="int8", cache=False)
    sub = A.plan.substrate("balanced")
    assert sub.vals.dtype == jnp.int8
    assert A.plan.quant_scales().dtype == jnp.float32
    meta = A.finalize(n=8).meta
    assert meta.quant == "int8"


# ---------------------------------------------------------------------------
# shared scalar codec + byte model
# ---------------------------------------------------------------------------

def test_compress_delegates_to_core_quant(rng):
    from repro.train import compress
    assert compress.int8_encode is qm.int8_encode
    assert compress.int8_decode is qm.int8_decode
    x = jnp.asarray(rng.standard_normal(257).astype(np.float32))
    q, scale = compress.int8_encode(x)
    assert q.dtype == jnp.int8
    np.testing.assert_allclose(np.asarray(compress.int8_decode(q, scale)),
                               np.asarray(x),
                               atol=float(np.abs(x).max()) / 127 + 1e-7)


def test_quantize_stream_roundtrip_bound(rng):
    vals = rng.standard_normal((4, 64)).astype(np.float32)
    q, sc = qm.quantize_stream(jnp.asarray(vals), "int8")
    assert q.dtype == jnp.int8 and sc.shape == (4,)
    back = np.asarray(qm.dequantize_stream(q, sc))
    assert np.abs(back - vals).max() <= 0.5 * float(np.asarray(sc).max()) + 1e-7


def test_execute_pattern_quant_reaches_coded_path(rng):
    """pattern_matmul(quant=) must route pattern-only call sites through the
    coded substrates (in-graph re-quantize, straight-through grads) instead
    of silently planning float — on every backend it reaches."""
    from repro.api import pattern_matmul
    from repro.core.formats import csr_to_balanced
    csr, dense = random_csr(rng, 48, 40, 0.2)
    bal = csr_to_balanced(csr, tile=256)
    x = jnp.asarray(rng.standard_normal((40, 16)).astype(np.float32))
    ref = dense @ np.asarray(x)
    scale = float(np.abs(ref).max())
    for kw in ({"backend": "xla"}, {"backend": "pallas"},
               {"mesh": Mesh(np.array(jax.devices()[:1]), ("s",))}):
        yq = pattern_matmul(bal.rows, bal.cols, bal.vals, csr.shape, x,
                            quant="int8", **kw)
        yf = pattern_matmul(bal.rows, bal.cols, bal.vals, csr.shape, x, **kw)
        err_q = float(np.abs(np.asarray(yq) - ref).max())
        err_f = float(np.abs(np.asarray(yf) - ref).max())
        assert err_q / scale < 0.05                 # int8 error bound
        assert err_f / scale < 1e-5                 # float path untouched
        assert err_q > err_f                        # the coded path ran
    with pytest.raises(ValueError):
        pattern_matmul(bal.rows, bal.cols, bal.vals, csr.shape, x,
                       quant="int4")
    # straight-through grads survive the in-graph round-trip
    g = jax.grad(lambda v: jnp.sum(pattern_matmul(
        bal.rows, bal.cols, v, csr.shape, x, quant="int8")))(bal.vals)
    assert float(jnp.abs(g).max()) > 0
