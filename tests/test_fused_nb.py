"""The visit-schedule NB kernels + autotuned tile geometry (DESIGN.md §6).

The NB SpMM/SpMV kernels (accumulation into revisited output blocks, no
partials buffer) against the dense product and the xla lowering, across
skewed R-MAT patterns, empty rows, single-row matrices, bf16, and N in
{1, 7, 128, 300} — forward and backward; plus the geometry plumbing:
visit-schedule invariants, PlanCache keying, thresholds persistence, the
tuner, a tile spanning a long empty-row gap, and the rs_pr width-chunking
fix."""
import json
import warnings

import numpy as np
import pytest
import jax
import jax.numpy as jnp

from jax.experimental.pallas import tpu as pltpu

from repro.core import (SelectorThresholds, TileGeometry, csr_from_dense,
                        csr_to_balanced, csr_to_ell, execute, geometry_key,
                        plan, rmat, spmm_rs_pr)
from repro.core.cache import PlanCache, cached_plan, pattern_fingerprint
from repro.core.formats import BalancedCOO
from repro.core.quant import dequantize_stream, quantize_stream
from repro.core.shard import VISIT_PAD, stack_visit_schedules
from repro.kernels import vsr
from repro.kernels.fused_chain import sddmm_pallas
from repro.kernels.spmv import spmv_vsr_fused
from repro.kernels.vsr import (GATHER_BYTES, GATHER_UNROLL, _vsr_fused_call,
                               gather_width, plan_visits, spmm_vsr_fused)

from conftest import random_csr


def _cases(rng):
    """(name, csr, dense) sweep: skew, empty rows, single row."""
    cases = []
    skewed = rmat(6, 8, seed=3)                      # 64x64, heavy skew
    cases.append(("skewed_rmat", skewed, np.asarray(skewed.to_dense())))
    a = np.zeros((48, 40), np.float32)               # empty-row bands
    a[1, :7] = rng.standard_normal(7)
    a[30, 5] = 2.5                                    # rows 2..29 empty
    a[45:, :] = (rng.random((3, 40)) < 0.3) * rng.standard_normal((3, 40))
    cases.append(("empty_rows", csr_from_dense(a), a))
    b = (rng.random((1, 40)) < 0.5) * rng.standard_normal((1, 40))
    b = b.astype(np.float32)
    cases.append(("single_row", csr_from_dense(b), b))
    return cases


@pytest.mark.parametrize("n", [1, 7, 128, 300, 256, 384, 1024, 1536])
def test_fused_matches_dense_and_xla(rng, n):
    for name, csr, a in _cases(rng):
        bal = csr_to_balanced(csr, tile=64)
        x = rng.standard_normal((csr.shape[1], n)).astype(np.float32)
        xj = jnp.asarray(x[:, 0] if n == 1 else x)
        ref = a @ x[:, 0] if n == 1 else a @ x
        fused = spmv_vsr_fused if n == 1 else spmm_vsr_fused
        got = np.asarray(fused(bal, xj, wb=16, interpret=True))
        xla = np.asarray(execute(plan(csr, backend="xla"), xj))
        np.testing.assert_allclose(got, ref, atol=2e-3, err_msg=name)
        np.testing.assert_allclose(got, xla, atol=2e-3, err_msg=name)


@pytest.mark.parametrize("n", [1, 7])
def test_fused_registry_default_through_execute(rng, n):
    """The registry's pallas NB path defaults to the fused kernels: execute
    produces the reference answer with the prep-time visit schedule."""
    for name, csr, a in _cases(rng):
        p = plan(csr, backend="pallas", tile=64)
        entry = p.entry("nb_pr")
        opts = p.kernel_opts(entry)
        assert {"visit_tile", "visit_block", "visit_start",
                "wb"} <= set(opts), name
        # the column-block width follows the call's N, not the prep
        assert "tile_n" not in opts, name
        x = rng.standard_normal((csr.shape[1], n)).astype(np.float32)
        xj = jnp.asarray(x[:, 0] if n == 1 else x)
        got = np.asarray(execute(p, xj, impl="nb_pr", interpret=True))
        ref = a @ x[:, 0] if n == 1 else a @ x
        np.testing.assert_allclose(got, ref, atol=2e-3, err_msg=name)


def test_fused_bf16(rng):
    csr, a = random_csr(rng, 64, 64, 0.2)
    bal = csr_to_balanced(csr, tile=64)
    x = rng.standard_normal((64, 8)).astype(np.float32)
    got = np.asarray(spmm_vsr_fused(
        bal, jnp.asarray(x, jnp.bfloat16), wb=16,
        interpret=True)).astype(np.float32)
    np.testing.assert_allclose(got, a @ x, atol=0.15, rtol=0.05)


def _dense_grads(csr, a, x):
    nz = np.nonzero(np.asarray(a))

    def f(v, xx):
        dense = jnp.zeros(a.shape, v.dtype).at[nz].set(v)
        return ((dense @ xx) ** 2).sum()

    return jax.grad(f, argnums=(0, 1))(csr.data, x)


@pytest.mark.parametrize("n", [1, 4])
def test_fused_grads_match_dense(rng, n):
    """Gradients flow through core/vjp.py with the fused forward: value- and
    dense-operand grads for SpMM and the N=1 SpMV variant."""
    csr = rmat(5, 6, seed=7)                          # skewed 32x32
    a = np.asarray(csr.to_dense())
    p = plan(csr, backend="pallas", tile=32)
    x = rng.standard_normal((32, n)).astype(np.float32)
    xv = jnp.asarray(x[:, 0] if n == 1 else x)
    gd_v, gd_x = _dense_grads(csr, a, xv)
    gv, gx = jax.grad(
        lambda v, xx: (execute(p, xx, vals=v, impl="nb_pr",
                               interpret=True) ** 2).sum(),
        argnums=(0, 1))(csr.data, xv)
    np.testing.assert_allclose(np.asarray(gv), np.asarray(gd_v), atol=2e-3)
    np.testing.assert_allclose(np.asarray(gx), np.asarray(gd_x), atol=2e-3)


def test_fused_grads_empty_rows_under_jit(rng):
    a = np.zeros((24, 20), np.float32)
    a[0, :5] = rng.standard_normal(5)
    a[20, 3] = -1.5
    csr = csr_from_dense(a)
    p = plan(csr, backend="pallas", tile=16)
    x = jnp.asarray(rng.standard_normal((20, 3)).astype(np.float32))
    gd_v, gd_x = _dense_grads(csr, a, x)
    grad_fn = jax.jit(jax.grad(
        lambda v, xx: (execute(p, xx, vals=v, impl="nb_sr",
                               interpret=True) ** 2).sum(), argnums=(0, 1)))
    gv, gx = grad_fn(csr.data, x)
    np.testing.assert_allclose(np.asarray(gv), np.asarray(gd_v), atol=2e-3)
    np.testing.assert_allclose(np.asarray(gx), np.asarray(gd_x), atol=2e-3)


# ---------------------------------------------------------------------------
# the double-buffered row gather of repro_vsr_fused
# ---------------------------------------------------------------------------

def _dma_interpret(mode):
    """The TPU interpreter over VMEM that reads NaN until written, carrying
    out each DMA as it starts (``eager``) or only once a wait asks for it
    (``on_wait``).  ``on_wait`` shows a tile reduced before its row copies
    were waited on; ``eager`` shows a prefetch landing in the slot still
    being reduced.  Plain interpret mode shows neither."""
    return pltpu.InterpretParams(dma_execution_mode=mode,
                                 uninitialized_memory="nan")


def _skip_tile(bal, at):
    """``bal`` with an all-sentinel tile put before tile ``at``: no visit
    names it, so the copies prefetched for it are drained unused."""
    m, t = bal.shape[0], bal.rows.shape[1]

    def put(a, fill):
        return jnp.concatenate([a[:at], jnp.full((1, t), fill, a.dtype),
                                a[at:]])

    return BalancedCOO(put(bal.rows, m), put(bal.cols, 0), put(bal.vals, 0),
                       bal.shape)


def _dense(bal):
    """The dense ``(m, k)`` matrix a balanced stream holds."""
    m, k = bal.shape
    rows, cols = np.asarray(bal.rows).ravel(), np.asarray(bal.cols).ravel()
    live = rows < m
    out = np.zeros((m, k), np.float32)
    np.add.at(out, (rows[live], cols[live]),
              np.asarray(bal.vals, np.float32).ravel()[live])
    return out


def _fused_at(bal, x, wb, width, interpret, scales=None):
    """``spmm_vsr_fused`` with its column blocks forced ``width`` wide
    (``_vsr_fused_call``'s static), where ``gather_width`` would pick
    another: at 128, N over 128 takes a sweep per block."""
    n = x.shape[1]
    vt, vb, vs = map(jnp.asarray, plan_visits(bal, wb))
    xp = jnp.pad(x, ((0, 0), (0, -n % width)))
    return np.asarray(_vsr_fused_call(
        vt, vb, vs, bal.rows, bal.cols, bal.vals, xp, scales,
        m=bal.shape[0], wb=wb, tile_n=width, interpret=interpret))[:, :n]


def _pipeline_case(name, rng):
    """``(csr, bal, wb)`` of a schedule that takes one branch of the
    double-buffered gather."""
    if name == "single_tile":                  # one tile over five blocks
        csr, _ = random_csr(rng, 40, 40, 0.03)
        return csr, csr_to_balanced(csr, tile=64), 8
    if name == "block_crossings":              # tiles visit several blocks
        csr = rmat(6, 8, seed=3)
        return csr, csr_to_balanced(csr, tile=64), 16
    if name == "empty_row_bands":              # dummy visits between tiles
        a = np.zeros((96, 40), np.float32)
        a[:20] = (rng.random((20, 40)) < 0.2) * rng.standard_normal((20, 40))
        a[70:] = (rng.random((26, 40)) < 0.2) * rng.standard_normal((26, 40))
        csr = csr_from_dense(a.astype(np.float32))
        return csr, csr_to_balanced(csr, tile=32), 8
    if name == "remainder_tile":               # T % GATHER_UNROLL copies
        csr = rmat(6, 8, seed=3)               # left to the static tail
        return csr, csr_to_balanced(csr, tile=2 * GATHER_UNROLL + 3), 16
    if name == "short_tile":                   # T < GATHER_UNROLL: the tail
        csr, _ = random_csr(rng, 40, 40, 0.05)  # issues every copy
        return csr, csr_to_balanced(csr, tile=GATHER_UNROLL - 1), 8
    csr = rmat(6, 8, seed=3)                   # skipped_tile: a drain
    return csr, _skip_tile(csr_to_balanced(csr, tile=64), 2), 16


@pytest.mark.parametrize("mode", ["on_wait", "eager"])
@pytest.mark.parametrize("n", [8, 256])
@pytest.mark.parametrize("case", ["single_tile", "block_crossings",
                                  "empty_row_bands", "skipped_tile",
                                  "remainder_tile", "short_tile"])
def test_pipelined_gather_matches_dense_and_xla(rng, case, n, mode):
    """Tile i + 1's rows land in one slot while tile i reduces out of the
    other; N = 256 gathers each row as one 256-wide unit copy.  Tiles of
    ``T`` not a multiple of ``GATHER_UNROLL`` issue some or all of their
    copies from the issue loop's static tail."""
    csr, bal, wb, x = _checked_pipeline_case(case, n, rng)
    assert gather_width(bal.rows.shape[1], n, wb) >= n
    got = np.asarray(spmm_vsr_fused(bal, x, wb=wb,
                                    interpret=_dma_interpret(mode)))
    xla = np.asarray(execute(plan(csr, backend="xla"), x))
    np.testing.assert_allclose(got, xla, atol=2e-3)
    np.testing.assert_allclose(got, np.asarray(csr.to_dense()) @ x,
                               atol=2e-3)


@pytest.mark.parametrize("mode", ["on_wait", "eager"])
@pytest.mark.parametrize("case", ["single_tile", "block_crossings",
                                  "empty_row_bands", "skipped_tile",
                                  "remainder_tile", "short_tile"])
def test_pipelined_gather_two_sweeps(case, mode):
    """The same schedules with N = 300 cut into three 128-wide column
    blocks: each sweep starts cold (no rows in flight on its first visit),
    a prefetch drained at the end of one sweep leaves the next one's
    semaphores balanced, and the last block is a third padding.  Where
    ``gather_width`` has one block, the program takes this path at N over
    the VMEM budget (4,096 tokens at T = 512), on the CSC path and per
    shard.  The schedules come from a generator of their own, so the
    tests run before this one cannot change them."""
    rng = np.random.default_rng(300)
    csr, bal, wb, x = _checked_pipeline_case(case, 300, rng)
    got = _fused_at(bal, x, wb, 128, _dma_interpret(mode))
    np.testing.assert_allclose(got, np.asarray(csr.to_dense()) @ x,
                               atol=2e-3)
    np.testing.assert_allclose(
        got, np.asarray(spmm_vsr_fused(bal, x, wb=wb, interpret=True)),
        atol=2e-3)


def _checked_pipeline_case(case, n, rng):
    """``_pipeline_case`` with the branch it is for asserted, and a dense
    ``(k, n)`` operand."""
    csr, bal, wb = _pipeline_case(case, rng)
    vt, vb, _ = plan_visits(bal, wb)
    runs = vt[np.r_[True, vt[1:] != vt[:-1]]]
    rows = np.asarray(csr.to_dense()).any(axis=1)
    empty = [not rows[b * wb:(b + 1) * wb].any() for b in vb]
    assert len(runs) < len(vt)                 # tiles revisited
    assert (bal.n_tiles == 1) == (case == "single_tile")
    assert any(empty) == (case == "empty_row_bands")
    assert np.any(np.diff(runs) > 1) == (case == "skipped_tile")
    t = bal.rows.shape[1]
    assert (t % GATHER_UNROLL != 0) == (case in ("remainder_tile",
                                                  "short_tile"))
    assert (t < GATHER_UNROLL) == (case == "short_tile")
    x = jnp.asarray(rng.standard_normal((csr.shape[1], n)).astype(np.float32))
    return csr, bal, wb, x


@pytest.mark.parametrize("mode", ["on_wait", "eager"])
@pytest.mark.parametrize("tile,n,width", [(64, 1024, 1024), (512, 1536, 768)])
def test_gather_width_matches_128_wide_blocks_bitwise(rng, tile, n, width,
                                                      mode):
    """The column blocks ``gather_width`` gives — one 1,024-wide block at
    T = 64, two sweeps of 768 at T = 512 — against the kernel forced to
    128-wide blocks.  Small integer operands make every product and sum
    exact whatever order a backend's matmul adds in, so equality says each
    output column reduced the same rows as at 128 wide."""
    bal = csr_to_balanced(rmat(7, 8, seed=4), tile=tile)
    bal = BalancedCOO(bal.rows, bal.cols, jnp.asarray(
        rng.integers(-4, 5, bal.rows.shape), jnp.float32), bal.shape)
    assert gather_width(tile, n) == width
    x = jnp.asarray(rng.integers(-4, 5, (bal.shape[1], n)), jnp.float32)
    got = np.asarray(spmm_vsr_fused(bal, x, wb=16,
                                    interpret=_dma_interpret(mode)))
    np.testing.assert_array_equal(
        got, _fused_at(bal, x, 16, width, _dma_interpret(mode)))
    np.testing.assert_array_equal(got, _fused_at(bal, x, 16, 128, True))
    np.testing.assert_array_equal(got, _dense(bal) @ np.asarray(x))


@pytest.mark.parametrize("t,n,width", [
    (256, 128, 128), (256, 1024, 1024), (256, 1536, 1536), (256, 4096, 2048),
    (512, 128, 128), (512, 1024, 1024), (512, 1536, 768), (512, 4096, 1024),
    (1024, 128, 128), (1024, 1024, 512), (1024, 1536, 512),
    (1024, 4096, 512)])
def test_gather_width(t, n, width):
    """All of N where both ``(T, N)`` f32 gather slots fit
    ``GATHER_BYTES``; else the fewest blocks that fit, cut equal so the
    last pads least (1,536 at T = 512 is 2 × 768, not 1,024 + a half-empty
    1,024)."""
    assert gather_width(t, n) == width
    blocks = -(-n // width)
    widest = GATHER_BYTES // (2 * 4 * t) // 128 * 128
    assert width % 128 == 0 and width <= widest
    assert blocks == -(-n // widest)
    assert blocks * width - n < 128 * blocks


@pytest.mark.parametrize("t,wb,n,width", [
    (512, 64, 1024, 1024), (512, 128, 1024, 1024), (128, 512, 4096, 1024),
    (256, 1024, 1024, 512)])
def test_gather_width_fits_the_output_blocks(t, wb, n, width):
    """The pipeline's two ``(wb, w)`` output blocks fit ``GATHER_BYTES``
    as the two gather slots do, so an output block taller than the tile
    narrows the width; at ``wb <= T`` the slots alone decide."""
    assert gather_width(t, n, wb) == width
    assert 2 * 4 * width * max(t, wb) <= GATHER_BYTES
    assert (width == gather_width(t, n)) == (wb <= t)


@pytest.mark.parametrize("mode", ["on_wait", "eager"])
def test_pipelined_gather_int8_stream(rng, mode):
    """An int8 value stream with per-tile scales, in one 256-wide sweep
    and in two sweeps forced 128 wide: the in-register dequant matches the
    kernel on the dequantized stream and the dense product of it."""
    csr = rmat(6, 8, seed=5)
    bal = csr_to_balanced(csr, tile=64)
    q, sc = quantize_stream(bal.vals, "int8")
    qbal = BalancedCOO(bal.rows, bal.cols, q, bal.shape)
    x = jnp.asarray(rng.standard_normal((64, 130)).astype(np.float32))
    got = np.asarray(spmm_vsr_fused(qbal, x, wb=16, scales=sc,
                                    interpret=_dma_interpret(mode)))
    deq = BalancedCOO(bal.rows, bal.cols, dequantize_stream(q, sc),
                      bal.shape)
    fused = np.asarray(spmm_vsr_fused(deq, x, wb=16, interpret=True))
    np.testing.assert_allclose(got, fused, atol=2e-3)
    np.testing.assert_allclose(got, _dense(deq) @ np.asarray(x), atol=2e-3)
    swept = _fused_at(qbal, x, 16, 128, _dma_interpret(mode), scales=sc)
    np.testing.assert_allclose(swept, fused, atol=2e-3)


@pytest.mark.parametrize("mode", ["on_wait", "eager"])
@pytest.mark.parametrize("tile", ["remainder", "short"])
def test_unit_row_gather_remainder_sddmm(rng, tile, mode):
    """The SDDMM's ``(rows, 1, w)`` unit copies, sentinel rows clamped, at
    a ``T`` the issue loop's groups do not divide: every score is read
    only once all ``T`` copies of both endpoints have landed."""
    t = 2 * GATHER_UNROLL + 3 if tile == "remainder" else GATHER_UNROLL - 1
    csr, a = random_csr(rng, 40, 30, 0.1)
    bal = csr_to_balanced(csr, tile=t)
    rows, cols = np.asarray(bal.rows), np.asarray(bal.cols)
    assert (rows == 40).any()                  # a padded last tile
    g = rng.standard_normal((40, 200)).astype(np.float32)
    x = rng.standard_normal((30, 200)).astype(np.float32)
    got = np.asarray(sddmm_pallas(bal.rows, bal.cols, jnp.asarray(g),
                                  jnp.asarray(x), shape=(40, 30),
                                  interpret=_dma_interpret(mode)))
    live = rows < 40
    want = np.where(live, np.einsum("tkd,tkd->tk", g[np.minimum(rows, 39)],
                                    x[cols]), 0.0)
    np.testing.assert_allclose(got, want, rtol=1e-4, atol=1e-3)


def test_pipelined_gather_grads_match_dense(rng):
    csr = rmat(5, 6, seed=7)                          # skewed 32x32
    a = np.asarray(csr.to_dense())
    p = plan(csr, backend="pallas", tile=32)
    x = jnp.asarray(rng.standard_normal((32, 130)).astype(np.float32))
    gd_v, gd_x = _dense_grads(csr, a, x)
    ip = _dma_interpret("on_wait")
    gv, gx = jax.grad(
        lambda v, xx: (execute(p, xx, vals=v, impl="nb_pr",
                               interpret=ip) ** 2).sum(),
        argnums=(0, 1))(csr.data, x)
    np.testing.assert_allclose(np.asarray(gv), np.asarray(gd_v), atol=2e-3)
    np.testing.assert_allclose(np.asarray(gx), np.asarray(gd_x), atol=2e-3)


def test_pipelined_gather_grads_two_sweeps(monkeypatch):
    """Gradients through ``execute`` where N does not fit one column
    block: the VMEM budget cut to two ``(32, 128)`` slots makes N = 200
    two 128-wide sweeps, which the traced kernel's grid shows."""
    rng = np.random.default_rng(200)
    monkeypatch.setattr(vsr, "GATHER_BYTES", 2 * 4 * 32 * 128)
    csr = rmat(5, 6, seed=7)
    a = np.asarray(csr.to_dense())
    p = plan(csr, backend="pallas", tile=32)
    x = jnp.asarray(rng.standard_normal((32, 200)).astype(np.float32))
    gd_v, gd_x = _dense_grads(csr, a, x)
    ip = _dma_interpret("on_wait")
    grad = jax.grad(
        lambda v, xx: (execute(p, xx, vals=v, impl="nb_pr",
                               interpret=ip) ** 2).sum(),
        argnums=(0, 1))
    assert "grid=(2, " in str(jax.make_jaxpr(grad)(csr.data, x))
    gv, gx = grad(csr.data, x)
    np.testing.assert_allclose(np.asarray(gv), np.asarray(gd_v), atol=2e-3)
    np.testing.assert_allclose(np.asarray(gx), np.asarray(gd_x), atol=2e-3)


def test_pipelined_gather_stacked_schedule(rng):
    """A shard's schedule padded to a longer one's with ``VISIT_PAD``
    visits, as the sharded backend stacks them: the padding repeats the
    last tile, so it neither waits nor prefetches."""
    long_csr = rmat(6, 8, seed=3)
    short_csr, a = random_csr(rng, 64, 64, 0.05)
    short = csr_to_balanced(short_csr, tile=64)
    vt, vb, vs = stack_visit_schedules(
        [plan_visits(csr_to_balanced(long_csr, tile=64), 16),
         plan_visits(short, 16)])
    assert (vs[1] == VISIT_PAD).sum() > 0
    x = jnp.asarray(rng.standard_normal((64, 8)).astype(np.float32))
    got = np.asarray(spmm_vsr_fused(
        short, x, wb=16, visit_tile=jnp.asarray(vt[1]),
        visit_block=jnp.asarray(vb[1]), visit_start=jnp.asarray(vs[1]),
        interpret=_dma_interpret("on_wait")))
    xla = np.asarray(execute(plan(short_csr, backend="xla"), x))
    np.testing.assert_allclose(got, xla, atol=2e-3)
    np.testing.assert_allclose(got, a @ np.asarray(x), atol=2e-3)


# ---------------------------------------------------------------------------
# visit-schedule invariants
# ---------------------------------------------------------------------------

def test_plan_visits_invariants(rng):
    for name, csr, a in _cases(rng):
        for wb in (8, 16, 64):
            bal = csr_to_balanced(csr, tile=32)
            vt, vb, vs = plan_visits(bal, wb)
            m = bal.shape[0]
            mb = max(1, -(-m // wb))
            # non-decreasing blocks, full coverage, one start per block
            assert np.all(np.diff(vb) >= 0), (name, wb)
            assert set(vb.tolist()) == set(range(mb)), (name, wb)
            starts = vs.astype(bool)
            assert starts[0] and np.all(starts[1:] == (vb[1:] != vb[:-1]))
            # every (tile, block) pair a real row needs is scheduled
            rows = np.asarray(bal.rows)
            for t in range(bal.n_tiles):
                r = rows[t][rows[t] < m]
                for b in np.unique(r // wb):
                    assert np.any((vt == t) & (vb == b)), (name, wb, t, b)


def test_plan_visits_skew_does_not_tax_every_tile():
    """One tile straddles a 4,000-row gap, so a window sized by the worst
    tile would charge every tile thousands of rows; the visit count only
    charges the tiles that cross blocks."""
    a = np.zeros((4096, 16), np.float32)
    a[:60, :] = 1.0                                   # dense head (960 nnz)
    a[4095, 0] = 1.0                                  # far row shares a tile
    csr = csr_from_dense(a)
    bal = csr_to_balanced(csr, tile=128)
    rows = np.asarray(bal.rows)
    live = rows < bal.shape[0]
    span = max(int(r[v].max() - r[v].min()) + 1
               for r, v in zip(rows, live) if v.any())
    assert span > 3000                                 # the worst tile
    vt, vb, vs = plan_visits(bal, 64)
    # the fused path's DMA cost is tile-stream *runs* (consecutive visits of
    # one tile — crossings and dummies re-use the resident tile): the gap
    # only adds empty-block dummy visits, not re-streams
    runs = 1 + int(np.count_nonzero(vt[1:] != vt[:-1]))
    assert runs <= bal.n_tiles + 2


# ---------------------------------------------------------------------------
# geometry: thresholds v2, cache keys, tuner, guard
# ---------------------------------------------------------------------------

def test_thresholds_v2_roundtrip_and_v1_compat(tmp_path):
    th = SelectorThresholds().with_geometry(
        geometry_key("pallas", "ab" * 20, 8), TileGeometry(256, 32, 128))
    text = th.to_json()
    assert json.loads(text)["version"] == 2
    assert SelectorThresholds.from_json(text) == th
    # v1 files (no geometry table) still load
    v1 = json.dumps({"version": 1, "n_threshold": 4, "pr_avg_row": 32.0,
                     "sr_cv": 0.5})
    th1 = SelectorThresholds.from_json(v1)
    assert th1.geometries == ()
    # plain thresholds still write v1
    assert json.loads(SelectorThresholds().to_json())["version"] == 1
    with pytest.raises(ValueError):
        SelectorThresholds(geometries=(("k", (0, 32, 128)),)).validate()
    with pytest.raises(ValueError):
        TileGeometry(512, 12, 128).validate()          # wb not sublane-aligned


#: one non-default field per schema version, as each version's writer
#: emitted it: the lowest version that holds the field
_VERSIONED = {2: {"overlap_min_n": 64}, 3: {"quant_min_n": 8},
              4: {"chain_fuse_min_n": 16}, 5: {"attn_fuse_min_seq": 256}}


@pytest.mark.parametrize("version", [2, 3, 4, 5])
def test_thresholds_file_with_max_win_loads(version):
    """A persisted file of each version that still carries ``max_win``
    (as older releases wrote it) loads with the key ignored, and
    saving it again writes the same version without that key."""
    th = SelectorThresholds(**_VERSIONED[version])
    d = json.loads(th.to_json())
    assert d["version"] == version and "max_win" not in d
    legacy = dict(d, max_win=512)
    loaded = SelectorThresholds.from_json(json.dumps(legacy))
    assert loaded == th
    assert json.loads(loaded.to_json()) == d


def test_geometry_distinct_cache_entries(rng):
    csr, _ = random_csr(rng, 32, 32, 0.2)
    cache = PlanCache(capacity=8)
    g1 = TileGeometry(256, 32, 128)
    g2 = TileGeometry(512, 64, 128)
    p1 = cached_plan(csr, cache=cache, backend="xla", geometry=g1)
    p2 = cached_plan(csr, cache=cache, backend="xla", geometry=g2)
    p1b = cached_plan(csr, cache=cache, backend="xla", geometry=g1)
    assert p1 is p1b and p1 is not p2                 # distinct ⇒ distinct
    s = cache.stats()
    assert s["builds"] == 2 and s["hits"] == 1
    # geometry-bearing thresholds segment the key too
    th = SelectorThresholds().with_geometry(
        geometry_key("xla", pattern_fingerprint(csr), None), g1)
    p3 = cached_plan(csr, cache=cache, backend="xla", thresholds=th)
    assert p3 is not p1 and p3.tile == g1.tile


def test_autotuner_picks_up_in_plan(rng):
    from repro.kernels.tune import autotune_geometry
    csr, a = random_csr(rng, 40, 30, 0.25)
    cands = (TileGeometry(64, 8, 128), TileGeometry(128, 16, 128))
    th = autotune_geometry(csr, ns=(4,), backend="pallas", interpret=True,
                           repeats=1, candidates=cands)
    keys = dict(th.geometries)
    fp = pattern_fingerprint(csr)
    assert geometry_key("pallas", fp, 4) in keys
    assert geometry_key("pallas", fp, None) in keys   # wildcard entry
    p = plan(csr, backend="pallas", thresholds=th, n_hint=4)
    assert p.geometry in cands and p.tile == p.geometry.tile
    x = jnp.asarray(rng.standard_normal((30, 4)).astype(np.float32))
    np.testing.assert_allclose(
        np.asarray(execute(p, x, impl="nb_pr", interpret=True)),
        a @ np.asarray(x), atol=2e-3)
    # the public facade reaches the N-bucketed entry too (regression: sparse
    # didn't forward n_hint into geometry resolution)
    import repro.api as api
    m = api.sparse(csr, backend="pallas", thresholds=th, n_hint=4,
                   cache=False)
    assert m.plan.geometry == p.geometry
    m2 = api.sparse(csr, backend="pallas", thresholds=th, n_hint=4)
    m3 = api.sparse(csr, backend="pallas", thresholds=th, n_hint=3)
    assert m3.plan is m2.plan        # same bucket ⇒ same resolved geometry


def test_pathological_span_stays_on_pallas(rng):
    """A tile spanning a 5,000-row gap stays on Pallas: the kernels size
    work per output block, so the plan is correct and warns nothing."""
    a = np.zeros((5000, 16), np.float32)
    a[0, :4] = 1.0
    a[4999, 0] = 1.0                                   # 5000-row gap in 1 tile
    csr = csr_from_dense(a)
    x = jnp.asarray(rng.standard_normal((16, 3)).astype(np.float32))
    with warnings.catch_warnings():
        warnings.simplefilter("error")
        p = plan(csr, backend="pallas")
        got = np.asarray(execute(p, x, impl="nb_pr", interpret=True))
    assert p.backend == "pallas"
    assert p.substrate("balanced").n_tiles == 1
    np.testing.assert_allclose(got, a @ np.asarray(x), atol=2e-3)


# ---------------------------------------------------------------------------
# rs_pr width chunking
# ---------------------------------------------------------------------------

def test_rs_pr_width_chunking_matches_unchunked(rng):
    a = np.zeros((40, 64), np.float32)
    a[7, :] = rng.standard_normal(64)                  # hub row → width 64
    a[: 40] += (rng.random((40, 64)) < 0.05) * rng.standard_normal((40, 64))
    a = a.astype(np.float32)
    ell = csr_to_ell(csr_from_dense(a))
    x = jnp.asarray(rng.standard_normal((64, 5)).astype(np.float32))
    full = np.asarray(spmm_rs_pr(ell, x))              # one-shot path
    chunked = np.asarray(spmm_rs_pr(ell, x, slab_elems=40 * 5 * 3))
    np.testing.assert_allclose(chunked, full, atol=1e-4)
    np.testing.assert_allclose(chunked, a @ np.asarray(x), atol=1e-3)
    # 1-D operand and jit through the chunked path
    xv = jnp.asarray(rng.standard_normal(64).astype(np.float32))
    got = jax.jit(lambda v: spmm_rs_pr(ell, v, slab_elems=100))(xv)
    np.testing.assert_allclose(np.asarray(got), a @ np.asarray(xv), atol=1e-3)
