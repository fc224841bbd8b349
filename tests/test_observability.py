"""Spans, compile counters and per-call work in ``repro.api.health()``
(DESIGN.md §12): the records the program keeps of its own planning, of
XLA's compilations and of what each bound kernel does per call."""
import glob
import os
import time

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from repro import api
from repro.core import csr_from_coo, csr_to_balanced, rmat
from repro.core import guardrails as G
from repro.core.formats import BalancedCOO
from repro.core.plan import pattern_prep, plan
from repro.kernels.fused_chain import sddmm_work
from repro.kernels.vsr import (GATHER_UNROLL, _nb_work, gather_width,
                               plan_visits)


@pytest.fixture(autouse=True)
def _fresh_health():
    api.reset_health()
    yield
    api.reset_health()


def test_span_nesting_self_time_and_parent():
    with G.HEALTH.span("outer"):
        time.sleep(0.02)
        with G.HEALTH.span("inner", kind="a"):
            time.sleep(0.03)
        with G.HEALTH.span("inner", kind="b"):
            pass
    spans = api.health()["spans"]
    outer, inner = spans["outer"], spans["inner"]
    assert outer["count"] == 1 and inner["count"] == 2
    assert outer["parents"] == {} and inner["parents"] == {"outer": 2}
    assert inner["args"] == {"kind=a": 1, "kind=b": 1}
    assert inner["self_s"] == pytest.approx(inner["total_s"])
    assert outer["self_s"] == pytest.approx(
        outer["total_s"] - inner["total_s"], abs=1e-9)
    assert outer["self_s"] >= 0.02 and inner["total_s"] >= 0.03


def test_span_records_through_an_exception_and_as_a_decorator():
    @G.HEALTH.span("decorated")
    def fails():
        raise ValueError("boom")

    for _ in range(2):
        with pytest.raises(ValueError):
            fails()
    assert api.health()["spans"]["decorated"]["count"] == 2
    with G.HEALTH.span("after"):
        pass
    assert api.health()["spans"]["after"]["parents"] == {}


def test_plan_span_lands_in_a_profiler_trace(tmp_path):
    """``repro.plan`` is a host annotation on the trace's clock: it covers
    its ``repro.plan.stats`` child and ends before the jitted call that
    follows it starts."""
    from jax.profiler import ProfileData
    f = jax.jit(lambda x: x * 2.0)
    x = jnp.ones(8)
    f(x).block_until_ready()
    jax.profiler.start_trace(str(tmp_path))
    try:
        plan(rmat(8, 4, seed=1))
        with jax.profiler.TraceAnnotation("call"):
            f(x).block_until_ready()
    finally:
        jax.profiler.stop_trace()
    path, = glob.glob(os.path.join(tmp_path, "plugins", "profile", "*",
                                   "*.xplane.pb"))
    ev = {}
    for plane in ProfileData.from_file(path).planes:
        if plane.name == "/host:CPU":
            for line in plane.lines:
                for e in line.events:
                    ev.setdefault(e.name, (e.start_ns,
                                           e.start_ns + e.duration_ns))
    (p0, p1), (s0, s1), (c0, _) = (ev["repro.plan"], ev["repro.plan.stats"],
                                   ev["call"])
    assert p0 <= s0 <= s1 <= p1 <= c0


def test_planning_and_tracing_record_their_spans():
    A = api.sparse(rmat(9, 4, seed=3), backend="pallas", cache=False)
    jax.jit(lambda x: A @ x).lower(
        jax.ShapeDtypeStruct((A.shape[1], 16), jnp.float32))
    spans = api.health()["spans"]
    for name in ("repro.plan", "repro.plan.stats", "repro.plan.substrate",
                 "repro.plan.prep", "repro.plan.visits",
                 "repro.plan.fingerprint", "repro.execute.trace"):
        assert spans[name]["count"] >= 1, name
    assert spans["repro.plan.substrate"]["args"] == {"kind=balanced": 1}
    assert spans["repro.plan.substrate"]["parents"] == {
        "repro.execute.trace": 1}
    assert spans["repro.plan.visits"]["parents"] == {"repro.plan.prep": 1}
    assert spans["repro.plan.prep"]["parents"] == {"repro.execute.trace": 1}


def _banded(m=640, k=512, seed=0):
    """Rows in bands with empty 64-row blocks between them, one long row:
    block crossings, shared tiles and dummy visits all occur."""
    rng = np.random.default_rng(seed)
    rows, cols = [], []
    for r in range(m):
        if (r // 64) % 3 == 1:
            continue                        # an empty band of output blocks
        n = 300 if r == 5 else int(rng.integers(1, 9))
        rows += [r] * n
        cols += list(rng.choice(k, n, replace=False))
    return csr_from_coo(np.array(rows), np.array(cols),
                        rng.standard_normal(len(rows)).astype(np.float32),
                        (m, k))


def _prefetches(vt):
    """``(used, drained)``: one sweep of the fused SpMM kernel's
    double-buffered gather, walked visit by visit — each new tile starts
    tile + 1 unless no later visit names a tile that far on."""
    used = drained = 0
    pending = None
    for v in range(len(vt)):
        if v and vt[v] == vt[v - 1]:
            continue
        if pending == vt[v]:
            used += 1
        elif pending is not None:
            drained += 1
        pending = vt[v] + 1 if vt[v] + 1 <= vt[-1] else None
    assert pending is None                  # nothing in flight at the end
    return used, drained


def _independent_count(bal, wb, n, width):
    """Visits, dummy visits, tile loads, prefetched loads (N > 1) and
    gathered rows (column blocks ``width`` wide) from the ``plan_visits``
    schedule and the tiles' rows, by direct iteration."""
    vt, vb, _ = plan_visits(bal, wb)
    rows = np.asarray(bal.rows)
    m = bal.shape[0]
    dummy = sum(1 for t, b in zip(vt, vb)
                if not np.any((rows[t] < m) & (rows[t] // wb == b)))
    loads = sum(1 for v in range(len(vt)) if v == 0 or vt[v] != vt[v - 1])
    t = rows.shape[1]
    count = {"visits": len(vt), "dummy_visits": dummy, "tile_loads": loads}
    if n == 1:
        return dict(count, rows_gathered=rows.size)
    used, drained = _prefetches(vt)
    return dict(count, prefetched_loads=used,
                rows_gathered=(loads + drained) * t * -(-n // width))


@pytest.mark.parametrize("csr", ["rmat", "banded"])
@pytest.mark.parametrize("n", [1, 128])
def test_work_matches_the_visit_schedule(csr, n):
    csr = rmat(10, 8, seed=5) if csr == "rmat" else _banded()
    A = api.sparse(csr, backend="pallas", cache=False)
    shape = (A.shape[1],) if n == 1 else (A.shape[1], n)
    jax.jit(lambda x: A @ x).lower(jax.ShapeDtypeStruct(shape, jnp.float32))
    p = A.plan
    opts = p.kernel_opts(p.entry(p.select(n)))
    (key, rec), = api.health()["work"].items()
    kernel = "repro_spmv_fused" if n == 1 else "repro_vsr_fused"
    assert key == f"{kernel}:{p.topology_key()[:12]}:n{n}"
    assert rec["kernel"] == kernel and rec["n"] == n and rec["binds"] == 1
    want = _independent_count(p.substrate("balanced"), opts["wb"], n, 128)
    assert {k: rec[k] for k in want} == want
    if n == 128:                        # one 128-wide column block
        assert rec["column_blocks"] == 1
        assert want["rows_gathered"] == want["tile_loads"] * p.tile
        # a row-ordered stream visits every tile: each load but the
        # sweep's first was prefetched, and no prefetch was drained
        assert rec["prefetched_loads"] == rec["tile_loads"] - 1
    else:
        assert "prefetched_loads" not in rec


def test_work_counts_a_drained_prefetch():
    """An all-sentinel tile no visit names: the copies started for it are
    drained, gathered all the same, and the load after it is not a
    prefetched one."""
    bal = csr_to_balanced(rmat(8, 8, seed=5), tile=64)
    m, t = bal.shape[0], bal.rows.shape[1]

    def skip(a, fill):
        return jnp.concatenate([a[:3], jnp.full((1, t), fill, a.dtype),
                                a[3:]])

    bal = BalancedCOO(skip(bal.rows, m), skip(bal.cols, 0),
                      skip(bal.vals, 0), bal.shape)
    vt, vb, _ = plan_visits(bal, 16)
    assert 3 not in vt
    name, work = _nb_work(bal, jax.ShapeDtypeStruct((m, 256), jnp.float32),
                          visit_tile=vt, visit_block=vb, wb=16)
    loads = work["tile_loads"]
    assert name == "repro_vsr_fused"
    assert _prefetches(vt) == (loads - 2, 1)
    assert work["prefetched_loads"] == loads - 1 - 1   # less the drain
    assert work["column_blocks"] == 1                  # 256 columns: one
    assert work["rows_gathered"] == (loads + 1) * t    # sweep


def test_work_names_the_gather_unroll():
    """The row-gather kernels record the copies one iteration of their
    issue loop starts; stacked layers sum their counts, not the unroll.
    SpMV gathers no rows, so its record has none."""
    bal = csr_to_balanced(rmat(8, 8, seed=5), tile=64)
    m = bal.shape[0]
    vt, vb, _ = plan_visits(bal, 16)
    for x in ((m, 128), (m,)):
        _, work = _nb_work(bal, jax.ShapeDtypeStruct(x, jnp.float32),
                           visit_tile=vt, visit_block=vb, wb=16)
        assert work.get("gather_unroll") == (GATHER_UNROLL if len(x) > 1
                                             else None)
    name, work = sddmm_work(bal.rows, jax.ShapeDtypeStruct((m, 1024),
                                                           jnp.float32))
    assert name == "repro_sddmm" and work["gather_unroll"] == GATHER_UNROLL
    rows = np.stack([np.asarray(bal.rows)] * 3)
    cols = np.stack([np.asarray(bal.cols)] * 3)
    pw = pattern_prep(rows, cols, bal.shape)["work"]
    one = dict(pattern_prep(rows[0], cols[0], bal.shape)["work"].forward)
    forward = dict(pw.forward)
    assert forward["gather_unroll"] == GATHER_UNROLL
    assert forward["tile_loads"] == 3 * one["tile_loads"]


@pytest.mark.parametrize("n,blocks", [(128, 1), (1024, 1), (4096, 4)])
def test_work_counts_column_blocks(n, blocks):
    """``_nb_work`` and a pattern's ``PatternWork`` count the kernel's
    column-block sweeps: at T = 512 one up to N = 1,024, where each row is
    one copy, and four at N = 4,096; the rows gathered scale with them."""
    bal = csr_to_balanced(rmat(9, 8, seed=5), tile=512)
    t, k = bal.rows.shape[1], bal.shape[1]
    vt, vb, _ = plan_visits(bal, 64)
    x = jax.ShapeDtypeStruct((k, n), jnp.float32)
    _, work = _nb_work(bal, x, visit_tile=vt, visit_block=vb, wb=64)
    assert work["column_blocks"] == blocks
    assert work["rows_gathered"] == work["tile_loads"] * t * blocks
    prep = pattern_prep(np.asarray(bal.rows), np.asarray(bal.cols),
                        bal.shape)
    prep["work"].record_forward(n)
    (rec,) = api.health()["work"].values()
    assert rec["n"] == n and rec["column_blocks"] == blocks
    assert rec["rows_gathered"] == work["rows_gathered"]


@pytest.mark.parametrize("d,blocks", [(1024, 1), (4096, 4)])
def test_sddmm_feature_blocks_follow_the_gather_width(d, blocks):
    """The SDDMM cuts its features by the same rule: d = 1,024 at T = 512
    stays one block of 1,024, d = 4,096 four."""
    rows = jax.ShapeDtypeStruct((10, 512), jnp.int32)
    _, work = sddmm_work(rows, jax.ShapeDtypeStruct((8, d), jnp.float32))
    assert gather_width(512, d) == 1024
    assert work["tile_loads"] == 10 * blocks


def test_banded_graph_has_dummy_visits():
    A = api.sparse(_banded(), backend="pallas", cache=False)
    bal = A.plan.substrate("balanced")
    want = _independent_count(bal, 64, 128, 128)
    assert want["dummy_visits"] > 0
    assert want["tile_loads"] < want["visits"]


def test_a_fresh_jit_bumps_the_compile_record():
    x = jnp.arange(5.0).block_until_ready()
    before = api.health()["compile"]

    def triple(x):
        return x * 3.0 + 1.0

    jax.jit(triple)(x).block_until_ready()
    after = api.health()["compile"]
    assert after["count"] == before["count"] + 1
    assert after["seconds"] > before["seconds"]
    assert after["by_function"]["jit(triple)"]["count"] == 1


def test_reset_clears_spans_compile_and_work():
    A = api.sparse(rmat(8, 4, seed=2), backend="pallas", cache=False)
    jax.jit(lambda x: A @ x)(jnp.ones((A.shape[1], 8))).block_until_ready()
    h = api.health()
    assert h["spans"] and h["work"] and h["compile"]["count"] >= 1
    api.reset_health()
    h = api.health()
    assert h["spans"] == {} and h["work"] == {}
    assert h["compile"] == {"count": 0, "seconds": 0.0, "cache_hits": 0,
                            "cache_misses": 0, "retrieval_s": 0.0,
                            "by_function": {}}
