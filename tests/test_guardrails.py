"""Core execution guardrails (DESIGN.md §12): pattern validation/repair,
numeric sentinels, the backend degradation ladder with circuit breakers,
fault sites, and plan integrity digests."""
import contextlib
import dataclasses
import warnings

import numpy as np
import pytest

import jax
import jax.numpy as jnp

import repro.api as api
from _hypothesis_compat import MALFORMED_KINDS, malformed_csr
from conftest import random_csr
from repro.core import guardrails as G
from repro.core import registry
from repro.core.cache import PlanCache, cached_plan
from repro.core.formats import CSR, csr_from_dense
from repro.core.plan import execute, execute_attention, execute_chain, plan
from repro.core.selector import default_thresholds
from repro.launch.mesh import make_local_mesh
from repro.runtime.faults import (FaultInjector, FaultSpec, InjectedFault,
                                  inject_faults)


@pytest.fixture(autouse=True)
def _fresh_health():
    G.HEALTH.reset()
    G.HEALTH.configure()
    yield
    G.HEALTH.reset()
    G.HEALTH.configure()


def _dense_semantics(csr):
    """The meaning a malformed CSR repairs to: duplicates coalesce by
    summation, out-of-range columns drop, non-finite values zero."""
    m, k = (int(s) for s in csr.shape)
    indptr = np.asarray(csr.indptr)
    idx = np.asarray(csr.indices)
    dat = np.asarray(csr.data, np.float64)
    out = np.zeros((m, k), np.float64)
    for r in range(m):
        for j in range(int(indptr[r]), int(indptr[r + 1])):
            c = int(idx[j])
            if 0 <= c < k:
                out[r, c] += dat[j] if np.isfinite(dat[j]) else 0.0
    return out


def _shuffle_rows(csr, seed=1):
    """Permute indices/data within each row (clean matrix → 'unsorted')."""
    indptr = np.asarray(csr.indptr)
    idx = np.asarray(csr.indices).copy()
    dat = np.asarray(csr.data).copy()
    r = np.random.default_rng(seed)
    for i in range(int(csr.shape[0])):
        lo, hi = int(indptr[i]), int(indptr[i + 1])
        pm = r.permutation(hi - lo)
        idx[lo:hi] = idx[lo:hi][pm]
        dat[lo:hi] = dat[lo:hi][pm]
    return CSR(csr.indptr, jnp.asarray(idx), jnp.asarray(dat), csr.shape)


# ---------------------------------------------------------------------------
# pillar 1: pattern validation & repair
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("kind", MALFORMED_KINDS)
def test_repair_produces_canonical_clean(kind):
    for seed in range(4):
        csr = malformed_csr(kind, seed)
        assert not G.inspect_csr(csr).ok
        fixed = G.repair_csr(csr)
        assert G.inspect_csr(fixed).ok, (kind, seed)
        np.testing.assert_allclose(_dense_semantics(fixed),
                                   _dense_semantics(csr), rtol=1e-6)


def test_repair_matches_presorted_reference(rng):
    csr, _ = random_csr(np.random.default_rng(0), 16, 12, 0.4)
    shuffled = _shuffle_rows(csr)
    fixed, report = G.validate_csr(shuffled, "repair")
    # bit-identical to what the pre-sorted input would have produced
    assert np.array_equal(np.asarray(fixed.indptr), np.asarray(csr.indptr))
    assert np.array_equal(np.asarray(fixed.indices), np.asarray(csr.indices))
    assert np.array_equal(np.asarray(fixed.data), np.asarray(csr.data))
    assert G.HEALTH.counter("pattern_repairs") == 1
    # clean input passes through untouched (same object, no counters)
    same, rep = G.validate_csr(csr, "repair")
    assert same is csr and rep.ok
    assert G.HEALTH.counter("pattern_repairs") == 1


def test_repair_handles_broken_indptr():
    csr, _ = random_csr(np.random.default_rng(3), 8, 6, 0.5)
    nnz = csr.nnz
    bad_ptr = np.asarray(csr.indptr).copy()
    bad_ptr[2] = nnz + 7          # non-monotone + out of range
    broken = CSR(jnp.asarray(bad_ptr), csr.indices, csr.data, csr.shape)
    assert "indptr" in G.inspect_csr(broken).issues
    fixed = G.repair_csr(broken)
    assert G.inspect_csr(fixed).ok


def test_validate_policies():
    bad = malformed_csr("mixed", 0)
    with pytest.raises(G.PatternError) as ei:
        G.validate_csr(bad, "strict")
    assert "out_of_range" in ei.value.issues
    assert isinstance(ei.value, ValueError)
    with pytest.warns(UserWarning, match="pattern has issues"):
        same, rep = G.validate_csr(bad, "check")
    assert same is bad and not rep.ok
    same2, rep2 = G.validate_csr(bad, "off")
    assert same2 is bad and rep2.ok          # off: no detection at all
    with pytest.raises(ValueError, match="unknown validate policy"):
        G.validate_csr(bad, "fixit")
    assert G.HEALTH.counter("pattern_issues") == 2   # strict + check


def test_sparse_validate_repair_executes():
    bad = malformed_csr("mixed", 3)
    m = api.sparse(bad, validate="repair", cache=False)
    x = np.random.default_rng(0).standard_normal(
        (int(bad.shape[1]), 4)).astype(np.float32)
    y = np.asarray(m.matmul(jnp.asarray(x)))
    ref = _dense_semantics(bad) @ x.astype(np.float64)
    np.testing.assert_allclose(y, ref, rtol=1e-4, atol=1e-4)
    with pytest.raises(api.PatternError):
        api.sparse(bad, validate="strict", cache=False)


def test_plan_validate_and_sentinel_args():
    bad = malformed_csr("unsorted", 1)
    p = plan(bad, backend="xla", validate="repair")
    assert G.inspect_csr(p.csr).ok
    with pytest.raises(G.PatternError):
        plan(bad, backend="xla", validate="strict")
    clean, _ = random_csr(np.random.default_rng(4), 8, 6, 0.5)
    with pytest.raises(ValueError, match="sentinel policy"):
        plan(clean, backend="xla", sentinel="bogus")


def test_cached_plan_repair_shares_clean_key():
    csr, _ = random_csr(np.random.default_rng(5), 12, 10, 0.4)
    shuffled = _shuffle_rows(csr, seed=7)
    cache = PlanCache(8)
    p1 = cached_plan(csr, cache=cache, backend="xla")
    # the repaired matrix keys under its canonical fingerprint → cache hit
    p2 = cached_plan(shuffled, cache=cache, backend="xla", validate="repair")
    assert p2 is p1
    assert cache.stats()["hits"] == 1 and cache.stats()["builds"] == 1


# ---------------------------------------------------------------------------
# pillar 3: degradation ladder + breakers + fault sites
# ---------------------------------------------------------------------------

def _mat(seed=2, m=32, k=24, n=8, density=0.3):
    rng = np.random.default_rng(seed)
    csr, _ = random_csr(rng, m, k, density)
    x = jnp.asarray(rng.standard_normal((k, n)).astype(np.float32))
    return csr, x


def test_fault_matrix_breaker_trip_reroute_recover():
    """The deterministic fault matrix: threshold=2, cooldown=0,
    3 injected pallas failures → reroute, trip, half-open probe failure,
    then a successful probe recovery — outputs bitwise-equal to xla
    throughout, all visible in api.health()."""
    csr, x = _mat()
    G.HEALTH.configure(threshold=2, cooldown_s=0.0)
    p = plan(csr, backend="pallas")
    ref = plan(csr, backend="xla")
    want = np.asarray(execute(ref, x, impl="nb_pr"))
    fi = FaultInjector({"kernel_execute:pallas": FaultSpec(fail=3)})
    outs = []
    with inject_faults(fi):
        for _ in range(4):
            outs.append(np.asarray(execute(p, x, impl="nb_pr",
                                           interpret=True)))
    # calls 1-3 rerouted through the identical xla path: bitwise equal
    for i in range(3):
        assert np.array_equal(outs[i], want), f"call {i} not bitwise xla"
    # call 4: half-open probe succeeds on the real pallas primary
    np.testing.assert_allclose(outs[3], want, rtol=2e-5, atol=2e-5)
    h = api.health()
    assert h["counters"]["kernel_reroute:pallas->xla:nb_pr"] == 3
    assert h["breakers"]["pallas:nb_pr"] == {
        "state": "closed", "failures": 0, "trips": 2, "recoveries": 1}


def test_breaker_reroute_grads_bitwise():
    csr, x = _mat(seed=6)
    G.HEALTH.configure(threshold=2, cooldown_s=0.0)
    p = plan(csr, backend="pallas")
    ref = plan(csr, backend="xla")
    g_ref = jax.grad(lambda xx: execute(ref, xx, impl="nb_pr").sum())(x)
    fi = FaultInjector({"kernel_execute:pallas": FaultSpec(fail=1)})
    with inject_faults(fi):
        g = jax.grad(lambda xx: execute(p, xx, impl="nb_pr",
                                        interpret=True).sum())(x)
    # the backward is kernel-independent (shared custom VJP), so the
    # rerouted forward yields grads bitwise-equal to the xla path
    assert np.array_equal(np.asarray(g), np.asarray(g_ref))
    assert G.HEALTH.counter("kernel_reroute:pallas->xla:nb_pr") == 1


def test_open_breaker_skips_primary():
    csr, x = _mat(seed=7)
    G.HEALTH.configure(threshold=1, cooldown_s=3600.0)
    p = plan(csr, backend="pallas")
    ref = plan(csr, backend="xla")
    want = np.asarray(execute(ref, x, impl="nb_pr"))
    with inject_faults(FaultInjector(
            {"kernel_execute:pallas": FaultSpec(fail=1)})):
        y1 = execute(p, x, impl="nb_pr", interpret=True)
    # breaker now open; long cooldown → the primary is skipped outright
    y2 = execute(p, x, impl="nb_pr", interpret=True)
    assert np.array_equal(np.asarray(y1), want)
    assert np.array_equal(np.asarray(y2), want)
    assert G.HEALTH.counter("breaker_skip:pallas:nb_pr") == 1
    assert G.HEALTH.snapshot()["breakers"]["pallas:nb_pr"]["state"] == "open"


def test_ladder_bottom_reraises():
    csr, x = _mat(seed=8)
    p = plan(csr, backend="xla")
    with inject_faults(FaultInjector(
            {"kernel_execute:xla": FaultSpec(fail=1)})):
        with pytest.raises(InjectedFault):
            execute(p, x, impl="nb_pr")
    # usage errors are never swallowed by the ladder
    p2 = plan(csr, backend="pallas")
    with pytest.raises(ValueError, match="vals stream"):
        execute(p2, x, vals=jnp.zeros(3), impl="nb_pr", interpret=True)


def test_sharded_demotes_inner_backend():
    csr, x = _mat(seed=9)
    mesh = make_local_mesh(jax.device_count(), 1)
    p = plan(csr, mesh=mesh, inner_backend="pallas")
    ref = plan(csr, mesh=mesh, inner_backend="xla")
    want = np.asarray(execute(ref, x, impl="nb_pr"))
    with inject_faults(FaultInjector(
            {"kernel_execute:sharded": FaultSpec(fail=1)})):
        y = execute(p, x, impl="nb_pr", interpret=True)
    assert np.array_equal(np.asarray(y), want)
    assert G.HEALTH.counter(
        "kernel_reroute:sharded->sharded/xla-inner:nb_pr") == 1


def test_plan_build_and_substrate_prep_fault_sites():
    csr, _ = _mat(seed=10)
    p = plan(csr, backend="xla")
    with inject_faults(FaultInjector({"plan_build": FaultSpec(fail=1)})):
        with pytest.raises(InjectedFault):
            p.substrate("balanced")
    p.substrate("balanced")                   # injector gone: builds fine
    p2 = plan(csr, backend="xla")
    entry = p2.entry("nb_pr", "xla")
    p2.substrate(entry.substrate)
    with inject_faults(FaultInjector({"substrate_prep": FaultSpec(fail=1)})):
        with pytest.raises(InjectedFault):
            p2.kernel_opts(entry)
    p2.kernel_opts(entry)


def test_serve_faults_shim_reexports():
    import repro.runtime.faults as rf
    import repro.serve.faults as sf
    assert sf.FaultInjector is rf.FaultInjector
    assert sf.FaultSpec is rf.FaultSpec
    assert sf.InjectedFault is rf.InjectedFault


# ---------------------------------------------------------------------------
# pillar 2: numeric sentinels
# ---------------------------------------------------------------------------

def _nan_kernel(bal, x, *extra, interpret=None, **opts):
    tail = x.shape[1:] if x.ndim > 1 else ()
    dt = x.dtype if jnp.issubdtype(x.dtype, jnp.inexact) else jnp.float32
    return jnp.full((int(bal.shape[0]),) + tuple(tail), jnp.nan, dt)


@contextlib.contextmanager
def _poisoned_backend(backend):
    """Temporarily replace the (nb_pr, backend) kernel with a NaN producer."""
    orig = registry.resolve("nb_pr", backend)
    registry.register("nb_pr", backend, "balanced", _nan_kernel)
    try:
        yield
    finally:
        registry._REGISTRY[("nb_pr", backend)] = orig


def test_sentinel_raise_and_sanitize():
    csr, x = _mat(seed=11)
    with _poisoned_backend("xla"):
        p = plan(csr, backend="xla")
        with pytest.raises(G.NumericFault, match="execute:nb_pr"):
            execute(p, x, impl="nb_pr", sentinel="raise")
        y = np.asarray(execute(p, x, impl="nb_pr", sentinel="sanitize"))
        assert np.all(y == 0.0)               # poisoned lanes zeroed
        y2 = np.asarray(execute(p, x, impl="nb_pr"))
        assert not np.any(np.isfinite(y2))    # opt-in: off by default
        with pytest.raises(ValueError, match="sentinel policy"):
            execute(p, x, impl="nb_pr", sentinel="bogus")
    assert G.HEALTH.counter("sentinel:execute:nb_pr") == 2


def test_sentinel_plan_default_and_scope():
    csr, x = _mat(seed=12)
    with _poisoned_backend("xla"):
        p = plan(csr, backend="xla", sentinel="sanitize")
        assert np.all(np.isfinite(np.asarray(execute(p, x, impl="nb_pr"))))
        p2 = plan(csr, backend="xla")
        with api.sentinel_scope("sanitize"):
            assert np.all(np.isfinite(
                np.asarray(execute(p2, x, impl="nb_pr"))))
        # explicit argument wins over the scope
        with api.sentinel_scope("sanitize"):
            with pytest.raises(G.NumericFault):
                execute(p2, x, impl="nb_pr", sentinel="raise")


def test_sentinel_traced_sanitize():
    csr, x = _mat(seed=13)
    with _poisoned_backend("xla"):
        p = plan(csr, backend="xla")
        y = jax.jit(lambda xx: execute(p, xx, impl="nb_pr",
                                       sentinel="sanitize"))(x)
        assert np.all(np.asarray(y) == 0.0)
    # no counters under trace: tracing stays side-effect-free
    assert G.HEALTH.counter("sentinel:execute:nb_pr") == 0


def test_sentinel_fallback_reexecutes_demoted():
    csr, x = _mat(seed=14)
    with _poisoned_backend("pallas"):
        p = plan(csr, backend="pallas")
        ref = plan(csr, backend="xla")
        want = np.asarray(execute(ref, x, impl="nb_pr"))
        y = np.asarray(execute(p, x, impl="nb_pr", sentinel="fallback"))
        assert np.array_equal(y, want)
    assert G.HEALTH.counter("sentinel_fallback:execute:nb_pr") == 1


def test_grad_scope_sanitizes_cotangents():
    csr, x = _mat(seed=15)
    p = plan(csr, backend="xla")
    y, vjp_fn = jax.vjp(lambda xx: execute(p, xx, impl="nb_pr"), x)
    ct = jnp.full_like(y, jnp.nan)
    (dx_plain,) = vjp_fn(ct)
    assert not np.all(np.isfinite(np.asarray(dx_plain)))
    with G.grad_scope("sanitize"):
        y2, vjp2 = jax.vjp(lambda xx: execute(p, xx, impl="nb_pr"), x)
        (dx,) = vjp2(ct)
    assert np.all(np.isfinite(np.asarray(dx)))
    with pytest.raises(ValueError, match="skip-and-report"):
        with G.grad_scope("raise"):
            pass


def test_train_step_skips_nonfinite():
    from repro.train.step import TrainConfig, init_state, make_train_step

    def loss_fn(params, batch):
        poison = jnp.where(batch["bad"] > 0, jnp.nan, 0.0)
        return jnp.sum(params["w"] * batch["x"]) + poison, {}

    tcfg = TrainConfig(skip_nonfinite=True)
    state = init_state({"w": jnp.ones((4,))}, tcfg)
    step = jax.jit(make_train_step(loss_fn, tcfg))
    good = {"x": jnp.arange(4.0), "bad": jnp.array(0)}
    bad = {"x": jnp.arange(4.0), "bad": jnp.array(1)}
    s1, m1 = step(state, good)
    assert int(m1["skipped_nonfinite"]) == 0
    s2, m2 = step(s1, bad)
    assert int(m2["skipped_nonfinite"]) == 1
    # the poisoned step kept params AND optimizer state bit-identical
    for tree1, tree2 in ((s1["params"], s2["params"]), (s1["opt"], s2["opt"])):
        for a, b in zip(jax.tree_util.tree_leaves(tree1),
                        jax.tree_util.tree_leaves(tree2)):
            assert np.array_equal(np.asarray(a), np.asarray(b))
    s3, m3 = step(s2, good)
    assert int(m3["skipped_nonfinite"]) == 0
    assert not np.array_equal(np.asarray(s3["params"]["w"]),
                              np.asarray(s2["params"]["w"]))


# ---------------------------------------------------------------------------
# named demotion counters (previously-silent warnings)
# ---------------------------------------------------------------------------

def test_quant_range_demotion_and_sentinel_raise():
    dense = np.full((8, 16), 1e-3, np.float32)
    dense[0, 0] = 1e6          # one tile, dynamic range ~1e9 >> bound
    csr = csr_from_dense(dense)
    with pytest.warns(UserWarning, match="dynamic range"):
        p = plan(csr, backend="xla", quant="int8")
        p.substrate("balanced")
    assert p.quant is None     # demoted to the unquantized substrate
    assert G.HEALTH.counter("quant_range_violations") == 1
    assert G.HEALTH.counter("demote:quant_range") == 1
    p2 = plan(csr, backend="xla", quant="int8", sentinel="raise")
    with warnings.catch_warnings():
        warnings.simplefilter("ignore")
        with pytest.raises(G.NumericFault, match="quant"):
            p2.substrate("balanced")


def test_fuse_crossover_counters():
    rng = np.random.default_rng(17)
    csr, _ = random_csr(rng, 12, 10, 0.4)
    th = dataclasses.replace(default_thresholds(),
                             chain_fuse_min_n=10**6,
                             attn_fuse_min_seq=10**6)
    p = plan(csr, backend="pallas", thresholds=th)
    a = jnp.asarray(rng.standard_normal((12, 6)).astype(np.float32))
    b = jnp.asarray(rng.standard_normal((10, 6)).astype(np.float32))
    x = jnp.asarray(rng.standard_normal((10, 4)).astype(np.float32))
    execute_chain(p, a, b, x, transform="softmax")
    assert G.HEALTH.counter("demote:chain_fuse") == 1
    execute_attention(p, a, b, x)
    assert G.HEALTH.counter("demote:attn_fuse") == 1


def test_sharded_attention_bias_names_alternatives():
    csr, _ = _mat(seed=18, m=16, k=12)
    mesh = make_local_mesh(jax.device_count(), 1)
    p = plan(csr, mesh=mesh)
    rng = np.random.default_rng(0)
    q = jnp.asarray(rng.standard_normal((16, 4)).astype(np.float32))
    k = jnp.asarray(rng.standard_normal((12, 4)).astype(np.float32))
    v = jnp.asarray(rng.standard_normal((12, 3)).astype(np.float32))
    with pytest.raises(NotImplementedError) as ei:
        execute_attention(p, q, k, v, bias=jnp.zeros((csr.nnz,)))
    msg = str(ei.value)
    assert "supported alternatives" in msg
    assert "backend='pallas'" in msg
    assert "drop bias=" in msg


# ---------------------------------------------------------------------------
# pillar 4: plan integrity digests
# ---------------------------------------------------------------------------

def test_plan_digest_stability_and_sensitivity():
    csr, _ = _mat(seed=19)
    other, _ = _mat(seed=20)
    p1 = plan(csr, backend="xla")
    p2 = plan(csr, backend="xla")
    assert G.plan_digest(p1) == G.plan_digest(p2)
    assert G.plan_digest(p1) != G.plan_digest(plan(other, backend="xla"))
    assert G.plan_digest(p1) != G.plan_digest(plan(csr, backend="pallas"))
    # lazily-built substrates mutate the builder but not its identity
    d = G.plan_digest(p1)
    p1.substrate("balanced")
    assert G.plan_digest(p1) == d


def test_cache_integrity_hit_rebuilds_corrupted():
    csr, _ = _mat(seed=21)
    other, _ = _mat(seed=22)
    cache = PlanCache(4, integrity="hit")
    builds = []

    def build():
        builds.append(1)
        return plan(csr, backend="xla")

    key = ("k",)
    v1 = cache.get_or_build(key, build)
    assert cache.get(key) is v1 and len(builds) == 1
    # corrupt in place: different plan under the stale digest
    corrupt = plan(other, backend="xla")
    with cache._lock:
        _, dig = cache._entries[key]
        cache._entries[key] = (corrupt, dig)
    v2 = cache.get_or_build(key, build)   # rebuilt, never executed
    assert v2 is not corrupt and len(builds) == 2
    assert cache.stats()["digest_mismatches"] == 1
    with cache._lock:
        _, dig = cache._entries[key]
        cache._entries[key] = (corrupt, dig)
    assert cache.get(key, None) is None   # dropped on the corrupted hit
    assert cache.stats()["digest_mismatches"] == 2


def test_put_built_replaces_corrupted_entry():
    csr, _ = _mat(seed=23)
    other, _ = _mat(seed=24)
    cache = PlanCache(4)                  # integrity="publish" default
    key = ("k",)
    first = plan(csr, backend="xla")
    fresh = plan(csr, backend="xla")
    cache.put_built(key, first)
    cache.put_built(key, fresh)           # healthy duplicate keeps first
    assert cache.get(key) is first
    assert cache.stats()["digest_mismatches"] == 0
    with cache._lock:
        _, dig = cache._entries[key]
        cache._entries[key] = (plan(other, backend="xla"), dig)
    cache.put_built(key, fresh)           # corrupted copy is replaced
    assert cache.get(key) is fresh
    assert cache.stats()["digest_mismatches"] == 1


def test_cache_integrity_off_skips_digests():
    csr, _ = _mat(seed=25)
    cache = PlanCache(4, integrity="off")
    cache.put(("k",), plan(csr, backend="xla"))
    with cache._lock:
        assert cache._entries[("k",)][1] is None
    with pytest.raises(ValueError, match="integrity"):
        PlanCache(4, integrity="paranoid")


# ---------------------------------------------------------------------------
# observability surface
# ---------------------------------------------------------------------------

def test_health_api_surface():
    G.HEALTH.bump("pattern_issues")
    G.HEALTH.breaker("pallas", "nb_pr")
    h = api.health()
    assert h["counters"]["pattern_issues"] == 1
    assert h["breakers"]["pallas:nb_pr"]["state"] == "closed"
    api.configure_guardrails(threshold=1, cooldown_s=0.0)
    assert G.HEALTH.breaker("pallas", "nb_pr").threshold == 1
    api.reset_health()
    assert api.health() == {
        "counters": {}, "breakers": {}, "spans": {}, "work": {},
        "compile": {"count": 0, "seconds": 0.0, "cache_hits": 0,
                    "cache_misses": 0, "retrieval_s": 0.0,
                    "by_function": {}}}


def test_health_summary_shape():
    from repro.serve import health_summary
    br = G.HEALTH.breaker("pallas", "rs_sr")
    hs = health_summary(G.HEALTH.snapshot())
    assert hs["breaker_trips"] == 0 and hs["open_breakers"] == []
    G.HEALTH.configure(threshold=1, cooldown_s=3600.0)
    br.record_failure()
    hs = health_summary(G.HEALTH.snapshot())
    assert hs["breaker_trips"] == 1
    assert hs["open_breakers"] == ["pallas:rs_sr"]


def test_circuit_breaker_state_machine():
    t = [0.0]
    br = G.CircuitBreaker(threshold=2, cooldown_s=10.0, clock=lambda: t[0])
    assert br.allow()
    br.record_failure()
    assert br.state == "closed" and br.allow()
    br.record_failure()                       # second consecutive: trip
    assert br.state == "open" and br.trips == 1
    assert not br.allow()                     # cooldown not elapsed
    t[0] = 11.0
    assert br.allow() and br.state == "half_open"
    br.record_failure()                       # probe fails: re-open
    assert br.state == "open" and br.trips == 2
    t[0] = 22.0
    assert br.allow()
    br.record_success()                       # probe succeeds: recover
    assert br.state == "closed" and br.recoveries == 1 and br.failures == 0
