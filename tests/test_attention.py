"""Block-sparse attention subsystem (DESIGN.md §10).

Pattern builders (property-tested against closed forms and CSR invariants),
the fused sparse-softmax attention chain vs a dense masked reference —
outputs AND grads, with and without the additive bias stream — the
``attn_fuse_min_seq`` gate, thresholds v5 persistence, plan-cache sharing
across layers, the sharded path, and the transformer/serving integration.
"""
import dataclasses
import json

import numpy as np
import pytest
import jax
import jax.numpy as jnp

from repro.api import (AttentionSpec, PlanCache, SparseAttention, bigbird,
                       build_mask, dense_attention, from_block_mask,
                       scoped_plan_cache, sliding_window, sparse_attention)
from repro.attention.patterns import expected_band_blocks
from repro.core import SelectorThresholds

from _hypothesis_compat import given, settings, st

BACKENDS = ("xla", "pallas")


def _dense_ref(mask_bool, q, k, v, scale=None, bias_flat=None):
    """Dense masked-softmax attention; fully-masked rows → exact zeros."""
    q, k, v = np.asarray(q), np.asarray(k), np.asarray(v)
    sc = q.shape[-1] ** -0.5 if scale is None else scale
    z = sc * (q @ k.T)
    if bias_flat is not None:
        b = np.zeros_like(z)
        b[mask_bool.nonzero()] = np.asarray(bias_flat)
        z = z + b
    zm = np.where(mask_bool, z, -np.inf)
    rmax = np.max(zm, axis=1, keepdims=True)
    rmax = np.where(np.isfinite(rmax), rmax, 0.0)
    e = np.where(mask_bool, np.exp(zm - rmax), 0.0)
    w = e / np.maximum(e.sum(axis=1, keepdims=True), 1e-30)
    return w @ v


def _mask_bool(spec):
    csr = build_mask(spec).csr
    m = np.zeros(csr.shape, dtype=bool)
    for i in range(csr.shape[0]):
        m[i, csr.indices[csr.indptr[i]:csr.indptr[i + 1]]] = True
    return m


def _qkv(rng, seq, d, scale=0.3):
    q = jnp.asarray(rng.standard_normal((seq, d)).astype(np.float32) * scale)
    k = jnp.asarray(rng.standard_normal((seq, d)).astype(np.float32) * scale)
    v = jnp.asarray(rng.standard_normal((seq, d)).astype(np.float32))
    return q, k, v


# ---------------------------------------------------------------------------
# pattern builders: closed forms + CSR invariants (property tests)
# ---------------------------------------------------------------------------

@settings(max_examples=25, deadline=None)
@given(nb=st.integers(1, 9), window=st.integers(0, 10),
       block=st.sampled_from((4, 8)),
       causal=st.sampled_from((False, True)))
def test_band_block_count_closed_form(nb, window, block, causal):
    spec = sliding_window(nb * block, window, block=block, causal=causal)
    mask = build_mask(spec)
    assert mask.nnz_blocks == expected_band_blocks(nb, window, causal=causal)
    assert mask.stats["nnz_blocks"] == mask.nnz_blocks
    assert mask.block_mask.shape == (nb, nb)


@settings(max_examples=25, deadline=None)
@given(seq=st.integers(3, 40), window=st.integers(0, 3),
       block=st.sampled_from((4, 8)),
       causal=st.sampled_from((False, True)),
       n_global=st.integers(0, 2), n_random=st.integers(0, 2))
def test_token_csr_invariants(seq, window, block, causal, n_global, n_random):
    """Every builder's CSR: sorted unique in-range columns, token-level
    causality, and every edge covered by an active block."""
    spec = bigbird(seq, window, n_global, n_random, block=block,
                   causal=causal)
    mask = build_mask(spec)
    csr, bm = mask.csr, mask.block_mask
    assert csr.shape == (seq, seq)
    for i in range(seq):
        cols = csr.indices[csr.indptr[i]:csr.indptr[i + 1]]
        assert (np.diff(cols) > 0).all()          # sorted, unique
        assert (cols < seq).all() and (cols >= 0).all()
        if causal:
            assert (cols <= i).all()
        assert bm[i // block, cols // block].all()  # block cover
    # causal block masks keep nothing above the block diagonal
    if causal:
        assert not np.triu(bm, 1).any()


def test_bigbird_deterministic_and_superset():
    spec = bigbird(96, 1, n_global=1, n_random=2, block=16, seed=3)
    m1, m2 = build_mask(spec), build_mask(spec)
    np.testing.assert_array_equal(m1.block_mask, m2.block_mask)
    band = build_mask(sliding_window(96, 1, block=16)).block_mask
    assert (m1.block_mask | band).sum() == m1.nnz_blocks  # band ⊆ bigbird
    assert m1.block_mask[0, :].all() and m1.block_mask[:, 0].all()  # global


def test_spec_validation_and_hashability():
    with pytest.raises(ValueError):
        AttentionSpec("poisson", 64)
    with pytest.raises(ValueError):
        sliding_window(0, 1)
    with pytest.raises(ValueError):
        AttentionSpec("sliding_window", 64, window=-1)
    with pytest.raises(ValueError):
        from_block_mask(np.ones((2, 2), bool), 64, block=8)  # wants (8, 8)
    s1 = sliding_window(64, 2, block=8, causal=True)
    assert s1 == sliding_window(64, 2, block=8, causal=True)
    assert len({s1, dense_attention(64, block=8)}) == 2  # hashable


# ---------------------------------------------------------------------------
# fused chain vs dense reference: outputs and grads
# ---------------------------------------------------------------------------

SPECS = (
    ("window", lambda seq, b: sliding_window(seq, 1, block=b)),
    ("window_causal", lambda seq, b: sliding_window(seq, 2, block=b,
                                                    causal=True)),
    ("bigbird", lambda seq, b: bigbird(seq, 1, 1, 1, block=b, seed=0)),
)


@pytest.mark.parametrize("backend", BACKENDS)
@pytest.mark.parametrize("name,make", SPECS)
@pytest.mark.parametrize("seq,block", ((24, 8), (64, 8)))
def test_attention_matches_dense(rng, backend, name, make, seq, block):
    spec = make(seq, block)
    q, k, v = _qkv(rng, seq, 16)
    y = sparse_attention(spec, q, k, v, backend=backend, cache=False)
    ref = _dense_ref(_mask_bool(spec), q, k, v)
    np.testing.assert_allclose(np.asarray(y), ref, atol=5e-5)


@pytest.mark.parametrize("d", (64, 128))
def test_attention_paper_head_dims(rng, d):
    """The serving head widths: fused pallas == unfused xla == dense ref."""
    spec = sliding_window(32, 1, block=8, causal=True)
    q, k, v = _qkv(rng, 32, d, scale=0.1)
    ref = _dense_ref(_mask_bool(spec), q, k, v)
    for backend in BACKENDS:
        y = sparse_attention(spec, q, k, v, backend=backend, cache=False)
        np.testing.assert_allclose(np.asarray(y), ref, atol=5e-5)


@pytest.mark.parametrize("backend", BACKENDS)
def test_attention_grads_match_dense(rng, backend):
    spec = sliding_window(40, 1, block=8, causal=True)
    mj = jnp.asarray(_mask_bool(spec))
    q, k, v = _qkv(rng, 40, 16)
    sc = 16 ** -0.5

    def f(qq, kk, vv):
        return jnp.sum(jnp.sin(sparse_attention(spec, qq, kk, vv,
                                                backend=backend,
                                                cache=False)))

    def f_dense(qq, kk, vv):
        z = jnp.where(mj, sc * (qq @ kk.T), -1e30)
        w = jnp.where(mj, jax.nn.softmax(z, axis=1), 0.0)
        return jnp.sum(jnp.sin(w @ vv))

    g = jax.grad(f, argnums=(0, 1, 2))(q, k, v)
    r = jax.grad(f_dense, argnums=(0, 1, 2))(q, k, v)
    for gi, ri in zip(g, r):
        np.testing.assert_allclose(np.asarray(gi), np.asarray(ri), atol=5e-4)


@pytest.mark.parametrize("backend", BACKENDS)
def test_attention_projection_grads(rng, backend):
    """Grads flow through Q/K/V *projections* (the transformer use): d/dW of
    attention(X@Wq, X@Wk, X@Wv) matches the dense reference."""
    spec = sliding_window(24, 1, block=8)
    mj = jnp.asarray(_mask_bool(spec))
    d = 8
    x = jnp.asarray(rng.standard_normal((24, d)).astype(np.float32) * 0.3)
    ws = [jnp.asarray(rng.standard_normal((d, d)).astype(np.float32) * 0.3)
          for _ in range(3)]
    sc = d ** -0.5

    def f(wq, wk, wv, xx):
        return jnp.sum(jnp.cos(sparse_attention(
            spec, xx @ wq, xx @ wk, xx @ wv, backend=backend, cache=False)))

    def f_dense(wq, wk, wv, xx):
        z = jnp.where(mj, sc * ((xx @ wq) @ (xx @ wk).T), -1e30)
        w = jnp.where(mj, jax.nn.softmax(z, axis=1), 0.0)
        return jnp.sum(jnp.cos(w @ (xx @ wv)))

    g = jax.grad(f, argnums=(0, 1, 2, 3))(*ws, x)
    r = jax.grad(f_dense, argnums=(0, 1, 2, 3))(*ws, x)
    for gi, ri in zip(g, r):
        np.testing.assert_allclose(np.asarray(gi), np.asarray(ri), atol=5e-4)


@pytest.mark.parametrize("backend", BACKENDS)
def test_attention_bias_outputs_and_grads(rng, backend):
    """The additive per-edge bias hook (ALiBi/relative-position style):
    outputs and the bias gradient itself against the dense reference."""
    spec = sliding_window(32, 1, block=8, causal=True)
    mb = _mask_bool(spec)
    nnz = build_mask(spec).csr.nnz
    q, k, v = _qkv(rng, 32, 16)
    bias = jnp.asarray(rng.standard_normal(nnz).astype(np.float32) * 0.5)
    y = sparse_attention(spec, q, k, v, bias=bias, backend=backend,
                         cache=False)
    ref = _dense_ref(mb, q, k, v, bias_flat=bias)
    np.testing.assert_allclose(np.asarray(y), ref, atol=5e-5)

    mj = jnp.asarray(mb)
    sc = 16 ** -0.5

    def f(bb):
        return jnp.sum(jnp.sin(sparse_attention(spec, q, k, v, bias=bb,
                                                backend=backend,
                                                cache=False)))

    def f_dense(bb):
        z = sc * (q @ k.T) + jnp.zeros(mj.shape).at[mj.nonzero()].set(bb)
        w = jnp.where(mj, jax.nn.softmax(jnp.where(mj, z, -1e30), axis=1),
                      0.0)
        return jnp.sum(jnp.sin(w @ v))

    gb = jax.grad(f)(bias)
    rb = jax.grad(f_dense)(bias)
    np.testing.assert_allclose(np.asarray(gb), np.asarray(rb), atol=5e-4)


@pytest.mark.parametrize("backend", BACKENDS)
def test_fully_masked_rows_exact_zero(rng, backend):
    """Block rows the mask leaves empty produce *exact* zeros (not NaN, not
    softmax-of-nothing garbage) — the contract long-context packing relies
    on for padded tail rows."""
    nb, block = 4, 8
    bm = np.tril(np.ones((nb, nb), bool))
    bm[2, :] = False                       # tokens 16..23 attend to nothing
    spec = from_block_mask(bm, nb * block, block=block, causal=True)
    q, k, v = _qkv(rng, nb * block, 16)
    y = np.asarray(sparse_attention(spec, q, k, v, backend=backend,
                                    cache=False))
    assert np.isfinite(y).all()
    np.testing.assert_array_equal(y[16:24], 0.0)
    ref = _dense_ref(_mask_bool(spec), q, k, v)
    np.testing.assert_allclose(y, ref, atol=5e-5)


def test_attention_batched_leading_dims(rng):
    """(B, H, S, d) operands: every leading slice through one shared plan."""
    spec = sliding_window(24, 1, block=8)
    q = jnp.asarray(rng.standard_normal((2, 3, 24, 8)).astype(np.float32)
                    * 0.3)
    y = sparse_attention(spec, q, q, q, backend="xla", cache=False)
    assert y.shape == q.shape
    ref0 = _dense_ref(_mask_bool(spec), q[1, 2], q[1, 2], q[1, 2])
    np.testing.assert_allclose(np.asarray(y[1, 2]), ref0, atol=5e-5)


def test_attention_validation(rng):
    spec = sliding_window(24, 1, block=8)
    q, k, v = _qkv(rng, 24, 8)
    with pytest.raises(ValueError):
        sparse_attention(spec, q[:16], k[:16], v[:16], cache=False)  # seq
    with pytest.raises(ValueError):
        sparse_attention(spec, q, k[:12], v, cache=False)  # shape mismatch
    with pytest.raises(ValueError):
        sparse_attention(spec, q, k, v, bias=jnp.ones(3), cache=False)


# ---------------------------------------------------------------------------
# the fuse gate, autotuner, and traffic model
# ---------------------------------------------------------------------------

def test_attn_fuse_gate(rng):
    """attn_fuse_min_seq shut → the pallas plan executes attention through
    the unfused XLA pair (visible in the bound-kernel cache); open → fused."""
    from repro.core.plan import execute_attention, plan
    from repro.kernels.tune import ATTN_NEVER
    spec = sliding_window(32, 1, block=8)
    csr = build_mask(spec).csr
    q, k, v = _qkv(rng, 32, 8)
    ref = _dense_ref(_mask_bool(spec), q, k, v)

    th = dataclasses.replace(SelectorThresholds(), attn_fuse_min_seq=ATTN_NEVER)
    p = plan(csr, backend="pallas", thresholds=th, chain_op="attn")
    y = execute_attention(p, q, k, v)
    np.testing.assert_allclose(np.asarray(y), ref, atol=5e-5)
    assert {kk[1] for kk in p._bound if kk[0] == "chain"} == {"xla"}

    p2 = plan(csr, backend="pallas", chain_op="attn")
    y2 = execute_attention(p2, q, k, v)
    np.testing.assert_allclose(np.asarray(y2), ref, atol=5e-5)
    assert {kk[1] for kk in p2._bound if kk[0] == "chain"} == {"pallas"}


def test_thresholds_v5_roundtrip_and_compat():
    th = dataclasses.replace(SelectorThresholds(), attn_fuse_min_seq=256)
    s = th.to_json()
    assert json.loads(s)["version"] == 5
    assert SelectorThresholds.from_json(s).attn_fuse_min_seq == 256
    # pre-attention calibrations (v1–v4) load with the always-fuse default
    for older in (SelectorThresholds(),                                  # v1
                  dataclasses.replace(SelectorThresholds(),
                                      overlap_min_n=64),                 # v2
                  dataclasses.replace(SelectorThresholds(), quant_min_n=8),  # v3
                  dataclasses.replace(SelectorThresholds(),
                                      chain_fuse_min_n=64)):             # v4
        text = older.to_json()
        assert json.loads(text)["version"] < 5
        back = SelectorThresholds.from_json(text)
        assert back.attn_fuse_min_seq == 1
        assert back.chain_fuse_min_n == older.chain_fuse_min_n
    with pytest.raises(ValueError):
        dataclasses.replace(SelectorThresholds(),
                            attn_fuse_min_seq=0).validate()


def test_autotune_attention_sets_threshold():
    from repro.api import autotune_attention
    specs = (sliding_window(16, 1, block=8), sliding_window(32, 1, block=8))
    th = autotune_attention(specs, d=8, repeats=1)
    assert isinstance(th.attn_fuse_min_seq, int)
    assert th.attn_fuse_min_seq >= 1


def test_modeled_traffic_attention_score_bytes():
    """The acceptance metric: the fused chain moves 0 HBM score bytes; the
    unfused pair pays the full 2·nnz_blocks·bs²·dtype round-trip."""
    from repro.kernels.tune import modeled_traffic_attention
    spec = sliding_window(256, 1, block=64, causal=True)
    mask = build_mask(spec)
    t = modeled_traffic_attention(mask, 64)
    assert t["fused_score_bytes"] == 0
    assert t["unfused_score_bytes"] == 2 * mask.nnz_blocks * 64 * 64 * 4
    assert t["nnz_blocks"] == expected_band_blocks(4, 1, causal=True)
    assert t["bytes_reduction"] > 1.0
    assert t["fused_edge_value_bytes"] == 0


# ---------------------------------------------------------------------------
# plan sharing: layers, scoped caches, serving
# ---------------------------------------------------------------------------

def test_plan_reuse_across_layers(rng):
    """Two layers, one spec, one PlanCache → exactly one build, the rest
    hits (the ISSUE's cross-layer mask-sharing contract)."""
    spec = sliding_window(32, 1, block=8, causal=True)
    pc = PlanCache(8)
    layers = [SparseAttention(spec, cache=pc) for _ in range(2)]
    q, k, v = _qkv(rng, 32, 8)
    y0 = layers[0](q, k, v)
    y1 = layers[1](q, k, v)
    np.testing.assert_allclose(np.asarray(y0), np.asarray(y1), atol=1e-6)
    s = pc.stats()
    assert s["builds"] == 1
    assert s["hits"] >= 1
    assert layers[0].plan is layers[1].plan
    assert "seq=32" in repr(layers[0])


def test_scoped_plan_cache(rng):
    spec = sliding_window(24, 1, block=8)
    pc = PlanCache(4)
    q, k, v = _qkv(rng, 24, 8)
    with scoped_plan_cache(pc):
        sparse_attention(spec, q, k, v)
        sparse_attention(spec, q, k, v)
    s = pc.stats()
    assert s["builds"] == 1 and s["hits"] == 1


def test_plan_cache_segments_attention_from_chain():
    """An attention plan and a chain plan over the same CSR topology are
    distinct cache entries (chain_op keying)."""
    from repro.core.cache import cached_plan
    csr = build_mask(sliding_window(24, 1, block=8)).csr
    pc = PlanCache(8)
    pa = cached_plan(csr, cache=pc, backend="xla", chain_op="attn")
    ps = cached_plan(csr, cache=pc, backend="xla", chain_op="softmax")
    assert pa is not ps
    assert pc.stats()["builds"] == 2


# ---------------------------------------------------------------------------
# sharded: the cross-shard softmax merge carries attention for free
# ---------------------------------------------------------------------------

needs_devices = pytest.mark.skipif(jax.device_count() < 2,
                                   reason="needs >= 2 devices")


@needs_devices
def test_sharded_attention_parity(rng):
    from jax.sharding import Mesh
    mesh = Mesh(np.array(jax.devices()), ("shard",))
    spec = sliding_window(64, 2, block=8, causal=True)
    q, k, v = _qkv(rng, 64, 16)
    y = sparse_attention(spec, q, k, v, mesh=mesh, cache=False)
    ref = _dense_ref(_mask_bool(spec), q, k, v)
    np.testing.assert_allclose(np.asarray(y), ref, atol=5e-5)


@needs_devices
def test_sharded_attention_grads(rng):
    from jax.sharding import Mesh
    mesh = Mesh(np.array(jax.devices()), ("shard",))
    spec = sliding_window(48, 1, block=8)
    q, k, v = _qkv(rng, 48, 8)

    def f(backend_kw):
        def g(qq, kk, vv):
            return jnp.sum(jnp.sin(sparse_attention(spec, qq, kk, vv,
                                                    cache=False,
                                                    **backend_kw)))
        return g

    gs = jax.grad(f({"mesh": mesh}), argnums=(0, 1, 2))(q, k, v)
    gr = jax.grad(f({"backend": "xla"}), argnums=(0, 1, 2))(q, k, v)
    for gi, ri in zip(gs, gr):
        np.testing.assert_allclose(np.asarray(gi), np.asarray(ri), atol=5e-4)


@needs_devices
def test_sharded_attention_bias_raises(rng):
    from jax.sharding import Mesh
    mesh = Mesh(np.array(jax.devices()), ("shard",))
    spec = sliding_window(48, 1, block=8)
    q, k, v = _qkv(rng, 48, 8)
    nnz = build_mask(spec).csr.nnz
    with pytest.raises(NotImplementedError):
        sparse_attention(spec, q, k, v, bias=jnp.zeros(nnz), mesh=mesh,
                         cache=False)


# ---------------------------------------------------------------------------
# model + serving integration
# ---------------------------------------------------------------------------

def test_model_block_sparse_dense_fallback_matches_full(rng, key):
    """A block_sparse config with no window (dense-fallback blocks) must be
    numerically identical to full attention — loss and grads."""
    from repro.configs import get_smoke
    from repro.models import Model
    base = get_smoke("llama3.2-1b").scaled(num_layers=1, remat="none")
    cfg_bs = base.scaled(attn_pattern="block_sparse", attn_block=8)
    m_full, m_bs = Model(base), Model(cfg_bs)
    params = m_full.init(key)
    toks = jnp.asarray(rng.integers(0, base.vocab_size, (2, 16)), jnp.int32)
    batch = {"tokens": toks, "labels": toks}
    (l_full, _), g_full = jax.value_and_grad(
        m_full.loss_fn, has_aux=True)(params, batch)
    (l_bs, _), g_bs = jax.value_and_grad(
        m_bs.loss_fn, has_aux=True)(params, batch)
    np.testing.assert_allclose(float(l_full), float(l_bs), atol=1e-5)
    for gf, gb in zip(jax.tree_util.tree_leaves(g_full),
                      jax.tree_util.tree_leaves(g_bs)):
        np.testing.assert_allclose(np.asarray(gf), np.asarray(gb), atol=5e-5)


def test_serve_engine_long_context(rng):
    """ServeEngine with a block-sparse prefill: requests complete and the
    engine's PlanCache carries the attention plans (DESIGN.md §10)."""
    from repro.configs import get_smoke
    from repro.models import Model
    from repro.serve import Request, ServeEngine
    cfg = get_smoke("llama3.2-1b").scaled(
        attn_pattern="block_sparse", window=16, attn_block=8)
    model = Model(cfg)
    params = model.init(jax.random.PRNGKey(0))
    engine = ServeEngine(model, params, slots=2, max_len=48)
    for i in range(3):
        engine.submit(Request(rid=i, prompt=[1 + i, 5, 9, 2 + i] * 4,
                              max_new=4))
    done = engine.run_until_done()
    assert all(r.done for r in done)
    assert all(len(r.out) == 4 for r in done)
    s = engine.plan_cache.stats()
    assert s["builds"] >= 1          # the 16-token prefill mask
    # same-spec lookups beyond the build are hits, never rebuilds
    assert s["misses"] == s["builds"]
