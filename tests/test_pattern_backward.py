"""The kernel backward of the balanced family (``execute_pattern`` with a
``pattern_prep`` that carries the transpose): the value gradient on the
SDDMM of the forward's tiles, the input gradient on the fused SpMM over the
transposed pattern — against the flat ``_coo_bwd`` of the XLA backend, in
Pallas interpret mode."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro import api
from repro.core.plan import (TRANSPOSE, execute_pattern, pattern_prep,
                             transpose_pattern)


def _pattern(seed, m, k, density, tile, empty=(), full_last=False):
    """A row-ordered balanced pattern; ``empty`` rows hold nothing and
    ``full_last`` drops nonzeros until the last tile is full."""
    rng = np.random.default_rng(seed)
    dense = rng.random((m, k)) < density
    dense[list(empty)] = False
    r, c = np.nonzero(dense)
    if full_last:
        keep = len(r) // tile * tile
        r, c = r[:keep], c[:keep]
    n_tiles = max(1, -(-len(r) // tile))
    pad = n_tiles * tile - len(r)
    rows = np.concatenate([r, np.full(pad, m)]).astype(np.int32)
    cols = np.concatenate([c, np.zeros(pad)]).astype(np.int32)
    return rows.reshape(n_tiles, tile), cols.reshape(n_tiles, tile)


def _grads(rows, cols, shape, vals, x, w, backend, prep):
    def loss(v, x):
        y = execute_pattern(jnp.asarray(rows), jnp.asarray(cols), v, shape,
                            x, backend=backend, prep=prep)
        return jnp.sum(y * w)
    return jax.grad(loss, argnums=(0, 1))(vals, x)


def _close(got, want, tol=2e-5):
    for g, r in zip(got, want):
        g, r = np.asarray(g), np.asarray(r)
        assert g.shape == r.shape
        assert np.abs(g - r).max() <= tol * max(np.abs(r).max(), 1e-30)


CASES = {
    "empty_rows": dict(m=200, k=96, density=0.1, tile=128,
                       empty=range(5, 40)),
    "padded_last_tile": dict(m=150, k=70, density=0.15, tile=128),
    "full_last_tile": dict(m=128, k=64, density=0.2, tile=128,
                           full_last=True),
}


@pytest.mark.parametrize("n", [1, 128, 200])
@pytest.mark.parametrize("case", sorted(CASES))
def test_kernel_backward_matches_flat_backward(case, n):
    spec = CASES[case]
    m, k = spec["m"], spec["k"]
    rows, cols = _pattern(1, m, k, spec["density"], spec["tile"],
                          spec.get("empty", ()), spec.get("full_last", False))
    rng = np.random.default_rng(2)
    vals = jnp.asarray(rng.standard_normal(rows.size), jnp.float32)
    x = jnp.asarray(rng.standard_normal((k, n)), jnp.float32)
    w = jnp.asarray(rng.standard_normal((m, n)), jnp.float32)
    api.reset_health()
    got = _grads(rows, cols, (m, k), vals, x, w, "pallas",
                 pattern_prep(rows, cols, (m, k)))
    want = _grads(rows, cols, (m, k), vals, x, w, "xla", None)
    _close(got, want)
    counters = api.health()["counters"]
    assert not any(c.startswith("demote:") for c in counters), counters
    # sentinel (padding) entries get no value gradient
    assert np.all(np.asarray(got[0]).reshape(rows.shape)[rows >= m] == 0)


def test_stacked_patterns_scan_with_the_kernel_backward():
    """Per-layer patterns stacked on a leading axis, scanned: each layer's
    slice of the prep (schedules padded with no-op visits) is its own, and
    the gradients match the flat backward layer by layer."""
    _stacked_scan(n=40)


def test_stacked_patterns_scan_at_full_width():
    """The same at N = 1,024: the forward and the transposed SpMM gather
    each row once, 1,024 wide, and the SDDMM takes the 1,024 features in
    one block."""
    _stacked_scan(n=1024)


def _stacked_scan(n):
    m, k, tile, layers = 96, 64, 64, 3
    pats = [_pattern(10 + i, m, k, 0.1 + 0.05 * i, tile) for i in range(2)]
    n_tiles = max(p[0].shape[0] for p in pats)

    def pad(p):
        extra = n_tiles - p[0].shape[0]
        return (np.concatenate([p[0], np.full((extra, tile), m, np.int32)]),
                np.concatenate([p[1], np.zeros((extra, tile), np.int32)]))
    pats = [pad(p) for p in pats] + [pad(pats[0])]
    rows = np.stack([p[0] for p in pats])
    cols = np.stack([p[1] for p in pats])
    prep = pattern_prep(rows, cols, (m, k))
    arrays = {key: v for key, v in prep.items() if hasattr(v, "shape")}
    statics = {key: v for key, v in prep.items() if key not in arrays}
    assert prep["visit_tile"].shape[0] == layers
    assert prep[TRANSPOSE + "rows"].shape[0] == layers
    rng = np.random.default_rng(3)
    vals = jnp.asarray(rng.standard_normal((layers, n_tiles * tile)),
                       jnp.float32)
    x0 = jnp.asarray(rng.standard_normal((k, n)), jnp.float32)
    proj = jnp.asarray(rng.standard_normal((m, k)) / 8, jnp.float32)

    def loss(vals, x0, backend):
        def body(x, xs):
            r, c, v, arr = xs
            p = dict(arr, **statics) if backend == "pallas" else None
            y = execute_pattern(r, c, v, (m, k), x, backend=backend, prep=p)
            return jnp.tanh(proj.T @ y), None
        x, _ = jax.lax.scan(body, x0, (jnp.asarray(rows), jnp.asarray(cols),
                                       vals, arrays))
        return jnp.sum(x * x)

    api.reset_health()
    got = jax.grad(loss, argnums=(0, 1))(vals, x0, "pallas")
    counters = api.health()["counters"]
    assert "demote:traced_pattern_to_xla" not in counters
    assert "demote:coo_bwd" not in counters
    want = jax.grad(loss, argnums=(0, 1))(vals, x0, "xla")
    _close(got, want)


def test_transpose_pattern_is_the_transpose():
    m, k = 60, 45
    rows, cols = _pattern(4, m, k, 0.2, 32, empty=(3, 4))
    perm, rt, ct = transpose_pattern(rows, cols, (m, k))
    vals = np.random.default_rng(5).standard_normal(rows.size)
    valid = rows.reshape(-1) < m
    dense = np.zeros((m, k))
    dense[rows.reshape(-1)[valid], cols.reshape(-1)[valid]] = vals[valid]
    vt = vals[perm.reshape(-1)]
    tvalid = rt.reshape(-1) < k
    dense_t = np.zeros((k, m))
    dense_t[rt.reshape(-1)[tvalid], ct.reshape(-1)[tvalid]] = vt[tvalid]
    np.testing.assert_array_equal(dense_t, dense.T)
    assert tvalid.sum() == valid.sum()
    assert np.all(np.diff(rt.reshape(-1)) >= 0)        # row-ordered


def test_pallas_pattern_without_transpose_demotes_visibly():
    """A Pallas-backed pattern whose prep carries no transpose runs the
    flat backward and says so on ``demote:coo_bwd``; the XLA backend's
    backward is the flat one by design and bumps nothing."""
    m, k, n = 80, 48, 16
    rows, cols = _pattern(6, m, k, 0.2, 64)
    rng = np.random.default_rng(7)
    vals = jnp.asarray(rng.standard_normal(rows.size), jnp.float32)
    x = jnp.asarray(rng.standard_normal((k, n)), jnp.float32)
    w = jnp.asarray(rng.standard_normal((m, n)), jnp.float32)
    want = _grads(rows, cols, (m, k), vals, x, w, "xla", None)
    api.reset_health()
    assert "demote:coo_bwd" not in api.health()["counters"]
    prep = {key: v for key, v in pattern_prep(rows, cols, (m, k)).items()
            if not key.startswith(TRANSPOSE)}
    for p in (prep, None):
        _close(_grads(rows, cols, (m, k), vals, x, w, "pallas", p), want)
    assert api.health()["counters"]["demote:coo_bwd"] == 2


def _largest_intermediate(jaxpr) -> int:
    """Elements of the largest value any equation of ``jaxpr`` (or of the
    jaxprs it holds) produces."""
    big = 0
    for eqn in jaxpr.eqns:
        for v in eqn.outvars:
            big = max(big, int(np.prod(getattr(v.aval, "shape", ()))))
        for sub in jax.core.jaxprs_in_params(eqn.params):
            big = max(big, _largest_intermediate(sub))
    return big


def test_kernel_backward_holds_no_nnz_by_n_intermediate():
    m, k, n = 256, 128, 64
    rows, cols = _pattern(8, m, k, 0.25, 128)
    nnz = int(np.count_nonzero(rows < m))
    vals = jnp.zeros(rows.size, jnp.float32)
    x = jnp.zeros((k, n), jnp.float32)

    def grad(backend, prep):
        def loss(v, x):
            return jnp.sum(execute_pattern(jnp.asarray(rows),
                                           jnp.asarray(cols), v, (m, k), x,
                                           backend=backend, prep=prep))
        return jax.make_jaxpr(jax.grad(loss, argnums=(0, 1)))(vals, x).jaxpr

    kernel = _largest_intermediate(grad("pallas",
                                        pattern_prep(rows, cols, (m, k))))
    flat = _largest_intermediate(grad("xla", None))
    assert flat >= nnz * n               # the flat backward's (nnz, N)
    assert kernel < nnz * n


def test_backward_kernels_record_their_work():
    """The SDDMM and the transposed SpMM record work under keys of their
    own: the transposed pattern's topology is not the forward's."""
    m, k, n = 100, 60, 130
    rows, cols = _pattern(9, m, k, 0.2, 64)
    prep = pattern_prep(rows, cols, (m, k))
    rng = np.random.default_rng(10)
    vals = jnp.asarray(rng.standard_normal(rows.size), jnp.float32)
    x = jnp.asarray(rng.standard_normal((k, n)), jnp.float32)
    w = jnp.ones((m, n), jnp.float32)
    api.reset_health()
    _grads(rows, cols, (m, k), vals, x, w, "pallas", prep)
    work = api.health()["work"]
    by_kernel = {}
    for key, rec in work.items():
        assert key.endswith(f":n{n}")
        by_kernel.setdefault(rec["kernel"], []).append(rec)
    fused, (sddmm,) = by_kernel["repro_vsr_fused"], by_kernel["repro_sddmm"]
    assert len(fused) == 2
    assert fused[0]["topology"] != fused[1]["topology"]
    n_tiles, t = rows.shape
    # 130 columns fit one gather block at T = 64: each row is one copy
    for rec in fused:
        assert rec["column_blocks"] == 1
        assert rec["rows_gathered"] == rec["tile_loads"] * t
    assert sddmm["tile_loads"] == n_tiles
    assert sddmm["rows_gathered"] == 2 * t * n_tiles
