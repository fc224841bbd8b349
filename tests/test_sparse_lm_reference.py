"""A small Phi-3-shaped sparse-FFN model against the plain reference
(``bench/reference/lm.py``) on seeded weights: the loss and every gradient
of ``Model.loss_fn``, through the kernel path (Pallas, interpret mode here)
and the XLA path."""
import dataclasses
import sys
from pathlib import Path

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro import api
from repro.configs import get_smoke
from repro.models import Model, SparseFFNConfig

sys.path.insert(0, str(Path(__file__).resolve().parents[1]))

from bench.reference import lm as reference  # noqa: E402

SEQ, BATCH = 24, 2


@pytest.fixture(scope="module")
def setup():
    cfg = dataclasses.replace(
        get_smoke("phi3-mini-3.8b"),
        sparse_ffn=SparseFFNConfig(density=0.2, tile=64))
    model = Model(cfg)
    params = model.init(jax.random.PRNGKey(0))
    # every leaf off its initial value, the norms' gains included
    leaves, tree = jax.tree_util.tree_flatten(params)
    keys = jax.random.split(jax.random.PRNGKey(1), len(leaves))
    params = jax.tree_util.tree_unflatten(tree, [
        a + 0.05 * jax.random.normal(key, a.shape, a.dtype)
        for a, key in zip(leaves, keys)])
    rng = np.random.default_rng(2)
    tokens = rng.integers(0, cfg.vocab_size, (BATCH, SEQ + 1), np.int32)
    labels = tokens[:, 1:].copy()
    labels[0, :3] = -1                       # ignored positions
    batch = {"tokens": jnp.asarray(tokens[:, :-1]),
             "labels": jnp.asarray(labels)}
    pats = {n: (np.asarray(model.patterns[n].rows),
                np.asarray(model.patterns[n].cols), model.patterns[n].shape)
            for n in reference.FFN}
    spec = {"heads": cfg.num_heads, "head_dim": cfg.head_dim,
            "rope_theta": cfg.rope_theta, "rms_norm_eps": cfg.norm_eps}
    ref = reference.Reference(spec, pats)
    return model, params, batch, ref.gradients(params, batch)


def _program(model, params, batch, backend):
    consts = model.sparse_inputs()

    def loss(p):
        return model.loss_fn(p, batch, consts)[0]

    with api.use_backend(backend):
        value, grads = jax.jit(jax.value_and_grad(loss))(params)
        jaxpr = jax.make_jaxpr(jax.grad(loss))(params).jaxpr
    return value, grads, jaxpr


def _largest(jaxpr) -> int:
    big = 0
    for eqn in jaxpr.eqns:
        for v in eqn.outvars:
            big = max(big, int(np.prod(getattr(v.aval, "shape", ()))))
        for sub in jax.core.jaxprs_in_params(eqn.params):
            big = max(big, _largest(sub))
    return big


@pytest.mark.parametrize("backend", ["pallas", "xla"])
def test_loss_and_every_gradient_match_the_reference(setup, backend):
    model, params, batch, (ref_loss, ref_grads) = setup
    api.reset_health()
    loss, grads, jaxpr = _program(model, params, batch, backend)
    assert abs(float(loss) - float(ref_loss)) <= 1e-5 * abs(float(ref_loss))
    got = jax.tree_util.tree_leaves_with_path(grads)
    want = dict(jax.tree_util.tree_leaves_with_path(ref_grads))
    assert len(got) == len(want)
    for path, g in got:
        r = np.asarray(want[path])
        err = np.abs(np.asarray(g) - r).max() / np.abs(r).max()
        assert err < 1e-4, (jax.tree_util.keystr(path), err)
    counters = api.health()["counters"]
    assert counters.get("demote:coo_bwd", 0) == 0
    assert counters.get("demote:traced_pattern_to_xla", 0) == 0
    rows = model.patterns["gate"].rows[0]
    nnz = int(np.count_nonzero(np.asarray(rows) < model.patterns["gate"]
                               .shape[0]))
    flat = nnz * BATCH * SEQ                   # one (nnz, tokens) buffer
    if backend == "pallas":
        assert _largest(jaxpr) < flat
        kernels = {w["kernel"] for w in api.health()["work"].values()}
        assert kernels == {"repro_vsr_fused", "repro_sddmm"}
    else:
        assert _largest(jaxpr) >= flat
