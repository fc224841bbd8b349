"""Training substrate: optimizer math, schedules, microbatching, compression,
and single-batch overfit (gradient-flow integration test)."""
import numpy as np
import pytest
import jax
import jax.numpy as jnp

from repro.configs import get_smoke
from repro.data import DataConfig, SyntheticLM
from repro.models import Model
from repro.train import OptConfig, TrainConfig, init_state, make_train_step
from repro.train.compress import ef_accumulate, int8_decode, int8_encode
from repro.train.optim import adamw_update, global_norm, init_opt_state, schedule


def test_adamw_matches_reference():
    """One AdamW step vs a hand-rolled numpy reference."""
    cfg = OptConfig(lr=1e-2, betas=(0.9, 0.99), eps=1e-8, weight_decay=0.0,
                    clip_norm=1e9, warmup_steps=0, total_steps=1,
                    min_lr_ratio=1.0)
    p = {"w": jnp.asarray([[1.0, -2.0]])}
    g = {"w": jnp.asarray([[0.5, 0.5]])}
    st = init_opt_state(p, cfg)
    newp, newst, _ = adamw_update(p, g, st, cfg)
    m = 0.1 * 0.5
    v = 0.01 * 0.25
    mhat, vhat = m / 0.1, v / 0.01
    expect = 1.0 - 1e-2 * mhat / (np.sqrt(vhat) + 1e-8)
    np.testing.assert_allclose(np.asarray(newp["w"])[0, 0], expect, rtol=1e-5)


def test_schedule_shape():
    cfg = OptConfig(lr=1.0, warmup_steps=10, total_steps=100, min_lr_ratio=0.1)
    s = [float(schedule(cfg, jnp.asarray(i))) for i in [0, 5, 10, 50, 100]]
    assert s[0] == 0.0 and abs(s[1] - 0.5) < 1e-6 and abs(s[2] - 1.0) < 1e-6
    assert s[3] < 1.0 and abs(s[4] - 0.1) < 1e-3


def test_clip_norm():
    cfg = OptConfig(clip_norm=1.0, warmup_steps=0, total_steps=1, min_lr_ratio=1.0)
    p = {"w": jnp.zeros((4,))}
    g = {"w": jnp.full((4,), 100.0)}
    st = init_opt_state(p, cfg)
    _, _, metrics = adamw_update(p, g, st, cfg)
    assert float(metrics["grad_norm"]) == pytest.approx(200.0)


def test_microbatch_equals_full_batch():
    """grad accumulation over 4 microbatches ≈ one full-batch step."""
    cfg = get_smoke("llama3.2-1b")
    model = Model(cfg)
    params = model.init(jax.random.PRNGKey(0))
    data = SyntheticLM(DataConfig(seed=0, vocab_size=cfg.vocab_size,
                                  seq_len=16, global_batch=8))
    batch = {k: jnp.asarray(v) for k, v in data.batch(0).items()}
    opt = OptConfig(lr=1e-3, warmup_steps=0, total_steps=10, min_lr_ratio=1.0)
    s1, m1 = make_train_step(model.loss_fn, TrainConfig(opt=opt))(
        init_state(params, TrainConfig(opt=opt)), batch)
    s4, m4 = make_train_step(model.loss_fn, TrainConfig(opt=opt, microbatches=4))(
        init_state(params, TrainConfig(opt=opt)), batch)
    # losses (mean over microbatches vs full) and updates should be close
    assert abs(float(m1["loss"]) - float(m4["loss"])) < 0.05
    d = jax.tree_util.tree_map(lambda a, b: float(jnp.abs(a - b).max()),
                               s1["params"], s4["params"])
    assert max(jax.tree_util.tree_leaves(d)) < 5e-3


def test_overfit_single_batch():
    cfg = get_smoke("llama3.2-1b")
    model = Model(cfg)
    tcfg = TrainConfig(opt=OptConfig(lr=3e-3, warmup_steps=2, total_steps=100,
                                     min_lr_ratio=1.0))
    step = jax.jit(make_train_step(model.loss_fn, tcfg))
    state = init_state(model.init(jax.random.PRNGKey(0)), tcfg)
    data = SyntheticLM(DataConfig(seed=0, vocab_size=cfg.vocab_size,
                                  seq_len=32, global_batch=8))
    batch = {k: jnp.asarray(v) for k, v in data.batch(0).items()}
    first = None
    for _ in range(30):
        state, metrics = step(state, batch)
        first = first if first is not None else float(metrics["loss"])
    assert float(metrics["loss"]) < first - 1.0, "overfit failed"


def test_sparse_ffn_step_reaches_pallas_through_scan():
    """The scanned sparse-FFN layers hand their precomputed visit schedules
    to the Pallas kernel (no traced-pattern fallback to XLA), and the
    patterns ride the state as operands: the step matches the XLA backend."""
    import dataclasses

    from repro import api
    from repro.models.config import SparseFFNConfig
    cfg = dataclasses.replace(get_smoke("llama3.2-1b"),
                              sparse_ffn=SparseFFNConfig(density=0.3, tile=128))
    model = Model(cfg)
    opt = OptConfig(lr=1e-3, warmup_steps=0, total_steps=2)
    data = SyntheticLM(DataConfig(seed=0, vocab_size=cfg.vocab_size,
                                  seq_len=8, global_batch=2))
    batch = {k: jnp.asarray(v) for k, v in data.batch(0).items()}
    params = model.init(jax.random.PRNGKey(0))
    out = {}
    api.reset_health()
    for backend in ("pallas", "xla"):
        tcfg = TrainConfig(opt=opt, sparse_backend=backend)
        state = init_state(params, tcfg, consts=model.sparse_inputs())
        state, metrics = jax.jit(make_train_step(model.loss_fn, tcfg))(
            state, batch)
        assert "consts" in state
        out[backend] = (float(metrics["loss"]), state["params"])
    assert "demote:traced_pattern_to_xla" not in api.health()["counters"]
    assert out["pallas"][0] == pytest.approx(out["xla"][0], rel=1e-5)
    d = jax.tree_util.tree_map(lambda a, b: float(jnp.abs(a - b).max()),
                               out["pallas"][1], out["xla"][1])
    assert max(jax.tree_util.tree_leaves(d)) < 1e-4


def test_int8_roundtrip_and_ef():
    rng = np.random.default_rng(0)
    x = jnp.asarray(rng.standard_normal(512).astype(np.float32))
    q, s = int8_encode(x)
    err = float(jnp.abs(int8_decode(q, s) - x).max())
    assert err <= float(s) * 0.51 + 1e-6
    # error feedback: quantized + residual reproduces input exactly
    r = jnp.zeros_like(x)
    q, s, r2 = ef_accumulate(x, r)
    np.testing.assert_allclose(np.asarray(int8_decode(q, s) + r2),
                               np.asarray(x), atol=1e-6)
    # EF converges: accumulated quantized stream ≈ accumulated true stream
    total_q, total_true = jnp.zeros_like(x), jnp.zeros_like(x)
    r = jnp.zeros_like(x)
    for i in range(20):
        g = jnp.asarray(rng.standard_normal(512).astype(np.float32))
        q, s, r = ef_accumulate(g, r)
        total_q = total_q + int8_decode(q, s)
        total_true = total_true + g
    resid = float(jnp.abs(total_q + r - total_true).max())
    assert resid < 1e-4


def test_dp_compressed_allreduce_matches_mean():
    """manual_collectives: the shard_map int8+EF gradient all-reduce over the
    DP axis ≈ the plain f32 mean (quantization error bounded, residual
    carries the remainder).  Exercises the shard_map path on however many
    devices the host exposes."""
    from repro.train.manual_collectives import make_dp_compressed_allreduce

    n = jax.device_count()
    mesh = jax.make_mesh((n,), ("data",),
                         axis_types=(jax.sharding.AxisType.Auto,))
    rng = np.random.default_rng(0)
    g_np = rng.standard_normal((n, 8)).astype(np.float32)
    grads = {"w": jnp.asarray(g_np)}
    residuals = {"w": jnp.zeros((n, 8), jnp.float32)}
    reduce_fn = make_dp_compressed_allreduce(mesh, "data")
    mean, new_r = reduce_fn(grads, residuals)
    # numpy mirror of the wire protocol: per-device int8 encode, int32 sum,
    # decode once with the mean scale
    scales = np.maximum(np.abs(g_np).max(axis=1), 1e-30) / 127.0
    q = np.clip(np.round(g_np / scales[:, None]), -127, 127)
    want = (q.sum(axis=0) * scales.mean()) / n
    np.testing.assert_allclose(np.asarray(mean["w"]), want, rtol=1e-5)
    # ...which stays within quantization distance of the true f32 mean:
    # per-device error ≤ 127·|s_i − s̄| (mean-scale decode) + s_i/2 (rounding)
    bound = (127 * np.abs(scales - scales.mean()).sum()
             + scales.sum() / 2) / n
    np.testing.assert_allclose(want, g_np.mean(axis=0), atol=float(bound))
    # error feedback carries the per-device quantization remainder
    assert new_r["w"].shape == (n, 8)


def test_data_pipeline_deterministic_resumable():
    cfg = DataConfig(seed=7, vocab_size=100, seq_len=8, global_batch=4)
    a, b = SyntheticLM(cfg), SyntheticLM(cfg)
    for step in [0, 5, 11]:
        np.testing.assert_array_equal(a.batch(step)["tokens"],
                                      b.batch(step)["tokens"])
    # host slicing partitions the global batch
    full = a.batch(3)["tokens"]
    parts = [a.host_slice(3, h, 2)["tokens"] for h in range(2)]
    np.testing.assert_array_equal(np.concatenate(parts), full)
