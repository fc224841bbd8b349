"""The sparse-FFN language-model cell at a small size on the CPU: a whole
run decides ``correct`` (the program passes; the bfloat16 control and the
planted fault do not), its traffic generator, its work counts and the
readers of its per-layer metrics."""
import json
import sys
import types
from pathlib import Path

import numpy as np
import pytest

ROOT = Path(__file__).resolve().parents[2]
sys.path.insert(0, str(ROOT))

from bench import lm_work, run  # noqa: E402
from bench.drivers import lm_train  # noqa: E402
from bench.metrics import mfu_pct, sddmm_roofline, sparse_ffn_roofline  # noqa: E402

NAME = "lm-train.phi3-mini-sparse-ffn"
SEED = 2**31 + 13
V5E = {"flops_per_s": 197e12, "hbm_bytes_per_s": 819e9}


def small():
    config = json.loads((ROOT / "bench" / "configs"
                         / "phi3-mini-sparse-ffn.json").read_text())
    config = dict(config, layers=2, hidden_size=64, intermediate_size=128,
                  num_attention_heads=4, num_key_value_heads=4, head_dim=16,
                  vocab_size=256, eos_token_id=255,
                  sparse_ffn=dict(config["sparse_ffn"], tile=64))
    traffic = json.loads((ROOT / "bench" / "traffic" / "lm-train.json")
                         .read_text())
    return config, dict(traffic, tokens=32, doc_len_median=8)


@pytest.mark.parametrize("variant,correct", [("program", True),
                                             ("control", False),
                                             ("altered", False)])
def test_run_decides_correct(variant, correct):
    config, traffic = small()
    result = run.execute(NAME, SEED, 0.2, False,
                         cell_spec={"name": NAME, "chips": 1}, config=config,
                         traffic=traffic, variant=variant, chip_check=False)
    assert result["correct"] is correct, json.dumps(result["checks"])
    assert list(result)[-1] == "checks"
    assert set(result["checks"]) == {"loss_gap", "grad_rel_err",
                                     "update_rel_err", "window_compilations"}
    assert result["checks"]["window_compilations"]["value"] == 0
    assert result["attempted"] >= 2
    assert {"step_ms", "setup_s"} <= set(result["metrics"])


def test_unchanged_state_fails_the_update_check(monkeypatch):
    """A step that writes its parameters back unchanged reads 1 in
    ``update_rel_err`` and fails, though its loss and gradient are right."""
    last_step = lm_train.Cell._last_step

    def frozen(self, batch):
        before, loss, grads, _ = last_step(self, batch)
        return before, loss, grads, before["params"]

    monkeypatch.setattr(lm_train.Cell, "_last_step", frozen)
    config, traffic = small()
    result = run.execute(NAME, SEED, 0.2, False,
                         cell_spec={"name": NAME, "chips": 1}, config=config,
                         traffic=traffic, chip_check=False)
    checks = result["checks"]
    assert checks["update_rel_err"]["value"] == pytest.approx(1.0)
    assert checks["grad_rel_err"]["value"] <= checks["grad_rel_err"]["limit"]
    assert checks["loss_gap"]["value"] <= checks["loss_gap"]["limit"]
    assert result["correct"] is False and result["failed"] == 1


def test_reference_adamw_against_the_program():
    """The reference's AdamW step, warm-up and clipping included, against
    the program's ``adamw_update`` on a clipped and an unclipped gradient."""
    from bench.reference import lm as reference
    from repro.train.optim import adamw_update
    config, _ = small()
    tcfg = lm_train.train_config(config)
    rng = np.random.default_rng(0)
    params = {"a": rng.normal(size=(5, 7)).astype(np.float32),
              "b": rng.normal(size=(3,)).astype(np.float32)}
    opt = {"step": np.int32(6),
           "m": {k: rng.normal(size=v.shape).astype(np.float32) * 0.1
                 for k, v in params.items()},
           "v": {k: rng.random(size=v.shape).astype(np.float32) * 0.01
                 for k, v in params.items()}}
    for size in (1e-3, 10.0):
        grads = {k: rng.normal(size=v.shape).astype(np.float32) * size
                 for k, v in params.items()}
        want, _, _ = adamw_update(params, grads, opt, tcfg.opt)
        got = reference.adamw(params, grads, opt, config["optimizer"])
        for k in params:
            np.testing.assert_allclose(np.asarray(got[k]),
                                       np.asarray(want[k]), rtol=1e-6,
                                       atol=1e-7)
    assert reference.learning_rate(config["optimizer"], 50) == \
        pytest.approx(1.5e-4)
    assert reference.learning_rate(config["optimizer"], 10_000) == \
        pytest.approx(3e-5)


def test_sequence_packs_seeded_documents():
    a = lm_train.sequence(np.random.default_rng(1), 4096, 32064, 32000,
                          256, 1.0)
    b = lm_train.sequence(np.random.default_rng(1), 4096, 32064, 32000,
                          256, 1.0)
    np.testing.assert_array_equal(a, b)
    assert a.shape == (4096,) and a.dtype == np.int32
    assert a.min() >= 0 and a.max() < 32064
    ends = np.flatnonzero(a == 32000)
    lengths = np.diff(ends) - 1
    assert 100 < np.median(lengths) < 600     # the log-normal's median, 256
    # documents are cut at ``longest``, not at the sequence's length
    short = lm_train.sequence(np.random.default_rng(1), 4096, 32064, 32000,
                              256, 1.0, longest=64)
    ends = np.flatnonzero(short == 32000)
    assert np.diff(ends).max() - 1 <= 64


def test_work_counts_by_hand():
    m, k, nnz, n = 8192, 3072, 2_516_582, 1024
    w = lm_work.sparse_products(m, k, nnz, n)
    assert all(p["flops"] == 2 * nnz * n for p in w.values())
    assert w["forward"]["bytes"] == (m + 1) * 4 + nnz * 8 + (k + m) * n * 4
    assert w["input_grad"]["bytes"] == (k + 1) * 4 + nnz * 8 + (m + k) * n * 4
    assert w["value_grad"]["bytes"] == nnz * 12 + (m + k) * n * 4
    step = lm_work.train_step(4, 3072, 32, 96, 8192, 32064, 1024,
                              {"gate": nnz, "up": nnz, "down": nnz})
    assert len(step["sparse"]) == 4 * 3 * 3
    attn = 4 * 3072 * 32 * 96
    assert step["model_flops"] == (6 * 1024 * 4 * (attn + 3 * nnz)
                                   + 6 * 1024 * 3072 * 32064
                                   + 6 * 4 * 1024 ** 2 * 32 * 96)
    # memory-bound: the forward's least time is its bytes at 819 GB/s
    t = lm_work.least_time(step["sparse"][:1], V5E)
    assert t == pytest.approx(w["forward"]["bytes"] / 819e9)


def _run(op_s, busy_s, steps=2):
    cell = types.SimpleNamespace(roofline_work=lm_work.train_step(
        1, 64, 4, 16, 128, 256, 32, {"gate": 800, "up": 800, "down": 800}))
    trace = types.SimpleNamespace(op_s=op_s, busy_s=busy_s)
    return types.SimpleNamespace(cell=cell, trace=trace, steps=steps,
                                 peak=V5E)


def test_metric_readers():
    r = _run({"repro_vsr_fused": 3.0, "repro_sddmm": 1.0, "fusion": 0.5},
             busy_s=4.5)
    work = r.cell.roofline_work
    assert mfu_pct.read(r) == pytest.approx(
        100 * work["model_flops"] / (197e12 * 4.5 / 2))
    assert sparse_ffn_roofline.read(r) == pytest.approx(
        100 * lm_work.least_time(work["sparse"], V5E) / (4.0 / 2))
    assert sddmm_roofline.read(r) == pytest.approx(
        100 * lm_work.least_time(work["sparse"], V5E, ("value_grad",))
        / (1.0 / 2))
    # nothing to read: no kernel time, no steps
    silent = _run({"fusion": 1.0}, busy_s=1.0)
    assert sparse_ffn_roofline.read(silent) is None
    assert sddmm_roofline.read(silent) is None
    assert mfu_pct.read(_run({}, busy_s=0.0)) is None
