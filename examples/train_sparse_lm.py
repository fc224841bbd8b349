"""End-to-end driver (deliverable b): train a ~100M-param llama-family model
for a few hundred steps on CPU, with the paper's sparse-FFN feature ON —
every MLP matmul runs through the adaptive SpMM with trainable nonzeros.

    PYTHONPATH=src python examples/train_sparse_lm.py --steps 200

Also demonstrates checkpoint/restart: kill it mid-run and rerun — it resumes
from the last committed step."""
import argparse
import sys

sys.path.insert(0, "src")

import jax
import jax.numpy as jnp

from repro.configs import get
from repro.data import DataConfig, SyntheticLM
from repro.models import Model
from repro.models.config import SparseFFNConfig
from repro.runtime import DriverConfig, TrainDriver
from repro.train import OptConfig, TrainConfig, init_state, make_train_step


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--steps", type=int, default=200)
    ap.add_argument("--batch", type=int, default=8)
    ap.add_argument("--seq", type=int, default=128)
    ap.add_argument("--density", type=float, default=0.15)
    ap.add_argument("--sparse-backend", default=None,
                    help="pin the sparse kernels' backend for the whole step "
                         "(repro.api.use_backend scope; default: platform)")
    ap.add_argument("--calibrate-to", default=None,
                    help="background-calibrate selector thresholds to this "
                         "JSON on first run (auto-loads via $REPRO_THRESHOLDS)")
    args = ap.parse_args()

    cfg = get("llama3.2-1b").scaled(
        num_layers=6, d_model=512, num_heads=8, num_kv_heads=4, d_ff=2048,
        vocab_size=8192, head_dim=64,
        sparse_ffn=SparseFFNConfig(density=args.density, tile=512),
        param_dtype="float32", compute_dtype="float32", remat="none")
    model = Model(cfg)
    from repro.models.params import param_count
    print(f"sparse-FFN LM: {param_count(model.specs)/1e6:.1f}M params "
          f"(FFN density {args.density})")

    tcfg = TrainConfig(opt=OptConfig(lr=1e-3, warmup_steps=20,
                                     total_steps=args.steps),
                       sparse_backend=args.sparse_backend)
    data = SyntheticLM(DataConfig(seed=0, vocab_size=cfg.vocab_size,
                                  seq_len=args.seq, global_batch=args.batch))
    step = jax.jit(make_train_step(model.loss_fn, tcfg), donate_argnums=(0,))
    # the sparse patterns ride the state as operands, not jit constants
    state = init_state(model.init(jax.random.PRNGKey(0)), tcfg,
                       consts=model.sparse_inputs())

    driver = TrainDriver(
        DriverConfig(total_steps=args.steps, checkpoint_every=50,
                     checkpoint_dir="/tmp/repro_sparse_lm_ckpt",
                     calibrate_to=args.calibrate_to),
        step, lambda i: {k: jnp.asarray(v) for k, v in data.batch(i).items()})
    driver.run(state)
    losses = [e.metrics["loss"] for e in driver.events]
    print(f"loss: {losses[0]:.3f} → {losses[-1]:.3f} over {len(losses)} steps")
    assert losses[-1] < losses[0], "training must reduce loss"


if __name__ == "__main__":
    main()
