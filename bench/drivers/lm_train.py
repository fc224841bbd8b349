"""Sparse-FFN language-model training: one AdamW step of a decoder whose FFN
weight matrices are pruned, through the program's normal path —
``repro.models.Model(cfg)``, its ``loss_fn`` with ``sparse_inputs()`` as
operands, ``repro.train.step.make_train_step`` and ``init_state`` — under
one ``jax.jit`` that donates the train state.  The pruned matrices run on
the program's sparse kernels forward and backward.

Configuration keys (``bench/configs/<config>.json``): the published widths
under their Hugging Face names, ``layers``, ``sparse_ffn`` (``density``,
``tile``; the patterns come from the program's fixed pattern seed),
``precision`` (parameters, optimizer state and compute; dense matmuls run
at that precision), ``optimizer`` (AdamW's hyperparameters, which the
program and the reference both take) and ``eos_token_id``.

Traffic keys: ``tokens`` per step (one sequence), ``batches`` (made from
the seed and rotated through the window), ``doc_len_median`` and
``doc_len_sigma`` (each sequence packs documents whose lengths are drawn
from that log-normal, cut at ``tokens``, each followed by the EOS id; token
ids are uniform over the vocabulary), ``in_flight``, ``samples`` and
``sample_span`` (the timed steps whose loss is checked, drawn from the seed
among the first ``sample_span``), and ``limits`` (``loss_gap``,
``grad_rel_err``, ``update_rel_err``).

The check, against the plain reference (``bench/reference/lm.py``):

* ``loss_gap``, ``|loss − ref| / |ref|``: each sampled timed step's loss
  on the step's own parameters and batch, and the loss of one more step of
  the same compiled program, untimed, from the state the window left;
* ``grad_rel_err``: the gradient that untimed step applied to every
  layer's three sparse value streams, read back from its first moment
  (``(m' − β₁ m) / (1 − β₁)``, unclipped by the step's own ``grad_norm``),
  against the reference's (the largest ``max|dv − ref| / max|ref|``);
* ``update_rel_err``: what that step wrote, every parameter leaf's change
  against the reference AdamW step on the reference's full gradient from
  the same state (the largest ``‖Δ − Δref‖ / ‖Δref‖``; a step that
  changes nothing reads 1).

Only the sampled timed steps' input parameters are copied on the device,
as each is sent, and brought to the host once the step before it has
finished.

Variants (the benchmark's runs use ``program``): ``control`` puts the
reference with bfloat16 operands in the program's place for every checked
loss, gradient and update; ``altered`` zeroes the gradient of one tile of
one layer's value stream, drawn from the seed, in the step.
"""
from __future__ import annotations

import time

import jax
import jax.numpy as jnp
import numpy as np

from bench import lm_work, seeds
from bench.reference import lm as reference

FFN = reference.FFN


def model_config(config: dict):
    """The program's ``ModelConfig`` of a configuration file."""
    from repro.models import ModelConfig, SparseFFNConfig
    sp = config["sparse_ffn"]
    return ModelConfig(
        name=config.get("name", "sparse-ffn-lm"), family="dense",
        num_layers=config["layers"], d_model=config["hidden_size"],
        num_heads=config["num_attention_heads"],
        num_kv_heads=config["num_key_value_heads"],
        d_ff=config["intermediate_size"], vocab_size=config["vocab_size"],
        head_dim=config["head_dim"], rope_theta=config["rope_theta"],
        norm_eps=config["rms_norm_eps"],
        tie_embeddings=config["tie_word_embeddings"], act="swiglu",
        param_dtype=config["precision"], compute_dtype=config["precision"],
        remat="none",
        sparse_ffn=SparseFFNConfig(density=sp["density"], tile=sp["tile"]))


def sequence(rng, n: int, vocab: int, eos: int, median: float,
             sigma: float, longest: int | None = None) -> np.ndarray:
    """``n`` tokens of packed documents: log-normal lengths (median
    ``median``, cut to ``[1, longest]``, ``longest`` defaulting to ``n``),
    uniform ids, an EOS after each."""
    out = []
    while sum(len(d) for d in out) < n:
        length = int(np.clip(round(rng.lognormal(np.log(median), sigma)),
                             1, longest or n))
        out.append(np.append(rng.integers(0, vocab, length), eos))
    return np.concatenate(out)[:n].astype(np.int32)


def train_config(config: dict):
    """The program's ``TrainConfig``: AdamW with the configuration's
    ``optimizer``."""
    from repro.train.optim import OptConfig
    from repro.train.step import TrainConfig
    opt = dict(config["optimizer"])
    return TrainConfig(opt=OptConfig(**dict(opt, betas=tuple(opt["betas"]))))


def _zero_tile_grad(layer: int, tile: int):
    """Identity whose backward zeroes one ``(layer, tile)`` row of the
    cotangent: the planted fault."""
    @jax.custom_vjp
    def f(v):
        return v

    f.defvjp(lambda v: (v, None),
             lambda _, g: (g.at[layer, tile].set(0.0),))
    return f


class Cell:
    variants = ("program", "control", "altered")

    def __init__(self, config: dict, traffic: dict, seed: int,
                 variant: str = "program"):
        from repro import api
        from repro.models import Model
        from repro.train.step import init_state, make_train_step
        if variant not in self.variants:
            raise ValueError(f"unknown variant {variant!r}")
        self.config, self.traffic, self.variant = config, traffic, variant
        self.precision = config["precision"]
        cfg = model_config(config)
        n_tok = traffic["tokens"]

        t0 = time.perf_counter()
        model = Model(cfg)                  # patterns and their kernel prep
        t1 = time.perf_counter()
        self.plan_s = t1 - t0
        self.phases = {"plan": self.plan_s}

        pats = {n: model.patterns[n] for n in FFN}
        self.patterns = {n: (np.asarray(p.rows), np.asarray(p.cols),
                             tuple(p.shape)) for n, p in pats.items()}
        nnz = {n: int(np.count_nonzero(r[0] < s[0]))
               for n, (r, _, s) in self.patterns.items()}
        tcfg = train_config(config)
        loss_fn = model.loss_fn
        rng = np.random.default_rng([seed, 3])
        if variant == "altered":
            name = FFN[int(rng.integers(len(FFN)))]
            layer = int(rng.integers(cfg.num_layers))
            tile = int(rng.integers(self.patterns[name][0].shape[1] - 1))
            zero = _zero_tile_grad(layer, tile)

            def loss_fn(params, batch, consts, _inner=model.loss_fn):
                ffn = dict(params["blocks"]["ffn"])
                ffn[f"v_{name}"] = zero(ffn[f"v_{name}"])
                params = dict(params, blocks=dict(params["blocks"], ffn=ffn))
                return _inner(params, batch, consts)
        self.sample_at = set(rng.choice(traffic["sample_span"],
                                        traffic["samples"],
                                        replace=False).tolist())

        params = model.init(seeds.key(seed, 0))
        self.state = init_state(params, tcfg, consts=model.sparse_inputs())
        data = np.random.default_rng([seed, 2])
        self.batches = []
        for _ in range(traffic["batches"]):
            s = sequence(data, n_tok + 1, cfg.vocab_size,
                         config["eos_token_id"], traffic["doc_len_median"],
                         traffic["doc_len_sigma"], longest=n_tok)
            self.batches.append({"tokens": jnp.asarray(s[None, :-1]),
                                 "labels": jnp.asarray(s[None, 1:])})
        t2 = time.perf_counter()
        self.phases["inputs"] = t2 - t1

        with jax.default_matmul_precision(self.precision):
            self.step = jax.jit(make_train_step(loss_fn, tcfg),
                                donate_argnums=(0,)).lower(
                self.state, self.batches[0]).compile()
        self.copy = jax.jit(lambda p: jax.tree_util.tree_map(jnp.copy, p)
                            ).lower(self.state["params"]).compile()
        t3 = time.perf_counter()
        self.phases["compile"] = t3 - t2
        self.state, metrics = self.step(self.state, self.batches[0])
        jax.block_until_ready(metrics["loss"])
        jax.block_until_ready(self.copy(self.state["params"]))
        # the sampled timed steps' parameters; after step 0 from ``launch``
        self.snaps = {0: jax.device_get(self.state["params"])} \
            if 0 in self.sample_at else {}
        self.phases["warm"] = time.perf_counter() - t3
        self.pending, self.losses = {}, {}

        self.describe = {
            "layers": cfg.num_layers, "d_model": cfg.d_model,
            "d_ff": cfg.d_ff, "heads": cfg.num_heads,
            "vocab": cfg.vocab_size, "tokens": n_tok,
            "ffn_density": cfg.sparse_ffn.density, "ffn_nnz": nnz,
            "kernels": sorted({w["kernel"]
                               for w in api.health()["work"].values()})}
        self.roofline_work = lm_work.train_step(
            cfg.num_layers, cfg.d_model, cfg.num_heads, cfg.head_dim,
            cfg.d_ff, cfg.vocab_size, n_tok, nnz)

    def batch(self, i: int) -> dict:
        return self.batches[i % len(self.batches)]

    def launch(self, i: int):
        if i > 0 and i in self.sample_at:
            self.pending[i] = self.copy(self.state["params"])
        self.state, metrics = self.step(self.state, self.batch(i))
        return metrics["loss"]

    def record(self, i: int, loss) -> None:
        self.losses[i] = float(loss)
        nxt = self.pending.pop(i + 1, None)
        if nxt is not None:          # step i + 1's parameters, to the host
            self.snaps[i + 1] = jax.device_get(nxt)
            del nxt

    def _last_step(self, batch) -> tuple:
        """One more step of the compiled program, untimed, from the state
        the window left: ``(state before, loss, gradient of the value
        streams, parameters after)``, on the host, the program's state
        freed."""
        before = jax.device_get({k: self.state[k] for k in ("params", "opt")})
        state, metrics = self.step(self.state, batch)
        self.state = None
        b1 = self.config["optimizer"]["betas"][0]
        clip = self.config["optimizer"]["clip_norm"]
        scale = min(1.0, clip / (float(metrics["grad_norm"]) + 1e-9))
        m0, m1 = before["opt"]["m"]["blocks"]["ffn"], jax.device_get(
            state["opt"]["m"]["blocks"]["ffn"])
        grads = {n: (np.asarray(m1[f"v_{n}"], np.float64)
                     - b1 * np.asarray(m0[f"v_{n}"], np.float64))
                 / ((1 - b1) * scale) for n in FFN}
        after = jax.device_get(state["params"])
        return before, float(metrics["loss"]), grads, after

    def check(self) -> tuple:
        """``(numbers, answers, wrong)``: the largest ``loss_gap`` of the
        checked losses, and the ``grad_rel_err`` and ``update_rel_err`` of
        one more step from the window's last state, each against the f32
        reference (program state freed first)."""
        from repro import api
        steps = sorted(i for i in self.losses if i in self.sample_at)
        snaps = {i: self.snaps[i] for i in steps}
        last = self.batch(max(self.losses) + 1)
        spec = {"heads": self.config["num_attention_heads"],
                "head_dim": self.config["head_dim"],
                "rope_theta": self.config["rope_theta"],
                "rms_norm_eps": self.config["rms_norm_eps"]}
        hp = self.config["optimizer"]
        if self.variant == "control":
            before = jax.device_get({k: self.state[k]
                                     for k in ("params", "opt")})
        else:
            with jax.default_matmul_precision(self.precision):
                before, loss, grads, after = self._last_step(last)
        self.state = self.step = self.copy = self.snaps = self.pending = None
        api.clear_cache()
        p0 = before["params"]
        if self.variant == "control":
            control = reference.Reference(spec, self.patterns, jnp.bfloat16)
            losses = [control.loss(snaps[i], self.batch(i)) for i in steps]
            loss, g = control.gradients(p0, last)
            after = jax.device_get(reference.adamw(p0, g, before["opt"], hp))
            grads = {n: np.asarray(g["blocks"]["ffn"][f"v_{n}"]) for n in FFN}
            del control, g
        else:
            losses = [self.losses[i] for i in steps]
        losses.append(float(loss))
        ref = reference.Reference(spec, self.patterns)
        want = [ref.loss(snaps[i], self.batch(i)) for i in steps]
        r_loss, g = ref.gradients(p0, last)
        want.append(float(r_loss))
        moved = jax.device_get(reference.adamw(p0, g, before["opt"], hp))
        g = {n: np.asarray(g["blocks"]["ffn"][f"v_{n}"]) for n in FFN}
        gaps = [abs(a - b) / abs(b) for a, b in zip(losses, want)]
        grad_err = max(reference.rel_err(grads[n][layer], g[n][layer])
                       for n in FFN for layer in range(g[n].shape[0]))
        update_err = max(jax.tree_util.tree_leaves(jax.tree_util.tree_map(
            _update_err, p0, after, moved)))
        limits = self.traffic["limits"]
        numbers = {"loss_gap": max(gaps), "grad_rel_err": grad_err,
                   "update_rel_err": update_err}
        wrong = sum(1 for e in gaps if limits["loss_gap"] is None
                    or not e <= limits["loss_gap"])
        wrong += sum(1 for k in ("grad_rel_err", "update_rel_err")
                     if limits[k] is None or not numbers[k] <= limits[k])
        return ({k: (v, limits[k]) for k, v in numbers.items()},
                len(gaps) + 2, int(wrong))


def _update_err(before, after, want) -> float:
    """``‖(after − before) − (want − before)‖ / ‖want − before‖``."""
    before = np.asarray(before, np.float64)
    step = np.asarray(want, np.float64) - before
    err = np.asarray(after, np.float64) - before - step
    return float(np.linalg.norm(err) / max(np.linalg.norm(step), 1e-30))
