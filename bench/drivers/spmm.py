"""SpMM / SpMV on a generated graph: ``Y = A @ X`` through the library's
front door, ``repro.api.sparse(csr)`` and its selector's pick, under one
``jax.jit``.

Traffic keys: ``n`` (width of ``X``; 0 is a 1-D ``x``), ``operands``
(seeded ``X`` rotated through the window), ``samples`` and ``sample_span``
(how many outputs are kept for the check, drawn from the seed among the
first ``sample_span`` steps; the window's last output is always kept) and
``limits.rel_err``.

Variants (the benchmark's runs use ``program``): ``control`` puts the
reference with bfloat16 operands in the program's place; ``altered``
changes one output row where the program produces it.
"""
from __future__ import annotations

import time

import jax
import jax.numpy as jnp
import numpy as np

from bench import graph, seeds, work
from bench.reference import spmm as reference


class Cell:
    variants = ("program", "control", "altered")

    def __init__(self, config: dict, traffic: dict, seed: int,
                 variant: str = "program"):
        from repro import api
        from repro.core.formats import CSR
        if variant not in self.variants:
            raise ValueError(f"unknown variant {variant!r}")
        self.traffic = traffic
        t_start = time.perf_counter()
        indptr, indices, data, n_vert = graph.kronecker_csr(
            config["graph_seed"], scale=config["scale"],
            edge_factor=config["edge_factor"], initiator=config["initiator"],
            permute=config["permute_vertices"])
        self.csr = (indptr, indices, data, n_vert)
        n = traffic["n"]
        shape = (n_vert,) if n == 0 else (n_vert, n)
        keys = jax.random.split(seeds.key(seed, 1), traffic["operands"])
        self.xs = [jax.random.normal(k, shape, jnp.float32) for k in keys]
        t0 = time.perf_counter()
        self.phases = {"inputs": t0 - t_start}
        A = api.sparse(CSR(jnp.asarray(indptr), jnp.asarray(indices),
                           jnp.asarray(data), (n_vert, n_vert)))
        st = A.stats
        self.describe = {"rows": st.m, "nnz": st.nnz,
                         "row_mean": st.avg_row, "row_cv": st.cv,
                         "row_max": st.max_row,
                         "empty_rows_share": st.empty_rows / st.m,
                         "backend": A.backend}
        if variant == "control":
            self.fn = reference.make(indptr, indices, data, n_vert,
                                     operand_dtype=jnp.bfloat16)
        else:
            if variant == "altered":
                self.fn = jax.jit(lambda x: (A @ x).at[0].add(1.0))
            else:
                self.fn = jax.jit(lambda x: A @ x)
            self.fn.lower(self.xs[0])    # the program plans as it is traced
        t1 = time.perf_counter()
        self.plan_s = self.phases["plan"] = t1 - t0
        for x in self.xs:                # compiles, then warms each operand
            jax.block_until_ready(self.fn(x))
        self.phases["compile_warm"] = time.perf_counter() - t1
        rng = np.random.default_rng(seed)
        self.sample_at = set(rng.choice(traffic["sample_span"],
                                        traffic["samples"], replace=False)
                             .tolist())
        self.kept = {}
        self.roofline_work = work.spmm(n_vert, n_vert, st.nnz, n)

    def launch(self, i: int):
        return self.fn(self.xs[i % len(self.xs)])

    def record(self, i: int, out) -> None:
        if i in self.sample_at:
            self.kept[i] = out
        self.kept["last"] = (i, out)

    def check(self) -> tuple:
        """``(numbers, answers, wrong)``: the largest relative error of the
        kept outputs against the f32 reference (freed program state
        first), how many outputs were compared and how many failed."""
        last_i, last = self.kept.pop("last")
        outs = {**self.kept, last_i: last}
        outs = {i: np.asarray(y) for i, y in outs.items()}
        self.fn = self.kept = None
        from repro import api
        api.clear_cache()
        indptr, indices, data, n_vert = self.csr
        ref = reference.make(indptr, indices, data, n_vert)
        limit = self.traffic["limits"]["rel_err"]
        errs = [reference.rel_err(y, ref(self.xs[i % len(self.xs)]))
                for i, y in sorted(outs.items())]
        wrong = sum(1 for e in errs if limit is None or not e <= limit)
        return {"rel_err": (max(errs), limit)}, len(errs), wrong

