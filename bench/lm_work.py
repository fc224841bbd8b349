"""Compulsory operations and bytes of the sparse-FFN language model's train
step, from its shapes (the counterpart of ``bench/work.py``, whose
conventions hold: every input read once, every output written once, each
multiply-add two operations, 4-byte values and indices).

Per pruned FFN matrix ``W`` ``(m, k)`` with ``nnz`` values, against ``n``
tokens, a step runs three sparse products:

* ``forward``: ``Y (m, n) = W X``: the pattern and values once, ``X``
  ``(k, n)`` and ``Y`` once;
* ``input_grad``: ``dX (k, n) = Wᵀ G``: the pattern and values once, ``G``
  ``(m, n)`` and ``dX`` once;
* ``value_grad`` (the SDDMM): ``dW[e] = <G[row_e], X[col_e]>``: the
  pattern once, ``G`` and ``X`` once, one value written per nonzero.

``model_flops`` is the step's model FLOPs as MFU counts them: six per
parameter of a matmul and token (forward and backward) for the attention
projections, the FFN's nonzeros and the head (the embedding is a lookup),
and the causal attention's score and value products,
``6 · layers · n² · heads · head_dim`` (half of the full square's 12).
"""
from __future__ import annotations

from bench import work

PRODUCTS = ("forward", "input_grad", "value_grad")


def sparse_products(m: int, k: int, nnz: int, n: int) -> dict:
    """``{product: {"flops", "bytes"}}`` of the three sparse products of one
    ``(m, k)`` matrix with ``nnz`` values against ``n`` tokens."""
    flops = 2 * nnz * n
    return {
        "forward": {"flops": flops,
                    "bytes": work.csr_bytes(m, nnz) + (k + m) * n * 4},
        "input_grad": {"flops": flops,
                       "bytes": work.csr_bytes(k, nnz) + (m + k) * n * 4},
        "value_grad": {"flops": flops,
                       "bytes": nnz * 8 + (m + k) * n * 4 + nnz * 4},
    }


def train_step(layers: int, d_model: int, heads: int, head_dim: int,
               d_ff: int, vocab: int, tokens: int, nnz: dict) -> dict:
    """The step's work: ``model_flops`` and ``sparse``, a list of
    ``(product, flops, bytes)`` for every sparse product of the step.
    ``nnz``: ``{"gate", "up", "down"}`` values per layer."""
    attn_params = 4 * d_model * heads * head_dim
    ffn_nnz = sum(nnz.values())
    model_flops = (6 * tokens * layers * (attn_params + ffn_nnz)
                   + 6 * tokens * d_model * vocab
                   + 6 * layers * tokens ** 2 * heads * head_dim)
    shapes = {"gate": (d_ff, d_model), "up": (d_ff, d_model),
              "down": (d_model, d_ff)}
    sparse = []
    for _ in range(layers):
        for name, (m, k) in shapes.items():
            for product, w in sparse_products(m, k, nnz[name],
                                              tokens).items():
                sparse.append((product, w["flops"], w["bytes"]))
    return {"model_flops": model_flops, "sparse": sparse}


def least_time(items, peak: dict, products=PRODUCTS) -> float:
    """Seconds: the sum of the least times of the ``items`` of ``sparse``
    whose product is in ``products``."""
    return sum(work.least_time(f, b, peak)[0] for p, f, b in items
               if p in products)
