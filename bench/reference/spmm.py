"""Plain reference of ``Y = A @ X`` for a CSR matrix: products at the
nonzeros, scatter-added into their rows.

It imports nothing of the system under test.  The nonzeros are taken in
blocks of ``chunk``, so the products of one block (``chunk`` rows of ``X``)
are all that is held beside ``Y``: a graph whose gathered rows would not
fit the chip at once still fits.  ``operand_dtype`` rounds the matrix values
and ``X`` to a narrower type before the products (the control: the same
computation with bfloat16 operands); products and sums stay in float32.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np

CHUNK = 1 << 21


@functools.partial(jax.jit, static_argnames=("m", "operand_dtype"))
def _apply(rows, cols, vals, x, *, m, operand_dtype):
    def rnd(a):
        return a.astype(operand_dtype).astype(jnp.float32)

    x2 = rnd(x[:, None] if x.ndim == 1 else x)

    def block(i, y):
        v = rnd(vals[i])
        return y.at[rows[i]].add(v[:, None] * x2[cols[i]])

    y = jax.lax.fori_loop(0, rows.shape[0], block,
                          jnp.zeros((m, x2.shape[1]), jnp.float32))
    return y[:, 0] if x.ndim == 1 else y


def make(indptr: np.ndarray, indices: np.ndarray, data: np.ndarray, m: int,
         operand_dtype=jnp.float32, chunk: int = CHUNK):
    """``ref(x)``: ``A @ x`` for a 1-D or 2-D ``x``.  The matrix is passed
    to the jitted product as arguments, not baked into it."""
    nnz = len(indices)
    blocks = max(1, -(-nnz // chunk))
    pad = blocks * chunk - nnz      # padding: value 0 at (0, 0)

    def blocked(a, dtype):
        return jnp.asarray(np.pad(np.asarray(a, dtype), (0, pad))
                           .reshape(blocks, chunk))

    rows = blocked(np.repeat(np.arange(m, dtype=np.int32), np.diff(indptr)),
                   np.int32)
    cols = blocked(indices, np.int32)
    vals = blocked(data, np.float32)
    return functools.partial(_apply, rows, cols, vals, m=m,
                             operand_dtype=jnp.dtype(operand_dtype))


def rel_err(got, want) -> float:
    """Largest absolute error over the largest absolute reference value."""
    got = jnp.asarray(got, jnp.float32)
    want = jnp.asarray(want, jnp.float32)
    return float(jnp.max(jnp.abs(got - want))
                 / jnp.maximum(jnp.max(jnp.abs(want)), 1e-30))
