"""Graph500 Kronecker (R-MAT) graphs, generated on the device from a seed.

The recipe is the Graph500 specification's Kronecker generator (section 3):
``edge_factor * 2**scale`` edges, each placed by ``scale`` independent
quadrant draws with initiator probabilities (A, B, C, D); vertex labels are
then permuted at random.  Duplicate edges are coalesced (their weights
summed), so the graph is a CSR matrix with sorted, unique column indices.

Everything up to the CSR arrays runs in one jitted call on the default
device; only the finished arrays come back to the host.  (The host loop of
``repro.core.rmat`` takes about ten seconds at scale 18 alone.)
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np

from bench import seeds


@functools.partial(jax.jit, static_argnames=("scale", "edge_factor",
                                             "initiator", "permute"))
def _coalesced_edges(key, *, scale, edge_factor, initiator, permute):
    n = 1 << scale
    ne = edge_factor * n
    a, b, c, _ = initiator
    k_bits, k_perm, k_w = jax.random.split(key, 3)

    def level(i, rc):
        rows, cols = rc
        r = jax.random.uniform(jax.random.fold_in(k_bits, i), (ne,))
        down = r >= a + b                      # quadrant C or D: row bit 1
        right = ((r >= a) & (r < a + b)) | (r >= a + b + c)   # B or D
        return (rows << 1) | down.astype(jnp.int32), \
            (cols << 1) | right.astype(jnp.int32)

    zeros = jnp.zeros((ne,), jnp.int32)
    rows, cols = jax.lax.fori_loop(0, scale, level, (zeros, zeros))
    if permute:
        perm = jax.random.permutation(k_perm, n).astype(jnp.int32)
        rows, cols = perm[rows], perm[cols]
    w = jax.random.uniform(k_w, (ne,), jnp.float32)
    rows, cols, w = jax.lax.sort((rows, cols, w), num_keys=2)
    first = jnp.concatenate([jnp.ones((1,), bool),
                             (rows[1:] != rows[:-1]) | (cols[1:] != cols[:-1])])
    slot = jnp.cumsum(first) - 1               # coalesced position of each edge
    total = rows.shape[0]
    out_cols = jnp.zeros((total,), jnp.int32).at[slot].set(cols)
    out_w = jax.ops.segment_sum(w, slot, num_segments=total)
    counts = jax.ops.segment_sum(first.astype(jnp.int32), rows, num_segments=n)
    indptr = jnp.concatenate([jnp.zeros((1,), jnp.int32), jnp.cumsum(counts)])
    return indptr, out_cols, out_w


def kronecker_csr(seed: int, *, scale: int, edge_factor: int,
                  initiator=(0.57, 0.19, 0.19, 0.05), permute: bool = True):
    """``(indptr, indices, data, n)`` of the seeded Graph500 graph as host
    numpy arrays (int32, int32, float32)."""
    indptr, cols, w = _coalesced_edges(
        seeds.key(seed, 0), scale=scale, edge_factor=edge_factor,
        initiator=tuple(float(p) for p in initiator), permute=permute)
    indptr = np.asarray(indptr)
    nnz = int(indptr[-1])
    return indptr, np.asarray(cols[:nnz]), np.asarray(w[:nnz]), 1 << scale

