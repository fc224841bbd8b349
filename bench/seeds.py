"""PRNG keys from the run's ``--seed``: any whole number up to a little
over 2**31, split into independent streams by number."""
from __future__ import annotations

import jax


def key(seed: int, stream: int):
    k = jax.random.fold_in(jax.random.PRNGKey(stream), seed % (1 << 31))
    return jax.random.fold_in(k, seed >> 31)
