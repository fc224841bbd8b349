"""Shared value-stream quantization: per-tile symmetric int8/fp8 + scales.

One module serves two consumers (the ISSUE-6 dedup):

* the **plan subsystem** — quantized BalancedCOO substrates store int8 (or
  fp8 where the runtime has the dtype) value streams with one f32 scale per
  nnz-tile; the fused NB kernels dequantize *in register* (the scale rides
  the scalar-prefetch path next to the visit schedule, DESIGN.md §8), so
  the HBM value stream shrinks 2–4x with no host-side dequant and no extra
  round trip;
* the **training side** — ``train/compress.py``'s gradient/optimizer-state
  compression keeps its public names but delegates to the per-tensor
  helpers here.

Per-*tile* scales (not per-tensor) are what make the scheme safe on real
matrices: a single huge nonzero only costs precision inside its own
``tile``-nonzero quota.  When even a single tile's dynamic range
(``amax / rms``) exceeds ``MAX_DYNAMIC_RANGE`` the plan layer falls back to
the unquantized substrate with a warning instead of silently shipping a
stream whose small values all collapsed to zero (``check_tile_range``).

Quantization error is forward-only by construction: the unified custom VJPs
(``core/vjp.py``) compute backward passes analytically from the *saved f32
residuals*, so gradients through quantized plans are straight-through —
exact for the unquantized operator, regardless of the forward kernel.
"""
from __future__ import annotations

import warnings

import jax
import jax.numpy as jnp
import numpy as np

#: quantized-substrate modes the plan layer accepts (``quant=`` option).
QUANT_MODES = ("int8", "fp8")

#: fp8 storage dtype — e4m3 (1 sign, 4 exponent, 3 mantissa): the variant
#: with the range/precision tradeoff tuned for forward values.
FP8_DTYPE = jnp.float8_e4m3fn

#: symmetric quantization ceiling per mode: int8 clips at +/-127, e4m3's
#: largest finite value is 448.
QMAX = {"int8": 127.0, "fp8": 448.0}

#: per-tile dynamic-range bound (amax / median |nonzero|) above which
#: quantization of the whole substrate is refused: the int8 grid spacing is
#: amax/127, so entries below amax/254 round to zero — at amax/median = 512
#: the *typical* entry is already two grid steps below representable and
#: most of the tile collapses.  Median (not rms) so a single huge outlier
#: cannot mask itself by inflating the denominator.
MAX_DYNAMIC_RANGE = 512.0


def quant_dtype(mode: str):
    """The storage dtype for one quant mode (raises on unknown/unsupported)."""
    if mode == "int8":
        return jnp.int8
    if mode == "fp8":
        return FP8_DTYPE
    raise ValueError(f"unknown quant mode {mode!r}; expected one of "
                     f"{QUANT_MODES}")


def is_quantized_dtype(dtype) -> bool:
    """True for value dtypes that need a scale to decode (int8/fp8 streams).

    The kernels use this to tell a baked quantized substrate (dequantize
    with the plan's scales) from a live f32/bf16 stream (re-quantize in
    graph, fresh scales)."""
    dtype = jnp.dtype(dtype)
    if dtype == jnp.dtype(jnp.int8):
        return True
    return dtype == jnp.dtype(FP8_DTYPE)


def value_bytes(dtype) -> int:
    """Bytes per element of a value stream — the traffic model's input
    (fixes the historical hardcoded 4: bf16 streams are 2, int8/fp8 are 1)."""
    return int(jnp.dtype(dtype).itemsize)


# ---------------------------------------------------------------------------
# per-tensor helpers (the training-side compression primitives)
# ---------------------------------------------------------------------------

def int8_encode(x: jax.Array) -> tuple[jax.Array, jax.Array]:
    """Per-tensor symmetric int8: ``q = round(x / scale)``, scale = amax/127."""
    amax = jnp.max(jnp.abs(x.astype(jnp.float32)))
    scale = jnp.where(amax > 0, amax / 127.0, 1.0)
    q = jnp.clip(jnp.round(x.astype(jnp.float32) / scale), -127, 127).astype(jnp.int8)
    return q, scale


def int8_decode(q: jax.Array, scale: jax.Array) -> jax.Array:
    return q.astype(jnp.float32) * scale


# ---------------------------------------------------------------------------
# per-tile stream quantization (the substrate/kernels contract)
# ---------------------------------------------------------------------------

def quantize_stream(vals: jax.Array, mode: str) -> tuple[jax.Array, jax.Array]:
    """Quantize a ``(..., tile)`` value slab per *leading-axes* tile.

    Returns ``(q, scales)`` with ``q`` shaped like ``vals`` in the mode's
    storage dtype and ``scales`` f32 shaped like ``vals.shape[:-1]`` (one
    scale per nnz-tile).  Pure jnp — usable both host-side (substrate
    baking under ``ensure_compile_time_eval``) and in-graph (``with_values``
    live streams re-quantize on the fly, differentiably via the
    straight-through custom VJPs)."""
    qmax = QMAX[mode]
    dtype = quant_dtype(mode)
    v = vals.astype(jnp.float32)
    amax = jnp.max(jnp.abs(v), axis=-1)
    scales = jnp.where(amax > 0, amax / qmax, 1.0)
    scaled = v / scales[..., None]
    if mode == "int8":
        q = jnp.clip(jnp.round(scaled), -qmax, qmax).astype(dtype)
    else:
        q = scaled.astype(dtype)
    return q, scales


def dequantize_stream(q: jax.Array, scales: jax.Array) -> jax.Array:
    """Decode a quantized slab back to f32 (reference/XLA path; the Pallas
    kernels do this multiply in register instead)."""
    return q.astype(jnp.float32) * scales[..., None]


def check_tile_range(vals, bound: float = MAX_DYNAMIC_RANGE,
                     context: str = "substrate") -> bool:
    """Per-tile dynamic-range guard for ``(..., tile)`` slabs.

    Returns True when every tile's ``amax / median(|nonzero|)`` (sentinel-
    padded zeros excluded) stays within ``bound`` — i.e. the slab quantizes
    safely.  On violation warns (naming the worst ratio) and returns False;
    the plan layer then keeps the unquantized substrate."""
    v = np.abs(np.asarray(vals, np.float64))
    nz = v > 0
    cnt = nz.sum(axis=-1)
    amax = v.max(axis=-1) if v.size else np.zeros(v.shape[:-1])
    with warnings.catch_warnings():
        # all-padding tiles produce an all-NaN nanmedian slice; masked below
        warnings.simplefilter("ignore", RuntimeWarning)
        med = np.nanmedian(np.where(nz, v, np.nan), axis=-1)
    med = np.where(cnt > 0, med, 1.0)
    ratio = np.where((cnt > 0) & (med > 0), amax / np.maximum(med, 1e-300), 0.0)
    worst = float(ratio.max()) if ratio.size else 0.0
    if worst > bound:
        from .guardrails import HEALTH
        HEALTH.bump("quant_range_violations")
        warnings.warn(
            f"quantization {context}: worst per-tile dynamic range "
            f"amax/rms = {worst:.1f} exceeds {bound:.0f}; keeping the "
            "unquantized value stream (small entries would collapse to "
            "zero on the int8/fp8 grid)", stacklevel=2)
        return False
    return True
