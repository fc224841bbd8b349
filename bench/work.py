"""Compulsory operations and bytes of each cell's step, from its shapes.

These are the work the mathematics requires, whatever implements it:
every input read once, every output written once, each multiply-add
counted as two operations.  Gathers, padding, recomputation and
intermediate round trips are an implementation's own cost and are not
counted, so no implementation can read over 100% of the roofline and
replacing a kernel does not change the yardstick.

The least time of a piece of work is the larger of its operations over the
chip's peak rate and its bytes over the peak bandwidth (``peaks.json``);
``least_time`` says which of the two bounds it.
"""
from __future__ import annotations

import json
from pathlib import Path

PEAKS = Path(__file__).resolve().parent / "peaks.json"


def peaks(device_kind: str) -> dict:
    """The published peaks of ``device_kind``; a kind missing from the
    table is an error, not a default."""
    table = json.loads(PEAKS.read_text())["devices"]
    if device_kind not in table:
        raise KeyError(f"no peaks for device kind {device_kind!r} in "
                       f"{PEAKS.name}; add its published numbers")
    return table[device_kind]


def least_time(flops: float, nbytes: float, peak: dict) -> tuple:
    """``(seconds, bound)``: the least time of the work, and ``"compute"``
    or ``"memory"``, whichever sets it."""
    t_c = flops / peak["flops_per_s"]
    t_m = nbytes / peak["hbm_bytes_per_s"]
    return (t_c, "compute") if t_c >= t_m else (t_m, "memory")


def csr_bytes(m: int, nnz: int, value_bytes: int = 4,
              index_bytes: int = 4) -> int:
    """indptr + column indices + values, once."""
    return (m + 1) * index_bytes + nnz * (index_bytes + value_bytes)


def spmm(m: int, k: int, nnz: int, n: int, x_bytes: int = 4) -> dict:
    """``Y (m, n) = A (m, k) @ X (k, n)`` for a CSR ``A`` of ``nnz`` f32
    values; ``n = 0`` is a 1-D ``x`` (SpMV), counted as ``n = 1``."""
    n = max(n, 1)
    return {"flops": 2 * nnz * n,
            "bytes": csr_bytes(m, nnz) + (k + m) * n * x_bytes}

