"""The sparse FFN's share of its roofline: the least time of the compulsory
work of every sparse-FFN product of a step (``bench/lm_work.py``: the
forward, input-gradient and value-gradient products of each pruned matrix
of each layer) over the device time per step of the kernels that run them,
``repro_vsr_fused`` and ``repro_sddmm``, in the traced window."""

from bench import lm_work

KERNELS = ("repro_vsr_fused", "repro_sddmm")


def read(run):
    items = run.cell.roofline_work.get("sparse")
    busy = sum(run.trace.op_s.get(k, 0.0) for k in KERNELS)
    if not items or not run.steps or busy <= 0:
        return None
    return 100.0 * lm_work.least_time(items, run.peak) / (busy / run.steps)
