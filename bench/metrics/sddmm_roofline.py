"""The value gradient's share of its roofline: the least time of the
compulsory work of every SDDMM of a step (``bench/lm_work.py``,
``value_grad``) over the device time per step of ``repro_sddmm`` in the
traced window."""

from bench import lm_work


def read(run):
    items = run.cell.roofline_work.get("sparse")
    busy = run.trace.op_s.get("repro_sddmm", 0.0)
    if not items or not run.steps or busy <= 0:
        return None
    least = lm_work.least_time(items, run.peak, ("value_grad",))
    return 100.0 * least / (busy / run.steps)
