"""The sparse product's share of its roofline: the least time of its
compulsory work (``bench/work.py``: 2 nnz N operations; the CSR, X and Y
each once) over the device's busy time per step in the traced window."""

from bench import work


def read(run):
    if not run.steps or run.trace.busy_s <= 0:
        return None
    least, _ = work.least_time(run.cell.roofline_work["flops"],
                               run.cell.roofline_work["bytes"], run.peak)
    return 100.0 * least / (run.trace.busy_s / run.steps)
