"""Model FLOPs utilisation of the train step: the step's model FLOPs
(``bench/lm_work.py``: six per matmul parameter and token for the attention
projections, the FFN's nonzeros and the head, plus the causal attention)
over the chip's peak rate (``peaks.json``) times the device's busy time
per step in the traced window."""


def read(run):
    flops = run.cell.roofline_work.get("model_flops")
    if not flops or not run.steps or run.trace.busy_s <= 0:
        return None
    return 100.0 * flops / (run.peak["flops_per_s"]
                            * run.trace.busy_s / run.steps)
