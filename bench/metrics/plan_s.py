"""Host seconds the program spends planning in set-up: for a graph,
``repro.api.sparse`` and the first trace of the step, in which the program
builds its plan's substrate lazily; for a language model, the FFN masks'
kernel prep and the model's construction.  Host clock around those calls."""


def read(run):
    return run.cell.plan_s
