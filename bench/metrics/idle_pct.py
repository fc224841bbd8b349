"""Share of the traced window in which no operation ran on the device:
1 - (union of the device operations' intervals) / window."""


def read(run):
    return 100.0 * run.trace.idle_share
