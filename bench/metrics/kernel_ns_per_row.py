"""Device nanoseconds per gathered row: the device's busy time per step in
the traced window over the rows one call of the step gathers, as the
program counted them from the visit schedule it bound
(``repro.api.health()["work"][...]["rows_gathered"]``: the Pallas row
gather's tile loads × tile × column blocks, or the elements XLA gathers
from ``x`` for SpMV).  These cells' steps bind one kernel once; where a
step binds several, their rows add up."""


def read(run):
    work = run.health_after.get("work")
    if not work or not run.steps or run.trace.busy_s <= 0:
        return None
    rows = sum(w["rows_gathered"] for w in work.values())
    return 1e9 * run.trace.busy_s / run.steps / rows if rows else None
