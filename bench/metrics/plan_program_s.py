"""Host seconds the program itself spends planning: the self seconds of
every ``repro.plan*`` span of ``repro.api.health()["spans"]`` (plan, the
plan cache, matrix statistics, pattern fingerprints, substrate builds and
the prep hooks with their window and visit schedules), from process start
to the end of the window.  No program span opens in the window (the step
is one compiled call), so all of it is set-up's.  ``plan_s`` less this is
JAX's tracing and lowering of the step."""


def read(run):
    spans = run.health_after.get("spans")
    if not spans:
        return None
    return sum(r["self_s"] for name, r in spans.items()
               if name.startswith("repro.plan"))
