"""Guardrail events from process start to the end of the window: the sum
of the ``kernel_reroute:``, ``breaker_skip:`` and ``demote:`` counters of
``repro.api.health()``.  The program decides most of them while it traces,
in set-up, and what it decides there is what the window runs."""

KINDS = ("kernel_reroute:", "breaker_skip:", "demote:")


def read(run):
    counters = run.health_after["counters"]
    return sum(v for k, v in counters.items() if k.startswith(KINDS))
