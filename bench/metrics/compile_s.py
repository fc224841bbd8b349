"""Seconds of XLA backend compilation in the run, as the program's
``jax.monitoring`` listener recorded them (``repro.api.health()
["compile"]["seconds"]``): JAX times a compilation around its persistent
cache, so a cache hit counts its read and a miss its compile and cache
write.  Read after the window, in which nothing may compile (the check's
``window_compilations``), so all of it is set-up's."""


def read(run):
    compiled = run.health_after.get("compile")
    if not compiled or not compiled["count"]:
        return None
    return compiled["seconds"]
