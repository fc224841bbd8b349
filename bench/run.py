#!/usr/bin/env python3
"""Run one benchmark cell once, on the chip this process finds.

    python3 bench/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

The cell is an entry of ``workloads`` in ``BENCHMARK.json``.  Its
configuration is ``bench/configs/<config>.json`` and its traffic
``bench/traffic/<traffic>.json``, whose ``driver`` names the module under
``bench/drivers/`` that builds the step; each per-layer metric is read by
``bench/metrics/<name>.py``.  So a new cell, configuration or metric is new
files and new manifest entries.  A driver's ``Cell(config, traffic, seed,
variant)`` builds the step in set-up and gives ``launch(i)``,
``record(i, out)`` and ``check()``, and the attributes ``describe``,
``phases`` (set-up seconds by part), ``plan_s`` and ``roofline_work``
that the metric readers take.

A run: set-up (inputs and weights from the seed, the program's planning,
compilation, the driver's first steps) is timed from process start to the
first timed step, as ``setup_s``.  Then the window: for ``--seconds`` one
caller issues steps, keeping the traffic's ``in_flight`` of them (1 if it
names none) dispatched ahead of the one it waits on with
``block_until_ready``; then it waits for all it sent.  Then, once device
memory's peak is read and the program's state freed, the driver compares what the window
produced with the plain reference.  With ``--trace 1`` the window runs under
``jax.profiler`` and the line carries the per-layer metrics; with
``--trace 0`` it carries the end-to-end ones.

The last line of standard output is one JSON object; the numbers compared
and their limits are its last key, ``checks``, and the last lines of
standard error.  With no TPU, or fewer chips than the cell asks for, the
run exits non-zero and prints no result.  JAX's compilation cache is
``$JAX_COMPILATION_CACHE_DIR`` or else ``.jax_cache/`` in the checkout.
"""
from __future__ import annotations

import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import collections  # noqa: E402
import importlib  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import shutil  # noqa: E402
import statistics  # noqa: E402
import sys  # noqa: E402
import tempfile  # noqa: E402
from pathlib import Path  # noqa: E402

ROOT = Path(__file__).resolve().parent.parent
BENCH = ROOT / "bench"
for _p in (ROOT, ROOT / "src"):
    if str(_p) not in sys.path:
        sys.path.insert(0, str(_p))

import jax  # noqa: E402

from bench import trace as tracing  # noqa: E402
from bench import work  # noqa: E402


class NoChip(RuntimeError):
    pass


def manifest() -> dict:
    return json.loads((ROOT / "BENCHMARK.json").read_text())


def resolve(name: str, spec: dict | None = None) -> tuple:
    """``(cell, config, traffic)`` of the workload ``name``."""
    spec = spec or manifest()
    cells = {w["name"]: w for w in spec["workloads"]}
    if name not in cells:
        raise KeyError(f"no workload {name!r} in BENCHMARK.json")
    cell = cells[name]
    config = json.loads((BENCH / "configs" / f"{cell['config']}.json")
                        .read_text())
    traffic = json.loads((BENCH / "traffic" / f"{cell['traffic']}.json")
                         .read_text())
    return cell, config, traffic


def metrics_for(name: str, kind: str, spec: dict | None = None) -> list:
    """The ``kind`` (``end_to_end`` or ``per_layer``) metrics that the
    workload ``name`` reports."""
    spec = spec or manifest()
    return [m for m in spec[kind]
            if "workloads" not in m or name in m["workloads"]]


def check_device(chips: int):
    devices = jax.devices()
    if devices[0].platform != "tpu":
        raise NoChip(f"no TPU: JAX found {devices[0].platform!r}")
    if len(devices) < chips:
        raise NoChip(f"the cell asks for {chips} chips; JAX found "
                     f"{len(devices)}")
    return devices


def use_compile_cache() -> None:
    path = os.environ.get("JAX_COMPILATION_CACHE_DIR") or str(ROOT /
                                                              ".jax_cache")
    jax.config.update("jax_compilation_cache_dir", path)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)


class Compiles:
    """Counts backend compilations while ``active``."""

    def __init__(self):
        self.active, self.count = False, 0
        jax.monitoring.register_event_duration_secs_listener(self._on)

    def _on(self, event: str, _secs: float, **_kw) -> None:
        if self.active and event == "/jax/core/compile/backend_compile_duration":
            self.count += 1


class Run:
    """What one traced run measured; the per-layer readers
    (``bench/metrics/<name>.py``, each ``read(run)``) take their numbers
    from it: ``None`` where a reader finds nothing to read."""

    def __init__(self, cell, steps, trace, health_after, peak):
        self.cell, self.steps, self.trace = cell, steps, trace
        self.health_after, self.peak = health_after, peak


def window(cell, seconds: float, compiles: Compiles,
           in_flight: int = 1) -> tuple:
    """Steps for ``seconds``, up to ``in_flight`` of them dispatched ahead
    of the one waited on, so that the chip keeps working while the host
    stands still.  Once the time is up nothing more is sent, and the
    window ends when all that was sent has finished.  Returns ``(step
    times, window seconds)``: a step's time runs from the end of the one
    before it (or the window's start) to its own end."""
    ann = jax.profiler.TraceAnnotation
    times, pending = [], collections.deque()
    compiles.active = True
    with ann("window"):
        t_start = t_last = time.perf_counter()
        deadline = t_start + seconds
        i = 0
        while True:
            with ann("dispatch"):
                while len(pending) < in_flight and \
                        time.perf_counter() < deadline:
                    pending.append((i, cell.launch(i)))
                    i += 1
            if not pending:
                break
            j, out = pending.popleft()
            with ann("wait"):
                jax.block_until_ready(out)
            t = time.perf_counter()
            times.append(t - t_last)
            t_last = t
            with ann("record"):
                cell.record(j, out)
    compiles.active = False
    return times, t_last - t_start


def memory_peak(device) -> int | None:
    stats = device.memory_stats()
    return None if not stats else stats.get("peak_bytes_in_use")


def percentile(values, q: float) -> float:
    """The ``q``-th percentile, by linear interpolation between ranks."""
    xs = sorted(values)
    pos = (len(xs) - 1) * q / 100.0
    lo = int(pos)
    hi = min(lo + 1, len(xs) - 1)
    return xs[lo] + (xs[hi] - xs[lo]) * (pos - lo)


def execute(name: str, seed: int, seconds: float, traced: bool, *,
            cell_spec=None, config=None, traffic=None, variant="program",
            chip_check: bool = True) -> dict:
    """One run of the workload ``name``; returns the result object.
    ``cell_spec``, ``config``, ``traffic`` replace what the manifest
    names (tests run small sizes so); ``chip_check=False`` skips the look
    for a TPU, and the peaks then come from the first device kind in the
    table."""
    t_imports = time.perf_counter() - T_START
    spec = manifest()
    if cell_spec is None:
        cell_spec, config, traffic = resolve(name, spec)
    devices = check_device(cell_spec["chips"]) if chip_check \
        else jax.devices()
    t_device = time.perf_counter() - T_START
    device = devices[0]
    if chip_check:
        use_compile_cache()
    compiles = Compiles()
    from repro import api
    os.environ.pop("REPRO_THRESHOLDS", None)
    kind = device.device_kind if chip_check else next(iter(json.loads(
        work.PEAKS.read_text())["devices"]))
    peak = work.peaks(kind)

    driver = importlib.import_module(f"bench.drivers.{traffic['driver']}")
    cell = driver.Cell(config, traffic, seed, variant=variant)
    print(f"cell {name}: {json.dumps(cell.describe)}", file=sys.stderr,
          flush=True)

    trace_dir = tempfile.mkdtemp(prefix="bench-trace-") if traced else None
    setup_s = time.perf_counter() - T_START
    phases = ", ".join(f"{k} {v:.3f} s" for k, v in cell.phases.items())
    print(f"setup: {setup_s:.3f} s; imports done at {t_imports:.3f} s, "
          f"device at {t_device:.3f} s; then {phases}", file=sys.stderr,
          flush=True)
    if traced:
        jax.profiler.start_trace(trace_dir)
    try:
        times, window_s = window(cell, seconds, compiles,
                                 traffic.get("in_flight", 1))
    finally:
        if traced:
            jax.profiler.stop_trace()
    mem = memory_peak(device)
    health_after = api.health()
    summary = None
    if traced:
        try:
            summary = tracing.reduce(tracing.load(
                tracing.find_xplane(trace_dir)))
        finally:
            shutil.rmtree(trace_dir, ignore_errors=True)
    print(f"window: {len(times)} steps in {window_s:.3f} s; "
          f"{compiles.count} compilations inside it", file=sys.stderr,
          flush=True)

    numbers, answers, wrong = cell.check()

    metrics = {}
    if traced:
        run = Run(cell, len(times), summary, health_after, peak)
        for m in metrics_for(name, "per_layer", spec):
            reader = importlib.import_module(f"bench.metrics.{m['name']}")
            value = reader.read(run)
            if value is not None:
                metrics[m["name"]] = {"value": value, "unit": m["unit"]}
    else:
        e2e = {"setup_s": setup_s,
               "step_ms": 1e3 * window_s / len(times),
               "step_ms_p95": 1e3 * percentile(times, 95),
               "peak_hbm_gib": None if mem is None else mem / 2**30}
        for m in metrics_for(name, "end_to_end", spec):
            if e2e.get(m["name"]) is not None:
                metrics[m["name"]] = {"value": e2e[m["name"]],
                                      "unit": m["unit"]}
    med = statistics.median(times)
    print(f"step times: median {1e3 * med:.4f} ms, "
          f"p95 {1e3 * percentile(times, 95):.4f} ms, "
          f"max {1e3 * max(times):.4f} ms over {len(times)}; "
          f"{sum(t > 1.5 * med for t in times)} over 1.5x the median",
          file=sys.stderr, flush=True)
    result = {
        "correct": wrong == 0 and compiles.count == 0,
        "attempted": answers,
        "failed": wrong,
        "metrics": metrics,
        "device": {"platform": device.platform, "kind": device.device_kind,
                   "count": len(devices), "memory_peak_bytes": mem},
    }
    if traced:
        result["device"].update(busy_s=summary.busy_s,
                                window_s=summary.window_s)
        result["breakdown"] = {"device_ops": summary.device_ops,
                               "idle_gaps": summary.idle_gaps}
    result["checks"] = {k: {"value": v, "limit": lim}
                        for k, (v, lim) in numbers.items()}
    result["checks"]["window_compilations"] = {"value": compiles.count,
                                               "limit": 0}
    return result


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)
    try:
        result = execute(args.workload, args.seed, args.seconds,
                         bool(args.trace))
    except NoChip as e:
        print(f"bench/run.py: {e}", file=sys.stderr)
        return 2
    for k, c in result["checks"].items():
        print(f"check {k}: {c['value']!r} (limit {c['limit']!r})",
              file=sys.stderr)
    sys.stderr.flush()
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
