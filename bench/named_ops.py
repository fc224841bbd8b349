#!/usr/bin/env python3
"""Device time of a traced window by the name the program gave each op.

    python3 bench/named_ops.py --workload <cell> --seed <n> --seconds <s> \\
        [--scale <k>] [--out <dir>]

A Pallas kernel is named by its ``name=`` (``repro_<kernel>``), which the
compiler keeps as the custom call's HLO instruction name, so a device trace
shows it as is.  An XLA op is named by the innermost ``repro`` scope
(``jax.named_scope``) of its ``op_name`` metadata; the trace does not carry
that metadata, so ``op_names`` reads it from the compiled program's HLO
text (``compiled.as_text()``), whose instruction names are the trace's.  An
op that neither names is ``other:<op>``.

The command runs one cell's window under ``jax.profiler`` as
``bench/run.py --trace 1`` does (``--scale`` shrinks the configuration's
graph) and prints one JSON object: the device time by program name, the
share of that time that carries a ``repro`` name, the program's spans that
opened inside the ``window`` annotation (none is expected: the step is one
compiled call), and the program's span, compile and work records.  With
``--out`` it also keeps the trace, the instruction-to-``op_name`` map and
the ``repro.api.health()`` snapshot there.
"""
from __future__ import annotations

import re
import sys
from collections import defaultdict
from pathlib import Path

ROOT = Path(__file__).resolve().parent.parent
for _p in (ROOT, ROOT / "src"):
    if str(_p) not in sys.path:
        sys.path.insert(0, str(_p))

from bench import trace  # noqa: E402

PREFIX = "repro"
_INSTR = re.compile(r'^\s*(?:ROOT\s+)?%([^\s=]+) = .*?metadata=\{[^}]*?'
                    r'op_name="([^"]*)"', re.M)


def op_names(hlo_text: str) -> dict:
    """``{instruction name: op_name}`` of every instruction of an HLO
    module's text that carries ``op_name`` metadata."""
    return dict(_INSTR.findall(hlo_text))


def program_name(instruction: str, op_name: str | None = None) -> str:
    """The program's name of one op: a ``repro_<kernel>`` instruction name
    (its ``.<n>`` suffix dropped), else the innermost ``repro`` scope of its
    ``op_name``, else ``other:<op>``."""
    base = trace.op_name(instruction)
    if base.startswith(PREFIX):
        return base
    for part in reversed((op_name or "").split("/")):
        if part.startswith(PREFIX):
            return part
    return f"other:{base}"


def attribute(profile, names: dict) -> list:
    """``[[program name, seconds], ...]``, most first: the device time of
    the traced window's ops (clipped to the window, summed over devices)
    under ``program_name``; ``names`` maps instruction names to
    ``op_name`` (``op_names``)."""
    windows = [(s, e) for n, s, e in trace._host_spans(profile)
               if n == "window"]
    if not windows:
        raise ValueError("the trace holds no 'window' annotation")
    w0, w1 = windows[0]
    out = defaultdict(float)
    for plane in profile.planes:
        if not plane.name.startswith(trace.DEVICE_PREFIX):
            continue
        for line in plane.lines:
            if line.name != trace.OPS_LINE:
                continue
            for ev in line.events:
                s, e = max(ev.start_ns, w0), min(ev.start_ns + ev.duration_ns,
                                                 w1)
                if e > s:
                    instr = ev.name.split(" = ", 1)[0].lstrip("%")
                    out[program_name(instr, names.get(instr))] += e - s
    return [[n, ns * 1e-9] for n, ns in sorted(out.items(),
                                                key=lambda kv: -kv[1])]


def named_share(ops: list) -> float:
    """Share of the attributed time that carries a ``repro`` name."""
    total = sum(t for _, t in ops)
    named = sum(t for n, t in ops if n.startswith(PREFIX))
    return named / total if total else 0.0


def spans_in_window(profile) -> list:
    """The program's host spans (``repro*``) that overlap the window."""
    host = [(ev.name, ev.start_ns, ev.start_ns + ev.duration_ns)
            for plane in profile.planes if plane.name == trace.HOST_PLANE
            for line in plane.lines for ev in line.events]
    windows = [(s, e) for n, s, e in host if n == "window"]
    if not windows:
        raise ValueError("the trace holds no 'window' annotation")
    w0, w1 = windows[0]
    return sorted({n for n, s, e in host
                   if n.startswith(PREFIX) and s < w1 and e > w0})


def main(argv=None) -> int:
    import argparse
    import importlib
    import json
    import shutil
    import tempfile

    import jax

    from bench import run
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--scale", type=int, default=None)
    ap.add_argument("--out", default=None)
    args = ap.parse_args(argv)
    cell_spec, config, traffic = run.resolve(args.workload)
    if args.scale is not None:
        config = dict(config, scale=args.scale)
    run.check_device(cell_spec["chips"])
    run.use_compile_cache()
    compiles = run.Compiles()
    from repro import api
    driver = importlib.import_module(f"bench.drivers.{traffic['driver']}")
    cell = driver.Cell(config, traffic, args.seed)
    names = op_names(cell.fn.lower(cell.xs[0]).compile().as_text())
    trace_dir = tempfile.mkdtemp(prefix="named-ops-")
    jax.profiler.start_trace(trace_dir)
    try:
        times, _ = run.window(cell, args.seconds, compiles,
                              traffic.get("in_flight", 1))
    finally:
        jax.profiler.stop_trace()
    health = api.health()
    path = trace.find_xplane(trace_dir)
    profile = trace.load(path)
    ops = attribute(profile, names)
    result = {"workload": args.workload, "scale": config["scale"],
              "steps": len(times), "window_compilations": compiles.count,
              "named_ops": ops, "named_share": named_share(ops),
              "spans_in_window": spans_in_window(profile),
              "busy_s": trace.reduce(profile).busy_s,
              "health": {k: health[k] for k in ("spans", "compile", "work")
                         if k in health}}
    if args.out:
        out = Path(args.out)
        out.mkdir(parents=True, exist_ok=True)
        shutil.copy(path, out / "trace.xplane.pb")
        (out / "op_names.json").write_text(json.dumps(names, indent=0))
        (out / "health.json").write_text(json.dumps(health, indent=1))
    shutil.rmtree(trace_dir, ignore_errors=True)
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
