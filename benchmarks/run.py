"""Benchmark entry point: one function per paper table/figure.
Prints ``name,us_per_call,derived`` CSV. ``--full`` uses the paper-sized
R-MAT suite (slower); default is the reduced CI suite; ``--quick`` is the
CI smoke mode — tiny shapes, single-iteration timing, Pallas in interpret
mode — meant to prove every benchmark entry point still runs, not to
measure anything.  ``--json`` additionally persists each suite's rows —
wall time, modeled HBM bytes, arithmetic intensity — to
``BENCH_<suite>.json`` at the repo root for machine consumption (perf
dashboards, regression diffs)."""
from __future__ import annotations

import argparse
import json
import pathlib
import re
import sys
import traceback

_REPO_ROOT = pathlib.Path(__file__).resolve().parent.parent


def _parse_row(row: str) -> dict:
    """Structure one ``name,us_per_call,derived`` CSV row: the derived
    column's ``bytes=``/``ai=`` fields (see ``common.bytes_derived``) are
    lifted into typed keys when present."""
    name, us, derived = row.split(",", 2)
    rec: dict = {"name": name, "us_per_call": float(us), "derived": derived}
    mb = re.search(r"bytes=(\d+)", derived)
    if mb:
        rec["modeled_bytes"] = int(mb.group(1))
    ma = re.search(r"ai=([0-9.eE+-]+)", derived)
    if ma:
        rec["arithmetic_intensity"] = float(ma.group(1))
    return rec


def _write_json(suite: str, rows: list) -> pathlib.Path:
    path = _REPO_ROOT / f"BENCH_{suite}.json"
    payload = {"suite": suite, "rows": [_parse_row(r) for r in rows]}
    path.write_text(json.dumps(payload, indent=2) + "\n")
    return path


def _use_compile_cache() -> None:
    """JAX's persistent compile cache: ``$JAX_COMPILATION_CACHE_DIR`` when
    set (JAX reads it itself), else ``.jax_cache/`` in the checkout — a fixed
    path, since the path is part of the cache key."""
    import os
    if "JAX_COMPILATION_CACHE_DIR" not in os.environ:
        import jax
        jax.config.update("jax_compilation_cache_dir",
                          str(_REPO_ROOT / ".jax_cache"))


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--full", action="store_true")
    ap.add_argument("--quick", action="store_true",
                    help="smoke mode: tiny suites, 1 timing iteration")
    ap.add_argument("--only", default=None,
                    help="comma-separated subset of benchmark names")
    ap.add_argument("--json", action="store_true",
                    help="persist each suite's rows to BENCH_<suite>.json "
                         "at the repo root")
    args = ap.parse_args()
    _use_compile_cache()

    from . import common
    if args.quick:
        common.set_quick(True)

    from . import (adaptive_strategy, attention, csc_ablation,
                   fig6_kernel_perf, guardrails, moe_dispatch, plan_cache,
                   roofline, sddmm_chain, serving, sharded_spmm,
                   vsr_ablation)

    benches = {
        "plan_cache": lambda: plan_cache.run(args.full),
        "vsr_ablation": lambda: vsr_ablation.run(args.full),
        "csc_ablation": lambda: csc_ablation.run(args.full),
        "fig6_kernel_perf": lambda: fig6_kernel_perf.run(args.full),
        "adaptive_strategy": lambda: adaptive_strategy.run(args.full),
        "moe_dispatch": moe_dispatch.run,
        "roofline": roofline.run,
        "sharded_spmm": lambda: sharded_spmm.run(args.full),
        "sddmm_chain": lambda: sddmm_chain.run(args.full),
        "attention": lambda: attention.run(args.full),
        "serving": lambda: serving.run(args.full),
        "guardrails": lambda: guardrails.run(args.full),
    }
    selected = (args.only.split(",") if args.only else list(benches))
    print("name,us_per_call,derived")
    failed = []
    for name in selected:
        try:
            rows = list(benches[name]())
            for row in rows:
                print(row, flush=True)
            if args.json:
                path = _write_json(name, rows)
                print(f"# wrote {path}", flush=True)
        except Exception:
            traceback.print_exc()
            failed.append(name)
    if failed:
        print(f"FAILED: {failed}", file=sys.stderr)
        sys.exit(1)


if __name__ == "__main__":
    main()
