"""§Roofline table generator: reads results/dryrun/*.json artifacts and
renders the per-(arch x cell) roofline table to results/roofline.md +
CSV rows for benchmarks.run."""
from __future__ import annotations

import glob
import json
import os

from .common import csv_row

DRYRUN_DIR = os.path.join(os.getcwd(), "results", "dryrun")


def load_artifacts(mesh: str = "single") -> list[dict]:
    arts = []
    for path in sorted(glob.glob(os.path.join(DRYRUN_DIR, f"*__{mesh}.json"))):
        with open(path) as f:
            arts.append(json.load(f))
    return arts


def render_table(arts: list[dict]) -> str:
    lines = [
        "| arch | cell | chips | compute s | memory s | collective s | "
        "bottleneck | MODEL_FLOPS/FLOPs | wire GB/dev | bytes/dev |",
        "|---|---|---|---|---|---|---|---|---|---|",
    ]
    for a in arts:
        if a.get("status") == "skipped":
            lines.append(f"| {a['arch']} | {a['cell']} | — | — | — | — | "
                         f"SKIP | — | — | — |")
            continue
        r = a["roofline"]
        mem = a.get("memory_analysis", {})
        dev_bytes = (mem.get("argument_size_in_bytes") or 0)
        lines.append(
            f"| {a['arch']} | {a['cell']} | {a['chips']} "
            f"| {r['compute_s']:.3e} | {r['memory_s']:.3e} "
            f"| {r['collective_s']:.3e} | {r['bottleneck']} "
            f"| {r['useful_ratio']:.2f} "
            f"| {r['wire_bytes_per_dev']/1e9:.1f} "
            f"| {dev_bytes/1e9:.2f}e9 |")
    return "\n".join(lines)


def run():
    rows = []
    for mesh in ("single", "multi"):
        arts = load_artifacts(mesh)
        if not arts:
            continue
        table = render_table(arts)
        os.makedirs("results", exist_ok=True)
        with open(f"results/roofline_{mesh}.md", "w") as f:
            f.write(table + "\n")
        ok = [a for a in arts if a.get("status") == "ok"]
        for a in ok:
            r = a["roofline"]
            dom = max(r["compute_s"], r["memory_s"], r["collective_s"])
            # modeled bytes + arithmetic intensity ride next to the wall-
            # clock term so traffic regressions (and wins like the fused
            # NB kernels, DESIGN.md §6) are visible as AI movement
            derived = f"bottleneck={r['bottleneck']}"
            flops = r.get("flops_global", 0.0)
            byts = r.get("bytes_global", 0.0)
            if byts:
                derived += (f"_bytes={byts:.3e}"
                            f"_ai={flops / byts:.2f}")
            rows.append(csv_row(
                f"roofline/{a['arch']}/{a['cell']}/{mesh}", dom * 1e6,
                derived))
        rows.append(csv_row(f"roofline/{mesh}_cells_ok", 0.0, str(len(ok))))
    return rows


def main():
    for r in run():
        print(r)


if __name__ == "__main__":
    main()
