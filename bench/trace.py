"""Reduce a ``jax.profiler`` trace of one measured window to numbers.

The harness wraps the window in a host annotation ``window`` and every
step in ``step``, with ``dispatch``, ``wait`` and ``record`` inside it
(``run.py``).  From the ``.xplane.pb`` this module takes:

* the window: the ``window`` annotation's interval on the host plane;
* device busy time: the union of the intervals of the operations on each
  device's ``XLA Ops`` line, clipped to the window, averaged over devices;
* the device time of each operation, by its HLO instruction name without
  the leading ``%`` and the trailing ``.<n>`` (the trace names an operation
  by its whole HLO text), and the operations that took most time;
* the device time of the Pallas kernels: the operations whose HLO is a
  ``tpu_custom_call``, whatever the kernel is called;
* the idle gaps (the window less the busy union), each labelled by the
  innermost host annotation that covers its middle, or ``host`` where none
  does.

Host and device events share the trace's clock, so the two can be matched.
"""
from __future__ import annotations

import dataclasses
import glob
import os
from collections import defaultdict

DEVICE_PREFIX = "/device:TPU:"
OPS_LINE = "XLA Ops"
HOST_PLANE = "/host:CPU"
ANNOTATIONS = ("window", "step", "dispatch", "wait", "record")
PALLAS = 'custom_call_target="tpu_custom_call"'


@dataclasses.dataclass
class Summary:
    window_s: float
    busy_s: float                      # averaged over devices
    op_s: dict                         # name: seconds, summed over devices
    pallas_s: float                    # summed over devices
    devices: int
    device_ops: list                   # [[name, seconds], ...] most first
    idle_gaps: list                    # [[label, seconds], ...] longest first

    @property
    def idle_share(self) -> float:
        return 1.0 - self.busy_s / self.window_s if self.window_s else 0.0


def op_name(hlo: str) -> str:
    """``%fusion.12 = f32[...] fusion(...)`` -> ``fusion``."""
    name = hlo.split(" = ", 1)[0].lstrip("%")
    base, _, suffix = name.rpartition(".")
    return base if base and suffix.isdigit() else name


def find_xplane(trace_dir: str) -> str:
    paths = sorted(glob.glob(os.path.join(trace_dir, "plugins", "profile",
                                          "*", "*.xplane.pb")))
    if not paths:
        raise FileNotFoundError(f"no .xplane.pb under {trace_dir}")
    return paths[-1]


def load(path: str):
    from jax.profiler import ProfileData
    return ProfileData.from_file(path)


def _union(intervals):
    """Sorted, merged ``[start, end]`` list of the given intervals."""
    out = []
    for s, e in sorted(intervals):
        if out and s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], e)
        else:
            out.append([s, e])
    return out


def _host_spans(profile):
    """(name, start_ns, end_ns) of the harness's annotations on the host."""
    spans = []
    for plane in profile.planes:
        if plane.name != HOST_PLANE:
            continue
        for line in plane.lines:
            for ev in line.events:
                if ev.name in ANNOTATIONS:
                    spans.append((ev.name, ev.start_ns,
                                  ev.start_ns + ev.duration_ns))
    return spans


def _label(spans, t: float) -> str:
    covering = [(e - s, name) for name, s, e in spans if s <= t <= e]
    return min(covering)[1] if covering else "host"


def reduce(profile, top: int = 10) -> Summary:
    """Summary of the traced window (see the module docstring)."""
    spans = _host_spans(profile)
    windows = [(s, e) for name, s, e in spans if name == "window"]
    if not windows:
        raise ValueError("the trace holds no 'window' annotation")
    w0, w1 = windows[0]
    busy_total, pallas_ns, devices = 0.0, 0.0, 0
    op_ns = defaultdict(float)
    gaps = []
    for plane in profile.planes:
        if not plane.name.startswith(DEVICE_PREFIX):
            continue
        intervals = []
        for line in plane.lines:
            if line.name != OPS_LINE:
                continue
            for ev in line.events:
                s, e = max(ev.start_ns, w0), min(ev.start_ns + ev.duration_ns,
                                                 w1)
                if e <= s:
                    continue
                intervals.append((s, e))
                op_ns[op_name(ev.name)] += e - s
                if PALLAS in ev.name:
                    pallas_ns += e - s
        devices += 1
        merged = _union(intervals)
        busy_total += sum(e - s for s, e in merged)
        edges = [w0] + [t for iv in merged for t in iv] + [w1]
        for s, e in zip(edges[::2], edges[1::2]):
            if e > s:
                gaps.append((e - s, _label(spans, (s + e) / 2)))
    if devices == 0:
        raise ValueError(f"the trace holds no {DEVICE_PREFIX}* plane")
    ops = sorted(op_ns.items(), key=lambda kv: -kv[1])[:top]
    gaps.sort(key=lambda g: -g[0])
    return Summary(
        window_s=(w1 - w0) * 1e-9,
        busy_s=busy_total / devices * 1e-9,
        op_s={name: ns * 1e-9 for name, ns in op_ns.items()},
        pallas_s=pallas_ns * 1e-9,
        devices=devices,
        device_ops=[[name, ns * 1e-9] for name, ns in ops],
        idle_gaps=[[label, ns * 1e-9] for ns, label in gaps[:top]])
