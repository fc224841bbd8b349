"""bench/trace.py on a hand-written trace, whose every number is known, and
on a small trace recorded on a TPU v5 lite (``data/spmm_small.xplane.pb``:
the SpMM cell at scale 12 under ``bench/run.py --trace 1``)."""
import sys
from pathlib import Path

import pytest

ROOT = Path(__file__).resolve().parents[2]
sys.path.insert(0, str(ROOT))

from bench import trace  # noqa: E402

RECORDED = Path(__file__).resolve().parent / "data" / "spmm_small.xplane.pb"

# times in microseconds from the host line's start
HOST = [("window", 0, 100), ("step", 5, 40), ("dispatch", 5, 9),
        ("wait", 10, 40), ("step", 50, 95), ("wait", 60, 95)]
KERNEL = ('%_vsr_fused_call.{} = f32[8,128] custom-call(f32[8,128] %x), '
          'custom_call_target=\\"tpu_custom_call\\"')
DEVICE = [(KERNEL.format(1), 20, 30), ("%fusion.1 = f32[8] fusion()", 25, 35),
          (KERNEL.format(2), 70, 90), ("%copy.2 = f32[8] copy()", 98, 110)]


def _xspace(host, device):
    def plane(pid, name, line, events):
        names = sorted({e[0] for e in events})
        ids = {n: i + 1 for i, n in enumerate(names)}
        evs = "".join(
            f"events {{ metadata_id: {ids[n]} offset_ps: {s * 1000000} "
            f"duration_ps: {(e - s) * 1000000} }}\n" for n, s, e in events)
        meta = "".join(f'event_metadata {{ key: {i} value {{ id: {i} '
                       f'name: "{n}" }} }}\n' for n, i in ids.items())
        return (f'planes {{ id: {pid} name: "{name}" lines {{ id: 1 '
                f'name: "{line}" timestamp_ns: 1000 {evs} }} {meta} }}\n')
    from jax.profiler import ProfileData
    text = (plane(1, "/host:CPU", "python", host)
            + plane(2, "/device:TPU:0", "XLA Ops", device))
    return ProfileData.from_serialized_xspace(
        ProfileData.text_proto_to_serialized_xspace(text))


def test_hand_written_trace():
    s = trace.reduce(_xspace(HOST, DEVICE))
    assert s.devices == 1
    assert s.window_s == pytest.approx(100e-6)
    # busy: [20, 35] + [70, 90] + [98, 100] (clipped to the window)
    assert s.busy_s == pytest.approx(37e-6)
    assert s.idle_share == pytest.approx(0.63)
    assert s.pallas_s == pytest.approx(30e-6)
    assert s.op_s["copy"] == pytest.approx(2e-6)
    assert s.device_ops[0] == ["_vsr_fused_call", pytest.approx(30e-6)]
    # gaps: [0,20] wait (innermost at 10), [35,70] host at 52.5 -> step,
    # [90,98] wait, longest first
    labels = [(g[0], round(g[1] * 1e6, 6)) for g in s.idle_gaps]
    assert labels == [("step", 35.0), ("wait", 20.0), ("wait", 8.0)]


def test_trace_without_a_window_is_refused():
    with pytest.raises(ValueError):
        trace.reduce(_xspace(HOST[1:], DEVICE))


def test_op_names_drop_the_hlo_text_and_suffix():
    assert trace.op_name("%fusion.12 = f32[4] fusion(f32[4] %a)") == "fusion"
    assert trace.op_name("%jvp_jit__chain_apply_call__.2 = f32[2] x") == \
        "jvp_jit__chain_apply_call__"
    assert trace.op_name("copy-start") == "copy-start"


def test_union_merges_overlaps():
    assert trace._union([(5, 7), (1, 3), (2, 4), (7, 9)]) == [[1, 4], [5, 9]]


def test_recorded_chip_trace():
    s = trace.reduce(trace.load(str(RECORDED)))
    assert s.devices == 1
    assert 0 < s.busy_s <= s.window_s
    assert 0 <= s.idle_share < 1
    assert s.pallas_s > 0 and s.op_s["_vsr_fused_call"] == s.pallas_s
    assert sum(t for _, t in s.device_ops) <= sum(s.op_s.values()) + 1e-12
    assert {g[0] for g in s.idle_gaps} <= set(trace.ANNOTATIONS) | {"host"}
    assert len(s.device_ops) <= 10 and len(s.idle_gaps) <= 10
