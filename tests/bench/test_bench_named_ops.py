"""bench/named_ops.py and the readers of the program's own records
(``plan_program_s``, ``compile_s``, ``kernel_ns_per_row``): on hand-written
traces and snapshots, and on the SpMV cell at scale 12 recorded on a TPU v5
lite (``data/spmv_small.*``: ``bench/named_ops.py --workload
spmv.graph500-s20 --seed 3100000001 --seconds 0.05 --scale 12 --out``)."""
import json
import sys
from pathlib import Path

import pytest

ROOT = Path(__file__).resolve().parents[2]
sys.path.insert(0, str(ROOT))

from bench import named_ops, run, trace  # noqa: E402
from bench.metrics import compile_s, kernel_ns_per_row  # noqa: E402
from bench.metrics import plan_program_s  # noqa: E402

DATA = Path(__file__).resolve().parent / "data"
RECORDED = DATA / "spmv_small.xplane.pb"
RECORDED_STEPS = 129      # the window's steps, from the recording's output

HLO = """\
ENTRY %main {
  %fusion.3 = f32[7168]{0} fusion(%a, %b), kind=kCustom, calls=%fc, \
metadata={op_name="jit(f)/jit(_spmv_fused_call)/repro.spmv.gather_x/jit(_take)/gather" \
stack_frame_id=8}, backend_config={}
  %repro_spmv_fused.1 = f32[1024,1]{1,0} custom-call(%c), \
custom_call_target="tpu_custom_call", metadata={op_name="jit(f)/jit(_spmv_fused_call)/repro_spmv_fused/pallas_call"}
  ROOT %reduce = f32[1024]{0} reduce(%repro_spmv_fused.1, %z), \
metadata={op_name="jit(f)/jit(_spmv_fused_call)/repro_spmv_fused/pallas_call"}
  %copy.2 = f32[8]{0} copy(%d)
}
"""


def _xspace(host, device):
    """An XSpace of one host line and one device ``XLA Ops`` line; times in
    microseconds."""
    def plane(pid, name, line, events):
        ids = {n: i + 1 for i, n in enumerate(sorted({e[0] for e in events}))}
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


HOST = [("repro.plan", 0, 8), ("window", 10, 110), ("step", 12, 60),
        ("repro.execute.trace", 100, 130)]
DEVICE = [("%fusion.3 = f32[7168] fusion()", 5, 40),
          ('%repro_spmv_fused.1 = f32[1024,1] custom-call(), '
           'custom_call_target=\\"tpu_custom_call\\"', 40, 70),
          ("%reduce = f32[1024] reduce()", 70, 72),
          ("%copy.2 = f32[8] copy()", 80, 90)]


def test_op_names_come_from_the_hlo_text():
    names = named_ops.op_names(HLO)
    assert set(names) == {"fusion.3", "repro_spmv_fused.1", "reduce"}
    assert names["fusion.3"].endswith("repro.spmv.gather_x/jit(_take)/gather")


@pytest.mark.parametrize("instr,op_name,want", [
    ("repro_vsr_fused.1", None, "repro_vsr_fused"),
    ("repro_vsr_fused", "jit(f)/other", "repro_vsr_fused"),
    ("fusion", "jit(f)/repro.spmm.unpad/jit(g)/repro.spmv.gather_x/gather",
     "repro.spmv.gather_x"),
    ("reduce", "jit(f)/repro_spmv_fused/pallas_call", "repro_spmv_fused"),
    ("copy-start", None, "other:copy-start"),
    ("fusion.7", "jit(f)/mul", "other:fusion"),
])
def test_program_name(instr, op_name, want):
    assert named_ops.program_name(instr, op_name) == want


def test_hand_written_trace():
    profile = _xspace(HOST, DEVICE)
    ops = named_ops.attribute(profile, named_ops.op_names(HLO))
    got = {n: round(t * 1e6, 6) for n, t in ops}
    # the gather is clipped to the window's start (10 us)
    assert got == {"repro.spmv.gather_x": 30.0, "repro_spmv_fused": 32.0,
                   "other:copy": 10.0}
    assert [n for n, _ in ops] == ["repro_spmv_fused", "repro.spmv.gather_x",
                                   "other:copy"]
    assert named_ops.named_share(ops) == pytest.approx(62 / 72)
    # repro.plan ends before the window; repro.execute.trace overlaps it
    assert named_ops.spans_in_window(profile) == ["repro.execute.trace"]


def test_a_trace_without_a_window_is_refused():
    with pytest.raises(ValueError):
        named_ops.attribute(_xspace(HOST[:1], DEVICE), {})
    with pytest.raises(ValueError):
        named_ops.spans_in_window(_xspace(HOST[:1], DEVICE))


def _run(health, profile, steps):
    return run.Run(None, steps, trace.reduce(profile), health, None)


def test_readers_on_a_hand_written_snapshot():
    health = {
        "counters": {}, "breakers": {},
        "spans": {
            "repro.plan": {"count": 1, "total_s": 3.0, "self_s": 0.5},
            "repro.plan.visits": {"count": 2, "total_s": 1.5, "self_s": 1.5},
            "repro.execute.trace": {"count": 1, "total_s": 9.0,
                                    "self_s": 4.0}},
        "compile": {"count": 3, "seconds": 12.5, "cache_hits": 1,
                    "cache_misses": 2, "retrieval_s": 0.5,
                    "by_function": {}},
        "work": {"repro_spmv_fused:abc:n1": {"rows_gathered": 1000}}}
    r = _run(health, _xspace(HOST, DEVICE), steps=2)
    assert plan_program_s.read(r) == pytest.approx(2.0)
    assert compile_s.read(r) == pytest.approx(12.5)
    # busy [10, 72] + [80, 90] = 72 us over 2 steps and 1000 rows
    assert kernel_ns_per_row.read(r) == pytest.approx(36.0)


@pytest.mark.parametrize("health", [
    {"counters": {}, "breakers": {}},
    {"counters": {}, "breakers": {}, "spans": {}, "work": {},
     "compile": {"count": 0, "seconds": 0.0}},
], ids=["before-the-records", "empty-records"])
def test_readers_find_nothing_to_read(health):
    r = _run(health, _xspace(HOST, DEVICE), steps=2)
    assert plan_program_s.read(r) is None
    assert compile_s.read(r) is None
    assert kernel_ns_per_row.read(r) is None


def _recorded():
    return (trace.load(str(RECORDED)),
            json.loads((DATA / "spmv_small.op_names.json").read_text()),
            json.loads((DATA / "spmv_small.health.json").read_text()))


def test_recorded_chip_trace_names_its_ops():
    profile, names, _ = _recorded()
    ops = named_ops.attribute(profile, names)
    got = dict(ops)
    assert got["repro.spmv.gather_x"] > got["repro_spmv_fused"] > 0
    assert named_ops.named_share(ops) >= 0.99
    assert named_ops.spans_in_window(profile) == []
    # the same clipped device time bench/trace.py sums by HLO name
    assert sum(got.values()) == pytest.approx(
        sum(trace.reduce(profile).op_s.values()))


def test_readers_on_the_recorded_run():
    profile, _, health = _recorded()
    r = _run(health, profile, RECORDED_STEPS)
    spans = health["spans"]
    plan = plan_program_s.read(r)
    assert plan == pytest.approx(sum(
        v["self_s"] for k, v in spans.items() if k.startswith("repro.plan")))
    assert 0 < plan < sum(v["self_s"] for v in spans.values())
    assert compile_s.read(r) == health["compile"]["seconds"] > 0
    (work,) = health["work"].values()
    # SpMV gathers cols.size elements: every tile, loaded once, 512 wide
    assert work["rows_gathered"] == work["tile_loads"] * 512
    assert kernel_ns_per_row.read(r) == pytest.approx(
        1e9 * r.trace.busy_s / RECORDED_STEPS / work["rows_gathered"])
