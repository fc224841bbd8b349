"""BENCHMARK.json: names and units in the allowed characters, and every
cell, configuration, traffic mix, driver and metric resolving to its files."""
import importlib
import json
import re
import sys
from pathlib import Path

import pytest

ROOT = Path(__file__).resolve().parents[2]
sys.path.insert(0, str(ROOT))

SPEC = json.loads((ROOT / "BENCHMARK.json").read_text())
NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.-]{1,16}$")
PATH = re.compile(r"^[A-Za-z0-9_./-]{1,200}$")
KEYS = {"command", "paths", "run_seconds", "configs", "workloads",
        "end_to_end", "per_layer"}


def _one_line(text):
    return 1 <= len(text) <= 200 and "\n" not in text and "\t" not in text


def test_top_level_keys_and_limits():
    assert set(SPEC) == KEYS
    assert 1 <= SPEC["run_seconds"] <= 51
    assert len((ROOT / "BENCHMARK.json").read_bytes()) <= 64 * 1024
    assert 1 <= len(SPEC["paths"]) <= 16
    for p in SPEC["paths"]:
        assert PATH.match(p) and not p.startswith("/") and ".." not in p
        assert (ROOT / p).is_dir()
    assert len(SPEC["command"]) <= 32
    assert all(_one_line(w) for w in SPEC["command"])
    script = ROOT / SPEC["command"][1]
    assert any(script.is_relative_to(ROOT / p) for p in SPEC["paths"])


@pytest.mark.parametrize("kind", ["configs", "workloads", "end_to_end",
                                  "per_layer"])
def test_names_are_unique_and_well_formed(kind):
    names = [e["name"] for e in SPEC[kind]]
    assert len(names) == len(set(names))
    assert all(NAME.match(n) for n in names)


def test_metric_fields():
    e2e = {m["name"] for m in SPEC["end_to_end"]}
    cells = {w["name"] for w in SPEC["workloads"]}
    assert "setup_s" in e2e
    for m in SPEC["end_to_end"]:
        assert set(m) <= {"name", "unit", "better", "bound", "source",
                          "workloads"}
        assert UNIT.match(m["unit"]) and m["better"] in ("lower", "higher")
        assert m["source"] in ("host_clock", "device_trace")
        assert 0.01 <= m["bound"] <= 0.25
        assert set(m.get("workloads", cells)) <= cells
    for m in SPEC["per_layer"]:
        assert set(m) <= {"name", "unit", "better", "source", "layer",
                          "moves", "workloads"}
        assert UNIT.match(m["unit"]) and m["better"] in ("lower", "higher")
        assert m["source"] in ("device_trace", "program_span",
                               "program_counter", "host_clock")
        assert m["moves"] in e2e and _one_line(m["layer"])
        assert set(m.get("workloads", cells)) <= cells
        assert (ROOT / "bench" / "metrics" / f"{m['name']}.py").is_file()
        mod = importlib.import_module(f"bench.metrics.{m['name']}")
        assert callable(mod.read)


def test_every_cell_reports_setup_another_e2e_and_a_per_layer_metric():
    for w in SPEC["workloads"]:
        e2e = [m["name"] for m in SPEC["end_to_end"]
               if w["name"] in m.get("workloads", [w["name"]])]
        per = [m for m in SPEC["per_layer"]
               if w["name"] in m.get("workloads", [w["name"]])]
        assert "setup_s" in e2e and len(e2e) >= 2
        assert per
        for m in per:         # each reports the metric it moves
            assert m["moves"] in e2e


def test_cells_resolve_to_config_traffic_and_driver():
    configs = {c["name"]: c for c in SPEC["configs"]}
    used = set()
    pairs = set()
    four = 0
    for w in SPEC["workloads"]:
        assert set(w) == {"name", "config", "traffic", "chips", "why"}
        assert w["chips"] in (1, 4) and _one_line(w["why"])
        four += w["chips"] == 4
        assert NAME.match(w["traffic"])
        assert (w["config"], w["traffic"]) not in pairs
        pairs.add((w["config"], w["traffic"]))
        assert w["config"] in configs
        used.add(w["config"])
        traffic = json.loads((ROOT / "bench" / "traffic"
                              / f"{w['traffic']}.json").read_text())
        driver = importlib.import_module(f"bench.drivers.{traffic['driver']}")
        assert "program" in driver.Cell.variants
    assert used == set(configs)
    assert four <= max(1, len(SPEC["workloads"]) // 2)


@pytest.mark.parametrize("config", SPEC["configs"], ids=lambda c: c["name"])
def test_config_files(config):
    assert set(config) == {"name", "source", "file", "reduced", "why"}
    assert _one_line(config["source"]) and _one_line(config["why"])
    path = ROOT / config["file"]
    assert path.is_file() and config["file"].startswith("bench/configs/")
    body = json.loads(path.read_text())
    assert body["reduced"] == config["reduced"]
    assert len(config["reduced"]) <= 16
    assert all(NAME.match(k) for k in config["reduced"])
    for key in config["reduced"]:
        assert not key.endswith(("_dim", "_rank", "_size"))
    files = [c["file"] for c in SPEC["configs"]]
    assert len(files) == len(set(files))
