"""A whole run of each cell at a small size on the CPU, past the harness's
look for a chip: the program comes out correct, and the control (the
reference in a lower precision in the program's place) and every planted
fault the cell can have come out not correct."""
import json
import sys
from pathlib import Path

import pytest

ROOT = Path(__file__).resolve().parents[2]
sys.path.insert(0, str(ROOT))

from bench import run  # noqa: E402

SEED = 2**31 + 11


def small(name):
    traffic_name, config_name = name.split(".")
    cell = {"name": name, "chips": 1}
    config = json.loads((ROOT / "bench" / "configs" / f"{config_name}.json")
                        .read_text())
    traffic = json.loads((ROOT / "bench" / "traffic" / f"{traffic_name}.json")
                         .read_text())
    return cell, dict(config, scale=9), traffic


def execute(name, variant):
    cell, config, traffic = small(name)
    return run.execute(name, SEED, 0.2, False, cell_spec=cell, config=config,
                       traffic=traffic, variant=variant, chip_check=False)


CASES = [
    ("spmm-n128.graph500-s20", "program", True),
    ("spmm-n128.graph500-s20", "control", False),
    ("spmm-n128.graph500-s20", "altered", False),
    ("spmv.graph500-s20", "program", True),
    ("spmv.graph500-s20", "control", False),
    ("spmv.graph500-s20", "altered", False),
]


@pytest.mark.parametrize("name,variant,correct", CASES,
                         ids=[f"{c[0].split('.')[0]}-{c[1]}" for c in CASES])
def test_run_decides_correct(name, variant, correct):
    result = execute(name, variant)
    assert result["correct"] is correct, json.dumps(result["checks"])
    assert list(result)[-1] == "checks"
    assert (result["failed"] == 0) is correct
    assert result["metrics"]["step_ms"]["value"] > 0
