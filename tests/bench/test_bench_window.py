"""bench/run.py's window: every step it sends is waited on, recorded once
and in order, and the window's time, from its start to the end of the
last step, is the sum of the step times."""
import sys
import types
from pathlib import Path

import jax.numpy as jnp
import pytest

ROOT = Path(__file__).resolve().parents[2]
sys.path.insert(0, str(ROOT))

from bench import run  # noqa: E402


class Counting:
    def __init__(self):
        self.launched, self.recorded = 0, []

    def launch(self, i):
        assert i == self.launched
        self.launched += 1
        return jnp.full((8, 128), i, jnp.float32) * 2.0

    def record(self, i, out):
        self.recorded.append((i, float(out[0, 0])))


@pytest.mark.parametrize("in_flight", [1, 4, 30])
def test_window_waits_for_all_it_sent(in_flight):
    cell = Counting()
    compiles = types.SimpleNamespace(active=False)
    times, window_s = run.window(cell, 0.3, compiles, in_flight)
    assert not compiles.active
    assert len(times) == cell.launched >= 1
    assert cell.recorded == [(i, 2.0 * i) for i in range(cell.launched)]
    assert sum(times) == pytest.approx(window_s)
    assert 0 < window_s < 30
