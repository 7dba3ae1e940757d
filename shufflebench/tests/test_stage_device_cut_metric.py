"""``map.stage_device_cut_pct``: the share of arena-staged map blocks
cut on the device, read from the program's counters; and nothing from a
program that lacks them."""

import pytest

from shufflebench import spec
from shufflebench.run import RunView


def _view(counters):
    return RunView(
        cell=None, window=(0.0, 10.0), spans=[], jobs=[],
        counters={"counters": counters, "gauges": {}, "histograms": {}},
        trace=None, peaks=None, compiles_in_window=0, chips=1)


@pytest.mark.parametrize("counters, want", [
    # the parent: no such counters
    ({}, None),
    ({"map.stage.arena_blocks{role=e0}": 0}, None),
    # every block cut on the device, over two executors
    ({"map.stage.arena_blocks{role=e0}": 200,
      "map.stage.arena_blocks{role=e1}": 200,
      "map.stage.device_cut_blocks{role=e0}": 200,
      "map.stage.device_cut_blocks{role=e1}": 200}, 100.0),
    # host copies only
    ({"map.stage.arena_blocks{role=e0}": 16}, 0.0),
    ({"map.stage.arena_blocks{role=e0}": 16,
      "map.stage.device_cut_blocks{role=e0}": 12}, 75.0),
])
def test_device_cut_share(counters, want):
    got = spec.load_reader("map.stage_device_cut_pct")(_view(counters))
    assert got == (None if want is None else pytest.approx(want))
