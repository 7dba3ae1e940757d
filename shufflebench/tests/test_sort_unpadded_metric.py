"""``map.sort_unpadded_pct``: the share of map shards sent to the
sorter's device without a host pad, read from the program's counters;
and nothing from a program that lacks them."""

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
    ({"map.sort.shards{role=e0}": 0}, None),
    # every shard fills its class, over two executors
    ({"map.sort.shards{role=e0}": 40,
      "map.sort.shards{role=e1}": 40,
      "map.sort.unpadded_shards{role=e0}": 40,
      "map.sort.unpadded_shards{role=e1}": 40}, 100.0),
    # every shard below its class
    ({"map.sort.shards{role=e0}": 16}, 0.0),
    ({"map.sort.shards{role=map}": 4,
      "map.sort.unpadded_shards{role=map}": 3}, 75.0),
])
def test_unpadded_share(counters, want):
    got = spec.load_reader("map.sort_unpadded_pct")(_view(counters))
    assert got == (None if want is None else pytest.approx(want))
