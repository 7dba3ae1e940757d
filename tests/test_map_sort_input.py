"""How a map shard's keys reach the chip that sorts them
(``MapShardSorter.sort_partition``): a shard that fills its size class
goes to the device as it is, one below it is padded on the host, both go
straight to the sorter's device, and either way the sorted keys and the
reducer bounds are those of ``np.sort`` and a ``searchsorted`` clamped to
the shard's length."""

import jax
import numpy as np
import pytest

from sparkrdma_tpu.models import MapShardSorter
from sparkrdma_tpu.obs import Tracer, get_registry
from sparkrdma_tpu.ops.hbm_arena import device_window

CAP = 1 << 20
ROLE = "sort-input"
NUM_EDGES = 7

# shard length -> whether it fills its size class
SIZES = {
    "fills": CAP,
    "below": CAP - 37,
    "floor": 500,  # below the 1024-key floor of the size classes
}


def _keys_and_edges(kind, n, rng):
    if kind == "edges_above_max":
        # keys below 2^23, as NPB IS draws them; the top edges lie above
        # every key, so their cuts fall on the padding but clamp to n
        keys = rng.integers(0, 1 << 23, n, dtype=np.uint32)
        edges = [1 << 20, 1 << 21, 3 << 21, 1 << 23, 1 << 24, 1 << 30,
                 0xFFFFFFFE]
    else:
        # real keys equal to the pad's sentinel, mixed with the rest
        keys = rng.integers(0, 1 << 32, n, dtype=np.uint32)
        keys[rng.choice(n, max(1, n // 64), replace=False)] = 0xFFFFFFFF
        edges = [(r << 32) // (NUM_EDGES + 1) for r in range(1, NUM_EDGES)]
        edges.append(0xFFFFFFFF)
    return keys, np.asarray(edges, dtype=np.uint32)


def _counts():
    reg = get_registry()
    return (reg.counter("map.sort.shards", role=ROLE).value,
            reg.counter("map.sort.unpadded_shards", role=ROLE).value)


def _shard_arrays_on(device, n):
    """Ids of the live arrays on ``device`` at least a shard long."""
    return {id(a) for a in jax.live_arrays()
            if a.size >= n and device in a.devices()}


@pytest.mark.parametrize("placement", ["default", "device3"])
@pytest.mark.parametrize("kind", ["edges_above_max", "sentinel_keys"])
@pytest.mark.parametrize("size", sorted(SIZES))
def test_sort_partition_input_path(size, kind, placement, monkeypatch):
    n = SIZES[size]
    keys, edges = _keys_and_edges(kind, n, np.random.default_rng(n))
    original = keys.copy()
    devices = jax.devices()
    target = devices[3] if placement == "device3" else None
    sorter = MapShardSorter(device=target, tracer=Tracer(role=ROLE))

    # no array of the shard's size reaches device 0 on the way to
    # device 3: checked once the shard is on the device and before the
    # sort reads it, and again after the call
    dev0 = devices[0]
    before = _shard_arrays_on(dev0, n)
    seen = []
    step = sorter._step

    def watched_step(*args):
        seen.append(_shard_arrays_on(dev0, n) - before)
        return step(*args)

    sorter._step = watched_step
    # every transfer of the shard: from where, and to where
    puts = []
    put = jax.device_put

    def watched_put(x, *args, **kwargs):
        out = put(x, *args, **kwargs)
        if getattr(x, "size", 0) >= n:
            puts.append((x.devices() if isinstance(x, jax.Array) else "host",
                         out.devices()))
        return out

    monkeypatch.setattr(jax, "device_put", watched_put)
    s0, u0 = _counts()
    local, bounds = sorter.sort_partition(keys, edges)
    s1, u1 = _counts()

    want = np.sort(original)
    np.testing.assert_array_equal(np.asarray(local), want)
    cuts = np.minimum(np.searchsorted(want, edges), n)
    np.testing.assert_array_equal(bounds, np.concatenate([[0], cuts, [n]]))
    np.testing.assert_array_equal(keys, original)  # the caller's keys

    assert s1 - s0 == 1
    assert u1 - u0 == (1 if size == "fills" else 0)

    window = device_window(local)
    assert window is not None
    assert window[0].devices() == {target or devices[0]}
    # one transfer, from the host straight to the sorter's device
    assert puts == [("host", {target or devices[0]})]
    if target is not None:
        assert seen == [set()]
        assert _shard_arrays_on(dev0, n) - before == set()
