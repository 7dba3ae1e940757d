"""Map staging cuts each block's arena slab on the device from the map
sort's own output (``DeviceReadback`` -> ``DeviceShuffleIO.
stage_device_blocks`` -> ``DeviceBufferManager.stage_device``): the
slabs equal the host path's byte for byte, inputs that do not name a
device array on the arena's device take the host path, one executable
serves a source length and slab class, and the sorted device array is
let go once the call has cut its last block."""

import threading

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from sparkrdma_tpu.models import MapShardSorter
from sparkrdma_tpu.obs import get_registry
from sparkrdma_tpu.ops.hbm_arena import (
    DeviceReadback,
    _cut_program,
    _size_class,
    device_cut_lock,
    device_window,
)
from sparkrdma_tpu.shuffle.device_io import DeviceShuffleIO
from sparkrdma_tpu.shuffle.manager import TpuShuffleManager
from sparkrdma_tpu.utils.config import TpuShuffleConf

EXEC = "cut-exec"
MIN_BLOCK = 16 * 1024  # deviceFetch.minBlockBytes' default

# block bounds over a sorted shard of ``n`` keys, in elements
LAYOUTS = {
    # 2^14 keys fill their class, so the sorted array has no sentinel
    # tail; the second block's 64 KiB class is the whole array, so its
    # window's start clamps to 0 and the block is shifted to the front
    "clamp": (1 << 14, [0, 5000, 1 << 14]),
    # 16, 32 and 64 KiB classes and two blocks under minBlockBytes,
    # from a 20,000-key shard padded to 32,768
    "mixed": (20_000, [0, 1000, 5096, 9196, 18_196, 20_000]),
    "whole": (1 << 14, [0, 1 << 14]),
}


@pytest.fixture(scope="module")
def io():
    conf = TpuShuffleConf({"tpu.shuffle.transport": "python"})
    driver = TpuShuffleManager(conf, is_driver=True)
    ex = TpuShuffleManager(conf, is_driver=False, executor_id=EXEC)
    dio = DeviceShuffleIO(ex)
    try:
        yield dio
    finally:
        dio.stop()
        ex.stop()
        driver.stop()


def _counts():
    reg = get_registry()
    return (reg.counter("map.stage.arena_blocks", role=EXEC).value,
            reg.counter("map.stage.device_cut_blocks", role=EXEC).value)


def _sorted(n, seed=11, device=None):
    keys = np.random.default_rng(seed).integers(0, 1 << 32, n, np.uint32)
    local, _ = MapShardSorter(device=device).sort_partition(
        keys, np.zeros((0,), np.uint32))
    return local


def _stage(io, sid, blocks):
    """Stage ``blocks`` (pid -> array); pid -> (slab contents, length)
    of every block that got an arena slab, and the counters' moves."""
    a0, c0 = _counts()
    locs = io.stage_device_blocks(sid, blocks)
    a1, c1 = _counts()
    slabs = {}
    for loc in locs:
        if loc.block.arena_handle:
            buf = io.device_buffers.resolve(loc.block.arena_handle)
            slabs[loc.partition_id] = (np.asarray(buf.array), buf.length)
    return slabs, a1 - a0, c1 - c0


@pytest.mark.parametrize("layout", sorted(LAYOUTS))
def test_device_cut_slabs_equal_the_host_path(io, layout):
    n, bounds = LAYOUTS[layout]
    local = _sorted(n)
    assert isinstance(local, DeviceReadback) and local.dtype == np.uint32
    assert np.array_equal(local, np.sort(np.asarray(local)))
    cuts = {r: local[bounds[r]:bounds[r + 1]] for r in range(len(bounds) - 1)}
    plain = {r: np.array(v) for r, v in cuts.items()}
    big = sum(v.nbytes >= MIN_BLOCK for v in plain.values())
    try:
        dev, staged, on_dev = _stage(io, 1, cuts)
        host, staged_h, on_dev_h = _stage(io, 2, plain)
    finally:
        io.unpublish(1)
        io.unpublish(2)
    assert (staged, on_dev) == (big, big)
    assert (staged_h, on_dev_h) == (big, 0)
    assert sorted(dev) == sorted(host) and len(dev) == big
    for r, (slab, length) in dev.items():
        want = plain[r]
        assert length == want.nbytes == host[r][1]
        assert slab.dtype == host[r][0].dtype == np.uint32
        assert slab.shape == (_size_class(want.nbytes) // 4,)
        assert slab.tobytes() == host[r][0].tobytes()
        assert np.array_equal(slab[:want.size], want)
        assert not slab[want.size:].any()


def test_a_strided_slice_names_no_window():
    # the host copy needs contiguous bytes too: the window is declined
    # before the copy's own error
    local = _sorted(1 << 14)
    assert device_window(local[::2]) is None
    assert device_window(local[:8192]) is not None


@pytest.mark.parametrize("kind", [
    "plain", "two_dim", "derived", "other_device", "released"])
def test_blocks_that_name_no_device_array_here_take_the_host_path(io, kind):
    n = 1 << 14
    if kind == "other_device":
        local = _sorted(n, device=jax.devices()[1])
    else:
        local = _sorted(n)
    block = {
        "plain": lambda: np.array(local[:8192]),
        "two_dim": lambda: local[:8192].reshape(2, 4096),
        "derived": lambda: local[:8192] + np.uint32(0),
        "other_device": lambda: local[:8192],
        "released": lambda: local[:8192],
    }[kind]()
    if kind == "released":
        local._source.array = None
    # another device's array is a window; the arena's device is not it
    assert (device_window(block) is None) == (kind != "other_device")
    try:
        slabs, staged, on_dev = _stage(io, 3, {0: block})
    finally:
        io.unpublish(3)
    assert (staged, on_dev) == (1, 0)
    slab, _ = slabs[0]
    assert np.array_equal(slab[:block.size], np.asarray(block).reshape(-1))


def test_a_device_array_is_its_own_window(io):
    arr = jnp.arange(20_000, dtype=jnp.uint32)
    try:
        slabs, staged, on_dev = _stage(io, 4, {0: arr})
    finally:
        io.unpublish(4)
    assert (staged, on_dev) == (1, 1)
    slab, length = slabs[0]
    assert length == 80_000 and slab.shape == (1 << 15,)
    assert np.array_equal(slab[:20_000], np.arange(20_000, dtype=np.uint32))
    assert not slab[20_000:].any()


def test_block_lengths_within_a_class_share_one_executable(io):
    n = 12_600  # padded to 2^14
    local = _sorted(n)
    bounds = [0, 4100, 8300, n]  # 16-32 KiB each: one 32 KiB class
    cut, _ = _cut_program(1 << 14, 1 << 13, "uint32")
    try:
        slabs, _, on_dev = _stage(io, 5, {
            r: local[bounds[r]:bounds[r + 1]] for r in range(3)})
    finally:
        io.unpublish(5)
    assert on_dev == 3
    assert cut._cache_size() == 1
    for r in range(3):
        lo, hi = bounds[r], bounds[r + 1]
        assert np.array_equal(slabs[r][0][:hi - lo], local[lo:hi])


@pytest.mark.parametrize("upto, released", [("last", True), ("first", False)])
def test_the_call_lets_the_device_source_go_once_it_cut_the_last_block(
        io, upto, released):
    n = 1 << 14
    local = _sorted(n)
    source = local._source
    blocks = {0: local[:6000], 1: local[6000:]}
    if upto == "first":
        del blocks[1]
    try:
        io.stage_device_blocks(6, blocks)
    finally:
        io.unpublish(6)
    assert (source.array is None) == released
    # what the call left in place still cuts on the device
    assert (device_window(local[6000:]) is None) == released


def test_executors_on_one_device_cut_in_turn(io):
    """While another executor cuts on the device, a call waits: its
    sorted array stays named, and no slab of it is cut, until the lock
    is free."""
    local = _sorted(1 << 14)
    lock = device_cut_lock(io.device_buffers.device)
    assert lock is device_cut_lock(io.device_buffers.device)
    out = {}

    def stage():
        out["locs"] = io.stage_device_blocks(
            7, {0: local[:8192], 1: local[8192:]})

    try:
        with lock:
            t = threading.Thread(target=stage)
            t.start()
            t.join(0.5)
            assert t.is_alive() and "locs" not in out
            assert local._source.array is not None
        t.join(30)
        assert not t.is_alive()
        assert local._source.array is None
        assert all(loc.block.arena_handle for loc in out["locs"])
    finally:
        io.unpublish(7)


@pytest.mark.parametrize("pooled_dtype", [np.uint32, np.uint8])
def test_a_pooled_slab_is_reused(io, pooled_dtype):
    """A free slab of the class is taken from the pool: donated into the
    cut where it already has the output's shape and dtype (it held a
    cut before), deleted where not (a zero slab from ``get``)."""
    mgr = io.device_buffers
    keep = mgr.get(1 << 20)  # a live slab keeps the idle pool pooled
    reg = get_registry()
    hits = reg.counter("hbm.pool_hits")
    src = jnp.arange(1 << 15, dtype=jnp.uint32) + 7
    try:
        if pooled_dtype == np.uint32:
            first = mgr.stage_device(src, 100, 6000)
        else:
            first = mgr.get(6000 * 4)
        first.free()
        h0 = hits.value
        buf = mgr.stage_device(src, 1000, 5000)
        assert hits.value == h0 + 1 and buf.handle == first.handle
        got = np.asarray(buf.array)
        assert got.dtype == np.uint32 and got.shape == (1 << 13,)
        assert np.array_equal(got[:5000], np.asarray(src)[1000:6000])
        assert not got[5000:].any()
        buf.free()
    finally:
        keep.free()
