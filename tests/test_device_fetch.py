"""Device fetch plane (DESIGN.md §17): wire extension, planner
fallbacks, and cluster byte-identity — all on the emulated
``JAX_PLATFORMS=cpu`` topology tier-1 runs on."""

import numpy as np
import pytest

from sparkrdma_tpu.locations import (
    BlockLocation,
    PartitionLocation,
    ShuffleManagerId,
)
from sparkrdma_tpu.rpc import PublishPartitionLocationsMsg, RpcMsg
from sparkrdma_tpu.shuffle.handle import BaseShuffleHandle, HashPartitioner
from sparkrdma_tpu.shuffle.manager import TpuShuffleManager
from sparkrdma_tpu.utils import checksum
from sparkrdma_tpu.utils.config import TpuShuffleConf


def _mk_loc(pid, length, mkey, ck=0, algo=0, coords=-1, handle=0, off=0):
    return PartitionLocation(
        ShuffleManagerId("host", 1234, f"exec-{mkey}"),
        pid,
        BlockLocation(
            0, length, mkey, checksum=ck, checksum_algo=algo,
            device_coords=coords, arena_handle=handle, arena_offset=off,
        ),
    )


# ----------------------------------------------------------------------
# wire: trailing device-location extension
# ----------------------------------------------------------------------
def test_publish_msg_device_extension_roundtrip():
    """Device coordinates ride the frame next to checksums AND the
    trace id — all three trailing extensions coexist."""
    locs = [
        _mk_loc(0, 100, 7, ck=0xDEADBEEF, algo=checksum.ALGO_CRC32,
                coords=3, handle=11, off=4096),
        _mk_loc(1, 200, 8, ck=0x12345678, algo=checksum.ALGO_CRC32),
    ]
    msg = PublishPartitionLocationsMsg(5, -1, locs, trace_id=0xABC)
    out = [RpcMsg.parse_segment(s) for s in msg.to_segments(4096)]
    got = sorted(
        (loc for m in out for loc in m.locations),
        key=lambda loc: loc.partition_id,
    )
    assert (got[0].block.device_coords, got[0].block.arena_handle,
            got[0].block.arena_offset) == (3, 11, 4096)
    assert got[0].block.has_device
    # the location WITHOUT a device copy parses with the no-device mark
    assert not got[1].block.has_device
    # the other extensions still parse alongside
    assert got[0].block.checksum == 0xDEADBEEF
    assert got[1].block.checksum == 0x12345678
    assert all(m.trace_id == 0xABC for m in out)


def test_publish_msg_without_device_is_byte_identical_legacy():
    """No device info -> no extension bytes: the frame is byte-for-byte
    the pre-extension layout (what examples/foreign_client.c parses)."""
    locs = [_mk_loc(0, 64, 3), _mk_loc(1, 64, 4)]
    msg = PublishPartitionLocationsMsg(2, -1, locs)
    baseline = PublishPartitionLocationsMsg(
        2, -1,
        [
            PartitionLocation(
                loc.manager_id, loc.partition_id,
                BlockLocation(loc.block.address, loc.block.length, loc.block.mkey),
            )
            for loc in locs
        ],
    )
    assert msg.to_segments(4096) == baseline.to_segments(4096)
    (seg,) = msg.to_segments(4096)
    m = RpcMsg.parse_segment(seg)
    assert [loc.block.arena_handle for loc in m.locations] == [0, 0]


def test_publish_msg_device_ext_survives_segmentation():
    """Device coordinates stay attached to THEIR location across
    segment splits (per-segment extension tables)."""
    locs = [
        _mk_loc(i, 10 + i, 100 + i, coords=i % 4, handle=i + 1, off=i * 64)
        for i in range(40)
    ]
    msg = PublishPartitionLocationsMsg(9, -1, locs)
    segments = msg.to_segments(256)
    assert len(segments) > 1
    got = []
    for seg in segments:
        got.extend(RpcMsg.parse_segment(seg).locations)
    assert len(got) == 40
    for i, loc in enumerate(sorted(got, key=lambda x: x.partition_id)):
        assert (loc.block.device_coords, loc.block.arena_handle,
                loc.block.arena_offset) == (i % 4, i + 1, i * 64)


# ----------------------------------------------------------------------
# planner + cluster (in-process emulated topology)
# ----------------------------------------------------------------------
BLOCK = 64 << 10  # above the 16 KiB deviceFetch.minBlockBytes default


@pytest.fixture()
def cluster():
    from sparkrdma_tpu.shuffle.device_io import DeviceShuffleIO

    # python transport: these tests assert planner/fallback counters,
    # not the native read plane
    conf = TpuShuffleConf({"tpu.shuffle.transport": "python"})
    driver = TpuShuffleManager(conf, is_driver=True)
    ex_map = TpuShuffleManager(conf, is_driver=False, executor_id="dfp-map")
    ex_red = TpuShuffleManager(conf, is_driver=False, executor_id="dfp-red")
    driver.register_shuffle(
        BaseShuffleHandle(
            shuffle_id=81, num_maps=1, partitioner=HashPartitioner(3)
        )
    )
    io_map, io_red = DeviceShuffleIO(ex_map), DeviceShuffleIO(ex_red)
    try:
        yield conf, io_map, io_red
    finally:
        io_red.stop()
        io_map.stop()
        ex_red.stop()
        ex_map.stop()
        driver.stop()


def _plane_counters(role):
    from sparkrdma_tpu.obs import get_registry

    reg = get_registry()
    return (
        reg.counter("device_fetch.plane.pulls", role=role),
        reg.counter("device_fetch.plane.fallbacks", role=role),
    )


def _publish(io_map, seed=17):
    rng = np.random.default_rng(seed)
    data = {p: rng.integers(0, 256, BLOCK + p, np.uint8) for p in range(3)}
    io_map.publish_device_blocks(81, data)
    return data


def test_device_pull_engages_and_is_byte_identical(cluster):
    """Arena-resident published blocks come back via HBM pulls (the
    plane counter moves, zero fallbacks) and the bytes match a
    host-path fetch of the same shuffle exactly."""
    conf, io_map, io_red = cluster
    data = _publish(io_map)
    pulls, fallbacks = _plane_counters("dfp-red")
    p0, f0 = pulls.value, fallbacks.value

    got_dev = io_red.fetch_device_blocks(81, 0, 3, timeout_s=30)
    dev_bytes = {
        p: bytes(got_dev[p][0].read(0, len(data[p]))) for p in range(3)
    }
    for bufs in got_dev.values():
        for b in bufs:
            b.free()
    assert pulls.value - p0 == 3, "device pulls did not engage"
    assert fallbacks.value == f0

    conf.set("tpu.shuffle.deviceFetch.enabled", "false")
    got_host = io_red.fetch_device_blocks(81, 0, 3, timeout_s=30)
    host_bytes = {
        p: bytes(got_host[p][0].read(0, len(data[p]))) for p in range(3)
    }
    for bufs in got_host.values():
        for b in bufs:
            b.free()
    assert pulls.value - p0 == 3, "disabled plane still pulled"

    for p in range(3):
        assert dev_bytes[p] == data[p].tobytes(), f"device path differs p{p}"
        assert host_bytes[p] == dev_bytes[p], f"host/device differ p{p}"


def test_planner_degrades_to_host_on_arena_spill(cluster):
    """The eviction race: every published arena copy is forced off the
    device mid-job. The fetch must complete byte-exact through the host
    triple — fallbacks counted, ZERO errors, zero pulls."""
    conf, io_map, io_red = cluster
    data = _publish(io_map)
    # force the race: all advertised slabs leave the device tier
    for abuf in io_map._arena_published[81]:
        abuf.spill_to_host()
        assert abuf.spilled
    pulls, fallbacks = _plane_counters("dfp-red")
    p0, f0 = pulls.value, fallbacks.value
    got = io_red.fetch_device_blocks(81, 0, 3, timeout_s=30)
    for p in range(3):
        assert bytes(got[p][0].read(0, len(data[p]))) == data[p].tobytes()
    for bufs in got.values():
        for b in bufs:
            b.free()
    assert pulls.value == p0, "spilled slab must not be pulled"
    assert fallbacks.value - f0 == 3, "each block counts one fallback"


def test_mover_failure_surfaces_instead_of_falling_back(cluster, monkeypatch):
    """A failure of the mover itself is not a planner decision: it
    raises out of the fetch, and no fallback is counted for it."""
    from sparkrdma_tpu.ops import remote_copy

    conf, io_map, io_red = cluster
    conf.set("tpu.shuffle.collective.enabled", "false")
    _publish(io_map)

    def broken(*_a, **_k):
        raise RuntimeError("injected: transfer engine failed")

    monkeypatch.setattr(remote_copy, "emulated_pull", broken)
    _pulls, fallbacks = _plane_counters("dfp-red")
    f0 = fallbacks.value
    with pytest.raises(RuntimeError, match="injected: transfer engine"):
        io_red.fetch_device_blocks(81, 0, 3, timeout_s=30)
    assert fallbacks.value == f0


def test_planner_skips_blocks_below_min_bytes(cluster):
    """Blocks under deviceFetch.minBlockBytes publish no pull-worthy
    offer the planner accepts: host path, one fallback each (the device
    ext IS present — arena staging floors at the same knob, so this
    exercises the size gate directly)."""
    conf, io_map, io_red = cluster
    conf.set("tpu.shuffle.deviceFetch.minBlockBytes", "1k")
    rng = np.random.default_rng(3)
    data = {p: rng.integers(0, 256, 2048, np.uint8) for p in range(3)}
    io_map.publish_device_blocks(81, data)
    conf.set("tpu.shuffle.deviceFetch.minBlockBytes", "16k")
    pulls, fallbacks = _plane_counters("dfp-red")
    p0, f0 = pulls.value, fallbacks.value
    got = io_red.fetch_device_blocks(81, 0, 3, timeout_s=30)
    for p in range(3):
        assert bytes(got[p][0].read(0, 2048)) == data[p].tobytes()
    for bufs in got.values():
        for b in bufs:
            b.free()
    assert pulls.value == p0
    assert fallbacks.value - f0 == 3


def test_split_phase_device_pull_byte_identity(cluster):
    """The split-phase reduce pipeline (fetch/verify/stage seams) with
    device pulls flowing through: DevicePulledBlock passes verify,
    unwraps at stage, and the staged bytes match the host path."""
    conf, io_map, io_red = cluster
    data = _publish(io_map, seed=23)
    pulls, _ = _plane_counters("dfp-red")
    p0 = pulls.value

    def run_pipeline():
        staged = {}
        got = io_red.fetch_host_blocks(81, 0, 3, timeout_s=30)
        for p, blocks in got.items():
            out = []
            for hb in blocks:
                hb = io_red.verify_host_block(hb)
                out.append(io_red.stage_host_block(hb))
            staged[p] = out
        return staged

    staged_dev = run_pipeline()
    n_pulled = pulls.value - p0
    assert n_pulled == 3, "split-phase fetch did not pull"
    dev_bytes = {
        p: bytes(staged_dev[p][0].read(0, len(data[p]))) for p in range(3)
    }
    for bufs in staged_dev.values():
        for b in bufs:
            b.free()

    conf.set("tpu.shuffle.deviceFetch.enabled", "false")
    staged_host = run_pipeline()
    for p in range(3):
        host = bytes(staged_host[p][0].read(0, len(data[p])))
        assert host == data[p].tobytes()
        assert host == dev_bytes[p], f"split-phase host/device differ p{p}"
    for bufs in staged_host.values():
        for b in bufs:
            b.free()


def test_pulled_block_release_covers_abort_drain(cluster):
    """A DevicePulledBlock abandoned before staging (abort drain) frees
    its slab — no arena leak."""
    conf, io_map, io_red = cluster
    _publish(io_map, seed=29)
    got = io_red.fetch_host_blocks(81, 0, 3, timeout_s=30)
    before = io_red.device_buffers.in_use_bytes
    assert before > 0
    for blocks in got.values():
        for hb in blocks:
            hb.release()
            hb.release()  # idempotent
    # only the publisher-side arena copies remain accounted elsewhere
    assert io_red.device_buffers.in_use_bytes == 0


def test_publish_staged_batch_one_rpc(cluster):
    """N shards' windows published in one RPC: the driver's barrier
    counts every map output and a fetch sees every block."""
    conf, io_map, io_red = cluster
    rng = np.random.default_rng(41)
    windows = []
    all_data = {}
    for shard in range(3):
        data = {
            p: rng.integers(0, 256, BLOCK, np.uint8) for p in range(3)
        }
        windows.append(io_map.stage_device_blocks(81, data))
        for p, arr in data.items():
            all_data.setdefault(p, []).append(arr)
    io_map.publish_staged_batch(81, windows, num_map_outputs_each=1)
    got = io_red.fetch_device_blocks(81, 0, 3, timeout_s=30)
    try:
        for p in range(3):
            assert len(got[p]) == 3, "batched publish dropped blocks"
            have = sorted(bytes(b.read(0, BLOCK)) for b in got[p])
            want = sorted(a.tobytes() for a in all_data[p])
            assert have == want
    finally:
        for bufs in got.values():
            for b in bufs:
                b.free()
