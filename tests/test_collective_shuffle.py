"""Whole-stage collective shuffle (DESIGN.md §22): schedule selection,
compiled-vs-per-block byte identity, mid-stage degrade, the wave
movers, and lane-balanced reduce cuts — on the ``JAX_PLATFORMS=cpu``
topology tier-1 runs on, through the same wave path as the chip (the
movers' jnp stand-ins in place of the Pallas kernels)."""

import numpy as np
import pytest

from sparkrdma_tpu.locations import (
    BlockLocation,
    PartitionLocation,
    ShuffleManagerId,
)
from sparkrdma_tpu.obs import get_registry
from sparkrdma_tpu.shuffle.handle import BaseShuffleHandle, HashPartitioner
from sparkrdma_tpu.shuffle.manager import TpuShuffleManager
from sparkrdma_tpu.utils.config import TpuShuffleConf

BLOCK = 64 << 10  # above the 16 KiB deviceFetch.minBlockBytes default


def _loc(pid, length, exec_id, mkey=1, handle=1, coords=0):
    return PartitionLocation(
        ShuffleManagerId("host", 1234, exec_id),
        pid,
        BlockLocation(
            0, length, mkey, device_coords=coords, arena_handle=handle
        ),
    )


def _counter(name, role):
    return get_registry().counter(name, role=role)


@pytest.fixture()
def cluster():
    from sparkrdma_tpu.shuffle.device_io import DeviceShuffleIO

    conf = TpuShuffleConf({"tpu.shuffle.transport": "python"})
    driver = TpuShuffleManager(conf, is_driver=True)
    ex_map = TpuShuffleManager(conf, is_driver=False, executor_id="cs-map")
    ex_red = TpuShuffleManager(conf, is_driver=False, executor_id="cs-red")
    driver.register_shuffle(
        BaseShuffleHandle(
            shuffle_id=91, num_maps=1, partitioner=HashPartitioner(3)
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


def _publish_shards(io_map, shards=3, seed=57):
    """``shards`` map windows, 3 partitions each -> 3 blocks per pid,
    all from one publisher (one DMA lane)."""
    rng = np.random.default_rng(seed)
    windows, all_data = [], {}
    for _ in range(shards):
        data = {p: rng.integers(0, 256, BLOCK + p, np.uint8) for p in range(3)}
        windows.append(io_map.stage_device_blocks(91, data))
        for p, arr in data.items():
            all_data.setdefault(p, []).append(arr)
    io_map.publish_staged_batch(91, windows, num_map_outputs_each=1)
    return all_data


# ----------------------------------------------------------------------
# schedule compilation (plan-level, synthetic location sets)
# ----------------------------------------------------------------------
def test_schedule_selection_and_passthrough(cluster):
    """auto resolves ring for <=2 source lanes and a2a above; explicit
    knob wins; sub-minBlocks stages and disabled compilers pass every
    location through untouched."""
    from sparkrdma_tpu.shuffle import device_fetch as df
    from sparkrdma_tpu.shuffle.collective import ShuffleScheduleCompiler

    conf, io_map, io_red = cluster
    for i in range(3):
        df.register_arena(f"cs-lane-{i}", io_map.device_buffers)
    try:
        comp = ShuffleScheduleCompiler(
            conf, io_red.device_buffers, "cs-sched"
        )
        three_lanes = [
            _loc(p, BLOCK, f"cs-lane-{p}", mkey=10 + p) for p in range(3)
        ]
        plan = comp.plan(three_lanes)
        assert plan.schedule == "a2a"
        assert plan.waves and not plan.passthrough
        assert plan.device_blocks == 3

        two_lanes = [
            _loc(p, BLOCK, f"cs-lane-{p % 2}", mkey=20 + p) for p in range(3)
        ]
        assert comp.plan(two_lanes).schedule == "ring"

        conf.set("tpu.shuffle.collective.schedule", "ring")
        try:
            assert comp.plan(three_lanes).schedule == "ring"
        finally:
            conf.set("tpu.shuffle.collective.schedule", "auto")

        # below minBlocks: the per-block planner keeps the whole stage
        solo = comp.plan([_loc(0, BLOCK, "cs-lane-0")])
        assert not solo.waves and len(solo.passthrough) == 1

        # a location with no device extension never schedules
        mixed = three_lanes + [_loc(9, BLOCK, "cs-lane-0", handle=0)]
        plan = comp.plan(mixed)
        assert len(plan.passthrough) == 1
        assert plan.passthrough[0].partition_id == 9

        conf.set("tpu.shuffle.collective.enabled", "false")
        try:
            off = comp.plan(three_lanes)
            assert not off.waves and len(off.passthrough) == 3
        finally:
            conf.set("tpu.shuffle.collective.enabled", "true")
    finally:
        for i in range(3):
            df.unregister_arena(f"cs-lane-{i}", io_map.device_buffers)


def test_wave_formation_buckets_and_pid_grouping(cluster):
    """Waves cut at partition boundaries under waveBytes, with both
    axes power-of-two bucketed so ragged stages share program shapes."""
    from sparkrdma_tpu.ops.exchange import round_bucket, round_rows
    from sparkrdma_tpu.shuffle import device_fetch as df
    from sparkrdma_tpu.shuffle.collective import ShuffleScheduleCompiler

    conf, io_map, io_red = cluster
    df.register_arena("cs-lane-w", io_map.device_buffers)
    try:
        comp = ShuffleScheduleCompiler(conf, io_red.device_buffers, "cs-wf")
        # ragged lengths across 3 pids, 2 blocks each
        locs = [
            _loc(p, BLOCK + 1000 * k, "cs-lane-w", mkey=30 + 2 * p + k)
            for p in range(3)
            for k in range(2)
        ]
        plan = comp.plan(locs)
        (wave,) = plan.waves
        assert wave.rows_b == round_rows(6)
        longest = max(loc.block.length for loc in locs)
        assert wave.bucket_elems == round_bucket(longest)
        # pid groups are contiguous in the wave, in merge order
        pids = [r.loc.partition_id for r in wave.rows]
        assert pids == sorted(pids)

        # a tight wave budget splits at pid boundaries
        conf.set("tpu.shuffle.collective.waveBytes", "192k")
        try:
            plan = comp.plan(locs)
            assert len(plan.waves) > 1
            for w in plan.waves:
                assert [r.loc.partition_id for r in w.rows] == sorted(
                    r.loc.partition_id for r in w.rows
                )
        finally:
            conf.set("tpu.shuffle.collective.waveBytes", "64m")
    finally:
        df.unregister_arena("cs-lane-w", io_map.device_buffers)


# ----------------------------------------------------------------------
# execution byte identity (in-process cluster)
# ----------------------------------------------------------------------
def test_collective_vs_per_block_vs_host_byte_identity(cluster):
    """The same stage fetched three ways — compiled collective,
    per-block device pulls, host triple — lands byte-identical block
    multisets, and the collective counters prove which path ran."""
    conf, io_map, io_red = cluster
    data = _publish_shards(io_map)
    plans = _counter("collective.plans", "cs-red")
    blocks = _counter("collective.blocks", "cs-red")
    p0, b0 = plans.value, blocks.value

    def fetch_multiset():
        got = io_red.fetch_device_blocks(91, 0, 3, timeout_s=30)
        try:
            return {
                p: sorted(bytes(b.read(0, b.length)) for b in got[p])
                for p in range(3)
            }
        finally:
            for bufs in got.values():
                for b in bufs:
                    b.free()

    via_collective = fetch_multiset()
    assert plans.value - p0 == 1, "compiler did not engage"
    assert blocks.value - b0 == 9, "not every block rode a wave"

    conf.set("tpu.shuffle.collective.enabled", "false")
    via_per_block = fetch_multiset()
    assert plans.value - p0 == 1, "disabled compiler still planned"

    conf.set("tpu.shuffle.deviceFetch.enabled", "false")
    via_host = fetch_multiset()

    want = {p: sorted(a.tobytes() for a in data[p]) for p in range(3)}
    assert via_collective == want
    assert via_per_block == want
    assert via_host == want


def test_eviction_mid_stage_degrades_silently(cluster):
    """A slab evicted between plan and pin degrades its row to the
    host triple — zero errors, byte-identical output, and exactly that
    row counted as a degrade, every other block landing per block from
    the waves."""
    conf, io_map, io_red = cluster
    data = _publish_shards(io_map, seed=67)
    degrades = _counter("collective.degrades", "cs-red")
    d0 = degrades.value

    # evict ONE of partition 1's three slabs (window 0 stages pids
    # 0,1,2 in order, so flat index 1 is w0/p1)
    victim = io_map._arena_published[91][1]
    victim.spill_to_host()
    assert victim.spilled

    blocks = _counter("collective.blocks", "cs-red")
    b0 = blocks.value
    got = io_red.fetch_device_blocks(91, 0, 3, timeout_s=30)
    try:
        for p in range(3):
            assert len(got[p]) == 3, "one slab per block"
            have = sorted(bytes(b.read(0, b.length)) for b in got[p])
            assert have == sorted(a.tobytes() for a in data[p])
    finally:
        for bufs in got.values():
            for b in bufs:
                b.free()
    assert degrades.value - d0 == 1, "exactly the evicted row degrades"
    assert blocks.value - b0 == 8, "every other block rode a wave"


def test_whole_stage_eviction_falls_back_to_host(cluster):
    """Every scheduled slab evicted: the stage still completes byte-
    exact through the host triple with one degrade per block."""
    conf, io_map, io_red = cluster
    data = _publish_shards(io_map, seed=71, shards=1)
    degrades = _counter("collective.degrades", "cs-red")
    d0 = degrades.value
    for abuf in io_map._arena_published[91]:
        abuf.spill_to_host()
    got = io_red.fetch_device_blocks(91, 0, 3, timeout_s=30)
    try:
        for p in range(3):
            assert bytes(got[p][0].read(0, len(data[p][0]))) == (
                data[p][0].tobytes()
            )
    finally:
        for bufs in got.values():
            for b in bufs:
                b.free()
    assert degrades.value - d0 == 3


def test_split_phase_collective_pull(cluster):
    """fetch_host_blocks routes wave rows back as DevicePulledBlock
    entries, one per block, that flow through verify/stage untouched."""
    from sparkrdma_tpu.shuffle.device_fetch import DevicePulledBlock

    conf, io_map, io_red = cluster
    data = _publish_shards(io_map, seed=73, shards=1)
    blocks = _counter("collective.blocks", "cs-red")
    b0 = blocks.value
    got = io_red.fetch_host_blocks(91, 0, 3, timeout_s=30)
    staged = {}
    for p, hbs in got.items():
        out = []
        for hb in hbs:
            assert isinstance(hb, DevicePulledBlock)
            out.append(io_red.stage_host_block(io_red.verify_host_block(hb)))
        staged[p] = out
    assert blocks.value - b0 == 3
    try:
        for p in range(3):
            assert bytes(staged[p][0].read(0, len(data[p][0]))) == (
                data[p][0].tobytes()
            )
    finally:
        for bufs in staged.values():
            for b in bufs:
                b.free()


# ----------------------------------------------------------------------
# double-buffered pipeline (DESIGN.md §22 pipelining)
# ----------------------------------------------------------------------
def test_pipeline_depth_byte_identity_and_overlap(cluster, monkeypatch):
    """depth>1 changes the overlap, never the bytes: the same multi-
    wave stage fetched at depth 1 and depth 2 lands identical block
    multisets, the overlap counter stays zero at depth 1 (nothing was
    in flight during any issue/consume) and goes positive at depth 2."""
    from sparkrdma_tpu.obs import attr

    conf, io_map, io_red = cluster
    # a stale breakdown from an earlier test could veto the tuner;
    # irrelevant here but keep the stage's wave count deterministic
    monkeypatch.setattr(attr, "_last_breakdown", None)
    conf.set("tpu.shuffle.collective.autoTune", "false")
    conf.set("tpu.shuffle.collective.waveBytes", "192k")
    data = _publish_shards(io_map, seed=79)
    overlap = _counter("collective.wave_overlap_ms", "cs-red")
    waves = get_registry().counter(
        "collective.waves", role="cs-red", schedule="ring"
    )

    def fetch_multiset():
        got = io_red.fetch_device_blocks(91, 0, 3, timeout_s=30)
        try:
            return {
                p: sorted(bytes(b.read(0, b.length)) for b in got[p])
                for p in range(3)
            }
        finally:
            for bufs in got.values():
                for b in bufs:
                    b.free()

    conf.set("tpu.shuffle.collective.pipelineDepth", "1")
    o0, w0 = overlap.value, waves.value
    depth1 = fetch_multiset()
    assert waves.value - w0 > 1, "stage must cut into multiple waves"
    assert overlap.value == o0, "depth 1 must never overlap"

    conf.set("tpu.shuffle.collective.pipelineDepth", "2")
    o1 = overlap.value
    depth2 = fetch_multiset()
    assert overlap.value > o1, "depth 2 must overlap issue with consume"

    want = {p: sorted(a.tobytes() for a in data[p]) for p in range(3)}
    assert depth1 == want
    assert depth2 == want


def test_pipeline_drain_on_midstage_abort(cluster, monkeypatch):
    """A wave whose landing fails mid-pipeline (the next entry's
    transfers already airborne) raises out of the fetch, as a mover
    failure does: every pin released, and no slab left behind on either
    endpoint."""
    from sparkrdma_tpu.obs import attr
    from sparkrdma_tpu.shuffle.collective import ShuffleScheduleCompiler

    conf, io_map, io_red = cluster
    monkeypatch.setattr(attr, "_last_breakdown", None)
    conf.set("tpu.shuffle.collective.autoTune", "false")
    conf.set("tpu.shuffle.collective.waveBytes", "192k")
    conf.set("tpu.shuffle.collective.pipelineDepth", "2")
    base_red = io_red.device_buffers.in_use_bytes
    _publish_shards(io_map, seed=83)
    base_map = io_map.device_buffers.in_use_bytes
    issue = ShuffleScheduleCompiler._issue_entry
    issued, landings = [], []

    def counting_issue(self, waves, *a, **k):
        issued.append(len(waves))
        return issue(self, waves, *a, **k)

    def failing_landing(self, entry):
        landings.append(len(issued))
        raise RuntimeError("injected: wave landing failed in flight")

    monkeypatch.setattr(ShuffleScheduleCompiler, "_issue_entry",
                        counting_issue)
    monkeypatch.setattr(ShuffleScheduleCompiler, "_landed_shard",
                        failing_landing)
    with pytest.raises(RuntimeError, match="injected: wave landing"):
        io_red.fetch_device_blocks(91, 0, 3, timeout_s=30)
    assert landings == [2], "the first landing fails, the next entry airborne"
    assert len(issued) == 2, "no entry is issued after the failure"
    # leak checks: no pin outlives the stage on the source arena, and no
    # slab is left on either arena
    assert not io_map.device_buffers._pins
    assert io_map.device_buffers.in_use_bytes == base_map
    assert io_red.device_buffers.in_use_bytes == base_red


def test_autotuner_converges_on_second_stage(cluster, monkeypatch):
    """The first identical stage runs monolithic (one wave under the
    default 64m budget) and is observed; the SECOND runs with the
    tuner's re-cut budget (multiple waves for the pipeline to overlap)
    and converges — no further adjustment on the third run, and no
    slowdown from the re-cut."""
    import time as _time

    from sparkrdma_tpu.obs import attr

    conf, io_map, io_red = cluster
    # the gate must judge THIS run, not a breakdown some earlier test
    # published; None means no veto
    monkeypatch.setattr(attr, "_last_breakdown", None)
    data = _publish_shards(io_map, seed=89)
    adjusts = _counter("collective.autotune_adjustments", "cs-red")
    waves = get_registry().counter(
        "collective.waves", role="cs-red", schedule="ring"
    )
    a0 = adjusts.value

    def timed_fetch():
        t0 = _time.perf_counter()
        w0 = waves.value
        got = io_red.fetch_device_blocks(91, 0, 3, timeout_s=30)
        wall = _time.perf_counter() - t0
        try:
            have = {
                p: sorted(bytes(b.read(0, b.length)) for b in got[p])
                for p in range(3)
            }
        finally:
            for bufs in got.values():
                for b in bufs:
                    b.free()
        return have, wall, waves.value - w0

    first, wall1, waves1 = timed_fetch()
    assert waves1 == 1, "default budget must run the stage monolithic"
    assert adjusts.value - a0 == 1, "first observation must re-cut"

    second, wall2, waves2 = timed_fetch()
    assert waves2 > 1, "second identical stage must run the tuned cut"
    assert adjusts.value - a0 == 1, "same stats -> same choice: converged"

    third, wall3, waves3 = timed_fetch()
    assert waves3 == waves2
    assert adjusts.value - a0 == 1

    want = {p: sorted(a.tobytes() for a in data[p]) for p in range(3)}
    assert first == second == third == want
    # not-slower gate, honest about the rig: sub-resolution walls say
    # nothing about a regression either way (the structural asserts
    # above are the convergence proof regardless)
    if wall1 < 0.02:
        pytest.skip(
            f"stage wall {wall1 * 1e3:.1f}ms below timing resolution on "
            "this rig; cannot resolve the not-slower comparison"
        )
    assert min(wall2, wall3) <= wall1 * 2.5 + 0.05, (
        "tuned stage must not be slower than the untuned first run"
    )


def test_autotuner_converges_structurally(cluster, monkeypatch):
    """Timing-free half of the convergence proof (the not-slower test
    above may skip on rigs whose stage wall is below resolution): the
    second identical stage plans with the tuned budget and the choice
    is stable across runs."""
    from sparkrdma_tpu.obs import attr

    conf, io_map, io_red = cluster
    monkeypatch.setattr(attr, "_last_breakdown", None)
    _publish_shards(io_map, seed=97)
    adjusts = _counter("collective.autotune_adjustments", "cs-red")
    waves = get_registry().counter(
        "collective.waves", role="cs-red", schedule="ring"
    )
    a0 = adjusts.value
    per_run = []
    for _ in range(3):
        w0 = waves.value
        got = io_red.fetch_device_blocks(91, 0, 3, timeout_s=30)
        for bufs in got.values():
            for b in bufs:
                b.free()
        per_run.append(waves.value - w0)
    assert per_run[0] == 1
    assert per_run[1] > 1 and per_run[2] == per_run[1]
    assert adjusts.value - a0 == 1


# ----------------------------------------------------------------------
# lane-balanced reduce cuts (planner-level)
# ----------------------------------------------------------------------
def test_planner_lane_balanced_cuts():
    """Equal byte totals hide a one-lane hotspot; the lane-aware cost
    (num_lanes * hottest lane) re-cuts the ranges around it while the
    totals-only plan stays static."""
    from sparkrdma_tpu.shuffle.planner import AdaptivePartitioner

    conf = TpuShuffleConf()
    p, n = 8, 4
    sizes = [100] * p
    lane_sizes = {src: [25] * p for src in ("la", "lb", "lc", "ld")}
    for src in ("lb", "lc", "ld"):
        lane_sizes[src][5] = 0
    lane_sizes["la"][5] = 100  # same total, one lane carries it all

    lane_plans = get_registry().counter("collective.lane_plans", role="driver")
    c0 = lane_plans.value
    ap = AdaptivePartitioner(conf)
    base = ap.plan(sizes, n)
    assert base == [(0, 2), (2, 4), (4, 6), (6, 8)], "uniform stays static"
    laned = ap.plan(sizes, n, lane_sizes=lane_sizes)
    assert lane_plans.value - c0 == 1
    assert laned != base, "lane hotspot must move the cuts"
    # structural safety: contiguous cover of [0, p), at most n ranges
    assert len(laned) <= n
    assert laned[0][0] == 0 and laned[-1][1] == p
    for (a, b), (c, d) in zip(laned, laned[1:]):
        assert b == c

    # balanced lanes change nothing
    even = {src: [25] * p for src in ("la", "lb", "lc", "ld")}
    assert ap.plan(sizes, n, lane_sizes=even) == base


# ----------------------------------------------------------------------
# the wave movers: the Pallas kernels on a TPU, their jnp stand-ins
# elsewhere, one path either way
# ----------------------------------------------------------------------
def _host_send_stack(rows, depth, rows_b, bucket_elems, dtype):
    """The host assembly the device gather replaced: a zeroed
    [depth * rows_b, bucket] stack, each row's payload copied in from
    its slab read back whole, shaped into the movers' lanes."""
    from sparkrdma_tpu.ops import remote_copy

    stack = np.zeros((depth * rows_b, bucket_elems), dtype=dtype)
    for slot, (src, off, n) in rows.items():
        stack[slot, :n] = np.asarray(src)[off : off + n]
    lanes = remote_copy.wave_row_shape(bucket_elems)
    shape = (rows_b, *lanes) if depth == 1 else (depth, rows_b, *lanes)
    return stack.reshape(shape)


# (depth, rows_b, bucket, {slot: (slab elems, offset, length)}); every
# slab is filled to its end, so a tail left unzeroed shows
_GATHER_CASES = {
    "uneven_lengths": (1, 4, 1024, {0: (1024, 0, 1024), 1: (1024, 0, 700),
                                    2: (1024, 0, 3), 3: (1024, 0, 513)}),
    "arena_offset": (1, 2, 1024, {0: (2048, 1000, 1024),
                                  1: (4096, 3000, 1000)}),
    "mixed_classes": (1, 4, 2048, {0: (512, 0, 512), 1: (2048, 0, 2000),
                                   2: (8192, 5000, 2048)}),
    "short_row_tail_zeroed": (1, 2, 1024, {0: (1024, 0, 1), 1: (1024, 0, 0)}),
    "pipelined_depth2": (2, 2, 1024, {0: (1024, 0, 900), 1: (2048, 24, 1024),
                                      3: (512, 0, 256)}),
}


def _gather_rows(spec, dtype, seed):
    import jax

    rng = np.random.default_rng(seed)
    info = np.iinfo(dtype)
    return {
        slot: (jax.device_put(
            rng.integers(1, info.max, size, dtype=dtype, endpoint=True)),
            off, n)
        for slot, (size, off, n) in spec.items()
    }


@pytest.mark.parametrize("case", sorted(_GATHER_CASES))
@pytest.mark.parametrize("dtype", [np.uint32, np.uint8])
def test_send_gather_matches_host_assembly(case, dtype):
    """The device send-stack gather lays out exactly the stack the host
    assembly built: each row's payload from its slab offset, the tail
    past its length zeroed, in the movers' lane layout."""
    import jax

    from sparkrdma_tpu.shuffle.collective import send_stack_shard

    depth, rows_b, bucket, spec = _GATHER_CASES[case]
    rows = _gather_rows(spec, dtype, seed=len(case))
    shard, keys = send_stack_shard(rows, jax.devices()[0], depth, rows_b,
                                   bucket, dtype)
    assert {k[0] for k in keys} == {size for size, _, _ in spec.values()}
    want = _host_send_stack(rows, depth, rows_b, bucket, dtype)
    assert shard.dtype == want.dtype
    np.testing.assert_array_equal(np.asarray(shard), want)


def _gather_programs(stack_shape, dtype=np.uint32):
    from sparkrdma_tpu.shuffle import collective

    return collective._send_gather_program(
        stack_shape, np.dtype(dtype).name)._cache_size()


def test_send_gather_compiles_once_per_slab_classes():
    """Lengths and offsets are runtime operands: waves whose slabs share
    their classes reuse one program, whatever their blocks' lengths."""
    import jax

    from sparkrdma_tpu.shuffle import collective

    collective._send_gather_program.cache_clear()
    dev = jax.devices()[0]
    programs = set()
    for seed, (off0, n0, off1, n1) in enumerate(
            [(0, 1024, 0, 7), (512, 300, 1024, 1024), (3, 1021, 77, 900)]):
        spec = {0: (2048, off0, n0), 1: (4096, off1, n1)}
        rows = _gather_rows(spec, np.uint32, seed)
        shard, keys = collective.send_stack_shard(rows, dev, 1, 2, 1024,
                                                  np.uint32)
        np.testing.assert_array_equal(
            np.asarray(shard),
            _host_send_stack(rows, 1, 2, 1024, np.uint32))
        programs |= keys
    assert {k[0] for k in programs} == {2048, 4096}
    assert collective._send_gather_program.cache_info().currsize == 1
    assert _gather_programs(shard.shape) == 2


def test_send_gather_programs_bounded_by_classes_in_wide_mixed_waves():
    """Waves of many rows whose slab classes mix in a different order
    every time compile one program per class, not one per combination
    of classes over the row slots."""
    import jax

    from sparkrdma_tpu.shuffle import collective

    collective._send_gather_program.cache_clear()
    rng = np.random.default_rng(11)
    classes, rows_b, bucket = (512, 2048, 8192), 12, 2048
    for seed in range(6):
        spec = {}
        for slot in rng.permutation(rows_b)[: rows_b - seed % 3]:
            size = int(rng.choice(classes))
            n = int(rng.integers(0, min(size, bucket) + 1))
            spec[int(slot)] = (size, int(rng.integers(0, size - n + 1)), n)
        rows = _gather_rows(spec, np.uint32, seed)
        shard, _ = collective.send_stack_shard(
            rows, jax.devices()[0], 1, rows_b, bucket, np.uint32)
        np.testing.assert_array_equal(
            np.asarray(shard),
            _host_send_stack(rows, 1, rows_b, bucket, np.uint32))
    assert _gather_programs(shard.shape) == len(classes)


@pytest.mark.parametrize(
    "wave_bytes, depth",
    [("64m", 1), ("128k", 2)],
    ids=["one_wave", "pipelined_waves"],
)
def test_tpu_mesh_device_gathered_waves_land_byte_identical(
        cluster, monkeypatch, wave_bytes, depth):
    """The wave path end to end on a one-device mesh, an identity mover
    in place of the one-chip movers: the send stack gathered on the
    device and its landed rows adopted whole, one slab per block, give
    the host path's bytes, every row counted as device-assembled."""
    from sparkrdma_tpu.ops import remote_copy

    conf, io_map, io_red = cluster
    conf.set("tpu.shuffle.collective.autoTune", "false")
    conf.set("tpu.shuffle.collective.waveBytes", wave_bytes)
    conf.set("tpu.shuffle.collective.pipelineDepth", str(depth))
    data = _publish_shards(io_map, seed=83)
    movers = []

    def mover(_hops, sharded, *d):
        movers.append(d)
        return sharded

    monkeypatch.setattr(remote_copy, "mesh_device_count", lambda: 1)
    monkeypatch.setattr(remote_copy, "pallas_wave_pull", mover)
    monkeypatch.setattr(remote_copy, "pallas_pipelined_wave_pull", mover)
    reg = get_registry()
    before = reg.snapshot()
    got = io_red.fetch_device_blocks(91, 0, 3, timeout_s=30)
    c = reg.delta(before)["counters"]
    try:
        for p in range(3):
            assert len(got[p]) == 3, "one slab per block"
            slabs = [bytes(b.read(0, b.capacity)) for b in got[p]]
            lengths = [b.length for b in got[p]]
            assert sorted(s[:n] for s, n in zip(slabs, lengths)) == sorted(
                a.tobytes() for a in data[p])
            # past its block a slab holds zeros, as the pad gave before
            assert not any(any(s[n:]) for s, n in zip(slabs, lengths))
    finally:
        for bufs in got.values():
            for b in bufs:
                b.free()
    assert c["collective.device_assembled_rows{role=cs-red}"] == 9
    assert (depth,) in movers if depth > 1 else movers


def _clear_wave_programs():
    from sparkrdma_tpu.ops import remote_copy

    remote_copy._wave_pull_program.cache_clear()
    remote_copy._pipelined_wave_pull_program.cache_clear()
    remote_copy._mesh_wave_pull_program.cache_clear()


@pytest.mark.parametrize("depth", [1, 2])
def test_tpu_mesh_pallas_mover_failure_raises(cluster, monkeypatch, depth):
    """The movers are the path: a mover failure raises out of the fetch
    instead of degrading its rows to the host triple, and the pins
    still release."""
    from sparkrdma_tpu.ops import remote_copy

    conf, io_map, io_red = cluster
    conf.set("tpu.shuffle.collective.autoTune", "false")
    conf.set("tpu.shuffle.collective.waveBytes", "192k")
    conf.set("tpu.shuffle.collective.pipelineDepth", str(depth))
    _publish_shards(io_map, seed=97)
    degrades = _counter("collective.degrades", "cs-red")
    d0 = degrades.value

    def broken(*_a, **_k):
        raise RuntimeError("injected: pallas mover failed")

    monkeypatch.setattr(remote_copy, "pallas_wave_pull", broken)
    monkeypatch.setattr(remote_copy, "pallas_pipelined_wave_pull", broken)
    monkeypatch.setattr(remote_copy, "pallas_mesh_wave_pull", broken)
    with pytest.raises(RuntimeError, match="injected: pallas mover"):
        io_red.fetch_host_blocks(91, 0, 3, timeout_s=30)
    assert degrades.value == d0, "a mover failure must not degrade"
    assert not io_map.device_buffers._pins


SENTINEL_WORD = 0xA5A5A5A5


def _check_mesh_epoch(lane, send, landed, depth, n, held, sentinel):
    """One n > 1 epoch, read back: each chip's send shard holds only rows
    of blocks resident on it (the rest zero), as many rows as the
    busiest chip sends, bucketed; each slot landed on its receiving chip
    only, every other chip's slot still holding the sentinel. Returns
    the cross-chip rows' (payload, bucket) bytes."""
    from sparkrdma_tpu.ops.exchange import round_rows

    def shards(arr):
        by_dev = {s.device.id: np.asarray(s.data) for s in
                  arr.addressable_shards}
        return [by_dev[d] for d in sorted(by_dev)]

    lane = np.asarray(lane).reshape(3, -1)
    sends, lands = shards(send), shards(landed)
    slots = lane.shape[1]
    assert lands[0].shape[0] == slots and slots % depth == 0
    counts = [int(np.sum(lane[0] == k)) for k in range(n)]
    assert sends[0].shape[0] == round_rows(max(counts))
    payload = moved = 0
    for k in range(n):
        rows = sends[k].reshape(sends[k].shape[0], -1)
        mine = {int(lane[1, j]) for j in range(slots) if lane[0, j] == k}
        assert mine == set(range(counts[k]))
        for r in range(rows.shape[0]):
            if r in mine:
                assert rows[r].tobytes() in held[k]
            else:
                assert not rows[r].any()
    for j in range(slots):
        src, row, hop = (int(v) for v in lane[:, j])
        for k in range(n):
            got = lands[k][j].reshape(-1)
            if src >= 0 and (src + hop) % n == k:
                want = sends[src][row].reshape(-1)
                np.testing.assert_array_equal(got, want)
            else:
                assert (got == sentinel).all(), (j, k)
        if src >= 0 and hop:
            payload += len(sends[src][row].tobytes())
            moved += sends[src][row].nbytes
    return payload, moved


@pytest.mark.parametrize("depth", [1, 2])
def test_pallas_wave_fetch_four_devices_interpreted(monkeypatch, depth):
    """chip_smoke.py's --chips 4 wave phase, rehearsed on the CPU mesh:
    the real Pallas wave programs in TPU interpret mode (remote DMAs and
    semaphores simulated across devices), four executors with arenas
    on four devices, at depth 1 and 2 — byte-identical to the host
    path, carried by the Pallas movers only. Every epoch sends only
    held rows: each chip's send shard holds the rows resident on it and
    no more, no slot lands anywhere but its receiving chip (a sentinel
    receive buffer shows it), ``collective.ici_payload_bytes`` is the
    cross-chip rows' lengths and ``ici_moved_bytes`` their buckets, and
    each executor allocates one receive buffer, reused by its later
    epochs."""
    import importlib.util
    import os

    import jax
    import jax.numpy as jnp
    from jax.experimental.pallas import tpu as pltpu

    from sparkrdma_tpu.ops import remote_copy
    from sparkrdma_tpu.ops.exchange import round_bucket
    from sparkrdma_tpu.shuffle.device_io import DeviceShuffleIO

    spec = importlib.util.spec_from_file_location(
        "chip_smoke",
        os.path.join(os.path.dirname(__file__), "..", "chip_smoke.py"),
    )
    chip_smoke = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(chip_smoke)

    n, block_keys = 4, 1 << 12
    held = {k: set() for k in range(n)}
    publish = DeviceShuffleIO.publish_device_blocks

    def recording_publish(io, sid, parts, *a, **k):
        dev = io.device_buffers.device.id
        held[dev].update(np.asarray(v).tobytes() for v in parts.values())
        return publish(io, sid, parts, *a, **k)

    epochs, allocated = [], []
    mesh_pull = remote_copy._mesh_wave_kernel

    def checked_pull(lane, send, recv, d):
        # the pooled buffer is laid out as the send stack is
        assert recv.sharding.is_equivalent_to(send.sharding, recv.ndim)
        # a sentinel receive buffer in place of the pooled one
        sentinel = jnp.full(recv.shape, SENTINEL_WORD, recv.dtype,
                            device=recv.sharding)
        landed = mesh_pull(lane, send, sentinel, d)
        epochs.append(_check_mesh_epoch(lane, send, landed, d, n, held,
                                        SENTINEL_WORD))
        allocated.append((send.nbytes, recv.nbytes))
        return landed

    monkeypatch.setattr(DeviceShuffleIO, "publish_device_blocks",
                        recording_publish)
    # a 4-device mesh, as on the 4-chip host (the interpreter's
    # cross-device rendezvous stalls on the 8-device farm's thread pool)
    monkeypatch.setattr(remote_copy, "mesh_device_count", lambda: n)
    monkeypatch.setattr(remote_copy, "pallas_mesh_wave_pull", checked_pull)
    _clear_wave_programs()
    reg = get_registry()
    before = reg.snapshot()
    pltpu.set_tpu_interpret_mode(pltpu.InterpretParams())
    try:
        rec = chip_smoke.phase_wave_fetch(
            jax.devices()[:n], block_keys=block_keys, transport="python",
            depths=(depth,),
        )
    finally:
        pltpu.set_tpu_interpret_mode(None)
        _clear_wave_programs()
    c = reg.delta(before)["counters"]

    def total(name):
        return sum(v for key, v in c.items() if key.split("{")[0] == name)

    mover = {1: "pallas_wave_pull", 2: "pallas_pipelined_wave_pull"}[depth]
    assert rec["arena_devices"] == [d.id for d in jax.devices()[:n]]
    assert rec[f"depth{depth}"]["movers"][mover] > 0
    reducers, block_bytes = rec["reducers"], rec["block_bytes"]
    assert rec[f"depth{depth}"]["blocks_pulled"] == n * reducers
    # every reducer takes one block from each chip, n - 1 of them remote
    crossing = reducers * (n - 1)
    assert epochs and sum(p for p, _ in epochs) == crossing * block_bytes
    assert total("collective.ici_payload_bytes") == crossing * block_bytes
    assert total("collective.ici_moved_bytes") == sum(m for _, m in epochs)
    assert total("collective.ici_moved_bytes") <= (
        crossing * round_bucket(block_bytes))
    # each epoch's send shards, and one receive buffer per executor:
    # its later waves reuse it
    (recv_bytes,) = {r for _, r in allocated}
    assert total("collective.wave_mesh_bytes") == (
        sum(s for s, _ in allocated) + n * recv_bytes)
    if depth == 1:
        # two waves per executor went through one buffer
        assert len(allocated) == 2 * n


def _mover_operands(mover, depth, rng):
    """A hop or slot lane and a stack for one mover, on one chip or on a
    4-device mesh: returns ``(run, stack)``, ``run(kernel)`` landing the
    epoch through the Pallas program (``kernel``) or the entry point."""
    import jax
    import jax.numpy as jnp
    from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

    from sparkrdma_tpu.ops import remote_copy

    rows, lanes = 4, remote_copy.wave_row_shape(1024)
    if mover == "one_chip":
        mesh = Mesh(jax.devices()[:1], ("x",))
        shape = (rows, *lanes) if depth == 1 else (depth, rows, *lanes)
        hops = jax.device_put(np.zeros(shape[:-2], np.int32),
                              NamedSharding(mesh, P()))
        stack = jax.device_put(
            rng.integers(1, 1 << 32, shape, dtype=np.uint32),
            NamedSharding(mesh, P("x")))

        def run(kernel):
            if depth == 1:
                pull = (remote_copy._wave_kernel if kernel
                        else remote_copy.pallas_wave_pull)
                return pull(hops, stack)
            pull = (remote_copy._pipelined_wave_kernel if kernel
                    else remote_copy.pallas_pipelined_wave_pull)
            return pull(hops, stack, depth)

        return run, stack
    n, send_rows, slots = 4, 4, depth * rows
    mesh = Mesh(jax.devices()[:n], ("x",))
    sharded = NamedSharding(mesh, P("x"))
    # per slot: source chip (-1 for a few: no row), send row, hop
    lane = np.stack([
        rng.integers(-1, n, slots), rng.integers(0, send_rows, slots),
        rng.integers(0, n, slots),
    ]).astype(np.int32)
    lane[0, :n] = np.arange(n)  # every chip sends at least once
    lane_dev = jax.device_put(lane.reshape(-1), NamedSharding(mesh, P()))
    send = jax.device_put(
        rng.integers(1, 1 << 32, (n * send_rows, *lanes), dtype=np.uint32),
        sharded)

    def run(kernel):
        recv = jnp.full((n * slots, *lanes), SENTINEL_WORD, np.uint32,
                        device=sharded)
        pull = (remote_copy._mesh_wave_kernel if kernel
                else remote_copy.pallas_mesh_wave_pull)
        return pull(lane_dev, send, recv, depth)

    return run, send


@pytest.mark.parametrize("depth", [1, 2])
@pytest.mark.parametrize("mover", ["one_chip", "mesh"])
def test_wave_mover_stand_ins_match_pallas_programs_interpreted(
        monkeypatch, mover, depth):
    """Off a TPU each wave mover runs a jnp stand-in, and it lands the
    same array, laid out over the same devices, as the mover's Pallas
    program in TPU interpret mode: on one chip a fresh array holding the
    stack, on a 4-device mesh (the interpreter stalls on the 8-device
    farm) each slot's row on its receiving chip only, every other slot
    still holding the sentinel the receive buffer came with."""
    import jax
    from jax.experimental.pallas import tpu as pltpu

    from sparkrdma_tpu.ops import remote_copy

    monkeypatch.setattr(remote_copy, "mesh_device_count",
                        lambda: 1 if mover == "one_chip" else 4)
    run, stack = _mover_operands(mover, depth, np.random.default_rng(depth))
    stand_in = run(kernel=False)
    _clear_wave_programs()
    pltpu.set_tpu_interpret_mode(pltpu.InterpretParams())
    try:
        pallas = jax.block_until_ready(run(kernel=True))
    finally:
        pltpu.set_tpu_interpret_mode(None)
        _clear_wave_programs()
    assert stand_in.shape == pallas.shape and stand_in.dtype == pallas.dtype
    assert stand_in.sharding.is_equivalent_to(pallas.sharding, pallas.ndim)
    want = np.asarray(pallas)
    if mover == "mesh":
        assert (want == SENTINEL_WORD).any() and (want != SENTINEL_WORD).any()
    # the landed array is the mover's own: it outlives the stack
    stack.delete()
    np.testing.assert_array_equal(np.asarray(stand_in), want)


def test_one_chip_wave_keeps_its_program_and_stack_shape(cluster,
                                                        monkeypatch):
    """On a one-device mesh the wave path is the one-chip path it was:
    the one-chip movers get a hop lane of zeros and a [rows_b, *lanes]
    stack over the one-device mesh, the compiler resolves the same
    (rows class, bucket class, dtype) wave key, and no byte counts as
    crossing chips."""
    from jax.sharding import NamedSharding

    from sparkrdma_tpu.ops import remote_copy
    from sparkrdma_tpu.ops.exchange import round_bucket, round_rows

    conf, io_map, io_red = cluster
    conf.set("tpu.shuffle.collective.autoTune", "false")
    conf.set("tpu.shuffle.collective.pipelineDepth", "1")
    _publish_shards(io_map, seed=61)
    calls = []

    def mover(hops, stack, *d):
        calls.append((np.asarray(hops), stack, d))
        return stack

    def no_mesh(*_a, **_k):
        raise AssertionError("the mesh mover ran on one device")

    monkeypatch.setattr(remote_copy, "mesh_device_count", lambda: 1)
    monkeypatch.setattr(remote_copy, "pallas_wave_pull", mover)
    monkeypatch.setattr(remote_copy, "pallas_mesh_wave_pull", no_mesh)
    compiler = io_red._collective
    compiler._seen_programs.clear()
    reg = get_registry()
    before = reg.snapshot()
    got = io_red.fetch_device_blocks(91, 0, 3, timeout_s=30)
    c = reg.delta(before)["counters"]
    for bufs in got.values():
        for b in bufs:
            b.free()
    rows_b, bucket = round_rows(9), round_bucket(BLOCK + 2)
    lanes = remote_copy.wave_row_shape(bucket)
    ((hops, stack, depth),) = calls
    assert depth == () and hops.shape == (rows_b,) and not hops.any()
    assert stack.shape == (rows_b, *lanes)
    assert isinstance(stack.sharding, NamedSharding)
    assert stack.sharding.mesh.devices.size == 1
    assert ("wave", rows_b, bucket, "uint8") in compiler._seen_programs
    assert {k[0] for k in compiler._seen_programs} == {
        "wave", "send-gather", "take"}
    assert c.get("collective.ici_payload_bytes{role=cs-red}", 0) == 0
    assert c.get("collective.ici_moved_bytes{role=cs-red}", 0) == 0
    # the send stack and the kernel's output
    assert c["collective.wave_mesh_bytes{role=cs-red}"] == 2 * stack.nbytes
