"""The program's spans on the profiler's clock: a map sort, its staging
and a wave fetch through ``DeviceShuffleIO`` under ``jax.profiler``,
read back from the ``.xplane.pb``; the span-duration histograms and the
HBM arena's slab counters they feed; and the tracer's off switch."""

import glob
import os

import numpy as np
import pytest

from sparkrdma_tpu.obs import Tracer, get_registry
from sparkrdma_tpu.obs import attr
from sparkrdma_tpu.shuffle.handle import BaseShuffleHandle, HashPartitioner
from sparkrdma_tpu.shuffle.manager import TpuShuffleManager
from sparkrdma_tpu.utils.config import TpuShuffleConf

SID = 93
REDUCERS = 3
MAPS = 2
SHARD_KEYS = 3 * 8192  # blocks of ~32 KiB: above deviceFetch.minBlockBytes

INNER_SPANS = (
    "map.sort.pad", "map.sort.h2d", "map.sort.device", "map.sort.d2h",
    "map.stage.copy", "map.stage.checksum", "map.stage.arena",
    "fetch.resolve", "fetch.plan", "fetch.wave.assemble", "fetch.wave.wait",
    "fetch.wave.adopt", "shuffle.collective.wave",
)


def _host_events(trace_dir):
    """(name, start ns, end ns, line id, stats) of every host-plane
    event in the newest profile under ``trace_dir``."""
    from jax.profiler import ProfileData

    path = sorted(glob.glob(os.path.join(str(trace_dir), "**",
                                         "*.xplane.pb"), recursive=True),
                  key=os.path.getmtime)[-1]
    out = []
    for plane in ProfileData.from_file(path).planes:
        if not plane.name.startswith("/host:"):
            continue
        for k, line in enumerate(plane.lines):
            for ev in line.events:
                out.append((ev.name, ev.start_ns,
                            ev.start_ns + ev.duration_ns, (plane.name, k),
                            dict(ev.stats)))
    return out


def _hist_counts(delta):
    return {k: h["count"] for k, h in delta["histograms"].items()}


@pytest.fixture(scope="module")
def job(tmp_path_factory):
    """One map stage (two shards sorted on the device, cut, staged and
    published) and one reduce-side wave fetch of every partition, under
    a profiler session. Returns what the assertions read."""
    import jax

    from sparkrdma_tpu.models import MapShardSorter
    from sparkrdma_tpu.shuffle.device_io import DeviceShuffleIO
    from sparkrdma_tpu.shuffle.writer.pipeline import MapTaskPipeline

    trace_dir = tmp_path_factory.mktemp("prof")
    rng = np.random.default_rng(5)
    keys = rng.integers(0, 1 << 32, MAPS * SHARD_KEYS, dtype=np.uint32)
    shards = np.split(keys, MAPS)
    edges = np.asarray([(r << 32) // REDUCERS for r in range(1, REDUCERS)],
                       np.uint32)
    conf = TpuShuffleConf({"tpu.shuffle.transport": "python"})
    driver = TpuShuffleManager(conf, is_driver=True)
    ex_map = TpuShuffleManager(conf, is_driver=False, executor_id="ps-map")
    ex_red = TpuShuffleManager(conf, is_driver=False, executor_id="ps-red")
    driver.register_shuffle(BaseShuffleHandle(
        shuffle_id=SID, num_maps=MAPS,
        partitioner=HashPartitioner(REDUCERS)))
    io_map, io_red = DeviceShuffleIO(ex_map), DeviceShuffleIO(ex_red)
    sorter = MapShardSorter(tracer=ex_map.tracer)
    sorter.warm(SHARD_KEYS, len(edges))
    reg = get_registry()
    waves = reg.counter("collective.waves", role="ps-red", schedule="ring")
    try:
        before, w0 = reg.snapshot(), waves.value
        opts = jax.profiler.ProfileOptions()
        opts.python_tracer_level = 0
        opts.host_tracer_level = 1
        jax.profiler.start_trace(str(trace_dir), profiler_options=opts)
        try:
            def stage(_m, out):
                local, bounds = out
                return io_map.stage_device_blocks(SID, {
                    r: local[bounds[r]: bounds[r + 1]]
                    for r in range(REDUCERS)})

            MapTaskPipeline(
                lambda m: sorter.sort_partition(shards[m], edges), stage,
                lambda _m, locs: io_map.publish_staged(SID, locs),
                role="ps-writer",
            ).run(range(MAPS))
            blocks = io_red.fetch_host_blocks(SID, 0, REDUCERS,
                                              dtype=np.uint32)
            got = {}
            for r, bl in blocks.items():
                for hb in bl:
                    n = hb.length // 4
                    dev = io_red.stage_host_block(hb, dtype=np.uint32)
                    got.setdefault(r, []).extend(
                        np.asarray(dev.array)[:n].tolist())
                    dev.free()
        finally:
            jax.profiler.stop_trace()
        delta = reg.delta(before)
        yield {
            "events": _host_events(trace_dir), "delta": delta,
            "waves": waves.value - w0, "blocks": blocks, "got": got,
            "keys": keys, "edges": edges,
            "spans": ex_red.tracer.spans(),
        }
    finally:
        io_red.stop()
        io_map.stop()
        ex_red.stop()
        ex_map.stop()
        driver.stop()


def test_the_job_sorts_every_key_into_its_reducer(job):
    want = np.sort(job["keys"])
    cuts = np.searchsorted(want, job["edges"])
    for r, part in enumerate(np.split(want, cuts)):
        assert sorted(job["got"][r]) == part.tolist()
    # every block travelled in a wave, not on the host path
    assert job["waves"] > 0
    assert sum(len(b) for b in job["blocks"].values()) == MAPS * REDUCERS


@pytest.mark.parametrize("name", INNER_SPANS)
def test_each_inner_span_lands_on_the_profiler_host_plane(job, name):
    found = [ev for ev in job["events"] if ev[0] == name]
    assert found, name
    # the role stat tells a program span from the runtime's own events
    assert all("role" in ev[4] for ev in found)


def test_arena_staging_nests_inside_the_pipeline_stage_span(job):
    events = job["events"]
    stages = [ev for ev in events if ev[0] == "writer.pipeline.stage"]
    arena = [ev for ev in events if ev[0] == "map.stage.arena"]
    assert len(stages) == MAPS and len(arena) == MAPS * REDUCERS
    for name, s, e, line, _ in arena:
        assert any(line == ln and s0 <= s and e <= e0
                   for _, s0, e0, ln, _ in stages), (name, s, e)


def test_span_histograms_count_one_per_block_shard_and_wave(job):
    counts = _hist_counts(job["delta"])
    blocks = MAPS * REDUCERS
    for name in ("map.sort.pad", "map.sort.h2d", "map.sort.device",
                 "map.sort.d2h"):
        assert counts[name + "_ms"] == MAPS, name
    for name in ("map.stage.copy", "map.stage.checksum", "map.stage.arena"):
        assert counts[name + "_ms"] == blocks, name
    assert counts["fetch.resolve_ms"] == 1
    assert counts["fetch.plan_ms"] == 1
    # one mover dispatch per pipeline entry: a same-class run of waves
    # shares one entry
    entries = sum(
        v for k, v in job["delta"]["counters"].items()
        if k.startswith("collective.mover_dispatches{")
        and "role=ps-red" in k)
    assert 0 < entries <= job["waves"]
    # the assembly is timed at each wave's pin and at the entry's gather
    assert counts["fetch.wave.assemble_ms"] == job["waves"] + entries
    for name in ("fetch.wave.h2d", "fetch.wave.wait"):
        assert counts[name + "_ms"] == entries, name
    assert counts["fetch.wave.adopt_ms"] == job["waves"]


def test_collective_wave_is_a_context_span_classified_dma_wave(job):
    spans = job["spans"]
    by_id = {sp.span_id: sp for sp in spans}
    wave = [sp for sp in spans if sp.name == "shuffle.collective.wave"]
    assert len(wave) == job["waves"]
    for sp in wave:
        assert by_id[sp.parent_id].name == "shuffle.collective"
        assert sp.end > sp.start
    adopt = [sp for sp in spans if sp.name == "fetch.wave.adopt"]
    assert {by_id[sp.parent_id].name for sp in adopt} == {
        "shuffle.collective.wave"}
    assert attr.classify("shuffle.collective.wave") == attr.DMA_WAVE
    assert attr.classify("fetch.wave.wait") == attr.DMA_WAVE
    assert attr.classify("map.stage.arena") == attr.DEVICE_COMPUTE
    assert attr.classify("fetch.resolve") == attr.RPC


def test_arena_slab_counters_count_both_ends_of_the_job(job):
    c = job["delta"]["counters"]
    payload = 4 * MAPS * SHARD_KEYS
    # staged on the map side, adopted again on the reduce side
    assert c["hbm.slab_payload_bytes"] == 2 * payload
    assert c["hbm.slab_payload_bytes"] < c["hbm.slab_bytes"]


# ----------------------------------------------------------------------
# the tracer's off switch, and the slab counters alone
# ----------------------------------------------------------------------
def test_a_disabled_tracer_writes_nothing_to_the_trace(tmp_path):
    import jax

    on, off = Tracer(role="ps-on"), Tracer(role="ps-off", enabled=False)
    hist = get_registry().histogram("ps.off.timed_ms")
    n0 = hist.snapshot()["count"]
    jax.profiler.start_trace(str(tmp_path))
    try:
        with on.span("ps.on.span"):
            pass
        with off.span("ps.off.span"), off.timed("ps.off.timed"):
            pass
    finally:
        jax.profiler.stop_trace()
    names = {ev[0] for ev in _host_events(tmp_path)}
    assert "ps.on.span" in names
    assert not names & {"ps.off.span", "ps.off.timed"}
    assert off.spans() == []
    # the duration histogram is a metric, not the trace: it still counts
    assert hist.snapshot()["count"] == n0 + 1


def test_slab_counters_sum_payload_and_class_bytes():
    from sparkrdma_tpu.ops.hbm_arena import DeviceBufferManager

    sizes = [1000, 16384, 20000, 70000, 20000]
    classes = [16384, 16384, 32768, 131072, 32768]
    reg = get_registry()
    mgr = DeviceBufferManager()
    try:
        before = reg.snapshot()
        bufs = [mgr.get(n) for n in sizes[:4]]
        bufs[2].free()
        bufs.append(mgr.get(sizes[4]))  # a pooled slab counts again
        c = reg.delta(before)["counters"]
    finally:
        mgr.stop()
    assert c["hbm.slab_payload_bytes"] == sum(sizes)
    assert c["hbm.slab_bytes"] == sum(classes)


def test_tpu_wave_assembly_times_its_transfer_and_counts_its_bytes(
        monkeypatch):
    """The wave path's send stack is gathered on the device from the
    pinned source slabs, with an identity mover in place of the one-chip
    movers on a one-device mesh: the rows land byte-identical, no source
    slab is read back to the host, ``collective.device_assembled_rows``
    counts every row moved, and ``fetch.wave.h2d`` times only the hop
    lane's transfer."""
    import jax

    from sparkrdma_tpu.ops import remote_copy
    from sparkrdma_tpu.ops.hbm_arena import _size_class
    from sparkrdma_tpu.shuffle import collective
    from sparkrdma_tpu.shuffle.device_io import DeviceShuffleIO

    sent = []

    def mover(_hops, sharded, *_depth):
        sent.append(sharded)
        return sharded

    monkeypatch.setattr(remote_copy, "mesh_device_count", lambda: 1)
    monkeypatch.setattr(remote_copy, "pallas_wave_pull", mover)
    monkeypatch.setattr(remote_copy, "pallas_pipelined_wave_pull", mover)
    readbacks = []

    class RecordingNumpy:
        """numpy for the schedule compiler, noting each array it reads
        back to the host"""

        def __getattr__(self, name):
            return getattr(np, name)

        def asarray(self, a, *args, **kwargs):
            if isinstance(a, jax.Array):
                readbacks.append(a.shape)
            return np.asarray(a, *args, **kwargs)

    conf = TpuShuffleConf({"tpu.shuffle.transport": "python"})
    driver = TpuShuffleManager(conf, is_driver=True)
    ex_map = TpuShuffleManager(conf, is_driver=False, executor_id="pt-map")
    ex_red = TpuShuffleManager(conf, is_driver=False, executor_id="pt-red")
    driver.register_shuffle(BaseShuffleHandle(
        shuffle_id=SID + 1, num_maps=1, partitioner=HashPartitioner(3)))
    io_map, io_red = DeviceShuffleIO(ex_map), DeviceShuffleIO(ex_red)
    try:
        rng = np.random.default_rng(3)
        data = {p: rng.integers(0, 256, (64 << 10) + p, np.uint8)
                for p in range(3)}
        io_map.publish_staged(SID + 1, io_map.stage_device_blocks(
            SID + 1, data))
        before = get_registry().snapshot()
        with monkeypatch.context() as m:
            m.setattr(collective, "np", RecordingNumpy())
            blocks = io_red.fetch_host_blocks(SID + 1, 0, 3)
        delta = get_registry().delta(before)
        for p, bl in blocks.items():
            (hb,) = bl
            dev = io_red.stage_host_block(hb)
            assert np.array_equal(np.asarray(dev.array)[: hb.length],
                                  data[p])
    finally:
        io_red.stop()
        io_map.stop()
        ex_red.stop()
        ex_map.stop()
        driver.stop()
    c, hist = delta["counters"], _hist_counts(delta)
    assert c['collective.device_assembled_rows{role=pt-red}'] == 3
    assert c['device_fetch.plane.pulls{role=pt-red}'] == 3
    assert sent and hist["fetch.wave.h2d_ms"] == len(sent)
    assert {_size_class(len(d)) for d in data.values()} == {
        64 << 10, 128 << 10}
    assert readbacks == []
