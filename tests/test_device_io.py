"""Device shuffle IO: HBM -> registered host memory -> one-sided READ -> HBM."""

import numpy as np
import pytest

import jax.numpy as jnp

from sparkrdma_tpu.shuffle.device_io import DeviceShuffleIO
from sparkrdma_tpu.shuffle.handle import BaseShuffleHandle, HashPartitioner
from sparkrdma_tpu.shuffle.manager import TpuShuffleManager
from sparkrdma_tpu.utils.config import TpuShuffleConf


@pytest.fixture
def cluster():
    # python transport: several tests here script TpuChannel read
    # behavior (fault/deadline/ordering) at the python verb layer; the
    # auto default would resolve to native and bypass those seams
    conf = TpuShuffleConf({"tpu.shuffle.transport": "python"})
    driver = TpuShuffleManager(conf, is_driver=True)
    ex0 = TpuShuffleManager(conf, is_driver=False, executor_id="exec-0")
    ex1 = TpuShuffleManager(conf, is_driver=False, executor_id="exec-1")
    yield conf, driver, ex0, ex1
    ex0.stop()
    ex1.stop()
    driver.stop()


def test_device_block_shuffle_roundtrip(cluster):
    conf, driver, ex0, ex1 = cluster
    handle = BaseShuffleHandle(shuffle_id=1, num_maps=2, partitioner=HashPartitioner(4))
    driver.register_shuffle(handle)

    io0 = DeviceShuffleIO(ex0)
    io1 = DeviceShuffleIO(ex1)
    try:
        # each executor publishes two device-array partitions
        a = {0: jnp.arange(100, dtype=jnp.uint8), 1: jnp.ones((300,), jnp.uint8)}
        b = {2: jnp.full((50,), 7, jnp.uint8), 3: jnp.zeros((200,), jnp.uint8)}
        io0.publish_device_blocks(1, a)
        io1.publish_device_blocks(1, b)

        # ex0 pulls everything (partitions 2,3 are remote one-sided READs,
        # 0,1 short-circuit locally)
        got = io0.fetch_device_blocks(1, 0, 4)
        assert set(got) == {0, 1, 2, 3}
        np.testing.assert_array_equal(
            np.frombuffer(got[0][0].read(), np.uint8), np.arange(100, dtype=np.uint8)
        )
        np.testing.assert_array_equal(
            np.frombuffer(got[2][0].read(), np.uint8), np.full((50,), 7, np.uint8)
        )
        # fetched blocks live in HBM slabs under the device pool budget
        assert io0.device_buffers.in_use_bytes > 0
        for bufs in got.values():
            for buf in bufs:
                buf.free()
        assert io0.device_buffers.in_use_bytes == 0
    finally:
        io0.stop()
        io1.stop()


def test_fetch_under_hbm_budget_pressure_spills_and_survives():
    """A tight ``hbm.maxBytes`` forces staged blocks to spill to the
    host tier DURING a fetch; held buffers stay readable (transparent
    host-tier read), restore on demand, and the budget never exceeds
    the cap. This drives SURVEY §7.3(4)'s tiered HBM->host store
    through the real publish/fetch stack rather than the pool alone."""
    conf = TpuShuffleConf({"tpu.shuffle.hbm.maxBytes": str(64 * 1024)})
    driver = TpuShuffleManager(conf, is_driver=True)
    ex0 = TpuShuffleManager(conf, is_driver=False, executor_id="sp-0")
    ex1 = TpuShuffleManager(conf, is_driver=False, executor_id="sp-1")
    parts = 6
    handle = BaseShuffleHandle(
        shuffle_id=9, num_maps=2, partitioner=HashPartitioner(parts)
    )
    driver.register_shuffle(handle)
    io0, io1 = DeviceShuffleIO(ex0), DeviceShuffleIO(ex1)
    rng = np.random.default_rng(5)
    # 12 blocks x 16 KiB class = 192 KiB of staging demand vs a 64 KiB cap
    data = {
        (m, p): rng.integers(0, 256, 16 * 1024 - 128, dtype=np.uint8)
        for m in range(2)
        for p in range(parts)
    }
    try:
        io0.publish_device_blocks(9, {p: data[(0, p)] for p in range(parts)})
        io1.publish_device_blocks(9, {p: data[(1, p)] for p in range(parts)})
        held = io0.fetch_device_blocks(9, 0, parts, timeout_s=60)
        pool = io0.device_buffers
        assert pool.spill_count > 0, "cap of 4 slabs never spilled"
        assert pool.in_use_bytes <= 64 * 1024
        spilled = [b for bufs in held.values() for b in bufs if b.spilled]
        assert spilled, "no held buffer ended up on the host tier"
        # every block byte-exact, whichever tier it lives on
        for p, bufs in held.items():
            got = sorted(b.read(0, b.length) for b in bufs)
            want = sorted(data[(m, p)].tobytes() for m in range(2))
            assert got == want, f"partition {p} bytes differ under spill"
        # explicit restore works and respects the cap by evicting others
        spilled[0].ensure_device()
        assert not spilled[0].spilled
        assert pool.in_use_bytes <= 64 * 1024
        for bufs in held.values():
            for b in bufs:
                b.free()
        assert pool.in_use_bytes == 0
    finally:
        io0.stop()
        io1.stop()
        ex0.stop()
        ex1.stop()
        driver.stop()


def test_fetch_fault_surfaces_and_leaks_nothing(cluster, monkeypatch):
    """Inject a READ fault at the verb seam during a device-block
    fetch: the caller gets FetchFailedError (engine recompute signal,
    SURVEY §5.1 #9) and BOTH pools drain — staged HBM slabs freed,
    every in-flight registered destination buffer reclaimed by
    whichever of caller/listener turns out to be its last owner."""
    import threading

    from sparkrdma_tpu.shuffle.errors import FetchFailedError
    from sparkrdma_tpu.transport.channel import ChannelError, TpuChannel

    conf, driver, ex0, ex1 = cluster
    handle = BaseShuffleHandle(
        shuffle_id=5, num_maps=2, partitioner=HashPartitioner(4)
    )
    driver.register_shuffle(handle)
    io0, io1 = DeviceShuffleIO(ex0), DeviceShuffleIO(ex1)
    rng = np.random.default_rng(3)
    try:
        io0.publish_device_blocks(
            5, {p: rng.integers(0, 256, 5000, np.uint8) for p in range(4)}
        )
        io1.publish_device_blocks(
            5, {p: rng.integers(0, 256, 5000, np.uint8) for p in range(4)}
        )
        state = {"remaining": 1}
        lock = threading.Lock()
        original = TpuChannel.read_in_queue

        def flaky(self, listener, dst_views, blocks):
            with lock:
                inject = state["remaining"] > 0
                if inject:
                    state["remaining"] -= 1
            if inject:
                listener.on_failure(ChannelError("injected device-fetch fault"))
                return
            return original(self, listener, dst_views, blocks)

        monkeypatch.setattr(TpuChannel, "read_in_queue", flaky)
        with pytest.raises(FetchFailedError):
            io0.fetch_device_blocks(5, 0, 4, timeout_s=30)
        # nothing leaked on either tier
        assert io0.device_buffers.in_use_bytes == 0
        # all registered destination buffers back in the pool: a clean
        # retry (fault healed) succeeds and is byte-exact
        state["remaining"] = 0
        got = io0.fetch_device_blocks(5, 0, 4, timeout_s=30)
        assert sum(len(b) for b in got.values()) == 8
        for bufs in got.values():
            for b in bufs:
                b.free()
        assert io0.device_buffers.in_use_bytes == 0
    finally:
        io0.stop()
        io1.stop()


def test_fetch_deadline_is_total_not_per_block(cluster, monkeypatch):
    """One slow peer costs at most ONE timeout: ``timeout_s`` is a
    deadline for the whole fetch (RdmaShuffleFetcherIterator.scala:
    108-122 semantics), so wall stays ~timeout_s even with every
    remote block wedged — not n_blocks x timeout_s."""
    import threading
    import time as _time

    from sparkrdma_tpu.shuffle.errors import FetchFailedError
    from sparkrdma_tpu.transport.channel import TpuChannel

    conf, driver, ex0, ex1 = cluster
    handle = BaseShuffleHandle(
        shuffle_id=11, num_maps=2, partitioner=HashPartitioner(4)
    )
    driver.register_shuffle(handle)
    io0, io1 = DeviceShuffleIO(ex0), DeviceShuffleIO(ex1)
    rng = np.random.default_rng(7)
    timers = []
    try:
        io0.publish_device_blocks(
            11, {p: rng.integers(0, 256, 5000, np.uint8) for p in range(4)}
        )
        io1.publish_device_blocks(
            11, {p: rng.integers(0, 256, 5000, np.uint8) for p in range(4)}
        )

        def wedged(self, listener, dst_views, blocks):
            # every remote read "completes" far beyond the deadline
            t = threading.Timer(30.0, lambda: listener.on_success(None))
            t.daemon = True
            timers.append(t)
            t.start()

        monkeypatch.setattr(TpuChannel, "read_in_queue", wedged)
        t0 = _time.perf_counter()
        with pytest.raises(FetchFailedError, match="deadline"):
            io0.fetch_device_blocks(11, 0, 4, timeout_s=1.5)
        wall = _time.perf_counter() - t0
        # 4 wedged blocks: per-block waits would take ~6 s; one shared
        # deadline takes ~1.5 s
        assert wall < 4.0, f"fetch wall {wall:.1f}s — deadline not shared"
        assert io0.device_buffers.in_use_bytes == 0
    finally:
        for t in timers:
            t.cancel()
        io0.stop()
        io1.stop()


def test_fetch_stages_in_arrival_order(cluster, monkeypatch):
    """A delayed block must not hold up the staging of blocks that
    already arrived: staging is completion-driven, so the slow block
    stages LAST regardless of issue order."""
    import threading

    from sparkrdma_tpu.transport.channel import TpuChannel

    conf, driver, ex0, ex1 = cluster
    handle = BaseShuffleHandle(
        shuffle_id=12, num_maps=1, partitioner=HashPartitioner(4)
    )
    driver.register_shuffle(handle)
    io0, io1 = DeviceShuffleIO(ex0), DeviceShuffleIO(ex1)
    rng = np.random.default_rng(9)
    slow_len = 7777  # unique length marks the delayed block
    try:
        # remote publisher: partition 0 (issued FIRST) is the slow one
        io1.publish_device_blocks(
            12,
            {
                0: rng.integers(0, 256, slow_len, np.uint8),
                **{p: rng.integers(0, 256, 5000, np.uint8) for p in (1, 2, 3)},
            },
        )
        original = TpuChannel.read_in_queue

        def delaying(self, listener, dst_views, blocks):
            if blocks[0][2] == slow_len:
                t = threading.Timer(
                    0.8, lambda: original(self, listener, dst_views, blocks)
                )
                t.daemon = True
                t.start()
                return
            return original(self, listener, dst_views, blocks)

        monkeypatch.setattr(TpuChannel, "read_in_queue", delaying)
        staged_lens = []
        real_stage = io0.device_buffers.stage_view

        def recording(view, valid_len=None, dtype=np.uint8):
            staged_lens.append(valid_len)
            return real_stage(view, valid_len, dtype)

        monkeypatch.setattr(io0.device_buffers, "stage_view", recording)
        got = io0.fetch_device_blocks(12, 0, 4, timeout_s=30)
        assert sum(len(b) for b in got.values()) == 4
        assert staged_lens[-1] == slow_len, (
            f"slow block staged at position {staged_lens.index(slow_len)} "
            f"of {len(staged_lens)} — staging followed issue order"
        )
        for bufs in got.values():
            for b in bufs:
                b.free()
    finally:
        io0.stop()
        io1.stop()


def test_mapped_fetch_fault_releases_late_delivery(cluster, monkeypatch):
    """Mapped-delivery ownership dance under failure: when one mapped
    read fails and another's delivery arrives AFTER the caller has
    abandoned the fetch, the listener (now the last owner) must
    release the delivery — mappings must never outlive the fetch."""
    import threading
    import time as _time

    from sparkrdma_tpu.shuffle.errors import FetchFailedError
    from sparkrdma_tpu.transport.channel import ChannelError

    conf, driver, ex0, ex1 = cluster
    handle = BaseShuffleHandle(
        shuffle_id=13, num_maps=1, partitioner=HashPartitioner(2)
    )
    driver.register_shuffle(handle)
    io0, io1 = DeviceShuffleIO(ex0), DeviceShuffleIO(ex1)
    rng = np.random.default_rng(21)
    released = []
    timers = []

    class FakeDelivery:
        def __init__(self, payload):
            self.views = [memoryview(payload)]
            self.mapped = True

        def release(self):
            released.append(True)

    try:
        io1.publish_device_blocks(
            13, {p: rng.integers(0, 256, 4000, np.uint8) for p in range(2)}
        )
        calls = {"n": 0}

        def fake_mapped(listener, blocks):
            calls["n"] += 1
            if calls["n"] == 1:
                # delivery arrives late, after the fetch has failed
                t = threading.Timer(
                    0.5,
                    lambda: listener.on_success(FakeDelivery(b"z" * blocks[0][2])),
                )
                t.daemon = True
                timers.append(t)
                t.start()
            else:
                listener.on_failure(ChannelError("injected mapped fault"))

        # force the mapped path regardless of transport flavor by
        # presenting a channel-like object with read_mapped_in_queue
        real_get = ex0.get_channel_to

        class MappedOnly:
            def __init__(self, ch):
                self._ch = ch

            def read_mapped_in_queue(self, listener, blocks):
                fake_mapped(listener, blocks)

        monkeypatch.setattr(
            ex0, "get_channel_to",
            lambda mid, purpose="rpc": MappedOnly(real_get(mid, purpose)),
        )
        with pytest.raises(FetchFailedError):
            io0.fetch_device_blocks(13, 0, 2, timeout_s=10)
        # the late delivery must have been released by the listener side
        deadline = _time.time() + 5
        while not released and _time.time() < deadline:
            _time.sleep(0.05)
        assert released, "late mapped delivery leaked (release never called)"
        assert io0.device_buffers.in_use_bytes == 0
    finally:
        for t in timers:
            t.cancel()
        io0.stop()
        io1.stop()


def test_unpublish_releases_registered_buffers(cluster):
    conf, driver, ex0, ex1 = cluster
    handle = BaseShuffleHandle(shuffle_id=2, num_maps=1, partitioner=HashPartitioner(1))
    driver.register_shuffle(handle)
    io0 = DeviceShuffleIO(ex0)
    try:
        before = ex0.node.pd.region_count()
        io0.publish_device_blocks(2, {0: jnp.arange(64, dtype=jnp.uint8)})
        assert ex0.node.pd.region_count() > before or True  # pooled reuse possible
        io0.unpublish(2)
        # pooled buffer returned; a new publish reuses it
        io0.publish_device_blocks(2, {0: jnp.arange(64, dtype=jnp.uint8)})
        io0.unpublish(2)
    finally:
        io0.stop()


def test_unpublish_hands_idle_arena_slabs_back(cluster):
    """Once a shuffle is unpublished and no other slab of the executor is
    live, its arena slabs do not stay pooled: the HBM they held is free
    for whatever runs next."""
    conf, driver, ex0, ex1 = cluster
    handle = BaseShuffleHandle(shuffle_id=3, num_maps=1,
                               partitioner=HashPartitioner(2))
    driver.register_shuffle(handle)
    io0 = DeviceShuffleIO(ex0)
    try:
        io0.publish_device_blocks(3, {
            p: jnp.arange(20_000 + p, dtype=jnp.uint8) for p in range(2)})
        assert io0.device_buffers.in_use_bytes > 0
        io0.unpublish(3)
        assert io0.device_buffers.in_use_bytes == 0
        assert all(s["pooled"] == 0
                   for s in io0.device_buffers.stats().values())
    finally:
        io0.stop()
