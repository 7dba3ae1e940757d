"""HBM slab pool tests — the device registered-memory plane.

Mirrors the buffer-pool property targets (reuse/leak accounting,
RdmaBufferManager.java:131-141; power-of-two size classing :103-118)."""

import pytest

from sparkrdma_tpu.ops.hbm_arena import (
    MIN_BLOCK_SIZE,
    DeviceBufferManager,
    _size_class,
)


def test_size_class_rounding():
    assert _size_class(1) == MIN_BLOCK_SIZE
    assert _size_class(MIN_BLOCK_SIZE) == MIN_BLOCK_SIZE
    assert _size_class(MIN_BLOCK_SIZE + 1) == MIN_BLOCK_SIZE * 2
    assert _size_class(1 << 20) == 1 << 20


def test_stage_read_roundtrip():
    mgr = DeviceBufferManager()
    data = bytes(range(256)) * 100
    buf = mgr.stage_bytes(data)
    assert buf.length == len(data)
    assert buf.capacity >= len(data)
    assert buf.read() == data
    assert buf.read(16, 16) == data[16:32]
    buf.free()
    mgr.stop()


def test_stage_view_typed_u32():
    """u32 staging: host-side reinterpret, byte-accurate readback, and
    spill/restore that survive a non-uint8 slab dtype (the merge path
    consumes keys directly — on-device byte->word assembly would pad
    the [..., 4] minor dim 4->128 under TPU tiling)."""
    import numpy as np

    mgr = DeviceBufferManager()
    keys = np.arange(7000, dtype=np.uint32)
    buf = mgr.stage_view(memoryview(keys.view(np.uint8)), keys.nbytes,
                         dtype=np.uint32)
    assert buf.length == keys.nbytes
    assert str(buf.array.dtype) == "uint32"
    assert buf.array.shape[0] == buf.capacity // 4
    assert np.array_equal(
        np.frombuffer(buf.read(0, keys.nbytes), np.uint32), keys
    )
    # unaligned byte read off a typed slab
    assert buf.read(2, 6) == keys.view(np.uint8)[2:8].tobytes()
    # spill -> restore keeps contents and dtype
    buf.spill_to_host()
    assert buf.read(0, keys.nbytes) == keys.tobytes()
    buf.ensure_device()
    assert str(buf.array.dtype) == "uint32"
    assert np.array_equal(
        np.frombuffer(buf.read(0, keys.nbytes), np.uint32), keys
    )
    buf.free()
    mgr.stop()


def test_pinned_working_set_never_victimized():
    """Restoring a held working set must not thrash: making room for
    one member may never spill another (b.array would be None under a
    direct consumer) — and while the pin is held, OTHER pool traffic
    can't victimize the set either, even a long-resident member that
    would otherwise be the global LRU. A set larger than the budget
    fails loudly."""
    budget = 4 * MIN_BLOCK_SIZE
    mgr = DeviceBufferManager(max_bytes=budget)
    bufs = [mgr.stage_bytes(bytes([i]) * 100) for i in range(8)]  # spills
    assert mgr.spill_count >= 4
    held = bufs[:4]  # exactly fits the budget
    with mgr.pinned_on_device(held):
        assert all(not b.spilled and b.array is not None for b in held)
        assert mgr.in_use_bytes <= budget
        # every OTHER buffer got pushed out, never a set member
        assert all(b.spilled for b in bufs[4:])
        # concurrent-traffic shape: with the whole budget pinned, new
        # demand has nothing to evict and must fail loudly — never
        # silently spill a pinned member
        with pytest.raises(MemoryError):
            mgr.stage_bytes(b"x" * 100)
        assert all(not b.spilled for b in held)
    # pins dropped: the same demand now evicts an (ex-)member fine
    extra = mgr.stage_bytes(b"x" * 100)
    assert sum(b.spilled for b in bufs[:4]) == 1
    extra.free()
    with pytest.raises(MemoryError):
        with mgr.pinned_on_device(bufs[:5]):  # 5 slabs > 4-slab budget
            pass
    # ensure_device_all remains as the non-holding convenience form
    mgr.ensure_device_all(held)
    assert all(not b.spilled for b in held)
    for b in bufs:
        b.free()
    mgr.stop()


def test_three_tier_spill_hbm_host_disk(tmp_path):
    """SURVEY §7.3(4): HBM -> host RAM -> disk, byte-exact reads from
    every tier, transparent climb back, accounting that returns to
    zero, and no spill files left behind."""
    import os

    budget = 2 * MIN_BLOCK_SIZE       # 2 slabs in HBM
    host_cap = 2 * MIN_BLOCK_SIZE     # 2 slabs in host RAM
    mgr = DeviceBufferManager(
        max_bytes=budget, max_host_bytes=host_cap, spill_dir=str(tmp_path)
    )
    payload = [bytes([i]) * (MIN_BLOCK_SIZE - 64) for i in range(6)]
    bufs = [mgr.stage_bytes(p) for p in payload]
    # 6 slabs through a 2-slab HBM budget: 4 spilled to host, and the
    # 2-slab host cap cascaded 2 of those onward to disk
    assert mgr.spill_count >= 4
    assert mgr.disk_spill_count >= 2
    assert mgr.in_use_bytes <= budget
    assert mgr.host_bytes <= host_cap
    tiers = {"device": 0, "host": 0, "disk": 0}
    for b in bufs:
        tiers["disk" if b.on_disk else "host" if b._host is not None
              else "device"] += 1
    assert tiers == {"device": 2, "host": 2, "disk": 2}
    # byte-exact from every tier (disk reads via memmap, no restore)
    for b, p in zip(bufs, payload):
        assert b.read(0, len(p)) == p
    # climb a disk-tier buffer all the way back to the device
    deep = next(b for b in bufs if b.on_disk)
    deep.ensure_device()
    assert deep.array is not None and not deep.spilled
    assert deep.read(0, deep.length) == payload[bufs.index(deep)]
    assert mgr.in_use_bytes <= budget and mgr.host_bytes <= host_cap
    for b in bufs:
        b.free()
    assert mgr.in_use_bytes == 0 and mgr.host_bytes == 0
    assert list(tmp_path.iterdir()) == [], "spill files leaked"
    mgr.stop()


def test_prefetch_restores_in_background(tmp_path):
    """prefetch() climbs a spilled set back to HBM off-thread; a later
    pinned_on_device is then a fast no-op."""
    budget = 2 * MIN_BLOCK_SIZE
    mgr = DeviceBufferManager(
        max_bytes=budget, max_host_bytes=MIN_BLOCK_SIZE,
        spill_dir=str(tmp_path),
    )
    payload = [bytes([i]) * 200 for i in range(4)]
    bufs = [mgr.stage_bytes(p) for p in payload]
    assert any(b.spilled for b in bufs[:2])  # pushed out by later stages
    done = mgr.prefetch(bufs[:2])
    assert done.wait(30)
    assert all(not b.spilled for b in bufs[:2])
    with mgr.pinned_on_device(bufs[:2]):
        for b, p in zip(bufs[:2], payload[:2]):
            assert b.read(0, len(p)) == p
    for b in bufs:
        b.free()
    mgr.stop()


def test_climb_after_free_charges_nothing(tmp_path):
    """A restore racing free() (the prefetch pattern) must not charge
    budget for a buffer whose tiers were already torn down."""
    mgr = DeviceBufferManager(
        max_bytes=2 * MIN_BLOCK_SIZE, spill_dir=str(tmp_path)
    )
    a = mgr.stage_bytes(b"a" * 100)
    b = mgr.stage_bytes(b"b" * 100)
    c = mgr.stage_bytes(b"c" * 100)  # spills a
    assert a.spilled
    a.free()  # freed while spilled — tiers torn down
    before_dev, before_host = mgr.in_use_bytes, mgr.host_bytes
    a.ensure_device()  # the racing climb: must be a no-op
    assert a.array is None
    assert mgr.in_use_bytes == before_dev
    assert mgr.host_bytes == before_host
    done = mgr.prefetch([a, b])  # mixed dead/live set: completes
    assert done.wait(30)
    assert not b.spilled
    for buf in (b, c):
        buf.free()
    assert mgr.in_use_bytes == 0 and mgr.host_bytes == 0
    mgr.stop()


def test_pool_reuse_same_class():
    mgr = DeviceBufferManager()
    live = mgr.get(1)  # keeps the pool from being released
    a = mgr.get(20_000)
    h = a.handle
    a.free()
    b = mgr.get(30_000)  # same 32 KiB class -> reused slab
    assert b.handle == h
    stats = mgr.stats()
    cls = _size_class(20_000)
    assert stats[cls]["total_alloc"] == 1
    assert stats[cls]["total_gets"] == 2
    b.free()
    live.free()
    mgr.stop()


def test_idle_pool_released_once_no_slab_is_live():
    """Freed slabs stay pooled while another slab is live; the free of
    the last live slab hands every idle slab's device array back, all
    but the preallocated ones, and the next get allocates anew."""
    mgr = DeviceBufferManager(prealloc=1, prealloc_size=20_000)
    pre = _size_class(20_000)
    assert mgr.stats()[pre] == {"total_alloc": 1, "total_gets": 1,
                                "pooled": 1}
    live = mgr.stage_bytes(b"\x07" * 20_000)  # takes the preallocated slab
    idle = [mgr.get(n) for n in (20_000, 40_000, 40_000)]
    arrays = [b.array for b in idle]
    for b in idle:
        b.free()
    assert mgr.stats()[_size_class(40_000)]["pooled"] == 2
    assert not any(a.is_deleted() for a in arrays)
    assert live.read() == b"\x07" * 20_000
    live.free()
    assert all(a.is_deleted() for a in arrays[1:])
    assert {c: s["pooled"] for c, s in mgr.stats().items()} == {
        pre: 1, _size_class(40_000): 0}
    again = mgr.get(40_000)
    assert again.handle not in {b.handle for b in idle}
    assert mgr.stats()[_size_class(40_000)]["total_alloc"] == 3
    kept = mgr.get(20_000)  # the preallocated count stays pooled
    assert not kept.array.is_deleted()
    assert mgr.stats()[pre]["total_alloc"] == 2
    mgr.stop()


def test_handle_table_resolution():
    mgr = DeviceBufferManager()
    buf = mgr.stage_bytes(b"registered")
    assert mgr.resolve(buf.handle) is buf
    buf.free()
    with pytest.raises(KeyError):
        mgr.resolve(buf.handle)
    mgr.stop()


def test_budget_enforced_device_residency():
    """The budget caps DEVICE residency: allocations beyond it demote
    LRU slabs to the host tier rather than failing."""
    mgr = DeviceBufferManager(max_bytes=MIN_BLOCK_SIZE * 2)
    a = mgr.get(1)
    b = mgr.get(1)
    c = mgr.get(1)  # over cap: a (LRU) demotes to host
    assert a.spilled
    assert mgr.in_use_bytes <= MIN_BLOCK_SIZE * 2
    a.free()
    b.free()
    c.free()
    assert mgr.in_use_bytes == 0
    mgr.stop()


def test_double_free_tolerated():
    mgr = DeviceBufferManager()
    buf = mgr.get(1)
    buf.free()
    buf.free()  # like RdmaCompletionListener.onFailure: reentry tolerated
    assert mgr.in_use_bytes == 0
    mgr.stop()


def test_budget_pressure_spills_lru_to_host():
    """SURVEY §7.3-4 tiering: over-budget allocation spills the
    least-recently-used live slab to host RAM instead of failing."""
    mgr = DeviceBufferManager(max_bytes=MIN_BLOCK_SIZE * 2)
    a = mgr.get(1)
    a.stage(b"oldest")
    b = mgr.get(1)
    b.stage(b"newer")
    c = mgr.get(1)  # budget full: LRU (a) must spill, not MemoryError
    assert a.spilled and not b.spilled and not c.spilled
    assert mgr.spill_count == 1
    assert a.read(0, 6) == b"oldest"  # readable from the host tier
    c.free()
    a.ensure_device()  # restore fits after c freed
    assert not a.spilled
    assert a.read(0, 6) == b"oldest"
    a.free()
    b.free()
    mgr.stop()


def test_restore_spills_someone_else():
    mgr = DeviceBufferManager(max_bytes=MIN_BLOCK_SIZE * 2)
    a = mgr.get(1); a.stage(b"aa")
    b = mgr.get(1); b.stage(b"bb")
    c = mgr.get(1); c.stage(b"cc")   # spills a
    assert a.spilled
    a.ensure_device()                 # must spill the new LRU (b)
    assert not a.spilled and b.spilled
    assert b.read(0, 2) == b"bb"
    for x in (a, b, c):
        x.free()
    mgr.stop()


def test_spilled_buffer_free_is_clean():
    mgr = DeviceBufferManager(max_bytes=MIN_BLOCK_SIZE)
    a = mgr.get(1); a.stage(b"x")
    b = mgr.get(1)   # spills a
    assert a.spilled
    a.free()         # freeing a spilled slab must not touch the budget
    assert mgr.in_use_bytes == b.capacity
    b.free()
    assert mgr.in_use_bytes == 0
    mgr.stop()


def test_nothing_spillable_raises():
    # cap smaller than one size class: no victim can ever make room
    mgr = DeviceBufferManager(max_bytes=MIN_BLOCK_SIZE // 2)
    with pytest.raises(MemoryError):
        mgr.get(1)
    mgr.stop()


def test_spill_of_freed_pooled_buffer_is_a_noop():
    """Race regression (caught by the threaded stress ~1-in-8 runs):
    _make_room picks its victim from the handle table WITHOUT holding
    any lock, so the victim can be free()d — and returned, array
    intact, to the pool stack — before its spill_to_host runs. The
    spill must then be a no-op: spilling a pooled slab released its
    device budget a SECOND time (in_use_bytes went negative) and left
    a tierless zombie in the pool."""
    from sparkrdma_tpu.ops.hbm_arena import MIN_BLOCK_SIZE, DeviceBufferManager

    mgr = DeviceBufferManager(max_bytes=4 * MIN_BLOCK_SIZE)
    try:
        live = mgr.get(1)  # keeps the freed slab pooled
        buf = mgr.stage_bytes(b"y" * 100)
        assert mgr.in_use_bytes == 2 * MIN_BLOCK_SIZE
        buf.free()  # pooled: array kept, budget released, handle removed
        assert mgr.in_use_bytes == MIN_BLOCK_SIZE
        # the raced victim pick fires AFTER the free
        buf.spill_to_host()
        assert mgr.in_use_bytes == MIN_BLOCK_SIZE, (
            "pooled slab's budget released twice")
        assert mgr.host_bytes == 0
        assert buf.array is not None and not buf.spilled, (
            "pooled slab was demoted to the host tier"
        )
        # the pooled slab is still perfectly reusable
        buf2 = mgr.stage_bytes(b"z" * 200)
        assert buf2 is buf  # LIFO pool reuse
        assert bytes(buf2.read(0, 200)) == b"z" * 200
        buf2.free()
        live.free()
        assert mgr.in_use_bytes == 0
    finally:
        mgr.stop()
