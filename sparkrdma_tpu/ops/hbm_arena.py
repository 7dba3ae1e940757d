"""HBM slab pool + handle table — the device registered-memory plane.

Device analogue of the host registered-buffer pool
(RdmaBufferManager.java): size-classed stacks of uint8 slabs resident
in device HBM, power-of-two rounding with a 16 KiB floor
(RdmaBufferManager.java:103-118), per-class allocation statistics
printed at shutdown (:131-141), and an optional preallocation pass
(:84-91).

The rkey/address concept (RdmaBlockLocation's ``(address, length,
mkey)``, RdmaPartitionLocation.scala:25) maps to ``(device ordinal,
handle, offset, length)``: the handle table resolves a handle to a
live ``jax.Array`` slab, so any framework component — the fetcher
staging received blocks, the exchange program sourcing send slabs —
can name device memory without holding the array itself.

``jax.Array`` is immutable, so "writing into a slab" means staging a
new array and retiring the old one under the same handle; pooling here
buys *budget accounting* and handle stability rather than malloc reuse
(XLA's allocator handles that). The budget mirrors the reference's
executor-wide in-memory cap (``shuffleWriteMaxInMemoryStoragePerExecutor``,
RdmaShuffleBlockResolver.scala:38-47) via ``hbm.maxBytes``.
"""

from __future__ import annotations

import contextlib
import functools
import logging
import os
import tempfile
import threading
import time
from typing import Dict, List, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from sparkrdma_tpu.analysis.lockorder import named_lock
from sparkrdma_tpu.obs import get_registry
from sparkrdma_tpu.tenancy import current_tenant, tenant_scope
from sparkrdma_tpu.tenancy import quota as _quota

logger = logging.getLogger(__name__)

_M_POOL_HITS = get_registry().counter("hbm.pool_hits")
_M_POOL_MISSES = get_registry().counter("hbm.pool_misses")
_M_SPILL_VICTIMS = get_registry().counter("hbm.spill_victims")
_M_DISK_SPILLS = get_registry().counter("hbm.disk_spills")
# bytes asked for and size-class bytes of every slab handed out: their
# ratio is how full the power-of-two classes run
_M_SLAB_PAYLOAD = get_registry().counter("hbm.slab_payload_bytes")
_M_SLAB_BYTES = get_registry().counter("hbm.slab_bytes")
# summed across managers; the gauge's high-water mark is the figure of
# interest for sizing hbm.maxBytes
_G_IN_USE = get_registry().gauge("hbm.in_use_bytes")

MIN_BLOCK_SIZE = 16 * 1024  # RdmaBufferManager.java MIN_BLOCK_SIZE analogue


def _size_class(nbytes: int) -> int:
    """Round up to a power of two, floored at MIN_BLOCK_SIZE."""
    n = max(nbytes, MIN_BLOCK_SIZE)
    return 1 << (n - 1).bit_length()


def _memory_owner(arr: np.ndarray) -> np.ndarray:
    """The ndarray at the end of ``arr``'s base chain."""
    while isinstance(arr.base, np.ndarray):
        arr = arr.base
    return arr


class _ReadbackSource:
    """The 1-D device array a host readback was read from: its first
    ``n`` elements are the readback's, starting at host address ``ptr``
    of the memory ``owner`` holds."""

    __slots__ = ("array", "owner", "ptr", "n")

    def __init__(self, array, owner: np.ndarray, ptr: int, n: int):
        self.array = array
        self.owner = owner
        self.ptr = ptr
        self.n = n


class DeviceReadback(np.ndarray):
    """A host array read back from a 1-D device array, which it and
    every basic slice of it still name: a slab of such a slice can be
    cut on the device (``DeviceBufferManager.stage_device``) instead of
    crossing back from the host. Arrays derived in any other way carry
    the name too, but own other memory, so ``device_window`` declines
    them."""

    def __array_finalize__(self, obj):
        self._source = getattr(obj, "_source", None)

    @classmethod
    def of(cls, host: np.ndarray, array, n: int) -> "DeviceReadback":
        """``host[:n]``, naming ``array`` (whose first ``n`` elements it
        holds) as its device source."""
        out = host[:n].view(cls)
        out._source = _ReadbackSource(
            array, _memory_owner(out), out.__array_interface__["data"][0], n
        )
        return out


def device_window(arr) -> Optional[Tuple[object, int, int, Optional[_ReadbackSource]]]:
    """Where ``arr``'s elements already sit on a device: ``(1-D device
    array, element offset, element count, readback source)``, or None.
    A 1-D ``jax.Array`` is its own window (no readback source); a
    ``DeviceReadback`` slice is one only while it is C-contiguous, lies
    in the memory the readback was read into, and its source still
    holds the device array."""
    if isinstance(arr, jax.Array):
        return (arr, 0, arr.shape[0], None) if arr.ndim == 1 else None
    src = getattr(arr, "_source", None)
    if not isinstance(src, _ReadbackSource):
        return None
    dev = src.array
    if (dev is None or arr.ndim != 1 or arr.dtype != dev.dtype
            or not arr.flags.c_contiguous
            or _memory_owner(arr) is not src.owner):
        return None
    off, rem = divmod(arr.__array_interface__["data"][0] - src.ptr,
                      arr.itemsize)
    if rem or off < 0 or off + arr.shape[0] > src.n:
        return None
    return dev, off, arr.shape[0], src


_CUT_LOCKS: Dict[object, object] = {}
_CUT_LOCKS_GUARD = threading.Lock()


def device_cut_lock(device):
    """The process's one lock for ``device`` under which a map task's
    blocks are cut from its sorted array (``stage_device``). Executors
    sharing a chip take turns, so no two of them hold their sorted
    arrays beside their new slabs at once: the chip's peak would carry
    both."""
    with _CUT_LOCKS_GUARD:
        lock = _CUT_LOCKS.get(device)
        if lock is None:
            lock = _CUT_LOCKS[device] = named_lock("hbm.device_cut")
        return lock


@functools.lru_cache(maxsize=64)
def _cut_program(src_elems: int, class_elems: int, dtype_str: str):
    """Jitted cut of one arena slab from a 1-D device array: the first
    ``len`` elements are ``src[off:off + len]``, the tail zero, as
    ``stage_view``'s host pad leaves it. ``(off, len)`` is a runtime
    operand, so one executable serves every block of a source length,
    slab class and dtype. Returns the plain program and one that takes
    a pooled slab of the output's shape, donated, to write into."""
    dtype = jnp.dtype(dtype_str)
    span = max(src_elems, class_elems)

    def arena_cut(src, meta):
        off, n = meta[0], meta[1]
        if src_elems < class_elems:
            # a source shorter than the class: at most one class copied
            src = jnp.pad(src, (0, class_elems - src_elems))
        # dynamic_slice clamps its start to keep the window inside the
        # source, so a block near the end reads from a clamped start
        # and is shifted to the front; the window never reads past it
        start = jnp.minimum(off, span - class_elems)
        win = jax.lax.dynamic_slice(src, (start,), (class_elems,))
        win = jax.lax.dynamic_slice(
            jnp.concatenate([win, jnp.zeros_like(win)]), (off - start,),
            (class_elems,),
        )
        col = jnp.arange(class_elems, dtype=jnp.int32)
        return jnp.where(col < n, win, jnp.zeros((), dtype))

    def arena_cut_into(slab, src, meta):
        del slab  # donated: the cut lands in its memory
        return arena_cut(src, meta)

    return jax.jit(arena_cut), jax.jit(
        arena_cut_into, donate_argnums=0, keep_unused=True
    )


class DeviceBuffer:
    """One pooled HBM slab plus the live view of its contents.

    ``length`` is the caller-requested byte length; ``capacity`` the
    size-class slab length actually resident. ``array`` always has
    shape [capacity] while device-resident; under budget pressure a
    buffer descends the tiered store of SURVEY.md §7.3(4) —
    HBM -> host RAM -> disk — and transparently climbs back on next
    device use. A shuffle far larger than HBM (the reference's 175 GB
    bar vs 16 GiB/chip) therefore degrades in steps, never OOMs.
    """

    __slots__ = (
        "handle", "capacity", "length", "array", "_manager", "_host",
        "_disk", "_tier_lock", "last_use", "tenant", "_quota_tag",
    )

    def __init__(self, handle: int, capacity: int, array, manager):
        self.handle = handle
        self.capacity = capacity
        self.length = 0
        self.array = array
        self._manager = manager
        self.tenant = None  # owning tenant id (spill-victim preference)
        self._quota_tag = None  # (broker, tenant, cls) while charged
        self._host: Optional[np.ndarray] = None  # set while in host tier
        self._disk = None  # (path, dtype_str, count) while in disk tier
        # serializes TIER MOVES of this buffer (manager-initiated
        # cascade victims race caller-initiated restores/frees).
        # Ordering rules that keep this deadlock-free:
        #  - buffer lock OUTER, manager._lock inner;
        #  - a thread holds at most one UNPINNED buffer's lock, and
        #    only for a self-contained move (no other buffer locks
        #    taken inside);
        #  - cascades run with NO buffer lock held;
        #  - victim picks (the only cross-thread acquisition) never
        #    target pinned buffers, and every climber pins itself.
        #  allow_self_nest: a climber legitimately holds its own tier
        #  lock while spilling an unpinned victim (_make_room /
        #  _cascade_host_tier) — safe because the climber is pinned and
        #  victim picks exclude pinned handles, so the inner lock can
        #  never belong to a thread's own outer buffer
        self._tier_lock = named_lock("hbm.buffer", allow_self_nest=True)
        self.last_use = 0

    @property
    def spilled(self) -> bool:
        return self._host is not None or self._disk is not None

    @property
    def on_disk(self) -> bool:
        return self._disk is not None

    @property
    def device(self):
        if self.array is not None:
            return next(iter(self.array.devices()))
        return self._manager.device

    def spill_to_host(self) -> None:
        """HBM -> host RAM; releases device budget, keeps the handle.
        May cascade another buffer host -> disk under the host cap.
        The cascade MUST run after this buffer's lock is released: it
        can legally pick this very buffer (freshly host-resident, LRU)
        and would self-deadlock on the non-reentrant tier lock."""
        with self._tier_lock:
            if self.array is None:
                return  # raced: someone else already moved it
            with self._manager._lock:
                if self.handle not in self._manager._handles:
                    # raced a free(): the victim pick happened before
                    # put() removed this buffer from the handle table,
                    # and put() then returned it (array intact) to the
                    # pool stack. Spilling a POOLED slab would release
                    # its device budget a second time — the only
                    # negative-budget race the threaded stress ever
                    # produced. (Pool reuse re-inserts the same handle,
                    # so a re-gotten buffer spills normally again.)
                    return
            self._host = np.asarray(self.array)
            self.array.delete()
            self.array = None
            self._manager._on_spill_accounting(self)
        self._manager._cascade_host_tier()

    def spill_to_disk(self) -> None:
        """Host RAM -> disk; releases host budget, keeps the handle.
        Acts only on a host-tier resident (cascade victims); a raced
        buffer that climbed away in the meantime is left alone."""
        with self._tier_lock:
            if self._host is None:
                return
            path = self._manager._disk_path(self.handle)
            self._host.tofile(path)
            self._disk = (path, str(self._host.dtype), self._host.shape[0])
            self._host = None
            self._manager._on_disk_spill(self)

    def _ensure_host_locked(self) -> None:
        """Disk -> host RAM (the climb's first step; tier lock held).
        Budget is rolled back if the spill file cannot be read, so a
        failed climb never inflates the host tier forever."""
        if self._disk is None:
            return
        path, dtype_str, count = self._disk
        self._manager._reserve_host(self)
        try:
            host = np.fromfile(path, dtype=np.dtype(dtype_str), count=count)
            if host.shape[0] != count:
                raise IOError(f"spill file truncated: {path}")
        except BaseException:
            self._manager._unreserve_host(self)
            raise
        os.unlink(path)
        self._host = host
        self._disk = None

    def _climb_locked(self) -> None:
        """To device residency; tier lock held, self pinned."""
        if self.array is not None:
            return
        if self._host is None and self._disk is None:
            # freed out from under a concurrent climb (put() won the
            # tier lock first and tore the tiers down) — restoring
            # nothing must charge nothing, or the budget counters
            # corrupt silently (a prefetch racing free() hits this)
            return
        self._ensure_host_locked()
        self._manager._reserve_for_restore(self)
        host, self._host = self._host, None
        self.array = jax.device_put(host, self._manager.device)

    def ensure_device(self) -> "DeviceBuffer":
        """Restore a spilled buffer to HBM from whichever tier holds it
        (may spill others to fit; never a buffer pinned via
        ``DeviceBufferManager.pinned_on_device``). The buffer pins
        ITSELF for the climb: the room-making its restore triggers
        (device victims spilling to host, host cascade to disk) must
        never pick the climber mid-ascent."""
        if self.array is not None:
            return self
        m = self._manager
        m._pin(self.handle)
        try:
            with self._tier_lock:
                self._climb_locked()
        finally:
            m._unpin(self.handle)
        return self

    def stage(self, data: bytes) -> "DeviceBuffer":
        """Host -> HBM: replace the slab contents (pads to capacity).
        Pinned + tier-locked: a concurrent spill can neither delete
        the array mid-swap nor demote the slab while its budget is
        accounted device-resident."""
        if len(data) > self.capacity:
            raise ValueError(f"{len(data)}B exceeds slab capacity {self.capacity}B")
        m = self._manager
        m._pin(self.handle)
        try:
            with self._tier_lock:
                self._climb_locked()
                host = np.zeros((self.capacity,), dtype=np.uint8)
                host[: len(data)] = np.frombuffer(data, dtype=np.uint8)
                old = self.array
                self.array = jax.device_put(host, self.device)
                old.delete()
                self.length = len(data)
        finally:
            m._unpin(self.handle)
        m._touch(self)
        return self

    def put_array(self, arr) -> "DeviceBuffer":
        """Adopt a device-resident 1-D array as the slab contents.

        Any dtype is allowed (``length`` stays in BYTES): staging keys
        as uint32 lets downstream programs consume the slab directly —
        assembling words from a uint8 slab on-device costs a
        [..., 4]-minor reshape whose TPU tiled layout pads 4 -> 128
        (measured: a 32 GiB allocation for a 1 GiB merge input)."""
        if arr.ndim != 1:
            raise ValueError("slab contents must be 1-D")
        if arr.nbytes > self.capacity:
            raise ValueError("array exceeds slab capacity")
        m = self._manager
        m._pin(self.handle)
        try:
            with self._tier_lock:
                self._climb_locked()
                self.length = arr.nbytes
                old = self.array
                if arr.nbytes < self.capacity:
                    n = self.capacity // arr.dtype.itemsize
                    arr = jnp.zeros((n,), dtype=arr.dtype).at[: arr.shape[0]].set(arr)
                self.array = arr
                old.delete()
        finally:
            m._unpin(self.handle)
        m._touch(self)
        return self

    def _refill(self, fill) -> None:
        """Replace the contents with ``fill(old array)``, pinned and
        tier-locked like ``put_array``; ``fill`` consumes the old
        array."""
        m = self._manager
        m._pin(self.handle)
        try:
            with self._tier_lock:
                self._climb_locked()
                self.array = fill(self.array)
        finally:
            m._unpin(self.handle)

    def read(self, offset: int = 0, length: Optional[int] = None) -> bytes:
        """Readback of BYTES ``[offset, offset+length)`` from whichever
        tier holds the slab, regardless of the staged dtype. Tier-locked
        so a concurrent spill cannot move (or delete) the bytes between
        the tier check and the copy."""
        if length is None:
            length = self.length - offset
        if offset < 0 or length < 0 or offset + length > self.capacity:
            raise ValueError("read out of slab bounds")
        with self._tier_lock:
            if self._disk is not None:
                path, dtype_str, count = self._disk
                mm = np.memmap(path, dtype=np.dtype(dtype_str), mode="r",
                               shape=(count,))
                return mm.view(np.uint8)[offset : offset + length].tobytes()
            if self._host is not None:
                return self._host.view(np.uint8)[
                    offset : offset + length
                ].tobytes()
            self._manager._touch(self)
            # slice on-device in whole elements (keeps the transfer
            # small), trim to byte bounds host-side
            k = np.dtype(self.array.dtype).itemsize
            lo = offset // k
            hi = -(-(offset + length) // k)
            chunk = np.asarray(self.array[lo:hi]).view(np.uint8)
            start = offset - lo * k
            return chunk[start : start + length].tobytes()

    def free(self) -> None:
        self._manager.put(self)


class _AllocatorStack:
    """Lock-guarded per-size-class free stack with a cumulative
    allocation counter (reference AllocatorStack,
    RdmaBufferManager.java:31-71)."""

    __slots__ = ("size", "stack", "total_alloc", "total_gets")

    def __init__(self, size: int):
        self.size = size
        self.stack: List[DeviceBuffer] = []
        self.total_alloc = 0
        self.total_gets = 0


class DeviceBufferManager:
    """Size-classed pool of HBM slabs for one device. Freed slabs stay
    pooled while any slab is live; once the last one is freed, the idle
    pool is released, all but the preallocated slabs."""

    def __init__(self, device=None, max_bytes: int = 0, prealloc: int = 0,
                 prealloc_size: int = 0, max_host_bytes: int = 0,
                 spill_dir: Optional[str] = None):
        if device is None:
            device = jax.devices()[0]
        self.device = device
        self.max_bytes = max_bytes  # 0 = unbounded
        # host-RAM tier cap; overflow cascades to disk (§7.3(4) tier 3)
        self.max_host_bytes = max_host_bytes
        self._spill_dir = spill_dir
        self._run_token = os.urandom(4).hex()
        self._stacks: Dict[int, _AllocatorStack] = {}
        self._handles: Dict[int, DeviceBuffer] = {}
        self._next_handle = 1
        self._in_use_bytes = 0
        self._host_bytes = 0
        self._use_clock = 0
        self._spill_count = 0
        self._disk_spill_count = 0
        self._pins: Dict[int, int] = {}  # handle -> pin refcount
        self._pin_threads: Dict[int, List[int]] = {}  # handle -> owner idents
        # budget reserved by get() for slabs not yet in the handle
        # table: invisible to victim picks, but a reason to WAIT
        self._allocating = 0
        # waiters in _make_room blocked on pinned residents; notified on
        # any pin drop or budget release
        self._evict_cond = threading.Condition(named_lock("hbm.evict"))
        self._lock = named_lock("hbm.manager")
        self._stopped = False
        # optional warm-up (reference maxAggPrealloc, RdmaBufferManager.java:84-91);
        # these slabs stay pooled when the rest of an idle pool is released
        self._prealloc: Dict[int, int] = {}
        if prealloc > 0 and prealloc_size > 0:
            self._prealloc[_size_class(prealloc_size)] = prealloc
            bufs = [self.get(prealloc_size) for _ in range(prealloc)]
            for b in bufs:
                b.free()

    # ------------------------------------------------------------------
    # HBM <-> host tiering (SURVEY.md §7.3-4). Tier moves synchronize on
    # buffer state loosely: concurrent spill/restore of the SAME buffer
    # is the caller's race to avoid; budget arithmetic itself is locked.
    def _touch(self, buf: DeviceBuffer) -> None:
        with self._lock:
            self._use_clock += 1
            buf.last_use = self._use_clock

    def _disk_path(self, handle: int) -> str:
        # pid + per-manager random token: two executor processes on one
        # host (the deployment model) must never collide on a spill
        # name — id(self) alone is just a heap address both can share
        d = self._spill_dir or tempfile.gettempdir()
        return f"{d}/hbm-spill-{os.getpid()}-{self._run_token}-{handle}.bin"

    def _pin(self, handle: int) -> None:
        with self._lock:
            self._pins[handle] = self._pins.get(handle, 0) + 1
            self._pin_threads.setdefault(handle, []).append(
                threading.get_ident()
            )

    def _unpin(self, handle: int) -> None:
        with self._lock:
            c = self._pins.get(handle, 0) - 1
            if c > 0:
                self._pins[handle] = c
            else:
                self._pins.pop(handle, None)
            owners = self._pin_threads.get(handle)
            if owners:
                try:
                    owners.remove(threading.get_ident())
                except ValueError:
                    pass
                if not owners:
                    self._pin_threads.pop(handle, None)
        with self._evict_cond:
            self._evict_cond.notify_all()

    def _on_spill_accounting(self, buf: DeviceBuffer) -> None:
        """Device -> host budget transfer. Safe under the mover's tier
        lock — the follow-up cascade is the CALLER's duty, outside it."""
        with self._lock:
            self._in_use_bytes -= buf.capacity
            self._host_bytes += buf.capacity
            self._spill_count += 1
        _G_IN_USE.add(-buf.capacity)
        _M_SPILL_VICTIMS.inc()
        with self._evict_cond:
            self._evict_cond.notify_all()

    def _on_disk_spill(self, buf: DeviceBuffer) -> None:
        with self._lock:
            self._host_bytes -= buf.capacity
            self._disk_spill_count += 1
        _M_DISK_SPILLS.inc()

    def _pick_host_victim(self, exclude_handle: int) -> Optional[DeviceBuffer]:
        with self._lock:
            candidates = [
                b
                for b in self._handles.values()
                if b.handle != exclude_handle
                and b.handle not in self._pins
                and b._host is not None
            ]
            if not candidates:
                return None
            return min(candidates, key=lambda b: b.last_use)

    def _cascade_host_tier(self, exclude_handle: int = -1) -> None:
        """Push LRU host-tier residents to disk while over the host cap."""
        while True:
            with self._lock:
                if not self.max_host_bytes or self._host_bytes <= self.max_host_bytes:
                    return
            victim = self._pick_host_victim(exclude_handle)
            if victim is None:
                return  # everything host-resident is excluded/pinned
            victim.spill_to_disk()

    def _reserve_host(self, buf: DeviceBuffer) -> None:
        """Account a disk -> host climb (cascading others down first;
        safe under the climber's tier lock — the climber is pinned, so
        no victim pick can wait on it)."""
        with self._lock:
            self._host_bytes += buf.capacity
        self._cascade_host_tier(exclude_handle=buf.handle)

    def _unreserve_host(self, buf: DeviceBuffer) -> None:
        """Roll back a failed disk -> host climb."""
        with self._lock:
            self._host_bytes -= buf.capacity

    def _pick_spill_victim(self, pinned) -> Optional[DeviceBuffer]:
        with self._lock:
            candidates = [
                b
                for b in self._handles.values()
                if b.handle not in pinned
                and b.handle not in self._pins
                and not b.spilled
                and b.array is not None
            ]
            if not candidates:
                return None
            broker = _quota.broker("hbm")
            if broker is not None:
                # an over-quota tenant's slabs go first: its own hoard
                # pays for the pressure it created, LRU breaks ties
                return min(
                    candidates,
                    key=lambda b: (
                        not (b.tenant and broker.over_quota(b.tenant)),
                        b.last_use,
                    ),
                )
            return min(candidates, key=lambda b: b.last_use)

    def _make_room(self, cls: int, pinned=frozenset()) -> None:
        """Spill LRU device-resident buffers (never a ``pinned`` handle)
        until ``cls`` bytes fit.

        When every resident slab is pinned by OTHER threads (concurrent
        climbers mid-restore), those pins are transient — wait for one
        to drop instead of failing a healthy pool. Raise immediately
        when only this thread's own pins block the way (waiting would
        self-deadlock), or after a deadline (wedged pin holder)."""
        me = threading.get_ident()
        deadline = time.monotonic() + 30.0
        while True:
            with self._lock:
                if not self.max_bytes or self._in_use_bytes + cls <= self.max_bytes:
                    return
            victim = self._pick_spill_victim(pinned)
            if victim is not None:
                victim.spill_to_host()
                continue
            with self._lock:
                # Any pin held by another thread counts as transient
                # contention worth waiting on — including a climber
                # mid-restore whose budget is already charged
                # (_reserve_for_restore) while its ``array`` is still
                # None until jax.device_put returns (seconds for large
                # slabs). Requiring device residency here raised
                # MemoryError on a healthy pool during that window.
                foreign_pins = any(
                    self._handles.get(h) is not None
                    and any(t != me for t in self._pin_threads.get(h, ()))
                    for h in self._pins
                ) or self._allocating > 0
                in_use = self._in_use_bytes
            if not foreign_pins or time.monotonic() > deadline:
                raise MemoryError(
                    f"HBM shuffle budget exceeded: in-use {in_use}B + {cls}B "
                    f"> cap {self.max_bytes}B and nothing left to spill"
                )
            with self._evict_cond:
                self._evict_cond.wait(0.05)

    def _reserve_for_restore(self, buf: DeviceBuffer) -> None:
        self._make_room(buf.capacity, {buf.handle})
        with self._lock:
            self._in_use_bytes += buf.capacity
            self._host_bytes -= buf.capacity  # leaving the host tier
            self._use_clock += 1
            buf.last_use = self._use_clock
        _G_IN_USE.add(buf.capacity)

    @contextlib.contextmanager
    def pinned_on_device(self, bufs):
        """Context manager: pin a WORKING SET device-resident.

        Inside the ``with`` body every buffer in ``bufs`` is
        device-resident and can never be picked as a spill victim —
        not while restoring other members, and not by CONCURRENT pool
        operations on other threads (pins are refcounted manager
        state, not a call-local exclude list). Direct ``.array``
        access is therefore safe exactly for the duration of the
        block, and only there: on exit the pins drop and any later
        pool op may spill the set again.

        Raises MemoryError up front if the set itself cannot fit the
        budget — loud, instead of thrash-spilling the set against
        itself (which would leave some ``.array`` None)."""
        bufs = list(bufs)
        if self.max_bytes:
            need = sum(b.capacity for b in bufs)
            if need > self.max_bytes:
                raise MemoryError(
                    f"working set of {need}B cannot fit HBM budget "
                    f"{self.max_bytes}B; consume in smaller batches"
                )
        handles = [b.handle for b in bufs]
        for h in handles:
            self._pin(h)
        try:
            for b in bufs:
                b.ensure_device()
                # freshen EVERY member: a long-resident member must not
                # linger as global LRU once the pins drop
                self._touch(b)
            yield
        finally:
            for h in handles:
                self._unpin(h)

    @contextlib.contextmanager
    def pinned_if_resident(self, handle: int):
        """Pin ``handle`` for the block iff it is live AND still
        device-resident; yield the buffer, or None otherwise.

        The device fetch plane's eviction-race guard: unlike
        ``pinned_on_device`` this NEVER climbs a spilled buffer back —
        a source shard the arena already demoted must degrade to the
        host fetch path, not trigger a restore (which could thrash the
        publisher's budget) and never error. While the body runs the
        pin keeps spill victim picks away, so ``.array`` stays valid
        for the duration of the pull."""
        try:
            buf = self.resolve(handle)
        except KeyError:
            yield None
            return
        self._pin(handle)
        try:
            # re-check residency under the pin: a spill that won the
            # race before the pin landed leaves array None / tiers set
            if buf.array is None or buf.spilled:
                yield None
            else:
                with self._lock:
                    live = self._handles.get(handle) is buf
                yield buf if live else None
        finally:
            self._unpin(handle)

    def ensure_device_all(self, bufs) -> None:
        """Restore a working set to HBM without the set victimizing
        itself. NOTE: protection ends when this returns — consumers
        that touch ``.array`` directly should hold
        ``pinned_on_device(bufs)`` across the access instead."""
        with self.pinned_on_device(bufs):
            pass

    def prefetch(self, bufs) -> threading.Event:
        """Start climbing ``bufs`` back toward HBM on a background
        thread — the "prefetch back to HBM on fetch" of SURVEY
        §7.3(4), overlapping tier restores with whatever the caller
        computes next. Returns an Event set when the pass finishes
        (success or not). The climb uses the same pinned restore as
        ``ensure_device_all``; consumers still wrap their access in
        ``pinned_on_device`` (a fast no-op once prefetched). Best
        effort: under budget pressure later traffic may re-spill."""
        bufs = list(bufs)
        done = threading.Event()
        # the climb re-spills victims and re-charges restores under the
        # CALLER's tenant, so the background thread must re-enter its
        # scope — otherwise the work bills the default tenant
        tenant = current_tenant()

        def run():
            with tenant_scope(tenant):
                try:
                    self.ensure_device_all(bufs)
                except Exception:
                    logger.exception("hbm prefetch pass failed")
                finally:
                    done.set()

        threading.Thread(target=run, daemon=True, name="hbm-prefetch").start()
        return done

    def get(self, nbytes: int) -> DeviceBuffer:
        """Allocate (or reuse) a slab whose class covers ``nbytes``.

        Under budget pressure, least-recently-used live slabs spill to
        host RAM first; MemoryError only when nothing is spillable.
        When an hbm quota broker is installed, the tenant's charge
        gates the allocation — an over-quota tenant blocks here, on
        its own worker thread, until its earlier slabs are put back
        (capacity is charged for the get→put lifetime, so spilling a
        slab to host does NOT un-block its tenant)."""
        return self._get(nbytes, None)

    def _get(self, nbytes: int, fill) -> DeviceBuffer:
        """``get``, with the slab's array made by ``fill`` where given
        (see ``_get_slab``)."""
        broker = _quota.broker("hbm")
        if broker is None:
            return self._get_slab(nbytes, None, fill)
        tenant = current_tenant()
        cls = _size_class(nbytes)
        broker.charge(tenant, cls)
        try:
            buf = self._get_slab(nbytes, tenant, fill)
        except BaseException:
            broker.release(tenant, cls)
            raise
        buf._quota_tag = (broker, tenant, cls)
        return buf

    def _get_slab(self, nbytes: int, tenant, fill=None) -> DeviceBuffer:
        """A slab of ``nbytes``' class. ``fill(old)`` makes its array:
        ``old`` is a pooled slab's array, which ``fill`` consumes, or
        None, and then no zero slab is built first."""
        cls = _size_class(nbytes)
        with self._lock:
            if self._stopped:
                raise RuntimeError("DeviceBufferManager is stopped")
            stack = self._stacks.setdefault(cls, _AllocatorStack(cls))
            stack.total_gets += 1
            pooled = stack.stack.pop() if stack.stack else None
            if pooled is not None:
                pooled.length = nbytes
                pooled.tenant = tenant
                pooled._quota_tag = None
                self._in_use_bytes += cls
                self._handles[pooled.handle] = pooled
                self._use_clock += 1
                pooled.last_use = self._use_clock
        if pooled is not None:
            _M_POOL_HITS.inc()
            _G_IN_USE.add(cls)
            # the pooled slab re-enters the budget: spill LRU others if
            # that pushed us over the cap
            self._make_room(0, {pooled.handle})
            if fill is not None:
                pooled._refill(fill)
            _M_SLAB_PAYLOAD.inc(nbytes)
            _M_SLAB_BYTES.inc(cls)
            return pooled
        _M_POOL_MISSES.inc()
        self._make_room(cls)
        with self._lock:
            handle = self._next_handle
            self._next_handle += 1
            stack.total_alloc += 1
            self._in_use_bytes += cls
            # budget held for a slab not yet visible in the handle
            # table: concurrent _make_room callers must WAIT for it to
            # materialize, not conclude the pool is unspillable
            self._allocating += 1
        _G_IN_USE.add(cls)
        try:
            if fill is not None:
                arr = fill(None)
            else:
                arr = jax.device_put(
                    jnp.zeros((cls,), dtype=jnp.uint8), self.device
                )
            buf = DeviceBuffer(handle, cls, arr, self)
            buf.length = nbytes
            buf.tenant = tenant
            with self._lock:
                self._handles[handle] = buf
                self._use_clock += 1
                buf.last_use = self._use_clock
        finally:
            with self._lock:
                self._allocating -= 1
            with self._evict_cond:
                self._evict_cond.notify_all()
        _M_SLAB_PAYLOAD.inc(nbytes)
        _M_SLAB_BYTES.inc(cls)
        return buf

    def put(self, buf: DeviceBuffer) -> None:
        """Return a slab to its class stack (RdmaBufferManager.java:120-127).

        Takes the buffer's tier lock so a manager-initiated cascade
        mid-move on this buffer finishes (or sees it gone) before the
        tiers are torn down."""
        with buf._tier_lock:
            with self._lock:
                if self._handles.pop(buf.handle, None) is None:
                    return  # double-free tolerated, like onFailure reentry
                # freeing while pinned is a caller bug; don't let the
                # stale pin shield a recycled slab from eviction forever
                self._pins.pop(buf.handle, None)
                self._pin_threads.pop(buf.handle, None)
                if buf.spilled:
                    # spilled slabs released their device budget already
                    # and have no device array to pool — drop whichever
                    # lower tier holds the bytes
                    if buf._host is not None:
                        self._host_bytes -= buf.capacity
                        buf._host = None
                    disk, buf._disk = buf._disk, None
                else:
                    disk = None
            tag, buf._quota_tag = buf._quota_tag, None
            if tag is not None:
                # held-capacity quota retires with the slab, whatever
                # tier the bytes ended up in
                tag[0].release(tag[1], tag[2])
            if disk is not None:
                try:
                    os.unlink(disk[0])
                except OSError:
                    pass
            if buf.array is None:
                return
            idle: List[DeviceBuffer] = []
            with self._lock:
                self._in_use_bytes -= buf.capacity
                stopped = self._stopped
                if stopped:
                    buf.array.delete()
                else:
                    self._stacks[buf.capacity].stack.append(buf)
                    if not self._handles and not self._allocating:
                        # the last live slab came back: the pool would
                        # otherwise hold its high-water mark of HBM
                        # under whatever runs next, so it goes back to
                        # the device down to the preallocated slabs
                        # (the next get allocates again)
                        for cls, s in self._stacks.items():
                            keep = self._prealloc.get(cls, 0)
                            idle.extend(s.stack[keep:])
                            del s.stack[keep:]
            for b in idle:
                b.array.delete()
                b.array = None
            _G_IN_USE.add(-buf.capacity)
            with self._evict_cond:
                self._evict_cond.notify_all()
            if not stopped:
                buf.length = 0

    def resolve(self, handle: int) -> DeviceBuffer:
        """Handle table lookup — the mkey/rkey resolution analogue."""
        with self._lock:
            buf = self._handles.get(handle)
        if buf is None:
            raise KeyError(f"no live device buffer for handle {handle}")
        return buf

    def stage_bytes(self, data: bytes) -> DeviceBuffer:
        """Pool + stage in one step (host bytes -> registered HBM slab)."""
        return self.get(len(data)).stage(data)

    def stage_view(self, view, valid_len: Optional[int] = None,
                   dtype=np.uint8) -> DeviceBuffer:
        """Pool + stage from a buffer-protocol object WITHOUT the host
        round trip ``stage_bytes`` pays: the device transfer reads the
        source memory directly (one DMA), and no pad program ever
        compiles — the transfer is exactly one slab class long
        (SURVEY.md §7.3(3): the copy count at the host<->HBM seam is
        the difference between matching and missing the wire rate).

        ``valid_len`` (default: the whole view) is the byte length of
        the real contents. When the source is at least a slab class
        long — always true for pooled registered buffers, whose
        power-of-two classes match the device pool's — the tail past
        ``valid_len`` rides along as this process's own pooled bytes
        and is masked by ``length`` downstream; that removes the
        per-(length, capacity) jitted pad `put_array` would otherwise
        build (measured: each novel shape pair cost a multi-second
        Mosaic compile in the fetch path).

        ``dtype`` reinterprets the bytes host-side (free) so the slab
        lands typed — e.g. uint32 keys a device merge consumes
        directly (see ``put_array`` on why on-device byte->word
        assembly is ruinous on TPU)."""
        src = np.frombuffer(view, dtype=np.uint8)
        n = src.nbytes if valid_len is None else valid_len
        buf = self.get(n)
        if src.nbytes >= buf.capacity:
            typed = src[: buf.capacity].view(dtype)
            if buf.device.platform == "cpu":
                # the CPU backend's device_put may ALIAS host memory
                # zero-copy — but the source is a pooled registered
                # buffer the caller recycles immediately, so a later
                # fetch would overwrite these "device" bytes in place
                # (caught by the overlapped e2e on the CPU mesh; TPU
                # always DMAs a real copy)
                typed = typed.copy()
            arr = jax.device_put(typed, buf.device)
        else:
            # short source (not from a pooled class): pad host-side —
            # one memcpy, still compile-free
            host = np.zeros((buf.capacity,), dtype=np.uint8)
            host[: src.nbytes] = src
            arr = jax.device_put(host.view(dtype), buf.device)
        buf = buf.put_array(arr)
        buf.length = n
        # device_put may read the source asynchronously; callers recycle
        # the source buffer (a pooled registered region) immediately, so
        # the transfer must be complete before this returns
        jax.block_until_ready(buf.array)
        return buf

    def stage_device(self, src, elem_offset: int, elem_len: int) -> DeviceBuffer:
        """Pool + stage ``src[elem_offset:elem_offset + elem_len]`` of a
        1-D device array on this manager's device, cut by one program on
        the device (``_cut_program``): no host pad, no host-to-device
        transfer, no zero slab. The slab holds the block typed as
        ``src`` and zero past it, as ``stage_view`` leaves it; budget,
        quota, pool and slab counters are ``get``'s. A pooled slab of
        the output's shape and dtype is donated into the program, any
        other is deleted. The cut is dispatched, not waited on: callers
        wait once for a batch."""
        dtype = np.dtype(src.dtype)
        nbytes = elem_len * dtype.itemsize
        class_elems = _size_class(nbytes) // dtype.itemsize
        cut, cut_into = _cut_program(src.shape[0], class_elems, dtype.name)
        meta = np.array([elem_offset, elem_len], dtype=np.int32)

        def fill(old):
            if old is None:
                return cut(src, meta)
            if old.shape == (class_elems,) and old.dtype == dtype:
                return cut_into(old, src, meta)
            old.delete()
            return cut(src, meta)

        return self._get(nbytes, fill)

    # ------------------------------------------------------------------
    @property
    def in_use_bytes(self) -> int:
        with self._lock:
            return self._in_use_bytes

    @property
    def spill_count(self) -> int:
        with self._lock:
            return self._spill_count

    @property
    def disk_spill_count(self) -> int:
        with self._lock:
            return self._disk_spill_count

    @property
    def host_bytes(self) -> int:
        with self._lock:
            return self._host_bytes

    def stats(self) -> Dict[int, Dict[str, int]]:
        with self._lock:
            return {
                size: {
                    "total_alloc": s.total_alloc,
                    "total_gets": s.total_gets,
                    "pooled": len(s.stack),
                }
                for size, s in self._stacks.items()
            }

    def stop(self) -> None:
        """Free everything; log per-class stats (RdmaBufferManager.java:131-141)."""
        with self._lock:
            if self._stopped:
                return
            self._stopped = True
            stacks = list(self._stacks.values())
            leaked = list(self._handles.values())
        for s in stacks:
            if s.total_alloc:
                logger.info(
                    "hbm pool class %dB: allocated %d, gets %d, pooled %d",
                    s.size, s.total_alloc, s.total_gets, len(s.stack),
                )
            for buf in s.stack:
                buf.array.delete()
            s.stack.clear()
        for buf in leaked:
            logger.warning("hbm slab handle %d leaked (freeing)", buf.handle)
            if buf.array is not None:
                buf.array.delete()
            buf._host = None
            if buf._disk is not None:
                try:
                    os.unlink(buf._disk[0])
                except OSError:
                    pass
                buf._disk = None
