"""Device fetch plane — per-block host-vs-device transport planning.

The reduce-side half of the device-native one-sided fetch path
(DESIGN.md §17): map tasks that stage a shard in the HBM arena publish
its ``(device_coords, arena_handle, arena_offset)`` next to the host
``(address, length, mkey)`` triple (locations.py / rpc.py trailing
extension), and the planner here decides per block whether the bytes
can move HBM→HBM — a Pallas/transfer-engine pull with no host CPU in
the data path (ops/remote_copy.py) — or must take the host socket
path. The host triple is ALWAYS valid; every planner decision in the
table below other than a pull is a fallback, never an error, so an
arena that spilled (or freed) the shard mid-job degrades to exactly
the pre-existing behavior.

Mesh visibility: a destination can pull a source arena it can reach
over the device fabric. On a real multi-chip mesh that is the ICI/DCN
domain; in this process-model reproduction (and under
``JAX_PLATFORMS=cpu``) the visible set is the arenas registered by
DeviceShuffleIO endpoints living in this process — the emulated
topology the cluster tests run on.

Planner decision table (see DESIGN.md §17):

| condition                                   | outcome        |
|---------------------------------------------|----------------|
| ``deviceFetch.enabled`` off                  | host (silent)  |
| location has no device extension             | host (silent)  |
| block < ``deviceFetch.minBlockBytes``        | host, fallback++|
| source arena not mesh-visible                | host, fallback++|
| arena slab freed / spilled / being spilled   | host, fallback++|
| staged dtype ≠ requested dtype               | host, fallback++|
| otherwise                                    | device pull    |

A failure of the mover itself is not in the table: it raises to the
fetch, like any other device error.

Checksums are verified at publish time on the host copy; the device
copy is the same staged bytes, so device pulls trust them (the host
path keeps its per-block verify gate).

Relationship to the whole-stage schedule compiler (DESIGN.md §22,
shuffle/collective.py): when a reduce stage carries enough
device-resident blocks, the compiler claims them up front and moves
them in batched DMA waves; THIS planner then only sees the compiler's
passthrough set (non-device blocks, sub-minimum blocks, stages below
``collective.minBlocks``) plus any wave rows that degraded mid-stage —
for those the decision table above applies unchanged. The plane's
``pulls``/``bytes``/``fallbacks`` counters stay the single source of
truth across both paths: the compiler feeds them for its landed and
degraded rows.
"""

from __future__ import annotations

import logging
import threading
import time
from typing import Dict, Optional

import numpy as np

from sparkrdma_tpu.locations import PartitionLocation
from sparkrdma_tpu.obs import get_registry
from sparkrdma_tpu.ops import remote_copy
from sparkrdma_tpu.ops.hbm_arena import DeviceBuffer, DeviceBufferManager

logger = logging.getLogger(__name__)

# mesh-visible arena registry: executor_id -> that endpoint's
# DeviceBufferManager. Registered by DeviceShuffleIO on construction,
# dropped on stop. Process-local by design (see module docstring).
_arenas: Dict[str, DeviceBufferManager] = {}
_arenas_lock = threading.Lock()


def register_arena(executor_id: str, dev: DeviceBufferManager) -> None:
    with _arenas_lock:
        _arenas[executor_id] = dev


def unregister_arena(executor_id: str, dev: DeviceBufferManager) -> None:
    """Drop the registration iff it is still ``dev`` (a newer endpoint
    under the same executor id wins; its registration must survive the
    old one's stop)."""
    with _arenas_lock:
        if _arenas.get(executor_id) is dev:
            del _arenas[executor_id]


def visible_arena(executor_id: str) -> Optional[DeviceBufferManager]:
    with _arenas_lock:
        return _arenas.get(executor_id)


class DevicePulledBlock:
    """A block that arrived HBM→HBM — the device plane's stand-in for
    a :class:`~sparkrdma_tpu.shuffle.device_io.HostBlock` in the reduce
    pipeline's hand-off. It is already staged (the pull landed in a
    local arena slab), already integrity-covered (checksum verified at
    publish), so verify passes it through and stage just unwraps it;
    ordering, abort-drain (``release`` frees the slab) and
    circuit-breaker bookkeeping flow through the same pipeline seams
    the host path uses."""

    kind = "device"

    __slots__ = ("shuffle_id", "loc", "length", "dev", "_released")

    def __init__(self, shuffle_id: int, loc: PartitionLocation, dev: DeviceBuffer):
        self.shuffle_id = shuffle_id
        self.loc = loc
        self.length = loc.block.length
        self.dev = dev
        self._released = False

    def release(self) -> None:
        """Abort-drain path: discard the pulled slab."""
        if self._released:
            return
        self._released = True
        self.dev.free()

    def take(self) -> DeviceBuffer:
        """Ownership transfer to the staging stage (release becomes a
        no-op; the consumer frees the slab)."""
        self._released = True
        return self.dev


class DeviceFetchPlane:
    """Per-endpoint planner + mover for device pulls."""

    def __init__(self, conf, dev: DeviceBufferManager, executor_id: str):
        self._conf = conf
        self._dev = dev
        self._executor_id = executor_id
        reg = get_registry()
        self._m_pulls = reg.counter("device_fetch.plane.pulls", role=executor_id)
        self._m_bytes = reg.counter("device_fetch.plane.bytes", role=executor_id)
        self._m_fallbacks = reg.counter(
            "device_fetch.plane.fallbacks", role=executor_id
        )
        self._m_plan_ms = reg.histogram(
            "device_fetch.plane.plan_ms", role=executor_id
        )

    def _fallback(self, reason: str) -> None:
        self._m_fallbacks.inc()
        logger.debug("device pull fallback: %s", reason)

    def try_pull(self, loc: PartitionLocation, dtype=np.uint8) -> Optional[DeviceBuffer]:
        """Plan + execute one block pull; None means 'use the host path'.

        The planner's decisions (the module table) return None; an
        eviction/spill race degrades, it does not error. A failure of
        the mover itself raises."""
        t0 = time.perf_counter()
        try:
            return self._try_pull(loc, dtype)
        finally:
            self._m_plan_ms.observe((time.perf_counter() - t0) * 1e3)

    def _try_pull(self, loc: PartitionLocation, dtype) -> Optional[DeviceBuffer]:
        block = loc.block
        if not self._conf.device_fetch_enabled or not block.has_device:
            return None  # silent: the publisher never offered a device copy
        if block.length < self._conf.device_fetch_min_block_bytes:
            self._fallback("below minBlockBytes")
            return None
        src_arena = visible_arena(loc.manager_id.executor_id)
        if src_arena is None:
            self._fallback("source arena not mesh-visible")
            return None
        with src_arena.pinned_if_resident(block.arena_handle) as src:
            if src is None:
                # freed, spilled, or mid-spill: the eviction race
                self._fallback("arena slab not device-resident")
                return None
            if block.arena_offset + block.length > src.capacity:
                self._fallback("stale arena coordinates")
                return None
            if np.dtype(src.array.dtype) != np.dtype(dtype):
                # the consumer asked for differently-typed slabs than
                # the publisher staged; host stage_view retypes for
                # free, a device-side cast would compile per shape
                self._fallback("staged dtype mismatch")
                return None
            pulled = remote_copy.emulated_pull(src.array, self._dev.device)
            # adopt into the local arena: source and destination size
            # classes match (same power-of-two classing both sides), so
            # the pulled slab-capacity array fits exactly
            local = self._dev.get(block.length)
            try:
                local = local.put_array(pulled)
            except Exception:
                local.free()
                raise
            local.length = block.length
        self._m_pulls.inc()
        self._m_bytes.inc(block.length)
        return local
