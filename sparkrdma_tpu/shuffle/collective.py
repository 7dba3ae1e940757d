"""Whole-stage collective shuffle — the pipelined shuffle-schedule compiler.

The device fetch plane (DESIGN.md §17) moves one block per planner
decision: pin, pull, adopt, repeat. This module treats a reduce
stage's ENTIRE published location set as one object to compile: every
device-resident block (0xFFFE extension coordinates) is grouped into
batched DMA *waves* — fixed-shape [rows, bucket] stacks moved in one
mover dispatch — over a ring or all-to-all schedule, with compile-once
programs cached by (rows-class, bucket-class, dtype) exactly like the
exchange executable cache (DESIGN.md §22).

Waves run as a double-buffered PIPELINE (``collective.pipelineDepth``
in-flight entries): wave N+1's remote DMAs are dispatched while wave
N's rows merge, so the drain epoch of every wave but the last overlaps
a wave's worth of in-flight transfer. The host-plane passthrough reads
overlap with both ends — issued before the first wave, drained
concurrently with the last via the caller's ``drain`` callback.

Movers: one Pallas kernel epoch issues a wave's DMAs together (start
all, wait all). Consecutive same-class waves coalesce into one
depth-aware epoch, wave d+1 started before wave d drains. On one chip
(``pallas_wave_pull`` / ``pallas_pipelined_wave_pull``) every row is a
local DMA. On an n > 1 mesh (``pallas_mesh_wave_pull``) a per-slot lane
names the chip holding each row: only that chip sends it, only the
receiving chip waits, each chip's send shard holds only its own rows,
and the receive buffer is pooled per (slots, bucket) class and donated
back in. The send stack is gathered on the device from the pinned
source slabs (``_send_gather_program``), so no payload byte crosses to
the host, and each landed row is taken whole into an arena slab
(``_row_take_program``). Off a TPU, ``ops/remote_copy.py`` swaps each
mover kernel for a jnp program with the same contract; this module
runs the same path on every backend.

Self-tuning: the compiler's :class:`~sparkrdma_tpu.shuffle.autotune.
WaveAutoTuner` re-derives the effective ``collective.waveBytes`` per
(shuffle, stage-shape) signature from the stage's own wave stats plus
the job's TimeBreakdown and profiler gap frames — the second identical
stage of a job already runs with the adjusted cut.

Degrade ladder (byte-identical where a row degrades):

| condition                                   | outcome             |
|---------------------------------------------|---------------------|
| ``collective.enabled`` off                   | per-block planner   |
| < ``collective.minBlocks`` device blocks     | per-block planner   |
| block fails eligibility (size/dtype/arena)   | per-block planner   |
| slab evicted/spilled between plan and pin    | host triple, degrade++ |
| mover fails to dispatch or to land           | raises, pins closed |
| row adoption fails mid-pipeline              | host triple, degrade++ |
| abort unwinds with waves in flight           | pins closed, rows degrade |
"""

from __future__ import annotations

import functools
import logging
import time
from collections import defaultdict, deque
from contextlib import ExitStack
from typing import Deque, Dict, List, Optional, Sequence, Tuple

import numpy as np

from sparkrdma_tpu.analysis.lockorder import named_lock
from sparkrdma_tpu.locations import PartitionLocation
from sparkrdma_tpu.obs import get_registry, get_tracer
from sparkrdma_tpu.ops import remote_copy
from sparkrdma_tpu.ops.exchange import round_bucket, round_rows
from sparkrdma_tpu.ops.hbm_arena import DeviceBuffer, DeviceBufferManager
from sparkrdma_tpu.shuffle.autotune import (
    WaveAutoTuner,
    WaveReport,
    stage_signature,
)
from sparkrdma_tpu.shuffle.device_fetch import visible_arena

logger = logging.getLogger(__name__)


def merge_order_key(loc: PartitionLocation) -> Tuple:
    """Deterministic within-partition order of a stage's wave rows, by
    source."""
    return (
        loc.manager_id.executor_id,
        loc.block.mkey,
        loc.block.address,
        loc.block.arena_handle,
    )


@functools.lru_cache(maxsize=64)
def _send_gather_program(stack_shape: Tuple[int, ...], dtype_str: str):
    """Jitted send-stack gather of one row: writes
    ``src[off:off + len]``, zero-filled to the bucket, into slot
    ``slot`` of a send stack in the movers' lane layout
    (``[rows_b, *lanes]``, or ``[depth, rows_b, *lanes]`` for a
    pipelined entry; slot ``d * rows_b + i`` is wave d's row i). The
    stack is donated, so the row lands in place. ``(slot, off, len)``
    is a runtime operand, so jit compiles one executable per source
    slab class for a stack shape, whatever the blocks' lengths and the
    mix of classes in a wave."""
    import jax
    import jax.numpy as jnp

    dtype = jnp.dtype(dtype_str)
    lanes = tuple(stack_shape[-2:])
    bucket_elems = lanes[0] * lanes[1]

    def collective_send_gather(stack, src, meta):
        col = jnp.arange(bucket_elems, dtype=jnp.int32).reshape(lanes)
        zero = jnp.zeros((), dtype)
        # a bucket of zeros past the slab's end keeps the window whole
        # at any in-bounds offset (dynamic_slice would clamp the start
        # instead, shifting the row)
        src = jnp.pad(src, (0, bucket_elems))
        win = jax.lax.dynamic_slice(src, (meta[1],), (bucket_elems,))
        row = jnp.where(col < meta[2], win.reshape(lanes), zero)
        rows = jax.lax.dynamic_update_slice(
            stack.reshape(-1, *lanes), row[None], (meta[0], 0, 0)
        )
        return rows.reshape(stack_shape)

    return jax.jit(collective_send_gather, donate_argnums=0)


@functools.lru_cache(maxsize=64)
def _row_take_program(slots: int, bucket_elems: int, take_elems: int,
                      dtype_str: str):
    """Jitted adoption of one landed wave row: slot ``slot`` (a runtime
    operand) of this device's landed shard — ``slots`` rows of
    ``bucket_elems`` in the movers' lane layout — flattened and cut to
    ``take_elems``, the row's slab class. The send-stack gather zeroed
    every row past its length, so the cut row is the whole slab: one
    copy, and no slice or pad program per block length."""
    import jax
    import jax.numpy as jnp

    jnp.dtype(dtype_str)  # validate the cache key up front
    lanes = remote_copy.wave_row_shape(bucket_elems)

    def collective_row_take(landed, slot):
        rows = landed.reshape(slots, *lanes)
        row = jax.lax.dynamic_index_in_dim(rows, slot, keepdims=False)
        return row.reshape(bucket_elems)[:take_elems]

    return jax.jit(collective_row_take)


def send_stack_shard(rows: Dict[int, Tuple[object, int, int]], device,
                     depth: int, rows_b: int, bucket_elems: int, dtype):
    """One device's shard of a TPU entry's send stack. ``rows`` maps a
    row slot to ``(source array, element offset, element length)``,
    every source resident on ``device``; the other slots stay zero.
    Returns ``(shard, program keys)``: one key (source class, stack
    shape, dtype) per class written."""
    import jax.numpy as jnp

    lanes = remote_copy.wave_row_shape(bucket_elems)
    shape = (rows_b, *lanes) if depth == 1 else (depth, rows_b, *lanes)
    dtype_str = np.dtype(dtype).name
    prog = _send_gather_program(shape, dtype_str)
    stack = jnp.zeros(shape, dtype, device=device)
    keys = set()
    for slot in sorted(rows):
        src, off, n = rows[slot]
        keys.add((src.shape[0], shape, dtype_str))
        stack = prog(stack, src, np.array([slot, off, n], dtype=np.int32))
    return stack, keys


class _Row:
    """One device-resident block scheduled into a wave."""

    __slots__ = ("loc", "elems", "live")

    def __init__(self, loc: PartitionLocation, elems: int):
        self.loc = loc
        self.elems = elems
        self.live = True


class CollectiveWave:
    """One batched mover dispatch: ``rows`` blocks of one bucket class."""

    __slots__ = ("rows", "bucket_elems", "rows_b", "lane")

    def __init__(self, rows: List[_Row], bucket_elems: int, lane: str):
        self.rows = rows
        self.bucket_elems = bucket_elems
        self.rows_b = round_rows(len(rows))
        self.lane = lane  # primary source executor (ring ordering key)


class CollectivePlan:
    """A compiled reduce-stage fetch schedule.

    ``passthrough`` locations never entered the schedule (collective
    off, too few device blocks, or per-block ineligibility) — the
    caller runs them through the pre-existing per-block loop, which
    preserves exactly the old behavior when the compiler declines.

    ``sig``/``stage_bytes``/``max_group_bytes`` feed the wave
    self-tuner after execution (None/0 when the compiler declined)."""

    __slots__ = ("schedule", "waves", "passthrough", "device_blocks", "sig",
                 "stage_bytes", "max_group_bytes")

    def __init__(self, schedule: str, waves: List[CollectiveWave],
                 passthrough: List[PartitionLocation], device_blocks: int,
                 sig: Optional[Tuple] = None, stage_bytes: int = 0,
                 max_group_bytes: int = 0):
        self.schedule = schedule
        self.waves = waves
        self.passthrough = passthrough
        self.device_blocks = device_blocks
        self.sig = sig
        self.stage_bytes = stage_bytes
        self.max_group_bytes = max_group_bytes


class CollectiveResult:
    """One landed block: its location and the arena slab holding it."""

    __slots__ = ("loc", "dev")

    def __init__(self, loc: PartitionLocation, dev: DeviceBuffer):
        self.loc = loc
        self.dev = dev


class _InflightWave:
    """One pipeline entry: a wave (or a same-class kernel run of them)
    whose transfers are airborne. Pins stay held from issue to consume
    — the source slabs must survive until the recv semaphores land; the
    pipeline bounds the held set to ``depth`` entries."""

    __slots__ = ("waves", "pins", "t0", "dead", "all_dead", "landed",
                 "recv_key", "nbytes", "live")

    def __init__(self, waves: List[CollectiveWave], pins: ExitStack,
                 t0: float):
        self.waves = waves
        self.pins = pins
        self.t0 = t0
        self.dead: List[_Row] = []
        self.all_dead = False
        # the mover epoch's in-flight output, sharded over the mesh
        self.landed = None
        # n > 1 mesh: the receive pool class the landed output goes
        # back to once adopted
        self.recv_key = None
        self.nbytes = 0
        self.live = 0

    def close(self) -> None:
        try:
            self.pins.close()
        except Exception:
            logger.exception("collective pin release failed")


class ShuffleScheduleCompiler:
    """Compile + execute whole-stage device fetch schedules."""

    def __init__(self, conf, dev: DeviceBufferManager, executor_id: str,
                 tracer=None):
        self._conf = conf
        self._dev = dev
        self._executor_id = executor_id
        self._tracer = tracer if tracer is not None else get_tracer(executor_id)
        # program-cache bookkeeping (the lru_caches hold the programs;
        # this counts resolutions for the compile-churn metrics)
        self._seen_programs: set = set()
        self._cache_lock = named_lock("collective.compiler")
        # n > 1 mesh: free receive buffers by (slots, bucket, dtype),
        # each [n * slots, *lanes] sharded over the mesh
        self._recv_free: Dict[Tuple, List[object]] = defaultdict(list)
        self._recv_lock = named_lock("collective.recv_pool")
        self._tuner = WaveAutoTuner(conf, executor_id)
        reg = get_registry()
        role = executor_id
        self._m_plans = reg.counter("collective.plans", role=role)
        self._m_blocks = reg.counter("collective.blocks", role=role)
        self._m_bytes = reg.counter("collective.bytes", role=role)
        self._m_degrades = reg.counter("collective.degrades", role=role)
        self._m_compiles = reg.counter("collective.compiles", role=role)
        self._m_cache_hits = reg.counter("collective.cache_hits", role=role)
        self._m_plan_ms = reg.histogram("collective.plan_ms", role=role)
        self._m_overlap = reg.counter(
            "collective.wave_overlap_ms", role=role
        )
        self._m_inflight = reg.histogram(
            "collective.wave_inflight", role=role
        )
        # rows laid into the send stack on the device
        self._m_device_rows = reg.counter(
            "collective.device_assembled_rows", role=role
        )
        # per epoch: payload of the rows that cross chips, the
        # bucket bytes their DMAs carry, and the HBM the epoch newly
        # allocates across the mesh (send and receive shards)
        self._m_ici_payload = reg.counter(
            "collective.ici_payload_bytes", role=role
        )
        self._m_ici_moved = reg.counter(
            "collective.ici_moved_bytes", role=role
        )
        self._m_mesh_bytes = reg.counter(
            "collective.wave_mesh_bytes", role=role
        )
        # the device-fetch plane's counters stay the one source of truth
        # for "blocks that moved HBM->HBM" vs "device offers declined":
        # a landed wave row IS a device pull, a degraded row IS a
        # fallback. collective.* adds the schedule-level detail on top.
        self._m_plane_pulls = reg.counter(
            "device_fetch.plane.pulls", role=role
        )
        self._m_plane_bytes = reg.counter(
            "device_fetch.plane.bytes", role=role
        )
        self._m_plane_fallbacks = reg.counter(
            "device_fetch.plane.fallbacks", role=role
        )

    # ------------------------------------------------------------------
    # plan
    # ------------------------------------------------------------------
    def plan(self, locations: Sequence[PartitionLocation],
             dtype=np.uint8) -> CollectivePlan:
        """Compile the stage's location set into a wave schedule.

        Eligibility here mirrors the per-block planner's static checks
        (device extension present, above minBlockBytes, source arena
        mesh-visible) plus an elem-alignment check the stacked layout
        needs; residency/dtype are re-checked under the pin at execute
        time, where a miss degrades to the host triple."""
        t0 = time.perf_counter()
        conf = self._conf
        itemsize = np.dtype(dtype).itemsize
        if not conf.collective_enabled or not conf.device_fetch_enabled:
            return CollectivePlan("off", [], list(locations), 0)
        min_bytes = conf.device_fetch_min_block_bytes
        eligible: List[PartitionLocation] = []
        passthrough: List[PartitionLocation] = []
        for loc in locations:
            b = loc.block
            if (
                b.has_device
                and b.length >= min_bytes
                and b.length % itemsize == 0
                and b.arena_offset % itemsize == 0
                and visible_arena(loc.manager_id.executor_id) is not None
            ):
                eligible.append(loc)
            else:
                passthrough.append(loc)
        if len(eligible) < conf.collective_min_blocks:
            # too small a stage for a wave: the per-block planner keeps
            # the whole set (it may still pull the stragglers one by one)
            return CollectivePlan("off", [], list(locations), 0)

        # merge order: partition-major so a pid's rows are contiguous,
        # source-ordered within the partition
        eligible.sort(key=lambda loc: (loc.partition_id, merge_order_key(loc)))
        per_pid_bytes: Dict[int, int] = {}
        stage_bytes = 0
        max_len = 0
        for loc in eligible:
            pid = loc.partition_id
            bucketed = round_bucket(loc.block.length)
            per_pid_bytes[pid] = per_pid_bytes.get(pid, 0) + bucketed
            stage_bytes += bucketed
            max_len = max(max_len, loc.block.length)
        max_group_bytes = max(per_pid_bytes.values())

        lanes = sorted({loc.manager_id.executor_id for loc in eligible})
        schedule = conf.collective_schedule
        if schedule == "auto":
            schedule = "a2a" if len(lanes) > 2 else "ring"

        # the self-tuned cut: a stage shape the tuner has observed runs
        # with its adjusted budget (never below the largest partition
        # group — a partition's rows share one wave — and never above
        # the operator's configured cap)
        sig = stage_signature(
            schedule, len(lanes), round_rows(len(eligible)),
            round_bucket(max_len), np.dtype(dtype).name,
        )
        wave_budget = conf.collective_wave_bytes
        tuned = self._tuner.wave_bytes_for(sig)
        if tuned:
            wave_budget = min(max(tuned, max_group_bytes), wave_budget)

        # wave formation: pid-group granularity (a pid's rows ride ONE
        # wave), split only when a single pid alone overflows the wave
        # budget
        waves: List[CollectiveWave] = []
        cur_rows: List[_Row] = []
        cur_max_len = 0

        def seal():
            nonlocal cur_rows, cur_max_len
            if cur_rows:
                bucket = round_bucket(cur_max_len)
                waves.append(CollectiveWave(
                    cur_rows, bucket // itemsize,
                    cur_rows[0].loc.manager_id.executor_id,
                ))
                cur_rows, cur_max_len = [], 0

        i = 0
        n = len(eligible)
        while i < n:
            pid = eligible[i].partition_id
            j = i
            group_max = 0
            while j < n and eligible[j].partition_id == pid:
                group_max = max(group_max, eligible[j].block.length)
                j += 1
            group = eligible[i:j]
            group_bytes = per_pid_bytes[pid]
            if group_bytes > wave_budget and len(group) > 1:
                # oversized pid: seal what we have, stream the pid
                # through dedicated waves
                seal()
                for loc in group:
                    cur_rows.append(_Row(loc, loc.block.length // itemsize))
                    cur_max_len = max(cur_max_len, loc.block.length)
                    if sum(round_bucket(r.loc.block.length)
                           for r in cur_rows) >= wave_budget:
                        seal()
                seal()
            else:
                cur_bytes = sum(
                    round_bucket(r.loc.block.length) for r in cur_rows
                )
                if cur_rows and cur_bytes + group_bytes > wave_budget:
                    seal()
                for loc in group:
                    cur_rows.append(_Row(loc, loc.block.length // itemsize))
                cur_max_len = max(cur_max_len, group_max)
            i = j
        seal()

        if schedule == "ring":
            # lane-major wave order: one source lane in flight at a
            # time, walking the ring — the flow-controlled schedule.
            # Index lookups go through a precomputed map: the linear
            # lanes.index() scan inside a sort key is O(waves * lanes)
            # work a wide stage pays on every plan
            lane_index = {lane: k for k, lane in enumerate(lanes)}
            waves.sort(key=lambda w: lane_index[w.lane])
        self._m_plan_ms.observe((time.perf_counter() - t0) * 1e3)
        return CollectivePlan(
            schedule, waves, passthrough, len(eligible), sig=sig,
            stage_bytes=stage_bytes, max_group_bytes=max_group_bytes,
        )

    # ------------------------------------------------------------------
    # execute
    # ------------------------------------------------------------------
    def execute(
        self,
        shuffle_id: int,
        plan: CollectivePlan,
        dtype=np.uint8,
        drain=None,
    ) -> Tuple[List[CollectiveResult], List[PartitionLocation]]:
        """Run the compiled schedule as a double-buffered pipeline;
        returns ``(results, degraded)``, one result per landed block.

        Up to ``collective.pipelineDepth`` entries stay in flight:
        entry N+1's transfers are DISPATCHED before entry N's rows are
        waited on and adopted, so merge epochs overlap in-flight DMA.
        ``drain``, when given, is called with no arguments between
        pipeline steps — the host-plane caller passes its non-blocking
        arrivals drain so passthrough READs are consumed WHILE waves
        are in flight rather than after the last one.

        ``degraded`` lists every scheduled block that missed (evicted
        mid-stage, stale coordinates, adoption failure) — the caller
        host-fetches them, so the byte content of the stage is
        identical on every path. A mover that fails to dispatch or to
        land raises; if an exception unwinds (that, or ``drain``),
        every in-flight entry's pins are closed on the way out — no
        slab or pin outlives the stage."""
        if not plan.waves:
            return [], []
        depth = max(1, self._conf.collective_pipeline_depth)
        self._schedule_label = plan.schedule
        reg = get_registry()
        results: List[CollectiveResult] = []
        degraded: List[PartitionLocation] = []
        self._m_plans.inc()
        stats = {"dispatch_ms": 0.0, "wave_ms": 0.0, "overlap_ms": 0.0}
        with self._tracer.span(
            "shuffle.collective", shuffle_id=shuffle_id,
            schedule=plan.schedule, waves=len(plan.waves),
            blocks=plan.device_blocks, depth=depth,
        ):
            inflight: Deque[_InflightWave] = deque()

            def _degrade_rows(rows: List[_Row]) -> None:
                if not rows:
                    return
                degraded.extend(row.loc for row in rows)
                self._m_degrades.inc(len(rows))
                self._m_plane_fallbacks.inc(len(rows))

            def _consume_next() -> None:
                entry = inflight.popleft()
                self._consume_entry(
                    entry, shuffle_id, dtype, results, _degrade_rows, reg,
                    overlapped=bool(inflight), stats=stats,
                )
                if drain is not None:
                    drain()

            try:
                for group in self._coalesce(plan.waves, depth):
                    while len(inflight) >= depth:
                        _consume_next()
                    entry = self._issue_entry(
                        group, dtype, reg, overlapped=bool(inflight),
                        stats=stats,
                    )
                    _degrade_rows(entry.dead)
                    if entry.all_dead:
                        continue
                    inflight.append(entry)
                    self._m_inflight.observe(float(len(inflight)))
                    if drain is not None:
                        drain()
                while inflight:
                    _consume_next()
            finally:
                # abort drain (an exception is unwinding): release every
                # in-flight entry's pins and degrade its unadopted rows
                # — leak-free by construction, and the caller's host
                # refill keeps the stage byte-identical when it survives
                while inflight:
                    entry = inflight.popleft()
                    entry.close()
                    _degrade_rows(
                        [r for w in entry.waves for r in w.rows if r.live]
                    )
        # close the loop: feed the stage's wave stats back into the
        # per-shape cut for the NEXT identical stage
        if plan.sig is not None:
            try:
                self._tuner.observe(plan.sig, WaveReport(
                    stage_bytes=plan.stage_bytes,
                    min_group_bytes=plan.max_group_bytes,
                    waves=len(plan.waves),
                    depth=depth,
                    dispatch_ms=stats["dispatch_ms"],
                    wave_ms=stats["wave_ms"],
                    overlap_ms=stats["overlap_ms"],
                ))
            except Exception:
                logger.exception("wave autotune observe failed")
        return results, degraded

    # ------------------------------------------------------------------
    def _mover_dispatched(self, mover: str) -> None:
        """Count one pipeline entry's dispatch by the mover that ran it
        (``collective.mover_dispatches{mover}``): the on-chip smoke
        asserts from this that the Pallas movers carried the waves."""
        get_registry().counter(
            "collective.mover_dispatches", role=self._executor_id,
            mover=mover,
        ).inc()

    def _program_key_seen(self, key) -> None:
        with self._cache_lock:
            if key in self._seen_programs:
                self._m_cache_hits.inc()
            else:
                self._seen_programs.add(key)
                self._m_compiles.inc()

    def _coalesce(
        self, waves: List[CollectiveWave], depth: int
    ) -> List[List[CollectiveWave]]:
        """Group consecutive same-class waves, up to ``depth`` of them,
        into one pipeline entry: the run becomes ONE pipelined mover
        epoch with a DMA-semaphore array per in-flight wave."""
        if depth <= 1:
            return [[w] for w in waves]
        groups: List[List[CollectiveWave]] = []
        i = 0
        while i < len(waves):
            j = i + 1
            while (
                j < len(waves)
                and j - i < depth
                and waves[j].rows_b == waves[i].rows_b
                and waves[j].bucket_elems == waves[i].bucket_elems
            ):
                j += 1
            groups.append(list(waves[i:j]))
            i = j
        return groups

    def _issue_entry(
        self, waves: List[CollectiveWave], dtype, reg, overlapped: bool,
        stats: Dict[str, float],
    ) -> _InflightWave:
        """Pin and DISPATCH one pipeline entry without waiting — the
        issue half of the double buffer. Rows that fail the under-pin
        residency re-check come back in ``entry.dead`` (the caller
        degrades them); a mover failure raises, pins released. The
        entry's pins stay held until its consume: the source slabs must
        outlive the in-flight DMAs."""
        t0 = time.perf_counter()
        itemsize = np.dtype(dtype).itemsize
        pins = ExitStack()
        entry = _InflightWave(waves, pins, t0)
        # per wave: row index -> pinned source slab
        sources: List[Dict[int, DeviceBuffer]] = []
        try:
            for wave in waves:
                # the send stack is gathered on the device at dispatch:
                # here the sources are only pinned
                with self._tracer.timed("fetch.wave.assemble"):
                    sources.append(self._pin_rows(wave, entry, dtype))
            live_rows = [r for w in waves for r in w.rows if r.live]
            if not live_rows:
                # every row died at the pin: nothing to move; the
                # caller degrades them all
                pins.close()
                entry.all_dead = True
                return entry
            self._dispatch_pallas(entry, sources, dtype)
            if len(waves) > 1:
                key = ("wave-pipe", len(waves), waves[0].rows_b,
                       waves[0].bucket_elems, np.dtype(dtype).name)
                self._program_key_seen(key)
            else:
                for wave in waves:
                    key = ("wave", wave.rows_b, wave.bucket_elems,
                           np.dtype(dtype).name)
                    self._program_key_seen(key)
        except Exception:
            # the movers ARE the path: a failure is a fault to surface,
            # not a row to degrade
            pins.close()
            raise
        entry.live = len(live_rows)
        entry.nbytes = sum(r.elems * itemsize for r in live_rows)
        dispatch_ms = (time.perf_counter() - t0) * 1e3
        reg.histogram(
            "collective.wave_dispatch_ms", role=self._executor_id,
            schedule=self._schedule_label,
        ).observe(dispatch_ms)
        stats["dispatch_ms"] += dispatch_ms
        if overlapped:
            # this dispatch ran while earlier waves were still in
            # flight — the pipeline's whole point, surfaced as a
            # counter the benches assert on
            stats["overlap_ms"] += dispatch_ms
            self._m_overlap.inc(dispatch_ms)
        return entry

    def _pin_rows(self, wave: CollectiveWave, entry: _InflightWave,
                  dtype) -> Dict[int, DeviceBuffer]:
        """Pin one wave's source slabs (into ``entry.pins``): returns
        row index -> pinned buffer for every row whose source is still
        resident and of ``dtype``; the others land in ``entry.dead``."""
        srcs: Dict[int, DeviceBuffer] = {}
        for i, row in enumerate(wave.rows):
            blk = row.loc.block
            arena = visible_arena(row.loc.manager_id.executor_id)
            src = None
            if arena is not None:
                src = entry.pins.enter_context(
                    arena.pinned_if_resident(blk.arena_handle)
                )
            if (
                src is None
                or blk.arena_offset + blk.length > src.capacity
                or np.dtype(src.array.dtype) != np.dtype(dtype)
            ):
                row.live = False
                entry.dead.append(row)
                continue
            srcs[i] = src
        return srcs

    def _dispatch_pallas(self, entry: _InflightWave,
                         sources: List[Dict[int, DeviceBuffer]], dtype):
        """START the entry's DMAs as one mover epoch (the depth-aware
        double-buffered program when the entry carries a same-class
        run) WITHOUT waiting, into ``entry.landed``; consume takes the
        landed rows per wave. The send stack is gathered on the device
        from the pinned source slabs. A mover failure raises."""
        import jax
        from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

        devices = jax.devices()[: remote_copy.mesh_device_count()]
        if len(devices) > 1:
            return self._dispatch_mesh(entry, sources, dtype, devices)
        waves = entry.waves
        itemsize = np.dtype(dtype).itemsize
        depth, rows_b = len(waves), waves[0].rows_b
        b_elems = waves[0].bucket_elems
        # one chip: every row is a local DMA, so every hop is 0
        hops = np.zeros((depth, rows_b), dtype=np.int32)
        # row slot -> (source array, offset, length)
        rows: Dict[int, Tuple[object, int, int]] = {}
        for d, (wave, srcs) in enumerate(zip(waves, sources)):
            for i, src in srcs.items():
                row = wave.rows[i]
                rows[d * rows_b + i] = (
                    src.array, row.loc.block.arena_offset // itemsize,
                    row.elems,
                )
        mesh = Mesh(devices, ("x",))
        with self._tracer.timed("fetch.wave.h2d"):
            hop_lane = jax.device_put(
                hops[0] if depth == 1 else hops, NamedSharding(mesh, P())
            )
        with self._tracer.timed("fetch.wave.assemble"):
            shard = self._send_shard(rows, devices[0], depth, rows_b,
                                     b_elems, dtype)
            stack = jax.make_array_from_single_device_arrays(
                shard.shape, NamedSharding(mesh, P("x")), [shard]
            )
        self._m_device_rows.inc(len(rows))
        # the send stack and the kernel's output, each a stack's bytes
        self._m_mesh_bytes.inc(2 * stack.nbytes)
        if depth == 1:
            self._mover_dispatched("pallas_wave_pull")
            entry.landed = remote_copy.pallas_wave_pull(hop_lane, stack)
            return
        self._mover_dispatched("pallas_pipelined_wave_pull")
        entry.landed = remote_copy.pallas_pipelined_wave_pull(
            hop_lane, stack, depth
        )

    def _send_shard(self, rows, device, depth: int, rows_b: int,
                    b_elems: int, dtype):
        """One chip's send shard (``send_stack_shard``), its gather
        programs counted."""
        shard, keys = send_stack_shard(rows, device, depth, rows_b,
                                       b_elems, dtype)
        for key in keys:
            self._program_key_seen(("send-gather", device.id, key))
        return shard

    def _dispatch_mesh(self, entry: _InflightWave,
                       sources: List[Dict[int, DeviceBuffer]], dtype,
                       devices) -> None:
        """``_dispatch_pallas`` on an n > 1 mesh. Slot ``d * rows_b +
        i`` carries wave d's row i. Each chip's send shard holds only
        the rows whose source slab it holds, as many rows as the
        busiest chip sends in the entry (bucketed); the lane tells the
        kernel which chip sends each slot, from which shard row, and
        the hop to this executor's chip. The receive buffer comes from
        the pool and goes back to it once the entry is adopted."""
        import jax
        from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

        waves = entry.waves
        n = len(devices)
        index = {d.id: k for k, d in enumerate(devices)}
        dst = index[self._dev.device.id]
        itemsize = np.dtype(dtype).itemsize
        depth, rows_b = len(waves), waves[0].rows_b
        b_elems = waves[0].bucket_elems
        slots = depth * rows_b
        # per slot: source chip (-1: no row), row of its send shard, hop
        lane = np.zeros((3, slots), dtype=np.int32)
        lane[0] = -1
        # per mesh chip: send shard row -> (source array, offset, length)
        shard_rows: List[Dict[int, Tuple[object, int, int]]] = [
            {} for _ in devices
        ]
        payload = crossing = 0
        for d, (wave, srcs) in enumerate(zip(waves, sources)):
            for i, src in srcs.items():
                row = wave.rows[i]
                k = index[src.device.id]
                hop = (dst - k) % n
                lane[:, d * rows_b + i] = (k, len(shard_rows[k]), hop)
                shard_rows[k][len(shard_rows[k])] = (
                    src.array, row.loc.block.arena_offset // itemsize,
                    row.elems,
                )
                if hop:
                    payload += row.elems * itemsize
                    crossing += 1
        send_rows = round_rows(max(len(r) for r in shard_rows))
        mesh = Mesh(devices, ("x",))
        sharded = NamedSharding(mesh, P("x"))
        lanes = remote_copy.wave_row_shape(b_elems)
        with self._tracer.timed("fetch.wave.h2d"):
            lane_dev = jax.device_put(lane.reshape(-1),
                                      NamedSharding(mesh, P()))
        with self._tracer.timed("fetch.wave.assemble"):
            shards = [
                self._send_shard(shard_rows[k], device, 1, send_rows,
                                 b_elems, dtype)
                for k, device in enumerate(devices)
            ]
            stack = jax.make_array_from_single_device_arrays(
                (n * send_rows, *lanes), sharded, shards
            )
            entry.recv_key = (slots, b_elems, np.dtype(dtype).name)
            recv = self._take_recv(entry.recv_key, sharded)
        self._m_device_rows.inc(sum(len(rows) for rows in shard_rows))
        self._m_ici_payload.inc(payload)
        self._m_ici_moved.inc(crossing * b_elems * itemsize)
        self._m_mesh_bytes.inc(stack.nbytes)
        single = depth == 1
        self._mover_dispatched(
            "pallas_wave_pull" if single else "pallas_pipelined_wave_pull"
        )
        entry.landed = remote_copy.pallas_mesh_wave_pull(
            lane_dev, stack, recv, depth
        )

    def _take_recv(self, key: Tuple, sharding):
        """A free receive buffer of class ``key`` (slots, bucket elems,
        dtype) from the pool, or a new one of ``slots`` rows on every
        chip of ``sharding``'s mesh, counted in
        ``collective.wave_mesh_bytes``."""
        import jax.numpy as jnp

        with self._recv_lock:
            free = self._recv_free[key]
            if free:
                return free.pop()
        slots, b_elems, dtype_name = key
        shape = (sharding.mesh.size * slots,
                 *remote_copy.wave_row_shape(b_elems))
        recv = jnp.zeros(shape, dtype_name, device=sharding)
        self._m_mesh_bytes.inc(recv.nbytes)
        return recv

    def release_receive_buffers(self) -> None:
        """Free the pooled receive buffers; buffers of entries still in
        flight return to the pool when adopted."""
        with self._recv_lock:
            free = [a for arrs in self._recv_free.values() for a in arrs]
            self._recv_free.clear()
        for arr in free:
            arr.delete()

    def _consume_entry(
        self, entry: _InflightWave, shuffle_id: int, dtype, results,
        _degrade_rows, reg, overlapped: bool, stats: Dict[str, float],
    ) -> None:
        """Wait for one entry's transfers (the recv-semaphore wait),
        adopt its rows into arena slabs, and release its pins. A landing
        failure raises, pins released; an adoption failure degrades the
        affected row and the pipeline keeps flowing."""
        import jax

        t0 = time.perf_counter()
        role = self._executor_id
        try:
            with self._tracer.timed("fetch.wave.wait"):
                jax.block_until_ready(entry.landed)
                landed = self._landed_shard(entry)
        except Exception:
            # an epoch that failed to land is a device fault: surface it
            # (the pins still release)
            entry.close()
            raise
        itemsize = np.dtype(dtype).itemsize
        now = time.perf_counter()
        try:
            for d, wave in enumerate(entry.waves):
                live = [r for r in wave.rows if r.live]
                if not live:
                    continue
                nbytes = sum(r.elems * itemsize for r in live)
                self._m_blocks.inc(len(live))
                self._m_bytes.inc(nbytes)
                self._m_plane_pulls.inc(len(live))
                self._m_plane_bytes.inc(nbytes)
                reg.counter(
                    "collective.waves", role=role,
                    schedule=self._schedule_label,
                ).inc()
                # per-wave span (dma-wave attribution, obs/attr.py):
                # nests under execute()'s shuffle.collective span via the
                # contextvar parent, so the critical path can enter the
                # wave level instead of one opaque multi-wave slice
                with self._tracer.span(
                    "shuffle.collective.wave", shuffle_id=shuffle_id,
                    rows=len(live), bytes=nbytes,
                ), self._tracer.timed("fetch.wave.adopt"):
                    out, failed = self._adopt_wave(
                        wave, landed, d * wave.rows_b, dtype
                    )
                results.extend(out)
                _degrade_rows(failed)
                reg.histogram(
                    "collective.wave_ms", role=role,
                    schedule=self._schedule_label,
                ).observe((now - entry.t0) * 1e3)
                stats["wave_ms"] += (now - entry.t0) * 1e3
            if entry.recv_key is not None:
                # every row taken from it is dispatched: the next
                # epoch of its class may write it again
                with self._recv_lock:
                    self._recv_free[entry.recv_key].append(entry.landed)
        finally:
            entry.close()
        consume_ms = (time.perf_counter() - t0) * 1e3
        if overlapped:
            # this merge ran with later waves' DMAs already airborne
            stats["overlap_ms"] += consume_ms
            self._m_overlap.inc(consume_ms)

    # conf-resolved schedule of the plan currently executing (execute()
    # runs plans one at a time per endpoint; set before the wave loop)
    _schedule_label = "ring"

    def _landed_shard(self, entry: _InflightWave):
        """This executor's own shard of an entry's landed output —
        ``[rows_b, *lanes]``, or ``[depth, rows_b, *lanes]`` for a
        pipelined entry; wave d's row i is slot ``d * rows_b + i``."""
        return next(
            s.data for s in entry.landed.addressable_shards
            if s.device == self._dev.device
        )

    def _take_row(self, landed, slot: int, wave: CollectiveWave,
                  class_elems: int, dtype):
        """One landed row cut to its slab class (``_row_take_program``);
        a class wider than the bucket keeps the bucket, and
        ``put_array`` pads the rest."""
        key = ("take", landed.size // wave.bucket_elems, wave.bucket_elems,
               min(class_elems, wave.bucket_elems), np.dtype(dtype).name)
        self._program_key_seen(key)
        return _row_take_program(*key[1:])(landed, slot)

    def _adopt_wave(self, wave, landed, slot0, dtype):
        """Adopt a landed wave into arena slabs, one per live row, each
        taken whole from this executor's landed shard (``landed``, the
        wave's rows from slot ``slot0``). Returns ``(results,
        failed_rows)`` — an adoption failure degrades its row instead of
        unwinding the pipeline."""
        itemsize = np.dtype(dtype).itemsize
        out: List[CollectiveResult] = []
        failed: List[_Row] = []
        for i, row in enumerate(wave.rows):
            if not row.live:
                continue
            nbytes = row.elems * itemsize
            try:
                dev = self._dev.get(nbytes)
                try:
                    dev = dev.put_array(self._take_row(
                        landed, slot0 + i, wave, dev.capacity // itemsize,
                        dtype,
                    ))
                except Exception:
                    dev.free()
                    raise
            except Exception:
                logger.exception(
                    "wave adoption failed for partition %d; degrading",
                    row.loc.partition_id,
                )
                failed.append(row)
                continue
            dev.length = nbytes
            out.append(CollectiveResult(row.loc, dev))
        return out, failed
