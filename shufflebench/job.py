"""One deployment and the sort jobs it runs, through the program's own
entry points only.

A job is the served path of ``chip_smoke.py`` phase A, with every map
and reduce task on the executor a scheduler would give it:

1. per executor, a ``MapTaskPipeline`` over its map tasks:
   ``MapShardSorter.sort_partition``, ``DeviceShuffleIO.
   stage_device_blocks``, ``publish_staged``;
2. per executor, a ``ReduceTaskPipeline`` over its reducers:
   ``fetch_host_blocks`` (the device fetch plane, default conf),
   ``verify_host_block``, ``stage_host_block``, then the merge, which is
   ``ops/sort.merge_received`` inside the jit ``shufflebench_merge``,
   and the jit ``shufflebench_digest`` over its output;
3. ``unpublish`` and ``unregister_shuffle``, so HBM is freed.

The range-partition edges come with the input (``keys.py``). Each
reducer's merged output leaves a digest (counts, sum, xor, least and
largest key, descents) for the reference to check; the outputs of a
seeded eighth of the reducers of the newest job are held on the device
until the next job ends, so the run reads the last job's back after
its window has closed.
Executors run their pipelines concurrently, one thread each, as their
own processes would. Every job gets a new shuffle id. The program runs
with its default configuration.

``fault`` breaks the timed path on purpose, for the control and the
fault tests; a benchmark run never sets it.
"""

from __future__ import annotations

import contextlib
import threading
import time
from concurrent.futures import ThreadPoolExecutor
from dataclasses import dataclass, field
from typing import Dict, List, Optional, Tuple

import numpy as np

from shufflebench.keys import CHECK, seed_words

SENTINEL = 0xFFFFFFFF
FAULTS = ("merge_unsorted", "drop_half", "local_only", "alter_key")


def _merge_programs():
    import jax
    import jax.numpy as jnp

    from sparkrdma_tpu.ops.sort import merge_received

    def slab_of(arrs):
        cap = max(a.shape[0] for a in arrs)
        return jnp.stack([
            a if a.shape[0] == cap else jnp.concatenate(
                [a, jnp.full((cap - a.shape[0],), SENTINEL, a.dtype)])
            for a in arrs
        ])

    @jax.jit
    def shufflebench_merge(arrs, counts):
        return merge_received(slab_of(arrs), counts, SENTINEL)

    @jax.jit
    def shufflebench_digest(merged, total):
        """Of the output's first ``total`` entries: ``total``, the keys
        among them, keys past them, their sum and xor (mod 2^32), the
        least and largest, and the descents between neighbours."""
        valid = jnp.arange(merged.shape[0], dtype=jnp.int32) < total
        is_key = merged != jnp.uint32(SENTINEL)
        keys = jnp.where(valid, merged, jnp.uint32(0))
        xor = jax.lax.reduce(keys, jnp.uint32(0), jax.lax.bitwise_xor, (0,))
        return jnp.stack([
            total.astype(jnp.uint32),
            jnp.sum(valid & is_key, dtype=jnp.uint32),
            jnp.sum(~valid & is_key, dtype=jnp.uint32),
            jnp.sum(keys, dtype=jnp.uint32),
            xor,
            jnp.min(jnp.where(valid, merged, jnp.uint32(SENTINEL))),
            jnp.max(keys),
            jnp.sum(valid[1:] & (merged[1:] < merged[:-1]),
                    dtype=jnp.uint32),
        ])

    @jax.jit
    def shufflebench_merge_unsorted(arrs, counts):
        # the control: the blocks' valid prefixes concatenated, with the
        # sort left out (each block is sorted, their union is not)
        slab = slab_of(arrs)
        valid = (jnp.arange(slab.shape[1], dtype=jnp.int32)[None, :]
                 < counts[:, None]).reshape(-1)
        flat = jnp.where(valid, slab.reshape(-1), jnp.uint32(SENTINEL))
        order = jnp.argsort(jnp.where(valid, 0, 1), stable=True)
        return flat[order], counts.sum()

    return shufflebench_merge, shufflebench_merge_unsorted, shufflebench_digest


class Spans:
    """The benchmark's own spans around each call into a layer, on the
    host clock; with ``annotate`` also written into the profiler's trace
    (``sb.<name>``), so device gaps can be named by host work."""

    def __init__(self, annotate: bool = False):
        self.items: List[Tuple[str, float, float]] = []
        self._lock = threading.Lock()
        self._annotate = annotate

    @contextlib.contextmanager
    def span(self, name: str):
        ctx = contextlib.nullcontext()
        if self._annotate:
            import jax

            ctx = jax.profiler.TraceAnnotation("sb." + name)
        t0 = time.perf_counter()
        try:
            with ctx:
                yield
        finally:
            t1 = time.perf_counter()
            with self._lock:
                self.items.append((name, t0, t1))


@dataclass
class JobResult:
    t0: float
    t1: float
    edges: np.ndarray
    digests: Dict[int, np.ndarray] = field(default_factory=dict)
    task_ms: List[float] = field(default_factory=list)
    kept: Dict[int, np.ndarray] = field(default_factory=dict)
    blocks: int = 0
    pulled: int = 0


class Deployment:
    """Driver, executors and their device endpoints for one config."""

    def __init__(self, config: dict, traffic: dict, devices, seed: int,
                 keys: np.ndarray, edges: np.ndarray,
                 spans: Optional[Spans] = None,
                 fault: Optional[str] = None, sid_base: int = 1000):
        from sparkrdma_tpu.models import MapShardSorter
        from sparkrdma_tpu.shuffle.device_io import DeviceShuffleIO
        from sparkrdma_tpu.shuffle.manager import TpuShuffleManager
        from sparkrdma_tpu.utils.config import TpuShuffleConf

        if fault is not None and fault not in FAULTS:
            raise ValueError(f"unknown fault {fault!r}")
        self.config, self.traffic, self.seed = config, traffic, seed
        self.keys, self.edges = keys, edges
        self.fault = fault
        self.spans = spans or Spans()
        self.n_exec = int(config["executors"])
        self.n_maps = int(config["maps"])
        self.reducers = int(traffic["reducers"])
        self.devices = list(devices)
        self.conf = TpuShuffleConf({})  # the default configuration
        self.driver = TpuShuffleManager(self.conf, is_driver=True)
        self.execs, self.ios, self.sorters = [], [], []
        try:
            for e in range(self.n_exec):
                dev = self.devices[e % len(self.devices)]
                ex = TpuShuffleManager(self.conf, is_driver=False,
                                       executor_id=f"sb-exec-{e}")
                self.execs.append(ex)
                self.ios.append(DeviceShuffleIO(ex, device=dev))
                self.sorters.append(MapShardSorter(device=dev))
        except BaseException:
            self.stop()
            raise
        self.shards = np.array_split(keys, self.n_maps)
        self.map_owner = [m * self.n_exec // self.n_maps
                          for m in range(self.n_maps)]
        self.reduce_owner = [r * self.n_exec // self.reducers
                             for r in range(self.reducers)]
        self.checked_per_job = max(1, self.reducers // 8)
        self.held: Dict[int, object] = {}  # the newest job's checked outputs
        self._result_lock = threading.Lock()
        self._merge, self._merge_unsorted, self._digest = _merge_programs()
        self._pool = ThreadPoolExecutor(self.n_exec,
                                        thread_name_prefix="sb-executor")
        self._next_sid = sid_base

    # ------------------------------------------------------------------
    def stop(self) -> None:
        pool = getattr(self, "_pool", None)
        if pool is not None:
            pool.shutdown(wait=True)
        for io in self.ios:
            io.stop()
        for ex in self.execs:
            ex.stop()
        self.driver.stop()

    def _on_executors(self, fn) -> list:
        futures = [self._pool.submit(fn, e) for e in range(self.n_exec)]
        return [f.result() for f in futures]

    # ------------------------------------------------------------------
    def run_job(self, job_index: int) -> JobResult:
        from sparkrdma_tpu.shuffle.handle import (
            BaseShuffleHandle,
            HashPartitioner,
        )

        sid = self._next_sid
        self._next_sid += 1
        span = self.spans.span
        edges = self.edges
        res = JobResult(time.perf_counter(), 0.0, edges)
        held: Dict[int, object] = {}
        checked = set()
        if job_index >= 0:  # warm-up jobs pass a negative index
            rng = np.random.default_rng(
                seed_words(self.seed, CHECK, job_index))
            checked = set(int(r) for r in rng.choice(
                self.reducers, self.checked_per_job, replace=False))
        self.driver.register_shuffle(BaseShuffleHandle(
            shuffle_id=sid, num_maps=self.n_maps,
            partitioner=HashPartitioner(self.reducers)))
        try:
            self._on_executors(lambda e: self._map_phase(e, sid, edges))
            reports = self._on_executors(
                lambda e: self._reduce_phase(e, sid, checked, res, held))
        finally:
            with span("job.release"):
                for io in self.ios:
                    io.unpublish(sid)
                for ex in self.execs:
                    ex.unregister_shuffle(sid)
                self.driver.unregister_shuffle(sid)
        for rep in reports:
            for r, digest in rep.results:
                res.digests[r] = digest
        self.held = held
        res.t1 = time.perf_counter()
        return res

    def read_back(self, res: JobResult) -> None:
        """The held outputs of the newest job, ``res``, to the host: run
        once its window has closed."""
        res.kept = {r: np.asarray(a)[: int(res.digests[r][0])]
                    for r, a in self.held.items()}
        self.held = {}

    # ------------------------------------------------------------------
    def _map_phase(self, e: int, sid: int, edges: np.ndarray) -> None:
        from sparkrdma_tpu.shuffle.writer.pipeline import MapTaskPipeline

        span = self.spans.span
        io, sorter = self.ios[e], self.sorters[e]
        reducers = self.reducers

        def sort_fn(m):
            with span("map.sort"):
                return sorter.sort_partition(self.shards[m], edges)

        def stage_fn(_m, sorted_out):
            local, bounds = sorted_out
            with span("map.stage"):
                return io.stage_device_blocks(sid, {
                    r: local[bounds[r]: bounds[r + 1]]
                    for r in range(reducers)})

        def publish_fn(_m, locs):
            with span("map.publish"):
                io.publish_staged(sid, locs, num_map_outputs=1)

        MapTaskPipeline(
            sort_fn, stage_fn, publish_fn,
            parallelism=self.conf.map_parallelism,
            depth=self.conf.map_pipeline_depth, role=f"sb-map-{e}",
        ).run([m for m in range(self.n_maps) if self.map_owner[m] == e])

    def _reduce_phase(self, e: int, sid: int, checked: set,
                      res: JobResult, held: dict):
        import jax
        import jax.numpy as jnp

        from sparkrdma_tpu.shuffle.device_fetch import DevicePulledBlock
        from sparkrdma_tpu.shuffle.reader.pipeline import ReduceTaskPipeline

        span = self.spans.span
        io = self.ios[e]
        me = self.execs[e].executor_id
        started: Dict[int, float] = {}
        lock = self._result_lock  # executors' pipelines share ``res``
        fault = self.fault

        def fetch(r):
            started[r] = time.perf_counter()
            with span("reduce.fetch"):
                blocks = io.fetch_host_blocks(
                    sid, r, r + 1, timeout_s=300, dtype=np.uint32
                ).get(r, [])
            blocks.sort(key=lambda hb: hb.loc.manager_id.executor_id)
            drop = []
            if fault == "drop_half":
                drop = blocks[len(blocks) - len(blocks) // 2:]
            elif fault == "local_only":
                drop = [hb for hb in blocks
                        if hb.loc.manager_id.executor_id != me]
            for hb in drop:
                hb.release()
                blocks.remove(hb)
            with lock:
                res.blocks += len(blocks)
                res.pulled += sum(isinstance(hb, DevicePulledBlock)
                                  for hb in blocks)
            return blocks

        def verify(_r, blocks):
            with span("reduce.verify"):
                return [io.verify_host_block(hb) for hb in blocks]

        def stage(_r, blocks):
            with span("reduce.stage"):
                return [io.stage_host_block(hb, dtype=np.uint32)
                        for hb in blocks]

        def merge(r, bufs):
            with span("reduce.merge"):
                # the largest slab first, so each mix of size classes
                # meets one compiled program
                bufs = sorted(bufs, key=lambda b: -b.capacity)
                with io.device_buffers.pinned_on_device(bufs):
                    arrs = tuple(b.array for b in bufs)
                    counts = jnp.asarray([b.length // 4 for b in bufs],
                                         jnp.int32)
                    prog = (self._merge_unsorted
                            if fault == "merge_unsorted" else self._merge)
                    merged, total = prog(arrs, counts)
                    if fault == "alter_key":
                        merged = merged.at[0].add(jnp.uint32(1))
                    jax.block_until_ready(merged)
                for b in bufs:
                    b.free()
            done = time.perf_counter()
            digest = np.asarray(self._digest(merged, total))
            with lock:
                res.task_ms.append((done - started[r]) * 1e3)
                if r in checked:
                    held[r] = merged
            return r, digest

        def discard(stage_name, _item, value):
            if not value:
                return
            if stage_name in ("fetch", "decode"):
                for hb in value:
                    hb.release()
            elif stage_name == "stage":
                for b in value:
                    b.free()

        conf = self.conf
        return ReduceTaskPipeline(
            fetch, verify, stage, merge,
            parallelism=conf.reduce_parallelism,
            depth=conf.reduce_pipeline_depth,
            double_buffer=conf.reduce_double_buffer_staging,
            role=f"sb-reduce-{e}", discard_fn=discard,
        ).run([r for r in range(self.reducers) if self.reduce_owner[r] == e])
