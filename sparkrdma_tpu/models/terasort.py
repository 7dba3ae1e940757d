"""Device-resident distributed TeraSort — the framework's flagship workload.

The reference's headline benchmark is HiBench TeraSort-175GB, 1.41x
over stock Spark sort shuffle (README.md:7-19, BASELINE.md). Its
pipeline is: map tasks range-partition records -> all-to-all shuffle
over one-sided RDMA READ -> reduce tasks merge-sort their range
(SURVEY.md §3.3-3.4). The TPU-native pipeline keeps the same three
stages but runs them *where the bytes live*:

  partition (radix on top key bits, on-device)
    -> exchange (ExchangeProgram: lax.all_to_all over ICI/DCN)
    -> merge (masked sort of the received slab, on-device)

all inside ONE jitted SPMD program per (mesh, shard size, capacity) —
compile-once / execute-many, the reference's SVC pattern. Output:
shard i of the mesh holds the globally i-th sorted key range, sorted
— i.e. a total global sort.

Static-shape handling (SURVEY.md §7.3(2)): each peer bucket holds
``capacity = ceil(N/E) * capacity_factor`` keys; the step returns an
``overflowed`` flag instead of silently corrupting, and the host
retries with the next capacity class — exactly how the registered
pool re-rounds sizes.
"""

from __future__ import annotations

import math
from typing import Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax import shard_map
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from sparkrdma_tpu.obs import get_registry, get_tracer
from sparkrdma_tpu.ops.hbm_arena import DeviceReadback
from sparkrdma_tpu.ops.sort import (
    device_sort,
    merge_received,
    split_sorted,
    split_sorted_edges,
)
from sparkrdma_tpu.parallel.mesh import make_mesh, shard_spec

KEY_BITS = 32
SENTINEL = jnp.uint32(0xFFFFFFFF)


class MapShardSorter:
    """Device sort + range-partition of ONE map shard — the map plane's
    compute kernel (pipelined map plane, DESIGN.md).

    The e2e map side was losing to the host baseline by running
    ``np.sort`` per shard while the device sort this framework owns
    runs ~9x host speed (BENCH_r05 ``device_sort_gbps``); this class
    moves that O(N log N) step onto the chip: send the shard straight to
    the sorter's device (padded on the host with the key-space sentinel
    only when it falls short of its size class), one ``device_sort`` (the
    measured optimum, ops/sort.py), then a device-side ``searchsorted``
    against the reducer range edges — the shard lands back on host
    already sorted AND cut at every reducer boundary, so staging is pure
    slicing.

    Compile-once/execute-many: shards pad up to a power-of-two size
    class, so jit's dispatch cache holds ONE executable per
    (size class, num edges) — the SVC pattern every model here follows.
    Edges ride as a device ARGUMENT (not a static), so different
    reducer counts reuse nothing but different edge VALUES recompile
    nothing.
    """

    def __init__(self, device=None, tracer=None):
        self._device = device
        # map.sort.{pad,h2d,device,d2h} spans: the host work around the
        # sort, apart from the sort itself
        self._tracer = tracer if tracer is not None else get_tracer("map")
        # shards sorted, and those of them sent without a host pad
        reg = get_registry()
        role = self._tracer.role
        self._m_shards = reg.counter("map.sort.shards", role=role)
        self._m_unpadded = reg.counter("map.sort.unpadded_shards", role=role)

        @jax.jit
        def _step(padded, edges, n_valid):
            s = device_sort(padded)
            # sentinels sort to the tail; clamp every cut to the valid
            # count so an edge above the max real key can't spill a
            # reducer's bound into the padding
            cuts = jnp.minimum(
                jnp.searchsorted(s, edges).astype(jnp.int32), n_valid
            )
            return s, cuts

        self._step = _step

    @staticmethod
    def _size_class(n: int) -> int:
        return max(1024, 1 << (n - 1).bit_length())

    def warm(self, n: int, num_edges: int) -> None:
        """Compile the (size class, edges) executable ahead of the
        timed path — the JVM-startup analogue the ledger excludes."""
        cap = self._size_class(n)
        jax.block_until_ready(
            self._step(
                jnp.full((cap,), SENTINEL, jnp.uint32),
                jnp.zeros((num_edges,), jnp.uint32),
                jnp.int32(0),
            )[0]
        )

    def sort_partition(
        self, keys: np.ndarray, edges: np.ndarray
    ) -> Tuple[np.ndarray, np.ndarray]:
        """Sort ``keys`` (uint32) and cut at ``edges`` (ascending reducer
        range boundaries, len = num_reducers - 1).

        Returns ``(sorted_keys [n], bounds [num_reducers + 1])`` with
        reducer r's keys at ``sorted_keys[bounds[r]:bounds[r + 1]]``.
        ``sorted_keys`` is a ``DeviceReadback``: it and its slices name
        the sorted device array, so staging cuts their arena slabs on
        the device (``DeviceShuffleIO.stage_device_blocks``).
        """
        timed = self._tracer.timed
        n = len(keys)
        cap = self._size_class(n)
        self._m_shards.inc()
        with timed("map.sort.pad"):
            # a shard that fills its class goes to the device as it is;
            # one below it is copied once into an uninitialised class
            # array whose tail alone takes the sentinel
            if n == cap:
                host = np.ascontiguousarray(keys, dtype=np.uint32)
                self._m_unpadded.inc()
            else:
                host = np.empty((cap,), dtype=np.uint32)
                host[:n] = keys
                host[n:] = np.uint32(SENTINEL)
        with timed("map.sort.h2d"):
            # on the CPU this may alias ``keys``; the alias goes with
            # ``dev`` before this call returns
            dev = jax.device_put(host, self._device)
            edges_dev = jax.device_put(
                np.asarray(edges, dtype=np.uint32), self._device)
            dev.block_until_ready()
        with timed("map.sort.device"):
            s, cuts = self._step(dev, edges_dev, np.int32(n))
            # the unsorted copy goes once the sort has read it, not at
            # return: the sorted one outlives this call until staging
            # has cut its blocks
            del dev
            jax.block_until_ready((s, cuts))
        with timed("map.sort.d2h"):
            local = DeviceReadback.of(np.asarray(s), s, n)
            bounds = np.concatenate(
                [[0], np.asarray(cuts, dtype=np.int64), [n]]
            )
        return local, bounds

    def sort_columnar_partition(
        self, frame, edges: np.ndarray
    ) -> Tuple[np.ndarray, np.ndarray]:
        """:meth:`sort_partition` taken straight off a columnar block
        (DESIGN.md §25): column 0 of ``frame`` is the uint32 key column,
        decoded as an ``np.frombuffer`` view aliasing the landed bytes —
        the view goes to the device as it is when it fills its size
        class (else it feeds the pad copy), so consuming a fetched
        shuffle block on-device costs header validation plus the one
        HBM DMA. No pickle, no per-record tuples."""
        from sparkrdma_tpu.shuffle import columnar

        keys = columnar.decode_columns(frame)[0]
        if keys.dtype != np.uint32:
            raise TypeError(
                f"columnar key column is {keys.dtype}, expected uint32"
            )
        return self.sort_partition(keys, edges)


class TeraSorter:
    """Compile-once global sorter over a device mesh.

    ``sort_sharded`` maps [E, n_local] uint32 keys (sharded over the
    mesh) to [E, P*capacity] sorted rows plus per-shard valid counts;
    row i's valid prefix is globally the i-th key range.
    """

    def __init__(
        self,
        mesh: Optional[Mesh] = None,
        capacity_factor: float = 2.0,
    ):
        self.mesh = mesh if mesh is not None else make_mesh()
        self.num_shards = math.prod(self.mesh.shape.values())
        if self.num_shards & (self.num_shards - 1):
            raise ValueError("TeraSorter requires a power-of-two shard count")
        self.capacity_factor = capacity_factor
        self._step_cache = {}

    # ------------------------------------------------------------------
    def _build_step(self, n_local: int, capacity: int, adaptive: bool = False):
        e = self.num_shards
        axes = tuple(self.mesh.axis_names)
        spec = shard_spec(self.mesh)

        def shard_fn(keys, edges=None):  # keys: [n_local] uint32 shard
            if e == 1:
                # single-shard short circuit: no split, no exchange — the
                # reference's invariant #2 (local partitions never loop
                # through the network, RdmaShuffleFetcherIterator.scala:328-339).
                # device_sort == lax.sort, the measured optimum for this
                # chip (ops/sort.py module doc, DESIGN.md §6) — the same
                # delegation the reference makes to Spark's sort writers.
                merged = device_sort(keys)
                total = jnp.asarray([keys.shape[0]], jnp.int32)
                return merged, total, jnp.zeros((), jnp.int32)
            # local sort FIRST: destinations are key ranges, so sorted
            # keys are grouped by destination and the send slab falls out
            # of range-edge slices — measured ~25x cheaper than the
            # argsort/scatter pack at 32M keys (benchmarks/sort_study.py)
            local = device_sort(keys)
            if adaptive:
                # sampled quantile edges ride as DATA (replicated over
                # the mesh): the adaptive planner's cuts balance the
                # receive counts under skew, and a re-plan changes only
                # values — the executable is reused (ops/sort.py
                # split_sorted_edges, shuffle/planner.py plan_edges)
                slab, counts, overflowed = split_sorted_edges(
                    local, edges, capacity, fill=int(SENTINEL)
                )
            else:
                slab, counts, overflowed = split_sorted(
                    local, e, capacity, KEY_BITS, fill=int(SENTINEL)
                )
            # one all_to_all delivers every peer's bucket — the one-sided
            # READ plane collapsed into a single XLA collective
            recv = jax.lax.all_to_all(slab, axes, split_axis=0, concat_axis=0, tiled=True)
            rcounts = jax.lax.all_to_all(counts, axes, split_axis=0, concat_axis=0, tiled=True)
            merged, total = merge_received(recv, rcounts, int(SENTINEL))
            # any shard overflowing must abort the round everywhere
            overflowed = jax.lax.pmax(overflowed.astype(jnp.int32), axes)
            return merged, total[None], overflowed

        # the non-adaptive step keeps its historic single-argument
        # signature (bench.py / graft entry call step(n)(keys)); only
        # the adaptive variant threads the replicated edges array
        in_specs = (spec, P()) if adaptive else (spec,)
        fn = shard_map(
            shard_fn,
            mesh=self.mesh,
            in_specs=in_specs,
            out_specs=(spec, spec, P()),
            check_vma=False,
        )
        return jax.jit(fn)

    def step(
        self,
        n_local: int,
        capacity: Optional[int] = None,
        adaptive: bool = False,
    ):
        """The jitted SPMD sort step for [E*n_local] global keys."""
        if capacity is None:
            capacity = self.default_capacity(n_local)
        key = (n_local, capacity, adaptive)
        fn = self._step_cache.get(key)
        if fn is None:
            fn = self._build_step(n_local, capacity, adaptive)
            self._step_cache[key] = fn
        return fn

    def default_capacity(self, n_local: int) -> int:
        cap = int(math.ceil(n_local / self.num_shards) * self.capacity_factor)
        return max(8, cap)

    # ------------------------------------------------------------------
    def sort(
        self,
        keys: np.ndarray,
        adaptive: bool = False,
        sample_size: int = 4096,
    ) -> np.ndarray:
        """Host-facing total sort of uint32 keys (pads to shard multiple).

        Retries with doubled capacity on bucket overflow (skewed data),
        mirroring the pool's size-class re-rounding. With ``adaptive``
        the shard range edges come from a host-side key sample
        (shuffle/planner.py ``plan_edges``) instead of static top bits,
        and the capacity class is sized from the sampled shard shares —
        under zipf skew this replaces several overflow-retry executions
        at doubled capacity with ONE right-sized run."""
        n = len(keys)
        e = self.num_shards
        n_local = int(math.ceil(n / e))
        padded = np.full((e * n_local,), np.uint32(SENTINEL), dtype=np.uint32)
        padded[:n] = keys
        sharding = NamedSharding(self.mesh, shard_spec(self.mesh))
        dev = jax.device_put(padded, sharding)

        use_adaptive = adaptive and e > 1 and n > 0
        if use_adaptive:
            from sparkrdma_tpu.shuffle.planner import (
                capacity_from_sample,
                plan_edges,
            )

            sample = keys[:: max(1, n // max(1, sample_size))][:sample_size]
            edges_np = plan_edges(sample, e)
            # + e covers the injected SENTINEL padding (< e keys, all
            # routed to the last shard); clamp to n_local (a sender
            # holds no more)
            capacity = min(
                n_local, capacity_from_sample(sample, e, n_local,
                                              edges=edges_np) + e,
            )
        else:
            edges_np = np.zeros((max(0, e - 1),), dtype=np.uint32)
            capacity = self.default_capacity(n_local)
        edges = jnp.asarray(edges_np, jnp.uint32)

        for _ in range(8):
            fn = self.step(n_local, capacity, adaptive=use_adaptive)
            merged, totals, overflowed = (
                fn(dev, edges) if use_adaptive else fn(dev)
            )
            if not bool(overflowed):
                break
            # n_local is a hard ceiling: one sender holds n_local keys,
            # so no per-destination run can exceed it
            capacity = min(n_local, capacity * 2)
        else:
            raise RuntimeError("terasort bucket overflow after 8 capacity doublings")

        merged = np.asarray(merged).reshape(e, -1)
        totals = np.asarray(totals).reshape(-1)
        out = np.concatenate([merged[i, : totals[i]] for i in range(e)])
        # drop the padding sentinels we injected (they sort to the tail)
        return out[:n] if n < len(out) else out
