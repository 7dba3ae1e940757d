"""The resident exchange program — device-side all-to-all block transfer.

TPU-native analogue of the reference's one-sided-READ data plane
(IBV_WR_RDMA_READ WR lists, RdmaChannel.java:360-393). The verbs
semantics are asynchronous, peer-passive, arbitrary-offset pulls;
XLA collectives are synchronous SPMD with static shapes. Following
SURVEY.md §7.3(1-2), the gap is bridged with:

- **bucketed static shapes**: every peer-to-peer block rides in a
  fixed-size bucket of ``block_bytes``; actual lengths travel alongside
  as an int32 "length prefix" lane (the rkey/length analogue). Buckets
  round to the conf's ``exchange.bucketMin``..``bucketMax`` power-of-two
  classes, exactly like the registered-buffer pool's size classes
  (RdmaBufferManager.java:103-118).
- **compile-once, execute-many**: one jitted SPMD program per
  (mesh, num rows, bucket) — the reference's stateful-verb-call
  pattern (pre-serialized WR lists executed repeatedly,
  RdmaChannel.java:185-192) becomes an XLA executable cache.
- **ICI before DCN**: on a multi-slice ``(dcn, exec)`` mesh the
  all-to-all runs over the flattened (dcn, exec) axes so XLA routes
  intra-slice traffic on ICI and only cross-slice rows on DCN.

Two transfer schedules are provided:

- ``exchange``: single ``lax.all_to_all`` — XLA's native schedule,
  best for dense all-to-all (the TeraSort repartition).
- ``ring_exchange``: E-1 ``lax.ppermute`` steps moving one peer-block
  per step around the ring — the staged, flow-controlled schedule
  (analogue of ``maxBytesInFlight`` throttled fetches,
  RdmaShuffleFetcherIterator.scala:279-284), and the building block
  shared with ring-attention-style long-sequence exchange.
"""

from __future__ import annotations

import math
import time
from typing import List, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import Mesh, NamedSharding

from sparkrdma_tpu.obs import get_registry
from sparkrdma_tpu.parallel.mesh import shard_spec
from jax import shard_map

MIN_BUCKET = 1024


def round_bucket(nbytes: int, lo: int = MIN_BUCKET, hi: int = 1 << 31) -> int:
    """Round a block size up to its power-of-two bucket class.

    Mirror of the registered-buffer pool's size classing
    (RdmaBufferManager.java:103-118: power-of-two rounding, 16 KiB min —
    buckets here may be smaller because device lanes are cheap).
    """
    n = max(lo, min(hi, nbytes))
    return 1 << max(n - 1, 1).bit_length() if n > lo else lo


def round_rows(rows: int, lo: int = 1) -> int:
    """Round a row count up to its power-of-two bucket class — the
    leading-axis twin of :func:`round_bucket`. Ragged stage sizes
    (distinct per-peer row counts, distinct wave populations) pad up to
    the class and reuse one cached executable instead of recompiling
    per distinct count; pad rows travel with a zero length prefix and
    are sliced off after the exchange."""
    n = max(lo, rows)
    return 1 << max(n - 1, 1).bit_length() if n > lo else lo


def pack_blocks(
    blocks: Sequence[bytes], block_bytes: int
) -> Tuple[np.ndarray, np.ndarray]:
    """Host-side: pack one peer-block per row into a [E, block_bytes] send
    buffer plus its length-prefix vector. Blocks longer than the bucket
    are a caller bug (callers split at ``shuffleReadBlockSize`` first,
    like AggregatedPartitionGroup packing)."""
    e = len(blocks)
    out = np.zeros((e, block_bytes), dtype=np.uint8)
    counts = np.zeros((e,), dtype=np.int32)
    for i, b in enumerate(blocks):
        if len(b) > block_bytes:
            raise ValueError(f"block {i} ({len(b)}B) exceeds bucket {block_bytes}B")
        out[i, : len(b)] = np.frombuffer(b, dtype=np.uint8)
        counts[i] = len(b)
    return out, counts


def unpack_blocks(recv: np.ndarray, counts: np.ndarray) -> List[bytes]:
    """Host-side inverse of pack_blocks on the received side."""
    return [recv[i, : int(counts[i])].tobytes() for i in range(recv.shape[0])]


class ExchangeProgram:
    """Compile-once all-to-all exchange over a mesh.

    Global layout: ``send`` is [E*rows, block] sharded on dim 0 over all
    mesh axes; each device's local [rows, block] slab holds one
    outgoing block per peer-row (rows == E for a plain all-to-all;
    multiples of E for multi-block rounds). ``counts`` is the int32
    length-prefix array of the same leading shape.

    After the exchange, device *i*'s local row *j* holds what device
    *j* staged for device *i* — the device analogue of "reduce task
    pulls its partition from every map output" (SURVEY.md §3.4).
    """

    def __init__(self, mesh: Mesh):
        self.mesh = mesh
        # Collective axis order MUST match the sharding's global shard
        # order (dcn-major, exec-minor) so that send-row j lands on the
        # device holding global shard j. XLA still routes the intra-slice
        # component over ICI; the order here is index math, not routing.
        self.axes = tuple(mesh.axis_names)
        self.num_shards = math.prod(mesh.shape[a] for a in self.axes)
        self._all_to_all_cache = {}
        self._ring_cache = {}
        # transfer accounting (reference: pool/read stats at stop,
        # RdmaBufferManager.java:131-141, RdmaShuffleReaderStats).
        # Aggregates for back-compat; per-schedule detail in
        # ``self.stats`` counts BOTH directions plus wall time per
        # step, so schedule comparisons (a2a vs ring) can cite real
        # transfer counters, not send-side capacity alone.
        self.exchanges = 0
        self.bytes_moved = 0
        self.stats = {
            label: {
                "exchanges": 0,
                "bytes_sent": 0,            # bucket capacity dispatched
                "bytes_received": 0,        # bucket capacity landed
                "bytes_received_valid": 0,  # sum of recv length prefixes
                "time_s": 0.0,              # wall incl. device sync
            }
            for label in ("a2a", "ring")
        }

    def _account(self, label: str, send, recv, rcounts, t0: float):
        """Block on the step's outputs and record both directions.

        Blocking is what makes the wall time a *step* time (dispatch
        alone is meaningless through an async runtime); callers of the
        host-level entry points consume the results immediately, so
        the sync costs them nothing extra. The valid-byte count reads
        the int32 length-prefix lane only (tiny), never the payload.

        On multi-host meshes ALL byte counters are per-process: capacity
        comes from this process's addressable shards, not the global
        array size — ``send.size`` spans every host, and charging the
        whole global slab to each process would over-report aggregate
        traffic by ``num_processes ×``."""

        def _cap_bytes(arr) -> int:
            itemsize = jnp.dtype(arr.dtype).itemsize
            if getattr(arr, "is_fully_addressable", True):
                return arr.size * itemsize
            return sum(s.data.size for s in arr.addressable_shards) * itemsize

        recv = jax.block_until_ready(recv)
        rcounts = jax.block_until_ready(rcounts)
        dt = time.perf_counter() - t0
        cap = _cap_bytes(send)
        if getattr(rcounts, "is_fully_addressable", True):
            valid = int(np.asarray(rcounts).sum())
        else:  # multi-host: only this process's shards are readable
            valid = int(
                sum(np.asarray(s.data).sum() for s in rcounts.addressable_shards)
            )
        recv_cap = _cap_bytes(recv)
        s = self.stats[label]
        s["exchanges"] += 1
        s["bytes_sent"] += cap
        # measured from the landed array, independently of the send side
        s["bytes_received"] += recv_cap
        s["bytes_received_valid"] += valid
        s["time_s"] += dt
        self.exchanges += 1
        self.bytes_moved += cap
        reg = get_registry()
        reg.counter("exchange.exchanges", schedule=label).inc()
        reg.counter("exchange.bytes_sent", schedule=label).inc(cap)
        reg.counter("exchange.bytes_received", schedule=label).inc(recv_cap)
        reg.counter("exchange.bytes_received_valid", schedule=label).inc(valid)
        reg.histogram("exchange.time_ms", schedule=label).observe(dt * 1e3)
        return recv, rcounts

    def _placed(self, send, counts):
        """Lay host arrays out over the mesh; pass device arrays through.

        A non-fully-addressable ``jax.Array`` is the multi-host path:
        no single process can materialize (or device_put) the full
        global slab, so the caller builds it from process-local shards
        (``jax.make_array_from_process_local_data``) and this must not
        touch it. Fully-addressable arrays still go through device_put
        so a committed single-device array (any prior jit's output)
        gets re-placed onto the mesh instead of crashing the shard_map
        with an incompatible-devices error."""
        sharding = NamedSharding(self.mesh, shard_spec(self.mesh))
        if not (isinstance(send, jax.Array) and not send.is_fully_addressable):
            send = jax.device_put(send, sharding)
        if not (isinstance(counts, jax.Array) and not counts.is_fully_addressable):
            counts = jax.device_put(counts, sharding)
        return send, counts

    # -- schedule 1: XLA-native dense all-to-all ---------------------------
    def _build_all_to_all(self, rows: int, block: int, dtype) -> "jax.stages.Wrapped":
        axes = self.axes
        spec = shard_spec(self.mesh)
        cspec = spec

        def shard_fn(send, counts):
            # send: [rows, block]; row j is the block bound for peer j.
            # tiled all_to_all: row j goes to device j, received rows
            # concatenate in peer order — one-sided semantics, no peer code.
            recv = jax.lax.all_to_all(
                send, axes, split_axis=0, concat_axis=0, tiled=True
            )
            rcounts = jax.lax.all_to_all(
                counts, axes, split_axis=0, concat_axis=0, tiled=True
            )
            return recv, rcounts

        fn = shard_map(
            shard_fn,
            mesh=self.mesh,
            in_specs=(spec, cspec),
            out_specs=(spec, cspec),
            check_vma=False,
        )
        return jax.jit(fn)

    def program_for(self, rows: int, block: int, dtype) -> "jax.stages.Wrapped":
        """The cached compile-once executable for a shape class — the
        SVC handle (pre-serialized WR list) callers may embed inside
        larger jitted programs (TeraSort steps, benches)."""
        key = ("a2a", rows, (block,), jnp.dtype(dtype).name)
        fn = self._all_to_all_cache.get(key)
        if fn is None:
            fn = self._build_all_to_all(rows, block, dtype)
            self._all_to_all_cache[key] = fn
        return fn

    def exchange(self, send, counts):
        """Dense exchange; returns (recv, recv_counts) with identical shapes.

        ``send``: [E*rows_per_shard, block] (any dtype), sharded or
        shardable over the mesh; ``counts``: [E*rows_per_shard] int32.

        Rows-per-peer are bucketed to power-of-two classes
        (:func:`round_rows`) the same way block bytes are: a ragged
        stage whose shards stage 3 then 5 then 4 blocks per peer
        compiles TWO executables (classes 4 and 8), not three — pad
        rows ride with a zero length prefix and are sliced off before
        returning, so results are byte-identical to the exact-shape
        program. Bucketing applies only to fully-addressable inputs
        whose rows divide evenly by E; the multi-host path (caller
        builds non-addressable global arrays from process-local
        shards) keeps exact shapes — padding there would need a
        cross-process layout agreement this entry point cannot make.
        """
        e = self.num_shards
        rows = send.shape[0] // e
        addressable = not (
            isinstance(send, jax.Array) and not send.is_fully_addressable
        )
        rpp = rows // e if (addressable and rows % e == 0 and rows > 0) else 0
        pad = 0
        if rpp > 0:
            rb = round_rows(rpp)
            pad = rb - rpp
            if pad:
                block = send.shape[1]
                s = np.asarray(send).reshape(e, e, rpp, block)
                c = np.asarray(counts).reshape(e, e, rpp)
                s = np.pad(s, ((0, 0), (0, 0), (0, pad), (0, 0)))
                c = np.pad(c, ((0, 0), (0, 0), (0, pad)))
                send = s.reshape(e * e * rb, block)
                counts = c.reshape(-1)
                rows = e * rb
        fn = self.program_for(rows, send.shape[1], send.dtype)
        send, counts = self._placed(send, counts)
        t0 = time.perf_counter()
        recv, rcounts = fn(send, counts)
        recv, rcounts = self._account("a2a", send, recv, rcounts, t0)
        if pad:
            # receivers see each peer's chunk padded at its tail; strip
            # the pad rows so callers get the exact-shape result back
            rb = rpp + pad
            block = recv.shape[1]
            r = np.asarray(recv).reshape(e, e, rb, block)[:, :, :rpp]
            rc = np.asarray(rcounts).reshape(e, e, rb)[:, :, :rpp]
            recv = r.reshape(e * e * rpp, block)
            rcounts = rc.reshape(-1)
        return recv, rcounts

    # -- schedule 2: staged ring (ppermute) --------------------------------
    def _build_ring(self, block: int, dtype) -> "jax.stages.Wrapped":
        if len(self.axes) != 1:
            raise NotImplementedError("ring schedule requires a 1-D mesh")
        axis = self.axes[0]
        e = self.num_shards
        spec = shard_spec(self.mesh)

        def shard_fn(send, counts):
            # send: [E, block]; deliver row j to device j by rotating the
            # slab around the ring, peeling off the arriving row each hop
            # — only neighbour links are ever used (the topology ring
            # attention shares), and each device has a bounded amount in
            # flight per step (the maxBytesInFlight-style staging).
            me = jax.lax.axis_index(axis)
            recv0 = send[me]  # my own row short-circuits locally
            rcount0 = counts[me]
            perm_fwd = [(i, (i + 1) % e) for i in range(e)]

            slab = send
            ccnt = counts
            outs = []
            couts = []
            for k in range(1, e):
                slab = jax.lax.ppermute(slab, axis, perm_fwd)
                ccnt = jax.lax.ppermute(ccnt, axis, perm_fwd)
                # after k hops the slab on me originated at device me-k;
                # its row `me` is the block that device staged for me.
                outs.append(slab[me])
                couts.append(ccnt[me])

            # reassemble receive slab in peer order: row j came from peer j
            # = me - k mod e at hop k. Scatter hop results to peer rows.
            recv = jnp.zeros_like(send)
            rcounts = jnp.zeros_like(counts)
            recv = recv.at[me].set(recv0)
            rcounts = rcounts.at[me].set(rcount0)
            for k in range(1, e):
                src = (me - k) % e
                recv = recv.at[src].set(outs[k - 1])
                rcounts = rcounts.at[src].set(couts[k - 1])
            return recv, rcounts

        fn = shard_map(
            shard_fn,
            mesh=self.mesh,
            in_specs=(spec, spec),
            out_specs=(spec, spec),
            check_vma=False,
        )
        return jax.jit(fn)

    def ring_exchange(self, send, counts):
        """Staged exchange: E-1 ppermute hops, one bucket in flight each.

        Semantically identical to ``exchange``; schedule differs (ring
        neighbours only — the pattern ring attention shares)."""
        key = ("ring", send.shape[1:], jnp.dtype(send.dtype).name)
        fn = self._ring_cache.get(key)
        if fn is None:
            fn = self._build_ring(send.shape[1], send.dtype)
            self._ring_cache[key] = fn
        send, counts = self._placed(send, counts)
        t0 = time.perf_counter()
        recv, rcounts = fn(send, counts)
        return self._account("ring", send, recv, rcounts, t0)
