"""HBM→HBM one-sided block pull — the device fetch plane's data mover.

This is the truest analogue of the reference's IBV_WR_RDMA_READ
(RdmaChannel.java:360-393): the destination device *pulls* a source
device's HBM slab over the interconnect with no host CPU in the data
path. The movers:

- ``pallas_neighbor_pull``: a Pallas ``make_async_remote_copy`` kernel
  over ICI (SNIPPETS.md [1]-[3] pattern) — each device DMAs its
  left-neighbor's slab into local HBM, start/wait on explicit DMA
  semaphores, ``memory_space=ANY`` so the compiler keeps the refs in
  HBM. Compiled once per (mesh size, shape, dtype) and wrapped in
  ``shard_map`` exactly as the guide prescribes. TPU meshes only.
- ``emulated_pull``: ``jax.device_put`` of the source array onto the
  destination device — the same copy expressed through XLA's transfer
  engine. It is the per-block planner's mover on every platform.
- The wave movers, one entry point each: ``pallas_wave_pull`` and
  ``pallas_pipelined_wave_pull`` on one chip, ``pallas_mesh_wave_pull``
  on an n > 1 mesh. This module is the only place that knows the
  platform: on a TPU each runs its Pallas program, elsewhere a plain
  jnp program with the same contract, so the schedule compiler runs
  one wave path on every backend.

On one chip a wave is ``rows`` local DMAs. On an n > 1 mesh
(``pallas_mesh_wave_pull``) each row slot names the chip that holds its
source slab, the row of that chip's send shard, and a hop: only that
chip starts the slot's DMA, toward the chip ``hop`` steps right, and
only that chip waits on the receive. No chip sends a zero slot, and the
send shards hold only their own chip's rows. The receive buffer is an
operand donated back in, so a wave allocates no receive shards.

The planner (shuffle/device_fetch.py) decides per block whether a
mover applies; this module only moves bytes, and a mover failure
raises to the caller.
"""

from __future__ import annotations

import functools
import jax
import jax.numpy as jnp
from jax import shard_map
from jax.sharding import Mesh, PartitionSpec as P


def mesh_device_count() -> int:
    return jax.local_device_count()


def is_tpu_mesh() -> bool:
    return jax.devices()[0].platform == "tpu"


def emulated_pull(src_array, dst_device):
    """Pull ``src_array`` onto ``dst_device`` via the transfer engine.

    One DMA on TPU (HBM→HBM over ICI when src/dst share a slice); a
    plain buffer copy on the CPU backend. Blocks until the bytes are
    resident so the caller may adopt the result into its arena and
    immediately recycle/unpin the source."""
    if dst_device in src_array.devices():
        # src already lives on dst_device: device_put would be a no-op
        # (or an alias of the same buffer). The caller is about to
        # unpin the source arena slab — whose later spill DELETES that
        # buffer — so the pull must own an independent copy; force one
        # through host memory. This is the single-device/CPU-mesh case,
        # never the cross-chip ICI one.
        import numpy as np

        pulled = jax.device_put(np.asarray(src_array), dst_device)
    else:
        pulled = jax.device_put(src_array, dst_device)
    jax.block_until_ready(pulled)
    return pulled


@functools.lru_cache(maxsize=64)
def _neighbor_pull_program(axis_size: int, shape, dtype_str: str):
    """Jitted shard_map'd Pallas program: every device pulls its RIGHT
    neighbor's shard into its own output ref (a rotate-left collective
    built from one-sided remote DMA, SNIPPETS.md [3]).

    Cached per (mesh size, block shape, dtype) like the exchange
    program cache — stateful-verb-call reuse, not per-block compiles."""
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    dtype = jnp.dtype(dtype_str)

    def kernel(src_ref, dst_ref, send_sem, recv_sem):
        my_id = jax.lax.axis_index("x")
        left = jax.lax.rem(my_id + axis_size - 1, axis_size)
        # one-sided semantics: the copy is *initiated* toward the left
        # neighbor, so each device's dst_ref receives its right
        # neighbor's shard — the reduce task's "pull" once the mesh
        # rotation places source data one hop right
        op = pltpu.make_async_remote_copy(
            src_ref=src_ref,
            dst_ref=dst_ref,
            send_sem=send_sem,
            recv_sem=recv_sem,
            device_id=(left,),
            device_id_type=pltpu.DeviceIdType.MESH,
        )
        op.start()
        op.wait()

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=0,
        in_specs=[pl.BlockSpec(memory_space=pltpu.MemorySpace.ANY)],
        out_specs=pl.BlockSpec(memory_space=pltpu.MemorySpace.ANY),
        scratch_shapes=([pltpu.SemaphoreType.DMA] * 2),
    )

    pull = pl.pallas_call(
        kernel,
        out_shape=jax.ShapeDtypeStruct(shape, dtype),
        grid_spec=grid_spec,
        name="pallas_neighbor_pull",
    )

    mesh = Mesh(jax.devices()[:axis_size], ("x",))
    f = shard_map(
        pull, mesh=mesh, in_specs=P("x"), out_specs=P("x"), check_vma=False
    )
    return jax.jit(f)


def pallas_neighbor_pull(sharded_blocks):
    """Run the ICI neighbor pull over a [axis_size, ...] sharded array.

    Returns the rotated array (row i now holds row (i+1) % n's bytes).
    Raises on non-TPU platforms — callers planner-gate on
    ``is_tpu_mesh()`` and use ``emulated_pull`` otherwise."""
    if not is_tpu_mesh():
        raise RuntimeError("pallas_neighbor_pull requires a TPU mesh")
    n = sharded_blocks.shape[0]
    per_dev = (sharded_blocks.shape[0] // n,) + tuple(sharded_blocks.shape[1:])
    prog = _neighbor_pull_program(
        n, per_dev, str(sharded_blocks.dtype)
    )
    return prog(sharded_blocks)


# The wave programs carry each row's bucket as [bucket // 128, 128]:
# the HBM tiling then covers only a row's own two minor dims, so slicing
# one row off the leading axis stays tile-aligned (a 2-D [rows, bucket]
# ref tiles rows in groups of 8 and Mosaic refuses a 1-row slice).
# Callers shape the stack on the host, where the reshape is free; done
# inside the program it costs a relayout pass. Buckets are >= 1 KiB,
# so every class divides into lanes.
_LANES = 128


def wave_row_shape(bucket_elems: int):
    """Device shape of one wave row of ``bucket_elems`` elements."""
    return (bucket_elems // _LANES, _LANES)


def _local_copy(src, dst, sem):
    """Start/wait pair of one row's local DMA: a one-chip wave."""
    from jax.experimental.pallas import tpu as pltpu

    copy = pltpu.make_async_copy(src, dst, sem)
    return copy.start, copy.wait


@functools.lru_cache(maxsize=64)
def _wave_pull_program(rows: int, bucket_elems: int, dtype_str: str):
    """Jitted shard_map'd Pallas program moving a whole fetch WAVE on
    one chip in one kernel epoch: ``rows`` local DMAs started together,
    waited together. The hop lane rides in scalar prefetch, all zeros
    on one chip, so one executable serves every wave of the same
    (rows, bucket) class.

    Cached per (bucketed rows, bucket elems, dtype) — the
    shuffle-schedule compiler buckets both axes so ragged stages reuse
    these executables (DESIGN.md §22)."""
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    dtype = jnp.dtype(dtype_str)

    def kernel(hops, src_ref, dst_ref, send_sem, recv_sem):
        def copy(i):
            return _local_copy(src_ref.at[i], dst_ref.at[i], recv_sem.at[i])

        # every DMA in flight before the first wait: the epoch's wall
        # is max(row latency), not sum — the whole point of the wave
        jax.lax.fori_loop(0, rows, lambda i, c: (copy(i)[0](), c)[1], 0)
        jax.lax.fori_loop(0, rows, lambda i, c: (copy(i)[1](), c)[1], 0)

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=1,
        in_specs=[pl.BlockSpec(memory_space=pltpu.MemorySpace.ANY)],
        out_specs=pl.BlockSpec(memory_space=pltpu.MemorySpace.ANY),
        scratch_shapes=(
            [pltpu.SemaphoreType.DMA((rows,))] * 2
        ),
    )

    pull = pl.pallas_call(
        kernel,
        out_shape=jax.ShapeDtypeStruct(
            (rows, *wave_row_shape(bucket_elems)), dtype
        ),
        grid_spec=grid_spec,
        name="pallas_wave_pull",
    )

    mesh = Mesh(jax.devices()[:1], ("x",))
    f = shard_map(
        pull, mesh=mesh, in_specs=(P(), P("x")), out_specs=P("x"),
        check_vma=False,
    )
    return jax.jit(f)


def pallas_wave_pull(hops, stacked):
    """Run one wave's batched pull on a one-chip mesh over a
    [rows, *wave_row_shape(b)] stack; ``hops`` is the int32 per-row
    lane, all zeros. Returns a fresh array holding the stack: the Pallas
    program on a TPU, ``_stack_copy`` elsewhere."""
    if not is_tpu_mesh():
        return _stack_copy(stacked)
    return _wave_kernel(hops, stacked)


def _wave_kernel(hops, stacked):
    rows = stacked.shape[0]
    bucket = stacked.shape[1] * stacked.shape[2]
    prog = _wave_pull_program(rows, bucket, str(stacked.dtype))
    return prog(hops, stacked)


@functools.lru_cache(maxsize=64)
def _pipelined_wave_pull_program(depth: int, rows: int, bucket_elems: int,
                                 dtype_str: str):
    """Depth-aware double-buffered wave program: ``depth`` waves of
    ``rows`` DMAs each, with wave d+1's DMAs STARTED before wave d's
    wait loop runs — so the interconnect always has a wave in flight
    while the previous one drains. One DMA-semaphore array per
    in-flight wave (send and recv), exactly the per-lane scratch shape
    of ``_wave_pull_program`` replicated per pipeline slot, so wave d's
    waits never consume wave d+1's completions.

    The caller groups consecutive same-(rows, bucket) waves up to the
    ``collective.pipelineDepth`` knob; ragged neighbors run the
    single-wave program. One chip; cached per (depth, rows class,
    bucket class, dtype) like every other wave executable."""
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    dtype = jnp.dtype(dtype_str)

    def kernel(hops, src_ref, dst_ref, *sems):
        send_sems, recv_sems = sems[:depth], sems[depth:]

        def copy(d, i):
            return _local_copy(
                src_ref.at[d, i], dst_ref.at[d, i], recv_sems[d].at[i]
            )

        def start_wave(d):
            jax.lax.fori_loop(
                0, rows, lambda i, c: (copy(d, i)[0](), c)[1], 0
            )

        def wait_wave(d):
            jax.lax.fori_loop(
                0, rows, lambda i, c: (copy(d, i)[1](), c)[1], 0
            )

        # the pipeline: wave d+1 is airborne before wave d drains, so
        # the drain epoch of every wave but the last overlaps a wave's
        # worth of in-flight DMA (depth is a Python constant — this
        # unrolls at trace time)
        start_wave(0)
        for d in range(1, depth):
            start_wave(d)
            wait_wave(d - 1)
        wait_wave(depth - 1)

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=1,
        in_specs=[pl.BlockSpec(memory_space=pltpu.MemorySpace.ANY)],
        out_specs=pl.BlockSpec(memory_space=pltpu.MemorySpace.ANY),
        scratch_shapes=(
            [pltpu.SemaphoreType.DMA((rows,))] * (2 * depth)
        ),
    )

    pull = pl.pallas_call(
        kernel,
        out_shape=jax.ShapeDtypeStruct(
            (depth, rows, *wave_row_shape(bucket_elems)), dtype
        ),
        grid_spec=grid_spec,
        name="pallas_pipelined_wave_pull",
    )

    mesh = Mesh(jax.devices()[:1], ("x",))
    f = shard_map(
        pull, mesh=mesh, in_specs=(P(), P("x")), out_specs=P("x"),
        check_vma=False,
    )
    return jax.jit(f)


def pallas_pipelined_wave_pull(hops, stacked, depth: int):
    """Run ``depth`` same-class waves as one double-buffered kernel
    epoch on a one-chip mesh over a [depth, rows, *wave_row_shape(b)]
    stack; ``hops`` is the [depth, rows] int32 hop lane, all zeros.
    Returns a fresh array holding the stack: the Pallas program on a
    TPU, ``_stack_copy`` elsewhere."""
    if not is_tpu_mesh():
        return _stack_copy(stacked)
    return _pipelined_wave_kernel(hops, stacked, depth)


def _pipelined_wave_kernel(hops, stacked, depth: int):
    rows = stacked.shape[1]
    bucket = stacked.shape[2] * stacked.shape[3]
    prog = _pipelined_wave_pull_program(
        depth, rows, bucket, str(stacked.dtype)
    )
    return prog(hops, stacked)


@functools.lru_cache(maxsize=64)
def _mesh_wave_pull_program(axis_size: int, depth: int, send_rows: int,
                            rows: int, bucket_elems: int, dtype_str: str):
    """Jitted shard_map'd Pallas program moving ``depth`` same-class
    waves of ``rows`` slots across an ``axis_size``-chip mesh in one
    kernel epoch, wave d+1's DMAs started before wave d's waits, as in
    ``_pipelined_wave_pull_program``.

    The scalar-prefetch lane holds three runs of ``depth * rows``
    int32s: slot j's source chip (-1: the slot carries no row), its row
    in that chip's ``send_rows``-row send shard, and its hop. Only the
    source chip starts slot j's DMA, local at hop 0 and remote toward
    ``(source + hop) % n`` otherwise; only the receiving chip waits on
    the receive, so no chip moves a slot it does not hold. A barrier on
    entry keeps a chip that left the previous epoch early from landing
    a DMA on a chip still inside it. The receive buffer, ``depth *
    rows`` slots on every chip, comes in as an operand aliased to the
    output and donated: slots that receive nothing keep what they held.

    Cached per (mesh size, depth, send rows class, rows class, bucket
    class, dtype): no block length, offset or source chip is part of
    the key."""
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    dtype = jnp.dtype(dtype_str)
    slots = depth * rows

    def kernel(lane, src_ref, _recv_in, dst_ref, send_sems, recv_sems):
        me = jax.lax.axis_index("x")
        barrier = pltpu.get_barrier_semaphore()
        for k in range(1, axis_size):
            pltpu.semaphore_signal(
                barrier, 1, device_id=(jax.lax.rem(me + k, axis_size),),
                device_id_type=pltpu.DeviceIdType.MESH,
            )
        pltpu.semaphore_wait(barrier, axis_size - 1)

        def dma(j):
            source, hop = lane[j], lane[2 * slots + j]
            target = jax.lax.rem(source + hop, axis_size)
            src = src_ref.at[lane[slots + j]]
            local = pltpu.make_async_copy(src, dst_ref.at[j], recv_sems.at[j])
            remote = pltpu.make_async_remote_copy(
                src_ref=src,
                dst_ref=dst_ref.at[j],
                send_sem=send_sems.at[j],
                recv_sem=recv_sems.at[j],
                device_id=(target,),
                device_id_type=pltpu.DeviceIdType.MESH,
            )
            sends, away = source == me, hop != 0
            receives = (source >= 0) & (target == me) & away
            return sends & ~away, sends & away, receives, local, remote

        def start(j):
            here, away, _, local, remote = dma(j)
            pl.when(here)(local.start)
            pl.when(away)(remote.start)

        def wait(j):
            here, away, receives, local, remote = dma(j)
            pl.when(here)(local.wait)
            pl.when(away)(remote.wait_send)
            pl.when(receives)(remote.wait_recv)

        def each_row(fn, d):
            jax.lax.fori_loop(d * rows, (d + 1) * rows,
                              lambda j, c: (fn(j), c)[1], 0)

        each_row(start, 0)
        for d in range(1, depth):
            each_row(start, d)
            each_row(wait, d - 1)
        each_row(wait, depth - 1)

    any_space = pl.BlockSpec(memory_space=pltpu.MemorySpace.ANY)
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=1,
        in_specs=[any_space, any_space],
        out_specs=any_space,
        scratch_shapes=[pltpu.SemaphoreType.DMA((slots,))] * 2,
    )
    pull = pl.pallas_call(
        kernel,
        out_shape=jax.ShapeDtypeStruct(
            (slots, *wave_row_shape(bucket_elems)), dtype
        ),
        grid_spec=grid_spec,
        input_output_aliases={2: 0},
        compiler_params=pltpu.CompilerParams(collective_id=0),
        name="pallas_wave_pull" if depth == 1
        else "pallas_pipelined_wave_pull",
    )

    mesh = Mesh(jax.devices()[:axis_size], ("x",))
    f = shard_map(
        pull, mesh=mesh, in_specs=(P(), P("x"), P("x")), out_specs=P("x"),
        check_vma=False,
    )
    return jax.jit(f, donate_argnums=2)


def pallas_mesh_wave_pull(lane, send, recv, depth: int):
    """Run ``depth`` same-class waves as one kernel epoch on an n > 1
    mesh. ``send`` is [n * send_rows, *wave_row_shape(b)] and ``recv``
    is [n * depth * rows, *wave_row_shape(b)], both sharded
    row-block-wise over the mesh; ``recv`` is donated and comes back as
    the result, slot ``d * rows + i`` holding wave d's row i on its
    receiving chip. The Pallas program (``_mesh_wave_pull_program``) on
    a TPU, ``_mesh_stand_in_program`` elsewhere."""
    if not is_tpu_mesh():
        prog = _mesh_stand_in_program(mesh_device_count(), recv.sharding)
        return prog(lane, send, recv)
    return _mesh_wave_kernel(lane, send, recv, depth)


def _mesh_wave_kernel(lane, send, recv, depth: int):
    n = mesh_device_count()
    bucket = send.shape[1] * send.shape[2]
    prog = _mesh_wave_pull_program(
        n, depth, send.shape[0] // n, recv.shape[0] // (n * depth), bucket,
        str(send.dtype),
    )
    return prog(lane, send, recv)


# Off a TPU the wave movers keep the kernels' contract in plain jnp: the
# one-chip movers land a fresh array holding the stack, and the mesh
# mover writes each slot's row into its receiving chip's slot of the
# donated receive buffer, leaving every other slot as it was.
_stack_copy = jax.jit(jnp.copy)


@functools.lru_cache(maxsize=64)
def _mesh_stand_in_program(axis_size: int, sharding):
    def mesh_wave_stand_in(lane, send, recv):
        source, row, hop = lane.reshape(3, -1)
        slots = source.shape[0]
        lanes = send.shape[1:]
        sent = send.reshape(axis_size, -1, *lanes)[jnp.maximum(source, 0),
                                                   row]
        land = recv.reshape(axis_size, slots, *lanes)
        target, slot = (source + hop) % axis_size, jnp.arange(slots)
        keep = land[target, slot]
        rows = jnp.where((source >= 0)[:, None, None], sent, keep)
        return land.at[target, slot].set(rows).reshape(recv.shape)

    return jax.jit(mesh_wave_stand_in, donate_argnums=2,
                   out_shardings=sharding)
