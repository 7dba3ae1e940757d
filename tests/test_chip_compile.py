"""Compile the main path's device programs for a described TPU v5e.

No chip is attached here: JAX's TPU compiler compiles for a v5e:2x2
topology that is described, not present (on-chip-measurement guide,
section 2). That catches what interpret mode cannot — tiling-misaligned
ref slices, programs that do not fit HBM, kernels that cannot be
partitioned — at no chip time. Nothing runs, so nothing here is a time
or a result.

The topology is described inside a module-scoped fixture, never at
import: only one process at a time may load the TPU library, and every
xdist worker imports this file. The program builders take their mesh
from ``jax.devices()``; the tests hand them the described devices by
patching that call around the build.
"""

import re

import numpy as np
import pytest

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

V5E_HBM = 16 * 1024**3


@pytest.fixture(scope="module")
def topo():
    import os

    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache

    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    # a described-topology compile cannot be read back without a chip:
    # keep the persistent cache out of it
    prev = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    try:
        yield topologies.get_topology_desc(
            platform="tpu", topology_name="v5e:2x2"
        )
    finally:
        jax.config.update("jax_enable_compilation_cache", prev)


@pytest.fixture()
def described_devices(topo, monkeypatch):
    """Program builders see the described chips as ``jax.devices()``."""
    monkeypatch.setattr(jax, "devices", lambda *a, **k: list(topo.devices))
    return list(topo.devices)


def _sds(shape, dtype, sharding):
    return jax.ShapeDtypeStruct(shape, dtype, sharding=sharding)


@pytest.mark.parametrize(
    "n, depth, rows, bucket_elems",
    [
        (4, 1, 4, 1 << 22),   # --chips 4 wave: 4 rows of 16 MiB
        (4, 2, 4, 1 << 22),   # ... pipelined at depth 2
        (1, 1, 1, 1 << 25),   # one-chip e2e: a 128 MiB row, local DMA
        (1, 2, 1, 1 << 25),
        (4, 1, 8, 256),       # smallest bucket class (1 KiB of uint32)
        (4, 1, 4, 1 << 20),   # 64-reducer class-D wave: 4 rows of 4 MiB
    ],
)
def test_wave_programs_compile(described_devices, n, depth, rows,
                               bucket_elems):
    """The wave movers for the chip. On a 4-chip mesh each chip's send
    shard holds its own rows (``rows // n`` here) and the receive
    buffer is donated into the output: the program allocates no
    receive shard of its own."""
    from sparkrdma_tpu.ops import remote_copy

    mesh = Mesh(np.array(described_devices[:n]), ("x",))
    rep, sh = NamedSharding(mesh, P()), NamedSharding(mesh, P("x"))
    lanes = remote_copy.wave_row_shape(bucket_elems)
    if n > 1:
        send_rows = rows // n
        prog = remote_copy._mesh_wave_pull_program.__wrapped__(
            n, depth, send_rows, rows, bucket_elems, "uint32"
        )
        slots = depth * rows
        args = (_sds((3 * slots,), jnp.int32, rep),
                _sds((n * send_rows, *lanes), jnp.uint32, sh),
                _sds((n * slots, *lanes), jnp.uint32, sh))
    elif depth == 1:
        prog = remote_copy._wave_pull_program.__wrapped__(
            rows, bucket_elems, "uint32"
        )
        args = (_sds((rows,), jnp.int32, rep),
                _sds((n * rows, *lanes), jnp.uint32, sh))
    else:
        prog = remote_copy._pipelined_wave_pull_program.__wrapped__(
            depth, rows, bucket_elems, "uint32"
        )
        args = (_sds((depth, rows), jnp.int32, rep),
                _sds((n * depth, rows, *lanes), jnp.uint32, sh))
    compiled = prog.lower(*args).compile()
    hlo = compiled.as_text()
    assert "tpu_custom_call" in hlo
    # rows arrive already lane-shaped: no relayout pass around the DMAs
    assert not re.search(r"\sfusion\(", hlo)
    mem = compiled.memory_analysis()
    assert mem.argument_size_in_bytes + mem.output_size_in_bytes < V5E_HBM
    if n > 1:
        recv_bytes = depth * rows * bucket_elems * 4
        assert mem.output_size_in_bytes == recv_bytes
        assert mem.alias_size_in_bytes == recv_bytes


@pytest.mark.parametrize(
    "class_elems, stack_shape",
    [
        (1 << 23, (2, 1 << 17, 128)),  # 8-reducer wave: a 32 MiB slab's row
        (1 << 24, (2, 1 << 17, 128)),  # and a 64 MiB slab's
        (1 << 19, (2, 1 << 12, 128)),  # 200-reducer wave: 2 MiB slabs
        (1 << 22, (2, 4, 1 << 15, 128)),  # pipelined entry
    ],
)
def test_send_gather_compiles(topo, class_elems, stack_shape):
    """One row of a wave's send stack, gathered on one chip from its
    source slab: written in place into the donated stack (no scratch
    the size of a row), and no op carries the movers' ``wave_pull``
    name."""
    from jax.sharding import SingleDeviceSharding

    from sparkrdma_tpu.shuffle.collective import _send_gather_program

    one = SingleDeviceSharding(topo.devices[0])
    prog = _send_gather_program.__wrapped__(stack_shape, "uint32")
    compiled = prog.lower(
        _sds(stack_shape, jnp.uint32, one),
        _sds((class_elems,), jnp.uint32, one),
        _sds((3,), jnp.int32, one),
    ).compile()
    assert "wave_pull" not in compiled.as_text()
    mem = compiled.memory_analysis()
    stack_bytes = int(np.prod(stack_shape)) * 4
    assert mem.output_size_in_bytes == stack_bytes
    assert mem.alias_size_in_bytes == stack_bytes
    assert mem.temp_size_in_bytes < 4 << 20


@pytest.mark.parametrize(
    "landed, bucket_elems, take_elems",
    [
        ((2, 1 << 17, 128), 1 << 24, 1 << 23),  # a 32 MiB slab from a 64 MiB row
        ((2, 1, 1 << 17, 128), 1 << 24, 1 << 24),  # pipelined, class = bucket
        ((2, 1 << 12, 128), 1 << 19, 1 << 19),  # 200-reducer wave row
    ],
)
def test_row_take_compiles(topo, landed, bucket_elems, take_elems):
    """Adoption of a landed row: one copy of the slab's class out of
    the landed shard, with no scratch the size of the shard."""
    from jax.sharding import SingleDeviceSharding

    from sparkrdma_tpu.shuffle.collective import _row_take_program

    one = SingleDeviceSharding(topo.devices[0])
    slots = int(np.prod(landed)) // bucket_elems
    prog = _row_take_program.__wrapped__(
        slots, bucket_elems, take_elems, "uint32"
    )
    compiled = prog.lower(
        _sds(landed, jnp.uint32, one), _sds((), jnp.int32, one)
    ).compile()
    mem = compiled.memory_analysis()
    assert mem.output_size_in_bytes == take_elems * 4
    assert mem.temp_size_in_bytes < 4 << 20


@pytest.mark.parametrize(
    "class_elems",
    [
        1 << 23,  # 8-reducer map blocks: 32 MiB slabs
        1 << 24,  # and 64 MiB, the last one's class past the source's end
        1 << 19,  # 200-reducer map blocks: 2 MiB slabs
        1 << 20,  # 64-reducer class-D map blocks: 4 MiB slabs
    ],
)
def test_arena_cut_compiles(topo, class_elems):
    """One map block's arena slab, cut on one chip from a map task's
    2^26-key sorted array: no scratch the size of the slab or the
    source, and a donated pooled slab is written in place."""
    from jax.sharding import SingleDeviceSharding

    from sparkrdma_tpu.ops.hbm_arena import _cut_program

    one = SingleDeviceSharding(topo.devices[0])
    cut, cut_into = _cut_program.__wrapped__(1 << 26, class_elems, "uint32")
    src = _sds((1 << 26,), jnp.uint32, one)
    meta = _sds((2,), jnp.int32, one)
    slab_bytes = class_elems * 4
    mem = cut.lower(src, meta).compile().memory_analysis()
    assert mem.output_size_in_bytes == slab_bytes
    assert mem.temp_size_in_bytes < 4 << 20
    mem = cut_into.lower(
        _sds((class_elems,), jnp.uint32, one), src, meta
    ).compile().memory_analysis()
    assert mem.alias_size_in_bytes == slab_bytes
    assert mem.temp_size_in_bytes < 4 << 20


def test_neighbor_pull_compiles(described_devices):
    from sparkrdma_tpu.ops import remote_copy

    mesh = Mesh(np.array(described_devices), ("x",))
    prog = remote_copy._neighbor_pull_program.__wrapped__(
        4, (1, 1 << 20), "uint32"
    )
    compiled = prog.lower(
        _sds((4, 1 << 20), jnp.uint32, NamedSharding(mesh, P("x")))
    ).compile()
    assert "tpu_custom_call" in compiled.as_text()


def test_map_shard_sorter_compiles_one_chip(topo):
    """The map side's device sort + cut at 2^27 keys (one executor's
    half of the 1 GiB TeraSort)."""
    from jax.sharding import SingleDeviceSharding

    from sparkrdma_tpu.models import MapShardSorter

    one = SingleDeviceSharding(topo.devices[0])
    compiled = MapShardSorter()._step.lower(
        _sds((1 << 27,), jnp.uint32, one),
        _sds((7,), jnp.uint32, one),
        _sds((), jnp.int32, one),
    ).compile()
    mem = compiled.memory_analysis()
    assert mem.argument_size_in_bytes >= 1 << 29
    assert mem.argument_size_in_bytes + mem.temp_size_in_bytes < V5E_HBM


def test_terasorter_step_compiles_four_chips(topo):
    """The mesh TeraSort step at 2^28 keys over the 2x2 mesh: one
    all-to-all exchange between the local and the merge sorts."""
    from sparkrdma_tpu.models import TeraSorter
    from sparkrdma_tpu.parallel.mesh import make_mesh, shard_spec

    mesh = make_mesh(list(topo.devices))
    sorter = TeraSorter(mesh)
    n_local = (1 << 28) // sorter.num_shards
    compiled = sorter.step(n_local).lower(
        _sds((1 << 28,), jnp.uint32, NamedSharding(mesh, shard_spec(mesh)))
    ).compile()
    assert "all-to-all" in compiled.as_text()
    mem = compiled.memory_analysis()
    assert mem.argument_size_in_bytes + mem.temp_size_in_bytes < V5E_HBM
