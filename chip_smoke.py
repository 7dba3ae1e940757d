"""On-chip smoke test: the served shuffle path, once, at a real size.

Run from the repo root on a machine with a TPU:

    python chip_smoke.py             # one chip: phases A and B
    python chip_smoke.py --chips 4   # four chips: mesh sort + ICI waves

Every phase drives the system through the entry points a user calls
(``TpuShuffleManager``, ``DeviceShuffleIO``, ``MapShardSorter``,
``TeraSorter``) and checks its output against a numpy reference made
from the same seeded input. Any failure raises and the process exits
non-zero; nothing is caught and turned into a pass.

- Phase 0: the first JAX device must be a TPU; there is no CPU branch.
- Phase A (one chip): full-stack TeraSort of 2^28 uint32 keys (1 GiB,
  HiBench TeraSort's 1 GB configuration, ``BASELINE.json`` configs[0]).
  Two executors with their own ``DeviceShuffleIO`` in this process, 8
  reducers, native transport: device sort + cut (``MapShardSorter``),
  stage + publish (``MapTaskPipeline``), driver location RPC, fetch with
  the default configuration (device fetch plane on), typed HBM staging,
  device merge. Checked by per-reducer count/sum/xor against numpy, an
  on-device sortedness flag, and an exact readback of one whole reducer
  against ``np.sort`` of the input.
- Phase B (one chip): ``TeraSorter`` over a one-chip mesh, 2^26 keys,
  exact against ``np.sort``.
- ``--chips 4`` runs only (i) ``TeraSorter`` over the 4-chip mesh on
  2^28 keys, exact against ``np.sort``, and (ii) a wave fetch by the
  collective schedule compiler with four executors, each arena on its
  own chip, at pipeline depth 1 and 2, byte-identical to a host-path
  fetch of the same blocks, with the Pallas movers asserted to have
  carried every wave.

Earlier output lines are JSON records of each phase (walls, compile
seconds, peak HBM, counters). The last line is exactly
``{"ok": true, "device": {"platform", "kind", "count"}}``.
"""

from __future__ import annotations

import argparse
import json
import time

import numpy as np

SEED = 21
TERASORT_KEYS = 1 << 28   # 1 GiB of uint32 keys
EXECUTORS = 2
REDUCERS = 8
SORTER_KEYS = 1 << 26
WAVE_REDUCERS = 8
WAVE_BLOCK_KEYS = 1 << 22  # 16 MiB blocks: one partition = 64 MiB wave


def emit(record: dict) -> None:
    print(json.dumps(record), flush=True)


def require_tpu(chips: int):
    """Phase 0: a TPU, with at least ``chips`` devices, or exit."""
    import jax

    devices = jax.devices()
    if devices[0].platform != "tpu":
        raise SystemExit(
            f"chip_smoke: no TPU (JAX platform {devices[0].platform!r})"
        )
    if len(devices) < chips:
        raise SystemExit(
            f"chip_smoke: {chips} chips asked for, {len(devices)} present"
        )
    return devices[:chips]


def peak_hbm(devices) -> dict:
    """Peak HBM per chip since the process started."""
    return {str(d.id): d.memory_stats()["peak_bytes_in_use"] for d in devices}


def versions() -> dict:
    import importlib.metadata as md

    import jax
    import jaxlib

    return {"jax": jax.__version__, "jaxlib": jaxlib.__version__,
            "libtpu": md.version("libtpu")}


def counter_total(name: str, **labels) -> int:
    """Sum of a counter over every series whose labels include
    ``labels`` (roles differ per executor)."""
    from sparkrdma_tpu.obs import get_registry

    snap = get_registry().snapshot(prefix=name)["counters"]
    total = 0
    for key, value in snap.items():
        if key.split("{")[0] != name:
            continue
        if all(f"{k}={v}" in key for k, v in labels.items()):
            total += value
    return total


def checksums(keys: np.ndarray):
    with np.errstate(over="ignore"):
        csum = int(keys.sum(dtype=np.uint32))
    cxor = int(np.bitwise_xor.reduce(keys)) if len(keys) else 0
    return len(keys), csum, cxor


# ----------------------------------------------------------------------
# Phase A: full-stack TeraSort through TpuShuffleManager
# ----------------------------------------------------------------------
def phase_terasort_e2e(n_keys: int = TERASORT_KEYS, executors: int = EXECUTORS,
                       reducers: int = REDUCERS, transport: str = "native",
                       seed: int = SEED) -> dict:
    import jax
    import jax.numpy as jnp

    from sparkrdma_tpu.models import MapShardSorter
    from sparkrdma_tpu.ops.hbm_arena import MIN_BLOCK_SIZE, _size_class
    from sparkrdma_tpu.ops.sort import device_sort
    from sparkrdma_tpu.shuffle.device_io import DeviceShuffleIO
    from sparkrdma_tpu.shuffle.handle import BaseShuffleHandle, HashPartitioner
    from sparkrdma_tpu.shuffle.manager import TpuShuffleManager
    from sparkrdma_tpu.shuffle.reader.pipeline import ReduceTaskPipeline
    from sparkrdma_tpu.shuffle.writer.pipeline import MapTaskPipeline
    from sparkrdma_tpu.utils.config import TpuShuffleConf

    sid = 21
    rng = np.random.default_rng(seed)
    keys = rng.integers(0, 1 << 32, n_keys, dtype=np.uint32)
    shards = np.split(keys, executors)
    edges = np.asarray(
        [(r * (1 << 32)) // reducers for r in range(1, reducers)], np.uint32
    )
    ref = np.sort(keys)
    ref_bounds = np.concatenate(
        [[0], np.searchsorted(ref, edges, side="left"), [n_keys]]
    )
    expect = [
        checksums(ref[ref_bounds[r]: ref_bounds[r + 1]])
        for r in range(reducers)
    ]
    check_r = reducers // 2  # the reducer read back whole

    conf = TpuShuffleConf({"tpu.shuffle.transport": transport})
    driver = TpuShuffleManager(conf, is_driver=True)
    execs = [
        TpuShuffleManager(conf, is_driver=False, executor_id=f"smoke-{i}")
        for i in range(executors)
    ]
    driver.register_shuffle(BaseShuffleHandle(
        shuffle_id=sid, num_maps=executors,
        partitioner=HashPartitioner(reducers),
    ))
    ios = [DeviceShuffleIO(ex) for ex in execs]
    reducer_io = ios[0]
    out = {"phase": "A_terasort_e2e", "keys": n_keys, "executors": executors,
           "reducers": reducers}
    try:
        node_class = type(execs[0].node).__name__
        if transport == "native" and node_class != "NativeTpuNode":
            raise SystemExit(f"chip_smoke: native transport not running "
                             f"({node_class})")
        out["transport_node"] = node_class

        sorter = MapShardSorter()
        t0 = time.perf_counter()
        sorter.warm(n_keys // executors, len(edges))
        out["map_compile_s"] = time.perf_counter() - t0

        def sort_shard(i):
            return sorter.sort_partition(shards[i], edges)

        def stage_shard(i, sorted_out):
            local, bounds = sorted_out
            return ios[i].stage_device_blocks(
                sid, {r: local[bounds[r]: bounds[r + 1]]
                      for r in range(reducers)},
            )

        def publish_shard(i, locs):
            ios[i].publish_staged(sid, locs, num_map_outputs=1)

        map_report = MapTaskPipeline(
            sort_shard, stage_shard, publish_shard,
            parallelism=conf.map_parallelism, depth=conf.map_pipeline_depth,
            role="smoke-map",
        ).run(range(executors))
        out["map_publish_wall_s"] = map_report.wall_s

        @jax.jit
        def merge(arrs, word_counts):
            stacked = jnp.stack(arrs)
            iota = jnp.arange(stacked.shape[1], dtype=jnp.int32)[None, :]
            masked = jnp.where(iota < word_counts[:, None], stacked,
                               jnp.uint32(0xFFFFFFFF))
            merged = device_sort(masked.reshape(-1))
            t = word_counts.sum().astype(jnp.uint32)
            valid = jnp.arange(merged.shape[0], dtype=jnp.int32) < t
            mm = jnp.where(valid, merged, jnp.uint32(0))
            csum = mm.sum(dtype=jnp.uint32)
            cxor = jax.lax.reduce(mm, jnp.uint32(0), jax.lax.bitwise_xor,
                                  (0,))
            ok = jnp.all(merged[1:] >= merged[:-1]).astype(jnp.uint32)
            return merged, jnp.stack([t, csum, cxor, ok])

        # compile every merge shape the reduce can hit: blocks land in
        # one of two adjacent size classes around the mean block size
        mean_block = n_keys // executors // reducers * 4
        cls_hi = _size_class(int(mean_block * 1.05)) // 4
        cls_lo = max(_size_class(MIN_BLOCK_SIZE) // 4, cls_hi // 2)
        t0 = time.perf_counter()
        for cw in {cls_hi, cls_lo}:
            jax.block_until_ready(merge(
                tuple(jnp.zeros((cw,), jnp.uint32) for _ in range(executors)),
                jnp.full((executors,), cw, jnp.int32),
            )[0])
        out["merge_compile_s"] = time.perf_counter() - t0

        kept = {}

        def fetch(r):
            return reducer_io.fetch_host_blocks(
                sid, r, r + 1, timeout_s=300, dtype=np.uint32
            ).get(r, [])

        def verify(_r, blocks):
            return [reducer_io.verify_host_block(hb) for hb in blocks]

        def stage(_r, blocks):
            return [reducer_io.stage_host_block(hb, dtype=np.uint32)
                    for hb in blocks]

        def merge_group(r, bufs):
            with reducer_io.device_buffers.pinned_on_device(bufs):
                cap = max(b.array.shape[0] for b in bufs)
                arrs = tuple(
                    b.array if b.array.shape[0] == cap
                    else jnp.zeros((cap,), jnp.uint32)
                    .at[: b.array.shape[0]].set(b.array)
                    for b in bufs
                )
                counts = jnp.asarray([b.length // 4 for b in bufs], jnp.int32)
                merged, packed = merge(arrs, counts)
            jax.block_until_ready(merged)
            for b in bufs:
                b.free()
            if r == check_r:
                kept["merged"] = merged
            return packed

        def discard(stage_name, _item, value):
            if not value:
                return
            if stage_name in ("fetch", "decode"):
                for hb in value:
                    hb.release()
            elif stage_name == "stage":
                for b in value:
                    b.free()

        t0 = time.perf_counter()
        reduce_report = ReduceTaskPipeline(
            fetch, verify, stage, merge_group,
            parallelism=conf.reduce_parallelism,
            depth=conf.reduce_pipeline_depth,
            double_buffer=conf.reduce_double_buffer_staging,
            role="smoke-reduce", discard_fn=discard,
        ).run(range(reducers))
        stats = np.asarray(jax.device_get(jnp.stack(reduce_report.results)))
        out["reduce_wall_s"] = time.perf_counter() - t0
        out["reduce_stage_busy_s"] = reduce_report.stage_busy_s
        for r in range(reducers):
            got = tuple(int(x) for x in stats[r])
            if got[:3] != expect[r]:
                raise SystemExit(f"chip_smoke: reducer {r} count/sum/xor "
                                 f"{got[:3]} != numpy {expect[r]}")
            if got[3] != 1:
                raise SystemExit(f"chip_smoke: reducer {r} not sorted on device")
        t0 = time.perf_counter()
        lo, hi = ref_bounds[check_r], ref_bounds[check_r + 1]
        back = np.asarray(kept.pop("merged"))[: hi - lo]
        out["readback_s"] = time.perf_counter() - t0
        if not np.array_equal(back, ref[lo:hi]):
            raise SystemExit(f"chip_smoke: reducer {check_r} differs from "
                             f"np.sort of the input")
        out["readback_bytes"] = int(back.nbytes)

        role = execs[0].executor_id
        out["device_fetch_pulls"] = counter_total(
            "device_fetch.plane.pulls", role=role)
        out["device_fetch_fallbacks"] = counter_total(
            "device_fetch.plane.fallbacks", role=role)
        out["mover_dispatches"] = {
            m: counter_total("collective.mover_dispatches", role=role, mover=m)
            for m in ("pallas_wave_pull", "pallas_pipelined_wave_pull")
        }
        if out["device_fetch_fallbacks"] != 0:
            raise SystemExit("chip_smoke: device fetch plane fell back")
        if out["device_fetch_pulls"] != executors * reducers:
            raise SystemExit(
                f"chip_smoke: {out['device_fetch_pulls']} of "
                f"{executors * reducers} blocks moved on the device plane")
    finally:
        for io in ios:
            io.stop()
        for ex in execs:
            ex.stop()
        driver.stop()
    out["verified"] = ("count+sum+xor per reducer, on-device sortedness, "
                       f"exact readback of reducer {check_r}")
    return out


# ----------------------------------------------------------------------
# Phase B / (i): TeraSorter over a mesh
# ----------------------------------------------------------------------
def phase_mesh_sort(devices, n_keys: int, seed: int = SEED) -> dict:
    from sparkrdma_tpu.models import TeraSorter
    from sparkrdma_tpu.parallel.mesh import make_mesh

    keys = np.random.default_rng(seed + 1).integers(
        0, 1 << 32, n_keys, dtype=np.uint32)
    ref = np.sort(keys)
    sorter = TeraSorter(make_mesh(list(devices)))
    t0 = time.perf_counter()
    got = sorter.sort(keys)
    first = time.perf_counter() - t0
    if not np.array_equal(got, ref):
        raise SystemExit(f"chip_smoke: TeraSorter on {len(devices)} chip(s) "
                         f"differs from np.sort")
    t0 = time.perf_counter()
    got = sorter.sort(keys)
    second = time.perf_counter() - t0
    if not np.array_equal(got, ref):
        raise SystemExit("chip_smoke: TeraSorter differs on its second run")
    return {"phase": f"mesh_sort_{len(devices)}chip", "keys": n_keys,
            "first_call_s": first, "second_call_s": second,
            "verified": "exact vs np.sort"}


# ----------------------------------------------------------------------
# --chips 4 (ii): wave fetch by the collective schedule compiler
# ----------------------------------------------------------------------
def phase_wave_fetch(devices, block_keys: int = WAVE_BLOCK_KEYS,
                     reducers: int = WAVE_REDUCERS, depths=(1, 2),
                     transport: str = "native", seed: int = SEED) -> dict:
    """One executor per device, each publishing a block per reducer
    from an arena on its own device; executor k then fetches its share
    of the reducers. Per depth: the wave fetch must equal a host-path
    fetch of the same blocks byte for byte, and the Pallas mover of
    that depth must have carried every wave."""
    from sparkrdma_tpu.shuffle.device_fetch import DevicePulledBlock
    from sparkrdma_tpu.shuffle.device_io import DeviceShuffleIO
    from sparkrdma_tpu.shuffle.handle import BaseShuffleHandle, HashPartitioner
    from sparkrdma_tpu.shuffle.manager import TpuShuffleManager
    from sparkrdma_tpu.utils.config import TpuShuffleConf

    sid = 22
    n = len(devices)
    block_bytes = block_keys * 4
    conf = TpuShuffleConf({
        "tpu.shuffle.transport": transport,
        # one partition's n blocks fill exactly one wave
        "tpu.shuffle.collective.waveBytes": str(n * block_bytes),
        "tpu.shuffle.collective.autoTune": "false",
    })
    driver = TpuShuffleManager(conf, is_driver=True)
    execs = [
        TpuShuffleManager(conf, is_driver=False, executor_id=f"smoke-wave-{i}")
        for i in range(n)
    ]
    driver.register_shuffle(BaseShuffleHandle(
        shuffle_id=sid, num_maps=n, partitioner=HashPartitioner(reducers)))
    ios = [DeviceShuffleIO(ex, device=d) for ex, d in zip(execs, devices)]
    out = {"phase": "wave_fetch", "executors": n, "reducers": reducers,
           "block_bytes": block_bytes}
    try:
        arena_devices = sorted({io.device_buffers.device.id for io in ios})
        if len(arena_devices) != n:
            raise SystemExit(f"chip_smoke: arenas on devices {arena_devices}, "
                             f"not {n} distinct ones")
        out["arena_devices"] = arena_devices
        rng = np.random.default_rng(seed + 2)
        for io in ios:
            io.publish_device_blocks(sid, {
                r: rng.integers(0, 1 << 32, block_keys, dtype=np.uint32)
                for r in range(reducers)
            })
        share = reducers // n

        def fetch_all():
            """Every executor fetches its reducers: pid -> sorted block
            bytes, and how many blocks arrived on the device plane."""
            got, pulled = {}, 0
            for k, io in enumerate(ios):
                lo = k * share
                for pid, blocks in io.fetch_host_blocks(
                    sid, lo, lo + share, timeout_s=300, dtype=np.uint32
                ).items():
                    for hb in blocks:
                        if isinstance(hb, DevicePulledBlock):
                            pulled += 1
                            dev = hb.take()
                            got.setdefault(pid, []).append(
                                dev.read(0, dev.length))
                            dev.free()
                        else:
                            got.setdefault(pid, []).append(bytes(hb.data))
                            hb.release()
            return {p: sorted(v) for p, v in got.items()}, pulled

        conf.set("tpu.shuffle.deviceFetch.enabled", "false")
        t0 = time.perf_counter()
        host, host_pulled = fetch_all()
        out["host_fetch_s"] = time.perf_counter() - t0
        conf.set("tpu.shuffle.deviceFetch.enabled", "true")
        if host_pulled or len(host) != reducers:
            raise SystemExit("chip_smoke: host-path reference fetch is wrong")
        movers = {1: "pallas_wave_pull", 2: "pallas_pipelined_wave_pull"}
        for depth in depths:
            conf.set("tpu.shuffle.collective.pipelineDepth", str(depth))
            before = {m: counter_total("collective.mover_dispatches", mover=m)
                      for m in movers.values()}
            falls0 = counter_total("device_fetch.plane.fallbacks")
            t0 = time.perf_counter()
            dev, pulled = fetch_all()
            wall = time.perf_counter() - t0
            ran = {m: counter_total("collective.mover_dispatches", mover=m)
                   - v for m, v in before.items()}
            falls = counter_total("device_fetch.plane.fallbacks") - falls0
            if dev != host:
                raise SystemExit(f"chip_smoke: depth-{depth} wave fetch "
                                 f"differs from the host path")
            if pulled != n * reducers or falls:
                raise SystemExit(f"chip_smoke: depth {depth}: {pulled} "
                                 f"blocks pulled, {falls} fallbacks")
            if ran[movers[depth]] == 0 or any(
                ran[m] for d, m in movers.items() if d != depth
            ):
                raise SystemExit(f"chip_smoke: depth {depth} movers {ran}")
            out[f"depth{depth}"] = {"wall_s": wall, "movers": ran,
                                    "blocks_pulled": pulled}
    finally:
        for io in ios:
            io.stop()
        for ex in execs:
            ex.stop()
        driver.stop()
    out["verified"] = "byte-identical to host-path fetch at every depth"
    return out


def main() -> None:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--chips", type=int, default=1, choices=(1, 4))
    args = ap.parse_args()

    devices = require_tpu(args.chips)

    from sparkrdma_tpu.utils.compile_cache import enable_compile_cache

    emit({"phase": "0_device", "platform": devices[0].platform,
          "kind": devices[0].device_kind, "count": len(devices),
          "compile_cache": enable_compile_cache(), **versions()})
    t_all = time.perf_counter()
    if args.chips == 1:
        phases = [
            ("A", lambda: phase_terasort_e2e()),
            ("B", lambda: phase_mesh_sort(devices, SORTER_KEYS)),
        ]
    else:
        phases = [
            ("i", lambda: phase_mesh_sort(devices, TERASORT_KEYS)),
            ("ii", lambda: phase_wave_fetch(devices)),
        ]
    for _name, run in phases:
        t0 = time.perf_counter()
        rec = run()
        rec["wall_s"] = time.perf_counter() - t0
        rec["peak_bytes_in_use"] = peak_hbm(devices)
        emit(rec)
    emit({"phase": "done", "total_s": time.perf_counter() - t_all})
    print(json.dumps({"ok": True, "device": {
        "platform": devices[0].platform,
        "kind": devices[0].device_kind,
        "count": len(devices),
    }}), flush=True)


if __name__ == "__main__":
    main()
