"""Multi-device exchange study — the first *measured* schedule evidence.

The reference characterized its data plane executor-to-executor on a
15-node cluster (README.md:7-19); real multi-chip hardware is not
available on this rig, so this study measures the exchange plane's
*scaling shape* two ways the rig does support:

1. **Single-process virtual-device meshes** (``--xla_force_host_
   platform_device_count=E``): step time + transfer counters for the
   all_to_all vs ring schedules at E in {2,4,8} and several bucket
   sizes, plus flat-vs-hierarchical ``(dcn, exec)`` sharding at E=8.
2. **Two-process ``jax.distributed``** (gloo over loopback TCP): the
   SAME ExchangeProgram on a global 8-device mesh spanning 2 processes
   x 4 devices — the multi-host code path (process-local shard
   construction, non-addressable accounting) executed for real.

Every record is labeled CPU-only: this box has ONE core, so absolute
GB/s says nothing about TPU ICI — what transfers across is the
schedule *shape* (a2a's single fused collective vs ring's E-1
dependent hops) and that the multi-host path runs at all. Correctness
is asserted per configuration (payload round-trip), so every number is
backed by a verified exchange, mirroring how the reference's 1.41x
came from a verified TeraSort run.

Usage:
    python benchmarks/exchange_study.py                 # full study -> EXCHANGE_r05.json
    python benchmarks/exchange_study.py --quick         # CI-sized subset, no file
    python benchmarks/exchange_study.py --stage-ab      # stage-level schedule A/B
                                                        #   -> BENCH_r08.json

The ``--stage-ab`` mode (DESIGN.md §22) measures one whole reduce
stage three ways on an in-process cluster — per-block device pull
(collective compiler off), compiled collective waves (pipeline depth
1), and double-buffered pipelined waves (depth 2, wave_overlap_ms > 0
asserted) — asserts all three land byte-identical partitions, and
reports each against the exchange-loopback roofline measured on the
SAME mesh in the same process (``*_roofline_fraction`` fields)."""

from __future__ import annotations

import argparse
import json
import os
import statistics
import subprocess
import sys
import time

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(HERE)
COORD = os.environ.get("SRT_EXCHANGE_COORD", "127.0.0.1:29791")


def _payload(src: int, dst: int, block: int) -> bytes:
    """Deterministic per-(src,dst) block, distinct lengths under the bucket."""
    n = max(1, (block // 2) + ((37 * src + 11 * dst) % (block // 2)))
    return bytes([(src * 16 + dst) % 251]) * n


def _build_send(e: int, block: int):
    import numpy as np

    from sparkrdma_tpu.ops.exchange import pack_blocks

    rows, counts = [], []
    for src in range(e):
        slab, cnt = pack_blocks(
            [_payload(src, dst, block) for dst in range(e)], block
        )
        rows.append(slab)
        counts.append(cnt)
    return np.concatenate(rows, axis=0), np.concatenate(counts, axis=0)


# ----------------------------------------------------------------------
# child: one (E, topology) mesh, all schedules x blocks, one JSON line
# ----------------------------------------------------------------------
def run_child(e: int, num_slices: int, blocks, reps: int) -> None:
    import numpy as np

    import jax

    jax.config.update("jax_platforms", "cpu")
    from sparkrdma_tpu.ops.exchange import ExchangeProgram, unpack_blocks
    from sparkrdma_tpu.parallel.mesh import make_mesh

    assert len(jax.devices()) >= e, "device farm came up short"
    mesh = make_mesh(jax.devices()[:e], num_slices=num_slices)
    topology = "hier" if num_slices > 1 else "flat"
    prog = ExchangeProgram(mesh)
    schedules = ["a2a"] if topology == "hier" else ["a2a", "ring"]
    records = []
    for block in blocks:
        send, counts = _build_send(e, block)
        for sched in schedules:
            fn = prog.exchange if sched == "a2a" else prog.ring_exchange
            recv, rcounts = fn(send, counts)  # warmup (compile) + verify
            r = np.asarray(recv).reshape(e, e, block)
            rc = np.asarray(rcounts).reshape(e, e)
            for dst in range(e):
                got = unpack_blocks(r[dst], rc[dst])
                want = [_payload(src, dst, block) for src in range(e)]
                assert got == want, f"corrupt exchange e={e} {sched} {block}"
            # counters are program-lifetime cumulative: snapshot after
            # the warmup/verify call so the record's deltas cover
            # exactly the `reps` timed steps of THIS config
            base = dict(prog.stats[sched])
            times = []
            for _ in range(reps):
                t0 = time.perf_counter()
                fn(send, counts)  # entry point blocks on completion
                times.append(time.perf_counter() - t0)
            s = prog.stats[sched]
            assert s["exchanges"] == base["exchanges"] + reps
            total = e * e * block
            med = statistics.median(times)
            records.append(
                {
                    "e": e,
                    "topology": topology,
                    "mesh_shape": dict(mesh.shape),
                    "schedule": sched,
                    "block_bytes": block,
                    "total_bytes_per_step": total,
                    "reps": reps,
                    "step_s_median": round(med, 6),
                    "step_s_min": round(min(times), 6),
                    "gbps_cpu_only": round(total / med / 1e9, 4),
                    "bytes_sent": s["bytes_sent"] - base["bytes_sent"],
                    "bytes_received": s["bytes_received"] - base["bytes_received"],
                    "bytes_received_valid": (
                        s["bytes_received_valid"] - base["bytes_received_valid"]
                    ),
                    "verified": True,
                }
            )
    print("RESULT " + json.dumps(records), flush=True)


# ----------------------------------------------------------------------
# child: stage-level schedule A/B (per-block vs collective vs pipelined)
# ----------------------------------------------------------------------
def run_stage_ab_child(nblocks: int, block_bytes: int, reps: int) -> None:
    import numpy as np

    import jax

    jax.config.update("jax_platforms", "cpu")
    from sparkrdma_tpu.ops.exchange import ExchangeProgram, round_bucket
    from sparkrdma_tpu.parallel.mesh import make_mesh
    from sparkrdma_tpu.shuffle.device_io import DeviceShuffleIO
    from sparkrdma_tpu.shuffle.handle import BaseShuffleHandle, HashPartitioner
    from sparkrdma_tpu.shuffle.manager import TpuShuffleManager
    from sparkrdma_tpu.utils.config import TpuShuffleConf

    num_parts = 4
    shards = max(1, nblocks // num_parts)
    total = shards * num_parts * block_bytes

    conf = TpuShuffleConf({"tpu.shuffle.transport": "python"})
    driver = TpuShuffleManager(conf, is_driver=True)
    ex_map = TpuShuffleManager(conf, is_driver=False, executor_id="ab-map")
    ex_red = TpuShuffleManager(conf, is_driver=False, executor_id="ab-red")
    driver.register_shuffle(
        BaseShuffleHandle(
            shuffle_id=61, num_maps=1, partitioner=HashPartitioner(num_parts)
        )
    )
    io_map, io_red = DeviceShuffleIO(ex_map), DeviceShuffleIO(ex_red)
    try:
        rng = np.random.default_rng(7)
        windows, want = [], {p: [] for p in range(num_parts)}
        for _ in range(shards):
            data = {
                p: rng.integers(0, 256, block_bytes, np.uint8)
                for p in range(num_parts)
            }
            windows.append(io_map.stage_device_blocks(61, data))
            for p, arr in data.items():
                want[p].append(arr)
        io_map.publish_staged_batch(61, windows, num_map_outputs_each=1)
        want_sets = {
            p: sorted(a.tobytes() for a in want[p]) for p in range(num_parts)
        }

        def fetch():
            got = io_red.fetch_device_blocks(
                61, 0, num_parts, timeout_s=120
            )
            for bufs in got.values():
                for b in bufs:
                    arr = getattr(b, "array", None)
                    if arr is not None:
                        jax.block_until_ready(arr)
            return got

        def free(got):
            for bufs in got.values():
                for b in bufs:
                    b.free()

        def verify(mode, got):
            for p in range(num_parts):
                have = sorted(bytes(b.read(0, b.length)) for b in got[p])
                assert have == want_sets[p], f"{mode}: pid {p} corrupt"

        # mode matrix is the A/B: the tuner would re-cut budgets
        # between reps and blur it, so it sits this bench out
        conf.set("tpu.shuffle.collective.autoTune", "false")
        from sparkrdma_tpu.obs import get_registry

        overlap_c = get_registry().counter(
            "collective.wave_overlap_ms", role="ab-red"
        )
        # a cut that forms several waves per stage — what the pipelined
        # mode needs in flight; the single-wave modes keep the default
        pipelined_cut = max(64 * 1024, round_bucket(total // 8))

        def run_mode(mode):
            conf.set(
                "tpu.shuffle.collective.enabled",
                "false" if mode == "per_block" else "true",
            )
            conf.set(
                "tpu.shuffle.collective.pipelineDepth",
                "2" if mode == "pipelined" else "1",
            )
            conf.set(
                "tpu.shuffle.collective.waveBytes",
                str(pipelined_cut) if mode == "pipelined" else "64m",
            )
            warm = fetch()  # warmup: compile + correctness gate
            verify(mode, warm)
            free(warm)
            o0 = overlap_c.value
            times = []
            for _ in range(reps):
                t0 = time.perf_counter()
                got = fetch()
                times.append(time.perf_counter() - t0)
                free(got)
            med = statistics.median(times)
            return {
                "step_s_median": round(med, 6),
                "step_s_min": round(min(times), 6),
                "gbps_cpu_only": round(total / med / 1e9, 4),
                "overlap_ms": round(overlap_c.value - o0, 3),
                "verified": True,
            }

        modes = {
            m: run_mode(m)
            for m in ("per_block", "collective", "pipelined")
        }
        conf.set("tpu.shuffle.collective.enabled", "true")
        # the pipelining A/B proof: depth 1 cannot overlap by
        # construction, depth 2 must (issue while consume runs)
        assert modes["collective"]["overlap_ms"] == 0.0, (
            "depth-1 collective recorded overlap"
        )
        assert modes["pipelined"]["overlap_ms"] > 0.0, (
            "depth-2 pipelined mode recorded no overlap"
        )

        # exchange-loopback roofline on the SAME mesh, same process:
        # the compiled collective's ceiling is what one fused exchange
        # step moves per second at this bucket size
        mesh = make_mesh(jax.devices()[:8])
        prog = ExchangeProgram(mesh)
        e = prog.num_shards
        bucket = round_bucket(block_bytes)
        send = np.zeros((e * e, bucket), np.uint8)
        counts = np.full((e * e,), bucket, np.int32)
        prog.exchange(send, counts)  # compile
        rtimes = []
        for _ in range(reps):
            t0 = time.perf_counter()
            prog.exchange(send, counts)
            rtimes.append(time.perf_counter() - t0)
        rmed = statistics.median(rtimes)
        roof_gbps = e * e * bucket / rmed / 1e9

        per_block = modes["per_block"]["gbps_cpu_only"]
        record = {
            "metric": "stage_schedule_ab",
            "unit": "GB/s (CPU-only; shapes transfer, absolutes do not)",
            "num_blocks": shards * num_parts,
            "block_bytes": block_bytes,
            "num_partitions": num_parts,
            "total_bytes_per_stage": total,
            "reps": reps,
            "per_block_pull": modes["per_block"],
            "compiled_collective": modes["collective"],
            "pipelined_collective": modes["pipelined"],
            "pipeline_depth": 2,
            "pipelined_wave_bytes": pipelined_cut,
            "pipelined_overlap_ms": modes["pipelined"]["overlap_ms"],
            "exchange_loopback_gbps": round(roof_gbps, 4),
            "collective_roofline_fraction": round(
                modes["collective"]["gbps_cpu_only"] / roof_gbps, 4
            ),
            "pipelined_roofline_fraction": round(
                modes["pipelined"]["gbps_cpu_only"] / roof_gbps, 4
            ),
            "collective_speedup_vs_per_block": round(
                modes["collective"]["gbps_cpu_only"] / max(per_block, 1e-9), 3
            ),
            "pipelined_speedup_vs_per_block": round(
                modes["pipelined"]["gbps_cpu_only"] / max(per_block, 1e-9), 3
            ),
            "byte_identical_across_paths": True,
            "note": (
                "CPU loopback: per-block pull pays no per-block "
                "issue/DMA latency here, so the amortization the "
                "collective exists for (BENCH_r05's ~20x exchange-vs-"
                "host gap) cannot show in the speedup column on this "
                "rig. What transfers: byte identity across all three "
                "paths, the depth-2 overlap counter going positive "
                "while depth 1 stays zero, the roofline fractions vs "
                "the same-mesh exchange, and the compile-once "
                "wave/program shapes."
            ),
        }
        print("RESULT " + json.dumps(record), flush=True)
    finally:
        io_red.stop()
        io_map.stop()
        ex_red.stop()
        ex_map.stop()
        driver.stop()


# ----------------------------------------------------------------------
# child: one rank of the 2-process jax.distributed run
# ----------------------------------------------------------------------
def run_dist_child(pid: int, nprocs: int, block: int, reps: int) -> None:
    import numpy as np

    import jax

    jax.config.update("jax_platforms", "cpu")
    jax.distributed.initialize(COORD, num_processes=nprocs, process_id=pid)
    from jax.sharding import NamedSharding

    from sparkrdma_tpu.ops.exchange import ExchangeProgram, unpack_blocks
    from sparkrdma_tpu.parallel.mesh import make_mesh, shard_spec

    e = len(jax.devices())  # global device count across processes
    local = len(jax.local_devices())
    mesh = make_mesh(jax.devices())
    prog = ExchangeProgram(mesh)
    sharding = NamedSharding(mesh, shard_spec(mesh))

    send_np, counts_np = _build_send(e, block)
    # multi-host construction: each process contributes ONLY the rows
    # its local devices hold (global row-shard d lives on device d)
    lo, hi = pid * local * e, (pid + 1) * local * e
    send = jax.make_array_from_process_local_data(
        sharding, send_np[lo:hi], send_np.shape
    )
    counts = jax.make_array_from_process_local_data(
        sharding, counts_np[lo:hi], counts_np.shape
    )

    recv, rcounts = prog.exchange(send, counts)  # warmup + verify below
    assert not recv.is_fully_addressable  # the real multi-host path
    for shard, cshard in zip(recv.addressable_shards, rcounts.addressable_shards):
        dst = shard.index[0].start // e
        got = unpack_blocks(
            np.asarray(shard.data), np.asarray(cshard.data)
        )
        want = [_payload(src, dst, block) for src in range(e)]
        assert got == want, f"rank {pid}: corrupt rows for dst {dst}"

    base = dict(prog.stats["a2a"])  # exclude warmup/verify traffic
    times = []
    for _ in range(reps):
        t0 = time.perf_counter()
        prog.exchange(send, counts)
        times.append(time.perf_counter() - t0)
    s = prog.stats["a2a"]
    if pid == 0:
        total = e * e * block
        med = statistics.median(times)
        print(
            "RESULT "
            + json.dumps(
                {
                    "processes": nprocs,
                    "local_devices_per_process": local,
                    "e": e,
                    "schedule": "a2a",
                    "block_bytes": block,
                    "total_bytes_per_step": total,
                    "reps": reps,
                    "step_s_median": round(med, 6),
                    "gbps_cpu_only": round(total / med / 1e9, 4),
                    # receive accounting from LOCAL shards only (the
                    # non-addressable branch of ExchangeProgram._account),
                    # as a delta over exactly the `reps` timed steps
                    "bytes_received_valid_local": (
                        s["bytes_received_valid"] - base["bytes_received_valid"]
                    ),
                    "verified": True,
                }
            ),
            flush=True,
        )
    jax.distributed.shutdown()


# ----------------------------------------------------------------------
# parent: orchestrate subprocesses, aggregate, write the artifact
# ----------------------------------------------------------------------
def _spawn_child(args, devcount: int):
    """Start one CPU-ONLY study child (``JAX_PLATFORMS=cpu``, a virtual
    device farm): the study measures XLA's CPU collectives and never
    runs on a chip path, so no child here ever needs a TPU."""
    env = dict(os.environ)
    env["JAX_PLATFORMS"] = "cpu"
    # keep inherited XLA flags but OWN the device count: a stale
    # --xla_force_host_platform_device_count (e.g. pytest's conftest
    # farm of 8) must not fight the one this child needs
    kept = [
        f
        for f in env.get("XLA_FLAGS", "").split()
        if "xla_force_host_platform_device_count" not in f
    ]
    env["XLA_FLAGS"] = " ".join(
        kept + [f"--xla_force_host_platform_device_count={devcount}"]
    )
    env["PYTHONPATH"] = ROOT + os.pathsep + env.get("PYTHONPATH", "")
    return subprocess.Popen(
        [sys.executable, os.path.abspath(__file__)] + args,
        env=env,
        cwd=ROOT,
        stdout=subprocess.PIPE,
        stderr=subprocess.PIPE,  # surfaced in errors when a child dies
        text=True,
    )


def _result_line(out: str):
    for line in out.splitlines():
        if line.startswith("RESULT "):
            return json.loads(line[len("RESULT "):])
    raise RuntimeError(f"child produced no RESULT line:\n{out[-2000:]}")


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--quick", action="store_true", help="CI subset, no artifact")
    ap.add_argument(
        "--reps", type=int, default=21,
        help="timed steps per config (median reported). 21 is the "
             "canonical artifact setting: 7-rep runs on this shared "
             "rig were noisy enough to fake a schedule crossover",
    )
    ap.add_argument("--out", default=os.path.join(ROOT, "EXCHANGE_r05.json"))
    ap.add_argument(
        "--stage-ab", action="store_true",
        help="stage-level schedule A/B (per-block vs collective vs "
             "pipelined, DESIGN.md §22) -> BENCH_r08.json",
    )
    ap.add_argument(
        "--stage-out", default=os.path.join(ROOT, "BENCH_r08.json"))
    ap.add_argument("--child", nargs=4, metavar=("E", "SLICES", "BLOCKS", "REPS"))
    ap.add_argument("--dist-child", nargs=4, metavar=("PID", "NPROCS", "BLOCK", "REPS"))
    ap.add_argument(
        "--stage-child", nargs=3, metavar=("NBLOCKS", "BLOCK", "REPS"))
    args = ap.parse_args()

    if args.stage_child:
        nblocks, block, reps = (int(x) for x in args.stage_child)
        run_stage_ab_child(nblocks, block, reps)
        return
    if args.stage_ab:
        nblocks, block = (8, 65536) if args.quick else (32, 262144)
        reps = 3 if args.quick else max(7, args.reps // 3)
        p = _spawn_child(
            ["--stage-child", str(nblocks), str(block), str(reps)], 8
        )
        out, err = p.communicate(timeout=1200)
        if p.returncode != 0:
            raise RuntimeError(f"stage-ab child rc={p.returncode}:\n{err[-2000:]}")
        record = _result_line(out)
        artifact = {
            "label": (
                "Stage-level schedule A/B on the 8-virtual-device CPU "
                "mesh: per-block device pull vs compiled collective vs "
                "double-buffered pipelined waves, "
                "byte-identity asserted per mode, depth-2 overlap "
                "counter asserted positive, roofline = exchange "
                "loopback on the same mesh."
            ),
            "host": {"nproc": os.cpu_count(), "platform": sys.platform},
            "parsed": record,
        }
        print(json.dumps(artifact, indent=1))
        if not args.quick:
            with open(args.stage_out, "w") as f:
                json.dump(artifact, f, indent=1)
            print(f"wrote {args.stage_out}", file=sys.stderr)
        return

    if args.child:
        e, slices, blocks, reps = args.child
        run_child(int(e), int(slices), [int(b) for b in blocks.split(",")], int(reps))
        return
    if args.dist_child:
        pid, nprocs, block, reps = (int(x) for x in args.dist_child)
        run_dist_child(pid, nprocs, block, reps)
        return

    blocks = "16384,262144" if args.quick else "4096,65536,524288"
    reps = 3 if args.quick else args.reps
    meshes = (
        [(4, 1), (8, 1), (8, 2)]
        if args.quick
        else [(2, 1), (4, 1), (8, 1), (8, 2), (8, 4)]
    )
    single = []
    for e, slices in meshes:
        p = _spawn_child(["--child", str(e), str(slices), blocks, str(reps)], e)
        out, err = p.communicate(timeout=1200)
        if p.returncode != 0:
            raise RuntimeError(
                f"child (e={e}, slices={slices}) rc={p.returncode}:\n{err[-2000:]}"
            )
        single.extend(_result_line(out))
        print(f"mesh e={e} slices={slices}: done", file=sys.stderr)

    dist_block = 16384 if args.quick else 65536
    dist_reps = 3 if args.quick else args.reps
    procs = [
        _spawn_child(["--dist-child", str(pid), "2", str(dist_block), str(dist_reps)], 4)
        for pid in range(2)
    ]
    # drain both children CONCURRENTLY: they form one jax.distributed
    # pair, so blocking on child 0 while child 1 fills its piped stderr
    # (gloo chatter can exceed the pipe buffer) would deadlock the run
    from concurrent.futures import ThreadPoolExecutor

    with ThreadPoolExecutor(len(procs)) as tp:
        results = list(tp.map(lambda p: p.communicate(timeout=1200), procs))
    for pid, p in enumerate(procs):
        if p.returncode != 0:
            raise RuntimeError(
                f"dist child {pid} rc={p.returncode}:\n{results[pid][1][-2000:]}"
            )
    dist = _result_line(results[0][0])
    print("distributed 2-process run: done", file=sys.stderr)

    # schedule comparison at a glance: ring/a2a step-time ratio per config
    compare = []
    flat = [r for r in single if r["topology"] == "flat"]
    for e in sorted({r["e"] for r in flat}):
        for b in sorted({r["block_bytes"] for r in flat}):
            a2a = next(
                (r for r in flat if r["e"] == e and r["block_bytes"] == b
                 and r["schedule"] == "a2a"), None)
            ring = next(
                (r for r in flat if r["e"] == e and r["block_bytes"] == b
                 and r["schedule"] == "ring"), None)
            if a2a and ring:
                compare.append(
                    {
                        "e": e,
                        "block_bytes": b,
                        "ring_over_a2a_step_ratio": round(
                            ring["step_s_median"] / a2a["step_s_median"], 3
                        ),
                    }
                )

    artifact = {
        "label": (
            "CPU-only: virtual-device meshes on a 1-core host. Schedule "
            "SHAPES and the multi-host code path transfer to TPU; "
            "absolute GB/s does not (no ICI here). Every record is "
            "correctness-verified payload round-trip."
        ),
        "host": {"nproc": os.cpu_count(), "platform": sys.platform},
        "single_process": single,
        "schedule_comparison": compare,
        "two_process_distributed": dist,
    }
    print(json.dumps(artifact, indent=1))
    if not args.quick:
        with open(args.out, "w") as f:
            json.dump(artifact, f, indent=1)
        print(f"wrote {args.out}", file=sys.stderr)


if __name__ == "__main__":
    main()
