"""Workload benchmark suite — the HiBench role (SURVEY.md §6).

Runs the BASELINE.md workload set against this framework and prints one
JSON line per workload (and, with --out, writes them all to a committed
artifact — WORKLOADS_r{N}.json — so regressions are visible
round-over-round):

  1. TeraSort via the HOST engine (full shuffle path: writers,
     registered memory, one-sided READs, fetcher) — BASELINE config #1
     shape, scaled by --scale.
  2. TeraSort via the DEVICE plane (partition -> all_to_all -> merge).
  3. PageRank (multi-round all-to-all).
  4. ALS (iterative wide shuffle).
  5. Hash join (shuffle-heavy join).
  6. Transformer training throughput (ulysses attention through the
     Pallas flash kernel fwd+bwd; K steps in one executable).
  7. With --e2e-gb G: END-TO-END TeraSort of G GiB through the WHOLE
     stack — host map sorts -> range split -> publish into registered
     memory -> driver location protocol -> one-sided native READs ->
     HBM staging -> device merge — verified on-device (sortedness +
     order-invariant checksums vs the host input) and phase-timed
     against the stock single-host ``np.sort`` baseline (the
     reference's 1.41x comparison shape, README.md:7-19).

Usage: python benchmarks/run_workloads.py [--scale 0.05]
         [--transport native] [--e2e-gb 1.0] [--out WORKLOADS_r04.json]
"""

import argparse
import json
import os
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import numpy as np

RECORDS = []


def report(workload, seconds, **extra):
    rec = {"workload": workload, "seconds": round(seconds, 4), **extra}
    RECORDS.append(rec)
    print(json.dumps(rec), flush=True)


def bench_engine_terasort(scale: float, transport: str):
    from sparkrdma_tpu.engine.context import TpuContext
    from sparkrdma_tpu.utils.config import TpuShuffleConf

    n = int(1_000_000 * scale)  # records of ~100B => scale * 100MB
    conf = TpuShuffleConf({"tpu.shuffle.transport": transport})
    rng = np.random.default_rng(0)
    keys = rng.integers(0, 1 << 32, n, dtype=np.uint64)

    with TpuContext(num_executors=2, conf=conf, task_threads=4) as ctx:
        data = [(int(k), b"x" * 90) for k in keys]
        t0 = time.perf_counter()
        rdd = ctx.parallelize(data, 8).sort_by_key(num_partitions=8)
        out = ctx.run_job(rdd)
        dt = time.perf_counter() - t0
        bd = ctx.last_breakdown  # critical-path verdict (obs/critpath.py)
    assert len(out) == n
    assert all(out[i][0] <= out[i + 1][0] for i in range(min(1000, n - 1)))
    report(
        "terasort_engine", dt,
        records=n, transport=transport,
        mb=round(n * 100 / 1e6, 1),
        records_per_s=int(n / dt),
        breakdown=bd.to_dict() if bd is not None else None,
    )


def bench_device_terasort(scale: float):
    import jax

    from sparkrdma_tpu.models import TeraSorter
    from sparkrdma_tpu.parallel.mesh import make_mesh

    n = int((1 << 24) * scale * 20)  # default scale 0.05 -> 16M keys
    rng = np.random.default_rng(0)
    keys = rng.integers(0, 1 << 32, n, dtype=np.uint32)
    sorter = TeraSorter(make_mesh())
    sorter.sort(keys)  # warm: compile at the real shape
    t0 = time.perf_counter()
    out = sorter.sort(keys)
    dt = time.perf_counter() - t0
    assert len(out) == n
    report(
        "terasort_device", dt,
        keys=n, devices=len(jax.devices()),
        e2e_gbps_incl_transfers=round(n * 4 / dt / 1e9, 3),
        note=(
            "wall time includes host->device and device->host of every "
            "byte; bench.py's device_sort_gbps is the on-chip rate of "
            "the same step"
        ),
    )


def bench_e2e_terasort(gb: float, transport: str, reducers: int = 8,
                       executors: int = 2, device_fetch: bool = True):
    """One measured TeraSort with the WHOLE framework in the loop.

    Map side plays Spark's part (host sorts, as the reference leaves to
    Spark's sort writers); everything after — registered-memory
    publish, driver location RPC, one-sided READs, HBM staging, device
    merge — is this framework. Output is verified on the device
    (order-invariant xor/sum checksums + a sortedness reduction), so
    the timed window holds no bulk device->host readback. chip_smoke.py
    additionally reads one reducer back and compares it exactly."""
    import jax
    import jax.numpy as jnp

    from sparkrdma_tpu.ops.sort import device_sort
    from sparkrdma_tpu.shuffle.device_io import DeviceShuffleIO
    from sparkrdma_tpu.shuffle.handle import BaseShuffleHandle, HashPartitioner
    from sparkrdma_tpu.shuffle.manager import TpuShuffleManager
    from sparkrdma_tpu.utils.config import TpuShuffleConf

    n = int(gb * (1 << 30)) // 4
    n -= n % executors
    rng = np.random.default_rng(12)
    shards = [
        rng.integers(0, 1 << 32, n // executors, dtype=np.uint32)
        for _ in range(executors)
    ]

    # stock role: one host np.sort over everything (what the reference's
    # baseline ran as Spark's sort shuffle on one node)
    t0 = time.perf_counter()
    host_sorted = np.sort(np.concatenate(shards))
    t_host = time.perf_counter() - t0
    del host_sorted  # multiset checks below; bytes never compared bulk

    # expected per-reducer order-invariant checksums from the INPUT
    edges = np.asarray(
        [(r * (1 << 32)) // reducers for r in range(1, reducers)], np.uint32
    )
    exp_sum = np.zeros(reducers, np.uint32)
    exp_xor = np.zeros(reducers, np.uint32)
    exp_cnt = np.zeros(reducers, np.int64)
    for sh in shards:
        dest = np.searchsorted(edges, sh, side="right")
        for r in range(reducers):
            sel = sh[dest == r]
            exp_cnt[r] += len(sel)
            with np.errstate(over="ignore"):
                exp_sum[r] += sel.sum(dtype=np.uint32)
            exp_xor[r] ^= np.bitwise_xor.reduce(sel) if len(sel) else np.uint32(0)

    # device_fetch=False pins the HOST transport plane under test: in
    # this single-process harness every executor's arena is
    # mesh-visible, so the device plane would otherwise pull every
    # remote block HBM->HBM and the host plane would idle (DESIGN.md
    # §17 — exactly what it should do in production, but not what a
    # transport benchmark wants)
    conf = TpuShuffleConf({
        "tpu.shuffle.transport": transport,
        "tpu.shuffle.deviceFetch.enabled": str(device_fetch).lower(),
    })
    driver = TpuShuffleManager(conf, is_driver=True)
    execs = [
        TpuShuffleManager(conf, is_driver=False, executor_id=f"e2e-{i}")
        for i in range(executors)
    ]
    handle = BaseShuffleHandle(
        shuffle_id=99, num_maps=executors, partitioner=HashPartitioner(reducers)
    )
    driver.register_shuffle(handle)
    ios = [DeviceShuffleIO(ex) for ex in execs]
    phases = {}
    try:
        # --- map side: the PIPELINED DEVICE-ACCELERATED map plane ------
        # WORKLOADS_r05 pinned the e2e loss here: sequential host
        # np.sort + publish walled 22.95 s. Two structural fixes ride
        # together (DESIGN.md "Pipelined map plane"):
        #   1. the O(N log N) sort runs ON DEVICE (MapShardSorter: one
        #      device_sort + device-side searchsorted per shard; the
        #      host never sorts),
        #   2. sort -> stage -> publish run as a bounded three-stage
        #      pipeline (MapTaskPipeline), so shard k+1 sorts while
        #      shard k stages into registered memory and shard k-1's
        #      locations upload.
        # Busy times per stage come from the pipeline report; the wall
        # is what counts. conf map.deviceSort=false falls back to the
        # host sort inside the same pipeline (stage/publish overlap
        # still applies).
        from sparkrdma_tpu.models import MapShardSorter
        from sparkrdma_tpu.shuffle.writer.pipeline import MapTaskPipeline

        keep0 = {}  # executor 0's sorted output, reused by the solo probe
        use_device_sort = bool(conf.map_device_sort)
        shard_sorter = MapShardSorter() if use_device_sort else None
        t0 = time.perf_counter()
        if shard_sorter is not None:
            shard_sorter.warm(n // executors, len(edges))
        map_compile_s = time.perf_counter() - t0

        def sort_shard(i):
            if shard_sorter is not None:
                local, bounds = shard_sorter.sort_partition(shards[i], edges)
            else:
                local = np.sort(shards[i])
                bounds = np.concatenate(
                    [[0], np.searchsorted(local, edges), [len(local)]]
                )
            if i == 0:
                keep0["local"], keep0["bounds"] = local, bounds
            return local, bounds

        def stage_shard(i, sorted_out):
            local, bounds = sorted_out
            return ios[i].stage_device_blocks(
                99,
                {r: local[bounds[r]: bounds[r + 1]] for r in range(reducers)},
            )

        def publish_shard(i, locs):
            ios[i].publish_staged(99, locs, num_map_outputs=1)

        pipe = MapTaskPipeline(
            sort_shard, stage_shard, publish_shard,
            parallelism=conf.map_parallelism,
            depth=conf.map_pipeline_depth,
            role="e2e-map",
        )
        pipe_report = pipe.run(range(executors))
        phases["map_publish_wall_s"] = pipe_report.wall_s

        # publish cost measured UNCONTENDED (solo re-publish of
        # executor 0's retained sorted output to a throwaway shuffle
        # id): busy timers under the pipelined phase inflate with CPU
        # contention against the sorts (1-core rig) and wall-minus-busy
        # arithmetic breaks on multi-core — a direct solo measurement
        # is right on both topologies
        local0, bounds0 = keep0["local"], keep0["bounds"]
        ts = time.perf_counter()
        ios[0].publish_device_blocks(
            98, {r: local0[bounds0[r]: bounds0[r + 1]] for r in range(reducers)}
        )
        publish_solo = time.perf_counter() - ts
        ios[0].unpublish(98)
        keep0.clear()
        del local0

        # --- reduce side: READ -> stage -> device merge ----------------
        # Blocks arrive STAGED AS uint32 (fetch dtype) — a uint8 slab
        # would force on-device byte->word assembly, whose [..., 4]-minor
        # reshape the TPU tiled layout pads 4->128 (measured: a 32 GiB
        # HBM allocation for a 1 GiB input). jit's own dispatch cache
        # handles per-shape retracing.
        @jax.jit
        def merge(arrs, word_counts):
            stacked_u32 = jnp.stack(arrs)
            _, words = stacked_u32.shape
            iota = jnp.arange(words, dtype=jnp.int32)[None, :]
            masked = jnp.where(
                iota < word_counts[:, None], stacked_u32,
                jnp.uint32(0xFFFFFFFF),
            )
            merged = device_sort(masked.reshape(-1))
            t = word_counts.sum().astype(jnp.uint32)
            vi = jnp.arange(merged.shape[0], dtype=jnp.int32)
            mm = jnp.where(vi < t, merged, jnp.uint32(0))
            csum = mm.sum(dtype=jnp.uint32)
            cxor = jax.lax.reduce(
                mm, jnp.uint32(0), jax.lax.bitwise_xor, (0,)
            )
            ok = jnp.all(merged[1:] >= merged[:-1]).astype(jnp.uint32)
            # ONE packed scalar vector per reducer; every reducer's
            # row comes back in one readback after the last merge
            return merged, jnp.stack([t, csum, cxor, ok])

        # warm the merge executable at the expected slab shape (compile
        # is the JVM-startup analogue the reference's numbers exclude)
        from sparkrdma_tpu.ops.hbm_arena import MIN_BLOCK_SIZE, _size_class

        # Warm every executable the timed loop can hit (compile is the
        # JVM-startup analogue the reference's numbers exclude). The
        # mean block size can sit ON a size-class boundary, so blocks
        # land in TWO adjacent classes: warm the merge at both
        # homogeneous shapes AND the small->large pad used when one
        # reducer's blocks mix classes.
        mean_block = int(n / executors / reducers * 4)
        cls_hi = _size_class(int(mean_block * 1.05)) // 4
        cls_lo = max(_size_class(MIN_BLOCK_SIZE) // 4, cls_hi // 2)
        t0 = time.perf_counter()
        for cw in {cls_hi, cls_lo}:
            jax.block_until_ready(
                merge(
                    tuple(jnp.zeros((cw,), jnp.uint32)
                          for _ in range(executors)),
                    jnp.full((executors,), cw, jnp.int32),
                )[0]
            )
        if cls_lo != cls_hi:
            jax.block_until_ready(
                jnp.zeros((cls_hi,), jnp.uint32)
                .at[:cls_lo]
                .set(jnp.zeros((cls_lo,), jnp.uint32))
            )
        phases_compile = time.perf_counter() - t0

        # fetch/compute overlap (SURVEY §2.3, DESIGN.md §16): the
        # reduce side runs on the ReduceTaskPipeline — group READs for
        # reducer k+2 in flight while k+1's checksum verify runs on the
        # decode pool, k's host->HBM staging rides under k-1's device
        # merge (double-buffered staging). r05's 1-deep prefetch loop
        # fused transport+verify+stage into one blocking call; the
        # split-phase DeviceShuffleIO API lets each plane's busy clock
        # tick on its own pipeline stage.
        from sparkrdma_tpu.shuffle.reader.pipeline import ReduceTaskPipeline

        reducer_io = ios[0]

        def fetch_blocks(r):
            got = reducer_io.fetch_host_blocks(
                99, r, r + 1, timeout_s=120, dtype=np.uint32
            )
            return got.get(r, [])

        def verify_blocks(_r, blocks):
            return [reducer_io.verify_host_block(hb) for hb in blocks]

        def stage_blocks(_r, blocks):
            return [
                reducer_io.stage_host_block(hb, dtype=np.uint32)
                for hb in blocks
            ]

        def merge_group(_r, bufs):
            # pin the set device-resident across the direct .array
            # access (no-op unless HBM pressure spilled some; members
            # are never victims while pinned)
            with reducer_io.device_buffers.pinned_on_device(bufs):
                cap = max(b.array.shape[0] for b in bufs)
                arrs = tuple(
                    b.array
                    if b.array.shape[0] == cap
                    else jnp.zeros((cap,), jnp.uint32)
                    .at[: b.array.shape[0]]
                    .set(b.array)
                    for b in bufs
                )
                counts = jnp.asarray(
                    [b.length // 4 for b in bufs], jnp.int32
                )
                merged, packed = merge(arrs, counts)
            jax.block_until_ready(merged)
            for b in bufs:
                b.free()
            return packed  # tiny, stays on device

        def discard_group(stage, _item, value):
            # abort drain: host blocks release, device slabs free;
            # merge outputs (packed scalar rows) hold no resources
            if not value:
                return
            if stage in ("fetch", "decode"):
                for hb in value:
                    hb.release()
            elif stage == "stage":
                for b in value:
                    b.free()

        pipe = ReduceTaskPipeline(
            fetch_blocks, verify_blocks, stage_blocks, merge_group,
            parallelism=conf.reduce_parallelism,
            depth=conf.reduce_pipeline_depth,
            double_buffer=conf.reduce_double_buffer_staging,
            role="e2e-reduce",
            discard_fn=discard_group,
        )
        t_wall0 = time.perf_counter()
        # verification scalars stay on the device until every merge is
        # done, then come back in ONE batched readback
        reduce_report = pipe.run(range(reducers))
        packed_rows = reduce_report.results
        # ONE readback for all reducers: [count, sum, xor, sorted] rows
        t0 = time.perf_counter()
        stats = np.asarray(jax.device_get(jnp.stack(packed_rows)))
        t_readback = time.perf_counter() - t0
        for r in range(reducers):
            t, csum, cxor, ok = (int(x) for x in stats[r])
            if t != exp_cnt[r]:
                raise SystemExit(
                    f"E2E FAILED: reducer {r} count {t} != {exp_cnt[r]}"
                )
            if csum != int(exp_sum[r]) or cxor != int(exp_xor[r]):
                raise SystemExit(f"E2E FAILED: reducer {r} checksum mismatch")
            if not ok:
                raise SystemExit(f"E2E FAILED: reducer {r} output not sorted")
        reduce_wall = time.perf_counter() - t_wall0
        # only wall time counts toward the total; per-plane busy times
        # are informational (they overlap)
        phases["reduce_wall_s"] = reduce_wall
        rbusy = reduce_report.stage_busy_s
        t_fetch = rbusy["fetch"] + rbusy["stage"]
        t_merge = rbusy["merge"]
        extra_busy = {
            "fetch_stage_busy_s": round(t_fetch, 3),
            "framework_decode_busy_s": round(rbusy["decode"], 3),
            "device_merge_busy_s": round(t_merge, 3),
            "verify_readback_s": round(t_readback, 3),
            "overlap_saved_s": round(reduce_report.overlap_s, 3),
            "reduce_pipeline_overlap_saved_s": round(
                reduce_report.overlap_s, 3
            ),
        }
        # live observability counters (pool allocs, read-path split,
        # fetch histograms, HBM budget/spills) into the artifact
        metrics = reducer_io.metrics_snapshot()
    finally:
        for io in ios:
            io.stop()
        for ex in execs:
            ex.stop()
        driver.stop()

    total = sum(phases.values())
    # publish cost: the solo uncontended measurement scaled to all
    # executors (see above). Busy timers from the pipelined phase stay
    # in the table, labeled contended, for transparency.
    publish_uncontended = publish_solo * executors
    # reduce-side residual: wall not accounted to either plane's busy
    # clock or the batched verify readback (scheduling gaps, Python
    # orchestration)
    reduce_residual = max(
        phases["reduce_wall_s"]
        - extra_busy["fetch_stage_busy_s"]
        - extra_busy["framework_decode_busy_s"]
        - t_merge
        - extra_busy["verify_readback_s"],
        0.0,
    )
    attribution = {
        "compute_map_sort_busy_s": round(
            pipe_report.stage_busy_s["sort"], 3
        ),
        "framework_map_stage_busy_s": round(
            pipe_report.stage_busy_s["stage"], 3
        ),
        "framework_publish_uncontended_s": round(publish_uncontended, 3),
        "framework_publish_busy_s_contended": round(
            pipe_report.stage_busy_s["publish"], 3
        ),
        "map_pipeline_overlap_saved_s": round(pipe_report.overlap_s, 3),
        "framework_reduce_residual_s": round(reduce_residual, 3),
        "verify_readback_s": extra_busy["verify_readback_s"],
    }
    # the framework's OWN code (registration+publish+orchestration
    # residual): what the reference's plugin
    # adds over Spark's sort machinery — compare against
    # host_sort_baseline_s
    framework_attributable = publish_uncontended + reduce_residual
    report(
        "terasort_e2e", total,
        gb=round(n * 4 / (1 << 30), 3), transport=transport,
        reducers=reducers, executors=executors,
        host_sort_baseline_s=round(t_host, 3),
        vs_host_sort=round(t_host / total, 3),
        framework_attributable_s=round(framework_attributable, 3),
        attribution=attribution,
        map_sorter=("device" if use_device_sort else "host"),
        map_parallelism=conf.map_parallelism,
        reduce_parallelism=conf.reduce_parallelism,
        reduce_pipeline_depth=conf.reduce_pipeline_depth,
        reduce_double_buffer=conf.reduce_double_buffer_staging,
        compile_warm_s=round(phases_compile + map_compile_s, 3),
        verified="count+sum+xor+sorted (on-device)",
        metrics=metrics,
        **extra_busy,
        note=(
            "seconds is the measured wall: map+publish wall plus reduce "
            "wall, host->HBM staging included. framework_attributable_s "
            "is the framework's OWN code (uncontended publish + reduce "
            "orchestration residual — the role the "
            "reference's plugin plays over Spark's sort machinery); "
            "busy rows overlap and do not sum to the wall"
        ),
        **{k: round(v, 3) for k, v in phases.items()},
    )


def bench_device_terasort_skew(scale: float):
    """The adversarial TeraSort round (SURVEY §7.3(2)): zipf-skewed
    keys concentrate mass in a few range partitions, so the static
    bucket capacity overflows and the sorter retries with doubled
    capacity (terasort.py capacity doubling). This workload makes that
    strategy's cost a NUMBER next to the uniform round: extra
    executions + a recompile per new capacity (cached within the
    process and across runs via the persistent cache).

    Overflow requires E > 1 (at E=1 every key lands in the one bucket,
    which is sized to hold them all), so the workload runs only on a
    multi-device mesh; on one device it is skipped and records nothing.
    """
    import jax

    from sparkrdma_tpu.models import TeraSorter
    from sparkrdma_tpu.parallel.mesh import make_mesh

    if len(jax.devices()) == 1:
        print("terasort_device_skew: skipped, needs a multi-device mesh",
              file=sys.stderr, flush=True)
        return

    n = int((1 << 24) * scale * 20)
    rng = np.random.default_rng(0)
    # zipf ranks mapped into the uint32 key space: heavy mass lands in
    # the lowest-range partitions (~a>1.5 concentrates >70% of keys in
    # the first percent of the key space)
    ranks = rng.zipf(1.5, size=n)
    keys = ((ranks % (1 << 16)) * 65536 + rng.integers(0, 65536, n)).astype(
        np.uint32
    )
    sorter = TeraSorter(make_mesh())

    out = sorter.sort(keys)  # warm: compiles base capacity AND retries
    assert len(out) == n
    doublings_warm = max(
        0, int(np.log2(max(k[1] for k in sorter._step_cache)
                       / min(k[1] for k in sorter._step_cache)))
    ) if len(sorter._step_cache) > 1 else 0
    t0 = time.perf_counter()
    out = sorter.sort(keys)
    dt_static = time.perf_counter() - t0
    assert all(out[i] <= out[i + 1] for i in range(0, min(2000, n - 1)))

    # adaptive control: sampled quantile edges + sampled capacity
    # (shuffle/planner.py plan_edges) replace the overflow-retry ladder
    out_ad = sorter.sort(keys, adaptive=True)  # warm adaptive executable
    assert len(out_ad) == n
    t0 = time.perf_counter()
    out_ad = sorter.sort(keys, adaptive=True)
    dt = time.perf_counter() - t0
    assert all(out_ad[i] <= out_ad[i + 1] for i in range(0, min(2000, n - 1)))

    # uniform control at the same n, same process (executables warm)
    uni = rng.integers(0, 1 << 32, n, dtype=np.uint32)
    sorter.sort(uni)  # warm any uniform-shape executable
    t0 = time.perf_counter()
    sorter.sort(uni)
    dt_uni = time.perf_counter() - t0
    report(
        "terasort_device_skew", dt,
        keys=n, zipf_a=1.5,
        capacity_doublings=doublings_warm,
        uniform_control_s=round(dt_uni, 4),
        static_plan_s=round(dt_static, 4),
        skew_overhead_x=round(dt / dt_uni, 3) if dt_uni > 0 else None,
        skew_overhead_x_static=(
            round(dt_static / dt_uni, 3) if dt_uni > 0 else None
        ),
        devices=len(jax.devices()),
        note=(
            "primary timing = adaptive plan (sampled quantile edges, "
            "shuffle/planner.py) — one right-sized execution; "
            "skew_overhead_x_static = the pre-planner overflow-retry "
            "ladder at doubled bucket capacities (SURVEY §7.3(2))"
        ),
    )


def bench_transformer_train(scale: float):
    """Sharded transformer training throughput on one chip: K SGD
    steps (ulysses attention -> the Pallas flash kernel fwd + custom-
    VJP bwd) inside ONE executable, so the measurement is steady-state
    compute, not per-step dispatch. Needs a TPU: off it the flash
    kernel would run in the Pallas interpreter and time that."""
    import jax
    import jax.numpy as jnp

    if jax.devices()[0].platform != "tpu":
        raise SystemExit("transformer_train needs a TPU; none found")

    from sparkrdma_tpu.models.transformer_step import (
        TransformerStep,
        init_params,
        make_training_mesh,
    )

    mesh = make_training_mesh(jax.devices()[:1])
    heads, dhead = 8, 64
    d_model, d_hidden = heads * dhead, 4 * heads * dhead
    b = 4
    s = max(128, int(2048 * scale * 20))  # default scale 0.05 -> 2048
    params = init_params(d_model, n_heads=heads, d_hidden=d_hidden, tp=1)
    rng = np.random.default_rng(3)
    x = jnp.asarray(rng.normal(size=(b, s, d_model)).astype(np.float32))
    y = jnp.asarray(rng.normal(size=(b, s, d_model)).astype(np.float32))
    step = TransformerStep(mesh, n_heads=heads, lr=0.01, attn="ulysses")
    pl, xl, yl = step.place(params, x, y)

    def run(n):
        loss, _ = step.run_steps(pl, xl, yl, n)
        return float(loss)

    l1 = run(1)  # warm: compiles step + loop
    run(9)
    t0 = time.perf_counter()
    run(1)
    t1 = time.perf_counter() - t0
    t0 = time.perf_counter()
    lk = run(9)
    tk = time.perf_counter() - t0
    if tk > t1:
        per_step = (tk - t1) / 8  # dispatch cancelled by differencing
    else:
        # timing noise ate the difference: fall back to the dispatch-
        # inclusive per-step time (conservative underestimate of
        # throughput) rather than reporting nonsense
        per_step = tk / 9
    assert np.isfinite(lk) and lk <= l1 * 1.01, "training diverged"
    # attention (fwd 1x + bwd 2.5x) + mlp/proj matmul flops per step
    att = 4 * b * heads * s * s * dhead * 3.5
    mlp = 2 * b * s * (4 * d_model * d_model + 2 * d_model * d_hidden) * 3
    report(
        "transformer_train", tk,
        steps_per_s=round(1.0 / per_step, 2),
        step_ms=round(per_step * 1e3, 2),
        tflops_effective=round((att + mlp) / per_step / 1e12, 2),
        b=b, s=s, d_model=d_model, heads=heads, attn="ulysses+flash_vjp",
        final_loss=round(lk, 5),
    )


def bench_pagerank(scale: float):
    from sparkrdma_tpu.models import PageRank
    from sparkrdma_tpu.parallel.mesh import make_mesh

    n = int(20000 * scale * 20)
    m = n * 8
    rng = np.random.default_rng(0)
    edges = rng.integers(0, n, size=(m, 2), dtype=np.int64)
    pr = PageRank(make_mesh())
    pr.run(edges, n, iters=10)  # warm compile
    t0 = time.perf_counter()
    ranks = pr.run(edges, n, iters=10)
    dt = time.perf_counter() - t0
    assert abs(ranks.sum() - 1.0) < 1e-2
    report("pagerank", dt, vertices=n, edges=m, iters=10)


def bench_als(scale: float):
    from sparkrdma_tpu.models import ALS
    from sparkrdma_tpu.models.als import rmse
    from sparkrdma_tpu.parallel.mesh import make_mesh

    n_u = int(2000 * scale * 20)
    n_i = n_u // 2
    m = n_u * 10
    rng = np.random.default_rng(0)
    tu = rng.normal(size=(n_u, 4))
    tv = rng.normal(size=(n_i, 4))
    users = rng.integers(0, n_u, m)
    items = rng.integers(0, n_i, m)
    vals = (tu[users] * tv[items]).sum(1)
    ratings = np.stack([users, items, vals], 1)
    als = ALS(make_mesh(), rank=8)
    als.fit(ratings, n_u, n_i, iters=5)  # warm compile
    t0 = time.perf_counter()
    u, v = als.fit(ratings, n_u, n_i, iters=5)
    dt = time.perf_counter() - t0
    report("als", dt, users=n_u, items=n_i, ratings=m, rmse=round(rmse(u, v, ratings), 4))


def bench_hashjoin(scale: float):
    from sparkrdma_tpu.models import HashJoin
    from sparkrdma_tpu.parallel.mesh import make_mesh

    nb = int(10000 * scale * 20)
    npr = nb * 8
    rng = np.random.default_rng(0)
    bk = rng.choice(1 << 24, nb, replace=False).astype(np.uint32)
    bv = rng.integers(0, 1 << 20, nb).astype(np.int32)
    pk = rng.choice(bk, npr).astype(np.uint32)
    pv = np.arange(npr, dtype=np.int32)
    hj = HashJoin(make_mesh())
    hj.join(bk, bv, pk, pv)  # warm compile
    t0 = time.perf_counter()
    out = hj.join(bk, bv, pk, pv)
    dt = time.perf_counter() - t0
    assert len(out) == npr
    report("hashjoin", dt, build=nb, probe=npr, rows_per_s=int(npr / dt))


def bench_analytic_scan(scale: float):
    """Analytic column scan over shuffled blocks (DESIGN.md §25): the
    same typed record set staged through both block encodings, then one
    full-column aggregate (sum of the value column) consumed straight
    off the framed partition stream. The columnar side decodes via
    zero-copy ``np.frombuffer`` views and reduces vectorized; the
    pickle side must materialize every row tuple first — the decode
    delta IS the workload, so both scans run on one core and the row
    reports both times plus the speedup. Results are asserted equal."""
    import io

    from sparkrdma_tpu.engine.serializer import (
        CompressionCodec,
        PickleSerializer,
        frame_compressed,
        iter_compressed_blocks,
    )
    from sparkrdma_tpu.shuffle import columnar
    from sparkrdma_tpu.shuffle.writer.columnar import ColumnarPartitionWriter

    n = int(4_000_000 * scale * 20)
    rng = np.random.default_rng(0)
    keys = rng.integers(0, 1 << 32, n, dtype=np.uint32)
    vals = rng.integers(0, 1 << 30, n, dtype=np.int64)
    records = [(k, v) for k, v in zip(keys, vals)]
    logical_bytes = keys.nbytes + vals.nbytes
    codec = CompressionCodec(enabled=True)

    chunks = []
    cw = ColumnarPartitionWriter(codec, chunks.append, batch_rows=4096)
    for rec in records:
        cw.write_record(rec)
    cw.flush_batch()
    col_stream = b"".join(chunks)

    import pickle
    import struct

    pack = struct.Struct(">I").pack
    pkl_stream = bytearray()
    buf = bytearray()
    for rec in records:
        data = pickle.dumps(rec, protocol=pickle.HIGHEST_PROTOCOL)
        buf += pack(len(data))
        buf += data
        if len(buf) >= (256 << 10):
            pkl_stream += frame_compressed(codec, bytes(buf))
            buf.clear()
    if buf:
        pkl_stream += frame_compressed(codec, bytes(buf))

    t0 = time.perf_counter()
    col_sum = 0
    for block in iter_compressed_blocks(io.BytesIO(col_stream), codec):
        col_sum += int(columnar.decode_columns(block)[1].sum(dtype=np.int64))
    dt_col = time.perf_counter() - t0

    ser = PickleSerializer()
    t0 = time.perf_counter()
    pkl_sum = 0
    for block in iter_compressed_blocks(io.BytesIO(bytes(pkl_stream)), codec):
        pkl_sum += sum(int(r[1]) for r in ser.load_buffer(block))
    dt_pkl = time.perf_counter() - t0

    assert col_sum == pkl_sum == int(vals.sum(dtype=np.int64))
    report(
        "analytic_scan", dt_col,
        rows=n,
        logical_mb=round(logical_bytes / 1e6, 1),
        columnar_scan_gbps=round(logical_bytes / dt_col / 1e9, 4),
        pickle_scan_gbps=round(logical_bytes / dt_pkl / 1e9, 4),
        pickle_seconds=round(dt_pkl, 4),
        scan_speedup=round(dt_pkl / dt_col, 2) if dt_col else None,
    )


if __name__ == "__main__":
    from sparkrdma_tpu.utils.compile_cache import enable_compile_cache

    enable_compile_cache()
    ap = argparse.ArgumentParser()
    ap.add_argument("--scale", type=float, default=0.05)
    ap.add_argument("--transport", default="python", choices=["python", "native"])
    ap.add_argument(
        "--only", default=None,
        choices=[None, "engine", "terasort", "skew", "e2e", "train",
                 "pagerank", "als", "join", "scan"],
    )
    ap.add_argument(
        "--e2e-gb", type=float, default=0.0,
        help="run the full-stack end-to-end TeraSort at this many GiB",
    )
    ap.add_argument(
        "--out", default=None,
        help="also write every record to this JSON artifact file",
    )
    ap.add_argument(
        "--trace-out", default=None,
        help="export the shuffle span trace (Chrome trace-event JSON, "
        "Perfetto-loadable) to this path; defaults to <out>.trace.json "
        "when --out is given",
    )
    args = ap.parse_args()
    runs = {
        "engine": lambda: bench_engine_terasort(args.scale, args.transport),
        "terasort": lambda: bench_device_terasort(args.scale),
        "skew": lambda: bench_device_terasort_skew(args.scale),
        "train": lambda: bench_transformer_train(args.scale),
        "pagerank": lambda: bench_pagerank(args.scale),
        "als": lambda: bench_als(args.scale),
        "join": lambda: bench_hashjoin(args.scale),
        "scan": lambda: bench_analytic_scan(args.scale),
    }
    if args.only == "e2e" and args.e2e_gb <= 0:
        ap.error("--only e2e requires --e2e-gb > 0")
    if args.e2e_gb > 0:
        runs["e2e"] = lambda: bench_e2e_terasort(args.e2e_gb, args.transport)

    from sparkrdma_tpu.obs import export_chrome_trace, get_registry
    from sparkrdma_tpu.obs.telemetry import Heartbeater, TelemetryHub

    # time-resolved telemetry across the whole run: the artifact gets a
    # timeline + straggler report, not just the end-state registry
    hub = TelemetryHub(role="workloads", interval_ms=500)
    heartbeater = Heartbeater(
        get_registry(), "workloads-proc", interval_ms=500, send=hub.ingest
    ).start()

    for name, fn in runs.items():
        if args.only in (None, name):
            fn()
    heartbeater.stop(flush=True)

    trace_out = args.trace_out or (f"{args.out}.trace.json" if args.out else None)
    if trace_out:
        trace = export_chrome_trace(trace_out)
        print(
            f"wrote {trace_out} ({len(trace['traceEvents'])} trace events)",
            flush=True,
        )
    if args.out:
        with open(args.out, "w") as f:
            json.dump(
                {
                    "generated_unix": int(time.time()),
                    "scale": args.scale,
                    "transport": args.transport,
                    "e2e_gb": args.e2e_gb,
                    "workloads": RECORDS,
                    "obs_registry": get_registry().snapshot(),
                    # last per-job critical-path verdict, if a workload
                    # produced one (obs --critical-path reads this)
                    "breakdown": next(
                        (r.get("breakdown") for r in reversed(RECORDS)
                         if r.get("breakdown")),
                        None,
                    ),
                    "trace_file": trace_out,
                    "telemetry_timeline": hub.timeline(),
                    "stragglers": hub.straggler_report(),
                },
                f, indent=1,
            )
            f.write("\n")
        print(f"wrote {args.out} ({len(RECORDS)} workloads)", flush=True)
    hub.stop()
