"""Benchmark: the framework's measured planes, one JSON line.

The reference's only published number is HiBench TeraSort 1.41x over
stock Spark sort shuffle on 100 GbE RoCE — won by replacing the
*transport* under Spark's unchanged sort machinery
(/root/reference/README.md:7-19, BASELINE.md). This bench measures the
same planes of this framework on one chip + one host:

- ``value`` / north star: **shuffle-read GB/s per chip** through the
  native one-sided READ plane (same-host pread fast path — the
  reference hot-path shape: 8 MiB read groups from registered memory,
  RdmaChannel.java:360-393 + RdmaMappedFile.java:135-209).
  ``vs_baseline`` divides by 12.5 GB/s, the 100 GbE wire-rate
  operating point the reference tuned against (BASELINE.md).
  ``pread_roofline_gbps`` is the MACHINE's limit for this path —
  raw single-core page-cache pread into the same rotating
  destination set, measured in-process — so the headline is
  interpretable: on this 1-core box the transport saturates it
  (~4 GB/s ≈ 100% of roofline; a naive single-dst probe reads ~70%
  high because the destination stays cache-resident).
- ``native_read_streamed_gbps``: the same READ path when the region is
  anonymous (no file backing), so every byte moves through the socket
  streaming plane. ``native_read_streamed_sendfile_gbps`` is the
  file-backed variant served by kernel ``sendfile`` (forced on for the
  bench: loopback peers normally keep the userspace send, which
  measures ~18% faster on this rig; sendfile is for real NICs).
- **fetch-to-CONSUMED planes** — where beating the copy roofline is
  physically possible: ``native_read_samehost_consumed_pread_gbps``
  (pread into a buffer, then one consume pass: 2 passes/byte) vs
  ``native_read_mapped_consumed_gbps`` (mapped zero-copy delivery with
  MAP_POPULATE prefaulting: the consume pass IS the first touch —
  1 pass/byte), both against ``consume_roofline_gbps`` (delivery
  assumed free). ``native_read_samehost_consumed_gbps`` reports the
  DEFAULT consume path — the mapped plane (conf mappedFetch=true on
  capable channels). Measured: mapped ≈ 1.4x the pread path at ≈ 90%
  of the roofline; ``ab_consume_mapped`` pins the delta with
  interleaved same-run pairs.
- ``pread_roofline_2thr_gbps``: 2-way threaded pread of the same
  volume. On this nproc=1 box it still measures ~1.4x one thread
  (kernel-side parallelism exists), but the gain does NOT survive the
  full stack (per-block control overheads serialize on the loop
  threads) — recorded so the striping story is numbers, not lore.
- ``device_sort_gbps`` + ``terasort_speedup_vs_host_sort``: the jitted
  TeraSort step, whose hot path is ``ops/sort.device_sort`` —
  ``lax.sort``, the measured optimum for this chip (evidence:
  benchmarks/sort_study.py, DESIGN.md §6; rounds 1-3 assumed a faster
  decomposition existed, round 4 measured that none does). Output is
  verified against the host sort in-loop.
- ``flash_attn_tflops``: the Pallas flash kernel, causal bf16
  B4 S2048 H8 D128 with measured 1024x1024 blocks, against XLA's
  materialized-scores attention timed identically in the same process
  (``flash_vs_xla_dense``). ``flash_train_tflops`` adds the custom
  VJP (blockwise dq / dkdv kernels): one full forward+backward per
  step, so long-context training runs at flash memory cost.
- ``ab_samehost_fileworkers`` / ``ab_streamed_connections``:
  interleaved SAME-RUN striped-vs-unstriped A/B pairs (fileWorkers
  1 vs N on the pread plane; 1 vs M data connections on the streamed
  plane) — per-pair ratios are immune to the run-to-run rig drift that
  made cross-round striping comparisons lore.
- ``flash_attn_mfu`` / ``flash_train_mfu``: the measured TFLOPs over
  the chip's dense bf16 peak (small public-spec table keyed on
  ``device_kind``; null off-TPU rather than a made-up peak).
- ``exchange_loopback_gbps``: the resident ExchangeProgram executable
  on the single-device mesh. Labeled loopback: at E=1 the collective
  degenerates to an on-device pass, so this bounds program overhead;
  multi-device exchange runs on chips in ``chip_smoke.py --chips 4``.

The device section needs a TPU and fails without one. Device compute
is timed as K data-dependent steps chained inside ONE jitted program,
differenced against a shorter chain, with a scalar readback. Host->HBM
staging is part of the served path; ``chip_smoke.py`` times it inside
the full-stack TeraSort.

Prints ONE JSON line: {"metric", "value", "unit", "vs_baseline", ...}.
"""

from __future__ import annotations

import json
import threading
import time
from functools import partial

import numpy as np

WIRE_RATE_GBPS = 12.5  # 100 GbE operating point (BASELINE.md)
N_KEYS = 1 << 25       # 32M uint32 keys = 128 MiB
READ_BLOCK = 8 << 20   # reference shuffleReadBlockSize default
READ_REGION = 64 << 20
READ_TOTAL = 1 << 30


# ---------------------------------------------------------------------------
# host plane: native one-sided READ bandwidth
# ---------------------------------------------------------------------------

def bench_native_reads() -> dict:
    from sparkrdma_tpu.memory.buffer import TpuBuffer
    from sparkrdma_tpu.transport import FnListener
    from sparkrdma_tpu.transport.native_node import NativeTpuNode
    from sparkrdma_tpu.utils.config import TpuShuffleConf

    conf = TpuShuffleConf()
    srv = NativeTpuNode(conf, "127.0.0.1", False, "bench-srv")
    cli = NativeTpuNode(conf, "127.0.0.1", True, "bench-cli")
    out = {}
    try:
        rng = np.random.default_rng(7)
        ch = cli.get_channel("127.0.0.1", srv.port)
        n_blocks = READ_REGION // READ_BLOCK
        rounds = READ_TOTAL // READ_REGION
        dsts = [memoryview(bytearray(READ_BLOCK)) for _ in range(n_blocks)]

        def one_round(mkey, label, c=None):
            c = c or ch
            evs = []
            errs = []
            for i in range(n_blocks):
                ev = threading.Event()

                def fail(e, ev=ev):
                    errs.append(e)
                    ev.set()

                c.read_in_queue(
                    FnListener(lambda _, ev=ev: ev.set(), fail),
                    [dsts[i]], [(mkey, i * READ_BLOCK, READ_BLOCK)],
                )
                evs.append(ev)
            for ev in evs:
                assert ev.wait(120), f"{label} read timed out"
            if errs:
                raise SystemExit(f"BENCH FAILED: {label} READ error: {errs[0]}")

        def pull(mkey, label, channel=None, consume=False):
            c = channel or ch
            one_round(mkey, label, c)  # warm: connection, fd + page cache
            sink = 0
            t0 = time.perf_counter()
            for _ in range(rounds):
                one_round(mkey, label, c)
                if consume:
                    for d in dsts:
                        sink += int(
                            np.add.reduce(
                                np.frombuffer(d, np.uint8), dtype=np.int64
                            )
                        )
            gbps = READ_TOTAL / (time.perf_counter() - t0) / 1e9
            return (gbps, sink) if consume else gbps

        def pull_mapped_consumed(mkey, channel):
            """Mapped delivery + one consume pass per block: the
            fetch-to-consumed number for the zero-copy plane. The
            consume (a full-speed sum over the mapping) is the FIRST
            touch of those page-cache pages in userspace — the pread
            plane pays the same pass PLUS its copy first."""
            def one_mapped_round():
                evs, deliveries, errs = [], [None] * n_blocks, []
                for i in range(n_blocks):
                    ev = threading.Event()

                    def ok(d, i=i, ev=ev):
                        deliveries[i] = d
                        ev.set()

                    def fail(e, ev=ev):
                        errs.append(e)
                        ev.set()

                    channel.read_mapped_in_queue(
                        FnListener(ok, fail),
                        [(mkey, i * READ_BLOCK, READ_BLOCK)],
                    )
                    evs.append(ev)
                sink = 0
                for i, ev in enumerate(evs):
                    assert ev.wait(120), "mapped read timed out"
                    if errs:
                        raise SystemExit(f"BENCH FAILED: mapped READ: {errs[0]}")
                    d = deliveries[i]
                    sink += int(
                        np.add.reduce(
                            np.frombuffer(d.views[0], np.uint8), dtype=np.int64
                        )
                    )
                    d.release()
                return sink

            one_mapped_round()  # warm
            sink = 0
            t0 = time.perf_counter()
            for _ in range(rounds):
                sink += one_mapped_round()
            return READ_TOTAL / (time.perf_counter() - t0) / 1e9, sink

        # machine roofline for the fast path: raw page-cache pread into
        # the SAME rotating destination set (cache-honest: a single
        # reused dst stays L3-resident and reads ~70% too fast)
        import os
        import tempfile

        with tempfile.NamedTemporaryFile(dir="/dev/shm") as f:
            f.write(rng.integers(0, 256, READ_REGION, dtype=np.uint8).tobytes())
            f.flush()
            rfd = f.fileno()
            for i in range(n_blocks):
                os.preadv(rfd, [dsts[i]], i * READ_BLOCK)
            t0 = time.perf_counter()
            moved = 0
            for _ in range(rounds):
                for i in range(n_blocks):
                    moved += os.preadv(rfd, [dsts[i]], i * READ_BLOCK)
            out["pread_roofline_gbps"] = round(
                moved / (time.perf_counter() - t0) / 1e9, 3
            )

            # striping non-lever evidence: the reference stripes READs
            # over multiple QPs because NIC/core parallelism exists;
            # this box has ONE core, so 2-way threaded pread of the
            # same volume cannot beat the single-thread roofline —
            # measured here so the design choice (kill copies, don't
            # stripe) is a number, not an assertion
            from concurrent.futures import ThreadPoolExecutor

            def half(lo, hi):
                m = 0
                for _ in range(rounds):
                    for i in range(lo, hi):
                        m += os.preadv(rfd, [dsts[i]], i * READ_BLOCK)
                return m

            with ThreadPoolExecutor(2) as pool:
                t0 = time.perf_counter()
                futs = [
                    pool.submit(half, 0, n_blocks // 2),
                    pool.submit(half, n_blocks // 2, n_blocks),
                ]
                moved = sum(f.result() for f in futs)
                out["pread_roofline_2thr_gbps"] = round(
                    moved / (time.perf_counter() - t0) / 1e9, 3
                )

        # same-host fast path: shm-backed registered slab (pread plane)
        buf = TpuBuffer(srv.pd, READ_REGION, register=True)
        src = rng.integers(0, 256, size=READ_REGION, dtype=np.uint8)
        np.frombuffer(buf.view, dtype=np.uint8)[:] = src
        gbps = pull(buf.mkey, "samehost")
        if not np.array_equal(np.frombuffer(dsts[1], np.uint8),
                              src[READ_BLOCK: 2 * READ_BLOCK]):
            raise SystemExit("BENCH FAILED: samehost READ bytes differ")
        fast, _ = cli.read_path_stats()
        if fast == 0:
            raise SystemExit("BENCH FAILED: samehost reads never took fast path")
        out["native_read_samehost_gbps"] = round(gbps, 3)

        # fetch-to-CONSUMED comparison on the same region: the pread
        # plane copies into a buffer the consumer then reads (2 passes
        # per byte); mapped delivery hands the consumer the page-cache
        # pages themselves (1 pass). Same consume (full-speed uint8
        # sum) both sides, so the delta is pure delivery cost — this is
        # where "beat your own roofline" is physically possible on a
        # 1-core box: not by copying faster, but by not copying.
        want_sum = int(np.add.reduce(src, dtype=np.int64)) * rounds
        gbps_c, sink = pull(buf.mkey, "samehost+consume", consume=True)
        if sink != want_sum:
            raise SystemExit("BENCH FAILED: consumed pread sum differs")
        out["native_read_samehost_consumed_pread_gbps"] = round(gbps_c, 3)
        gbps_m, sink_m = pull_mapped_consumed(buf.mkey, ch)
        if sink_m != want_sum:
            raise SystemExit("BENCH FAILED: consumed mapped sum differs")
        out["native_read_mapped_consumed_gbps"] = round(gbps_m, 3)
        # the headline consumed number reports the DEFAULT consume path:
        # mapped zero-copy delivery (conf mappedFetch=true, the record
        # and device fetchers both select it on capable channels) with
        # MAP_POPULATE prefaulting on the file worker. One pass per
        # byte instead of copy+pass — the only shape that can approach
        # the consume roofline on a 1-core box. The pread plane's
        # number stays above as *_consumed_pread_gbps.
        out["native_read_samehost_consumed_gbps"] = round(gbps_m, 3)
        # this comparison's machine limit: ONE touch pass per byte over
        # the same rotating set (delivery assumed free)
        for d in dsts:
            np.add.reduce(np.frombuffer(d, np.uint8), dtype=np.int64)
        t0 = time.perf_counter()
        moved = 0
        for _ in range(rounds):
            for d in dsts:
                np.add.reduce(np.frombuffer(d, np.uint8), dtype=np.int64)
                moved += READ_BLOCK
        out["consume_roofline_gbps"] = round(
            moved / (time.perf_counter() - t0) / 1e9, 3
        )

        # streamed plane with the SAME file-backed region: a client
        # with fileFastPath=false simulates a remote peer, the server
        # serves via sendfile (kernel zero-copy; one userspace copy per
        # byte total vs the plain socket plane's two)
        # second server with forceSendfile (loopback peers would
        # otherwise get the faster-on-this-rig userspace send)
        conf_sf = TpuShuffleConf({"tpu.shuffle.fileFastPath": "false"})
        srv_sf = NativeTpuNode(
            TpuShuffleConf({"tpu.shuffle.forceSendfile": "true"}),
            "127.0.0.1", False, "bench-srv-sf",
        )
        cli_sf = NativeTpuNode(conf_sf, "127.0.0.1", True, "bench-cli-sf")
        try:
            buf_sf = TpuBuffer(srv_sf.pd, READ_REGION, register=True)
            np.frombuffer(buf_sf.view, dtype=np.uint8)[:] = src
            ch_sf = cli_sf.get_channel("127.0.0.1", srv_sf.port)
            gbps = pull(buf_sf.mkey, "streamed-sendfile", channel=ch_sf)
            if not np.array_equal(np.frombuffer(dsts[2], np.uint8),
                                  src[2 * READ_BLOCK: 3 * READ_BLOCK]):
                raise SystemExit("BENCH FAILED: sendfile READ bytes differ")
            f_sf, s_sf = cli_sf.read_path_stats()
            if f_sf != 0 or s_sf == 0:
                raise SystemExit("BENCH FAILED: sendfile pull not streamed")
            out["native_read_streamed_sendfile_gbps"] = round(gbps, 3)
        finally:
            cli_sf.stop()
            srv_sf.stop()
        buf.free()

        # streamed plane: anonymous region -> socket streaming path
        anon = rng.integers(0, 256, size=READ_REGION, dtype=np.uint8)
        mkey2 = srv.pd.register(memoryview(anon.data))
        gbps = pull(mkey2, "streamed")
        if not np.array_equal(np.frombuffer(dsts[1], np.uint8),
                              anon[READ_BLOCK: 2 * READ_BLOCK]):
            raise SystemExit("BENCH FAILED: streamed READ bytes differ")
        out["native_read_streamed_gbps"] = round(gbps, 3)

        # this plane's machine limit: raw single-core loopback socket
        # (8 MiB sends, rotating destination set, same rig)
        out["socket_roofline_gbps"] = _socket_roofline()
        # ...and the sendfile plane's: kernel-side file->socket moves,
        # userspace only on the receive side
        out["sendfile_roofline_gbps"] = _sendfile_roofline()
    finally:
        cli.stop()
        srv.stop()
    return out


def bench_consume_pipelined_ab() -> dict:
    """Interleaved serial-vs-pipelined consume A/B pairs, SAME run.

    BENCH_r05 pinned the reduce-side loss: same-host native READ
    sustains ~4 GB/s raw but only ~1.5 GB/s fetch-to-CONSUMED against a
    ~2.4 GB/s consume roofline — the READ wait and the consume pass ran
    strictly in sequence. The reduce pipeline's lever (DESIGN.md §16)
    is to keep the next group's READs in flight under the current
    group's consume; this A/B isolates exactly that on the same-host
    pread plane. The A side is today's serial loop (the
    ``native_read_samehost_consumed_gbps`` shape: read a region, then
    sum it). The B side double-buffers two destination sets: round
    k+1's preads (C++ file workers — the GIL is released) land while
    round k is consumed (``np.add.reduce`` — also GIL-free), same total
    volume and the same consume pass per byte. Same interleaved-pair
    methodology as :func:`bench_striping_ab`, so per-pair ratios are
    drift-immune; both sides verify the summed payload."""
    from sparkrdma_tpu.memory.buffer import TpuBuffer
    from sparkrdma_tpu.transport import FnListener
    from sparkrdma_tpu.transport.native_node import NativeTpuNode
    from sparkrdma_tpu.utils.config import TpuShuffleConf

    out = {}
    rng = np.random.default_rng(13)
    srv = NativeTpuNode(TpuShuffleConf(), "127.0.0.1", False, "cab-srv")
    cli = NativeTpuNode(TpuShuffleConf(), "127.0.0.1", True, "cab-cli")
    n_blocks = READ_REGION // READ_BLOCK
    N_PAIRS = 3
    ROUNDS_PER_SIDE = 4
    dsts_a = [memoryview(bytearray(READ_BLOCK)) for _ in range(n_blocks)]
    dsts_b = [memoryview(bytearray(READ_BLOCK)) for _ in range(n_blocks)]
    try:
        ch = cli.get_channel("127.0.0.1", srv.port)
        src = rng.integers(0, 256, size=READ_REGION, dtype=np.uint8)
        buf = TpuBuffer(srv.pd, READ_REGION, register=True)
        np.frombuffer(buf.view, dtype=np.uint8)[:] = src
        want_round = int(np.add.reduce(src, dtype=np.int64))

        def issue(dsts):
            evs, errs = [], []
            for i in range(n_blocks):
                ev = threading.Event()

                def fail(e, ev=ev):
                    errs.append(e)
                    ev.set()

                ch.read_in_queue(
                    FnListener(lambda _, ev=ev: ev.set(), fail),
                    [dsts[i]], [(buf.mkey, i * READ_BLOCK, READ_BLOCK)],
                )
                evs.append(ev)
            return evs, errs

        def wait(evs, errs):
            for ev in evs:
                assert ev.wait(120), "consume A/B read timed out"
            if errs:
                raise SystemExit(
                    f"BENCH FAILED: consume A/B READ error: {errs[0]}"
                )

        def consume(dsts):
            s = 0
            for d in dsts:
                s += int(
                    np.add.reduce(np.frombuffer(d, np.uint8), dtype=np.int64)
                )
            return s

        def serial_side():
            sink = 0
            t0 = time.perf_counter()
            for _ in range(ROUNDS_PER_SIDE):
                wait(*issue(dsts_a))
                sink += consume(dsts_a)
            dt = time.perf_counter() - t0
            return ROUNDS_PER_SIDE * READ_REGION / dt / 1e9, sink

        def pipelined_side():
            sink = 0
            t0 = time.perf_counter()
            pend = issue(dsts_a)
            cur, nxt = dsts_a, dsts_b
            for r in range(ROUNDS_PER_SIDE):
                wait(*pend)
                if r + 1 < ROUNDS_PER_SIDE:
                    pend = issue(nxt)
                sink += consume(cur)
                cur, nxt = nxt, cur
            dt = time.perf_counter() - t0
            return ROUNDS_PER_SIDE * READ_REGION / dt / 1e9, sink

        # warm: connection, fd + page cache, BOTH destination sets
        # faulted in (the B side must not pay first-touch the A side
        # already paid)
        wait(*issue(dsts_a))
        wait(*issue(dsts_b))
        fast, _ = cli.read_path_stats()
        if fast == 0:
            raise SystemExit(
                "BENCH FAILED: consume A/B never took the fast path"
            )
        pairs = []
        for _ in range(N_PAIRS):
            a, sink_a = serial_side()
            b, sink_b = pipelined_side()
            if (sink_a != want_round * ROUNDS_PER_SIDE
                    or sink_b != want_round * ROUNDS_PER_SIDE):
                raise SystemExit("BENCH FAILED: consume A/B sums differ")
            pairs.append(
                {"serial_gbps": round(a, 3), "pipelined_gbps": round(b, 3)}
            )
        med_a = float(np.median([p["serial_gbps"] for p in pairs]))
        med_b = float(np.median([p["pipelined_gbps"] for p in pairs]))
        out["ab_consume_pipelined"] = {
            "pairs": pairs,
            "native_read_samehost_consumed_gbps": round(med_a, 3),
            "native_read_samehost_consumed_pipelined_gbps": round(med_b, 3),
            "pipelined_speedup": round(med_b / med_a, 3) if med_a else None,
        }
        buf.free()
    finally:
        cli.stop()
        srv.stop()
    return out


def bench_consume_mapped_ab() -> dict:
    """Interleaved pread-vs-mapped consume A/B pairs, SAME run.

    The consume-path ceiling satellite: the pread plane pays two passes
    per byte (page cache -> destination buffer, then the consumer's
    sum) and is structurally capped below the one-pass consume
    roofline; mapped delivery hands the consumer the MAP_POPULATE-
    prefaulted page-cache pages themselves. This A/B pins the delta
    with drift-immune interleaved pairs: the A side is the pread
    consume loop, the B side the mapped consume loop, same volume, same
    full-speed uint8 sum per byte, sums verified both sides. B is the
    DEFAULT fetch shape (conf mappedFetch=true on capable channels) —
    the top-level ``native_read_samehost_consumed_gbps`` reports it."""
    from sparkrdma_tpu.memory.buffer import TpuBuffer
    from sparkrdma_tpu.transport import FnListener
    from sparkrdma_tpu.transport.native_node import NativeTpuNode
    from sparkrdma_tpu.utils.config import TpuShuffleConf

    out = {}
    rng = np.random.default_rng(17)
    srv = NativeTpuNode(TpuShuffleConf(), "127.0.0.1", False, "cmab-srv")
    cli = NativeTpuNode(TpuShuffleConf(), "127.0.0.1", True, "cmab-cli")
    n_blocks = READ_REGION // READ_BLOCK
    N_PAIRS = 3
    ROUNDS_PER_SIDE = 4
    dsts = [memoryview(bytearray(READ_BLOCK)) for _ in range(n_blocks)]
    try:
        ch = cli.get_channel("127.0.0.1", srv.port)
        src = rng.integers(0, 256, size=READ_REGION, dtype=np.uint8)
        buf = TpuBuffer(srv.pd, READ_REGION, register=True)
        np.frombuffer(buf.view, dtype=np.uint8)[:] = src
        want_round = int(np.add.reduce(src, dtype=np.int64))

        def pread_round():
            evs, errs = [], []
            for i in range(n_blocks):
                ev = threading.Event()

                def fail(e, ev=ev):
                    errs.append(e)
                    ev.set()

                ch.read_in_queue(
                    FnListener(lambda _, ev=ev: ev.set(), fail),
                    [dsts[i]], [(buf.mkey, i * READ_BLOCK, READ_BLOCK)],
                )
                evs.append(ev)
            for ev in evs:
                assert ev.wait(120), "mapped A/B pread timed out"
            if errs:
                raise SystemExit(
                    f"BENCH FAILED: mapped A/B READ error: {errs[0]}"
                )
            s = 0
            for d in dsts:
                s += int(
                    np.add.reduce(np.frombuffer(d, np.uint8), dtype=np.int64)
                )
            return s

        def mapped_round():
            evs, deliveries, errs = [], [None] * n_blocks, []
            for i in range(n_blocks):
                ev = threading.Event()

                def ok(d, i=i, ev=ev):
                    deliveries[i] = d
                    ev.set()

                def fail(e, ev=ev):
                    errs.append(e)
                    ev.set()

                ch.read_mapped_in_queue(
                    FnListener(ok, fail),
                    [(buf.mkey, i * READ_BLOCK, READ_BLOCK)],
                )
                evs.append(ev)
            s = 0
            for i, ev in enumerate(evs):
                assert ev.wait(120), "mapped A/B mapped read timed out"
                if errs:
                    raise SystemExit(
                        f"BENCH FAILED: mapped A/B mapped READ: {errs[0]}"
                    )
                d = deliveries[i]
                s += int(
                    np.add.reduce(
                        np.frombuffer(d.views[0], np.uint8), dtype=np.int64
                    )
                )
                d.release()
            return s

        def side(round_fn):
            sink = 0
            t0 = time.perf_counter()
            for _ in range(ROUNDS_PER_SIDE):
                sink += round_fn()
            dt = time.perf_counter() - t0
            return ROUNDS_PER_SIDE * READ_REGION / dt / 1e9, sink

        # warm both planes: connection, fds, page cache, dst faults
        pread_round()
        mapped_round()
        pairs = []
        for _ in range(N_PAIRS):
            a, sink_a = side(pread_round)
            b, sink_b = side(mapped_round)
            if (sink_a != want_round * ROUNDS_PER_SIDE
                    or sink_b != want_round * ROUNDS_PER_SIDE):
                raise SystemExit("BENCH FAILED: mapped A/B sums differ")
            pairs.append(
                {"pread_gbps": round(a, 3), "mapped_gbps": round(b, 3)}
            )
        med_a = float(np.median([p["pread_gbps"] for p in pairs]))
        med_b = float(np.median([p["mapped_gbps"] for p in pairs]))
        out["ab_consume_mapped"] = {
            "pairs": pairs,
            "pread_consumed_gbps": round(med_a, 3),
            "mapped_consumed_gbps": round(med_b, 3),
            "mapped_speedup": round(med_b / med_a, 3) if med_a else None,
        }
        buf.free()
    finally:
        cli.stop()
        srv.stop()
    return out


def bench_striping_ab() -> dict:
    """Interleaved striped-vs-unstriped A/B pairs, SAME run.

    The reference stripes READs over multiple QPs (RdmaChannel.java
    rdma_channel_conn_count); this rig's counterpart levers are the
    same-host file-worker pool (conf ``fileWorkers``) and multiple data
    connections on the streamed plane. Round-over-round numbers from
    DIFFERENT runs can't separate striping from rig drift, so each pair
    here interleaves A (unstriped) and B (striped) back to back against
    the SAME server region — per-pair ratios are drift-immune. Both
    clients/channel sets stay alive across all pairs (workers never
    shrink; connections are cached), so warm-up cost lands before the
    first pair, not inside one side of it."""
    from sparkrdma_tpu.memory.buffer import TpuBuffer
    from sparkrdma_tpu.transport import FnListener
    from sparkrdma_tpu.transport.native_node import NativeTpuNode
    from sparkrdma_tpu.utils.config import TpuShuffleConf

    out = {}
    rng = np.random.default_rng(11)
    srv = NativeTpuNode(TpuShuffleConf(), "127.0.0.1", False, "ab-srv")
    n_blocks = READ_REGION // READ_BLOCK
    dsts = [memoryview(bytearray(READ_BLOCK)) for _ in range(n_blocks)]
    N_PAIRS = 3
    ROUNDS_PER_SIDE = 4

    def one_round(channels, mkey, label):
        # round-robin the region's blocks over the channel set (one
        # entry = unstriped; M entries = striped across M connections)
        evs, errs = [], []
        for i in range(n_blocks):
            ev = threading.Event()

            def fail(e, ev=ev):
                errs.append(e)
                ev.set()

            channels[i % len(channels)].read_in_queue(
                FnListener(lambda _, ev=ev: ev.set(), fail),
                [dsts[i]], [(mkey, i * READ_BLOCK, READ_BLOCK)],
            )
            evs.append(ev)
        for ev in evs:
            assert ev.wait(120), f"{label}: A/B read timed out"
        if errs:
            raise SystemExit(f"BENCH FAILED: {label} READ error: {errs[0]}")

    def timed_side(channels, mkey, label):
        t0 = time.perf_counter()
        for _ in range(ROUNDS_PER_SIDE):
            one_round(channels, mkey, label)
        dt = time.perf_counter() - t0
        return ROUNDS_PER_SIDE * READ_REGION / dt / 1e9

    def run_pairs(ch_a, ch_b, mkey, label):
        pairs = []
        for _ in range(N_PAIRS):
            a = timed_side(ch_a, mkey, label)
            b = timed_side(ch_b, mkey, label)
            pairs.append(
                {"unstriped_gbps": round(a, 3), "striped_gbps": round(b, 3)}
            )
        med_a = float(np.median([p["unstriped_gbps"] for p in pairs]))
        med_b = float(np.median([p["striped_gbps"] for p in pairs]))
        return {
            "pairs": pairs,
            "unstriped_gbps": round(med_a, 3),
            "striped_gbps": round(med_b, 3),
            "striped_speedup": round(med_b / med_a, 3) if med_a else None,
        }

    clients = []
    try:
        src = rng.integers(0, 256, size=READ_REGION, dtype=np.uint8)
        buf = TpuBuffer(srv.pd, READ_REGION, register=True)
        np.frombuffer(buf.view, dtype=np.uint8)[:] = src

        # --- pair set 1: same-host pread plane, fileWorkers 1 vs N ----
        conf_s = TpuShuffleConf()  # shipped default worker count
        cli_u = NativeTpuNode(
            TpuShuffleConf({"tpu.shuffle.fileWorkers": "1"}),
            "127.0.0.1", True, "ab-cli-unstriped",
        )
        cli_s = NativeTpuNode(conf_s, "127.0.0.1", True, "ab-cli-striped")
        clients += [cli_u, cli_s]
        ch_u = [cli_u.get_channel("127.0.0.1", srv.port)]
        ch_s = [cli_s.get_channel("127.0.0.1", srv.port)]
        one_round(ch_u, buf.mkey, "samehost-warm")
        one_round(ch_s, buf.mkey, "samehost-warm")
        if not np.array_equal(np.frombuffer(dsts[1], np.uint8),
                              src[READ_BLOCK: 2 * READ_BLOCK]):
            raise SystemExit("BENCH FAILED: A/B samehost READ bytes differ")
        res = run_pairs(ch_u, ch_s, buf.mkey, "samehost")
        res["striped_workers"] = conf_s.file_workers
        out["ab_samehost_fileworkers"] = res

        # --- pair set 2: streamed plane, 1 vs M data connections ------
        # fileFastPath=false makes the loopback client behave like a
        # remote peer: every block rides a socket, so connection count
        # is the striping lever (purpose-distinct channels are distinct
        # connections in the native plane's channel cache)
        M = 4
        cli_r = NativeTpuNode(
            TpuShuffleConf({"tpu.shuffle.fileFastPath": "false"}),
            "127.0.0.1", True, "ab-cli-streamed",
        )
        clients.append(cli_r)
        ch_many = [
            cli_r.get_channel("127.0.0.1", srv.port, purpose=f"data-{j}")
            for j in range(M)
        ]
        ch_one = ch_many[:1]
        one_round(ch_many, buf.mkey, "streamed-warm")
        fast, streamed = cli_r.read_path_stats()
        if fast != 0 or streamed == 0:
            raise SystemExit("BENCH FAILED: A/B streamed pull not streamed")
        if not np.array_equal(np.frombuffer(dsts[1], np.uint8),
                              src[READ_BLOCK: 2 * READ_BLOCK]):
            raise SystemExit("BENCH FAILED: A/B streamed READ bytes differ")
        res = run_pairs(ch_one, ch_many, buf.mkey, "streamed")
        res["striped_connections"] = M
        out["ab_streamed_connections"] = res
        buf.free()
    finally:
        for c in clients:
            c.stop()
        srv.stop()
    return out


def bench_iouring_read_ab(dry_run: bool = False) -> dict:
    """Interleaved pread-vs-io_uring backend A/B pairs, SAME run.

    The submission plane (DESIGN.md §24) lets the same-host read path
    swap backends under an unchanged caller: the A side forces
    ``readBackend=pread`` (per-run preadv2 scatter), the B side
    ``readBackend=iouring`` (batched SQEs, fixed buffers registered
    once per worker ring, one ``io_uring_enter`` per task). Same
    channel, same region, same rotating destination set; bytes are
    verified under BOTH backends before timing — the A/B's first job
    is proving byte identity, its second is measuring the syscall
    batching. Where io_uring is unavailable (old kernel, seccomp,
    ``SPARKRDMA_NATIVE_NO_IOURING`` build) the row records the
    degradation honestly instead of timing pread against itself. On a
    1-core page-cache-resident rig the win is bounded by syscall
    count, not I/O parallelism — ``cores`` is recorded so the ledger
    stays interpretable."""
    import os
    import tempfile

    from sparkrdma_tpu.memory.buffer import TpuBuffer
    from sparkrdma_tpu.transport import FnListener
    from sparkrdma_tpu.transport.native_node import NativeTpuNode
    from sparkrdma_tpu.utils.config import TpuShuffleConf

    out = {}
    rng = np.random.default_rng(23)
    srv = NativeTpuNode(TpuShuffleConf(), "127.0.0.1", False, "uab-srv")
    cli = NativeTpuNode(TpuShuffleConf(), "127.0.0.1", True, "uab-cli")
    n_blocks = READ_REGION // READ_BLOCK
    N_PAIRS = 1 if dry_run else 3
    ROUNDS_PER_SIDE = 2 if dry_run else 4
    dsts = [memoryview(bytearray(READ_BLOCK)) for _ in range(n_blocks)]
    try:
        ch = cli.get_channel("127.0.0.1", srv.port)
        src = rng.integers(0, 256, size=READ_REGION, dtype=np.uint8)
        buf = TpuBuffer(srv.pd, READ_REGION, register=True)
        np.frombuffer(buf.view, dtype=np.uint8)[:] = src

        def one_round(label):
            evs, errs = [], []
            for i in range(n_blocks):
                ev = threading.Event()

                def fail(e, ev=ev):
                    errs.append(e)
                    ev.set()

                ch.read_in_queue(
                    FnListener(lambda _, ev=ev: ev.set(), fail),
                    [dsts[i]], [(buf.mkey, i * READ_BLOCK, READ_BLOCK)],
                )
                evs.append(ev)
            for ev in evs:
                assert ev.wait(120), f"{label}: iouring A/B read timed out"
            if errs:
                raise SystemExit(
                    f"BENCH FAILED: iouring A/B READ error: {errs[0]}"
                )

        def verify(label):
            for i in (0, 1, n_blocks - 1):
                if not np.array_equal(
                    np.frombuffer(dsts[i], np.uint8),
                    src[i * READ_BLOCK: (i + 1) * READ_BLOCK],
                ):
                    raise SystemExit(
                        f"BENCH FAILED: {label} READ bytes differ"
                    )

        def timed_side(backend):
            cli.set_read_backend(backend)
            t0 = time.perf_counter()
            for _ in range(ROUNDS_PER_SIDE):
                one_round(backend)
            dt = time.perf_counter() - t0
            return ROUNDS_PER_SIDE * READ_REGION / dt / 1e9

        # warm + byte-identity check, BOTH backends, before any timing
        cli.set_read_backend("iouring")
        one_round("iouring-warm")
        verify("iouring")
        stats = cli.sq_stats()
        cli.set_read_backend("pread")
        one_round("pread-warm")
        verify("pread")
        fast, _ = cli.read_path_stats()
        if fast == 0:
            raise SystemExit(
                "BENCH FAILED: iouring A/B never took the fast path"
            )
        row = {
            "uring_compiled": stats.get("uring_compiled"),
            "iouring_available": stats.get("backend") == "iouring",
            "backend_fallbacks": stats.get("backend_fallbacks"),
            "cores": os.cpu_count() or 1,
        }
        if stats.get("backend") != "iouring":
            # degradation is the result, not an error: pread served the
            # warm round byte-identically and the fallback was counted
            row["skip_reason"] = (
                "io_uring unavailable on this rig/build; timing pread "
                "against itself would be noise"
            )
            out["ab_iouring_read"] = row
            buf.free()
            return out

        s0 = cli.sq_stats()
        pairs = []
        for _ in range(N_PAIRS):
            a = timed_side("pread")
            b = timed_side("iouring")
            pairs.append(
                {"pread_gbps": round(a, 3), "iouring_gbps": round(b, 3)}
            )
        s1 = cli.sq_stats()
        med_a = float(np.median([p["pread_gbps"] for p in pairs]))
        med_b = float(np.median([p["iouring_gbps"] for p in pairs]))

        # machine roofline for this path: raw page-cache pread of the
        # same volume into the same rotating destination set
        with tempfile.NamedTemporaryFile(dir="/dev/shm") as f:
            f.write(src.tobytes())
            f.flush()
            rfd = f.fileno()
            for i in range(n_blocks):
                os.preadv(rfd, [dsts[i]], i * READ_BLOCK)
            t0 = time.perf_counter()
            moved = 0
            for _ in range(ROUNDS_PER_SIDE):
                for i in range(n_blocks):
                    moved += os.preadv(rfd, [dsts[i]], i * READ_BLOCK)
            roofline = moved / (time.perf_counter() - t0) / 1e9

        d_submits = s1["submits"] - s0["submits"]
        d_batches = s1["batches"] - s0["batches"]
        row.update({
            "pairs": pairs,
            "pread_gbps": round(med_a, 3),
            "iouring_gbps": round(med_b, 3),
            "iouring_speedup": round(med_b / med_a, 3) if med_a else None,
            "sq_submits": d_submits,
            "sq_batches": d_batches,
            "sqe_batching_factor": (
                round(d_submits / d_batches, 2) if d_batches else None
            ),
            "pread_roofline_gbps": round(roofline, 3),
            "roofline_fraction": (
                round(med_b / roofline, 3) if roofline else None
            ),
        })
        out["ab_iouring_read"] = row
        buf.free()
    finally:
        cli.stop()
        srv.stop()
    return out


def bench_consume_sharded_ab(dry_run: bool = False) -> dict:
    """Interleaved inline-vs-sharded consume A/B pairs, SAME run.

    ``tpu.shuffle.native.consumeWorkers`` shards READ_DONE completion
    work (checksum + decode + delivery) across lanes routed by channel
    (DESIGN.md §24); this A/B isolates exactly that seam. Both sides
    run the SAME fetch-to-consumed shape — read a region's blocks
    round-robin over 4 connections, uint8-sum every byte in the
    completion listener — but the A client consumes inline on its poll
    thread (``consumeWorkers=1``) while the B client's 4 lanes run the
    sums concurrently with the poll loop and each other (the sum
    releases the GIL). Sums are verified both sides every round, so
    sharding is proven order-safe and byte-identical before it is
    credited with anything. On a 1-core rig the lanes can only overlap
    consume with poll-loop bookkeeping, so ~1x is honest — the ≥90%
    consume-roofline expectation applies where cores exist (``cores``
    recorded)."""
    import os

    from sparkrdma_tpu.memory.buffer import TpuBuffer
    from sparkrdma_tpu.transport import FnListener
    from sparkrdma_tpu.transport.native_node import NativeTpuNode
    from sparkrdma_tpu.utils.config import TpuShuffleConf

    out = {}
    rng = np.random.default_rng(29)
    LANES = 4
    srv = NativeTpuNode(TpuShuffleConf(), "127.0.0.1", False, "sab-srv")
    cli_i = NativeTpuNode(
        TpuShuffleConf({"tpu.shuffle.native.consumeWorkers": "1"}),
        "127.0.0.1", True, "sab-cli-inline",
    )
    cli_s = NativeTpuNode(
        TpuShuffleConf({"tpu.shuffle.native.consumeWorkers": str(LANES)}),
        "127.0.0.1", True, "sab-cli-sharded",
    )
    n_blocks = READ_REGION // READ_BLOCK
    N_PAIRS = 1 if dry_run else 3
    ROUNDS_PER_SIDE = 2 if dry_run else 4
    dsts = [memoryview(bytearray(READ_BLOCK)) for _ in range(n_blocks)]
    try:
        src = rng.integers(0, 256, size=READ_REGION, dtype=np.uint8)
        buf = TpuBuffer(srv.pd, READ_REGION, register=True)
        np.frombuffer(buf.view, dtype=np.uint8)[:] = src
        want_round = int(np.add.reduce(src, dtype=np.int64))
        # lanes shard by channel: spread the region over LANES distinct
        # connections so the B side actually exercises every lane
        ch_i = [
            cli_i.get_channel("127.0.0.1", srv.port, purpose=f"data-{j}")
            for j in range(LANES)
        ]
        ch_s = [
            cli_s.get_channel("127.0.0.1", srv.port, purpose=f"data-{j}")
            for j in range(LANES)
        ]

        def one_round(channels, label):
            sums = [0] * n_blocks
            evs, errs = [], []
            for i in range(n_blocks):
                ev = threading.Event()

                def ok(_, i=i, ev=ev):
                    # THE consume: full-speed sum of the landed block,
                    # on whatever thread the node's consume plane picks
                    sums[i] = int(np.add.reduce(
                        np.frombuffer(dsts[i], np.uint8), dtype=np.int64
                    ))
                    ev.set()

                def fail(e, ev=ev):
                    errs.append(e)
                    ev.set()

                channels[i % len(channels)].read_in_queue(
                    FnListener(ok, fail),
                    [dsts[i]], [(buf.mkey, i * READ_BLOCK, READ_BLOCK)],
                )
                evs.append(ev)
            for ev in evs:
                assert ev.wait(120), f"{label}: consume A/B read timed out"
            if errs:
                raise SystemExit(
                    f"BENCH FAILED: {label} READ error: {errs[0]}"
                )
            if sum(sums) != want_round:
                raise SystemExit(
                    f"BENCH FAILED: {label} consume A/B sums differ"
                )

        def timed_side(channels, label):
            t0 = time.perf_counter()
            for _ in range(ROUNDS_PER_SIDE):
                one_round(channels, label)
            dt = time.perf_counter() - t0
            return ROUNDS_PER_SIDE * READ_REGION / dt / 1e9

        one_round(ch_i, "inline-warm")
        one_round(ch_s, "sharded-warm")
        if cli_s.sq_stats().get("consume_workers") != LANES:
            raise SystemExit(
                "BENCH FAILED: sharded client has no consume lanes"
            )
        pairs = []
        for _ in range(N_PAIRS):
            a = timed_side(ch_i, "inline")
            b = timed_side(ch_s, "sharded")
            pairs.append(
                {"inline_gbps": round(a, 3), "sharded_gbps": round(b, 3)}
            )
        med_a = float(np.median([p["inline_gbps"] for p in pairs]))
        med_b = float(np.median([p["sharded_gbps"] for p in pairs]))

        # this comparison's machine limit: the one-pass consume over
        # the same rotating set with delivery assumed free
        t0 = time.perf_counter()
        moved = 0
        for _ in range(ROUNDS_PER_SIDE):
            for d in dsts:
                np.add.reduce(np.frombuffer(d, np.uint8), dtype=np.int64)
                moved += READ_BLOCK
        roofline = moved / (time.perf_counter() - t0) / 1e9

        out["ab_consume_sharded"] = {
            "pairs": pairs,
            "inline_consumed_gbps": round(med_a, 3),
            "sharded_consumed_gbps": round(med_b, 3),
            "sharded_speedup": round(med_b / med_a, 3) if med_a else None,
            "consume_workers": LANES,
            "cores": os.cpu_count() or 1,
            "consume_roofline_gbps": round(roofline, 3),
            "roofline_fraction": (
                round(med_b / roofline, 3) if roofline else None
            ),
        }
        buf.free()
    finally:
        cli_i.stop()
        cli_s.stop()
        srv.stop()
    return out


def bench_device_fetch_ab(dry_run: bool = False) -> dict:
    """Interleaved device-pull vs host-fetch A/B pairs, SAME run.

    The device fetch plane (DESIGN.md §17) moves arena-resident blocks
    HBM→HBM behind the same resolver API the host path uses; this A/B
    toggles ``deviceFetch.enabled`` between sides of each pair so both
    fetch the SAME published blocks through the same
    ``fetch_device_blocks`` call. Both sides byte-verify against the
    source; the B side additionally proves the pulls actually engaged
    (plane counter moved, zero fallbacks). Under ``JAX_PLATFORMS=cpu``
    the mover is the emulated ``jax.device_put`` path, so ~1.0x is the
    expected speedup — the row exists to keep the plane measured and
    regression-gated, and to light up on a real ICI mesh.

    ``dry_run`` shrinks the volume for the CI obs smoke
    (``bench.py --ab device_fetch``)."""
    from sparkrdma_tpu.obs import get_registry
    from sparkrdma_tpu.ops import remote_copy
    from sparkrdma_tpu.shuffle.device_io import DeviceShuffleIO
    from sparkrdma_tpu.shuffle.handle import BaseShuffleHandle, HashPartitioner
    from sparkrdma_tpu.shuffle.manager import TpuShuffleManager
    from sparkrdma_tpu.utils.config import TpuShuffleConf

    out = {}
    n_parts = 4 if dry_run else 8
    block = (256 << 10) if dry_run else (2 << 20)
    n_pairs = 1 if dry_run else 3
    rounds = 2 if dry_run else 4
    conf = TpuShuffleConf()
    driver = TpuShuffleManager(conf, is_driver=True)
    ex_map = TpuShuffleManager(conf, is_driver=False, executor_id="dfab-map")
    ex_red = TpuShuffleManager(conf, is_driver=False, executor_id="dfab-red")
    driver.register_shuffle(
        BaseShuffleHandle(
            shuffle_id=71, num_maps=1, partitioner=HashPartitioner(n_parts)
        )
    )
    io_map, io_red = DeviceShuffleIO(ex_map), DeviceShuffleIO(ex_red)
    rng = np.random.default_rng(31)
    data = {
        p: rng.integers(0, 256, block, np.uint8) for p in range(n_parts)
    }
    total = n_parts * block
    reg = get_registry()
    pulls = reg.counter("device_fetch.plane.pulls", role="dfab-red")
    fallbacks = reg.counter("device_fetch.plane.fallbacks", role="dfab-red")
    try:
        io_map.publish_device_blocks(71, data)

        def fetch_round(verify: bool) -> None:
            got = io_red.fetch_device_blocks(71, 0, n_parts, timeout_s=120)
            try:
                if verify:
                    for p in range(n_parts):
                        if bytes(got[p][0].read(0, block)) != data[p].tobytes():
                            raise SystemExit(
                                "BENCH FAILED: device-fetch A/B bytes differ"
                            )
            finally:
                for bufs in got.values():
                    for b in bufs:
                        b.free()

        def side(enabled: bool):
            conf.set("tpu.shuffle.deviceFetch.enabled", str(enabled).lower())
            fetch_round(verify=True)  # warm + byte-identity, untimed
            t0 = time.perf_counter()
            for _ in range(rounds):
                fetch_round(verify=False)
            dt = time.perf_counter() - t0
            return rounds * total / dt / 1e9

        pairs = []
        for _ in range(n_pairs):
            a = side(False)
            p0, f0 = pulls.value, fallbacks.value
            b = side(True)
            if pulls.value - p0 < (rounds + 1) * n_parts:
                raise SystemExit(
                    "BENCH FAILED: device-fetch A/B pulls did not engage"
                )
            if fallbacks.value != f0:
                raise SystemExit(
                    "BENCH FAILED: device-fetch A/B fell back mid-pair"
                )
            pairs.append(
                {"host_gbps": round(a, 3), "device_gbps": round(b, 3)}
            )
        med_a = float(np.median([p["host_gbps"] for p in pairs]))
        med_b = float(np.median([p["device_gbps"] for p in pairs]))
        out["ab_device_fetch"] = {
            "pairs": pairs,
            "host_fetch_gbps": round(med_a, 3),
            "device_fetch_gbps": round(med_b, 3),
            "speedup": round(med_b / med_a, 3) if med_a else None,
            "mover": (
                "pallas-ici" if remote_copy.is_tpu_mesh()
                else "emulated-device-put"
            ),
        }
    finally:
        io_red.stop()
        io_map.stop()
        ex_red.stop()
        ex_map.stop()
        driver.stop()
    return out


def bench_concurrent_jobs_ab(dry_run: bool = False) -> dict:
    """Interleaved sequential-vs-concurrent job serving A/B, SAME run.

    The tenancy tentpole's headline: one TpuContext serving K jobs from
    K tenants concurrently (admission + fair-share pools, DESIGN.md
    §19) against the same K jobs run back to back. Each side runs the
    SAME job set on the SAME context (warm executors, warm pools);
    aggregate MB/s is the writer-bytes moved over the side's wall
    clock, so the ratio is the serving-concurrency win, not a cache
    artifact. Every job's result is verified on both sides.

    On a 1-core rig the concurrent side mostly overlaps I/O waits and
    ~1x is honest; the ≥1.5x acceptance gate applies where parallelism
    exists (recorded as ``cores`` so the ledger is interpretable)."""
    import os

    from sparkrdma_tpu.engine.context import TpuContext
    from sparkrdma_tpu.obs import get_registry
    from sparkrdma_tpu.utils.config import TpuShuffleConf

    n_jobs = 4
    n_rows = 2_000 if dry_run else 20_000
    n_parts = 4
    n_pairs = 1 if dry_run else 3
    reg = get_registry()
    out = {}
    conf = TpuShuffleConf()
    with TpuContext(num_executors=2, conf=conf, task_threads=n_jobs) as ctx:
        def make_job(j):
            # wide key space: map-side aggregation barely collapses it,
            # so the shuffle moves real bytes and MB/s means throughput
            mod = 4093 + j
            rdd = (
                ctx.parallelize(range(n_rows), n_parts)
                .map(lambda x, m=mod: (x % m, x))
                .reduce_by_key(lambda a, b: a + b, num_partitions=n_parts)
            )
            expected = {}
            for x in range(n_rows):
                expected[x % mod] = expected.get(x % mod, 0) + x
            return rdd, expected

        def run_one(j):
            rdd, expected = make_job(j)
            got = dict(ctx.run_job(rdd, tenant=f"t{j}"))
            if got != expected:
                raise SystemExit(
                    f"BENCH FAILED: concurrent-jobs A/B job {j} wrong result"
                )

        def bytes_written():
            snap = reg.snapshot(prefix="writer.bytes_written")
            return sum(snap.get("counters", {}).values())

        def sequential_side():
            b0 = bytes_written()
            t0 = time.perf_counter()
            for j in range(n_jobs):
                run_one(j)
            dt = time.perf_counter() - t0
            return (bytes_written() - b0) / dt / 1e6

        def concurrent_side():
            errs = []

            def worker(j):
                try:
                    run_one(j)
                except BaseException as e:  # noqa: BLE001
                    errs.append(e)

            b0 = bytes_written()
            t0 = time.perf_counter()
            threads = [
                threading.Thread(target=worker, args=(j,))
                for j in range(n_jobs)
            ]
            for t in threads:
                t.start()
            for t in threads:
                t.join()
            dt = time.perf_counter() - t0
            if errs:
                raise errs[0]
            return (bytes_written() - b0) / dt / 1e6

        run_one(0)  # warm: executors, pools, codecs
        pairs = []
        for _ in range(n_pairs):
            a = sequential_side()
            b = concurrent_side()
            pairs.append(
                {"sequential_mbps": round(a, 3), "concurrent_mbps": round(b, 3)}
            )
    med_a = float(np.median([p["sequential_mbps"] for p in pairs]))
    med_b = float(np.median([p["concurrent_mbps"] for p in pairs]))
    speedup = round(med_b / med_a, 3) if med_a else None
    cores = os.cpu_count() or 1
    # the ≥1.5x gate only MEANS anything where parallelism exists;
    # everywhere this row is checked (CI smoke included) the consumer
    # must branch on gate_evaluated and surface gate_skip_reason
    # loudly instead of silently passing on a small rig
    gate_evaluated = cores >= 4 and speedup is not None
    gate_skip_reason = None
    if not gate_evaluated:
        gate_skip_reason = (
            f"only {cores} core(s): concurrency gate needs >= 4"
            if cores < 4 else "no speedup measured"
        )
    if gate_evaluated and speedup < 1.5:
        raise SystemExit(
            f"BENCH FAILED: concurrent serving {speedup}x < 1.5x on a "
            f"{cores}-core rig"
        )
    out["ab_concurrent_jobs"] = {
        "pairs": pairs,
        "sequential_mbps": round(med_a, 3),
        "concurrent_mbps": round(med_b, 3),
        "concurrency_speedup": speedup,
        "jobs": n_jobs,
        "cores": cores,
        "gate_evaluated": gate_evaluated,
        "gate_skip_reason": gate_skip_reason,
    }
    return out


def bench_profiler_overhead_ab(dry_run: bool = False) -> dict:
    """Interleaved profiler-off vs profiler-on A/B on the SAME warm
    context (obs/profiler.py, docs/OBSERVABILITY.md "Continuous
    profiling").

    Both sides run the same sequential job set on one TpuContext; the
    "on" side additionally runs the wall-clock sampler at the DEFAULT
    rate (``tpu.shuffle.obs.profile.hz``), so the throughput delta is
    the profiler's whole marginal cost. The acceptance budget is ≤2%
    — but wall-clock noise on a shared rig is routinely bigger than
    that, so the gate is only *evaluated* when the interleaved pairs
    were stable enough to resolve it (pair spread ≤ 4%); otherwise it
    SKIPS LOUDLY with ``gate_skip_reason``, never a silent pass."""
    from sparkrdma_tpu.engine.context import TpuContext
    from sparkrdma_tpu.obs import get_registry
    from sparkrdma_tpu.obs.profiler import SamplingProfiler, get_profiler
    from sparkrdma_tpu.utils.config import TpuShuffleConf

    n_jobs = 2
    n_rows = 2_000 if dry_run else 20_000
    n_parts = 4
    n_pairs = 2 if dry_run else 5
    reg = get_registry()
    default_hz = TpuShuffleConf().profile_hz
    # keep the off side honest: pause any ambient process sampler (the
    # bench harness runs one for its own artifact) for the A/B's span
    ambient = get_profiler()
    ambient_was_running = ambient is not None and ambient.running
    if ambient_was_running:
        ambient.stop()
    # the context under test runs with the profiler knob OFF — the "on"
    # side's sampler below is the only one observing either side
    conf = TpuShuffleConf({"tpu.shuffle.obs.profile.enabled": "false"})
    out = {}
    try:
        with TpuContext(num_executors=2, conf=conf, task_threads=2) as ctx:
            def run_jobs():
                for j in range(n_jobs):
                    mod = 4093 + j
                    rdd = (
                        ctx.parallelize(range(n_rows), n_parts)
                        .map(lambda x, m=mod: (x % m, x))
                        .reduce_by_key(lambda a, b: a + b,
                                       num_partitions=n_parts)
                    )
                    if not ctx.run_job(rdd):
                        raise SystemExit(
                            "BENCH FAILED: profiler A/B job returned nothing"
                        )

            def bytes_written():
                snap = reg.snapshot(prefix="writer.bytes_written")
                return sum(snap.get("counters", {}).values())

            def one_side(profiler):
                if profiler is not None:
                    profiler.start()
                b0 = bytes_written()
                t0 = time.perf_counter()
                try:
                    run_jobs()
                finally:
                    if profiler is not None:
                        profiler.stop()
                return (bytes_written() - b0) / (time.perf_counter() - t0) / 1e6

            run_jobs()  # warm: executors, pools, codecs
            sampler = SamplingProfiler(reg, role="bench-ab", hz=default_hz)
            pairs = []
            for _ in range(n_pairs):
                a = one_side(None)
                b = one_side(sampler)
                pairs.append({"off_mbps": round(a, 3), "on_mbps": round(b, 3)})
    finally:
        if ambient_was_running:
            ambient.start()
    med_a = float(np.median([p["off_mbps"] for p in pairs]))
    med_b = float(np.median([p["on_mbps"] for p in pairs]))
    overhead_pct = round((1.0 - med_b / med_a) * 100.0, 3) if med_a else None
    ratios = [p["on_mbps"] / p["off_mbps"] for p in pairs if p["off_mbps"]]
    pair_spread_pct = (
        round((max(ratios) - min(ratios)) * 100.0, 3) if ratios else None
    )
    samples = int(reg.snapshot(prefix="profile.samples")
                  .get("counters", {})
                  .get("profile.samples{role=bench-ab}", 0))
    gate_evaluated = (
        not dry_run
        and overhead_pct is not None
        and pair_spread_pct is not None
        and pair_spread_pct <= 4.0
        and samples > 0
    )
    gate_skip_reason = None
    if not gate_evaluated:
        if dry_run:
            gate_skip_reason = (
                "dry run: volume too small to resolve a 2% delta"
            )
        elif samples == 0:
            gate_skip_reason = "sampler recorded zero samples"
        elif pair_spread_pct is None or overhead_pct is None:
            gate_skip_reason = "no throughput measured"
        else:
            gate_skip_reason = (
                f"pair spread {pair_spread_pct}% > 4%: run too noisy to "
                "resolve a 2% overhead budget"
            )
    if gate_evaluated and overhead_pct > 2.0:
        raise SystemExit(
            f"BENCH FAILED: profiler overhead {overhead_pct}% > 2% at "
            f"{default_hz} Hz (off {med_a:.1f} MB/s, on {med_b:.1f} MB/s)"
        )
    out["ab_profiler_overhead"] = {
        "pairs": pairs,
        "off_mbps": round(med_a, 3),
        "on_mbps": round(med_b, 3),
        "overhead_pct": overhead_pct,
        "pair_spread_pct": pair_spread_pct,
        "hz": default_hz,
        "profile_samples": samples,
        "gate_evaluated": gate_evaluated,
        "gate_skip_reason": gate_skip_reason,
    }
    return out


def bench_slo_overhead_ab(dry_run: bool = False) -> dict:
    """Interleaved SLO-evaluator-off vs -on A/B on the SAME warm context
    (obs/slo.py, docs/OBSERVABILITY.md "SLOs & automated diagnosis").

    Both sides run the same sequential job set on one TpuContext whose
    driver hub evaluates every 100 ms with a latency objective installed
    (a deliberately unreachable p99 bar, so no breach/diagnosis path
    fires — this measures the steady-state cost of burn-rate evaluation
    itself); the "off" side flips ``hub.slo.enabled`` so heartbeats skip
    evaluation entirely. The acceptance budget is ≤2%, evaluated only
    when the interleaved pairs are stable enough to resolve it (pair
    spread ≤ 4%); otherwise it SKIPS LOUDLY with ``gate_skip_reason``,
    never a silent pass."""
    from sparkrdma_tpu.engine.context import TpuContext
    from sparkrdma_tpu.obs import get_registry
    from sparkrdma_tpu.utils.config import TpuShuffleConf

    n_jobs = 2
    n_rows = 2_000 if dry_run else 20_000
    n_parts = 4
    n_pairs = 2 if dry_run else 5
    reg = get_registry()
    eval_interval_ms = 100
    conf = TpuShuffleConf({
        "tpu.shuffle.obs.profile.enabled": "false",
        "tpu.shuffle.obs.telemetry.intervalMs": "100",
        "tpu.shuffle.obs.slo.evalIntervalMs": str(eval_interval_ms),
        # install the latency objective but keep it unbreachable: the
        # A/B measures evaluation cost, not breach handling
        "tpu.shuffle.obs.slo.taskP99Ms": "600000",
    })

    def evaluations():
        snap = reg.snapshot(prefix="slo.evaluations")
        return sum(snap.get("counters", {}).values())

    with TpuContext(num_executors=2, conf=conf, task_threads=2) as ctx:
        hub = ctx.driver.telemetry
        if hub is None:
            raise SystemExit("BENCH FAILED: slo A/B needs driver telemetry")

        def run_jobs():
            for j in range(n_jobs):
                mod = 4093 + j
                rdd = (
                    ctx.parallelize(range(n_rows), n_parts)
                    .map(lambda x, m=mod: (x % m, x))
                    .reduce_by_key(lambda a, b: a + b,
                                   num_partitions=n_parts)
                )
                if not ctx.run_job(rdd):
                    raise SystemExit(
                        "BENCH FAILED: slo A/B job returned nothing"
                    )

        def bytes_written():
            snap = reg.snapshot(prefix="writer.bytes_written")
            return sum(snap.get("counters", {}).values())

        def one_side(enabled):
            hub.slo.enabled = enabled
            b0 = bytes_written()
            t0 = time.perf_counter()
            try:
                run_jobs()
            finally:
                hub.slo.enabled = True
            return (bytes_written() - b0) / (time.perf_counter() - t0) / 1e6

        run_jobs()  # warm: executors, pools, codecs
        e0 = evaluations()
        pairs = []
        for _ in range(n_pairs):
            a = one_side(False)
            b = one_side(True)
            pairs.append({"off_mbps": round(a, 3), "on_mbps": round(b, 3)})
        evals = int(evaluations() - e0)
        breaches = len(hub.slo.breaches)
    med_a = float(np.median([p["off_mbps"] for p in pairs]))
    med_b = float(np.median([p["on_mbps"] for p in pairs]))
    overhead_pct = round((1.0 - med_b / med_a) * 100.0, 3) if med_a else None
    ratios = [p["on_mbps"] / p["off_mbps"] for p in pairs if p["off_mbps"]]
    pair_spread_pct = (
        round((max(ratios) - min(ratios)) * 100.0, 3) if ratios else None
    )
    gate_evaluated = (
        not dry_run
        and overhead_pct is not None
        and pair_spread_pct is not None
        and pair_spread_pct <= 4.0
        and evals > 0
    )
    gate_skip_reason = None
    if not gate_evaluated:
        if dry_run:
            gate_skip_reason = (
                "dry run: volume too small to resolve a 2% delta"
            )
        elif evals == 0:
            gate_skip_reason = "SLO engine recorded zero evaluations"
        elif pair_spread_pct is None or overhead_pct is None:
            gate_skip_reason = "no throughput measured"
        else:
            gate_skip_reason = (
                f"pair spread {pair_spread_pct}% > 4%: run too noisy to "
                "resolve a 2% overhead budget"
            )
    if gate_evaluated and overhead_pct > 2.0:
        raise SystemExit(
            f"BENCH FAILED: SLO evaluator overhead {overhead_pct}% > 2% at "
            f"{eval_interval_ms} ms cadence (off {med_a:.1f} MB/s, "
            f"on {med_b:.1f} MB/s)"
        )
    return {
        "ab_slo_overhead": {
            "pairs": pairs,
            "off_mbps": round(med_a, 3),
            "on_mbps": round(med_b, 3),
            "overhead_pct": overhead_pct,
            "pair_spread_pct": pair_spread_pct,
            "eval_interval_ms": eval_interval_ms,
            "slo_evaluations": evals,
            "slo_breaches": breaches,
            "gate_evaluated": gate_evaluated,
            "gate_skip_reason": gate_skip_reason,
        }
    }


def bench_journal_overhead_ab(dry_run: bool = False) -> dict:
    """Interleaved event-journal-off vs -on A/B on the SAME warm context
    (obs/journal.py, docs/OBSERVABILITY.md "Event journal & capacity
    plane").

    Both sides run the same sequential job set on one TpuContext with
    100 ms heartbeats; each job additionally drives a burst of emits
    through the module-level seam so the measurement covers the full
    plane — emit under lock, HLC tick, heartbeat shipping with one-beat
    redundancy, and hub-side merge — not just the quiet steady state.
    The "off" side flips :func:`journal.set_enabled`, which parks the
    journal (seq continuity preserved) and reduces every emit site to a
    module-global load + None check. The acceptance budget is ≤2%,
    evaluated only when the interleaved pairs are stable enough to
    resolve it (pair spread ≤ 4%); otherwise it SKIPS LOUDLY with
    ``gate_skip_reason``, never a silent pass."""
    from sparkrdma_tpu.engine.context import TpuContext
    from sparkrdma_tpu.obs import get_registry
    from sparkrdma_tpu.obs import journal as journal_mod
    from sparkrdma_tpu.utils.config import TpuShuffleConf

    n_jobs = 2
    n_rows = 2_000 if dry_run else 20_000
    n_parts = 4
    n_pairs = 2 if dry_run else 5
    burst = 64  # emits per job through the module seam
    reg = get_registry()
    conf = TpuShuffleConf({
        "tpu.shuffle.obs.profile.enabled": "false",
        "tpu.shuffle.obs.telemetry.intervalMs": "100",
    })

    def journal_counter(name):
        snap = reg.snapshot(prefix=name)
        return sum(snap.get("counters", {}).values())

    with TpuContext(num_executors=2, conf=conf, task_threads=2) as ctx:
        hub = ctx.driver.telemetry
        if hub is None:
            raise SystemExit(
                "BENCH FAILED: journal A/B needs driver telemetry"
            )

        def run_jobs():
            for j in range(n_jobs):
                mod = 4093 + j
                rdd = (
                    ctx.parallelize(range(n_rows), n_parts)
                    .map(lambda x, m=mod: (x % m, x))
                    .reduce_by_key(lambda a, b: a + b,
                                   num_partitions=n_parts)
                )
                # incident-storm sized burst at a real emit site shape:
                # a no-op on the off side, the full ring/ship/merge
                # plane on the on side
                for i in range(burst):
                    journal_mod.emit("bench.tick", role="bench", beat=i)
                if not ctx.run_job(rdd):
                    raise SystemExit(
                        "BENCH FAILED: journal A/B job returned nothing"
                    )

        def bytes_written():
            snap = reg.snapshot(prefix="writer.bytes_written")
            return sum(snap.get("counters", {}).values())

        def one_side(enabled):
            journal_mod.set_enabled(enabled)
            b0 = bytes_written()
            t0 = time.perf_counter()
            try:
                run_jobs()
            finally:
                journal_mod.set_enabled(True)
            return (bytes_written() - b0) / (time.perf_counter() - t0) / 1e6

        run_jobs()  # warm: executors, pools, codecs
        ev0 = journal_counter("journal.events")
        mg0 = journal_counter("journal.merged")
        pairs = []
        for _ in range(n_pairs):
            a = one_side(False)
            b = one_side(True)
            pairs.append({"off_mbps": round(a, 3), "on_mbps": round(b, 3)})
        events = int(journal_counter("journal.events") - ev0)
        merged = int(journal_counter("journal.merged") - mg0)
    med_a = float(np.median([p["off_mbps"] for p in pairs]))
    med_b = float(np.median([p["on_mbps"] for p in pairs]))
    overhead_pct = round((1.0 - med_b / med_a) * 100.0, 3) if med_a else None
    ratios = [p["on_mbps"] / p["off_mbps"] for p in pairs if p["off_mbps"]]
    pair_spread_pct = (
        round((max(ratios) - min(ratios)) * 100.0, 3) if ratios else None
    )
    gate_evaluated = (
        not dry_run
        and overhead_pct is not None
        and pair_spread_pct is not None
        and pair_spread_pct <= 4.0
        and events > 0
    )
    gate_skip_reason = None
    if not gate_evaluated:
        if dry_run:
            gate_skip_reason = (
                "dry run: volume too small to resolve a 2% delta"
            )
        elif events == 0:
            gate_skip_reason = "journal recorded zero events on the on side"
        elif pair_spread_pct is None or overhead_pct is None:
            gate_skip_reason = "no throughput measured"
        else:
            gate_skip_reason = (
                f"pair spread {pair_spread_pct}% > 4%: run too noisy to "
                "resolve a 2% overhead budget"
            )
    if gate_evaluated and overhead_pct > 2.0:
        raise SystemExit(
            f"BENCH FAILED: event journal overhead {overhead_pct}% > 2% "
            f"(off {med_a:.1f} MB/s, on {med_b:.1f} MB/s, "
            f"{events} events emitted)"
        )
    return {
        "ab_journal_overhead": {
            "pairs": pairs,
            "off_mbps": round(med_a, 3),
            "on_mbps": round(med_b, 3),
            "overhead_pct": overhead_pct,
            "pair_spread_pct": pair_spread_pct,
            "journal_events": events,
            "journal_merged": merged,
            "burst_per_job": burst,
            "gate_evaluated": gate_evaluated,
            "gate_skip_reason": gate_skip_reason,
        }
    }


def _socket_roofline() -> float:
    """Raw single-core loopback TCP throughput at the bench's block
    size — the streamed plane's machine limit on this rig. Moves the
    same volume as the paths it calibrates (a short probe jitters
    enough on a loaded 1-core rig to land under the plane it bounds)."""
    import socket

    from sparkrdma_tpu.transport.wire import read_into

    block = READ_BLOCK
    total = READ_TOTAL
    srv = socket.socket()
    srv.bind(("127.0.0.1", 0))
    srv.listen(1)
    port = srv.getsockname()[1]
    src = np.random.default_rng(3).integers(
        0, 256, block, dtype=np.uint8
    ).tobytes()

    def server():
        c, _ = srv.accept()
        c.setsockopt(socket.IPPROTO_TCP, socket.TCP_NODELAY, 1)
        for _ in range(total // block):
            c.sendall(src)
        c.close()

    t = threading.Thread(target=server, daemon=True)
    t.start()
    cli = socket.create_connection(("127.0.0.1", port))
    cli.settimeout(120)
    try:
        dsts = [memoryview(bytearray(block)) for _ in range(8)]

        read_into(cli, dsts[0])  # warm
        t0 = time.perf_counter()
        n = 0
        for i in range(1, total // block):
            read_into(cli, dsts[i % 8])
            n += block
        gbps = n / (time.perf_counter() - t0) / 1e9
    finally:
        cli.close()
        srv.close()
        t.join(10)
    return round(gbps, 3)


def _sendfile_roofline() -> float:
    """Raw loopback throughput when the sender is ``sendfile`` from a
    page-cache-resident shm file (no sender userspace copy) and the
    receiver recv_intos a rotating destination set — the machine limit
    for the streamed-sendfile plane on this rig."""
    import os
    import socket
    import tempfile

    from sparkrdma_tpu.transport.wire import read_into

    block = READ_BLOCK
    total = READ_TOTAL
    srv = socket.socket()
    srv.bind(("127.0.0.1", 0))
    srv.listen(1)
    port = srv.getsockname()[1]

    with tempfile.NamedTemporaryFile(dir="/dev/shm") as f:
        f.write(np.random.default_rng(5).integers(
            0, 256, block, dtype=np.uint8).tobytes())
        f.flush()
        sfd = f.fileno()

        def server():
            c, _ = srv.accept()
            c.setsockopt(socket.IPPROTO_TCP, socket.TCP_NODELAY, 1)
            try:
                for _ in range(total // block):
                    sent = 0
                    while sent < block:
                        sent += os.sendfile(c.fileno(), sfd, sent, block - sent)
            finally:
                c.close()

        t = threading.Thread(target=server, daemon=True)
        t.start()
        cli = socket.create_connection(("127.0.0.1", port))
        cli.settimeout(120)
        try:
            dsts = [memoryview(bytearray(block)) for _ in range(8)]
            read_into(cli, dsts[0])  # warm
            t0 = time.perf_counter()
            n = 0
            for i in range(1, total // block):
                read_into(cli, dsts[i % 8])
                n += block
            gbps = n / (time.perf_counter() - t0) / 1e9
        finally:
            cli.close()
            srv.close()
            t.join(10)
    return round(gbps, 3)


# ---------------------------------------------------------------------------
# columnar block format: decode-path A/B (DESIGN.md §25)
# ---------------------------------------------------------------------------

def bench_columnar_decode_ab(dry_run: bool = False) -> dict:
    """Interleaved pickle-decode vs columnar-decode A/B over identical
    record sets (DESIGN.md §25, ``bench.py --ab columnar_decode``).

    Both sides consume the exact framed partition stream the reduce
    pipeline fetches (length-prefixed frames through
    ``iter_compressed_blocks``), built from the same (uint32, int64)
    records by the real writers. The PICKLE side measures the legacy
    decode stage end to end: zlib decompress + ``load_buffer`` row
    materialization. The COLUMNAR side measures what that stage
    degenerated to for the analytic/device consumers: header validation
    + ``np.frombuffer`` column views, plus a full-column reduction so
    every landed byte is actually read (views alone would time header
    parsing, not the record plane). ``row_gbps`` additionally reports
    the columnar path when per-row tuples ARE materialized
    (``iter_records``) — the host reader's shape — kept in the record
    for honesty: the gated headline is the column-scan decode, which is
    what the zero-copy format exists for. Decode is single-threaded
    pure CPU on both sides, so the A/B is fair at any core count;
    ``cores`` rides along for the ledger (the honest-caveat pattern the
    other rows follow). Gate: column-scan decode ≥ 1.5x pickle, or a
    loud ``gate_skip_reason``."""
    import io
    import os

    from sparkrdma_tpu.engine.serializer import (
        CompressionCodec,
        frame_compressed,
        iter_compressed_blocks,
        PickleSerializer,
    )
    from sparkrdma_tpu.shuffle import columnar as col
    from sparkrdma_tpu.shuffle.writer.columnar import ColumnarPartitionWriter

    rows = 40_000 if dry_run else 400_000
    n_pairs = 2 if dry_run else 5
    rng = np.random.default_rng(33)
    keys = rng.integers(0, 1 << 32, rows, dtype=np.uint32)
    vals = rng.integers(0, 1 << 31, rows, dtype=np.int64)
    records = [(k, v) for k, v in zip(keys, vals)]
    logical_bytes = keys.nbytes + vals.nbytes
    codec = CompressionCodec(enabled=True)
    ser = PickleSerializer()

    # pickle stream: the legacy sort-file framing (256 KiB flushes)
    import pickle as _pickle
    import struct as _struct

    pack = _struct.Struct(">I").pack
    pkl_stream = bytearray()
    buf = bytearray()
    for rec in records:
        data = _pickle.dumps(rec, protocol=_pickle.HIGHEST_PROTOCOL)
        buf += pack(len(data))
        buf += data
        if len(buf) >= (256 << 10):
            pkl_stream += frame_compressed(codec, bytes(buf))
            buf.clear()
    if buf:
        pkl_stream += frame_compressed(codec, bytes(buf))
    pkl_stream = bytes(pkl_stream)

    # columnar stream: the real partition writer, default batch rows
    chunks = []
    cw = ColumnarPartitionWriter(codec, chunks.append, batch_rows=4096)
    for rec in records:
        cw.write_record(rec)
    cw.flush_batch()
    assert cw.all_columnar, "bench records must conform"
    col_stream = b"".join(chunks)

    expect_keys = int(keys.sum(dtype=np.uint64) & 0xFFFFFFFFFFFFFFFF)

    def decode_pickle():
        n, ksum = 0, 0
        for block in iter_compressed_blocks(io.BytesIO(pkl_stream), codec):
            recs = list(ser.load_buffer(block))
            n += len(recs)
            ksum += int(np.add.reduce([int(r[0]) for r in recs]))
        return n, ksum & 0xFFFFFFFFFFFFFFFF

    def decode_columnar_scan():
        n, ksum, vsum = 0, 0, 0
        for block in iter_compressed_blocks(io.BytesIO(col_stream), codec):
            cols = col.decode_columns(block)
            n += len(cols[0])
            ksum += int(cols[0].sum(dtype=np.uint64))
            vsum += int(cols[1].sum(dtype=np.int64))
        return n, ksum & 0xFFFFFFFFFFFFFFFF

    def decode_columnar_rows():
        n = 0
        for block in iter_compressed_blocks(io.BytesIO(col_stream), codec):
            n += len(list(col.iter_records(block)))
        return n

    # byte identity before timing: both sides see every row
    n_p, sum_p = decode_pickle()
    n_c, sum_c = decode_columnar_scan()
    if n_p != rows or n_c != rows or sum_p != expect_keys or sum_c != expect_keys:
        raise SystemExit("BENCH FAILED: columnar A/B decode sums differ")

    pairs = []
    for _ in range(n_pairs):
        t0 = time.perf_counter()
        decode_pickle()
        t_p = time.perf_counter() - t0
        t0 = time.perf_counter()
        decode_columnar_scan()
        t_c = time.perf_counter() - t0
        t0 = time.perf_counter()
        decode_columnar_rows()
        t_r = time.perf_counter() - t0
        pairs.append({
            "pickle_gbps": round(logical_bytes / t_p / 1e9, 4),
            "columnar_gbps": round(logical_bytes / t_c / 1e9, 4),
            "columnar_row_gbps": round(logical_bytes / t_r / 1e9, 4),
        })
    med_p = float(np.median([p["pickle_gbps"] for p in pairs]))
    med_c = float(np.median([p["columnar_gbps"] for p in pairs]))
    med_r = float(np.median([p["columnar_row_gbps"] for p in pairs]))
    speedup = round(med_c / med_p, 3) if med_p else None
    gate_evaluated = not dry_run and speedup is not None
    gate_skip_reason = None
    if not gate_evaluated:
        gate_skip_reason = (
            "dry run: volume too small to resolve decode throughput"
            if dry_run else "no throughput measured"
        )
    if gate_evaluated and speedup < 1.5:
        raise SystemExit(
            f"BENCH FAILED: columnar decode {speedup}x < 1.5x over pickle "
            f"(pickle {med_p:.3f} GB/s, columnar {med_c:.3f} GB/s)"
        )
    return {
        "ab_columnar_decode": {
            "pairs": pairs,
            "rows": rows,
            "logical_mb": round(logical_bytes / 1e6, 3),
            "pickle_gbps": round(med_p, 4),
            "columnar_gbps": round(med_c, 4),
            "row_gbps": round(med_r, 4),
            "decode_speedup": speedup,
            "columnar_framed_bytes": len(col_stream),
            "pickle_framed_bytes": len(pkl_stream),
            "cores": os.cpu_count() or 1,
            "gate_evaluated": gate_evaluated,
            "gate_skip_reason": gate_skip_reason,
        }
    }


# ---------------------------------------------------------------------------
# device plane: chained-jit differencing (see module docstring)
# ---------------------------------------------------------------------------

def _chained_ms(jax, jnp, step, x, k1, k2, reps=6):
    """ms per step of ``step(state, i) -> state`` (state: device pytree).

    Differences a k2-step chain against a k1-step chain to cancel
    dispatch latency. Under rig-load spikes the difference can come
    out non-positive; fall back to the k2 chain's per-step time —
    dispatch-inclusive, so a conservative UNDER-estimate of
    throughput — rather than ever reporting a negative rate."""

    @partial(jax.jit, static_argnums=(1,))
    def runk(v, k):
        out = jax.lax.fori_loop(0, k, lambda i, v: step(v, i), v)
        leaf = jax.tree.leaves(out)[0]
        return leaf.reshape(-1)[:1].astype(jnp.float32).sum()

    def timed(k):
        float(runk(x, k))  # compile
        ts = []
        for _ in range(reps):
            t0 = time.perf_counter()
            float(runk(x, k))
            ts.append(time.perf_counter() - t0)
        return min(ts)

    for _ in range(2):
        t_hi = timed(k2)
        delta = t_hi - timed(k1)
        if delta > 0:
            return delta / (k2 - k1) * 1e3
    return t_hi / k2 * 1e3


def bench_device(jax) -> dict:
    import jax.numpy as jnp

    if jax.devices()[0].platform != "tpu":
        raise SystemExit("bench_device needs a TPU; none found")

    from sparkrdma_tpu.models.terasort import TeraSorter
    from sparkrdma_tpu.ops.exchange import ExchangeProgram
    from sparkrdma_tpu.ops.pallas_attention import flash_attention
    from sparkrdma_tpu.parallel.mesh import make_mesh

    out = {}
    device = jax.devices()[0]
    rng = np.random.default_rng(0)
    mesh = make_mesh([device])

    # --- TeraSort step (device_sort hot path), verified in-loop ---------
    keys = rng.integers(0, 1 << 32, size=N_KEYS, dtype=np.uint32)
    t0 = time.perf_counter()
    host_sorted = np.sort(keys)
    host_s = time.perf_counter() - t0
    sorter = TeraSorter(mesh)
    step = sorter.step(N_KEYS)
    dev_keys = jax.device_put(keys, device)
    merged, total, overflowed = step(dev_keys)
    got = np.asarray(merged)[: int(np.asarray(total)[0])]
    if bool(overflowed) or not np.array_equal(got[:N_KEYS], host_sorted):
        raise SystemExit("BENCH FAILED: device TeraSort != host sort")

    def sort_step(v, i):
        # re-disorder (xor is order-hostile; sorting stays honest)
        v = jnp.flip(v) ^ (i.astype(jnp.uint32) * jnp.uint32(2654435761))
        m, _, _ = step(v)
        return m[:N_KEYS]

    ms = _chained_ms(jax, jnp, sort_step, dev_keys, 1, 9)
    out["device_sort_gbps"] = round(N_KEYS * 4 / (ms / 1e3) / 1e9, 3)
    out["terasort_speedup_vs_host_sort"] = round(host_s / (ms / 1e3), 3)
    out["host_sort_s"] = round(host_s, 4)

    # --- flash attention vs XLA dense, same process, same method --------
    B, S, H, D = 4, 2048, 8, 128
    q = jnp.asarray(rng.standard_normal((B, S, H, D)), jnp.bfloat16)
    k = jnp.asarray(rng.standard_normal((B, S, H, D)), jnp.bfloat16)
    v = jnp.asarray(rng.standard_normal((B, S, H, D)), jnp.bfloat16)

    def attn_chain(attn_fn):
        def stepf(qkv, i):
            qq, kk, vv = qkv
            return (attn_fn(qq, kk, vv), kk, vv)  # output feeds next q

        return _chained_ms(jax, jnp, stepf, (q, k, v), 16, 272)

    flash_ms = attn_chain(
        lambda a, b, c: flash_attention(
            a, b, c, causal=True, block_q=1024, block_k=1024, interpret=False
        )
    )

    def xla_dense(a, b, c):
        qt = jnp.transpose(a, (0, 2, 1, 3)).astype(jnp.float32)
        kt = jnp.transpose(b, (0, 2, 1, 3)).astype(jnp.float32)
        vt = jnp.transpose(c, (0, 2, 1, 3))
        s = jnp.einsum("bhqd,bhkd->bhqk", qt, kt) / np.sqrt(D)
        s = jnp.where(np.tril(np.ones((S, S), bool)), s, -1e30)
        p = jax.nn.softmax(s, axis=-1).astype(jnp.bfloat16)
        o = jnp.einsum("bhqk,bhkd->bhqd", p, vt)
        return jnp.transpose(o, (0, 2, 1, 3)).astype(jnp.bfloat16)

    xla_ms = attn_chain(xla_dense)
    causal_flops = 4 * B * H * S * S * D * 0.5
    out["flash_attn_ms"] = round(flash_ms, 3)
    out["flash_attn_tflops"] = round(causal_flops / (flash_ms / 1e3) / 1e12, 2)
    out["xla_dense_attn_ms"] = round(xla_ms, 3)
    out["flash_vs_xla_dense"] = round(xla_ms / flash_ms, 2)

    # --- flash TRAINING step: forward + custom-VJP backward (the two
    # blockwise dq / dkdv Pallas kernels; 512^2 blocks measured best
    # for the VJP — 1024^2 pays VMEM pressure in the backward) --------
    def train_step(qkv, i):
        qq, kk, vv = qkv

        def lf(a, b, c):
            return flash_attention(
                a, b, c, causal=True, block_q=512, block_k=512,
                interpret=False,
            ).astype(jnp.float32).sum()

        dq, dk, dv = jax.grad(lf, argnums=(0, 1, 2))(qq, kk, vv)
        # feed gradients forward so the chain is data-dependent
        return (dq.astype(jnp.bfloat16), kk, vv)

    train_ms = _chained_ms(jax, jnp, train_step, (q, k, v), 16, 144)
    # physical floor: a fwd+bwd step cannot beat the forward alone —
    # if the differencing lands below it (dispatch jitter on a loaded
    # rig), remeasure once and then clamp to the consistent bound
    if train_ms < flash_ms:
        train_ms = _chained_ms(jax, jnp, train_step, (q, k, v), 16, 144)
    train_ms = max(train_ms, flash_ms)
    out["flash_train_ms"] = round(train_ms, 3)
    # fwd (1x) + bwd (2.5x) of the causal matmul flops
    out["flash_train_tflops"] = round(
        causal_flops * 3.5 / (train_ms / 1e3) / 1e12, 2
    )

    # --- MFU: measured TFLOPs against the chip's dense bf16 peak --------
    # peak table from public spec sheets (per device, bf16, no
    # sparsity); an unlisted kind (CPU, emulator) reports null MFU
    # rather than a made-up peak
    _BF16_PEAK_TFLOPS = {
        "tpu v4": 275.0,
        "tpu v5 lite": 197.0,
        "tpu v5e": 197.0,
        "tpu v5": 459.0,
        "tpu v5p": 459.0,
        "tpu v6 lite": 918.0,
        "tpu v6e": 918.0,
    }
    kind = str(getattr(device, "device_kind", "") or "")
    peak = _BF16_PEAK_TFLOPS.get(kind.strip().lower())
    out["device_kind"] = kind
    out["bf16_peak_tflops"] = peak
    out["flash_attn_mfu"] = (
        round(out["flash_attn_tflops"] / peak, 4) if peak else None
    )
    out["flash_train_mfu"] = (
        round(out["flash_train_tflops"] / peak, 4) if peak else None
    )

    # --- loopback exchange program executable ---------------------------
    prog = ExchangeProgram(mesh)
    block = 64 << 20
    slab = jax.device_put(
        rng.integers(0, 256, size=(1, block), dtype=np.uint8), device
    )
    counts = jax.device_put(np.asarray([block], np.int32), device)
    xfn = prog.program_for(1, block, slab.dtype)

    def ex_step(sc, i):
        s_, c_ = sc
        r, rc = xfn(s_ ^ jnp.uint8(1), c_)  # xor defeats loop collapsing
        return (r, rc)

    # long chain: per-step is sub-ms, so a short chain's difference
    # drowns in dispatch jitter (observed 27-309 GB/s run-to-run)
    ems = _chained_ms(jax, jnp, ex_step, (slab, counts), 32, 288)
    out["exchange_loopback_gbps"] = round(block / (ems / 1e3) / 1e9, 3)
    return out


def main() -> None:
    import argparse
    import os

    from sparkrdma_tpu.obs import export_chrome_trace, get_registry
    from sparkrdma_tpu.testing import faults
    from sparkrdma_tpu.utils.compile_cache import enable_compile_cache

    enable_compile_cache()
    parser = argparse.ArgumentParser(description="sparkrdma_tpu benchmark")
    parser.add_argument(
        "--fault-plan",
        default="",
        help="fault-injection spec, e.g. 'read:fail:2;rpc:delay:1:delay_ms=50' "
        "— exercises the resilience ladder under load (docs/RESILIENCE.md)",
    )
    parser.add_argument(
        "--fault-seed",
        type=int,
        default=0,
        help="seed for deterministic fault placement (corrupt byte choice)",
    )
    parser.add_argument(
        "--ab",
        default="",
        choices=["", "device_fetch", "concurrent_jobs", "iouring_read",
                 "consume_sharded", "profiler_overhead", "slo_overhead",
                 "journal_overhead", "columnar_decode"],
        help="run ONE A/B at reduced volume and print its JSON — the CI "
        "obs smoke's dry-run mode (e.g. --ab device_fetch)",
    )
    args = parser.parse_args()
    dry_abs = {
        "device_fetch": bench_device_fetch_ab,
        "concurrent_jobs": bench_concurrent_jobs_ab,
        "iouring_read": bench_iouring_read_ab,
        "consume_sharded": bench_consume_sharded_ab,
        "profiler_overhead": bench_profiler_overhead_ab,
        "slo_overhead": bench_slo_overhead_ab,
        "journal_overhead": bench_journal_overhead_ab,
        "columnar_decode": bench_columnar_decode_ab,
    }
    if args.ab:
        record = dry_abs[args.ab](dry_run=True)
        record["dry_run"] = True
        print(json.dumps(record))
        return
    plan = None
    if args.fault_plan:
        plan = faults.FaultPlan.parse(args.fault_plan, seed=args.fault_seed)
        faults.install(plan)

    # time-resolved telemetry: a local hub + one heartbeater make the
    # artifact a timeline instead of an end-state snapshot
    from sparkrdma_tpu.obs.telemetry import Heartbeater, TelemetryHub

    from sparkrdma_tpu.obs.profiler import acquire_profiler, release_profiler

    hub = TelemetryHub(role="bench", interval_ms=250)
    # the bench process profiles itself: its sampler rides the same
    # heartbeats, so the artifact carries a flamegraph-ready profile
    profiler = acquire_profiler(None, role="bench-proc")
    heartbeater = Heartbeater(
        get_registry(), "bench-proc", interval_ms=250, send=hub.ingest,
        profiler=profiler,
    ).start()

    out = {}
    out.update(bench_native_reads())
    out.update(bench_consume_pipelined_ab())
    out.update(bench_consume_mapped_ab())
    out.update(bench_striping_ab())
    out.update(bench_iouring_read_ab())
    out.update(bench_consume_sharded_ab())
    out.update(bench_device_fetch_ab())
    out.update(bench_concurrent_jobs_ab())
    out.update(bench_profiler_overhead_ab())
    out.update(bench_slo_overhead_ab())
    out.update(bench_journal_overhead_ab())
    out.update(bench_columnar_decode_ab())
    import jax

    out.update(bench_device(jax))
    heartbeater.stop(flush=True)
    release_profiler(profiler)
    value = out["native_read_samehost_gbps"]
    trace_path = os.environ.get("SRT_TRACE_OUT", "bench_trace.json")
    try:
        export_chrome_trace(trace_path)
    except OSError:
        trace_path = None
    record = {
        "metric": "shuffle_read_gbps_per_chip",
        "value": value,
        "unit": "GB/s",
        "vs_baseline": round(value / WIRE_RATE_GBPS, 3),
        **out,
        "n_keys": N_KEYS,
        "read_block_bytes": READ_BLOCK,
        "device": str(jax.devices()[0]),
        "note": (
            "vs_baseline = same-host one-sided READ GB/s over the "
            "12.5 GB/s 100GbE wire-rate operating point (BASELINE.md)"
        ),
        "obs_registry": get_registry().snapshot(),
        "trace_file": trace_path,
        "telemetry_timeline": hub.timeline(),
        "stragglers": hub.straggler_report(),
        "profile": hub.profiles.summary(),
    }
    hub.stop()
    if plan is not None:
        record["fault_plan"] = {
            "spec": args.fault_plan,
            "seed": args.fault_seed,
            "injected": plan.total_injected,
        }
    print(json.dumps(record))


if __name__ == "__main__":
    main()
