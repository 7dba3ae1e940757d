"""Measured study: what is the fastest exact device sort on one TPU chip?

This is the evidence behind ``ops/sort.device_sort`` and
docs/DESIGN.md §6. It exists because rounds 1-3 kept *assuming* a
faster-than-XLA sort decomposition existed (row-wise shapes, Pallas
bitonic networks) without ever timing one on the hardware. Run it on a
real chip; it prints one JSON object with every measurement.

Methodology (as in bench.py): K data-dependent steps chained inside
ONE jitted program, differenced against a 1-step run, scalar readback;
median of ``--reps`` runs. The findings below were taken on an earlier
remote chip whose ``block_until_ready`` returned early, where naive
per-dispatch timing reported fantasy numbers ("5.8 TB/s" for a flat
sort); chaining keeps the study valid either way.

Findings (v5e, 2026-07, jax 0.9):

- flat ``lax.sort`` of 32M u32: ~82 ms (1.6 GB/s). This is the VPU
  comparator roofline, not an XLA weakness: a bitonic network is
  ~log2(n)^2/2 ≈ 310 compare-exchange stages at n=2^25, and XLA
  executes them at ~0.25 ms/stage — ~10x better fused than anything
  composable from jnp ops (a single reshape+min/max merge stage costs
  ~2.5 ms at the jnp level, measured below).
- row-wise sort IS much faster per pass (short rows vectorize across
  sublanes), but a full sort needs log2(R) merge levels on top, and
  every expressible merge (jnp strided min/max chains, Pallas
  compare-exchange kernels) pays the same comparator bound with worse
  fusion than XLA's own sort. Every decomposition we measured or
  bounded lands at or above flat-sort time.
- scatter/gather-based radix passes are 3-6x slower than sorting
  itself (random scatter ~0.55 GB/s, gather ~0.28 GB/s) — counting
  sort is a dead end on this hardware.

Conclusion: ``lax.sort`` is the optimal exact-sort primitive on this
chip; the framework's own perf leverage is the byte plane around it.
That mirrors the reference exactly: SparkRDMA never replaced Spark's
sort — it replaced the transport under it
(/root/reference/README.md:7-19; RdmaWrapperShuffleWriter delegates to
Spark's own sort writers, RdmaWrapperShuffleWriter.scala:85-101).
"""

from __future__ import annotations

import argparse
import json
import os
import sys
import time
from functools import partial

import numpy as np

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

N = 1 << 25  # 32M u32 keys = 128 MiB


def _bench(x, step, chain, reps):
    import jax
    import jax.numpy as jnp

    @partial(jax.jit, static_argnums=(1,))
    def chained(v, k):
        def body(i, v):
            # re-disorder between rounds; xor keeps any sort honest
            v = jnp.flip(v) ^ (i.astype(jnp.uint32) * jnp.uint32(2654435761))
            return step(v)

        return jax.lax.fori_loop(0, k, body, v).sum()

    float(chained(x, 1))
    float(chained(x, chain))  # compile both
    dts = []
    for _ in range(reps):
        t0 = time.perf_counter()
        float(chained(x, 1))
        t1 = time.perf_counter() - t0
        t0 = time.perf_counter()
        float(chained(x, chain))
        tk = time.perf_counter() - t0
        dts.append(max((tk - t1) / (chain - 1), 1e-9))
    dt = float(np.median(dts))
    return {"ms": round(dt * 1e3, 2), "gbps": round(N * 4 / dt / 1e9, 2)}


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--chain", type=int, default=16,
                    help="chained steps per jit (>= 2: differencing needs it)")
    ap.add_argument("--reps", type=int, default=3)
    ap.add_argument("--quick", action="store_true", help="flat + 3 row shapes only")
    args = ap.parse_args()
    if args.chain < 2:
        ap.error("--chain must be >= 2 (K-vs-1 differencing)")

    import jax
    import jax.numpy as jnp

    from sparkrdma_tpu.ops.sort import pack_by_partition, radix_partition

    rng = np.random.default_rng(0)
    x = jax.device_put(
        rng.integers(0, 1 << 32, size=N, dtype=np.uint32), jax.devices()[0]
    )
    out = {"n": N, "device": str(jax.devices()[0])}

    out["flat_sort"] = _bench(x, jnp.sort, args.chain, args.reps)
    row_cs = [9, 11, 13] if args.quick else [7, 8, 9, 10, 11, 13, 15, 17, 19, 21]
    for logc in row_cs:
        c = 1 << logc
        out[f"rowsort_2^{logc}"] = _bench(
            x, lambda v, c=c: jnp.sort(v.reshape(-1, c), axis=-1).reshape(-1),
            args.chain, args.reps,
        )
    if not args.quick:
        # one bitonic merge stage at the jnp level (reshape + min/max):
        # the building block every hand-rolled merge tree pays per stage
        for logd in [13, 21]:
            d = 1 << logd

            def stage(v, d=d):
                w = v.reshape(-1, 2, d)
                lo = jnp.minimum(w[:, 0, :], w[:, 1, :])
                hi = jnp.maximum(w[:, 0, :], w[:, 1, :])
                return jnp.stack([lo, hi], axis=1).reshape(-1)

            out[f"minmax_stage_2^{logd}"] = _bench(x, stage, args.chain, args.reps)
        # the shuffle partition/pack pass (argsort-based stable bucketing):
        # what the e>1 write path costs per step on one chip
        def pack(v):
            dest = radix_partition(v, 8, 32)
            slab, _, _ = pack_by_partition(v, dest, 8, (N // 8) * 2, fill=0)
            return slab.reshape(-1)[:N]

        out["radix_pack_e8"] = _bench(x, pack, max(2, args.chain // 4), args.reps)

    print(json.dumps(out))


if __name__ == "__main__":
    main()
