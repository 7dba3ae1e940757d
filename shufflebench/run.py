"""The benchmark's command: whole NPB IS sort jobs, closed loop, one at a
time, through ``TpuShuffleManager``, for ``--seconds``.

    python3 shufflebench/run.py --workload isC-r8 --seed 7 --seconds 30 --trace 0

Set-up (timed as ``setup_s``): JAX on the chips, the compile cache at
``<checkout>/.jax_cache``, keys from ``--seed``, the deployment, and one
untimed warm-up job of the cell's own shape. The window then runs whole
jobs and closes at the end of the first job that ends after
``--seconds``. After it: peak HBM, the deployment stopped, the numpy
reference compared (``reference.py``), and with ``--trace 1`` the
per-layer metrics read from the trace and the spans.

Earlier output lines are JSON records; the last line of standard output
is the result, and the last lines of standard error are the numbers
compared, each beside its limit. Without a TPU, or with fewer chips than
the cell asks for, it exits 2 and prints no result.
"""

from __future__ import annotations

import time

T_PROCESS = time.perf_counter()

import argparse  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import shutil  # noqa: E402
import statistics  # noqa: E402
import sys  # noqa: E402
from dataclasses import dataclass  # noqa: E402
from typing import List, Optional  # noqa: E402

_HERE = os.path.dirname(os.path.abspath(__file__))
if os.path.dirname(_HERE) not in sys.path:
    sys.path.insert(0, os.path.dirname(_HERE))

from shufflebench import spec  # noqa: E402
from shufflebench.job import FAULTS, Deployment, Spans  # noqa: E402
from shufflebench.keys import make_input  # noqa: E402
from shufflebench.reference import compare  # noqa: E402
from shufflebench.trace import union  # noqa: E402

WARMUP_JOBS = 1
LOWERING_EVENT = "/jax/core/compile/jaxpr_to_mlir_module_duration"


def emit(record: dict) -> None:
    print(json.dumps(record), flush=True)


class CompileCounter:
    """Programs lowered (compiled, or loaded from the persistent cache)
    since the listener was added, through ``jax.monitoring``."""

    def __init__(self):
        import jax

        self.count = 0
        self._jax = jax
        jax.monitoring.register_event_duration_secs_listener(self._on)

    def _on(self, name, _secs, **_kw):
        if name == LOWERING_EVENT:
            self.count += 1

    def close(self):
        self._jax.monitoring.unregister_event_duration_listener(self._on)


@dataclass
class RunView:
    """What a per-layer reader may read: spans and counters of the
    window, the trace, the jobs and the cell's files."""

    cell: spec.Cell
    window: tuple          # host perf_counter seconds
    spans: list            # (name, t0, t1) in the window
    jobs: list
    counters: dict         # registry snapshot delta over the window
    trace: Optional[object]
    peaks: Optional[dict]
    compiles_in_window: int
    chips: int

    @property
    def window_s(self) -> float:
        return self.window[1] - self.window[0]

    def span_union_s(self, *prefixes: str) -> float:
        return sum(e - s for s, e in union(
            (s, e) for n, s, e in self.spans if n.startswith(prefixes)))

    def counter(self, name: str) -> float:
        return sum(v for k, v in self.counters["counters"].items()
                   if k.split("{")[0] == name)

    def histogram_sum(self, name: str) -> float:
        return sum(h["sum"] for k, h in self.counters["histograms"].items()
                   if k.split("{")[0] == name)


def parse(argv):
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    # breaks the timed path on purpose (the control and the fault
    # tests); the benchmark's own runs never pass it
    ap.add_argument("--fault", choices=FAULTS, default=None)
    return ap.parse_args(argv)


def device_record(devices) -> dict:
    d = devices[0]
    return {"platform": d.platform, "kind": d.device_kind,
            "count": len(devices)}


def memory(devices) -> dict:
    """Peak and present bytes in use on the fullest chip."""
    stats = [d.memory_stats() or {} for d in devices]
    return {k: max(int(s.get(k, 0)) for s in stats)
            for k in ("peak_bytes_in_use", "bytes_in_use")}


def p95(values: List[float]) -> float:
    return statistics.quantiles(values, n=20)[18]


def main(argv=None, root: str = spec.ROOT,
         harness_dir: str = spec.HARNESS_DIR,
         require_chip: bool = True) -> int:
    args = parse(argv)
    cell = spec.load_cell(args.workload, root, harness_dir)
    cache_dir = os.path.join(root, ".jax_cache")
    os.environ["JAX_COMPILATION_CACHE_DIR"] = cache_dir
    import jax

    from sparkrdma_tpu.obs import get_registry
    from sparkrdma_tpu.obs.metrics import snapshot_delta
    from sparkrdma_tpu.utils.compile_cache import enable_compile_cache

    all_devices = jax.devices()
    if require_chip and all_devices[0].platform != "tpu":
        print(f"shufflebench: no TPU (JAX platform "
              f"{all_devices[0].platform!r})", file=sys.stderr)
        return 2
    if len(all_devices) < cell.chips:
        print(f"shufflebench: {cell.chips} chips asked for, "
              f"{len(all_devices)} present", file=sys.stderr)
        return 2
    devices = all_devices[: cell.chips]
    device = device_record(devices)
    peaks = spec.peaks(device["kind"], harness_dir) if require_chip else None

    jax.config.update("jax_compilation_cache_dir", cache_dir)
    enable_compile_cache()
    cached = len(os.listdir(cache_dir)) if os.path.isdir(cache_dir) else 0
    emit({"record": "device", "device": device,
          "compile_cache": {"dir": cache_dir, "entries_at_start": cached},
          "workload": cell.name, "seed": args.seed, "fault": args.fault})

    cfg, traffic = cell.config, cell.traffic
    compiles = CompileCounter()
    spans = Spans(annotate=bool(args.trace))
    t_keys = time.perf_counter()
    keys, edges, _ = make_input(cfg, traffic, args.seed)
    t_dep = time.perf_counter()
    dep = Deployment(cfg, traffic, devices, args.seed, keys, edges, spans,
                     fault=args.fault)
    # which phase sets the peak: nothing but the shuffle touches the
    # device (the keys are made on the host)
    mem = {"deployment": memory(devices)}
    try:
        t_warm = time.perf_counter()
        for i in range(WARMUP_JOBS):
            dep.run_job(-1 - i)
        setup_parts = {"jax_s": t_keys - T_PROCESS, "keys_s": t_dep - t_keys,
                       "deployment_s": t_warm - t_dep,
                       "warmup_s": time.perf_counter() - t_warm}
        mem["warmup"] = memory(devices)
        warm_compiles = compiles.count
        spans.items.clear()
        registry = get_registry()
        before = registry.snapshot()
        trace_dir = os.path.join(root, ".shufflebench", "trace")
        if args.trace:
            shutil.rmtree(trace_dir, ignore_errors=True)
            opts = jax.profiler.ProfileOptions()
            opts.python_tracer_level = 0
            opts.host_tracer_level = 1
            jax.profiler.start_trace(trace_dir, profiler_options=opts)
        jobs = []
        with spans.span("window"):
            t_w0 = time.perf_counter()
            c0 = compiles.count
            while True:
                jobs.append(dep.run_job(len(jobs)))
                if time.perf_counter() - t_w0 >= args.seconds:
                    break
            t_w1 = time.perf_counter()
            in_window = compiles.count - c0
        if args.trace:
            jax.profiler.stop_trace()
        counters = snapshot_delta(before, registry.snapshot())
        mem["window"] = memory(devices)  # before the deployment is freed
        dep.read_back(jobs[-1])
    finally:
        compiles.close()
        dep.stop()

    setup_s = t_w0 - T_PROCESS
    peak = mem["window"]["peak_bytes_in_use"]
    tasks = [t for j in jobs for t in j.task_ms]
    view = RunView(cell, (t_w0, t_w1), list(spans.items), jobs, counters,
                   None, peaks, in_window, cell.chips)
    pulled = sum(j.pulled for j in jobs)
    blocks = sum(j.blocks for j in jobs)
    emit({"record": "window", "device": device, "jobs": len(jobs),
          "reduce_tasks": len(tasks), "window_s": t_w1 - t_w0,
          "setup_s": setup_s, "setup_parts": setup_parts,
          "warmup_jobs": WARMUP_JOBS, "warmup_compiles": warm_compiles,
          "compiles_in_window": in_window, "blocks": blocks,
          "device_plane_pulls": pulled, "host_path_blocks": blocks - pulled,
          "plane_fallbacks": view.counter("device_fetch.plane.fallbacks"),
          "memory": mem,
          "job_s": [j.t1 - j.t0 for j in jobs]})

    verdict = compare(keys, jobs)
    window_s = t_w1 - t_w0
    e2e = {
        "sort_gbps": lambda: len(jobs) * keys.nbytes / window_s / 1e9,
        "reduce_task_p95_ms": lambda: p95(tasks),
        "hbm_peak_gib": lambda: peak / 2**30,
        "setup_s": lambda: setup_s,
    }
    result = {"correct": verdict["correct"], "attempted": len(tasks),
              "failed": verdict["failed"], "metrics": {}, "device": device}
    if not args.trace:
        for m in cell.end_to_end:
            result["metrics"][m["name"]] = {"value": e2e[m["name"]](),
                                            "unit": m["unit"]}
    else:
        from shufflebench.trace import load

        view.trace = load(trace_dir)
        lo, hi = view.trace.window()
        result["device"]["busy_s"] = view.trace.busy_s()
        result["device"]["window_s"] = (hi - lo) / 1e9
        for m in cell.per_layer:
            value = spec.load_reader(m["name"], harness_dir)(view)
            if value is not None:
                result["metrics"][m["name"]] = {"value": value,
                                                "unit": m["unit"]}
        result["breakdown"] = {"device_ops": view.trace.top_ops(),
                               "idle_gaps": view.trace.idle_gaps()}
    result["device"]["memory_peak_bytes"] = peak
    result["checks"] = verdict["checks"]
    emit({"record": "checked", "device": device,
          "checked_outputs": verdict["checked_outputs"],
          "jobs": len(jobs), "reduce_tasks": len(tasks)})
    print(json.dumps(result), flush=True)
    for name, c in verdict["checks"].items():
        print(f"check {name} {c['value']} limit {c['limit']}",
              file=sys.stderr, flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
