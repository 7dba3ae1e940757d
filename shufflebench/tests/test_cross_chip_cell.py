"""The four-chip cell ``isD-x4-r64`` on the CPU: its files run through
the command at a tiny size on four CPU devices, the control and a
dropped exchange fail it, and its three readers of the cross-chip
movers read what the program counts, or nothing from a program that
lacks the counters."""

import json
import os
import subprocess
import sys
import types

import pytest

from shufflebench import spec, trace
from shufflebench.run import RunView

REPO = spec.ROOT
CELL = "isD-x4-r64"
READERS = ("mover_ici_roofline", "fetch.ici_payload_pct",
           "fetch.wave_mesh_bytes_per_payload")

# one process, four CPU devices: each run of the command, its result
# line tagged with the fault it ran under
_RUNS = """
import json, sys
sys.path.insert(0, {repo!r})
from shufflebench import run
for fault in (None, "drop_half", "merge_unsorted"):
    argv = ["--workload", {cell!r}, "--seed", str(2**31 + 29),
            "--seconds", "0.3"] + (["--fault", fault] if fault else [])
    print(json.dumps({{"fault": fault}}), flush=True)
    assert run.main(argv, root={root!r}, require_chip=False) == 0
"""


def _cpu_root(tmp_path):
    """A checkout-like root holding the cell as the benchmark declares
    it, its configuration cut to 2^16 keys (MAX_KEY unchanged)."""
    bench = json.load(open(os.path.join(REPO, "BENCHMARK.json")))
    (entry,) = [w for w in bench["workloads"] if w["name"] == CELL]
    (cfg_entry,) = [c for c in bench["configs"]
                    if c["name"] == entry["config"]]
    cfg = json.load(open(os.path.join(REPO, cfg_entry["file"])))
    assert (cfg["keys"], cfg["max_key"], cfg["chips"]) == (
        1 << 28, 1 << 27, entry["chips"])
    cfg["keys"] = 1 << 16
    root = tmp_path / "root"
    (root / "cfg").mkdir(parents=True)
    (root / "cfg" / "cut.json").write_text(json.dumps(cfg))
    cfg_entry["file"] = "cfg/cut.json"
    (root / "BENCHMARK.json").write_text(json.dumps(bench))
    return str(root)


def test_the_cell_runs_on_four_cpu_devices_and_its_faults_fail_it(
        tmp_path):
    root = _cpu_root(tmp_path)
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               XLA_FLAGS="--xla_force_host_platform_device_count=4")
    p = subprocess.run(
        [sys.executable, "-c",
         _RUNS.format(repo=REPO, cell=CELL, root=root)],
        cwd=REPO, env=env, capture_output=True, text=True, timeout=600)
    assert p.returncode == 0, p.stderr[-4000:]
    results, fault = {}, "none"
    for line in p.stdout.splitlines():
        rec = json.loads(line)
        if "fault" in rec and len(rec) == 1:
            fault = rec["fault"] or "none"
        elif "correct" in rec:
            results[fault] = rec
        elif rec.get("record") == "device":
            assert rec["device"] == {"platform": "cpu", "kind": "cpu",
                                     "count": 4}
    assert set(results) == {"none", "drop_half", "merge_unsorted"}
    ok = results["none"]
    assert ok["correct"] is True and ok["failed"] == 0
    assert ok["attempted"] % 64 == 0 and ok["attempted"] >= 64
    assert set(ok["metrics"]) == {"sort_gbps", "hbm_peak_gib", "setup_s"}
    for fault in ("drop_half", "merge_unsorted"):
        bad = results[fault]
        assert bad["correct"] is False and bad["failed"] > 0, fault
        assert bad["checks"]["reducers_wrong"]["value"] > 0, fault


def test_the_cell_takes_four_chips_and_its_three_readers():
    cell = spec.load_cell(CELL)
    assert cell.chips == 4 and cell.traffic["reducers"] == 64
    names = [m["name"] for m in cell.per_layer]
    assert all(r in names for r in READERS)
    assert "mover_roofline" not in names
    for other in ("isC-r8", "isC-r200"):
        assert not set(READERS) & {
            m["name"] for m in spec.load_cell(other).per_layer}


def _view(counters=None, tr=None, chips=4, peaks=None):
    return RunView(
        cell=None, window=(0.0, 10.0), spans=[],
        jobs=[types.SimpleNamespace(blocks=256)] * 2,
        counters={"counters": counters or {}, "gauges": {},
                  "histograms": {}},
        trace=tr, peaks=peaks, compiles_in_window=0, chips=chips)


def _mesh_trace(kernel_ns):
    """A four-chip trace whose chips ran the wave movers for
    ``kernel_ns`` each, beside another op."""
    devices = []
    for k, ns in enumerate(kernel_ns):
        devices.append(trace.DevicePlane(f"/device:TPU:{k}", ops=[
            ("pallas_wave_pull", 1e6, 1e6 + ns),
            ("jit_shufflebench_merge", 2e8, 3e8)]))
    return trace.Trace(devices, [("sb.window", 0.0, 1e10)])


COUNTERS = {
    "collective.ici_payload_bytes{role=sb-exec-0}": 300e6,
    "collective.ici_payload_bytes{role=sb-exec-1}": 100e6,
    "collective.ici_moved_bytes{role=sb-exec-0}": 400e6,
    "collective.ici_moved_bytes{role=sb-exec-1}": 100e6,
    "collective.wave_mesh_bytes{role=sb-exec-0}": 600e6,
    "device_fetch.plane.bytes{role=sb-exec-0}": 400e6,
    "device_fetch.plane.bytes{role=sb-exec-1}": 100e6,
}


@pytest.mark.parametrize("name,want", [
    ("fetch.ici_payload_pct", 80.0),
    ("fetch.wave_mesh_bytes_per_payload", 1.2),
    # 400 MB at 200 GB/s is 2 ms, over a mean of 4 ms a chip
    ("mover_ici_roofline", 50.0),
])
def test_each_reader_on_a_run_view(name, want):
    peaks = spec.peaks("TPU v5 lite")
    view = _view(COUNTERS, _mesh_trace([2e6, 4e6, 5e6, 5e6]), peaks=peaks)
    assert spec.load_reader(name)(view) == pytest.approx(want)


@pytest.mark.parametrize("name", READERS)
def test_a_program_without_the_counters_reads_nothing(name):
    # the parent of the cross-chip counters: the same trace and plane
    # bytes, none of the collective.ici_* or wave_mesh counters
    counters = {k: v for k, v in COUNTERS.items()
                if k.startswith("device_fetch.")}
    view = _view(counters, _mesh_trace([4e6] * 4),
                 peaks=spec.peaks("TPU v5 lite"))
    assert spec.load_reader(name)(view) is None


def test_the_ici_roofline_needs_a_mesh_a_trace_and_peaks():
    read = spec.load_reader("mover_ici_roofline")
    peaks = spec.peaks("TPU v5 lite")
    tr = _mesh_trace([4e6] * 4)
    assert read(_view(COUNTERS, tr, chips=1, peaks=peaks)) is None
    assert read(_view(COUNTERS, None, peaks=peaks)) is None
    assert read(_view(COUNTERS, tr, peaks=None)) is None
    assert read(_view(COUNTERS, _mesh_trace([0, 0, 0, 0]),
                      peaks=peaks)) is None
