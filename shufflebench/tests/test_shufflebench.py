"""Tests of the benchmark harness, on the CPU at tiny sizes.

The trace reducer, the byte counts, the key generator, discovery by
name, the job driver against the numpy reference, the control and each
fault the cells can have, and the command's refusal of a CPU.
"""

import json
import os
import shutil
import subprocess
import sys

import numpy as np
import pytest

from shufflebench import keys as sbkeys
from shufflebench import reference, roofline, spec, trace
from shufflebench.job import FAULTS, Deployment

HARNESS = spec.HARNESS_DIR
REPO = spec.ROOT


# ----------------------------------------------------------------------
# trace reduction
# ----------------------------------------------------------------------
def test_union_gaps_busy_and_names():
    ev = [("a", 0, 10), ("b", 5, 20), ("a", 30, 40), ("c", 50, 55)]
    assert trace.union([(s, e) for _, s, e in ev]) == [
        (0, 20), (30, 40), (50, 55)]
    assert trace.busy_ns(ev, 0, 60) == 35
    assert trace.busy_ns(ev, 15, 35) == 10
    assert trace.gaps(ev, 0, 60) == [(20, 30), (40, 50), (55, 60)]
    assert trace.name_time_ns(ev, 0, 60, "a") == 20
    spans = [("sb.job", 0, 100), ("sb.fetch", 18, 32)]
    assert trace.innermost(spans, 25) == "sb.fetch"
    assert trace.innermost(spans, 45) == "sb.job"
    assert trace.innermost(spans, 200) == "no span"


def test_trace_reducer_on_a_recorded_trace(tmp_path):
    import jax
    import jax.numpy as jnp

    @jax.jit
    def shufflebench_sort(x):
        return jnp.sort(x)

    x = jnp.arange(1 << 18, dtype=jnp.uint32)[::-1]
    shufflebench_sort(x).block_until_ready()
    jax.profiler.start_trace(str(tmp_path))
    with jax.profiler.TraceAnnotation("sb.window"):
        for _ in range(3):
            with jax.profiler.TraceAnnotation("sb.reduce.merge"):
                shufflebench_sort(x).block_until_ready()
            with jax.profiler.TraceAnnotation("sb.idle"):
                np.sort(np.asarray(x))
    jax.profiler.stop_trace()
    # the CPU backend has no device plane: its XLA worker threads on the
    # host plane stand in for one
    t = trace.load(str(tmp_path),
                   device_plane=lambda n: n == "/host:CPU",
                   op_line=lambda n: n.startswith("tf_XLA"),
                   module_line=lambda n: n.startswith("tf_XLA"))
    lo, hi = t.window()
    window_s = (hi - lo) / 1e9
    assert 0 < t.busy_s() <= window_s
    assert 0 < t.kernel_s("sort", ops=True) <= t.busy_s() * 64
    gaps = dict((n, s) for n, s in t.idle_gaps())
    assert sum(gaps.values()) == pytest.approx(window_s - t.busy_s())
    assert "sb.idle" in gaps
    assert t.top_ops()[0][0].startswith("sort")


# ----------------------------------------------------------------------
# byte counts and roofline shares
# ----------------------------------------------------------------------
def test_byte_counts_and_shares():
    peaks = spec.peaks("TPU v5 lite")
    assert peaks["hbm_bytes_per_s"] == 819e9
    assert peaks["ici_bytes_per_s"] == 200e9
    assert roofline.sort_bytes(1 << 20) == 8 << 20
    assert roofline.merge_bytes(3) == 24
    assert roofline.mover_seconds(819e9, peaks) == pytest.approx(2.0)
    assert roofline.share_pct(1.0, 4.0) == 25.0
    assert roofline.share_pct(1.0, 0.0) is None
    with pytest.raises(SystemExit):
        spec.peaks("TPU v9 imaginary")


# ----------------------------------------------------------------------
# keys and edges
# ----------------------------------------------------------------------
def test_key_distribution_counts_match_brute_force():
    import itertools

    m = 8
    for draws in (1, 2, 4):
        keys = [sum(t) // draws
                for t in itertools.product(range(m), repeat=draws)]
        for e in range(m + 1):
            assert sbkeys._below(e, m, draws) == sum(k < e for k in keys)


def test_npb_input_is_seeded_in_range_bell_shaped_and_sized_alike():
    cfg = {"keys": 1 << 20, "max_key": 1 << 16, "maps": 2}
    traffic = {"reducers": 8, "edges": "quantile",
               "key_distribution": "npb_is"}
    big = 2**31 + 977
    assert sbkeys.make_input(cfg, traffic, 3)[0].dtype == np.uint32
    a, edges, counts = sbkeys.make_input(cfg, traffic, big)
    assert np.array_equal(a, sbkeys.make_input(cfg, traffic, big)[0])
    # seeds beyond 32 bits are not folded onto smaller ones
    b = sbkeys.make_input(cfg, traffic, big + 2**32)[0]
    assert not np.array_equal(a, b)
    assert a.dtype == np.uint32 and a.max() < cfg["max_key"]
    assert abs(a.mean() / (cfg["max_key"] / 2) - 1) < 0.01
    # the mean of four uniforms (sd 0.144): about 92% of keys in the
    # middle half, against 50% for uniform keys
    mid = np.mean((a >= 1 << 14) & (a < 3 << 14))
    assert 0.90 < mid < 0.93
    # every seed: each map holds the same count in each reducer range
    assert list(counts) == [1 << 16] * 8
    for keys in (a, b):
        for shard in np.split(keys, 2):
            got = np.bincount(np.searchsorted(edges, shard, side="right"),
                              minlength=8)
            assert np.array_equal(got, counts)
        # and the input is not grouped by range
        assert np.mean(np.diff(np.searchsorted(edges, keys[:4096],
                                               side="right")) != 0) > 0.5


def test_edge_rules_and_shares():
    e, shares = sbkeys.edges_and_shares(
        {"reducers": 200, "edges": "quantile", "key_distribution": "npb_is"},
        1 << 23, 2)
    assert len(e) == 199 == len(shares) - 1
    assert np.all(np.diff(e.astype(np.int64)) > 0)
    assert sum(shares) == pytest.approx(1.0)
    assert max(shares) - min(shares) < 1e-5
    sampled = {"reducers": 8, "edges": "sampled",
               "sample_points_per_reducer": 20, "sample_seed": 0,
               "key_distribution": "npb_is"}
    e, shares = sbkeys.edges_and_shares(sampled, 1 << 23, 2)
    # the same bounds for every run, as uneven as 480 sample points
    # make them
    assert np.array_equal(e, sbkeys.edges_and_shares(sampled, 1 << 23, 2)[0])
    assert sum(shares) == pytest.approx(1.0)
    assert 0.05 < max(shares) - min(shares) < 0.1
    # RangePartitioner's bounds are sample points; every reducer after
    # the first starts one past a bound
    rng = np.random.default_rng(0)
    sample = np.sort(sbkeys.draw_keys(rng, 3 * 20 * 8, 1 << 23, 4))
    assert set((e - 1).tolist()) <= set(sample.tolist())
    counts = sbkeys.range_counts(1 << 20, sampled, shares)
    assert counts.sum() == 1 << 20
    assert np.all(np.abs(counts - np.asarray(shares) * (1 << 20)) < 1)
    cfg = {"keys": 1 << 18, "max_key": 1 << 12, "maps": 1}
    k, e, counts = sbkeys.make_input(cfg, dict(sampled, reducers=4), 3)
    assert np.array_equal(np.bincount(np.searchsorted(e, k, side="right"),
                                      minlength=4), counts)
    with pytest.raises(ValueError):
        sbkeys.edges_and_shares(dict(sampled, edges="equal_width"),
                                1 << 12, 1)


# ----------------------------------------------------------------------
# discovery: new files under new names, no file edited
# ----------------------------------------------------------------------
def _tiny_root(tmp_path, harness_copy=False):
    """A checkout-like root with tiny one-chip cells, and (optionally)
    its own harness directory."""
    root = tmp_path / "root"
    (root / "cfg").mkdir(parents=True)
    (root / "cfg" / "tiny.json").write_text(json.dumps(
        {"keys": 1 << 16, "max_key": 1 << 14, "maps": 2, "executors": 2,
         "chips": 1}))
    bench = json.load(open(os.path.join(REPO, "BENCHMARK.json")))
    bench["configs"] = [
        {"name": "tiny", "source": "test", "file": "cfg/tiny.json",
         "reduced": [], "why": "test"}]
    bench["workloads"] = [
        {"name": "t-r8", "config": "tiny", "traffic": "sampled-r8",
         "chips": 1, "why": "test"},
        {"name": "t-r200", "config": "tiny", "traffic": "quantile-r200",
         "chips": 1, "why": "test"}]
    for m in bench["end_to_end"] + bench["per_layer"]:
        if "workloads" in m:
            m["workloads"] = ["t-r200"]
    (root / "BENCHMARK.json").write_text(json.dumps(bench))
    harness = HARNESS
    if harness_copy:
        harness = str(root / "harness")
        for sub in ("traffic", "metrics"):
            shutil.copytree(os.path.join(HARNESS, sub),
                            os.path.join(harness, sub))
        shutil.copy(os.path.join(HARNESS, "peaks.json"), harness)
    return str(root), harness


def test_new_config_traffic_metric_and_cell_are_found_by_name(tmp_path):
    root, harness = _tiny_root(tmp_path, harness_copy=True)
    before = {p: open(p).read() for p in
              [os.path.join(harness, "traffic", f)
               for f in os.listdir(os.path.join(harness, "traffic"))]}
    # only new files, and a new entry in BENCHMARK.json
    with open(os.path.join(root, "cfg", "other.json"), "w") as f:
        json.dump({"keys": 4096, "max_key": 1024, "maps": 1,
                   "executors": 1, "chips": 1}, f)
    with open(os.path.join(harness, "traffic", "s-r3.json"), "w") as f:
        json.dump({"reducers": 3, "edges": "sampled",
                   "sample_points_per_reducer": 60, "sample_seed": 9,
                   "key_distribution": "npb_is", "jobs_in_flight": 1}, f)
    with open(os.path.join(harness, "metrics", "new.metric.py"), "w") as f:
        f.write("def read(run):\n    return 42.0\n")
    bench = json.load(open(os.path.join(root, "BENCHMARK.json")))
    bench["configs"].append({"name": "other", "source": "test",
                             "file": "cfg/other.json", "reduced": [],
                             "why": "test"})
    bench["workloads"].append({"name": "o-s3", "config": "other",
                               "traffic": "s-r3", "chips": 1,
                               "why": "test"})
    bench["per_layer"].append({"name": "new.metric", "unit": "%",
                               "better": "higher", "source": "host_clock",
                               "layer": "device", "moves": "sort_gbps",
                               "workloads": ["o-s3"]})
    with open(os.path.join(root, "BENCHMARK.json"), "w") as f:
        json.dump(bench, f)
    cell = spec.load_cell("o-s3", root, harness)
    assert cell.config["keys"] == 4096 and cell.traffic["reducers"] == 3
    names = [m["name"] for m in cell.per_layer]
    assert "new.metric" in names and "map.busy_share" in names
    assert spec.load_reader("new.metric", harness)(None) == 42.0
    assert "new.metric" not in [m["name"] for m in
                                spec.load_cell("t-r8", root, harness)
                                .per_layer]
    assert [m["name"] for m in spec.load_cell("t-r200", root, harness)
            .end_to_end].count("reduce_task_p95_ms") == 1
    assert "reduce_task_p95_ms" not in [
        m["name"] for m in spec.load_cell("t-r8", root, harness).end_to_end]
    for p, text in before.items():
        assert open(p).read() == text


def test_every_benchmark_metric_has_a_reader_and_every_cell_its_files():
    bench = json.load(open(os.path.join(REPO, "BENCHMARK.json")))
    for m in bench["per_layer"]:
        assert callable(spec.load_reader(m["name"]))
    for w in bench["workloads"]:
        cell = spec.load_cell(w["name"])
        assert cell.config["chips"] == w["chips"]
        assert cell.per_layer and cell.end_to_end


# ----------------------------------------------------------------------
# the job driver against the reference
# ----------------------------------------------------------------------
@pytest.mark.parametrize("workload", ["t-r8", "t-r200"])
def test_job_driver_matches_the_numpy_reference(tmp_path, workload):
    import jax

    root, harness = _tiny_root(tmp_path)
    cell = spec.load_cell(workload, root, harness)
    cfg = cell.config
    k, edges, _ = sbkeys.make_input(cfg, cell.traffic, 11)
    dep = Deployment(cfg, cell.traffic, jax.devices()[: cell.chips], 11, k,
                     edges)
    try:
        jobs = [dep.run_job(i) for i in range(2)]
        # the newest job's checked outputs stay on the device until the
        # run reads them back, after its window
        held = len(dep.held)
        dep.read_back(jobs[-1])
    finally:
        dep.stop()
    reducers = cell.traffic["reducers"]
    assert held == len(jobs[-1].kept) == max(1, reducers // 8)
    assert dep.held == {} and jobs[0].kept == {}
    for job in jobs:
        assert sorted(job.digests) == list(range(reducers))
        assert sum(int(d[reference.TOTAL]) for d in job.digests.values()
                   ) == cfg["keys"]
        assert len(job.task_ms) == reducers
        # blocks under deviceFetch.minBlockBytes (16 KiB) take the host
        # path at this tiny size
        assert job.blocks == cfg["maps"] * reducers
        assert job.pulled <= job.blocks
    verdict = reference.compare(k, jobs)
    assert verdict["correct"], verdict
    assert verdict["checks"]["reducers_wrong"]["value"] == 0
    assert verdict["checks"]["keys_wrong"]["value"] == 0
    assert verdict["checked_outputs"] == max(1, reducers // 8)
    # every field of a digest is held to the reference
    for field, value in ((reference.TOTAL, 0), (reference.KEYS, 0),
                         (reference.STRAY, 1), (reference.SUM, 0),
                         (reference.XOR, 0), (reference.LEAST, 0),
                         (reference.LARGEST, 2**32 - 1),
                         (reference.DESCENTS, 1)):
        saved = jobs[0].digests[1]
        jobs[0].digests[1] = saved.copy()
        jobs[0].digests[1][field] = value
        assert reference.compare(k, jobs)["checks"]["reducers_wrong"][
            "value"] == 1, field
        jobs[0].digests[1] = saved


def _run_main(root, harness, argv, capsys):
    from shufflebench import run

    rc = run.main(argv, root=root, harness_dir=harness, require_chip=False)
    out, err = capsys.readouterr()
    return rc, out.strip().splitlines(), err.strip().splitlines()


def test_a_run_on_cpu_prints_its_result_and_checks_last(tmp_path, capsys):
    root, harness = _tiny_root(tmp_path)
    rc, out, err = _run_main(root, harness, [
        "--workload", "t-r200", "--seed", str(2**31 + 7), "--seconds",
        "0.5"], capsys)
    assert rc == 0
    result = json.loads(out[-1])
    assert list(result) == ["correct", "attempted", "failed", "metrics",
                            "device", "checks"]
    assert result["correct"] is True and result["failed"] == 0
    assert result["attempted"] % 200 == 0 and result["attempted"] >= 200
    assert set(result["metrics"]) == {"sort_gbps", "reduce_task_p95_ms",
                                      "hbm_peak_gib", "setup_s"}
    for line in out[:-1]:
        rec = json.loads(line)
        assert rec["device"]["platform"] == "cpu"
    assert err[-2:] == ["check reducers_wrong 0 limit 0",
                        "check keys_wrong 0 limit 0"]


# ----------------------------------------------------------------------
# the control and the faults: correct must come out false
# ----------------------------------------------------------------------
@pytest.mark.parametrize("workload", ["t-r8", "t-r200"])
@pytest.mark.parametrize("fault", FAULTS)
def test_a_broken_timed_path_is_not_correct(tmp_path, capsys, fault,
                                            workload):
    """``merge_unsorted`` is the control (the merge's sort left out, so
    the order guarantee breaks) and the step that returns its state
    unchanged; ``drop_half`` leaves half of each reducer's blocks out;
    ``local_only`` leaves out the exchange between executors;
    ``alter_key`` alters one key of every merged output
    where it is produced."""
    root, harness = _tiny_root(tmp_path)
    rc, out, err = _run_main(root, harness, [
        "--workload", workload, "--seed", "5", "--seconds", "0.3",
        "--fault", fault], capsys)
    assert rc == 0
    result = json.loads(out[-1])
    assert result["correct"] is False
    assert result["failed"] > 0
    wrong = {c: v["value"] for c, v in result["checks"].items()}
    # every reducer's digest and the read-back sample both catch it
    assert wrong["reducers_wrong"] > 0 and wrong["keys_wrong"] > 0


# ----------------------------------------------------------------------
# the command refuses what is not a chip
# ----------------------------------------------------------------------
def test_the_command_refuses_a_cpu():
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    p = subprocess.run(
        [sys.executable, "shufflebench/run.py", "--workload", "isC-r8",
         "--seed", "1", "--seconds", "1"],
        cwd=REPO, env=env, capture_output=True, text=True, timeout=120)
    assert p.returncode != 0
    assert p.stdout.strip() == ""
    assert "no TPU" in p.stderr


def test_the_command_fails_without_the_program(tmp_path):
    shutil.copy(os.path.join(REPO, "BENCHMARK.json"), tmp_path)
    shutil.copytree(HARNESS, tmp_path / "shufflebench",
                    ignore=shutil.ignore_patterns("__pycache__"))
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    p = subprocess.run(
        [sys.executable, "shufflebench/run.py", "--workload", "isC-r8",
         "--seed", "1", "--seconds", "1"],
        cwd=tmp_path, env=env, capture_output=True, text=True, timeout=120)
    assert p.returncode != 0
    assert p.stdout.strip() == ""
    assert "No module named 'sparkrdma_tpu'" in p.stderr
