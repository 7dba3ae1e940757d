"""Critical-path attribution engine: synthetic-DAG extraction units,
tracer causal-edge semantics (record parentage, epoch-skewed merges),
the tier-1 e2e — a real in-process cluster job whose TimeBreakdown
covers >= 90% of the job wall and whose Perfetto export carries the
cross-role publish -> resolve -> fetch flow chain — and the perf-trend
regression gate over the committed bench ledgers."""

import json
from pathlib import Path

import pytest

from sparkrdma_tpu.obs import Tracer, to_chrome_trace
from sparkrdma_tpu.obs.attr import attribute, classify
from sparkrdma_tpu.obs.critpath import PSpan, extract, spans_from_chrome
from sparkrdma_tpu.obs.trace import collect_spans_with_epochs

REPO_ROOT = Path(__file__).resolve().parents[1]


# ---------------------------------------------------------------------------
# synthetic DAG: the walk must follow explicit edges, not span length
# ---------------------------------------------------------------------------

def test_extract_prefers_causal_edges_over_long_spans():
    spans = [
        PSpan("shuffle.fetch", "e", 1, 0, 0.0, 4.0),
        PSpan("reader.pipeline.decode", "e", 2, 0, 4.0, 7.0, follows=[1]),
        PSpan("reader.pipeline.merge", "e", 3, 0, 7.0, 10.0, follows=[2]),
        # distractor: long concurrent span with no causal edges — a
        # naive "pick the longest" would attribute everything here
        PSpan("shuffle.push", "e", 4, 0, 0.0, 9.0),
    ]
    path = extract(spans, 0.0, 10.0)
    chain = [s.name for s in path.segments if s.kind == "span"]
    assert chain == [
        "shuffle.fetch", "reader.pipeline.decode", "reader.pipeline.merge",
    ]
    assert not [s for s in path.segments if s.kind == "gap"]
    assert path.coverage == pytest.approx(1.0)


def test_extract_emits_gap_segments_for_untraced_time():
    spans = [
        PSpan("shuffle.fetch", "e", 1, 0, 0.0, 2.0),
        PSpan("reader.pipeline.merge", "e", 2, 0, 7.0, 10.0),
    ]
    path = extract(spans, 0.0, 10.0)
    kinds = [(s.kind, round(s.t0, 6), round(s.t1, 6)) for s in path.segments]
    assert kinds == [("span", 0.0, 2.0), ("gap", 2.0, 7.0), ("span", 7.0, 10.0)]
    assert path.coverage == pytest.approx(0.5)
    # segments tile the window exactly — nothing double-counted or lost
    assert sum(s.dur_s for s in path.segments) == pytest.approx(path.wall_s)


def test_extract_untraced_tail_is_a_gap():
    """Nothing running at the window end: the tail must be accounted as
    idle, not silently dropped from the segment list."""
    spans = [PSpan("shuffle.fetch", "e", 1, 0, 0.0, 3.0)]
    path = extract(spans, 0.0, 10.0)
    assert sum(s.dur_s for s in path.segments) == pytest.approx(10.0)
    assert path.coverage == pytest.approx(0.3)


def test_attribute_folds_categories_with_known_longest_path():
    spans = [
        PSpan("engine.task", "d", 1, 0, 0.0, 5.0),
        PSpan("shuffle.fetch", "e", 2, 1, 5.0, 8.0, follows=[1]),
        PSpan("reader.pipeline.decode", "e", 3, 0, 8.0, 9.0, follows=[2]),
    ]
    bd = attribute(extract(spans, 0.0, 10.0))
    assert bd.wall_ms == pytest.approx(10_000.0)
    assert bd.categories["device-compute"] == pytest.approx(5_000.0)
    assert bd.categories["host-read"] == pytest.approx(3_000.0)
    assert bd.categories["decode"] == pytest.approx(1_000.0)
    assert bd.categories["idle-untraced"] == pytest.approx(1_000.0)
    assert bd.coverage == pytest.approx(0.9)
    assert sum(bd.categories.values()) == pytest.approx(bd.wall_ms)


def test_classify_longest_prefix_wins():
    assert classify("shuffle.fetch_request") == "rpc"
    assert classify("shuffle.fetch") == "host-read"
    assert classify("shuffle.collective.wave") == "dma-wave"
    assert classify("tenant.queue_wait") == "queue-wait"
    assert classify("something.novel") == "other"


# ---------------------------------------------------------------------------
# tracer causal-edge semantics
# ---------------------------------------------------------------------------

def test_record_attaches_contextvar_parent():
    tr = Tracer(role="t-rec-parent")
    with tr.span("outer", trace_id=5) as outer:
        child = tr.record("child", 0.0, 1.0)
    assert child.parent_id == outer.span_id
    assert child.trace_id == 5


def test_two_fake_epoch_tracers_merge_onto_one_timeline():
    """Spans from processes with different wall anchors normalize onto
    one axis: a span starting 1 s into a process whose epoch is 2000
    lands at wall 2001, after a span at 1005 from an epoch-1000 peer."""
    t_a = Tracer(role="epoch-a", epoch=1000.0)
    t_b = Tracer(role="epoch-b", epoch=2000.0)
    sp_a = t_a.record("shuffle.fetch", 5.0, 6.0)
    sp_b = t_b.record("reader.pipeline.decode", 1.0, 2.0)
    sp_b.add_follows(sp_a)
    pairs = collect_spans_with_epochs([t_a, t_b])
    assert pairs == [(sp_a, 1000.0), (sp_b, 2000.0)]
    path = extract(pairs, 1005.0, 2002.0)
    names = [(s.kind, s.name) for s in path.segments]
    assert ("span", "shuffle.fetch") in names
    assert ("span", "reader.pipeline.decode") in names
    # the decode span follows the fetch span across the epoch seam, so
    # the interval between them is one explicit gap, not a dead walk
    segs = path.segments
    assert segs[0].name == "shuffle.fetch"
    assert segs[-1].name == "reader.pipeline.decode"
    # override map re-anchors a role wholesale
    pairs2 = collect_spans_with_epochs([t_b], epochs={"epoch-b": 0.0})
    assert pairs2[0][1] == 0.0


def test_heartbeat_carries_epoch_anchor_to_hub():
    from sparkrdma_tpu.obs import get_registry
    from sparkrdma_tpu.obs.telemetry import Heartbeater, TelemetryHub
    from sparkrdma_tpu.obs.trace import epoch_anchor

    hub = TelemetryHub(role="t-epoch-hub", interval_ms=50)
    hb = Heartbeater(get_registry(), "epoch-exec", interval_ms=50,
                     send=hub.ingest)
    try:
        payload = hb.beat()
        assert payload is not None
        hub.ingest(payload)
        anchors = hub.epoch_anchors()
        assert anchors["epoch-exec"] == pytest.approx(
            epoch_anchor(), abs=0.01
        )
    finally:
        hub.stop()


# ---------------------------------------------------------------------------
# tier-1 e2e: real cluster job -> breakdown coverage + flow-event chain
# ---------------------------------------------------------------------------

@pytest.fixture(scope="module")
def job_artifacts():
    from sparkrdma_tpu.engine.context import TpuContext
    from sparkrdma_tpu.utils.config import TpuShuffleConf

    conf = TpuShuffleConf({})
    with TpuContext(num_executors=2, conf=conf, task_threads=4) as ctx:
        rdd = (
            ctx.parallelize(range(8000), 4)
            .map(lambda x: (x % 97, 1))
            .reduce_by_key(lambda a, b: a + b, num_partitions=4)
        )
        out = dict(ctx.run_job(rdd))
        bd = ctx.last_breakdown
        snap = ctx.metrics_snapshot()
        doc = to_chrome_trace()
    return {"out": out, "breakdown": bd, "snapshot": snap, "trace": doc}


def test_e2e_breakdown_covers_90pct_of_job_wall(job_artifacts):
    assert job_artifacts["out"][0] == 8000 // 97 + 1
    bd = job_artifacts["breakdown"]
    assert bd is not None
    assert bd.coverage >= 0.9, bd.render()
    traced_ms = sum(
        v for k, v in bd.categories.items() if k != "idle-untraced"
    )
    assert traced_ms >= 0.9 * bd.wall_ms, bd.render()
    # the verdict also rides the metrics snapshot for artifact embedding
    assert job_artifacts["snapshot"]["breakdown"]["coverage"] >= 0.9


def test_e2e_perfetto_has_cross_role_publish_resolve_fetch_chain(job_artifacts):
    doc = job_artifacts["trace"]
    pid_names = {
        e["pid"]: e["args"]["name"]
        for e in doc["traceEvents"]
        if e.get("ph") == "M" and e.get("name") == "process_name"
    }
    spans = {
        e["args"]["span_id"]: e
        for e in doc["traceEvents"]
        if e.get("ph") == "X" and (e.get("args") or {}).get("span_id")
    }
    edges = set()
    finish = 0
    for e in doc["traceEvents"]:
        if e.get("cat") != "critpath":
            continue
        if e.get("ph") == "f":
            finish += 1
            continue
        if e.get("ph") != "s":
            continue
        src = spans.get(e["args"]["from_span"])
        dst = spans.get(e["args"]["to_span"])
        if src and dst:
            edges.add((
                src["name"], pid_names.get(src["pid"]),
                dst["name"], pid_names.get(dst["pid"]),
            ))
    assert finish > 0  # every flow start pairs with a finish
    execs = {r for _, r, _, _ in edges} | {r for _, _, _, r in edges}
    assert any(r and r.startswith("exec-") for r in execs)
    # executor publish -> driver publish record (cross-role)
    assert any(
        s == "shuffle.publish" and sr != "driver"
        and d == "shuffle.publish" and dr == "driver"
        for s, sr, d, dr in edges
    ), edges
    # driver publish record -> driver resolve
    assert ("shuffle.publish", "driver", "shuffle.resolve", "driver") in edges
    # driver resolve -> executor fetch (cross-role)
    assert any(
        s == "shuffle.resolve" and sr == "driver"
        and d == "shuffle.fetch" and dr != "driver"
        for s, sr, d, dr in edges
    ), edges


def test_spans_from_chrome_round_trips_follows(job_artifacts):
    spans = spans_from_chrome(job_artifacts["trace"])
    by_name = {}
    for p in spans:
        by_name.setdefault(p.name, []).append(p)
    assert "job.run" in by_name
    resolves = by_name.get("shuffle.resolve", [])
    assert resolves and any(p.follows for p in resolves)


def test_critical_path_cli_over_saved_trace(job_artifacts, tmp_path, capsys):
    from sparkrdma_tpu.obs.__main__ import main as obs_main

    f = tmp_path / "trace.json"
    f.write_text(json.dumps(job_artifacts["trace"]))
    assert obs_main(["--critical-path", str(f)]) == 0
    out = capsys.readouterr().out
    assert "window: job.run span" in out
    assert "coverage" in out
    assert "top segments:" in out


def test_critical_path_cli_over_stored_breakdown(job_artifacts, tmp_path,
                                                 capsys):
    from sparkrdma_tpu.obs.__main__ import main as obs_main

    f = tmp_path / "artifact.json"
    f.write_text(json.dumps(
        {"workloads": [], "breakdown": job_artifacts["breakdown"].to_dict()}
    ))
    assert obs_main(["--critical-path", str(f)]) == 0
    out = capsys.readouterr().out
    assert "stored breakdown" in out


def test_critpath_knob_disables_attribution():
    from sparkrdma_tpu.engine.context import TpuContext
    from sparkrdma_tpu.utils.config import TpuShuffleConf

    conf = TpuShuffleConf({"tpu.shuffle.obs.critpath.enabled": "false"})
    assert conf.critpath_enabled is False
    with TpuContext(num_executors=1, conf=conf, task_threads=2) as ctx:
        rdd = ctx.parallelize(range(100), 2).map(lambda x: (x % 5, 1)) \
            .reduce_by_key(lambda a, b: a + b, num_partitions=2)
        ctx.run_job(rdd)
        assert ctx.last_breakdown is None


# ---------------------------------------------------------------------------
# perf-trend engine (obs/trend.py)
# ---------------------------------------------------------------------------

def _write(path: Path, doc: dict) -> None:
    path.write_text(json.dumps(doc))


def test_trend_covers_every_committed_bench_round():
    from sparkrdma_tpu.obs.trend import build_trend

    trend = build_trend(str(REPO_ROOT))
    committed = sorted(
        int(p.stem.split("_r")[1]) for p in REPO_ROOT.glob("BENCH_r*.json")
    )
    assert committed and trend["rounds"]["bench"] == committed
    assert not trend["errors"], trend["errors"]
    assert not trend["regressions"], trend["regressions"]
    assert trend["num_series"] > 100
    # every skip is loud: a row and a reason, never a silent drop
    assert all(s["row"] and s["reason"] for s in trend["skipped"])
    tracked = [
        n for n, t in trend["series"].items() if t.get("tracked")
    ]
    assert any("gbps" in n for n in tracked)


def test_trend_gate_fails_on_synthetic_regression(tmp_path):
    from sparkrdma_tpu.obs.trend import main as trend_main

    _write(tmp_path / "BENCH_r01.json",
           {"parsed": {"metric": "m", "read_gbps": 10.0}})
    _write(tmp_path / "BENCH_r02.json",
           {"parsed": {"metric": "m", "read_gbps": 4.0}})
    argv = ["--dir", str(tmp_path), "--out", str(tmp_path / "TREND.json"),
            "--md", str(tmp_path / "TREND.md"), "--check"]
    assert trend_main(argv) == 1
    trend = json.loads((tmp_path / "TREND.json").read_text())
    assert trend["regressions"][0]["series"] == "bench.read_gbps"


def test_trend_gate_fails_on_unclassifiable_row(tmp_path):
    from sparkrdma_tpu.obs.trend import main as trend_main

    _write(tmp_path / "BENCH_r01.json",
           {"parsed": {"mystery": "what is this"}})
    argv = ["--dir", str(tmp_path), "--out", str(tmp_path / "TREND.json"),
            "--md", str(tmp_path / "TREND.md"), "--check"]
    assert trend_main(argv) == 2


def test_trend_stale_series_chart_but_do_not_gate(tmp_path):
    from sparkrdma_tpu.obs.trend import build_trend, main as trend_main

    # a_gbps drops 60% between r01 and r02 but vanishes from the
    # newest round (r03) — historical fact, not an actionable gate
    _write(tmp_path / "BENCH_r01.json",
           {"parsed": {"a_gbps": 10.0, "b_gbps": 5.0}})
    _write(tmp_path / "BENCH_r02.json",
           {"parsed": {"a_gbps": 4.0, "b_gbps": 5.0}})
    _write(tmp_path / "BENCH_r03.json", {"parsed": {"b_gbps": 5.1}})
    argv = ["--dir", str(tmp_path), "--out", str(tmp_path / "TREND.json"),
            "--md", str(tmp_path / "TREND.md"), "--check"]
    assert trend_main(argv) == 0
    trend = build_trend(str(tmp_path))
    assert trend["series"]["bench.a_gbps"].get("stale") is True


def test_trend_rig_normalized_gate_forgives_slower_rig(tmp_path):
    from sparkrdma_tpu.obs.trend import build_trend, main as trend_main

    # the rig halved (probe 2.0 -> 1.0) and read_gbps halved with it:
    # the roofline fraction is flat, so nothing actionable regressed —
    # and the probe itself never gates (it measures the machine)
    _write(tmp_path / "BENCH_r01.json",
           {"parsed": {"read_gbps": 1.6, "exchange_loopback_gbps": 2.0}})
    _write(tmp_path / "BENCH_r02.json",
           {"parsed": {"read_gbps": 0.8, "exchange_loopback_gbps": 1.0}})
    argv = ["--dir", str(tmp_path), "--out", str(tmp_path / "TREND.json"),
            "--md", str(tmp_path / "TREND.md"), "--check"]
    assert trend_main(argv) == 0
    trend = build_trend(str(tmp_path))
    assert trend["series"]["bench.exchange_loopback_gbps"].get(
        "rig_probe") is True
    assert trend["series"]["bench.read_gbps"].get(
        "rel_delta_normalized") == 0.0


def test_trend_rig_normalized_gate_still_catches_code_regressions(tmp_path):
    from sparkrdma_tpu.obs.trend import main as trend_main

    # same rig both rounds (probe flat) but read_gbps dropped 60%:
    # normalization must not launder a genuine regression
    _write(tmp_path / "BENCH_r01.json",
           {"parsed": {"read_gbps": 1.6, "exchange_loopback_gbps": 2.0}})
    _write(tmp_path / "BENCH_r02.json",
           {"parsed": {"read_gbps": 0.64, "exchange_loopback_gbps": 2.0}})
    argv = ["--dir", str(tmp_path), "--out", str(tmp_path / "TREND.json"),
            "--md", str(tmp_path / "TREND.md"), "--check"]
    assert trend_main(argv) == 1
    trend = json.loads((tmp_path / "TREND.json").read_text())
    assert trend["regressions"][0]["series"] == "bench.read_gbps"
    assert trend["regressions"][0]["rig_normalized"] is True


def test_trend_flattens_workloads_and_soak(tmp_path):
    from sparkrdma_tpu.obs.trend import build_trend

    _write(tmp_path / "WORKLOADS_r01.json", {
        "generated_unix": 1, "scale": 0.1,
        "workloads": [
            {"workload": "pagerank", "seconds": 1.5, "records_per_s": 200},
            {"workload": "terasort_engine", "seconds": 2.0,
             "note": "free text", "breakdown": None},
        ],
    })
    _write(tmp_path / "SOAK_r01.json", {
        "args": {"seconds": 20},
        "ok": True,
        "checks": {"hwm_flat": True, "zero_job_failures": False},
    })
    trend = build_trend(str(tmp_path))
    s = trend["series"]
    assert s["workloads.pagerank.records_per_s"]["latest"] == 200
    assert s["workloads.terasort_engine.seconds"]["latest"] == 2.0
    assert s["soak.ok"]["latest"] == 1.0
    assert s["soak.checks.hwm_flat"]["latest"] == 1.0
    assert s["soak.checks.zero_job_failures"]["latest"] == 0.0
    assert not trend["errors"], trend["errors"]
    reasons = {x["reason"] for x in trend["skipped"]}
    assert "run-config" in reasons      # soak args subtree
    assert "string-metadata" in reasons  # the note field
