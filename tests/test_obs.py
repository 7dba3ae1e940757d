"""Unified observability layer: metrics registry units, span tracer
semantics, and the tier-1 e2e — a real cluster shuffle whose exported
Chrome trace carries one trace id across driver and executor roles,
with registry counters populated from every instrumented layer."""

import json
import subprocess
import sys
import threading

import pytest

from sparkrdma_tpu.obs import (
    MetricsRegistry,
    Tracer,
    get_registry,
    metric_key,
    mint_trace_id,
    to_chrome_trace,
)


# ---------------------------------------------------------------------------
# registry units (fresh instances — the global registry belongs to e2e)
# ---------------------------------------------------------------------------

def test_metric_key_canonical():
    assert metric_key("a.b", {}) == "a.b"
    assert metric_key("a.b", {"z": "1", "a": "2"}) == "a.b{a=2,z=1}"


def test_counter_get_or_create_and_inc():
    reg = MetricsRegistry()
    c1 = reg.counter("x.sends", role="e0")
    c2 = reg.counter("x.sends", role="e0")
    assert c1 is c2
    c1.inc()
    c1.inc(41)
    assert reg.snapshot()["counters"]["x.sends{role=e0}"] == 42


def test_kind_mismatch_raises():
    reg = MetricsRegistry()
    reg.counter("m")
    with pytest.raises(TypeError):
        reg.gauge("m")


def test_gauge_tracks_high_water_mark():
    reg = MetricsRegistry()
    g = reg.gauge("x.in_use")
    g.add(100)
    g.add(200)
    g.add(-250)
    snap = reg.snapshot()["gauges"]["x.in_use"]
    assert snap == {"value": 50, "hwm": 300}


def test_histogram_buckets_and_overflow():
    reg = MetricsRegistry()
    h = reg.histogram("x.ms", bounds=(1, 10, 100))
    for v in (0.5, 1.0, 9, 100, 101, 5000):
        h.observe(v)
    snap = reg.snapshot()["histograms"]["x.ms"]
    assert snap["count"] == 6
    assert snap["min"] == 0.5 and snap["max"] == 5000
    # bounds are inclusive upper edges; 1.0 -> le_1, 100 -> le_100
    assert snap["buckets"] == {"le_1": 2, "le_10": 1, "le_100": 1, "overflow": 2}


def test_snapshot_match_includes_unlabeled():
    """Role-filtered views keep process-global metrics (no role label)
    but exclude other roles'."""
    reg = MetricsRegistry()
    reg.counter("a.n", role="e0").inc()
    reg.counter("a.n", role="e1").inc()
    reg.counter("b.global").inc()
    snap = reg.snapshot(match={"role": "e0"})
    assert set(snap["counters"]) == {"a.n{role=e0}", "b.global"}


def test_delta_diffs_counters_and_histograms():
    reg = MetricsRegistry()
    c = reg.counter("d.n")
    h = reg.histogram("d.ms", bounds=(10,))
    c.inc(5)
    h.observe(3)
    prev = reg.snapshot()
    c.inc(7)
    h.observe(4)
    d = reg.delta(prev)
    assert d["counters"]["d.n"] == 7
    assert d["histograms"]["d.ms"]["count"] == 1
    assert d["histograms"]["d.ms"]["sum"] == pytest.approx(4.0)


def test_delta_after_reset_does_not_resurrect_totals():
    """Registry lifecycle for long-lived hubs (ISSUE 5 satellite): a
    moving-baseline delta taken across a reset() must apply the
    counter-reset rule — restart from the current value — instead of
    going negative or replaying pre-reset totals."""
    reg = MetricsRegistry()
    c = reg.counter("r.n")
    h = reg.histogram("r.ms", bounds=(10,))
    c.inc(5)
    h.observe(3)
    h.observe(7)
    prev = reg.snapshot()  # moving baseline: 5 / count 2
    reg.reset()
    c.inc(2)
    h.observe(1)
    d = reg.delta(prev)
    assert d["counters"]["r.n"] == 2
    assert d["histograms"]["r.ms"]["count"] == 1
    assert d["histograms"]["r.ms"]["sum"] == pytest.approx(1.0)
    # and the next interval, with the baseline advanced, diffs normally
    prev = reg.snapshot()
    c.inc(3)
    assert reg.delta(prev)["counters"]["r.n"] == 3


def test_registry_concurrent_get_or_create_and_inc():
    reg = MetricsRegistry()
    n_threads, per_thread = 8, 500

    def work():
        for i in range(per_thread):
            reg.counter("c.n", k=str(i % 5)).inc()

    threads = [threading.Thread(target=work) for _ in range(n_threads)]
    for t in threads:
        t.start()
    for t in threads:
        t.join()
    snap = reg.snapshot()["counters"]
    assert sum(snap.values()) == n_threads * per_thread
    assert len(snap) == 5


def test_to_json_round_trips():
    reg = MetricsRegistry()
    reg.counter("j.n", role="r").inc(3)
    doc = json.loads(reg.to_json(indent=1))
    assert doc["counters"]["j.n{role=r}"] == 3


# ---------------------------------------------------------------------------
# tracer units
# ---------------------------------------------------------------------------

def test_mint_trace_id_nonzero_63bit():
    for _ in range(100):
        t = mint_trace_id()
        assert 0 < t < (1 << 63)


def test_span_nesting_and_parent_ids():
    tr = Tracer(role="t-nest")
    with tr.span("outer", trace_id=7) as outer:
        with tr.span("inner") as inner:
            pass
    spans = {s.name: s for s in tr.spans()}
    assert spans["inner"].parent_id == spans["outer"].span_id
    # inner had no explicit id/binding: inherits the parent's trace
    assert spans["inner"].trace_id == 7
    assert spans["outer"].trace_id == 7
    assert spans["outer"].end >= spans["inner"].end >= spans["inner"].start


def test_binding_resolves_open_span_at_close():
    """The executor pattern: a span opens before the trace id arrives
    on the wire; the binding lands while it is open and the span still
    resolves it at close time."""
    tr = Tracer(role="t-bind")
    with tr.span("fetch", shuffle_id=3):
        tr.bind_shuffle(3, 99)
    assert tr.spans()[0].trace_id == 99


def test_disabled_tracer_records_nothing():
    tr = Tracer(role="t-off", enabled=False)
    with tr.span("x"):
        pass
    tr.record("y", 0.0, 1.0)
    assert tr.spans() == []


def test_max_spans_bounds_memory():
    tr = Tracer(role="t-cap", max_spans=100)
    for i in range(250):
        tr.record("s", float(i), float(i))
    spans = tr.spans()
    assert len(spans) == 100
    assert spans[0].start == 150.0  # oldest dropped


def test_chrome_trace_format():
    tr = Tracer(role="t-fmt")
    with tr.span("work", trace_id=0xAB, foo="bar"):
        pass
    doc = to_chrome_trace([tr])
    assert doc["displayTimeUnit"] == "ms"
    meta = [e for e in doc["traceEvents"] if e["ph"] == "M"]
    assert meta[0]["args"]["name"] == "t-fmt"
    ev = [e for e in doc["traceEvents"] if e["ph"] == "X"][0]
    assert ev["name"] == "work"
    assert ev["dur"] >= 0
    assert ev["args"]["trace_id"] == "0xab"
    assert ev["args"]["foo"] == "bar"
    json.dumps(doc)  # must be JSON-serializable as-is


# ---------------------------------------------------------------------------
# tier-1 e2e: cluster shuffle -> registry counters + cross-role trace
# ---------------------------------------------------------------------------

def test_cluster_shuffle_trace_and_registry(tmp_path):
    from sparkrdma_tpu.obs import export_chrome_trace
    from sparkrdma_tpu.shuffle.handle import BaseShuffleHandle, HashPartitioner
    from sparkrdma_tpu.shuffle.manager import TpuShuffleManager
    from sparkrdma_tpu.utils.config import TpuShuffleConf

    conf = TpuShuffleConf(
        {
            "tpu.shuffle.shuffleWriteMethod": "wrapper",
            "tpu.shuffle.shuffleWriteBlockSize": "65536",
            "tpu.shuffle.shuffleReadBlockSize": "65536",
            # reads land in pooled registered buffers, so the pool layer
            # is on the path: mapped delivery (the native transport's
            # default) lands them in page-cache mappings instead
            "tpu.shuffle.mappedFetch": "false",
        }
    )
    driver = TpuShuffleManager(conf, is_driver=True)
    ex0 = TpuShuffleManager(conf, is_driver=False, executor_id="obs-ex-0")
    ex1 = TpuShuffleManager(conf, is_driver=False, executor_id="obs-ex-1")
    shuffle_id = 7731  # unlikely to collide with other tests' bindings
    try:
        handle = BaseShuffleHandle(
            shuffle_id=shuffle_id, num_maps=2,
            partitioner=HashPartitioner(4),
        )
        driver.register_shuffle(handle)
        for map_id, ex in [(0, ex0), (1, ex1)]:
            w = ex.get_writer(handle, map_id)
            w.write(iter((f"k{i % 53}", i) for i in range(2000)))
            assert w.stop(True) is not None
        ex0.finalize_maps(shuffle_id)
        ex1.finalize_maps(shuffle_id)
        for ex, (lo, hi) in [(ex0, (0, 2)), (ex1, (2, 4))]:
            n = sum(1 for _ in ex.get_reader(handle, lo, hi).read())
            assert n > 0

        # -- satellite: manager snapshot surfaces reader-side metrics --
        snap0 = ex0.metrics_snapshot()
        sr = snap0["shuffle_read"]
        assert sr["remote_blocks"] > 0
        assert sr["local_blocks"] > 0
        assert sr["remote_bytes"] > 0
        assert sr["local_bytes"] > 0
        assert sr["records_read"] > 0

        # -- registry: counters present from every host layer ----------
        reg = get_registry().snapshot()
        counters = reg["counters"]

        def layer_total(prefix):
            return sum(v for k, v in counters.items() if k.startswith(prefix))

        assert layer_total("transport.sends") > 0
        assert layer_total("transport.recvs") > 0
        assert layer_total("rpc.messages") > 0
        assert layer_total("writer.map_outputs") > 0
        assert layer_total("writer.bytes_written") > 0
        assert layer_total("mempool.hits") + layer_total("mempool.misses") > 0
        assert layer_total("reader.remote_blocks") > 0
        # rpc handling latency histograms recorded per message type
        assert any(
            k.startswith("rpc.handle_ms") and v["count"] > 0
            for k, v in reg["histograms"].items()
        )
        # the role-filtered view the manager snapshot embeds
        role_counters = snap0["registry"]["counters"]
        assert any(k.startswith("writer.") for k in role_counters)
        assert all(
            "role=" not in k or "role=obs-ex-0" in k for k in role_counters
        )

        # -- trace: publish/resolve/fetch share one id across roles ----
        path = tmp_path / "trace.json"
        doc = export_chrome_trace(
            str(path), [driver.tracer, ex0.tracer, ex1.tracer]
        )
        on_disk = json.loads(path.read_text())
        assert on_disk == doc
        events = [e for e in doc["traceEvents"] if e.get("ph") == "X"]
        ours = [
            e for e in events
            if e["args"].get("shuffle_id") == shuffle_id
        ]
        by_phase = {}
        for e in ours:
            by_phase.setdefault(e["name"], []).append(e)
        for phase in ("shuffle.register", "shuffle.publish",
                      "shuffle.resolve", "shuffle.fetch"):
            assert by_phase.get(phase), f"no {phase} span for the shuffle"
        trace_id = driver.tracer.trace_for(shuffle_id)
        assert trace_id != 0
        want = f"{trace_id:#x}"
        correlated = [e for e in ours if e["args"].get("trace_id") == want]
        roles_sharing = {e["pid"] for e in correlated}
        assert len(roles_sharing) >= 2, (
            "trace id must correlate spans across driver and executor roles"
        )
        phases_sharing = {e["name"] for e in correlated}
        assert {"shuffle.resolve", "shuffle.fetch"} <= phases_sharing
    finally:
        ex0.stop()
        ex1.stop()
        driver.stop()


def test_metrics_snapshot_delta_between_runs():
    """delta() isolates one run's traffic from the process-global
    counters — the pattern bench artifacts use."""
    reg = get_registry()
    prev = reg.snapshot(prefix="obsdelta.")
    reg.counter("obsdelta.n").inc(3)
    d = reg.delta(prev, prefix="obsdelta.")
    assert d["counters"]["obsdelta.n"] == 3


# ---------------------------------------------------------------------------
# exchange-layer counters (jax; cpu platform)
# ---------------------------------------------------------------------------

def test_exchange_registry_counters():
    jax = pytest.importorskip("jax")
    import numpy as np
    from jax.sharding import Mesh

    from sparkrdma_tpu.ops.exchange import ExchangeProgram, pack_blocks

    prev = get_registry().snapshot(prefix="exchange.")
    mesh = Mesh(np.array(jax.devices()[:1]), ("exec",))
    prog = ExchangeProgram(mesh)
    send, counts = pack_blocks([b"abc"], 1024)
    prog.exchange(send, counts)
    d = get_registry().delta(prev, prefix="exchange.")
    assert d["counters"]["exchange.exchanges{schedule=a2a}"] == 1
    assert d["counters"]["exchange.bytes_sent{schedule=a2a}"] == 1024
    assert d["counters"]["exchange.bytes_received_valid{schedule=a2a}"] == 3
    # stats dict kept for back-compat mirrors the registry
    assert prog.stats["a2a"]["exchanges"] == 1


# ---------------------------------------------------------------------------
# CLI
# ---------------------------------------------------------------------------

def test_obs_cli_demo(tmp_path):
    trace_path = tmp_path / "cli_trace.json"
    out = subprocess.run(
        [sys.executable, "-m", "sparkrdma_tpu.obs", "--demo",
         "--trace-out", str(trace_path), "--indent", "0"],
        capture_output=True, text=True, timeout=120,
    )
    assert out.returncode == 0, out.stderr
    snap = json.loads(out.stdout)
    layers = {k.split(".")[0] for k in snap["counters"]}
    assert {"transport", "rpc", "writer", "mempool", "reader"} <= layers
    doc = json.loads(trace_path.read_text())
    names = {e["name"] for e in doc["traceEvents"] if e.get("ph") == "X"}
    assert {"shuffle.publish", "shuffle.resolve", "shuffle.fetch"} <= names
