"""The per-layer readers of the program's own spans and counters, on
hand-made run views and on a small recorded trace; and what each reads
from a program that lacks them: nothing."""

import types

import pytest

from shufflebench import program_trace, spec, trace
from shufflebench.run import RunView

NEW_METRICS = (
    "map.sort_transfer_ms_per_job", "map.stage_copy_ms_per_job",
    "map.stage_arena_ms_per_job", "fetch.assembly_ms_per_job",
    "fetch.resolve_ms_per_block", "hbm.slab_fill_pct",
    "device.idle_named_pct",
)


def _hist(count, total):
    return {"count": count, "sum": total, "min": 0, "max": 0}


def _view(histograms=None, counters=None, jobs=2, blocks=6, tr=None):
    job = types.SimpleNamespace(blocks=blocks)
    return RunView(
        cell=None, window=(0.0, 10.0), spans=[], jobs=[job] * jobs,
        counters={"counters": counters or {}, "gauges": {},
                  "histograms": histograms or {}},
        trace=tr, peaks=None, compiles_in_window=0, chips=1)


@pytest.fixture()
def program_view():
    return _view(histograms={
        "map.sort.pad_ms": _hist(2, 10.0),
        "map.sort.h2d_ms": _hist(2, 20.0),
        "map.sort.device_ms": _hist(2, 400.0),  # not a transfer
        "map.sort.d2h_ms": _hist(2, 30.0),
        "map.stage.copy_ms": _hist(6, 60.0),
        "map.stage.checksum_ms": _hist(6, 12.0),
        "map.stage.arena_ms": _hist(6, 90.0),
        "fetch.resolve_ms": _hist(2, 3.0),
        "fetch.plan_ms": _hist(2, 1.0),
        "fetch.wave.assemble_ms": _hist(4, 8.0),
        "fetch.wave.h2d_ms": _hist(4, 16.0),
        "fetch.wave.wait_ms": _hist(4, 100.0),  # device time, not host
        "fetch.wave.adopt_ms": _hist(4, 4.0),
        "rpc.handle_ms{role=driver,type=X}": _hist(1, 5.0),
    }, counters={
        "hbm.slab_payload_bytes": 615,
        "hbm.slab_bytes": 1000,
    })


@pytest.mark.parametrize("name,want", [
    ("map.sort_transfer_ms_per_job", (10 + 20 + 30) / 2),
    ("map.stage_copy_ms_per_job", (60 + 12) / 2),
    ("map.stage_arena_ms_per_job", 90 / 2),
    ("fetch.assembly_ms_per_job", (8 + 16 + 4) / 2),
    ("fetch.resolve_ms_per_block", 3 / 12),
    ("hbm.slab_fill_pct", 61.5),
])
def test_each_counter_reader_on_a_run_view(program_view, name, want):
    assert spec.load_reader(name)(program_view) == pytest.approx(want)


@pytest.mark.parametrize("name", NEW_METRICS)
def test_a_program_without_the_spans_reads_nothing(name):
    # the parent of this change: no such histograms, counters or
    # program spans, and a trace holding only the benchmark's own
    assert spec.load_reader(name)(_view()) is None


def test_histograms_without_observations_read_nothing():
    view = _view(histograms={"map.stage.arena_ms": _hist(0, 0.0)},
                 counters={"hbm.slab_payload_bytes": 0, "hbm.slab_bytes": 0})
    assert spec.load_reader("map.stage_arena_ms_per_job")(view) is None
    assert spec.load_reader("hbm.slab_fill_pct")(view) is None


def test_idle_gaps_are_named_by_their_midpoint():
    dev = trace.DevicePlane("/device:TPU:0", ops=[
        ("op", 10, 20), ("op", 40, 50), ("op", 70, 80)])
    t = trace.Trace([dev], [("sb.window", 0, 100)])
    # gaps: [0,10] mid 5, [20,40] mid 30, [50,70] mid 60, [80,100] mid 90
    spans = [("map.stage.copy", 0, 6), ("fetch.resolve", 25, 35),
             ("fetch.plan", 28, 29), ("fetch.wave.wait", 61, 95)]
    idle, named = program_trace.idle_named_ns(t, spans)
    assert idle == 10 + 20 + 20 + 20
    assert named == 10 + 20 + 0 + 20
    # two chips: summed, so the share is their mean weighted by idle
    t2 = trace.Trace([dev, trace.DevicePlane("/device:TPU:1", ops=[
        ("op", 0, 100)])], [("sb.window", 0, 100)])
    assert program_trace.idle_named_ns(t2, spans) == (70, 50)
    assert program_trace.idle_named_ns(t, []) == (70, 0)


def test_idle_named_pct_on_a_recorded_trace(tmp_path):
    import jax
    import jax.numpy as jnp
    import numpy as np

    from sparkrdma_tpu.obs import Tracer

    @jax.jit
    def shufflebench_sort(x):
        return jnp.sort(x)

    x = jnp.arange(1 << 18, dtype=jnp.uint32)[::-1]
    shufflebench_sort(x).block_until_ready()
    tracer = Tracer(role="sb-test")
    jax.profiler.start_trace(str(tmp_path))
    with jax.profiler.TraceAnnotation("sb.window"):
        for _ in range(3):
            shufflebench_sort(x).block_until_ready()
            with tracer.span("map.stage.copy"):
                np.sort(np.asarray(x))
            shufflebench_sort(x).block_until_ready()
            np.sort(np.asarray(x))  # idle time no program span names
    jax.profiler.stop_trace()
    t = trace.load(str(tmp_path),
                   device_plane=lambda n: n == "/host:CPU",
                   op_line=lambda n: n.startswith("tf_XLA"),
                   module_line=lambda n: n.startswith("tf_XLA"))
    window, spans = program_trace.load_spans(str(tmp_path))
    assert window == t.window()
    assert {n for n, _, _ in spans} == {"map.stage.copy"}
    pct = program_trace.idle_named_pct(_view(tr=t), str(tmp_path))
    assert 10 < pct < 90
    # a trace that is not the run's (a stale file) reads nothing
    stale = trace.Trace(t.devices, [("sb.window", 0, 1)])
    assert program_trace.idle_named_pct(_view(tr=stale),
                                        str(tmp_path)) is None
    assert program_trace.idle_named_pct(_view(tr=t),
                                        str(tmp_path / "none")) is None
