"""The program's own spans and span histograms, for the per-layer
readers.

The program enters each of its spans as a
``jax.profiler.TraceAnnotation`` that carries a ``role`` stat, so they
land on the ``.xplane.pb`` host plane on the device's clock; and it
times its inner spans on registry histograms named ``<span>_ms``.
``trace.load`` keeps only the benchmark's ``sb.`` events; this module
reads the program's spans from the same file. A program without these
spans or histograms reads as None, never as 0.
"""

from __future__ import annotations

import bisect
import glob
import os
from typing import List, Optional, Tuple

from shufflebench import spec
from shufflebench.trace import Event, Trace, gaps, union

# where ``run.py`` records a traced run's profile
TRACE_DIR = os.path.join(spec.ROOT, ".shufflebench", "trace")


def load_spans(trace_dir: str = TRACE_DIR
               ) -> Tuple[Optional[Tuple[float, float]], List[Event]]:
    """The ``sb.window`` of the newest ``.xplane.pb`` under
    ``trace_dir`` (None if it has none), and the program's spans in
    it: host events that carry a ``role`` stat."""
    from jax.profiler import ProfileData

    paths = sorted(glob.glob(os.path.join(trace_dir, "**", "*.xplane.pb"),
                             recursive=True), key=os.path.getmtime)
    if not paths:
        return None, []
    window, spans = None, []
    for plane in ProfileData.from_file(paths[-1]).planes:
        if not plane.name.startswith("/host:"):
            continue
        for line in plane.lines:
            for ev in line.events:
                if ev.name == "sb.window":
                    window = (ev.start_ns, ev.start_ns + ev.duration_ns)
                elif not ev.name.startswith("sb.") and any(
                        k == "role" for k, _ in ev.stats):
                    spans.append((ev.name, ev.start_ns,
                                  ev.start_ns + ev.duration_ns))
    return window, spans


def idle_named_ns(trace: Trace, spans: List[Event]) -> Tuple[float, float]:
    """Device-idle ns in the trace's window, summed over the chips, and
    the part of it in gaps whose midpoint lies inside some span."""
    lo, hi = trace.window()
    cover = union((s, e) for _, s, e in spans)
    starts = [s for s, _ in cover]
    idle = named = 0.0
    for d in trace.devices:
        for s, e in gaps(d.ops, lo, hi):
            idle += e - s
            mid = (s + e) / 2
            i = bisect.bisect_right(starts, mid) - 1
            if i >= 0 and cover[i][1] >= mid:
                named += e - s
    return idle, named


def idle_named_pct(run, trace_dir: str = TRACE_DIR) -> Optional[float]:
    """Share of the window's device-idle time named by a program span.
    None without a trace, without program spans, or when the file under
    ``trace_dir`` is not the trace the run loaded."""
    if run.trace is None:
        return None
    window, spans = load_spans(trace_dir)
    if not spans or window != tuple(run.trace.window()):
        return None
    idle, named = idle_named_ns(run.trace, spans)
    return 100.0 * named / idle if idle > 0 else None


def span_ms(run, *spans: str) -> Optional[float]:
    """Milliseconds summed over the window from the ``<span>_ms``
    histograms of ``spans``; None if none of them observed anything."""
    names = {s + "_ms" for s in spans}
    found = [h for k, h in run.counters["histograms"].items()
             if k.split("{")[0] in names and h["count"] > 0]
    if not found:
        return None
    return sum(h["sum"] for h in found)


def per_job(run, *spans: str) -> Optional[float]:
    ms = span_ms(run, *spans)
    return None if ms is None or not run.jobs else ms / len(run.jobs)
