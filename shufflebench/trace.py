"""From the profiler's trace to device busy time, kernel time and the
longest idle gaps, named by what the host was doing.

The run wraps its window and each call into a layer in
``jax.profiler.TraceAnnotation("sb.<name>")``, so host spans and device
operations share the trace's clock. Device operations are the events of
the device planes' op lines; a kernel's time is the summed duration of
the module events whose name holds the kernel's name.
"""

from __future__ import annotations

import glob
import os
from dataclasses import dataclass, field
from typing import Callable, Dict, Iterable, List, Tuple

Event = Tuple[str, float, float]  # name, start ns, end ns


def union(intervals: Iterable[Tuple[float, float]]) -> List[Tuple[float, float]]:
    """Sorted, disjoint cover of ``intervals``."""
    out: List[List[float]] = []
    for s, e in sorted(intervals):
        if out and s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], e)
        else:
            out.append([s, e])
    return [(s, e) for s, e in out]


def clip(events: Iterable[Event], lo: float, hi: float) -> List[Event]:
    return [(n, max(s, lo), min(e, hi)) for n, s, e in events
            if e > lo and s < hi]


def busy_ns(events: Iterable[Event], lo: float, hi: float) -> float:
    return sum(e - s for s, e in union((s, e) for _, s, e in
                                        clip(events, lo, hi)))


def gaps(events: Iterable[Event], lo: float, hi: float
         ) -> List[Tuple[float, float]]:
    """The stretches of ``[lo, hi]`` in which no event runs."""
    out, t = [], lo
    for s, e in union((s, e) for _, s, e in clip(events, lo, hi)):
        if s > t:
            out.append((t, s))
        t = max(t, e)
    if hi > t:
        out.append((t, hi))
    return out


def name_time_ns(events: Iterable[Event], lo: float, hi: float,
                 contains: str) -> float:
    """Summed duration of the events whose name holds ``contains``."""
    return sum(e - s for n, s, e in clip(events, lo, hi) if contains in n)


def innermost(spans: List[Event], t: float) -> str:
    """The shortest host span covering ``t``, or ``"no span"``."""
    best, width = "no span", None
    for n, s, e in spans:
        if s <= t <= e and (width is None or e - s < width):
            best, width = n, e - s
    return best


@dataclass
class DevicePlane:
    name: str
    ops: List[Event] = field(default_factory=list)
    modules: List[Event] = field(default_factory=list)


@dataclass
class Trace:
    devices: List[DevicePlane]
    host_spans: List[Event]

    def window(self) -> Tuple[float, float]:
        w = [(s, e) for n, s, e in self.host_spans if n == "sb.window"]
        if not w:
            raise ValueError("trace holds no sb.window span")
        return w[0]

    def busy_s(self) -> float:
        """Device busy seconds in the window, averaged over the chips."""
        lo, hi = self.window()
        return sum(busy_ns(d.ops, lo, hi) for d in self.devices) / (
            len(self.devices) * 1e9)

    def kernel_s(self, contains: str, ops: bool = False) -> float:
        """Device seconds of the modules (or, with ``ops``, the
        operations) named with ``contains``, summed over the chips."""
        lo, hi = self.window()
        return sum(name_time_ns(d.ops if ops else d.modules, lo, hi,
                                contains) for d in self.devices) / 1e9

    def top_ops(self, k: int = 10) -> List[list]:
        lo, hi = self.window()
        tot: Dict[str, float] = {}
        for d in self.devices:
            for n, s, e in clip(d.modules or d.ops, lo, hi):
                tot[n] = tot.get(n, 0.0) + (e - s) / 1e9
        return [[n, v / len(self.devices)] for n, v in
                sorted(tot.items(), key=lambda kv: -kv[1])[:k]]

    def idle_gaps(self, k: int = 10) -> List[list]:
        """Idle device time in the window, by the host span that was
        innermost at each gap's middle, averaged over the chips."""
        lo, hi = self.window()
        spans = sorted((ev for ev in self.host_spans if ev[0] != "sb.window"),
                       key=lambda ev: ev[1])
        tot: Dict[str, float] = {}
        for d in self.devices:
            found = gaps(d.ops, lo, hi)
            # sweep the gaps in time order beside the spans that have
            # started, dropping those that have ended
            active, i = [], 0
            for s, e in found:
                t = (s + e) / 2
                while i < len(spans) and spans[i][1] <= t:
                    active.append(spans[i])
                    i += 1
                active = [sp for sp in active if sp[2] >= t]
                n = innermost(active, t)
                tot[n] = tot.get(n, 0.0) + (e - s) / 1e9
        return [[n, v / len(self.devices)] for n, v in
                sorted(tot.items(), key=lambda kv: -kv[1])[:k]]


def _is_tpu_plane(name: str) -> bool:
    return name.startswith("/device:TPU:") and "TPU Core" not in name


def load(trace_dir: str,
         device_plane: Callable[[str], bool] = _is_tpu_plane,
         op_line: Callable[[str], bool] = lambda n: n == "XLA Ops",
         module_line: Callable[[str], bool] = lambda n: n == "XLA Modules",
         ) -> Trace:
    """Read the newest ``.xplane.pb`` under ``trace_dir``."""
    from jax.profiler import ProfileData

    paths = sorted(glob.glob(os.path.join(trace_dir, "**", "*.xplane.pb"),
                             recursive=True), key=os.path.getmtime)
    if not paths:
        raise FileNotFoundError(f"no .xplane.pb under {trace_dir}")
    data = ProfileData.from_file(paths[-1])
    devices, host = [], []
    for plane in data.planes:
        if device_plane(plane.name):
            d = DevicePlane(plane.name)
            for line in plane.lines:
                dest = (d.ops if op_line(line.name) else
                        d.modules if module_line(line.name) else None)
                if dest is None:
                    continue
                for ev in line.events:
                    if ev.duration_ns > 0:
                        dest.append((ev.name, ev.start_ns,
                                     ev.start_ns + ev.duration_ns))
            devices.append(d)
        if plane.name.startswith("/host:"):
            for line in plane.lines:
                for ev in line.events:
                    if ev.name.startswith("sb."):
                        host.append((ev.name, ev.start_ns,
                                     ev.start_ns + ev.duration_ns))
    return Trace(devices, host)


def structure(trace_dir: str, per_line: int = 5) -> List[dict]:
    """Planes, lines, event counts and a few event names: what a reader
    of a new device's trace looks at first."""
    from jax.profiler import ProfileData

    paths = sorted(glob.glob(os.path.join(trace_dir, "**", "*.xplane.pb"),
                             recursive=True), key=os.path.getmtime)
    data = ProfileData.from_file(paths[-1])
    out = []
    for plane in data.planes:
        for line in plane.lines:
            evs = list(line.events)
            names: Dict[str, int] = {}
            for ev in evs:
                names[ev.name] = names.get(ev.name, 0) + 1
            out.append({
                "plane": plane.name, "line": line.name, "events": len(evs),
                "first_ns": evs[0].start_ns if evs else None,
                "top": sorted(names.items(), key=lambda kv: -kv[1])[:per_line],
            })
    return out
