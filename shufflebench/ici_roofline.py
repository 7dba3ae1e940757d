"""The cross-chip movers' roofline, in the style of ``roofline.py``:
the bytes the work must carry between chips, not what the movers'
layout carries, so zero slots and padding show as a lower share."""

from __future__ import annotations


def ici_seconds(payload_bytes: float, peaks: dict) -> float:
    """Least time to carry ``payload_bytes`` of rows between chips at
    one chip's interconnect peak."""
    return payload_bytes / peaks["ici_bytes_per_s"]


def mean_kernel_s(trace, contains: str) -> float:
    """Device seconds of the operations named with ``contains``,
    averaged over the trace's chips."""
    if not trace.devices:
        return 0.0
    return trace.kernel_s(contains, ops=True) / len(trace.devices)
