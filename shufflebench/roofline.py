"""The bytes each kernel must move for the work it is given, and its
share of the roofline. The counts follow the work (keys sorted, bytes
fetched), not the implementation: padding, size classes and extra
passes are not counted, so they show as a lower share.
"""

from __future__ import annotations

KEY_BYTES = 4


def sort_bytes(keys: int) -> float:
    """A sort reads and writes each key once at the least."""
    return 2.0 * KEY_BYTES * keys


def merge_bytes(keys: int) -> float:
    """A merge of received blocks reads and writes each key once."""
    return 2.0 * KEY_BYTES * keys


def mover_seconds(local_bytes: float, peaks: dict) -> float:
    """Least time to land blocks fetched on the reducer's own chip:
    each byte read and written once in HBM."""
    return 2.0 * local_bytes / peaks["hbm_bytes_per_s"]


def share_pct(least_s: float, measured_s: float):
    """Least time over measured time, in percent; None where nothing
    was measured."""
    if measured_s <= 0:
        return None
    return 100.0 * least_s / measured_s
