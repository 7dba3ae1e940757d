"""The wave movers' share of their roofline on one chip: the bytes the
device plane landed (``device_fetch.plane.bytes``), each read and
written once in HBM, over the device time of the ``pallas_wave_pull``
and ``pallas_pipelined_wave_pull`` kernels."""

from shufflebench.roofline import mover_seconds, share_pct

KERNEL = "wave_pull"


def read(run):
    if run.trace is None or run.peaks is None or run.chips != 1:
        return None
    landed = run.counter("device_fetch.plane.bytes")
    if landed <= 0:
        return None
    return share_pct(mover_seconds(landed, run.peaks),
                     run.trace.kernel_s(KERNEL, ops=True))
