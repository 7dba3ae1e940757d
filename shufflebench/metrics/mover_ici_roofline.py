"""The wave movers' share of the interconnect roofline on a mesh: the
payload of the rows that crossed chips (``collective.ici_payload_bytes``)
at one chip's ICI peak, over the device time of the ``pallas_wave_pull``
and ``pallas_pipelined_wave_pull`` kernels averaged over the chips. A
program without the counter reads as None."""

from shufflebench.ici_roofline import ici_seconds, mean_kernel_s
from shufflebench.roofline import share_pct

KERNEL = "wave_pull"


def read(run):
    if run.trace is None or run.peaks is None or run.chips < 2:
        return None
    payload = run.counter("collective.ici_payload_bytes")
    if payload <= 0:
        return None
    return share_pct(ici_seconds(payload, run.peaks),
                     mean_kernel_s(run.trace, KERNEL))
