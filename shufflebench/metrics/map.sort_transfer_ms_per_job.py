"""Executor milliseconds per job the map sort spends around the device
step: the host pad copy into the size class, the host-to-HBM transfer
and the readback (the program's ``map.sort.{pad,h2d,d2h}`` span
histograms over the window, summed over both executors)."""

from shufflebench.program_trace import per_job


def read(run):
    return per_job(run, "map.sort.pad", "map.sort.h2d", "map.sort.d2h")
