"""Executor milliseconds per job the fetch's waves spend on the host:
assembling the send stack from source slabs read back, putting it on
the device, and adopting the landed rows into slabs (the program's
``fetch.wave.{assemble,h2d,adopt}`` span histograms over the window)."""

from shufflebench.program_trace import per_job


def read(run):
    return per_job(run, "fetch.wave.assemble", "fetch.wave.h2d",
                   "fetch.wave.adopt")
