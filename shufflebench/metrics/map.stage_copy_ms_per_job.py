"""Executor milliseconds per job map staging spends copying blocks into
registered host buffers and checksumming them (the program's
``map.stage.{copy,checksum}`` span histograms over the window)."""

from shufflebench.program_trace import per_job


def read(run):
    return per_job(run, "map.stage.copy", "map.stage.checksum")
