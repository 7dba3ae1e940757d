"""Milliseconds reducers wait for the driver's location reply, per
block fetched (the program's ``fetch.resolve`` span histogram over the
window)."""

from shufflebench.program_trace import span_ms


def read(run):
    ms = span_ms(run, "fetch.resolve")
    blocks = sum(j.blocks for j in run.jobs)
    return None if ms is None or not blocks else ms / blocks
