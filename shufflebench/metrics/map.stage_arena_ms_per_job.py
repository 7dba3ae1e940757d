"""Executor milliseconds per job map staging spends putting blocks into
HBM arena slabs: the pad to the slab class and the transfer (the
program's ``map.stage.arena`` span histogram over the window)."""

from shufflebench.program_trace import per_job


def read(run):
    return per_job(run, "map.stage.arena")
