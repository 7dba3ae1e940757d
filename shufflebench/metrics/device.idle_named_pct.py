"""Share of the window's device-idle time that a program span names:
idle gaps whose midpoint lies inside at least one of the program's own
spans on the trace's host plane, averaged over the chips."""

from shufflebench.program_trace import idle_named_pct


def read(run):
    return idle_named_pct(run)
