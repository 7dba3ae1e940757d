"""Share of the window in which some map task was sorting, staging or
publishing: the union of the benchmark's ``map.*`` spans (host clock)."""


def read(run):
    return 100.0 * run.span_union_s("map.") / run.window_s
