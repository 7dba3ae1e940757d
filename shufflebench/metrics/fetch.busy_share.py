"""Share of the window in which some reducer was inside
``fetch_host_blocks``: the union of the benchmark's ``reduce.fetch``
spans (host clock)."""


def read(run):
    return 100.0 * run.span_union_s("reduce.fetch") / run.window_s
