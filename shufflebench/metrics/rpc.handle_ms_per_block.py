"""Milliseconds the driver and executors spent handling control-plane
messages (the ``rpc.handle_ms`` histogram's sum over the window), per
block published."""


def read(run):
    published = len(run.jobs) * int(run.cell.config["maps"]) * int(
        run.cell.traffic["reducers"])
    return run.histogram_sum("rpc.handle_ms") / published
