"""Share of the map shards sent to the sorter's device as they are,
without a host pad copy, because they fill their size class (the
program's ``map.sort.unpadded_shards`` and ``map.sort.shards``). A
program without the counters reads as None."""


def read(run):
    shards = run.counter("map.sort.shards")
    if shards <= 0:
        return None
    return 100.0 * run.counter("map.sort.unpadded_shards") / shards
