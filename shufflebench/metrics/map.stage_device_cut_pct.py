"""Share of the map blocks put into HBM arena slabs whose slab was cut
on the device from the map sort's own output, not transferred from the
block's host copy (the program's ``map.stage.device_cut_blocks`` and
``map.stage.arena_blocks``). A program without the counters reads as
None."""


def read(run):
    staged = run.counter("map.stage.arena_blocks")
    if staged <= 0:
        return None
    return 100.0 * run.counter("map.stage.device_cut_blocks") / staged
