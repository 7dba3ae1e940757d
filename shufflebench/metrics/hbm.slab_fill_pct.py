"""How full the HBM arena's power-of-two slabs run: bytes asked for
over the size-class bytes of every slab handed out in the window (the
program's ``hbm.slab_payload_bytes`` and ``hbm.slab_bytes``)."""


def read(run):
    slab = run.counter("hbm.slab_bytes")
    if slab <= 0:
        return None
    return 100.0 * run.counter("hbm.slab_payload_bytes") / slab
