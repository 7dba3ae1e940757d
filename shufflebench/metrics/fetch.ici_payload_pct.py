"""How much of what the wave movers carry between chips is payload:
the cross-chip rows' bytes at their lengths over the bytes their DMAs
carry at bucket size, summed over every chip (the program's
``collective.ici_payload_bytes`` and ``collective.ici_moved_bytes``).
A program without the counters reads as None."""


def read(run):
    moved = run.counter("collective.ici_moved_bytes")
    if moved <= 0:
        return None
    return 100.0 * run.counter("collective.ici_payload_bytes") / moved
