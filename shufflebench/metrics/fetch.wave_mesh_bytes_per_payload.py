"""HBM the wave epochs newly allocate across the mesh, send and receive
shards on every chip (the program's ``collective.wave_mesh_bytes``),
per byte the device plane landed (``device_fetch.plane.bytes``). A
program without the counter reads as None."""


def read(run):
    mesh = run.counter("collective.wave_mesh_bytes")
    landed = run.counter("device_fetch.plane.bytes")
    if mesh <= 0 or landed <= 0:
        return None
    return mesh / landed
