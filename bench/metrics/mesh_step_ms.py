"""Struct stage, mesh path: milliseconds the ``struct.device_step`` span
was open per mesh step (the ``struct.steps`` counter).  A step's span
holds its seeds, its dispatch (``mesh_dispatch_ms``) and the four-chip
copy back (``mesh_fetch_ms``).  A program without the counter reads
nothing."""


def read(ctx):
    busy = ctx.spans.get("struct.device_step", 0.0)
    steps = ctx.counters.get("struct.steps", 0.0)
    if busy <= 0 or steps <= 0:
        return None
    return 1000.0 * busy / steps
