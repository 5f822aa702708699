"""Struct stage, mesh path: milliseconds the ``struct.fetch`` span was
open per mesh step (the ``struct.steps`` counter): the copy of the step's
ids from the four chips to the host, which also waits for the step's
device work to finish.  A program without the counter reads nothing."""


def read(ctx):
    busy = ctx.spans.get("struct.fetch", 0.0)
    steps = ctx.counters.get("struct.steps", 0.0)
    if busy <= 0 or steps <= 0:
        return None
    return 1000.0 * busy / steps
