"""Struct stage, mesh path: milliseconds the ``struct.dispatch`` span was
open per mesh step (the ``struct.steps`` counter): the host's call of the
jitted ``shard_map`` step, which returns before the chips finish.  A
program without the counter reads nothing."""


def read(ctx):
    busy = ctx.spans.get("struct.dispatch", 0.0)
    steps = ctx.counters.get("struct.steps", 0.0)
    if busy <= 0 or steps <= 0:
        return None
    return 1000.0 * busy / steps
