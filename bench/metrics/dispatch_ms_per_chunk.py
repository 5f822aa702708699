"""Struct stage: milliseconds the ``struct.dispatch`` span was open per
chunk dispatched (the ``struct.chunks`` counter).  A dispatch holds
whatever JAX compiles for the chunk; ``compile_pct`` says how much that
is."""


def read(ctx):
    busy = ctx.spans.get("struct.dispatch", 0.0)
    chunks = ctx.counters.get("struct.chunks", 0.0)
    if busy <= 0 or chunks <= 0:
        return None
    return 1000.0 * busy / chunks
