"""Struct stage: bytes copied from the device to the host
(``struct.bytes_fetched``) per second the ``struct.fetch`` span was
open.  In the double-buffered pump a fetch also waits for its chunk's
device work to finish."""


def read(ctx):
    busy = ctx.spans.get("struct.fetch", 0.0)
    fetched = ctx.counters.get("struct.bytes_fetched", 0.0)
    if busy <= 0 or fetched <= 0:
        return None
    return fetched / busy / 1e9
